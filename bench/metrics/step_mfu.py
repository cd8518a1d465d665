"""step_mfu: model FLOPs of the window's work over window wall time x the
chip's bf16 peak, as a percentage.  The work is the prompts of the requests
admitted in the window and every token decoded in it, at its context
(``bench/counts.py``); K/V regenerated from ACT checkpoints is recompute
and does not count."""
from bench import counts


def read(w):
    if not w.peaks:
        return None
    c = w.config
    flops = sum(counts.prefill_flops(c, p) for p in w.admitted_prompts())
    for kv, act, k in w.decode_contexts():
        flops += sum(counts.decode_flops(c, kv + act + j) for j in range(k))
    return 100.0 * flops / (w.seconds * w.peaks["bf16_flops"])
