"""step_mfu: model FLOPs of the window's work over window wall time x the
bf16 peak of the cell's chips (one chip's peak times their number), as a
percentage.  The work is the prompts of the requests admitted in the window
and every token decoded in it, at its context (the configuration's counts,
``Window.counts``); K/V regenerated from ACT checkpoints is recompute and
does not count."""


def read(w):
    if not w.peaks:
        return None
    c, counts = w.config, w.counts
    flops = sum(counts.prefill_flops(c, p) for p in w.admitted_prompts())
    for kv, act, k in w.decode_contexts():
        flops += sum(counts.decode_flops(c, kv + act + j) for j in range(k))
    return 100.0 * flops / (w.seconds * w.chips * w.peaks["bf16_flops"])
