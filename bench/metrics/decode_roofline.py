"""decode_roofline: the least time the window's decode steps could take on
the cell's chips over the device time of the programs that ran them, as a
percentage.  Those programs are, by name in the profiler trace, the
resident server's decode chunk (``_decode_chunk_impl``) or the offload
executor's per-step and per-layer stages (``_pre_impl``, ``_layer_impl``,
``_post_impl``); their device time is summed over their runs and averaged
over the device planes they ran on (``devtrace.module_seconds``).  Each
step's least time is the larger of its FLOPs over the bf16 peak and its
bytes over the HBM bandwidth, each one chip's peak times the number of
chips: its FLOPs are the model FLOPs of its tokens plus the K/V regenerated
from ACT checkpoints, its bytes every layer's weights, the LM head and each
slot's KV and ACT rows (the configuration's counts, ``Window.counts``).
Which bound applies is printed to stderr."""
import sys

from bench import devtrace

PROGRAMS = {"resident": ("_decode_chunk_impl",),
            "offload": ("_pre_impl", "_layer_impl", "_post_impl")}


def read(w):
    if not w.trace or not w.peaks:
        return None
    secs = runs = 0
    for name in PROGRAMS[w.config["regime"]]:
        s, n = devtrace.module_seconds(w.trace, name)
        secs, runs = secs + s, runs + n
    if not runs:
        return None
    c, counts = w.config, w.counts
    peak_flops = w.chips * w.peaks["bf16_flops"]
    peak_bytes = w.chips * w.peaks["hbm_bytes_per_s"]
    ideal = t_flops = t_bytes = 0.0
    for ch in w.chunks():
        for j in range(ch.steps):
            live = [(kv + j, act) for _, k, kv, act in ch.slots if k > j]
            if not live:
                continue
            f = sum(counts.decode_flops(c, kv + act)
                    + counts.regen_flops(c, act) for kv, act in live)
            b = counts.decode_step_bytes(c, sum(x[0] for x in live),
                                         sum(x[1] for x in live), len(live))
            t_flops += f / peak_flops
            t_bytes += b / peak_bytes
            ideal += max(f / peak_flops, b / peak_bytes)
    print(f"[bench] decode_roofline: bound by "
          f"{'bytes' if t_bytes >= t_flops else 'FLOPs'} "
          f"(FLOPs {t_flops:.6f} s, bytes {t_bytes:.6f} s, device "
          f"{secs:.6f} s over {runs} program runs)", file=sys.stderr)
    return 100.0 * ideal / secs
