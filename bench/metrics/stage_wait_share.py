"""stage_wait_share: the compute thread's waits for a layer's weight
staging copy inside decode chunks, over the wall time of those chunks, as a
percentage.  A wait is a ``host``/``w_wait`` lane span (the streamer's
``fut.result()`` on the copy stream, or a synchronous emergency stage).
The base is ``upload_share``'s, so the two add.  Offload cells only; None
where the program records no such span.

Also prints to stderr how much of each chunk's wall the compute thread's
spans tile: the ``host`` lane, the weight hand-offs and the layer forwards
(``gpu``/``fwd``), least and mean over the window's chunks."""
import sys

from bench.devtrace import union
from bench.metrics.upload_gbps import handoffs


def _covered(spans, a, b):
    """Seconds of [a, b] under the union of ``spans``."""
    return sum(hi - lo for lo, hi in union(
        (max(s.start, a), min(s.end, b)) for s in spans
        if s.end > a and s.start < b))


def read(w):
    chunks = [(c.start, c.end) for c in w.chunks()]
    wall = sum(b - a for a, b in chunks)
    waits = [s for s in w.tap.lanes if s.name == "host/w_wait"
             and any(a <= s.start and s.end <= b for a, b in chunks)]
    if not waits or wall <= 0:
        return None
    compute = handoffs(w) + [s for s in w.tap.lanes
                             if s.name.startswith("host/")
                             or s.name == "gpu/fwd"]
    tiled = [_covered(compute, a, b) / (b - a) for a, b in chunks]
    print(f"[bench] stage_wait_share: compute-thread spans tile "
          f"{100 * min(tiled):.3f} % of a decode chunk at least, "
          f"{100 * sum(tiled) / len(tiled):.3f} % on mean, over "
          f"{len(tiled)} chunks", file=sys.stderr)
    return 100.0 * sum(s.end - s.start for s in waits) / wall
