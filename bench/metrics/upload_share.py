"""upload_share: the weight hand-offs inside decode chunks over the wall
time of those chunks, as a percentage.  Offload cells only."""
from bench.metrics.upload_gbps import handoffs


def read(w):
    chunks = [(c.start, c.end) for c in w.chunks()]
    wall = sum(b - a for a, b in chunks)
    if not chunks or wall <= 0:
        return None
    inside = sum(s.end - s.start for s in handoffs(w)
                 if any(a <= s.start and s.end <= b for a, b in chunks))
    return 100.0 * inside / wall
