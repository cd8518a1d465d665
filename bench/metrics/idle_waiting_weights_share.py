"""idle_waiting_weights_share: the time the device is idle (outside the
union of its ``XLA Ops`` intervals) while the host has an
``offload.w_wait`` or ``offload.w_handoff`` annotation open (waiting for a
layer's staging copy, or handing it to the device), over the traced
window, as a percentage averaged over the chips used.  The base is
``device_idle_share``'s.  Both sets of intervals come from the one profiler
trace, on its clock.  None where the program writes no such annotation.

Also prints to stderr the device's idle seconds by the innermost
``offload.*``/``serve.*`` annotation open over them (the one opened last,
on any host thread), and the idle seconds under none."""
import sys

from bench import devtrace

WAITS = ("offload.w_wait", "offload.w_handoff")
PREFIXES = ("offload.", "serve.")


def complement(busy, lo, hi):
    """The gaps of the sorted disjoint ``busy`` intervals inside [lo, hi]."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def overlap(xs, ys):
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = total = 0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        total += max(hi - lo, 0)
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def attribute(idle, spans):
    """{name or None: length of ``idle`` under it}, each instant charged to
    the latest-opened of the (start, end, name) ``spans`` open over it."""
    spans = [s for s in spans if s[1] > s[0]]
    marks = [m for a, b in idle for m in ((a, 1, -1), (b, -1, -1))]
    marks += [m for k, (a, b, _) in enumerate(spans)
              for m in ((a, 1, k), (b, -1, k))]
    marks.sort(key=lambda m: (m[0], m[1]))
    out, idle_open, open_, prev = {}, 0, set(), None
    for t, d, k in marks:
        if idle_open and t > prev:
            inner = max(open_, key=lambda j: spans[j][0], default=None)
            name = None if inner is None else spans[inner][2]
            out[name] = out.get(name, 0) + t - prev
        if k < 0:
            idle_open += d
        elif d > 0:
            open_.add(k)
        else:
            open_.discard(k)
        prev = t
    return out


def read(w):
    if not w.trace:
        return None
    host = [(e.start_ns, e.end_ns, e.name) for e in w.trace
            if not devtrace.is_device(e) and e.name.startswith(PREFIXES)]
    waits = devtrace.union((a, b) for a, b, n in host if n in WAITS)
    planes = devtrace.device_planes(w.trace)
    if not waits or not planes:
        return None
    lo = min(e.start_ns for e in w.trace)
    hi = max(e.end_ns for e in w.trace)
    waiting, by = 0, {}
    for p in planes:
        busy = devtrace.union((e.start_ns, e.end_ns) for e in w.trace
                              if e.plane == p and e.line == devtrace.OPS_LINE)
        idle = complement(busy, lo, hi)
        waiting += overlap(idle, waits)
        for name, ns in attribute(idle, host).items():
            by[name] = by.get(name, 0) + ns
    n = len(planes)
    total = sum(by.values())
    named = sorted(((v, k) for k, v in by.items() if k is not None),
                   reverse=True)
    print("[bench] idle_waiting_weights_share: device idle s by innermost "
          "annotation: " + ", ".join(f"{k} {v / n / 1e9:.9f}"
                                     for v, k in named)
          + f"; under none {by.get(None, 0) / n / 1e9:.9f} of "
          f"{total / n / 1e9:.9f} s idle "
          f"({100 * (total - by.get(None, 0)) / max(total, 1):.3f} % "
          f"under an annotation)", file=sys.stderr)
    return 100.0 * waiting / n / 1e9 / w.trace_window_s
