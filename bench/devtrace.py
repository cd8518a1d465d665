"""Reduction of a profiler trace to device busy time, per-program device time
and the longest idle gaps.

``read_events`` flattens the ``.xplane.pb`` that ``jax.profiler`` writes into
``Event`` tuples; everything else works on such lists, so the reduction is
tested on small synthetic traces.  Device planes are those named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per operation run
and their ``XLA Modules`` line one event per program run (named after the
jitted function, e.g. ``jit__decode_chunk_impl(...)``).  Host planes hold the
host threads' events, which label the idle gaps.  An operation's event also
carries, as ``info``, the trace's ``long_name`` and ``hlo_category`` stats of
it (its HLO instruction and the profiler's class of it), read once per
operation name and program.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: int
    dur_ns: int
    info: str = ""   # a device operation's INFO_STATS, space-separated

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
INFO_STATS = ("long_name", "hlo_category")


def _info(ev) -> str:
    return " ".join(str(v) for k, v in ev.stats if k in INFO_STATS)


def read_events(trace_dir: str) -> List[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return []
    data = jax.profiler.ProfileData.from_file(files[-1])
    out: List[Event] = []
    for plane in data.planes:
        lines = list(plane.lines)
        device = plane.name.startswith("/device:TPU")
        mods = sorted((int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                       ev.name) for line in lines
                      if device and line.name == MODULES_LINE
                      for ev in line.events)
        starts = [m[0] for m in mods]
        info: Dict[Tuple[str, str], str] = {}
        for line in lines:
            ops = device and line.name == OPS_LINE
            for ev in line.events:
                t, text = int(ev.start_ns), ""
                if ops:   # the same name in another program is another op
                    i = bisect.bisect_right(starts, t) - 1
                    key = (mods[i][2] if i >= 0 and t < mods[i][1] else "",
                           ev.name)
                    text = info.get(key)
                    if text is None:
                        text = info[key] = _info(ev)
                out.append(Event(plane.name, line.name, ev.name, t,
                                 int(ev.duration_ns), text))
    return out


def is_device(e: Event) -> bool:
    return e.plane.startswith("/device:TPU")


def device_planes(events: Iterable[Event]) -> List[str]:
    return sorted({e.plane for e in events if is_device(e)})


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted (start, end) intervals."""
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def busy_by_plane(events: Iterable[Event]) -> Dict[str, int]:
    """Nanoseconds in which an operation ran, for each device plane that
    ran one."""
    by: Dict[str, List[Tuple[int, int]]] = {}
    for e in events:
        if is_device(e) and e.line == OPS_LINE:
            by.setdefault(e.plane, []).append((e.start_ns, e.end_ns))
    return {p: sum(b - a for a, b in union(iv)) for p, iv in by.items()}


def busy_seconds(events: Iterable[Event]) -> float:
    """Seconds in which an operation ran, averaged over the device planes."""
    busy = busy_by_plane(events)
    if not busy:
        return 0.0
    return sum(busy.values()) / len(busy) / 1e9


def program_of(name: str) -> str:
    """The jitted function a module event ran: ``jit__layer_impl(12)`` ->
    ``_layer_impl``."""
    base = name.split("(")[0]
    return base[4:] if base.startswith("jit_") else base


def module_seconds(events: Iterable[Event], fn: str) -> Tuple[float, int]:
    """(device seconds, runs) of the programs of jitted function ``fn``,
    summed over runs and averaged over the device planes."""
    mods = [e for e in events if is_device(e) and e.line == MODULES_LINE
            and program_of(e.name) == fn]
    planes = {e.plane for e in mods} or {""}
    return (sum(e.dur_ns for e in mods) / len(planes) / 1e9,
            len(mods) // len(planes))


def top_ops(events: Iterable[Event], n: int = 10) -> List[List]:
    """[[op name, seconds]] of the n operations that took the most device
    time (summed over runs, averaged over device planes)."""
    ops = [e for e in events if is_device(e) and e.line == OPS_LINE]
    planes = {e.plane for e in ops} or {""}
    by: Dict[str, int] = {}
    for e in ops:
        by[e.name] = by.get(e.name, 0) + e.dur_ns
    top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / len(planes) / 1e9] for k, v in top]


def idle_gaps(events: List[Event], n: int = 10,
              plane: Optional[str] = None) -> List[List]:
    """[[label, seconds]] of the n longest gaps between device operations on
    one device plane, each labelled by what the host was doing in it: the
    shortest host event that covers at least half of the gap (the innermost
    frame), else the host event that overlaps it most."""
    planes = device_planes(events)
    if not planes:
        return []
    plane = plane or planes[0]
    busy = union((e.start_ns, e.end_ns) for e in events
                 if e.plane == plane and e.line == OPS_LINE)
    gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    host = sorted((e for e in events if not is_device(e)),
                  key=lambda e: e.start_ns)
    starts = [e.start_ns for e in host]
    longest = max((e.dur_ns for e in host), default=0)
    out = []
    for a, b in gaps:
        lo = bisect.bisect_left(starts, a - longest)
        hi = bisect.bisect_right(starts, b)
        best, label, inner = 0, "no host event", None
        for e in host[lo:hi]:
            ov = min(b, e.end_ns) - max(a, e.start_ns)
            if ov > best:
                best, label = ov, e.name
            if 2 * ov >= b - a and (inner is None
                                    or e.dur_ns < inner.dur_ns):
                inner = e
        out.append([inner.name if inner is not None else label,
                    (b - a) / 1e9])
    return out
