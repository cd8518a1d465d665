"""Measured per-task lane timelines for the host-offload runtime.

The analytic two-lane simulator (`core/pipeline.py`) predicts what a decode
step costs on the target hardware; the offload executor records what the
step actually cost on *this* machine, task by task, in the same three-lane
vocabulary ("pcie" loads, "pcie_up" stores, "gpu" compute) and emits
``TimelineResult`` objects with the same schema as ``simulate_steps`` — so
benchmarks can plot measured-vs-analytic side by side and quantify the
§4.3 cost-model's predictor error.

Spans are recorded from two threads (the copy stream and the compute
thread); a lock serialises appends.  A span is attributed to the step that
is current when it *completes* — prefetches issued across a step boundary
land in the step they finish in, a bounded attribution skew that washes out
over a generation.

``task`` is the one span primitive: it times a block into a lane span and
opens a ``jax.profiler.TraceAnnotation`` ``offload.<name>`` over the same
interval, so the program's spans also land in a profiler trace, on the
device trace's clock.
"""
from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import List, Optional

import jax

from repro.core.pipeline import TimelineResult

#: traffic categories, matching ``simulate_steps``'s traffic dict keys
TRAFFIC_TAGS = ("weights", "kv_load", "act_load", "store")

#: lane names, matching ``core.pipeline.run_timeline``.  "cpu" is the
#: host-compute attention lane (DESIGN.md §15): spans recorded from the
#: HostAttnExecutor worker thread, overlapping the gpu lane in wall time.
LANES = ("pcie", "pcie_up", "gpu", "cpu")

#: the compute thread's own host work between device calls (``w_wait`` on a
#: weight staging, ``pre``/``post`` dispatch and readback, cache
#: ``unstack``/``restack``).  Its spans reach the tracer and the profiler
#: so a decode step is tiled end to end, but never a step's lane totals:
#: the simulator has no such lane, and the controller and drift monitor
#: read ``TimelineResult`` as before.
HOST = "host"


@dataclass
class Span:
    lane: str                 # one of LANES
    tag: str                  # "w" | "kv" | "act" | "st" | "gen" | "fwd" | "cpu"
    start: float              # perf_counter seconds
    end: float
    nbytes: int = 0
    # mesh-position lane index (DESIGN.md §11): under tensor parallelism
    # every shard owns its own PCIe lane, so per-shard spans of one step
    # aggregate by MAX (the lanes run in parallel), not by sum.  0 = the
    # single-shard default, which reproduces the old sum exactly.
    shard: int = 0

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class _Step:
    tag: str
    start: float
    end: float = 0.0
    spans: List[Span] = field(default_factory=list)
    events: dict = field(default_factory=dict)    # robustness events by name


#: span tag -> traffic category (compute tags carry no bytes)
_TAG_TO_TRAFFIC = {"w": "weights", "kv": "kv_load", "act": "act_load",
                   "st": "store"}


class MeasuredTimeline:
    """Collects wall-clock lane spans grouped into steps.

    Usage::

        tl = MeasuredTimeline()
        tl.begin_step("decode")
        with tl.task("gpu", "fwd"):
            ... compute ...
        tl.end_step()
        results = tl.results()          # List[TimelineResult], one per step
    """

    def __init__(self, tracer=None):
        self._lock = threading.Lock()
        self._steps: List[_Step] = []
        self._cur: Optional[_Step] = None
        # optional obs bridge (repro.obs.trace.Tracer): every recorded span
        # / robustness event is mirrored onto the tracer's lane tracks, so
        # the offload runtime needs no second instrumentation layer.  None
        # (the default) keeps recording exactly as before.
        self.tracer = tracer

    # ------------------------------------------------------------------ steps
    def begin_step(self, tag: str = "decode",
                   now: Optional[float] = None) -> None:
        """``now`` overrides the wall clock (golden-trace tests drive the
        timeline with synthetic timestamps; production callers omit it)."""
        with self._lock:
            if self._cur is not None:
                self._cur.end = time.perf_counter() if now is None else now
                self._steps.append(self._cur)
            self._cur = _Step(
                tag=tag, start=time.perf_counter() if now is None else now)

    def end_step(self, now: Optional[float] = None) -> None:
        with self._lock:
            if self._cur is not None:
                self._cur.end = time.perf_counter() if now is None else now
                self._steps.append(self._cur)
                self._cur = None

    # ------------------------------------------------------------------ spans
    def record(self, lane: str, tag: str, start: float, end: float,
               nbytes: int = 0, shard: int = 0) -> None:
        assert lane in LANES or lane == HOST, lane
        if lane != HOST:
            with self._lock:
                if self._cur is None:       # span outside any step: open one
                    self._cur = _Step(tag="untagged", start=start)
                self._cur.spans.append(
                    Span(lane, tag, start, end, nbytes, shard))
        if self.tracer is not None:
            self.tracer.lane_span(lane, tag, start, end, nbytes=nbytes,
                                  shard=shard)

    def record_event(self, name: str, n: int = 1) -> None:
        """Count a robustness event (watchdog timeout, copy retry, lane
        fallback, arena denial, ...) against the current step.  Events ride
        the ``TimelineResult.events`` field so downstream consumers — the
        adaptive controller above all — can tell a degraded step from a
        clean one instead of fitting the cost model to it."""
        with self._lock:
            if self._cur is None:
                self._cur = _Step(tag="untagged", start=time.perf_counter())
            self._cur.events[name] = self._cur.events.get(name, 0) + n
        if self.tracer is not None:
            self.tracer.lane_event(name)

    @contextmanager
    def task(self, lane: str, tag: str, nbytes: int = 0, shard: int = 0,
             name: Optional[str] = None):
        """Record the block as one ``lane``/``tag`` span and annotate it in
        the profiler's trace as ``offload.<name or tag>`` (about a
        microsecond when no profiler runs, so it is always on)."""
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(f"offload.{name or tag}"):
                yield
        finally:
            self.record(lane, tag, t0, time.perf_counter(), nbytes, shard)

    # ---------------------------------------------------------------- results
    def results(self, tag: Optional[str] = None) -> List[TimelineResult]:
        """Per-step measured ``TimelineResult``s (same schema as
        ``simulate_steps``).  ``tag`` filters steps (e.g. only "decode").

        Read-only snapshot of COMPLETED steps: an in-flight step is neither
        closed nor included, so a monitoring read mid-run cannot corrupt
        step attribution.  Close steps with ``end_step`` (the executor does
        after every step) or collect-and-reset with ``drain``."""
        out = []
        with self._lock:
            steps = [s for s in self._steps if tag is None or s.tag == tag]
        for s in steps:
            # per-(lane, shard) and per-(tag, shard) sums first; the step's
            # lane/tag seconds are then the MAX across shards — per-shard
            # PCIe lanes run in parallel, so the slowest lane is the lane
            # time the controller should regress against.  Single-shard
            # spans (shard 0 everywhere) reduce to the old plain sums, so
            # the aggregation is one code path for every mesh.
            busy_s: dict = {}
            tag_s: dict = {}
            traffic = {k: 0.0 for k in TRAFFIC_TAGS}
            finish = []
            end = s.end
            for sp in s.spans:
                busy_s[(sp.lane, sp.shard)] = \
                    busy_s.get((sp.lane, sp.shard), 0.0) + sp.dur
                tag_s[(sp.tag, sp.shard)] = \
                    tag_s.get((sp.tag, sp.shard), 0.0) + sp.dur
                cat = _TAG_TO_TRAFFIC.get(sp.tag)
                if cat is not None:
                    traffic[cat] += sp.nbytes       # bytes ARE additive
                finish.append(sp.end - s.start)
                end = max(end, sp.end)
            busy = {l: 0.0 for l in LANES}
            for (l, _), v in busy_s.items():
                busy[l] = max(busy[l], v)
            tag_busy: dict = {}
            for (t, _), v in tag_s.items():
                tag_busy[t] = max(tag_busy.get(t, 0.0), v)
            out.append(TimelineResult(
                total=end - s.start, pcie_busy=busy["pcie"],
                gpu_busy=busy["gpu"], cpu_busy=busy["cpu"], traffic=traffic,
                finish=finish, tag_busy=tag_busy, events=dict(s.events)))
        return out

    def step_tags(self) -> List[str]:
        """Tags of completed steps (snapshot, like ``results``)."""
        with self._lock:
            return [s.tag for s in self._steps]

    def drain(self, tag: Optional[str] = None) -> List[TimelineResult]:
        """Close the in-flight step, return ``results`` and reset — the
        mutating collector a caller uses at group boundaries."""
        self.end_step()
        res = self.results(tag)
        with self._lock:
            self._steps.clear()
            self._cur = None
        return res
