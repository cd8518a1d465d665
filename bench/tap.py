"""The harness's taps on the server: a tracer and a metrics registry.

The server's public hooks are the only taps (``tracer=``, ``metrics=`` of
``ContinuousBatchingServer``; the offload executor mirrors its lane spans onto
the same tracer).  ``Tap`` records, on the host clock:

- each decode chunk: its start, its ``steps``, and for each slot that takes
  part the request id, its step count and its KV/ACT token counts before the
  chunk (``server.slots[i].kv_tokens``/``.act_tokens``);
- each admission span (``admit``) with the requests it admits (their
  ``prefill`` spans), and each offload lane span;
- each delivery: the moment the registry counter ``serve_generated_tokens``
  is incremented, which is the first hook after the chunk's host readback.
  The chunk's tokens are stamped with that moment;
- each completion, with the served tokens read from the slot that holds it
  (``server.slots[i].generated``) when ``request_end(rid, "complete")``
  fires.

``on_delivery`` is called after each delivery and may raise ``Stop`` to
end ``server.run`` where it is.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from repro.obs import MetricsRegistry


class Stop(Exception):
    """Raised from a hook to end ``server.run``."""


@dataclass
class Chunk:
    start: float
    steps: int
    slots: List[Tuple[int, int, int, int]] = field(default_factory=list)
    # (rid, steps this chunk, kv tokens before, act tokens before)
    end: float = 0.0              # the delivery stamp
    tokens: int = 0


@dataclass
class Span:
    name: str
    start: float
    end: float
    args: Dict = field(default_factory=dict)


class Tap:
    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self.clock = clock
        self.server = None
        self.chunks: List[Chunk] = []
        self.admits: List[Span] = []
        self.lanes: List[Span] = []
        self.completed: Dict[int, Tuple[float, List[int]]] = {}
        self.failed: List[int] = []
        self.preempts = 0
        self.on_delivery: Optional[Callable[["Tap"], None]] = None
        self._pending_completions: List[int] = []
        self._admit: Optional[Span] = None
        self.completion_order: List[Tuple[int, float]] = []  # (rid, t)
        self.registry = _Registry(self)

    def reset(self) -> None:
        """Forget what was recorded (between the warm-up and the window)."""
        self.chunks.clear()
        self.admits.clear()
        self.lanes.clear()
        self.completed.clear()
        self.failed.clear()
        self.completion_order.clear()
        self._pending_completions.clear()
        self.preempts = 0

    # ------------------------------------------------------- tracer protocol
    def request_begin(self, rid: int, **args) -> None:
        pass

    def request_event(self, rid: int, name: str, **args) -> None:
        if name == "preempt":
            self.preempts += 1

    def request_span(self, rid: int, name: str, **args):
        if name in ("prefill", "resume_prefill") and self._admit is not None:
            self._admit.args["rids"].append(int(rid))
        if name == "decode" and self.chunks:
            s = self.server.slots
            st = next(x for x in s if x.rid == rid)
            self.chunks[-1].slots.append(
                (int(rid), int(args["steps"]), int(st.kv_tokens),
                 int(st.act_tokens)))
        return contextlib.nullcontext()

    def request_end(self, rid: int, status: str = "complete", **args) -> None:
        if status != "complete":
            self.failed.append(int(rid))
            return
        st = next(x for x in self.server.slots if x.rid == rid)
        self.completed[int(rid)] = (0.0, list(st.generated))
        self._pending_completions.append(int(rid))

    @contextlib.contextmanager
    def server_span(self, name: str, **args):
        t0 = self.clock()
        if name == "chunk":
            self.chunks.append(Chunk(start=t0, steps=int(args["steps"])))
        if name == "admit":
            self._admit = Span(name, t0, 0.0, dict(args, rids=[]))
        try:
            yield
        finally:
            if name == "admit":
                self._admit.end = self.clock()
                self.admits.append(self._admit)
                self._admit = None

    def lane_span(self, lane: str, tag: str, start: float, end: float,
                  nbytes: int = 0, shard: int = 0) -> None:
        self.lanes.append(Span(f"{lane}/{tag}", start, end,
                               {"nbytes": nbytes, "shard": shard}))

    def lane_event(self, name: str, shard: int = 0, lane: str = "pcie",
                   **args) -> None:
        pass

    def served(self) -> Dict[int, List[int]]:
        """Every request's delivered tokens: the finished ones', and the
        tokens delivered so far to those still in a slot."""
        out = {r: list(toks) for r, (_, toks) in self.completed.items()}
        for st in self.server.slots:
            if st.active and st.generated:
                out[int(st.rid)] = list(st.generated)
        return out

    def act_held(self, tag: str) -> Dict[Tuple[str, int], int]:
        """(tag, request id) -> the most ACT tokens the request held at the
        start of a chunk."""
        out: Dict[Tuple[str, int], int] = {}
        for c in self.chunks:
            for rid, _, _, act in c.slots:
                out[(tag, rid)] = max(out.get((tag, rid), 0), act)
        return out

    # ------------------------------------------------------------- delivery
    def _delivered(self, n: int) -> None:
        t = self.clock()
        c = self.chunks[-1]
        c.end, c.tokens = t, int(n)
        for rid in self._pending_completions:
            self.completed[rid] = (t, self.completed[rid][1])
            self.completion_order.append((rid, t))
        self._pending_completions.clear()
        if self.on_delivery is not None:
            self.on_delivery(self)


class _Counter:
    def __init__(self, tap: Tap, real):
        self._tap, self._real = tap, real

    def inc(self, n: float = 1.0) -> None:
        self._real.inc(n)
        self._tap._delivered(n)

    def __getattr__(self, k):
        return getattr(self._real, k)


class _Registry(MetricsRegistry):
    """The program's registry; ``serve_generated_tokens`` reports to the
    tap when it is incremented."""

    def __init__(self, tap: Tap):
        super().__init__()
        self._tap = tap

    def counter(self, name: str, **labels):
        c = super().counter(name, **labels)
        return _Counter(self._tap, c) if name == "serve_generated_tokens" \
            else c
