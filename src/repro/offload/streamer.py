"""Double-buffered host→device weight streamer (paper Fig. 8's PCIe lane).

A single background thread is the *copy stream*: uploads are submitted in
consumption order, so transfers serialise exactly like DMA on one PCIe
direction while the main thread keeps the compute lane busy — the overlap
HybridServe's pipeline model assumes, produced for real.

Two-phase upload (CPU-backend deviation, documented in DESIGN.md §8): on
this runtime ``jax.device_put`` is a synchronous, GIL-holding memcpy, so a
worker thread calling it would *serialise against* compute instead of
overlapping (measured: negative saving).  What does overlap is a raw numpy
copy (GIL released).  The streamer therefore keeps ``prefetch_depth + 1``
preallocated staging slots — the double buffers — and

  1. the copy stream STAGES layer ``l``'s host shard into its slot
     (``np.copyto``, the DMA analogue, genuinely concurrent with compute);
  2. ``acquire`` performs the final ``device_put`` hand-off on the caller
     thread (the serial tail this backend cannot hide).

On a real accelerator ``device_put`` from pinned memory IS the DMA and
phase 2 collapses into phase 1; the protocol, slot discipline and
donation rules are unchanged.

Dispatch-ahead protocol (prefetch depth ``d``):

  * ``begin(schedule)`` arms a pass over a sequence of layer ids (a decode
    loop cycles ``[0..L-1]`` per step — prefetch crosses step boundaries
    so layer 0 of step ``s+1`` stages while layer ``L-1`` of step ``s``
    computes).
  * ``acquire(i)`` blocks until staging ``i`` has landed, tops the
    in-flight window back up to ``d`` stagings beyond ``i``, then hands
    slot ``i`` off to the device — so the copy stream stages the next
    layers WHILE the caller thread runs ``i``'s hand-off, not after it.
    With ``d=0`` everything runs inline on the caller thread — no
    overlap, the stream-only baseline.
  * ``release(i)`` donates the stale buffer: every device leaf of upload
    ``i`` is deleted, bounding device residency to ``d + 1`` layer shards
    (classic double buffering at ``d=1``).

Slot safety: the top-up in ``acquire(i)`` writes at most slot
``(i+d) % (d+1) = (i-1) % (d+1)``, last held by position ``i-1``, which
the caller handed off and released before asking for ``i`` (the
acquire → forward → release discipline); it is never slot ``i % (d+1)``,
the one being handed off.  A backend whose ``device_put`` aliases host
memory therefore never sees a live device tree's slot overwritten.

Engagement: every acquire counts, per shard, whether its staging had
already ``landed`` when the caller asked for it or the caller ``waited``
(``streamer_acquires{key,shard}``; an inline depth-0 stage and a degraded
lane's emergency stage count as waited).

``submit`` exposes the same serialized stream for other host→device work.
"""
from __future__ import annotations

import time
from concurrent.futures import Future, ThreadPoolExecutor
from concurrent.futures import TimeoutError as FuturesTimeout
from typing import Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.obs.metrics import CounterDictView, MetricsRegistry
from repro.offload.faults import FaultPlan, TransientCopyError
from repro.offload.host_pool import HostWeightPool
from repro.offload.timeline import HOST, MeasuredTimeline

#: the streamer's robustness-counter ladder (DESIGN.md §12)
FAULT_COUNTER_KEYS = ("watchdog_timeouts", "copy_retries", "copy_failures",
                      "sync_fallbacks", "stalls_injected")
#: acquires whose staging had landed before the caller asked, and the rest
ACQUIRE_COUNTER_KEYS = ("landed", "waited")


def donate_buffers(tree) -> None:
    """Free a device pytree's buffers eagerly (the stale double buffer)."""
    for leaf in jax.tree.leaves(tree):
        delete = getattr(leaf, "delete", None)
        if delete is not None:
            try:
                delete()
            except RuntimeError:          # already donated to a jit call
                pass


class WeightStreamer:
    """Streams per-layer weight shards from a ``HostWeightPool`` (or one
    mesh position's ``LaneView`` of it).

    ``device``: target device for the hand-off ``device_put`` (None = the
    default device — today's single-lane behaviour).  ``shard``: mesh lane
    index stamped on every recorded span, so per-shard lane times aggregate
    by max across lanes in the timeline (DESIGN.md §11).

    Robustness (DESIGN.md §12): ``watchdog_s`` arms a deadline on every
    staged upload — a staging copy that has not landed within it (a stalled
    lane) trips the watchdog, the lane drops to DEGRADED, and all further
    acquires of the pass stage *synchronously* on the caller thread through
    a dedicated emergency buffer (never the staging ring, whose in-flight
    slot the stalled copy may still write).  ``TransientCopyError`` from a
    staging copy is retried up to ``max_retries`` times with exponential
    backoff before the same synchronous fallback engages.  ``begin()``
    drains stragglers and restores the lane to HEALTHY — a lane recovers at
    pass granularity, counters persist.  ``faults`` injects deterministic
    stalls / slowdowns / copy failures at the staging site (``FaultPlan``);
    the emergency path deliberately bypasses injection, modelling the
    direct, reliable-but-serial load the degraded mode IS."""

    def __init__(self, pool, *, timeline: MeasuredTimeline,
                 prefetch_depth: int = 1, device=None, shard: int = 0,
                 faults: Optional[FaultPlan] = None,
                 watchdog_s: Optional[float] = None, max_retries: int = 2,
                 metrics: Optional[MetricsRegistry] = None):
        assert prefetch_depth >= 0
        assert watchdog_s is None or watchdog_s > 0.0
        self.pool = pool
        self.depth = prefetch_depth
        self.device = device
        self.shard = shard
        self.timeline = timeline
        self.faults = faults
        self.watchdog_s = watchdog_s
        self.max_retries = max(int(max_retries), 0)
        self._stream = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="copy-stream")
        # the double buffers: depth+1 staging slots shaped like a layer shard
        # (the stacked layer pytree is uniform, so one prototype fits all)
        self._slots = [
            jax.tree.map(lambda a: np.empty_like(a), pool.layer(0))
            for _ in range(prefetch_depth + 1)
        ]
        self._spare = None        # emergency slot, allocated on first fallback
        self._sched: List[int] = []
        self._staging: Dict[int, Future] = {}       # seq index -> Future[slot]
        self._abandoned: List[Future] = []          # timed-out / failed stages
        self._live: Dict[int, object] = {}          # seq index -> device tree
        self.uploads = 0
        self.bytes_uploaded = 0
        self.peak_resident = 0
        self.degraded = False     # lane health: False=healthy, True=degraded
        # robustness counters (cumulative across passes; see lane_health)
        # and staging engagement (``acquires``).  With a metrics registry
        # each dict is a live VIEW over ``<name>{key=...,shard=N}``
        # counters — same mapping surface, one counter source of truth
        # (DESIGN.md §13); without one it stays a plain dict.
        def counts(name: str, keys):
            if metrics is None:
                return {k: 0 for k in keys}
            return CounterDictView(metrics, name, labels={"shard": shard},
                                   keys=keys)
        self.counters = counts("streamer_faults", FAULT_COUNTER_KEYS)
        self.acquires = counts("streamer_acquires", ACQUIRE_COUNTER_KEYS)

    # ----------------------------------------------------------------- stream
    def submit(self, fn: Callable[[], object]) -> Future:
        """Enqueue arbitrary work on the serialized copy stream."""
        return self._stream.submit(fn)

    def _stage(self, layer: int, slot: int):
        """Copy-stream phase: pinned staging copy (overlaps with compute)."""
        if self.faults is not None:
            ev = self.faults.draw(f"stage:{self.shard}",
                                  kinds=("stall", "copy_fail", "slow"))
            if ev is not None:
                if ev.kind == "copy_fail":
                    self.timeline.record_event("copy_fail_injected")
                    raise TransientCopyError(
                        f"injected staging failure "
                        f"(layer {layer}, shard {self.shard})")
                if ev.kind == "stall":
                    self.counters["stalls_injected"] += 1
                self.timeline.record_event(f"{ev.kind}_injected")
                time.sleep(ev.seconds)
        return self._stage_into(layer, self._slots[slot])

    def _stage_into(self, layer: int, dst):
        nbytes = self.pool.layer_nbytes[layer]
        with self.timeline.task("pcie", "w", nbytes, self.shard,
                                name="w_stage"):
            jax.tree.map(np.copyto, dst, self.pool.layer(layer))
        self.uploads += 1
        self.bytes_uploaded += nbytes
        return dst

    def _stage_emergency(self, layer: int):
        """Degraded-mode stage: synchronous copy on the caller thread into a
        dedicated spare buffer.  Never touches the staging ring — an
        abandoned (stalled) stage may still write into its ring slot — and
        deliberately bypasses fault injection: this IS the direct, serial,
        reliable load path the lane falls back to."""
        if self._spare is None:
            self._spare = jax.tree.map(
                lambda a: np.empty_like(a), self.pool.layer(0))
        self.counters["sync_fallbacks"] += 1
        self.timeline.record_event("sync_fallback")
        return self._stage_into(layer, self._spare)

    # ------------------------------------------------------------------- pass
    def begin(self, schedule: Sequence[int]) -> None:
        """Arm a pass; any leftover device buffers are donated first.  A
        degraded lane recovers here — pass granularity — once stragglers
        (including abandoned, timed-out stages) have drained, so ring slots
        are provably quiescent before reuse."""
        for i in list(self._live):
            self.release(i)
        self._drain_staging()           # drain stragglers before slot reuse
        self._sched = list(schedule)
        self._live = {}
        self.degraded = False
        for j in range(min(self.depth, len(self._sched))):
            self._dispatch(j)

    def _drain_staging(self) -> None:
        """Wait out every in-flight or abandoned staging future, swallowing
        their failures — a drained fault is already counted."""
        for fut in list(self._staging.values()) + self._abandoned:
            try:
                fut.result()
            except Exception:           # injected/transient copy failures
                pass
        self._staging = {}
        self._abandoned = []

    def _degrade(self, i: int) -> None:
        """Drop the lane to degraded mode: abandon every in-flight staging
        (their futures drain at the next ``begin``/``close``; their ring
        slots are off-limits until then) and stop prefetching."""
        self.degraded = True
        for j in list(self._staging):
            self._abandoned.append(self._staging.pop(j))

    def _dispatch(self, i: int) -> None:
        if i in self._staging or not (0 <= i < len(self._sched)):
            return
        self._staging[i] = self._stream.submit(
            self._stage, self._sched[i], i % (self.depth + 1))

    def acquire(self, i: int):
        """Device weights for schedule position ``i``: wait for the staging
        copy (bounded by the watchdog, retried on transient failure), top
        the prefetch window up so the next stagings run under this one's
        hand-off, then hand the slot off to the device (serial tail).  The
        caller's wait is a ``host``/``w_wait`` span; the hand-off rides the
        pcie lane as a ``w`` span with no byte count
        (``offload.w_handoff``)."""
        if i in self._live:
            return self._live[i]
        tl = self.timeline
        fut = self._staging.get(i)          # none on a degraded lane
        landed = fut is not None and fut.done() and fut.exception() is None
        self.acquires["landed" if landed else "waited"] += 1
        with tl.task(HOST, "w_wait", shard=self.shard):
            staged = (self._stage_emergency(self._sched[i]) if self.degraded
                      else self._acquire_staged(i))
        if not self.degraded:               # degraded: no prefetch top-up
            for j in range(i + 1, min(i + 1 + self.depth, len(self._sched))):
                self._dispatch(j)
        with tl.task("pcie", "w", shard=self.shard, name="w_handoff"):
            dev = (jax.device_put(staged) if self.device is None
                   else jax.device_put(staged, self.device))
            jax.block_until_ready(dev)
        self._live[i] = dev
        self.peak_resident = max(self.peak_resident,
                                 len(self._live) + len(self._staging))
        return dev

    def _acquire_staged(self, i: int):
        """Healthy-path wait: watchdog deadline on the staged future, bounded
        retry with exponential backoff on ``TransientCopyError``; either
        ladder exhausting drops the lane to degraded and falls back to the
        emergency synchronous stage."""
        layer = self._sched[i]
        if i not in self._staging:
            if self.depth == 0:             # synchronous: stage inline
                fut: Future = Future()
                try:
                    fut.set_result(self._stage(layer, 0))
                except TransientCopyError as e:
                    fut = Future()
                    fut.set_exception(e)
                self._staging[i] = fut
            else:
                self._dispatch(i)
        retries = 0
        while True:
            fut = self._staging[i]
            try:
                staged = fut.result(timeout=self.watchdog_s)
            except FuturesTimeout:
                self.counters["watchdog_timeouts"] += 1
                self.timeline.record_event("watchdog_timeout")
                self._degrade(i)
                return self._stage_emergency(layer)
            except TransientCopyError:
                retries += 1
                if retries > self.max_retries:
                    self.counters["copy_failures"] += 1
                    self.timeline.record_event("copy_give_up")
                    self._degrade(i)
                    return self._stage_emergency(layer)
                self.counters["copy_retries"] += 1
                self.timeline.record_event("copy_retry")
                time.sleep(min(0.001 * (2 ** (retries - 1)), 0.05))
                self._staging[i] = self._stream.submit(
                    self._stage, layer, i % (self.depth + 1))
                continue
            del self._staging[i]
            return staged

    def release(self, i: int) -> None:
        """Donate schedule position ``i``'s stale device buffer."""
        dev = self._live.pop(i, None)
        if dev is not None:
            donate_buffers(dev)

    def close(self) -> None:
        """Deterministic teardown: drain every outstanding staging (faults
        swallowed — already counted), donate live buffers, and join the
        copy-stream thread.  Idempotent; also the context-manager exit."""
        self._drain_staging()
        for i in list(self._live):
            self.release(i)
        self._stream.shutdown(wait=True)

    def __enter__(self) -> "WeightStreamer":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    # ------------------------------------------------------------------ stats
    @property
    def resident_buffers(self) -> int:
        return len(self._live)

    @property
    def lane_health(self) -> str:
        """"healthy" | "degraded" — degraded clears at the next ``begin``."""
        return "degraded" if self.degraded else "healthy"

    @property
    def fault_counters(self) -> Dict[str, int]:
        return dict(self.counters)


class ShardedWeightLanes:
    """Per-mesh-position weight lanes behind the ``WeightStreamer`` API
    (DESIGN.md §11).

    One ``WeightStreamer`` per mesh device, each with its own staging ring
    and copy-stream thread, staging only that device's slice of every layer
    (``HostWeightPool.lane_view``).  ``acquire`` waits on every lane's
    staging, hands each slice to ITS device, and assembles the global
    sharded layer tree with ``jax.make_array_from_single_device_arrays`` —
    zero copy, the per-lane buffers ARE the global array's shards.  The
    per-lane ``device_put`` hand-offs serialise on the caller thread (the
    same CPU-backend tail the single-lane streamer documents); the staging
    copies — the DMA analogue — genuinely run on N concurrent lanes.

    Spans are recorded into ONE shared timeline with per-lane ``shard``
    stamps, so lane seconds aggregate by max across shards downstream.
    """

    def __init__(self, pool, plan, *, timeline: MeasuredTimeline,
                 prefetch_depth: int = 1, faults=None,
                 watchdog_s: Optional[float] = None, max_retries: int = 2,
                 metrics: Optional[MetricsRegistry] = None):
        self.plan = plan
        self.pool = pool
        self.devices = plan.lane_devices()
        self.lanes = [
            WeightStreamer(pool.lane_view(i), prefetch_depth=prefetch_depth,
                           timeline=timeline, device=dev, shard=i,
                           faults=faults, watchdog_s=watchdog_s,
                           max_retries=max_retries, metrics=metrics)
            for i, dev in enumerate(self.devices)
        ]
        # global leaf shapes/specs for assembly (uniform across layers)
        import jax.tree_util as jtu
        self._leaf_shapes = [a.shape for a in jtu.tree_leaves(pool.layer(0))]
        self._treedef = jtu.tree_structure(pool.layer(0))
        from jax.sharding import NamedSharding
        self._shardings = [NamedSharding(plan.mesh, s)
                           for s in pool.layer_leaf_specs]

    def begin(self, schedule) -> None:
        sched = list(schedule)
        for lane in self.lanes:
            lane.begin(sched)

    def acquire(self, i: int):
        import jax.tree_util as jtu
        per_lane = [jtu.tree_leaves(lane.acquire(i)) for lane in self.lanes]
        leaves = [
            jax.make_array_from_single_device_arrays(
                shape, sharding, [per_lane[ln][j] for ln in range(
                    len(self.lanes))])
            for j, (shape, sharding) in enumerate(
                zip(self._leaf_shapes, self._shardings))
        ]
        return jtu.tree_unflatten(self._treedef, leaves)

    def release(self, i: int) -> None:
        for lane in self.lanes:
            lane.release(i)

    def close(self) -> None:
        for lane in self.lanes:
            lane.close()

    def __enter__(self) -> "ShardedWeightLanes":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    # aggregated stats (sums across lanes; per-lane detail on .lanes)
    @property
    def uploads(self) -> int:
        return sum(lane.uploads for lane in self.lanes)

    @property
    def bytes_uploaded(self) -> int:
        return sum(lane.bytes_uploaded for lane in self.lanes)

    @property
    def peak_resident(self) -> int:
        return max(lane.peak_resident for lane in self.lanes)

    @property
    def resident_buffers(self) -> int:
        return max(lane.resident_buffers for lane in self.lanes)

    @property
    def lane_health(self) -> str:
        """Worst health across lanes: one degraded lane degrades the mesh."""
        return ("degraded" if any(l.degraded for l in self.lanes)
                else "healthy")

    @property
    def fault_counters(self) -> Dict[str, int]:
        agg: Dict[str, int] = {}
        for lane in self.lanes:
            for k, v in lane.counters.items():
                agg[k] = agg.get(k, 0) + v
        return agg
