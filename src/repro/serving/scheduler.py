"""Chunked-scan continuous batching on top of the hybrid KV/ACT cache.

Orca-style scheduling (the paper's §2.1 batching substrate): a fixed pool of
B_slots decode slots; finished requests leave and queued arrivals are
admitted at CHUNK boundaries.  The serving hot loop is built around chunked
on-device scan decode (DESIGN.md §10):

  * every chunk of ``chunk_steps`` iterations is ONE jitted dispatch
    (``M.hybrid_decode_chunk``: greedy sampling, per-slot store flags and
    active masks all on-device, cache donated) followed by ONE blocking
    host sync for the chunk's token matrix — not one dispatch + one sync
    per generated token,
  * all arrivals queued at a chunk boundary are coalesced into ONE batched
    prefill dispatch (``M.hybrid_prefill_batched`` writes its rows into the
    free slots inside the same jit call) instead of one retracing B=1
    prefill each,
  * the per-slot store-type schedule is precomputed host-side
    (``core.policy.store_act_schedule``, property-tested) and replayed
    after the dispatch through the ``BlockManager`` for block accounting,
  * TTFT / TBT are reconstructed at SUB-chunk granularity from the per-step
    ``simulate_steps`` results, so latency metrics stay step-accurate even
    though the device ran the whole chunk in one dispatch,
  * the known per-slot lengths bound the occupied prefix of both cache
    regions, and the bound is passed to the decode attention as a static
    page-aligned ``kv_bound``/``act_bound`` — the scheduler-side twin of
    the paged kernel's ``pages_bound`` grid shrink.

``chunk_steps=1`` IS the classic step server (admission every iteration);
larger chunks amortize the dispatch tax at the cost of admission latency
(arrivals wait for the running chunk to finish — the TTFT/throughput
frontier ``benchmarks/serving_bench.py`` sweeps).

Reports per-request TTFT / TBT and aggregate throughput (simulated on the
target hardware via the two-lane pipeline model), alongside the real tokens.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from contextlib import ExitStack
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import (BLOCK_TOKENS, BlockManager, BlockType,
                        ControllerConfig, HostAllocation,
                        HybridCacheController, Location, device_act_blocks,
                        host_block_allocation, store_act_schedule)
from repro.core import costmodel as cm
from repro.core.pipeline import MiniBatchSpec, simulate_steps
from repro.data.pipeline import Request
from repro.models import model as M
from repro.obs import (DriftMonitor, NULL_TRACER, fold_timeline_metrics,
                       register_busy_fraction_collector)
from repro.serving.recovery import (CapacityError, ParkedRequest,
                                    RecoveryConfig, RecoveryStats,
                                    blocks_for_tokens, resume_cost)
from repro.serving.util import bucket, pack_group, trace_ctx
from repro.sharding import ShardPlan


@dataclass
class SlotState:
    rid: int = -1
    remaining: int = 0
    kv_tokens: int = 0          # host mirror of this slot's device kv_len
    act_tokens: int = 0         # host mirror of this slot's device act_len
    generated: List[int] = field(default_factory=list)
    preempts: int = 0           # times this request has been preempted
    request: Optional[Request] = None   # original request (resume prefix)

    @property
    def active(self) -> bool:
        return self.rid >= 0


@dataclass
class ServeStats:
    steps: int = 0              # decode iterations executed (sub-chunk)
    chunks: int = 0             # chunked decode dispatches
    admission_batches: int = 0  # coalesced prefill dispatches
    admitted: int = 0           # requests admitted across all batches
    generated_tokens: int = 0
    device_calls: int = 0       # jitted dispatches the server issued
    # blocking device->host materialisation points.  Device-resident path:
    # one per chunk + one per admission batch.  Offload path: the layer-
    # streamed executor blocks per layer by design, so its real per-layer
    # count is reported (OffloadExecutor.blocking_syncs) — chunking there
    # amortizes per-STEP overheads, not sync counts.
    host_syncs: int = 0
    sim_time: float = 0.0
    measured_time: float = 0.0  # offload runtime ground truth (else 0)
    ttft: Dict[int, float] = field(default_factory=dict)
    tbt: Dict[int, float] = field(default_factory=dict)
    completed_at: Dict[int, int] = field(default_factory=dict)  # rid -> step
    # host clock (perf_counter) of each request's entry into the queue and
    # of the readback that delivered its first token; the registry's
    # wall-clock histograms (queue_wait_s, ttft_s, tbt_s) read these,
    # while ttft/tbt above stay the simulator's
    queued_at: Dict[int, float] = field(default_factory=dict)
    first_token_at: Dict[int, float] = field(default_factory=dict)

    @property
    def throughput(self) -> float:
        return self.generated_tokens / self.sim_time if self.sim_time else 0.0

    @property
    def dispatches_per_token(self) -> float:
        return (self.device_calls / self.generated_tokens
                if self.generated_tokens else 0.0)


class ContinuousBatchingServer:
    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 kv_cap: int = 256, act_cap: int = 256,
                 chunk_steps: int = 1,
                 hw: Optional[cm.HardwareSpec] = None,
                 generalized: bool = True,
                 offload: bool = False, prefetch_depth: int = 1,
                 adaptive: bool = False,
                 ctl: Optional[ControllerConfig] = None,
                 plan: Optional[ShardPlan] = None,
                 recovery: Optional[RecoveryConfig] = None,
                 faults=None, watchdog_s: Optional[float] = None,
                 host_kv_blocks: Optional[int] = None,
                 host_act_blocks: Optional[int] = None,
                 dev_kv_blocks: Optional[int] = None,
                 dev_act_blocks: Optional[int] = None,
                 tracer=None, metrics=None, quant=None,
                 host_attn: bool = False):
        """chunk_steps: decode iterations per jitted dispatch.  1 reproduces
        the classic step server (admission every iteration); S>1 runs S
        masked steps per dispatch, admitting/retiring only at chunk
        boundaries — dispatches per generated token drop toward 1/S while
        arrivals may wait up to S steps for admission (TTFT cost under
        bursty traffic; see DESIGN.md §10).

        hw: the machine the policy stack plans for; default
        ``costmodel.local_hardware()`` — the TPU this process runs on, or
        the v5e prior on any other backend.

        offload=True swaps the jitted monolithic decode chunk for the
        layer-streamed offload executor (DESIGN.md §8): weights arrive over
        the copy stream each iteration while the slots' KV Gen runs, with
        the streamer's prefetch window spanning the whole chunk, and
        ``self.measured_steps`` exposes the measured per-iteration lane
        timelines.  Admission prefill streams the layers too, so the full
        parameter set (which may be host numpy, ``init_params(...,
        on_host=True)``) never reaches the device.  Tokens are identical
        either way.

        adaptive=True runs the hybrid-cache controller between chunks
        (DESIGN.md §9): per-chunk timeline batches (measured under offload,
        simulated otherwise) refit the cost model, and the running ACT:KV
        target that drives per-slot store decisions follows the refit
        allocation, mirrored onto the block pools by bounded capacity
        retags.  Host-side only; the decode dispatch is unchanged.

        plan=... serves tensor-parallel under the given ``ShardPlan``
        (DESIGN.md §11): the slot cache is sharded per the plan (KV heads
        over 'model', slots over 'data'), weights are committed to the
        mesh, and the policy stack prices the aggregate machine
        (``costmodel.scale_for_shards``).  The chunk structure — ONE
        dispatch + ONE blocking sync per chunk, ONE per admission batch —
        holds PER MESH: sharding adds collectives inside the dispatch,
        never host syncs (the PR 4 dispatch-count guarantees).

        recovery=RecoveryConfig(...) arms pressure recovery (DESIGN.md
        §12; on by default): block-pool exhaustion preempts victim slots —
        demoting their KV blocks to ACT checkpoints when ACT capacity
        exists, dropping to token-ID recompute otherwise — and parks them
        in a bounded re-admission queue with resume priority over fresh
        arrivals.  Resumes re-prefill over prompt + generated prefix,
        token-exact vs the never-preempted oracle under greedy decoding.
        ``RecoveryConfig(max_parked=0)`` restores pure fail-loud behaviour
        (now a structured ``CapacityError``).

        faults / watchdog_s: offload-lane fault injection and upload
        deadline, forwarded to the ``OffloadExecutor`` (offload=True only).

        host_kv_blocks / host_act_blocks / dev_kv_blocks / dev_act_blocks
        override the Algorithm-1 pool sizing — the pressure tests' knob for
        provoking exhaustion at smoke scale.

        quant=... serves with block-quantized cache regions (DESIGN.md
        §14): cache writes fake-quant inside the same dispatches, and the
        policy stack / block accounting price the quantized bytes.
        ``quant=None`` (default) is bit-identical to today's server.

        host_attn=True (offload mode only) routes every slot's KV-region
        attention to the cpu lane (DESIGN.md §15): the executor keeps a
        host mirror of the occupied KV prefix, a worker thread computes
        flash-style LSE partials over it while the device recomputes the
        ACT region, and the partials merge on device — token-exact, with
        the cpu lane recorded in the measured timelines and priced by the
        three-way placement stack.  ``host_attn=False`` (default) is
        bit-identical to today's server."""
        assert M.family(cfg) == "uniform"
        assert not host_attn or offload, \
            "host_attn rides the offload runtime's host mirror"
        self.host_attn = bool(host_attn)
        self.plan = plan
        self.quant = quant
        shards = plan.shard_factor if plan is not None else 1
        hw = cm.scale_for_shards(
            hw if hw is not None else cm.local_hardware(), shards)
        self.cfg, self.params, self.hw = cfg, params, hw
        self.n_slots, self.kv_cap, self.act_cap = slots, kv_cap, act_cap
        self.chunk_steps = max(int(chunk_steps), 1)
        # observability (DESIGN.md §13) — host-side only; the dispatch- and
        # sync-count invariants below hold bit-identical with tracing on
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics
        self.drift = DriftMonitor(registry=metrics)
        if metrics is not None:
            register_busy_fraction_collector(metrics)
            metrics.register_collector(self._collect_metrics)
        self.alloc = host_block_allocation(
            cfg, hw, device_act_blocks(cfg, hw, quant=quant),
            generalized=generalized, quant=quant)
        self.act_frac = self.alloc.act_fraction
        self.controller = None
        if adaptive:
            self.controller = HybridCacheController(
                cfg, hw, self.alloc, device_act_blocks(cfg, hw, quant=quant),
                generalized=generalized,
                ctl=ctl if ctl is not None else
                ControllerConfig(update_every=4), drift=self.drift,
                quant=quant, cpu=host_attn)
        # physical block accounting, replayed per chunk from the precomputed
        # store schedule (the engine's pattern, DESIGN.md §5): host pools in
        # the Algorithm-1 split, device pools as the engine sizes them
        self.blockman = BlockManager(
            cfg,
            host_kv_blocks=(host_kv_blocks if host_kv_blocks is not None
                            else max(self.alloc.kv_blocks, 1)),
            host_act_blocks=(host_act_blocks if host_act_blocks is not None
                             else max(self.alloc.act_blocks, 1)),
            dev_kv_blocks=(dev_kv_blocks if dev_kv_blocks is not None
                           else 64),
            dev_act_blocks=(dev_act_blocks if dev_act_blocks is not None
                            else device_act_blocks(cfg, hw, quant=quant)),
            shard_factor=shards, quant=quant)
        # pressure recovery (DESIGN.md §12): parked re-admission queue +
        # counters; profiled fits price resume costs in sim_time units
        self.recovery = recovery if recovery is not None else RecoveryConfig()
        self.recovery_stats = RecoveryStats(metrics)
        self.parked: List[ParkedRequest] = []
        self.fits = cm.profile_cost_fns(cfg, hw, quant=quant)
        # offload mode: per-iteration timelines drained out of the executor
        # as they complete (keeping its span store bounded) and accumulated
        # here for the measured_steps property
        self._measured: List = []
        self.cache = M.init_hybrid_cache(cfg, slots, kv_cap, act_cap)
        if plan is not None:
            self.cache = plan.place_cache(self.cache)
        self.slots = [SlotState() for _ in range(slots)]
        self.executor = None
        if offload:
            from repro.offload import OffloadExecutor
            self.executor = OffloadExecutor(cfg, params,
                                            prefetch_depth=prefetch_depth,
                                            plan=plan, faults=faults,
                                            watchdog_s=watchdog_s,
                                            tracer=tracer, metrics=metrics,
                                            quant=quant)
            # the executor owns the weights (host layer shards + resident
            # remainder) and runs admission prefill layer by layer too: the
            # full parameter set never reaches the device
            self.params = None
            self._write_rows_jit = jax.jit(self._write_rows_impl,
                                           donate_argnums=(0,))
        else:
            if plan is not None:
                self.params = plan.place_params(params)
            # cache donated: the slot pools update in place every chunk
            self._decode_chunk_jit = functools.partial(
                jax.jit, static_argnames=("kv_bound", "act_bound"),
                donate_argnums=(2,))(self._decode_chunk_impl)
            # admission is one jitted call per boundary: batched prefill +
            # greedy sample + slot-row writes, cache donated
            self._admit_jit = functools.partial(
                jax.jit, static_argnames=("kv_cap", "act_cap"),
                donate_argnums=(5,))(self._admit_impl)
        self._cur_tok = np.zeros((slots,), np.int32)

    @property
    def measured_steps(self):
        """Measured per-iteration timelines (offload mode; else empty)."""
        if self.executor is None:
            return []
        return self._measured + self.executor.timeline.results("decode")

    def snapshot(self) -> Dict[str, object]:
        """One-call observability read (DESIGN.md §13): TTFT/TBT
        percentiles, lane busy fractions, fault/recovery counters, block
        occupancy, and per-lane predictor drift — the registry snapshot
        with collectors run, plus the drift monitor's full summary."""
        out: Dict[str, object] = (self.metrics.snapshot()
                                  if self.metrics is not None else {})
        out["predictor_drift"] = self.drift.summary()
        return out

    def _collect_metrics(self, reg) -> None:
        """Pull-style collector: occupancy-by-tag, retags, parked depth and
        controller state read at snapshot() time, never on the hot path."""
        for (kind, loc), pool in self.blockman.pools.items():
            labels = dict(kind=kind.value, tier=loc.value)
            reg.gauge("blocks_capacity", **labels).set(pool.capacity)
            reg.gauge("blocks_allocated", **labels).set(pool.allocated)
        for (loc, src, dst), n in self.blockman.retags.items():
            reg.counter("retagged_blocks", tier=loc.value, src=src.value,
                        dst=dst.value).set(n)
        reg.gauge("parked_requests").set(len(self.parked))
        reg.gauge("act_fraction").set(self.act_frac)
        if self.controller is not None:
            reg.gauge("controller_updates").set(self.controller.updates)
            reg.gauge("controller_migrated_blocks").set(
                self.controller.migrated_blocks)
            reg.gauge("controller_faulted_skipped").set(
                self.controller.faulted_skipped)

    def close(self) -> None:
        """Shut down the offload executor (no-op in device-resident mode).
        Each offload executor owns a copy-stream thread and layer-shard
        staging buffers, so long-lived processes building servers per batch
        must close them."""
        if self.executor is not None:
            self.executor.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # --- jitted wrappers ------------------------------------------------------
    # params are an explicit jit argument (not a closure capture) so their
    # committed mesh placement under a ShardPlan reaches XLA as the input
    # sharding — the lowered computation is genuinely tensor-parallel
    def _admit_impl(self, params, tokens, kv_keep, last_pos, slot_idx, cache,
                    kv_cap, act_cap):
        """ONE dispatch per admission batch: group-batched prefill, greedy
        sample of its logits, and the scatter of the new rows into the free
        slots of the (donated) server cache."""
        lg, c1 = M.hybrid_prefill_batched(
            params, self.cfg, {"tokens": tokens}, kv_cap=kv_cap,
            act_cap=act_cap, kv_keep=kv_keep, last_pos=last_pos,
            quant=self.quant)
        return (jnp.argmax(lg[:, -1], -1).astype(jnp.int32),
                self._write_rows_impl(cache, c1, slot_idx))

    def _write_rows_impl(self, cache, rows, slot_idx):
        """Scatter a batch's prefilled cache rows into the slot cache (the
        offload executor's rows arrive as per-layer lists)."""
        for key in ("k", "v", "act"):
            r = rows[key]
            cache[key] = cache[key].at[:, slot_idx].set(
                jnp.stack(r, 0) if isinstance(r, list) else r)
        for key in ("act_pos", "kv_len", "act_len"):
            cache[key] = cache[key].at[slot_idx].set(rows[key])
        if self.plan is not None:
            cache = self.plan.constrain_cache(cache)
        return cache

    def _decode_chunk_impl(self, params, cur, cache, store_sched,
                           active_sched, kv_bound, act_bound):
        if self.plan is not None:
            cache = self.plan.constrain_cache(cache)
        toks, cur, cache = M.hybrid_decode_chunk(
            params, self.cfg, cur, cache, store_sched, active_sched,
            kv_bound=kv_bound, act_bound=act_bound, quant=self.quant)
        if self.plan is not None:
            cache = self.plan.constrain_cache(cache)
        return toks, cur, cache

    # ------------------------------------------------------------- admission
    def _admission_split(self, pb: int) -> Tuple[int, int]:
        """(kv_tokens, act_tokens) the admission prefill will use for a
        ``pb``-token prefix — the host-side twin of ``pack_group``'s
        clamped Eq. 11 split, for pre-admission capacity forecasting."""
        kk = int(round(pb * (1 - self.act_frac) / BLOCK_TOKENS)) * BLOCK_TOKENS
        if pb <= self.kv_cap + self.act_cap:
            lo = bucket(max(pb - self.act_cap, 0)) if pb > self.act_cap else 0
            kk = min(max(kk, lo), min(self.kv_cap, pb))
        return kk, pb - kk

    def _plan_admission(self, queue: List[Request]
                        ) -> List[Tuple[int, Request,
                                        Optional[ParkedRequest]]]:
        """Chunk-boundary admission plan: parked resumes strictly first
        (backpressure — fresh arrivals never starve a preempted request),
        then queued arrivals, each capacity-checked against the free block
        pools so admission cannot trigger the exhaustion it exists to
        relieve.  Candidates that do not fit stay parked/queued.  Mutates
        ``self.parked``/``queue`` for what it admits."""
        free_slots = [i for i, s in enumerate(self.slots) if not s.active]
        free_kv = self.blockman.free_blocks(BlockType.KV)
        free_act = self.blockman.free_blocks(BlockType.ACT)
        out: List[Tuple[int, Request, Optional[ParkedRequest]]] = []
        for slot in free_slots:
            if self.parked:
                pk = self.parked[0]
                pb = bucket(pk.prefix_tokens)
                kk, at = self._admission_split(pb)
                kb = blocks_for_tokens(0, kk)
                ab = blocks_for_tokens(0, at)
                # an "act" resume releases its parked holdings on admission
                credit = (self.blockman.counts(pk.rid)["act_blocks"]
                          if pk.mode == "act" else 0)
                if kb <= free_kv and ab <= free_act + credit:
                    free_kv -= kb
                    free_act += credit - ab
                    out.append((slot, pk.request, self.parked.pop(0)))
                    continue
                break           # head-of-line blocked: hold ALL admissions
            if not queue:
                break
            pb = bucket(len(queue[0].prompt))
            kk, at = self._admission_split(pb)
            kb, ab = blocks_for_tokens(0, kk), blocks_for_tokens(0, at)
            if kb > free_kv or ab > free_act:
                break           # backpressure: wait for blocks to free
            free_kv -= kb
            free_act -= ab
            out.append((slot, queue.pop(0), None))
        return out

    def _admit_batch(self, assignments: List[Tuple[int, Request,
                                                   Optional[ParkedRequest]]],
                     stats: ServeStats) -> None:
        """Admit every planned candidate in ONE batched prefill dispatch
        (per-request kv_keep/last_pos, rows written into the slots inside
        the same jit call).  Resumes ride the same dispatch: their prefix
        is prompt + generated-so-far, their parked holdings are released
        first, and the resume's simulated cost (KV Gen regenerate for
        "act", full-forward recompute for "tokens") is priced into
        sim_time."""
        k = len(assignments)
        reqs: List[Request] = []
        lens: List[int] = []      # true prefill lengths (-1: fill from pbs)
        rstats = self.recovery_stats
        t_admit = time.perf_counter()
        for i, r, pk in assignments:
            if pk is None:
                # fresh admission opens the request's root trace span; a
                # resume re-enters the root its first admission opened
                self.tracer.request_begin(r.rid, prompt_tokens=len(r.prompt),
                                          max_new=r.max_new_tokens)
                if self.metrics is not None:
                    self.metrics.histogram("queue_wait_s").observe(
                        t_admit - stats.queued_at[r.rid])
                reqs.append(r)
                lens.append(-1)   # fresh: the padded bucket IS the prompt
                continue
            self.tracer.request_event(r.rid, "resume", mode=pk.mode,
                                      generated=len(pk.generated))
            # release the parked holdings (the demoted ACT checkpoints this
            # resume regenerates from), then re-prefill over the prefix
            if pk.mode == "act":
                self.blockman.free_request(pk.rid)
                rstats.resume_from_act += 1
            else:
                rstats.resume_from_tokens += 1
            rstats.resumes += 1
            cost = resume_cost(self.cfg, self.hw, self.fits,
                               pk.prefix_tokens, pk.mode)
            rstats.resume_cost_s += cost
            stats.sim_time += cost
            # the resume prefix is the EFFECTIVE served context: the prompt
            # as originally admitted — bucket-padded with its last token —
            # plus every generated token.  Its true length (generally not a
            # bucket multiple) becomes this row's last_pos, so re-prefill
            # padding can never shift the resumed positions.
            pp = np.asarray(r.prompt, np.int32)
            pad = bucket(len(pp)) - len(pp)
            prefix = np.concatenate([pp, np.full((pad,), pp[-1], np.int32),
                                     np.asarray(pk.generated, np.int32)])
            reqs.append(Request(rid=r.rid, prompt=prefix,
                                max_new_tokens=pk.remaining))
            lens.append(len(prefix))
        # pad to the batch bucket + Eq. 11 split (clamped off full regions);
        # a prefix that fits neither region combined is infeasible
        try:
            toks, kv_keep, pbs = pack_group(reqs, self.act_frac, self.kv_cap,
                                            self.act_cap, clamp=True)
        except ValueError as e:
            raise CapacityError(
                f"admission prefix does not fit the cache regions: {e}",
                rids=[r.rid for r in reqs], resource="cache region",
                hint="raise kv_cap/act_cap or shorten prompts") from e
        lens = [pbs[j] if lens[j] < 0 else lens[j] for j in range(k)]
        kv_keep = np.asarray(kv_keep, np.int32).copy()
        for j, tl in enumerate(lens):
            if tl != pbs[j]:
                # resume row: re-clamp the bucket-derived split into the TRUE
                # prefix length's feasible window (act span <= act_cap, kv
                # prefix <= kv_cap); pack_group validated the bucket >= tl
                kv_keep[j] = min(max(int(kv_keep[j]), max(tl - self.act_cap,
                                                          0)),
                                 min(self.kv_cap, tl))
        slot_idx = np.asarray([i for i, _, _ in assignments], np.int32)
        with ExitStack() as tspans:
            tspans.enter_context(jax.profiler.TraceAnnotation("serve.admit"))
            tspans.enter_context(self.tracer.server_span("admit", batch=k))
            for j, (_, _, pk) in enumerate(assignments):
                tspans.enter_context(self.tracer.request_span(
                    reqs[j].rid,
                    "resume_prefill" if pk is not None else "prefill"))
            if self.executor is not None:
                # layer-streamed prefill, then ONE row-scatter dispatch
                d0, b0 = (self.executor.dispatches,
                          self.executor.blocking_syncs)
                cur, rows = self.executor.prefill_batched(
                    toks, kv_keep, np.asarray(lens, np.int32),
                    kv_cap=self.kv_cap, act_cap=self.act_cap)
                with trace_ctx(self.plan):
                    self.cache = self._write_rows_jit(
                        self.cache, rows, jnp.asarray(slot_idx))
                stats.device_calls += self.executor.dispatches - d0 + 1
                stats.host_syncs += self.executor.blocking_syncs - b0
            else:
                with trace_ctx(self.plan):
                    cur, self.cache = self._admit_jit(
                        self.params, jnp.asarray(toks), jnp.asarray(kv_keep),
                        jnp.asarray(np.asarray(lens, np.int32)),
                        jnp.asarray(slot_idx),
                        self.cache, kv_cap=self.kv_cap, act_cap=self.act_cap)
                stats.device_calls += 1
            # the admission's spans end at the readback of its first tokens
            cur_np = np.asarray(cur, np.int32)
            stats.host_syncs += 1
        stats.admission_batches += 1
        stats.admitted += k
        stats.sim_time += self.hw.dispatch_overhead
        try:
            for j, (i, orig, pk) in enumerate(assignments):
                r = reqs[j]
                st = self.slots[i]
                st.rid, st.remaining = r.rid, r.max_new_tokens
                st.generated = list(pk.generated) if pk is not None else []
                st.preempts = pk.preempts if pk is not None else 0
                st.request = orig
                st.kv_tokens = int(kv_keep[j])
                st.act_tokens = lens[j] - int(kv_keep[j])
                self._cur_tok[i] = cur_np[j]
                self.blockman.new_request(r.rid)
                if self.host_attn:
                    # KV blocks attend on the cpu lane (DESIGN.md §15)
                    self.blockman.tag_host_attend(r.rid, True)
                for t in range(lens[j]):
                    kind = BlockType.KV if t < kv_keep[j] else BlockType.ACT
                    if self.blockman.append_token(r.rid, kind) is None:
                        raise CapacityError(
                            f"{kind.value} block pool exhausted during "
                            f"prefill of request {r.rid}",
                            rids=[rr.rid for rr in reqs],
                            resource=f"{kind.value} blocks",
                            hint="grow the host pools or lower concurrency")
        except Exception:
            # a fail-loud raise must not leak the batch's rids/blocks and
            # poison the server for retries (the engine's guard, mirrored):
            # release every slot of THIS batch before propagating
            self._release_slots([i for i, _, _ in assignments])
            raise

    # --- adaptive controller hook (between chunks) ----------------------------
    def _apply_alloc(self, new_alloc: HostAllocation) -> None:
        """Retag host pool capacity toward ``new_alloc`` and commit whatever
        actually moved (free capacity only; live blocks never stranded)."""
        delta = new_alloc.act_blocks - self.alloc.act_blocks
        if delta > 0:
            moved = self.blockman.retag_capacity(
                Location.HOST, BlockType.KV, BlockType.ACT, delta)
        elif delta < 0:
            moved = -self.blockman.retag_capacity(
                Location.HOST, BlockType.ACT, BlockType.KV, -delta)
        else:
            moved = 0
        self.alloc = dataclasses.replace(
            self.alloc, act_blocks=self.alloc.act_blocks + moved,
            kv_blocks=self.alloc.kv_blocks - moved)
        self.act_frac = self.alloc.act_fraction
        if self.controller is not None:
            self.controller.alloc = self.alloc

    def _release_slots(self, slot_idx) -> None:
        """Failure-path cleanup: free the given slots' requests (block
        tables included) and reset their states, so a fail-loud raise never
        leaks rids/blocks and poisons the server for later requests
        (``free_request`` is a no-op for unknown rids)."""
        for i in slot_idx:
            st = self.slots[i]
            if st.active:
                self.blockman.free_request(st.rid)
                self.tracer.request_end(st.rid, "fail")
            self.slots[i] = SlotState()

    # ----------------------------------------------- pressure recovery (§12)
    def _release_parked(self) -> List[int]:
        """Failure-path cleanup for the re-admission queue: drop every
        parked request's holdings and return their rids — after a
        ``CapacityError`` the server must be fully admissible again."""
        rids = []
        for pk in self.parked:
            if pk.mode == "act":
                self.blockman.free_request(pk.rid)
            self.tracer.request_end(pk.rid, "fail")
            rids.append(pk.rid)
        self.parked.clear()
        return rids

    def _degrade_parked(self) -> bool:
        """Backpressure relief: drop the YOUNGEST parked "act" holding to
        token-ID mode, freeing its ACT blocks (youngest first — oldest
        resumes first and should keep its cheap resume).  True if one was
        degraded."""
        for pk in reversed(self.parked):
            if pk.mode == "act":
                self.blockman.free_request(pk.rid)
                pk.mode = "tokens"
                self.recovery_stats.parked_degraded += 1
                return True
        return False

    def _preempt_slot(self, v: int, active: np.ndarray,
                      sched_t: np.ndarray, allow_demote: bool) -> None:
        """Evict slot ``v`` pre-dispatch: demote its KV blocks to ACT
        checkpoints (paper-native — the regenerate lane resumes from them)
        when allowed, else drop everything to token-IDs; park it for
        re-admission and mask it out of this chunk."""
        st = self.slots[v]
        c = self.blockman.counts(st.rid)
        rstats = self.recovery_stats
        mode = "tokens"
        if allow_demote:
            demoted = self.blockman.demote_request_kv(st.rid)
            if demoted == c["kv_blocks"]:
                mode = "act"
                rstats.demoted_blocks += demoted
        if mode == "tokens":
            self.blockman.free_request(st.rid)
            rstats.dropped_blocks += c["kv_blocks"] + c["act_blocks"]
            rstats.preempt_to_tokens += 1
        else:
            rstats.preempt_to_act += 1
        rstats.preemptions += 1
        self.tracer.request_event(st.rid, "preempt", mode=mode,
                                  generated=len(st.generated))
        self.parked.append(ParkedRequest(
            request=st.request, generated=list(st.generated), mode=mode,
            preempts=st.preempts + 1))
        self.tracer.request_event(st.rid, "park", depth=len(self.parked))
        rstats.parked_peak = max(rstats.parked_peak, len(self.parked))
        active[:, v] = False
        sched_t[:, v] = False
        self.slots[v] = SlotState()

    def _relieve_pressure(self, active: np.ndarray, sched_t: np.ndarray,
                          kt0: np.ndarray, at0: np.ndarray) -> None:
        """Pre-dispatch pool-pressure loop: forecast exactly how many new
        blocks each kind needs for this chunk (block boundaries every
        BLOCK_TOKENS) and, while a pool cannot cover its forecast, free
        capacity — first by degrading parked ACT holdings (ACT pressure),
        then by preempting the victim slot holding the most blocks.  After
        this returns, the replay's ``append_token`` calls cannot exhaust.

        Raises ``CapacityError`` (all slots + parked released) when
        preemption cannot help: recovery disabled, re-admission queue full,
        every candidate exhausted its progress guard, or only one runnable
        slot remains (preempting it frees nothing another slot could use —
        its own resume needs at least as much)."""
        B = self.n_slots

        def forecast() -> Tuple[int, int]:
            kv_need = act_need = 0
            for i in range(B):
                if not self.slots[i].active:
                    continue
                col = active[:, i]
                kv_end = int(kt0[i]) + int((~sched_t[:, i] & col).sum())
                act_end = int(at0[i]) + int((sched_t[:, i] & col).sum())
                kv_need += blocks_for_tokens(int(kt0[i]), kv_end)
                act_need += blocks_for_tokens(int(at0[i]), act_end)
            return kv_need, act_need

        while True:
            kv_need, act_need = forecast()
            free_kv = self.blockman.free_blocks(BlockType.KV)
            free_act = self.blockman.free_blocks(BlockType.ACT)
            if kv_need <= free_kv and act_need <= free_act:
                return
            if act_need > free_act and self._degrade_parked():
                continue                     # parked holdings freed ACT
            runnable = [i for i in range(B) if self.slots[i].active]
            victims = [i for i in runnable if self.slots[i].preempts <
                       self.recovery.max_preempts_per_request]
            if (self.recovery.max_parked <= 0
                    or len(self.parked) >= self.recovery.max_parked
                    or not victims or len(runnable) < 2):
                rids = [self.slots[i].rid for i in runnable]
                self._release_slots(range(B))
                rids += self._release_parked()
                raise CapacityError(
                    f"block pools exhausted mid-chunk and preemption "
                    f"cannot relieve the pressure (need kv={kv_need}/"
                    f"{free_kv} act={act_need}/{free_act} free blocks)",
                    rids=rids, resource="blocks",
                    hint="grow the host pools, raise max_parked, or lower "
                         "concurrency")

            def held(i: int) -> int:
                c = self.blockman.counts(self.slots[i].rid)
                return c["kv_blocks"] + c["act_blocks"]

            v = max(victims, key=lambda i: (held(i), i))
            c_kv = self.blockman.counts(self.slots[v].rid)["kv_blocks"]
            # demote only under KV pressure with ACT slack left over AFTER
            # the chunk's own ACT forecast — demoting into ACT pressure
            # would just move the exhaustion across pools
            allow = (self.recovery.prefer_act
                     and c_kv <= free_act - act_need)
            self._preempt_slot(v, active, sched_t, allow)

    # ------------------------------------------------------------- one chunk
    def _run_chunk(self, n_steps: int, step_idx: int,
                   out: Dict[int, np.ndarray], stats: ServeStats) -> None:
        """ONE decode dispatch for ``n_steps`` masked iterations and its
        readback, then the host-side replay (``_replay_chunk``)."""
        B = self.n_slots
        remaining = np.asarray([s.remaining if s.active else 0
                                for s in self.slots])
        active = np.zeros((n_steps, B), bool)           # (S, B)
        for i in range(B):
            active[:min(int(remaining[i]), n_steps), i] = True
        at0 = np.asarray([s.act_tokens for s in self.slots], np.int64)
        kt0 = np.asarray([s.kv_tokens for s in self.slots], np.int64)
        # per-slot store schedule for the chunk (Eq. 11 running ratio,
        # unrolled host-side exactly like the engine's decode loop)
        sched = store_act_schedule(self.alloc, at0, kt0, n_steps)  # (B, S)
        sched_t = (sched.T & active).copy()                        # (S, B)
        # a region overflow inside the scan would drop writes SILENTLY while
        # the validity masks keep claiming the slots.  First remedy: CLAMP
        # the store schedule — flip store flags toward the non-full region
        # (token-exact by the hybrid representation equivalence; caps are
        # per-slot, so preemption cannot help here).  A slot whose context
        # cannot fit BOTH regions combined is genuinely infeasible: release
        # it and fail loudly, structured (DESIGN.md §12).
        doomed: List[int] = []
        for i in range(B):
            if not self.slots[i].active:
                continue
            kv, act = int(kt0[i]), int(at0[i])
            for s in range(n_steps):
                if not active[s, i]:
                    continue
                store = bool(sched_t[s, i])
                if store and act + 1 > self.act_cap:
                    if kv + 1 > self.kv_cap:
                        doomed.append(i)
                        break
                    sched_t[s, i] = store = False
                    self.recovery_stats.sched_clamps += 1
                elif not store and kv + 1 > self.kv_cap:
                    if act + 1 > self.act_cap:
                        doomed.append(i)
                        break
                    sched_t[s, i] = store = True
                    self.recovery_stats.sched_clamps += 1
                if store:
                    act += 1
                else:
                    kv += 1
        if doomed:
            rids = [self.slots[i].rid for i in doomed]
            self._release_slots(doomed)
            raise CapacityError(
                f"cache region would overflow within this chunk "
                f"(kv_cap={self.kv_cap}, act_cap={self.act_cap}) for "
                f"requests {rids}",
                rids=rids, resource="cache region",
                hint="raise the caps or cap max_new_tokens")
        # second remedy: pool pressure — preempt victims until the block
        # forecast fits the free pools (may mask slots out of this chunk)
        self._relieve_pressure(active, sched_t, kt0, at0)
        if not active.any():
            return
        # per-step region growth (host replay of what the device will do);
        # sched_t is already active-masked, ~sched_t is not
        act_run = at0[None, :] + np.cumsum(sched_t, 0)   # lengths AFTER step s
        kv_run = kt0[None, :] + np.cumsum((~sched_t) & active, 0)
        # static attention bounds from the known slot lengths, page-aligned
        # so jit shapes bucket (the pages_bound idiom, DESIGN.md §7.4/§10);
        # the overflow check above guarantees they cover every active slot
        kv_bound = min(self.kv_cap, bucket(int(kt0.max()) + n_steps))
        act_bound = min(self.act_cap, bucket(int(at0.max()) + n_steps))

        with ExitStack() as tspans:
            tspans.enter_context(jax.profiler.TraceAnnotation("serve.chunk"))
            tspans.enter_context(self.tracer.server_span(
                "chunk", steps=n_steps, idx=stats.chunks))
            for i, st in enumerate(self.slots):
                if st.active and active[:, i].any():
                    tspans.enter_context(self.tracer.request_span(
                        st.rid, "decode", chunk=stats.chunks,
                        steps=int(active[:, i].sum())))
            if self.executor is not None:
                # the layer-streamed loop blocks per layer by design: report
                # its real dispatch and sync counts, not one-per-chunk
                d0, b0 = (self.executor.dispatches,
                          self.executor.blocking_syncs)
                toks, cur, self.cache = self.executor.decode_chunk(
                    jnp.asarray(self._cur_tok), self.cache, sched_t, active,
                    kv_bound=kv_bound, act_bound=act_bound,
                    host_attn=self.host_attn)
                stats.device_calls += self.executor.dispatches - d0
                stats.host_syncs += self.executor.blocking_syncs - b0
            else:
                with trace_ctx(self.plan):
                    toks, cur, self.cache = self._decode_chunk_jit(
                        self.params, jnp.asarray(self._cur_tok), self.cache,
                        jnp.asarray(sched_t), jnp.asarray(active),
                        kv_bound=kv_bound, act_bound=act_bound)
                stats.device_calls += 1
                stats.host_syncs += 1  # the chunk's ONE blocking readback
            # the chunk's spans end at the readback, not at the enqueue
            toks_np = np.asarray(toks, np.int32)
            self._cur_tok = np.array(cur, np.int32)     # writable host copy
        t_read = time.perf_counter()
        with jax.profiler.TraceAnnotation("serve.replay"):
            self._replay_chunk(n_steps, step_idx, out, stats, toks_np,
                               t_read, active, sched_t, kv_run, act_run)

    def _replay_chunk(self, n_steps: int, step_idx: int,
                      out: Dict[int, np.ndarray], stats: ServeStats,
                      toks_np: np.ndarray, t_read: float, active: np.ndarray,
                      sched_t: np.ndarray, kv_run: np.ndarray,
                      act_run: np.ndarray) -> None:
        """The host replay after a chunk's readback (delivered at
        ``t_read``): block accounting, per-step pipeline simulation,
        TTFT/TBT and retirement, then the timeline drain and metric fold."""
        stats.chunks += 1
        # the amortized tax: ONE host dispatch + blocking sync per chunk
        # (per token at chunk_steps=1) — serialized on the critical path, so
        # it lands in sim_time ahead of the chunk's per-step lane totals
        stats.sim_time += self.hw.dispatch_overhead

        # per-step token totals AFTER each step (host replay — no device
        # sync; the mirrors advance exactly like the on-device lengths)
        kv_tok = [int(kv_run[s][active[s]].sum()) for s in range(n_steps)]
        act_tok = [int(act_run[s][active[s]].sum()) for s in range(n_steps)]
        # host_attn: the KV region attends on the cpu lane, so the sim prices
        # those tokens as cpu_host_tokens (three-way pipeline, DESIGN.md §15)
        use_cpu = self.host_attn
        specs = [[MiniBatchSpec(int(active[s].sum()),
                                0 if use_cpu else kv_tok[s], act_tok[s],
                                0, ctx_tokens=int(
                                    (kv_run[s] + act_run[s])[active[s]].mean()),
                                cpu_host_tokens=kv_tok[s] if use_cpu else 0)]
                 for s in range(n_steps)]
        sim_results = simulate_steps(self.cfg, self.hw, specs,
                                     quant=self.quant)

        # sub-chunk bookkeeping: tokens, block replay, TTFT/TBT, retirement.
        # A pool-exhausted raise mid-replay releases every slot (the host
        # mirrors are no longer trustworthy) instead of leaking their blocks.
        try:
            for s in range(n_steps):
                stats.sim_time += sim_results[s].total
                stats.steps += 1
                for i, st in enumerate(self.slots):
                    if not active[s, i]:
                        continue
                    st.generated.append(int(toks_np[i, s]))
                    st.remaining -= 1
                    stats.generated_tokens += 1
                    if sched_t[s, i]:
                        st.act_tokens += 1
                    else:
                        st.kv_tokens += 1
                    kind = BlockType.ACT if sched_t[s, i] else BlockType.KV
                    if self.blockman.append_token(st.rid, kind) is None:
                        # unreachable in normal operation: _relieve_pressure
                        # forecast the chunk's exact block needs pre-dispatch
                        raise CapacityError(
                            f"{kind.value} block pool exhausted at decode "
                            f"step {step_idx + s} of request {st.rid}; the "
                            "precomputed store_act schedule requires "
                            "allocation to succeed",
                            rids=[st.rid], resource=f"{kind.value} blocks",
                            hint="grow the host pools or lower concurrency")
                    if st.rid not in stats.ttft:
                        stats.ttft[st.rid] = stats.sim_time
                        stats.first_token_at[st.rid] = t_read
                        if self.metrics is not None:
                            self.metrics.histogram("ttft_s").observe(
                                t_read - stats.queued_at[st.rid])
                    if st.remaining == 0:
                        out[st.rid] = np.asarray(st.generated, np.int32)
                        stats.tbt[st.rid] = stats.sim_time / max(
                            len(st.generated), 1)
                        stats.completed_at[st.rid] = step_idx + s
                        if self.metrics is not None:
                            # the mean gap between the request's deliveries
                            self.metrics.histogram("tbt_s").observe(
                                (t_read - stats.first_token_at[st.rid])
                                / max(len(st.generated) - 1, 1))
                        self.tracer.request_end(
                            st.rid, "complete", tokens=len(st.generated),
                            step=step_idx + s)
                        self.blockman.free_request(st.rid)
                        # free the slot (cache rows overwritten on admit)
                        self.slots[i] = SlotState()
        except Exception:
            self._release_slots(range(self.n_slots))
            self._release_parked()
            raise

        meas: List = []
        if self.executor is not None:
            # drain completed iteration timelines so the executor's span
            # store stays bounded over a long-lived server
            meas = self.executor.drain_timeline("decode")
            self._measured.extend(meas)
            stats.measured_time += sum(m.total for m in meas)
        if self.metrics is not None:
            fold_timeline_metrics(self.metrics, sim_results, source="sim")
            fold_timeline_metrics(self.metrics, meas, source="measured")
            self.metrics.counter("serve_generated_tokens").inc(
                int(active.sum()))
            self.metrics.counter("serve_chunks").inc()
        if self.controller is not None:
            # per-chunk timeline batch: measured iteration timelines where
            # they exist (offload), the simulated predictions otherwise —
            # the engine's group-granular observe, at chunk granularity
            self.controller.observe(
                meas if meas else sim_results,
                [0] * n_steps if use_cpu else kv_tok, act_tok,
                sim=sim_results,
                cpu_tokens=kv_tok if use_cpu else None)
            self._apply_alloc(self.controller.update())
        elif self.executor is not None:
            # no controller to route through: feed the drift monitor its
            # (measured, predicted) pairs directly
            self.drift.observe_steps(meas, sim_results)

    # ---------------------------------------------------------------- serving
    def run(self, requests: List[Request],
            arrival_steps: Optional[List[int]] = None
            ) -> (Dict[int, np.ndarray], ServeStats):
        """Serve ``requests`` through the slot pool.

        arrival_steps: optional per-request admission step, aligned with
        ``requests`` — request i joins the queue once the iteration index
        reaches ``arrival_steps[i]`` (the soak harness's randomised open-loop
        traffic).  Omitted, every request is queued up front (closed loop).
        """
        stats = ServeStats()
        if arrival_steps is None:
            pending: List = []
            queue = list(requests)
            t = time.perf_counter()
            stats.queued_at.update((r.rid, t) for r in queue)
        else:
            assert len(arrival_steps) == len(requests)
            order = sorted(range(len(requests)),
                           key=lambda i: (arrival_steps[i], i))
            pending = [(int(arrival_steps[i]), requests[i]) for i in order]
            queue = []
        out: Dict[int, np.ndarray] = {}
        step_idx = 0
        while (queue or pending or self.parked
               or any(s.active for s in self.slots)):
            while pending and pending[0][0] <= step_idx:
                r = pending.pop(0)[1]
                stats.queued_at[r.rid] = time.perf_counter()
                queue.append(r)
            # chunk-boundary admission: parked resumes first, then ALL due
            # arrivals that fit, coalesced into one batched prefill dispatch
            assignments = self._plan_admission(queue)
            if assignments:
                self._admit_batch(assignments, stats)
            if not any(s.active for s in self.slots):
                if pending:                  # idle gap before the next arrival
                    step_idx = pending[0][0]
                    continue
                if not (self.parked or queue):
                    break
                # stalled: nothing runs, nothing fits.  Degrade parked ACT
                # holdings (youngest first) to free blocks and retry; a
                # stall that survives every degradation is genuine
                # overcommit — release everything and fail structured
                if self._degrade_parked():
                    continue
                rids = self._release_parked() + [r.rid for r in queue]
                raise CapacityError(
                    "server stalled: no admission fits the free block "
                    "pools even with every parked holding degraded",
                    rids=rids, resource="blocks",
                    hint="grow the host pools or shorten prompts")
            n_steps = min(self.chunk_steps,
                          max(s.remaining for s in self.slots if s.active))
            self._run_chunk(n_steps, step_idx, out, stats)
            step_idx += n_steps
        return out, stats
