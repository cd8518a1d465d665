"""HybridServe engine: end-to-end serving with the KV/ACT hybrid cache.

Executable engine (CPU, reduced configs): real prompts in, real tokens out,
with the paper's policy stack driving representation choices:

  1. Algorithm 1 fixes the host ACT:KV ratio for the model + hardware.
  2. Each request's prompt is split KV-prefix / ACT-suffix at that ratio
     (Eq. 11); generated tokens keep the running ratio via the precomputed
     store_act_schedule (next_block_kind unrolled host-side, DESIGN.md §5).
  3. Mini-batches are formed by the F_b bin packer; each jit group runs ONE
     batched hybrid prefill + ONE lax.scan decode loop (KV Gen fused into
     the step, greedy sampling on-device, cache buffers donated).
  4. The BlockManager accounts physical blocks on both tiers; the pipeline
     simulator reports what the schedule would cost on the target hardware.

Baselines: mode="kv" (FlexGen-style full-KV decode) and mode="act"
(HybridServe-Act-Cache) run the same engine with the ratio pinned.

Two executors share the policy stack (DESIGN.md §5 vs §8): the default
device-resident hot path (one batched prefill + one lax.scan decode per jit
group), and the ``offload=True`` host-offload runtime, which streams layer
weights from pinned host pools, spills KV regions when the config-driven
budget demands, and reports MEASURED lane timelines next to the simulated
predictions — token-exact against each other.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.configs.offload import OffloadBudget, offload_budget
from repro.core import (BLOCK_TOKENS, BlockManager, BlockType,
                        ControllerConfig, HostAllocation,
                        HybridCacheController, Location, RequestBlocks,
                        device_act_blocks, form_minibatches,
                        host_block_allocation, profile_cost_fns,
                        store_act_schedule)
from repro.core import costmodel as cm
from repro.core.pipeline import MiniBatchSpec, TimelineResult, simulate_steps
from repro.data.pipeline import Request
from repro.models import model as M
from repro.obs import (DriftMonitor, NULL_TRACER, ScalarStatsView,
                       fold_timeline_metrics,
                       register_busy_fraction_collector)
from repro.serving.recovery import CapacityError
from repro.serving.util import bucket, pack_group, trace_ctx
from repro.sharding import ShardPlan


class GenStats(ScalarStatsView):
    """Per-call generation stats.  Same attribute surface as the original
    dataclass; constructed with a ``MetricsRegistry`` the scalar fields
    become live views over ``gen_*`` counters (DESIGN.md §13) — each view
    reads zero at construction while the registry keeps engine-lifetime
    totals — and without one they are plain attributes, as before."""

    _FIELDS = {
        "generated_tokens": 0,
        "steps": 0,
        "sim_time": 0.0,
        "sim_gpu_busy": 0.0,
        "device_calls": 0,     # jit dispatches (host<->device round trips)
        # measured (offload runtime ground truth; zero device-resident)
        "measured_time": 0.0,
        "measured_gpu_busy": 0.0,
        "measured_cpu_busy": 0.0,    # cpu attention lane (DESIGN.md §15)
    }

    def __init__(self, registry=None):
        super().__init__(registry, prefix="gen")
        self.traffic: Dict[str, float] = {}

    @property
    def sim_throughput(self) -> float:
        return self.generated_tokens / self.sim_time if self.sim_time else 0.0

    @property
    def sim_gpu_util(self) -> float:
        return self.sim_gpu_busy / self.sim_time if self.sim_time else 0.0

    @property
    def measured_gpu_util(self) -> float:
        return (self.measured_gpu_busy / self.measured_time
                if self.measured_time else 0.0)


class HybridServeEngine:
    def __init__(self, cfg: ModelConfig, params, *,
                 hw: Optional[cm.HardwareSpec] = None,
                 mode: str = "hybrid", max_minibatch: int = 4,
                 kv_cap: int = 512, act_cap: int = 512, seed: int = 0,
                 generalized: bool = False, offload: bool = False,
                 budget: Optional[OffloadBudget] = None,
                 adaptive: bool = False,
                 faults=None, watchdog_s: Optional[float] = None,
                 ctl: Optional[ControllerConfig] = None,
                 plan: Optional[ShardPlan] = None,
                 tracer=None, metrics=None, quant=None,
                 host_attn: bool = False):
        """hw: the machine the policy stack plans for; default
        ``costmodel.local_hardware()`` — the TPU this process runs on, or
        the v5e prior on any other backend.

        generalized=True uses the byte-ratio-aware Algorithm-1 variant
        (DESIGN.md §7) — recommended for GQA models; False reproduces the
        paper's policy exactly.

        adaptive=True closes the measurement->policy loop (DESIGN.md §9):
        between jit groups the ``HybridCacheController`` refits the cost
        model from the group's lane timelines (measured under offload, else
        the simulated predictions) and re-balances the host ACT:KV split by
        bounded role retags of the BlockManager's free capacity.  Purely
        host-side on already-materialised results — the decode hot path
        gains no device syncs.  Tokens stay exact at any ratio.

        offload=True runs the host-offload runtime (DESIGN.md §8): layer
        weights stream from pinned host pools through the double-buffered
        copy stream, and KV regions spill to the host arena whenever the
        config-driven ``budget`` can't hold the group's KV blocks
        device-side.  Tokens are identical to the device-resident path;
        stats additionally carry measured lane times (``measured_time`` /
        ``measured_gpu_busy``) next to the simulated predictions.

        plan=... runs the whole hot path tensor-parallel under the given
        ``ShardPlan`` (DESIGN.md §11): weights are committed to the mesh
        under the serve TP specs, caches carry the plan's KV-head/d_model
        shardings through both jitted dispatches (greedy argmax included —
        the logits reduction lowers to one on-device collective, no new
        host syncs), and the whole policy stack prices the AGGREGATE
        machine (``costmodel.scale_for_shards``: per-shard PCIe bandwidth x
        shard count, device memory x shard count).  ``plan=None`` (or a
        1x1 mesh) is bit-for-bit today's single-device engine.

        quant=... stores both cache regions block-quantized (DESIGN.md §14):
        the hot path fake-quantizes every cache write (numerically identical
        to int8 residency + dequant-on-load), while the BlockManager, spill
        arena, cost model, and simulator all price the REAL quantized bytes
        — so lane slopes drop and Algorithm 1 re-balances.  ``quant=None``
        (default) is bit-identical to the unquantized engine.

        host_attn=True (offload only) enables the cpu attention lane
        (DESIGN.md §15): groups that physically spill run their KV-region
        attention ON THE HOST over the pinned arena — only softmax
        statistics and the new row cross the link — overlapped with the
        device partial on a dedicated worker thread.  Spilled blocks gain
        the BlockManager's ``host_attend`` residency tag, the simulator
        prices the third lane, and an adaptive controller arbitrates
        three ways {device KV, ACT regenerate, CPU attend}.  Tokens stay
        exact; ``host_attn=False`` is bit-identical to the PR 8 engine."""
        assert mode in ("hybrid", "kv", "act")
        assert M.family(cfg) == "uniform", "engine drives uniform-family models"
        assert not host_attn or offload, \
            "host_attn rides the offload runtime's spill arena"
        self.host_attn = bool(host_attn)
        self.plan = plan
        self.quant = quant
        shards = plan.shard_factor if plan is not None else 1
        hw = cm.scale_for_shards(
            hw if hw is not None else cm.local_hardware(), shards)
        self.cfg, self.params, self.hw, self.mode = cfg, params, hw, mode
        self.max_minibatch = max_minibatch
        self.kv_cap, self.act_cap = kv_cap, act_cap
        self.rng = np.random.default_rng(seed)
        self.offload = offload
        self.budget = (budget if budget is not None
                       else offload_budget(cfg, hw))

        # observability (DESIGN.md §13) — all host-side, zero dispatches:
        # the tracer records request/lane lifecycle (NULL_TRACER = off, the
        # default), the registry absorbs the scattered counters, and the
        # drift monitor accumulates sim-vs-measured lane residuals
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics
        self.drift = DriftMonitor(registry=metrics)
        if metrics is not None:
            register_busy_fraction_collector(metrics)
            metrics.register_collector(self._collect_metrics)

        self.fits = profile_cost_fns(cfg, hw, quant=quant)
        self.alloc = host_block_allocation(
            cfg, hw, device_act_blocks(cfg, hw, quant=quant),
            generalized=generalized, quant=quant)
        if mode == "kv":
            self.alloc = dataclasses.replace(self.alloc, act_blocks=0, kv_blocks=max(
                self.alloc.kv_blocks, 1))
        elif mode == "act":
            self.alloc = dataclasses.replace(self.alloc, kv_blocks=0, act_blocks=max(
                self.alloc.act_blocks, 1))
        self.act_frac = self.alloc.act_fraction

        self.controller: Optional[HybridCacheController] = None
        self._last_obs = None
        if adaptive:
            assert mode == "hybrid", "adaptive controller re-balances the " \
                "hybrid split; kv/act baselines pin the ratio"
            self.controller = HybridCacheController(
                cfg, hw, self.alloc, device_act_blocks(cfg, hw, quant=quant),
                fits=self.fits, generalized=generalized,
                ctl=ctl if ctl is not None else ControllerConfig(),
                drift=self.drift, quant=quant, cpu=host_attn)

        # device KV pool: generous when device-resident; budget-derived under
        # offload so tight (reduced) budgets force real spill to the host arena
        dev_kv = self.budget.dev_kv_blocks(cfg) if offload else 64
        self.blockman = BlockManager(
            cfg,
            host_kv_blocks=max(self.alloc.kv_blocks, 1),
            host_act_blocks=max(self.alloc.act_blocks, 1),
            dev_kv_blocks=dev_kv,
            dev_act_blocks=device_act_blocks(cfg, hw, quant=quant),
            shard_factor=shards, quant=quant)

        self.executor = None
        self.measured_steps: List[TimelineResult] = []
        # robustness (DESIGN.md §12): deterministic fault injection + lane
        # watchdog forwarded to the offload runtime; arena denials (real or
        # injected) degrade to device-resident serving instead of raising
        self.faults = faults
        self.arena_denials = 0
        if offload:
            from repro.offload import OffloadExecutor, make_spill_pool
            self.executor = OffloadExecutor(
                cfg, params, prefetch_depth=self.budget.prefetch_depth,
                plan=plan, faults=faults, watchdog_s=watchdog_s,
                tracer=tracer, metrics=metrics, quant=quant)
            self.spill_kv_pool = make_spill_pool(
                cfg, max_requests=max_minibatch, kv_cap=kv_cap,
                shards=shards, quant=quant)
            # the executor owns host shards of the layer weights + the small
            # resident tree; the engine must not pin the caller's full
            # device-resident parameter set for its lifetime (the monolithic
            # jit wrappers below are the device-resident path's, not ours)
            self.params = None
        else:
            if plan is not None:
                # weights committed to the mesh under the serve TP specs;
                # the jitted dispatches below inherit the placement and the
                # cache constraints keep SPMD propagation honest
                self.params = plan.place_params(params)
            self._prefill_batch_jit = functools.partial(
                jax.jit, static_argnames=("kv_cap", "act_cap"))(
                    self._prefill_batch_impl)
            # cache pools are donated: each scan iteration updates the KV/ACT
            # buffers in place instead of copying the full pools
            self._decode_loop_jit = jax.jit(self._decode_loop_impl,
                                            donate_argnums=(2,))

    def close(self) -> None:
        """Shut down the offload executor's copy-stream thread and staging
        buffers (no-op for the device-resident engine).  Long-lived
        processes that build engines repeatedly should call this — each
        offload executor owns a worker thread and layer-shard-sized
        staging slots."""
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
    def _prefill_batch_impl(self, params, tokens, kv_keep, last_pos, kv_cap,
                            act_cap):
        lg, cache = M.hybrid_prefill_batched(
            params, self.cfg, {"tokens": tokens}, kv_cap=kv_cap,
            act_cap=act_cap, kv_keep=kv_keep, last_pos=last_pos,
            quant=self.quant)
        if self.plan is not None:
            cache = self.plan.constrain_cache(cache)
        # fold the greedy sample of the prefill logits into the same dispatch
        # (under a plan the argmax reduces sharded logits with one on-device
        # collective — the token, not the logits, crosses back to the host)
        return jnp.argmax(lg[:, -1], -1).astype(jnp.int32), cache

    def _decode_loop_impl(self, params, cur, cache, store_sched):
        if self.plan is not None:
            cache = self.plan.constrain_cache(cache)
        toks, cache = M.hybrid_decode_loop(params, self.cfg, cur, cache,
                                           store_sched, quant=self.quant)
        if self.plan is not None:
            cache = self.plan.constrain_cache(cache)
        return toks, cache

    # --- public API ----------------------------------------------------------
    def plan_groups(self, requests: List[Request]) -> List[List[Request]]:
        """Deterministic jit-group plan for a request batch: Eq. 11 request
        split + F_b mini-batch packing over block counts, chunked to the
        engine's jit width.  Each group costs exactly TWO device dispatches
        (batched prefill + scan decode loop); tests and benchmarks use this
        to predict dispatch counts independently of the measured stats."""
        reqs_blocks = []
        for r in requests:
            blocks = (len(r.prompt) + r.max_new_tokens + BLOCK_TOKENS - 1) // BLOCK_TOKENS
            n_act = int(round(blocks * self.act_frac))
            reqs_blocks.append(RequestBlocks(r.rid, n_act, blocks - n_act))
        mbs = form_minibatches(
            reqs_blocks, *self.fits,
            act_max=max(self.max_minibatch * (self.act_cap // BLOCK_TOKENS), 1),
            kv_max=max(self.max_minibatch * (self.kv_cap // BLOCK_TOKENS), 1))
        by_rid = {r.rid: r for r in requests}
        groups: List[List[Request]] = []
        for mb in mbs:
            batch_reqs = [by_rid[rb.rid] for rb in mb.requests]
            # chunk the packed mini-batch to the engine's jit width
            for i in range(0, len(batch_reqs), self.max_minibatch):
                groups.append(batch_reqs[i: i + self.max_minibatch])
        return groups

    def snapshot(self) -> Dict[str, object]:
        """One-call observability read (DESIGN.md §13): the metrics
        registry's snapshot — collectors run, so occupancy / busy-fraction /
        drift gauges are freshly derived — plus the drift monitor's full
        summary.  Works without a registry too (drift summary only)."""
        out: Dict[str, object] = (self.metrics.snapshot()
                                  if self.metrics is not None else {})
        out["predictor_drift"] = self.drift.summary()
        return out

    def _collect_metrics(self, reg) -> None:
        """Pull-style collector: occupancy-by-tag, retags, and controller
        state read at snapshot() time, never maintained on the hot path."""
        for (kind, loc), pool in self.blockman.pools.items():
            labels = dict(kind=kind.value, tier=loc.value)
            reg.gauge("blocks_capacity", **labels).set(pool.capacity)
            reg.gauge("blocks_allocated", **labels).set(pool.allocated)
        for (loc, src, dst), n in self.blockman.retags.items():
            reg.counter("retagged_blocks", tier=loc.value, src=src.value,
                        dst=dst.value).set(n)
        reg.counter("arena_denials").set(self.arena_denials)
        reg.gauge("act_fraction").set(self.act_frac)
        if self.controller is not None:
            reg.gauge("controller_updates").set(self.controller.updates)
            reg.gauge("controller_migrated_blocks").set(
                self.controller.migrated_blocks)
            reg.gauge("controller_faulted_skipped").set(
                self.controller.faulted_skipped)

    def generate(self, requests: List[Request]) -> Tuple[Dict[int, np.ndarray], GenStats]:
        stats = GenStats(self.metrics)
        outputs: Dict[int, np.ndarray] = {}
        for group in self.plan_groups(requests):
            out, st = self._run_group(group)
            self._controller_step()
            outputs.update(out)
            stats.generated_tokens += st.generated_tokens
            stats.steps += st.steps
            stats.sim_time += st.sim_time
            stats.sim_gpu_busy += st.sim_gpu_busy
            stats.device_calls += st.device_calls
            stats.measured_time += st.measured_time
            stats.measured_gpu_busy += st.measured_gpu_busy
            stats.measured_cpu_busy += st.measured_cpu_busy
            for k, v in st.traffic.items():
                stats.traffic[k] = stats.traffic.get(k, 0.0) + v
        return outputs, stats

    # --- adaptive controller hook (between jit groups) ------------------------
    def _controller_step(self) -> None:
        """Feed the last group's lane timelines to the controller and apply
        its bounded re-balance.  Runs between jit groups on host-side data
        that the stats path already materialised — no device syncs."""
        if self.controller is None or self._last_obs is None:
            return
        results, sim, kv_tok, act_tok, cpu_tok = self._last_obs
        self._last_obs = None
        self.controller.observe(results, kv_tok, act_tok, sim=sim,
                                cpu_tokens=cpu_tok)
        self._apply_alloc(self.controller.update())

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

    # --- one jit-width group of requests -------------------------------------
    def _run_group(self, group: List[Request]) -> Tuple[Dict[int, np.ndarray], GenStats]:
        """Device-resident hot path: ONE batched prefill dispatch + ONE
        lax.scan decode dispatch for the whole group's generation.

        The per-token Python of the seed engine (a jit call, two host<->device
        syncs and a cost-model invocation per generated token) is replaced by
        (1) the precomputed store_act schedule (policy.store_act_schedule),
        (2) an on-device greedy scan over it (M.hybrid_decode_loop, cache
        donated so the pools update in place), and (3) a post-hoc replay of
        the schedule through the BlockManager plus one vectorized
        simulate_steps call — identical accounting and identical tokens, with
        host<->device round trips per group dropping from O(max_new) to 2.
        """
        cfg = self.cfg
        stats = GenStats()
        B = len(group)
        for r in group:
            self.tracer.request_begin(r.rid, prompt_tokens=len(r.prompt),
                                      max_new=r.max_new_tokens)
        # batched prefill: pad every request to the group bucket (causality
        # keeps positions < pb identical to the per-request prefill); the
        # shared packer fails loudly on region overflow
        toks, kv_keep, pbs = pack_group(group, self.act_frac, self.kv_cap,
                                        self.act_cap, mode=self.mode)
        with self.tracer.server_span("prefill", batch=B):
            if self.executor is not None:
                # layer-streamed prefill: weights arrive over the copy
                # stream, the full parameter set is never device-resident
                d0 = self.executor.dispatches
                cur, cache = self.executor.prefill_batched(
                    toks, kv_keep, np.asarray(pbs, np.int32),
                    kv_cap=self.kv_cap, act_cap=self.act_cap)
                stats.device_calls += self.executor.dispatches - d0
            else:
                with trace_ctx(self.plan):
                    cur, cache = self._prefill_batch_jit(
                        self.params, jnp.asarray(toks), jnp.asarray(kv_keep),
                        jnp.asarray(np.asarray(pbs, np.int32)),
                        kv_cap=self.kv_cap, act_cap=self.act_cap)
                stats.device_calls += 1

        # all block accounting under try/finally: a fail-loud raise below must
        # not leak the group's rids/blocks and poison the engine for retries
        # (free_request is a no-op for already-freed or unregistered rids)
        region = None
        try:
            for i, r in enumerate(group):
                self.blockman.new_request(r.rid)
                for t in range(pbs[i]):
                    kind = BlockType.KV if t < kv_keep[i] else BlockType.ACT
                    if self.blockman.append_token(r.rid, kind) is None:
                        raise CapacityError(
                            f"{kind.value} block pool exhausted during "
                            f"prefill of request {r.rid}",
                            rids=[rr.rid for rr in group],
                            resource=f"{kind.value} blocks",
                            hint="grow the host pools or shrink the group")

            # precomputed store schedule -> one on-device scan for all tokens
            max_new = max(r.max_new_tokens for r in group)
            act0 = np.asarray(pbs) - kv_keep
            sched = store_act_schedule(self.alloc, act0, kv_keep, max_new)

            measured: List[TimelineResult] = []
            # offload: decide residency for the group's KV blocks up front.
            # If the device pool (sized by the config-driven budget) can hold
            # the group's final KV block count, migrate prefill blocks to
            # DEVICE; otherwise the region physically spills to the pinned
            # host arena and every block stays HOST.
            spilled = False
            if self.executor is not None and max_new:
                from repro.offload import kv_region_blocks
                kv_end = kv_keep + (~sched).sum(1)
                need = int(np.sum(-(-kv_end // BLOCK_TOKENS)))
                free = self.blockman.pools[
                    (BlockType.KV, Location.DEVICE)].free_blocks
                spilled = need > free
                if spilled:
                    # deterministic fault site "arena": an injected deny
                    # models transient host-arena exhaustion; a real None
                    # from the pool is the same condition for real
                    deny = (self.faults is not None and
                            self.faults.draw("arena", kinds=("deny",))
                            is not None)
                    region = None if deny else self.spill_kv_pool.alloc(
                        kv_region_blocks(B, self.kv_cap))
                    if region is None:
                        # degraded mode: serve the group device-resident
                        # (best-effort block migration; tokens are exact
                        # either way) instead of failing the requests —
                        # surfaced to the controller via the timeline event
                        spilled = False
                        self.arena_denials += 1
                        self.executor.timeline.record_event("arena_denied")
                if not spilled:
                    for r in group:
                        self.blockman.migrate(r.rid, BlockType.KV,
                                              Location.DEVICE)

            # cpu lane engages only for groups that physically spilled: the
            # arena KV blocks are attended in place (host_attend residency
            # tag) instead of riding PCIe back up every step
            use_cpu = self.host_attn and region is not None
            if use_cpu:
                for r in group:
                    self.blockman.tag_host_attend(r.rid, True)

            if max_new:
                with self.tracer.server_span("decode", batch=B,
                                             steps=max_new):
                    if self.executor is not None:
                        d0 = self.executor.dispatches
                        gen, _ = self.executor.decode_loop(
                            cur, cache, sched.T, spill_region=region,
                            host_attn=use_cpu)
                        stats.device_calls += self.executor.dispatches - d0
                        measured = self.executor.drain_timeline("decode")
                        self.measured_steps += measured
                        stats.measured_time += sum(m.total for m in measured)
                        stats.measured_gpu_busy += sum(m.gpu_busy
                                                       for m in measured)
                        stats.measured_cpu_busy += sum(m.cpu_busy
                                                       for m in measured)
                    else:
                        with trace_ctx(self.plan):
                            gen_dev, _ = self._decode_loop_jit(
                                self.params, cur, cache,
                                jnp.asarray(sched.T))
                        gen = np.asarray(gen_dev, np.int32)
                        stats.device_calls += 1
            else:
                gen = np.zeros((B, 0), np.int32)
            stats.steps += max_new
            # outputs are trimmed to each request's own budget below, so the
            # stat must count the same thing: sum(max_new_tokens), NOT
            # B * max_new (which credits sim_throughput for padded steps of
            # shorter requests in a heterogeneous group)
            stats.generated_tokens += sum(r.max_new_tokens for r in group)

            # replay the schedule through the BlockManager (same accounting
            # the per-token loop performed, now off the device hot path).
            # The schedule assumes allocation never fails; if a pool empties
            # the decisions would silently diverge from a count-driven loop,
            # so fail loudly instead.
            for step in range(max_new):
                for bi, r in enumerate(group):
                    kind = BlockType.ACT if sched[bi, step] else BlockType.KV
                    blk = self.blockman.append_token(r.rid, kind)
                    if blk is None:
                        raise CapacityError(
                            f"{kind.value} block pool exhausted at decode "
                            f"step {step} of request {r.rid}; the precomputed "
                            "store_act schedule requires allocation to succeed",
                            rids=[rr.rid for rr in group],
                            resource=f"{kind.value} blocks",
                            hint="grow the host pools or shrink the group")
                    if (self.executor is not None and not spilled
                            and kind == BlockType.KV
                            and blk.location == Location.HOST):
                        # device-resident group: keep appended KV on device
                        self.blockman.move_block(
                            r.rid, self.blockman.tables[r.rid].index(blk),
                            Location.DEVICE)

            # cost of every step on the target hardware (vectorized reporting)
            steps_ahead = np.arange(1, max_new + 1)
            kv_tok = int(kv_keep.sum()) + np.cumsum((~sched).sum(0))
            act_tok = int(act0.sum()) + np.cumsum(sched.sum(0))
            # host-attended groups move their KV tokens off the pcie lane
            # and onto the cpu lane — the simulator prices the same
            # placement the executor ran
            specs = [[MiniBatchSpec(
                B, 0 if use_cpu else int(kv_tok[s]), int(act_tok[s]), 0,
                ctx_tokens=int(np.mean(np.asarray(pbs) + steps_ahead[s])),
                cpu_host_tokens=int(kv_tok[s]) if use_cpu else 0)]
                for s in range(max_new)]
            sim_results = simulate_steps(cfg, self.hw, specs,
                                         quant=self.quant)
            for res in sim_results:
                stats.sim_time += res.total
                stats.sim_gpu_busy += res.gpu_busy
                for k, v in res.traffic.items():
                    stats.traffic[k] = stats.traffic.get(k, 0.0) + v
            if self.metrics is not None:
                fold_timeline_metrics(self.metrics, sim_results,
                                      source="sim")
                fold_timeline_metrics(self.metrics, measured,
                                      source="measured")
            if self.controller is not None:
                # controller food: measured lane times where they exist
                # (offload runtime), the simulated prediction otherwise,
                # with the schedule's per-step host token counts.  A
                # host-attended group's KV tokens fed the cpu lane, not the
                # pcie lane — route the counts to the lane they exercised
                self._last_obs = (measured if self.executor is not None
                                  else sim_results, sim_results,
                                  [0] * max_new if use_cpu
                                  else kv_tok.tolist(), act_tok.tolist(),
                                  kv_tok.tolist() if use_cpu else None)
            elif self.executor is not None:
                # no controller to route through: feed the drift monitor
                # its (measured, predicted) pairs directly
                self.drift.observe_steps(measured, sim_results)

            out = {}
            for bi, r in enumerate(group):
                out[r.rid] = gen[bi, : r.max_new_tokens]
                self.tracer.request_end(
                    r.rid, "complete", tokens=int(len(out[r.rid])))
            return out, stats
        except BaseException:
            for r in group:
                self.tracer.request_end(r.rid, "fail")
            raise
        finally:
            if region is not None:
                region.free()               # staging arena is reused per group
            for r in group:
                self.blockman.free_request(r.rid)


def exact_reference_generate(cfg, params, requests: List[Request]) -> Dict[int, np.ndarray]:
    """Oracle: plain full-KV incremental decode, one request at a time.

    Uses the same scan-based device-resident loop as the engine (M.decode_loop)
    so the oracle is a single decode dispatch per request rather than one per
    token; the prefill cache is donated into the loop.  The params are a jit
    argument, never a captured constant, so they keep whatever placement the
    caller committed (one device, or a mesh)."""
    out = {}
    prefill = functools.partial(jax.jit, static_argnames=("max_len",))(
        lambda p, toks, max_len: M.prefill(p, cfg, {"tokens": toks},
                                           max_len=max_len))
    loop = functools.partial(jax.jit, static_argnames=("n_steps",),
                             donate_argnums=(2,))(
        lambda p, cur, cache, n_steps: M.decode_loop(p, cfg, cur, cache,
                                                     n_steps))
    for r in requests:
        plen = len(r.prompt)
        pb = bucket(plen)
        toks = np.zeros((1, pb), np.int32)
        toks[0, :plen] = r.prompt
        toks[0, plen:] = r.prompt[-1]
        lg, cache = prefill(params, jnp.asarray(toks),
                            max_len=pb + r.max_new_tokens + 8)
        cur = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)
        gen, _ = loop(params, cur, cache, n_steps=r.max_new_tokens)
        out[r.rid] = np.asarray(gen, np.int32)[0]
    return out
