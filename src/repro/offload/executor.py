"""Layer-granular offload executor: weight streaming overlapped with KV Gen.

The device-resident engine runs the whole generation as two monolithic jit
dispatches (`M.hybrid_prefill_batched` + `M.hybrid_decode_loop`), which is
the right hot path when all weights fit the device.  When they don't —
HybridServe's actual regime — each layer's weights must cross the host link
every step, and the schedulable units are individual layers.  This executor
is that regime's ground truth: a Python-driven loop at layer granularity
where

  * the ``WeightStreamer`` uploads layer ``l+1``'s shard on the copy
    stream while layer ``l``'s compute (KV Gen from ACT checkpoints fused
    into the hybrid attention step) runs on the main thread,
  * an optionally *spilled* KV region lives in the pinned
    ``HostBlockPool`` between steps: each layer's KV tiles ride the same
    copy stream down, and the new token's K/V row rides the full-duplex
    upstream direction back,
  * every task is timed into a ``MeasuredTimeline`` whose per-step results
    share ``simulate_steps``'s schema — the analytic simulator becomes the
    predictor, this loop the measurement.

Exactness contract: the math per layer is ``M._hybrid_layer_step`` — the
same function the monolithic scan's body calls — with pre/post stages
mirroring ``hybrid_decode_step`` / ``hybrid_prefill_batched`` term for
term, so generated tokens are identical to the device-resident path at any
prefetch depth, with or without spill.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.core.quant import SCALE_FLOOR
from repro.models import layers as nn
from repro.models import model as M
from repro.models import transformer as T
from repro.models.quant_ops import fake_quant
from repro.offload.host_attn import HostAttnExecutor, merge_partials
from repro.offload.host_pool import HostWeightPool, Region, ShardedRegion
from repro.offload.streamer import (ShardedWeightLanes, WeightStreamer,
                                    donate_buffers)
from repro.offload.timeline import HOST, MeasuredTimeline

Cache = Dict[str, Any]


# --- host-side quantized spill format (DESIGN.md §14) ------------------------
# numpy mirror of models.quant_ops: identical op sequence (f32 absmax, scale
# floored then f16-cast BEFORE the codes, round-half-even, clip ±127), so a
# value that went through the device-side fake_quant requantizes here to the
# SAME codes and scales — the spill round trip is bit-exact by construction.

def np_quantize(x: np.ndarray, axis: int = -1):
    amax = np.max(np.abs(x.astype(np.float32)), axis=axis, keepdims=True)
    scale = np.maximum(amax / 127.0, SCALE_FLOOR).astype(np.float16)
    q = np.clip(np.rint(x.astype(np.float32) / scale.astype(np.float32)),
                -127, 127)
    return q.astype(np.int8), scale


def np_dequantize(q: np.ndarray, scale: np.ndarray, dtype=np.float32):
    return (q.astype(np.float32) * scale.astype(np.float32)).astype(dtype)


class QuantSlab:
    """One layer's spilled K or V plane in the pinned arena: an int8 payload
    view plus its f16 scale sidecar (both carved from the same ``Region``).
    ``nbytes`` is what actually crosses the measured lane."""

    __slots__ = ("q", "s")

    def __init__(self, q: np.ndarray, s: np.ndarray):
        self.q, self.s = q, s

    @property
    def nbytes(self) -> int:
        return self.q.nbytes + self.s.nbytes

    @property
    def shape(self):
        return self.q.shape


class OffloadExecutor:
    """Executes hybrid-cache inference with host-streamed layer weights.

    ``plan`` (a ``ShardPlan``, DESIGN.md §11) turns the single weight lane
    into per-mesh-position lanes: each device gets its own host shard,
    staging ring and copy stream (``ShardedWeightLanes``), the resident
    remainder is committed to the mesh, spilled KV regions live in
    per-shard pinned arenas, and every recorded span carries its shard so
    lane timelines aggregate across shards (max — parallel lanes) for the
    controller.  ``plan=None`` (or a 1x1 mesh) is today's executor
    unchanged."""

    def __init__(self, cfg: ModelConfig, params, *, prefetch_depth: int = 1,
                 timeline: Optional[MeasuredTimeline] = None, plan=None,
                 faults=None, watchdog_s: Optional[float] = None,
                 max_copy_retries: int = 2, tracer=None, metrics=None,
                 quant=None):
        assert M.family(cfg) == "uniform", \
            "offload executor drives uniform-family models"
        self.cfg = cfg
        # QuantConfig: cache writes fake-quant on device (token-exact vs the
        # quantized monolithic loop) and the spill arena stores REAL int8
        # payload + f16 scales — lane spans carry the reduced byte counts
        self.quant = quant
        self.is_moe = cfg.is_moe and cfg.moe_every == 1
        self.timeline = timeline if timeline is not None else MeasuredTimeline()
        # obs plumbing (DESIGN.md §13): the tracer rides the shared timeline
        # — every recorded lane span / robustness event mirrors onto the
        # trace's lane tracks — and the registry backs the streamers' fault
        # counters.  Both default off; neither adds dispatches or syncs.
        if tracer is not None and self.timeline.tracer is None:
            self.timeline.tracer = tracer
        self.plan = plan if (plan is not None and plan.mesh.size > 1) else None
        self.faults = faults
        # cpu attention lane (DESIGN.md §15): created lazily on the first
        # host-attend decode; shares the timeline/fault-plan/metrics wiring
        self._watchdog_s = watchdog_s
        self._max_copy_retries = max_copy_retries
        self._metrics = metrics
        self.host_lane: Optional[HostAttnExecutor] = None
        self.pool = HostWeightPool(cfg, params, plan=self.plan)
        if self.plan is not None:
            self.streamer = ShardedWeightLanes(
                self.pool, self.plan, prefetch_depth=prefetch_depth,
                timeline=self.timeline, faults=faults, watchdog_s=watchdog_s,
                max_retries=max_copy_retries, metrics=metrics)
            self.resident = self.plan.place_params(self.pool.resident)
        else:
            self.streamer = WeightStreamer(
                self.pool, prefetch_depth=prefetch_depth,
                timeline=self.timeline, faults=faults, watchdog_s=watchdog_s,
                max_retries=max_copy_retries, metrics=metrics)
            # committed once: a host-built (numpy) remainder would
            # otherwise cross the link on every dispatch
            self.resident = jax.device_put(self.pool.resident)
        self.dispatches = 0                     # jit calls (device round trips)
        # blocking host materialisation points (block_until_ready / D2H
        # reads): the layer-streamed loops block once per layer by
        # design, so consumers reporting sync counts (ServeStats.
        # host_syncs) read this instead of assuming one sync per call
        self.blocking_syncs = 0

        # spilled-KV upload in quant mode: int8 payload + f16 scales cross
        # the (measured) link, dequant runs device-side — the fp cache never
        # rides the lane
        self._dequant_kv = jax.jit(
            lambda q, s: (q.astype(jnp.float32) * s.astype(jnp.float32))
            .astype(jnp.dtype(cfg.dtype)))
        self._pre = jax.jit(self._pre_impl)
        self._layer = jax.jit(self._layer_impl, donate_argnums=(1, 2, 3),
                              static_argnames=("kv_bound", "act_bound"))
        # host-attend stage split (DESIGN.md §15): qk → [host job ‖ device
        # partial] → merge; three dispatches per layer instead of one
        self._ha_qk = jax.jit(self._ha_qk_impl)
        self._ha_dev_partial = jax.jit(self._ha_dev_partial_impl,
                                       donate_argnums=(1,),
                                       static_argnames=("act_bound",))
        self._ha_dev_partial_kv = jax.jit(self._ha_dev_partial_kv_impl,
                                          donate_argnums=(1, 2, 3),
                                          static_argnames=("act_bound",))
        self._ha_merge = jax.jit(self._ha_merge_impl)
        self._post = jax.jit(self._post_impl)
        self._prefill_embed = jax.jit(self._prefill_embed_impl)
        self._prefill_layer = jax.jit(self._prefill_layer_impl,
                                      static_argnames=("kv_cap", "act_cap"))
        self._prefill_post = jax.jit(self._prefill_post_impl,
                                     static_argnames=("kfit", "act_cap"))

    # ========================================================== jitted stages
    # decode pre/post mirror M.hybrid_decode_step outside the layer scan
    def _pre_impl(self, resident, tok, kv_len, act_len, act_pos, store):
        cfg = self.cfg
        B = tok.shape[0]
        ctx = kv_len + act_len
        sincos_new = (T._rope_for(cfg, ctx[:, None])
                      if cfg.pos_type in ("rope",) else None)
        act_pos2 = act_pos.at[jnp.arange(B), act_len].set(
            jnp.where(store, ctx, act_pos[jnp.arange(B), act_len]))
        sincos_act = (T._rope_for(cfg, act_pos2)
                      if cfg.pos_type in ("rope",) else None)
        x = M._embed_tokens(resident, cfg, tok)
        if cfg.pos_type == "learned":
            x = x + jnp.take(resident["pos_embed"], ctx, axis=0)[:, None]
        return x, act_pos2, sincos_new, sincos_act

    def _layer_impl(self, lp, kc, vc, ac, h, kv_len, act_len, store,
                    sincos_new, sincos_act, kv_bound=None, act_bound=None):
        return M._hybrid_layer_step(lp, self.cfg, h, kc, vc, ac, kv_len,
                                    act_len, store, sincos_new, sincos_act,
                                    self.is_moe, kv_bound=kv_bound,
                                    act_bound=act_bound, quant=self.quant)

    # host-attend layer split (DESIGN.md §15).  The three stages partition
    # ``M._hybrid_layer_step`` term for term: the union of the host
    # partition (arena KV rows [0, kv_len)) and the device partition
    # (recomputed ACT region + the new token's own row) is EXACTLY the
    # oracle's valid set, so the merged softmax matches the dense one.
    def _ha_qk_impl(self, lp, h, sincos_new):
        """Stage A: projections for the new token.  Returns the roped query
        (synced host-side to seed the cpu-lane job) plus the exact and
        stored K/V rows both later stages need."""
        cfg = self.cfg
        act_in = h[:, 0]                                 # A^i of new token
        hn = nn.apply_norm(h, lp["ln1"], cfg.norm_type)
        q, k, v = T._qk(lp["attn"], cfg, hn)
        if sincos_new is not None:
            q = nn.apply_rope(q, *sincos_new)
            k = nn.apply_rope(k, *sincos_new)
        dt = jnp.dtype(cfg.dtype)
        if self.quant is not None:
            k_store, v_store = fake_quant(k[:, 0]), fake_quant(v[:, 0])
            act_store = fake_quant(act_in).astype(dt)
        else:
            k_store, v_store = k[:, 0], v[:, 0]
            act_store = act_in.astype(dt)
        return q, k[:, 0], v[:, 0], k_store, v_store, act_store

    def _ha_dev_core(self, lp, ac, act_len, store, sincos_act, q, k0, v0,
                     k_store, v_store, act_store, act_b):
        """Device partial: KV Gen over the ACT prefix (Eq. 7), new-token
        overrides, then partial attention over [ACT region ; own row]."""
        cfg = self.cfg
        B = ac.shape[0]
        arangeB = jnp.arange(B)
        dt = jnp.dtype(cfg.dtype)
        an = nn.apply_norm(ac[:, :act_b], lp["ln1"], cfg.norm_type)
        ka = (an @ lp["attn"]["wk"]).reshape(B, act_b, cfg.num_kv_heads,
                                             cfg.head_dim)
        va = (an @ lp["attn"]["wv"]).reshape(B, act_b, cfg.num_kv_heads,
                                             cfg.head_dim)
        if cfg.qk_norm:
            ka = nn.rms_norm(ka, lp["attn"]["knorm"])
        if sincos_act is not None:
            ka = nn.apply_rope(ka, sincos_act[0][:, :act_b],
                               sincos_act[1][:, :act_b])
        # the token's OWN k/v used for this step's attention stay exact
        ka = ka.at[arangeB, act_len].set(
            jnp.where(store[:, None, None], k0, ka[arangeB, act_len]))
        va = va.at[arangeB, act_len].set(
            jnp.where(store[:, None, None], v0, va[arangeB, act_len]))
        ac2 = ac.at[arangeB, act_len].set(
            jnp.where(store[:, None], act_store, ac[arangeB, act_len]))
        # own row joins the device partition with the oracle's kv validity
        k_dev = jnp.concatenate([ka.astype(dt), k_store[:, None].astype(dt)],
                                axis=1)
        v_dev = jnp.concatenate([va.astype(dt), v_store[:, None].astype(dt)],
                                axis=1)
        act_valid = jnp.arange(act_b)[None, :] < (act_len + store)[:, None]
        valid = jnp.concatenate([act_valid, (~store)[:, None]], axis=1)
        o, m, l = T._partial_masked_attn(q, k_dev, v_dev, valid)
        return o, m, l, ac2

    def _ha_dev_partial_impl(self, lp, ac, act_len, store, sincos_act, q,
                             k0, v0, k_store, v_store, act_store,
                             act_bound=None):
        """Stage B, spill flavour: the host arena owns the KV region, so no
        device KV write happens at all (the row store-back is host-side)."""
        S_act = ac.shape[1]
        act_b = S_act if act_bound is None else min(int(act_bound), S_act)
        return self._ha_dev_core(lp, ac, act_len, store, sincos_act, q, k0,
                                 v0, k_store, v_store, act_store, act_b)

    def _ha_dev_partial_kv_impl(self, lp, kc, vc, ac, kv_len, act_len, store,
                                sincos_act, q, k0, v0, k_store, v_store,
                                act_store, act_bound=None):
        """Stage B, stacked-cache flavour (chunked scheduler): the device
        cache stays source of truth, so the new row IS written device-side
        exactly as ``_hybrid_layer_step`` writes it."""
        B = ac.shape[0]
        arangeB = jnp.arange(B)
        S_act = ac.shape[1]
        act_b = S_act if act_bound is None else min(int(act_bound), S_act)
        o, m, l, ac2 = self._ha_dev_core(lp, ac, act_len, store, sincos_act,
                                         q, k0, v0, k_store, v_store,
                                         act_store, act_b)
        kc2 = kc.at[arangeB, kv_len].set(
            jnp.where(store[:, None, None], kc[arangeB, kv_len], k_store))
        vc2 = vc.at[arangeB, kv_len].set(
            jnp.where(store[:, None, None], vc[arangeB, kv_len], v_store))
        return o, m, l, kc2, vc2, ac2

    def _ha_merge_impl(self, lp, h, o_d, m_d, l_d, o_h, m_h, l_h):
        """Stage C: fold the host partial into the device partial, project,
        FFN — the tail of ``_hybrid_layer_step`` after its attention."""
        cfg = self.cfg
        B = h.shape[0]
        o, _, _ = merge_partials(o_d, m_d, l_d, o_h, m_h, l_h, xp=jnp)
        o = o.reshape(B, 1, cfg.num_heads, cfg.head_dim).astype(h.dtype)
        h = h + o.reshape(B, 1, cfg.q_dim) @ lp["attn"]["wo"]
        if cfg.d_ff > 0:
            hf = nn.apply_norm(h, lp["ln2"], cfg.norm_type)
            f, _ = T.ffn_apply(lp["ffn"], cfg, hf, self.is_moe)
            h = h + f
        return h

    def _post_impl(self, resident, h, prev, kv_len, act_len, store, active):
        """active: (B,) bool — inactive slots keep their carried token and
        frozen lengths (the chunked scheduler retires slots mid-chunk; the
        full-loop callers pass all-true)."""
        cfg = self.cfg
        x = nn.apply_norm(h, resident["final_norm"], cfg.norm_type)
        logits = M.unembed(resident, cfg, x)
        nxt = jnp.where(active,
                        jnp.argmax(logits[:, -1], -1).astype(jnp.int32), prev)
        return logits, nxt, (kv_len + ((~store) & active).astype(jnp.int32),
                             act_len + (store & active).astype(jnp.int32))

    # prefill stages mirror M.hybrid_prefill_batched around the layer scan
    def _prefill_embed_impl(self, resident, tokens):
        x, positions = M.embed_input(resident, self.cfg,
                                     {"tokens": tokens})
        return x, T._rope_for(self.cfg, positions)

    def _prefill_layer_impl(self, lp, x, sincos, kv_keep, kv_cap, act_cap):
        cfg = self.cfg
        B, S = x.shape[0], x.shape[1]
        dt = jnp.dtype(cfg.dtype)
        act_in = x                                       # A^i — the checkpoint
        h, (k, v), _ = T.layer_full(lp, cfg, x, sincos, kind="attn",
                                    is_moe=self.is_moe, want_cache=True,
                                    q_chunk=M.Q_CHUNK, k_chunk=M.K_CHUNK)
        if self.quant is not None:    # stored regions only; h stays exact
            k, v, act_in = fake_quant(k), fake_quant(v), fake_quant(act_in)
        kfit = min(S, kv_cap)
        kc = lax.dynamic_update_slice_in_dim(
            jnp.zeros((B, kv_cap, cfg.num_kv_heads, cfg.head_dim), dt),
            k[:, :kfit].astype(dt), 0, axis=1)
        vc = lax.dynamic_update_slice_in_dim(
            jnp.zeros((B, kv_cap, cfg.num_kv_heads, cfg.head_dim), dt),
            v[:, :kfit].astype(dt), 0, axis=1)
        act_idx = jnp.clip(kv_keep[:, None] +
                           jnp.arange(act_cap, dtype=jnp.int32)[None], 0, S - 1)
        ac = jnp.take_along_axis(act_in, act_idx[:, :, None], axis=1).astype(dt)
        return h, kc, vc, ac

    def _prefill_post_impl(self, resident, h, kv_keep, last_pos, kfit, act_cap):
        cfg = self.cfg
        B = h.shape[0]
        h = nn.apply_norm(h, resident["final_norm"], cfg.norm_type)
        logits = M.unembed(resident, cfg,
                           h[jnp.arange(B), last_pos - 1][:, None])
        cur = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        act_pos = kv_keep[:, None] + jnp.arange(act_cap, dtype=jnp.int32)[None]
        kv_len = jnp.minimum(kv_keep, kfit).astype(jnp.int32)
        act_len = jnp.minimum(last_pos - kv_keep, act_cap).astype(jnp.int32)
        return cur, act_pos, kv_len, act_len

    # ================================================================ prefill
    def prefill_batched(self, tokens, kv_keep, last_pos, *, kv_cap: int,
                        act_cap: int) -> Tuple[jax.Array, Cache]:
        """Layer-streamed batched hybrid prefill.

        Same contract as ``M.hybrid_prefill_batched`` (the engine validates
        capacities loudly before calling), but the layer loop runs host-side
        with weights arriving over the copy stream — the full parameter set
        is never device-resident.  Returns ``(first_token, cache)`` with the
        per-layer pools as *lists* (the executor's native layout;
        ``stack_cache`` converts when a monolithic consumer needs it).
        """
        cfg = self.cfg
        tokens = jnp.asarray(tokens)
        kv_keep = jnp.asarray(kv_keep, jnp.int32)
        last_pos = jnp.asarray(last_pos, jnp.int32)
        S = int(tokens.shape[1])
        self.timeline.begin_step("prefill")
        x, sincos = self._prefill_embed(self.resident, tokens)
        self.dispatches += 1
        ks: List[jax.Array] = []
        vs: List[jax.Array] = []
        acs: List[jax.Array] = []
        self.streamer.begin(range(cfg.num_layers))
        for l in range(cfg.num_layers):
            lp = self.streamer.acquire(l)
            with self.timeline.task("gpu", "fwd"):
                x, kc, vc, ac = self._prefill_layer(
                    lp, x, sincos, kv_keep, kv_cap=kv_cap, act_cap=act_cap)
                jax.block_until_ready(x)
            self.blocking_syncs += 1
            self.dispatches += 1
            self.streamer.release(l)
            ks.append(kc); vs.append(vc); acs.append(ac)
        cur, act_pos, kv_len, act_len = self._prefill_post(
            self.resident, x, kv_keep, last_pos, kfit=min(S, kv_cap),
            act_cap=act_cap)
        self.dispatches += 1
        self.timeline.end_step()
        cache: Cache = {"k": ks, "v": vs, "act": acs, "act_pos": act_pos,
                        "kv_len": kv_len, "act_len": act_len}
        return cur, cache

    # ================================================================= decode
    def _unstack(self, cache: Cache):
        def split(v):
            return list(v) if isinstance(v, list) else \
                [v[l] for l in range(self.cfg.num_layers)]
        return split(cache["k"]), split(cache["v"]), split(cache["act"])

    def _kv_layer_sharding(self, shape):
        """NamedSharding of one layer's (B, kv_cap, KVH, D) KV slice under
        the plan (the stacked cache spec with the layer dim dropped)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = self.plan.cache_spec("k", (1,) + tuple(shape))
        return NamedSharding(self.plan.mesh, P(*tuple(spec)[1:]))

    def _kv_upload(self, hk_l, hv_l):
        """Spilled-KV region load for one layer.  Runs on the caller thread:
        ``jax.device_put`` is a synchronous GIL-holding copy on this backend
        (DESIGN.md §8.4), so routing it through the copy stream would
        serialise against compute rather than overlap — the lane time is
        recorded either way and the simulator's pcie lane stays the
        predictor for it.

        Per-shard lanes (plan): ``hk_l``/``hv_l`` are per-lane head-slice
        views; the put lands sharded on the mesh and the wall window is
        recorded once per lane with that lane's bytes — N physical lanes
        moving 1/N each in parallel.

        Quantized spill (``self.quant``): the slabs hold int8 payload + f16
        scales; those REDUCED bytes are what the lane moves and what the
        span records.  Single-lane mode uploads the quantized planes and
        dequantizes device-side (one extra fused dispatch per plane) — the
        fp cache never rides the lane.  Per-shard lanes dequantize in the
        host view before the sharded put (mesh placement of the scale
        sidecar is not worth the complexity at smoke scale) but still
        record the quantized transfer bytes."""
        t0 = time.perf_counter()
        if isinstance(hk_l, list):              # per-shard lanes
            if self.quant is not None:
                dt = np.dtype(self.cfg.dtype)
                full_k = np.concatenate(
                    [np_dequantize(s.q, s.s, dt) for s in hk_l], axis=2)
                full_v = np.concatenate(
                    [np_dequantize(s.q, s.s, dt) for s in hv_l], axis=2)
            else:
                full_k = np.concatenate(hk_l, axis=2)
                full_v = np.concatenate(hv_l, axis=2)
            sh = self._kv_layer_sharding(full_k.shape)
            kc = jax.device_put(full_k, sh)
            vc = jax.device_put(full_v, sh)
            jax.block_until_ready((kc, vc))
            self.blocking_syncs += 1
            t1 = time.perf_counter()
            for s, (k_s, v_s) in enumerate(zip(hk_l, hv_l)):
                self.timeline.record("pcie", "kv", t0, t1,
                                     k_s.nbytes + v_s.nbytes, shard=s)
            return kc, vc
        if self.quant is not None:
            kc = self._dequant_kv(jax.device_put(hk_l.q),
                                  jax.device_put(hk_l.s))
            vc = self._dequant_kv(jax.device_put(hv_l.q),
                                  jax.device_put(hv_l.s))
            jax.block_until_ready((kc, vc))
            self.blocking_syncs += 1
            self.dispatches += 2
            self.timeline.record("pcie", "kv", t0, time.perf_counter(),
                                 hk_l.nbytes + hv_l.nbytes)
            return kc, vc
        if self.plan is not None:
            # single arena (cache dims indivisible) but mesh execution: the
            # put must still land ON the mesh, or the layer jit would mix
            # mesh-committed and device-0-committed operands
            sh = self._kv_layer_sharding(hk_l.shape)
            kc = jax.device_put(hk_l, sh)
            vc = jax.device_put(hv_l, sh)
        else:
            kc = jax.device_put(hk_l)
            vc = jax.device_put(hv_l)
        jax.block_until_ready((kc, vc))
        self.blocking_syncs += 1
        self.timeline.record("pcie", "kv", t0, time.perf_counter(),
                             hk_l.nbytes + hv_l.nbytes)
        return kc, vc

    def _kv_store_back(self, kc2, vc2, hk_l, hv_l, kv_idx: np.ndarray,
                       store_np: np.ndarray) -> None:
        """Write the new token's K/V row back into the spilled host region
        (the paper's per-step store traffic, upstream lane).  Per-shard
        lanes write their own head slice of the row."""
        t0 = time.perf_counter()
        lanes = isinstance(hk_l, list)
        hk0 = hk_l[0] if lanes else hk_l
        B = kv_idx.shape[0]
        cap = hk0.shape[1]
        gather = jnp.asarray(np.minimum(kv_idx, cap - 1))
        rows_k = np.asarray(kc2[jnp.arange(B), gather])
        rows_v = np.asarray(vc2[jnp.arange(B), gather])
        nbytes = self._rows_store_back(rows_k, rows_v, hk_l, hv_l, kv_idx,
                                       store_np)
        t1 = time.perf_counter()
        if lanes:
            n = len(hk_l)
            for s in range(n):
                self.timeline.record("pcie_up", "st", t0, t1, nbytes // n,
                                     shard=s)
        else:
            self.timeline.record("pcie_up", "st", t0, t1, nbytes)

    def _ha_store_back(self, k_store, v_store, hk_l, hv_l,
                       kv_idx: np.ndarray, store_np: np.ndarray) -> None:
        """Host-attend flavour of the row store-back: the KV region never
        came up, so the new row rides D2H straight from the qk stage's
        store values (same upstream lane, same quant round trip)."""
        t0 = time.perf_counter()
        rows_k = np.asarray(k_store)
        rows_v = np.asarray(v_store)
        self.blocking_syncs += 1
        nbytes = self._rows_store_back(rows_k, rows_v, hk_l, hv_l, kv_idx,
                                       store_np)
        t1 = time.perf_counter()
        if isinstance(hk_l, list):
            n = len(hk_l)
            for s in range(n):
                self.timeline.record("pcie_up", "st", t0, t1, nbytes // n,
                                     shard=s)
        else:
            self.timeline.record("pcie_up", "st", t0, t1, nbytes)

    def _rows_store_back(self, rows_k, rows_v, hk_l, hv_l,
                         kv_idx: np.ndarray, store_np: np.ndarray) -> int:
        """Shared row-write loop: place each KV-bound request's new K/V row
        (host-side (B, KVH, D) values) into its arena slot; returns the
        bytes written."""
        lanes = isinstance(hk_l, list)
        hk0 = hk_l[0] if lanes else hk_l
        B = kv_idx.shape[0]
        cap = hk0.shape[1]
        if self.quant is not None:
            # device rows are fake-quant values: requantizing reproduces the
            # exact codes/scales the device dequantized from (lossless)
            qk, sk = np_quantize(rows_k)
            qv, sv = np_quantize(rows_v)
        nbytes = 0
        n = len(hk_l) if lanes else 1
        kvh_s = rows_k.shape[1] // n
        for b in range(B):
            if not store_np[b]:                 # KV-bound token: row is new
                row = min(kv_idx[b], cap - 1)
                if self.quant is not None:
                    if lanes:
                        for s in range(n):
                            hs = slice(s * kvh_s, (s + 1) * kvh_s)
                            hk_l[s].q[b, row] = qk[b, hs]
                            hk_l[s].s[b, row] = sk[b, hs]
                            hv_l[s].q[b, row] = qv[b, hs]
                            hv_l[s].s[b, row] = sv[b, hs]
                    else:
                        hk_l.q[b, row] = qk[b]
                        hk_l.s[b, row] = sk[b]
                        hv_l.q[b, row] = qv[b]
                        hv_l.s[b, row] = sv[b]
                    nbytes += (qk[b].nbytes + sk[b].nbytes
                               + qv[b].nbytes + sv[b].nbytes)
                elif lanes:
                    for s in range(n):
                        hk_l[s][b, row] = rows_k[b, s * kvh_s:(s + 1) * kvh_s]
                        hv_l[s][b, row] = rows_v[b, s * kvh_s:(s + 1) * kvh_s]
                    nbytes += rows_k[b].nbytes + rows_v[b].nbytes
                else:
                    hk_l[b, row] = rows_k[b]
                    hv_l[b, row] = rows_v[b]
                    nbytes += rows_k[b].nbytes + rows_v[b].nbytes
        return nbytes

    def _spill_out(self, ks, vs, region, kv_len):
        """Move the whole KV region device→host into the pinned arena(s).

        Single arena: per-layer views of one contiguous region.  Per-shard
        arenas (``ShardedRegion``): each model-axis lane's arena receives
        that lane's head slice; ``hk[l]``/``hv[l]`` become per-lane view
        lists and the store spans carry per-shard byte counts.

        Quantized spill (``self.quant``): the region is carved into int8
        payload planes + f16 scale sidecars (``Region.views``) and each
        layer is host-quantized on the way down — the arena holds and the
        upstream span counts the REDUCED bytes.  Device values are already
        fake-quant, so this quantization is lossless (codes round-trip)."""
        cfg = self.cfg
        Lc = cfg.num_layers
        B, kv_cap = ks[0].shape[0], ks[0].shape[1]
        t0 = time.perf_counter()
        if isinstance(region, ShardedRegion):
            n = region.n_lanes
            kvh_s = cfg.num_kv_heads // n
            if self.quant is not None:
                psh = (Lc, B, kv_cap, kvh_s, cfg.head_dim)
                ssh = (Lc, B, kv_cap, kvh_s, 1)
                lanes = [region.lane_views(
                    s, [(psh, np.int8), (ssh, np.float16),
                        (psh, np.int8), (ssh, np.float16)])
                    for s in range(n)]
                hk = [[QuantSlab(lanes[s][0][l], lanes[s][1][l])
                       for s in range(n)] for l in range(Lc)]
                hv = [[QuantSlab(lanes[s][2][l], lanes[s][3][l])
                       for s in range(n)] for l in range(Lc)]
                nbytes = 0
                for l in range(Lc):
                    kq, ksc = np_quantize(np.asarray(ks[l]))
                    vq, vsc = np_quantize(np.asarray(vs[l]))
                    for s in range(n):
                        hs = slice(s * kvh_s, (s + 1) * kvh_s)
                        hk[l][s].q[...] = kq[:, :, hs]
                        hk[l][s].s[...] = ksc[:, :, hs]
                        hv[l][s].q[...] = vq[:, :, hs]
                        hv[l][s].s[...] = vsc[:, :, hs]
                    nbytes += (kq.nbytes + ksc.nbytes
                               + vq.nbytes + vsc.nbytes)
                    donate_buffers((ks[l], vs[l]))
            else:
                views = [region.lane_view(
                    s, (2, Lc, B, kv_cap, kvh_s, cfg.head_dim),
                    np.dtype(cfg.dtype)) for s in range(n)]
                hk = [[views[s][0][l] for s in range(n)] for l in range(Lc)]
                hv = [[views[s][1][l] for s in range(n)] for l in range(Lc)]
                nbytes = 0
                for l in range(Lc):
                    k_np, v_np = np.asarray(ks[l]), np.asarray(vs[l])
                    for s in range(n):
                        hk[l][s][...] = k_np[:, :, s * kvh_s:(s + 1) * kvh_s]
                        hv[l][s][...] = v_np[:, :, s * kvh_s:(s + 1) * kvh_s]
                    nbytes += k_np.nbytes + v_np.nbytes
                    donate_buffers((ks[l], vs[l]))   # device copies now stale
            t1 = time.perf_counter()
            for s in range(n):
                self.timeline.record("pcie_up", "st", t0, t1, nbytes // n,
                                     shard=s)
            return hk, hv, np.asarray(kv_len).copy()
        if self.quant is not None:
            psh = (Lc, B, kv_cap, cfg.num_kv_heads, cfg.head_dim)
            ssh = (Lc, B, kv_cap, cfg.num_kv_heads, 1)
            kqv, ksv, vqv, vsv = region.views(
                [(psh, np.int8), (ssh, np.float16),
                 (psh, np.int8), (ssh, np.float16)])
            hk = [QuantSlab(kqv[l], ksv[l]) for l in range(Lc)]
            hv = [QuantSlab(vqv[l], vsv[l]) for l in range(Lc)]
            nbytes = 0
            for l in range(Lc):
                hk[l].q[...], hk[l].s[...] = np_quantize(np.asarray(ks[l]))
                hv[l].q[...], hv[l].s[...] = np_quantize(np.asarray(vs[l]))
                nbytes += hk[l].nbytes + hv[l].nbytes
                donate_buffers((ks[l], vs[l]))       # device copies now stale
            self.timeline.record("pcie_up", "st", t0, time.perf_counter(),
                                 nbytes)
            return hk, hv, np.asarray(kv_len).copy()
        arr = region.view((2, Lc, B, kv_cap, cfg.num_kv_heads, cfg.head_dim),
                          np.dtype(cfg.dtype))
        hk, hv = arr[0], arr[1]
        nbytes = 0
        for l in range(Lc):
            hk[l][...] = np.asarray(ks[l])
            hv[l][...] = np.asarray(vs[l])
            nbytes += hk[l].nbytes + hv[l].nbytes
            donate_buffers((ks[l], vs[l]))       # device copies are now stale
        self.timeline.record("pcie_up", "st", t0, time.perf_counter(), nbytes)
        return hk, hv, np.asarray(kv_len).copy()

    # ------------------------------------------------- host-attend layer path
    def _ensure_host_lane(self) -> HostAttnExecutor:
        """Create (once) and re-arm the cpu attention lane, sharing the
        executor's timeline, fault plan, watchdog and metrics wiring."""
        if self.host_lane is None:
            self.host_lane = HostAttnExecutor(
                timeline=self.timeline, faults=self.faults,
                watchdog_s=self._watchdog_s,
                max_retries=self._max_copy_retries, metrics=self._metrics,
                cache_dtype=np.dtype(self.cfg.dtype))
        self.host_lane.begin()
        return self.host_lane

    def _q_host(self, q) -> np.ndarray:
        """Sync the roped query host-side, grouped per KV head —
        (B, 1, H, D) → (B, KVH, G, D), the cpu lane's layout."""
        cfg = self.cfg
        q_np = np.asarray(q)[:, 0]
        B = q_np.shape[0]
        return q_np.reshape(B, cfg.num_kv_heads,
                            cfg.num_heads // cfg.num_kv_heads, cfg.head_dim)

    def _ha_layer_spill(self, lane, lp, h, ac, hk_l, hv_l, kv_len_np,
                        act_len, store, sn, sa, store_np):
        """One host-attend layer against the spilled arena: the KV region
        never crosses the link — only the query (D2H), the merged softmax
        statistics (H2D) and the new row's store-back (D2H) do."""
        tl = self.timeline
        with tl.task("gpu", "fwd"):
            q, k0, v0, k_store, v_store, act_store = self._ha_qk(lp, h, sn)
            q_np = self._q_host(q)
        self.blocking_syncs += 1
        self.dispatches += 1
        job = lane.submit(q_np, hk_l, hv_l, kv_len_np)
        with tl.task("gpu", "fwd"):     # device partial overlaps the cpu job
            o_d, m_d, l_d, ac2 = self._ha_dev_partial(
                lp, ac, act_len, store, sa, q, k0, v0, k_store, v_store,
                act_store)
            jax.block_until_ready(o_d)
        self.blocking_syncs += 1
        self.dispatches += 1
        o_h, m_h, l_h = lane.collect(job)
        with tl.task("gpu", "fwd"):
            h = self._ha_merge(lp, h, o_d, m_d, l_d, jnp.asarray(o_h),
                               jnp.asarray(m_h), jnp.asarray(l_h))
            jax.block_until_ready(h)
        self.blocking_syncs += 1
        self.dispatches += 1
        self._ha_store_back(k_store, v_store, hk_l, hv_l, kv_len_np,
                            store_np)
        return h, ac2

    def _ha_layer_kv(self, lane, lp, h, kc, vc, ac, hk_np, hv_np, kv_len_np,
                     kv_len, act_len, store, sn, sa, act_bound):
        """One host-attend layer over a stacked device cache (chunked
        scheduler): the cpu lane attends over the chunk's host MIRROR of
        the KV region while the device cache stays source of truth."""
        tl = self.timeline
        with tl.task("gpu", "fwd"):
            q, k0, v0, k_store, v_store, act_store = self._ha_qk(lp, h, sn)
            q_np = self._q_host(q)
        self.blocking_syncs += 1
        self.dispatches += 1
        job = lane.submit(q_np, hk_np, hv_np, kv_len_np)
        with tl.task("gpu", "fwd"):     # device partial overlaps the cpu job
            o_d, m_d, l_d, kc2, vc2, ac2 = self._ha_dev_partial_kv(
                lp, kc, vc, ac, kv_len, act_len, store, sa, q, k0, v0,
                k_store, v_store, act_store, act_bound=act_bound)
            jax.block_until_ready(o_d)
        self.blocking_syncs += 1
        self.dispatches += 1
        o_h, m_h, l_h = lane.collect(job)
        with tl.task("gpu", "fwd"):
            h = self._ha_merge(lp, h, o_d, m_d, l_d, jnp.asarray(o_h),
                               jnp.asarray(m_h), jnp.asarray(l_h))
            jax.block_until_ready(h)
        self.blocking_syncs += 1
        self.dispatches += 1
        rows_k = np.asarray(k_store)
        rows_v = np.asarray(v_store)
        self.blocking_syncs += 1
        return h, kc2, vc2, ac2, rows_k, rows_v

    def _mirror_append(self, hk_np, hv_np, rows_k, rows_v,
                       kv_idx: np.ndarray, store_np: np.ndarray) -> None:
        """Append each KV-bound request's new row to the chunk's host
        mirror — the same write condition ``_hybrid_layer_step`` applies to
        the device region, so mirror and cache stay in lockstep."""
        t0 = time.perf_counter()
        cap = hk_np.shape[1]
        nbytes = 0
        for b in range(rows_k.shape[0]):
            if not store_np[b]:
                row = min(kv_idx[b], cap - 1)
                hk_np[b, row] = rows_k[b]
                hv_np[b, row] = rows_v[b]
                nbytes += rows_k[b].nbytes + rows_v[b].nbytes
        self.timeline.record("pcie_up", "st", t0, time.perf_counter(),
                             nbytes)

    def decode_loop(self, cur, cache: Cache, store_sched, *,
                    spill_region: Optional[Region] = None,
                    host_attn: bool = False
                    ) -> Tuple[np.ndarray, Cache]:
        """Layer-streamed greedy generation, token-exact vs
        ``M.hybrid_decode_loop``.

        cur:          (B,) int32 — first token to emit.
        store_sched:  (n_steps, B) bool — per-step store_act flags (same
                      orientation the monolithic loop scans over).
        spill_region: when given, the KV region lives in this pinned host
                      region between steps — every layer's tiles are
                      re-uploaded per step and the new token's row is stored
                      back (real PCIe-style traffic on the reduced configs).
        host_attn:    spill mode only — instead of re-uploading the KV
                      region every step, the cpu lane attends over it in
                      place (DESIGN.md §15): only softmax statistics and
                      the new row cross the link.

        The cache is donated: its per-layer pools are updated in place or
        freed (spill mode).  Returns ``(tokens (B, n_steps), final cache)``.
        """
        cfg = self.cfg
        Lc = cfg.num_layers
        sched = np.asarray(store_sched, bool)
        n_steps = int(sched.shape[0])
        B = int(cur.shape[0])
        ks, vs, acs = self._unstack(cache)
        kv_len, act_len = cache["kv_len"], cache["act_len"]
        act_pos = cache["act_pos"]
        spill = spill_region is not None
        assert not host_attn or spill, "host_attn requires a spilled KV region"
        lane = self._ensure_host_lane() if host_attn else None
        hk = hv = kv_len_np = None
        if spill:
            hk, hv, kv_len_np = self._spill_out(ks, vs, spill_region, kv_len)
            ks = vs = None
        toks: List[np.ndarray] = []
        self.streamer.begin([l for _ in range(n_steps) for l in range(Lc)])
        seq = 0
        for s in range(n_steps):
            self.timeline.begin_step("decode")
            store = jnp.asarray(sched[s])
            x, act_pos, sn, sa = self._pre(self.resident, cur[:, None],
                                           kv_len, act_len, act_pos, store)
            self.dispatches += 1
            for l in range(Lc):
                lp = self.streamer.acquire(seq)
                if host_attn:
                    x, acs[l] = self._ha_layer_spill(
                        lane, lp, x, acs[l], hk[l], hv[l], kv_len_np,
                        act_len, store, sn, sa, sched[s])
                    self.streamer.release(seq)
                    seq += 1
                    continue
                if spill:
                    kc, vc = self._kv_upload(hk[l], hv[l])
                else:
                    kc, vc = ks[l], vs[l]
                with self.timeline.task("gpu", "fwd"):
                    x, kc2, vc2, ac2 = self._layer(lp, kc, vc, acs[l], x,
                                                   kv_len, act_len, store,
                                                   sn, sa)
                    jax.block_until_ready(x)
                self.blocking_syncs += 1
                self.dispatches += 1
                self.streamer.release(seq)
                seq += 1
                acs[l] = ac2
                if spill:
                    self._kv_store_back(kc2, vc2, hk[l], hv[l], kv_len_np,
                                        sched[s])
                    donate_buffers((kc2, vc2))   # stale: host copy is truth
                else:
                    ks[l], vs[l] = kc2, vc2
            toks.append(np.asarray(cur, np.int32))
            self.blocking_syncs += 1
            _, cur, (kv_len, act_len) = self._post(
                self.resident, x, cur, kv_len, act_len, store,
                jnp.ones((B,), bool))
            self.dispatches += 1
            if spill:
                kv_len_np = kv_len_np + (~sched[s]).astype(kv_len_np.dtype)
            self.timeline.end_step()
        out = (np.stack(toks, axis=1) if toks
               else np.zeros((B, 0), np.int32))
        final: Cache = {"k": ks, "v": vs, "act": acs, "act_pos": act_pos,
                        "kv_len": kv_len, "act_len": act_len,
                        "spilled": spill}
        return out, final

    def decode_step(self, tok, cache: Cache, store) -> Tuple[jax.Array, Cache]:
        """One layer-streamed decode iteration over a *stacked* hybrid cache
        (drop-in for the continuous-batching scheduler's jitted
        ``hybrid_decode_step`` call; no spill — slots churn too fast for
        group-scoped host regions).

        Known cost vs the jitted monolith it replaces: the stacked layout is
        unstacked into per-layer slices on entry and restacked on exit (the
        scheduler's admission path writes slot rows into stacked arrays), so
        each iteration copies the cache instead of donating it in place —
        acceptable at slot-pool smoke scale; keeping the scheduler cache
        per-layer end-to-end would remove both copies."""
        cfg = self.cfg
        Lc = cfg.num_layers
        ks, vs, acs = self._unstack(cache)
        kv_len, act_len = cache["kv_len"], cache["act_len"]
        store = jnp.asarray(store)
        self.timeline.begin_step("decode")
        x, act_pos, sn, sa = self._pre(self.resident, tok, kv_len,
                                       act_len, cache["act_pos"], store)
        self.dispatches += 1
        self.streamer.begin(range(Lc))
        for l in range(Lc):
            lp = self.streamer.acquire(l)
            with self.timeline.task("gpu", "fwd"):
                x, ks[l], vs[l], acs[l] = self._layer(
                    lp, ks[l], vs[l], acs[l], x, kv_len, act_len, store,
                    sn, sa)
                jax.block_until_ready(x)
            self.blocking_syncs += 1
            self.dispatches += 1
            self.streamer.release(l)
        logits, _, (kv_len2, act_len2) = self._post(
            self.resident, x, tok[:, 0], kv_len, act_len, store,
            jnp.ones((tok.shape[0],), bool))
        self.dispatches += 1
        self.timeline.end_step()
        new_cache = dict(cache)
        new_cache.update(k=jnp.stack(ks, 0), v=jnp.stack(vs, 0),
                         act=jnp.stack(acs, 0), act_pos=act_pos,
                         kv_len=kv_len2, act_len=act_len2)
        return logits, new_cache

    def decode_chunk(self, cur, cache: Cache, store_sched, active_sched, *,
                     kv_bound: Optional[int] = None,
                     act_bound: Optional[int] = None,
                     host_attn: bool = False
                     ) -> Tuple[np.ndarray, np.ndarray, Cache]:
        """Chunked layer-streamed decode over a *stacked* hybrid cache (the
        continuous-batching scheduler's offload hot path, DESIGN.md §10).

        Versus calling ``decode_step`` once per token, the chunk amortizes
        the per-iteration fixed costs the way the monolithic scan does for
        the device-resident path: the cache is unstacked ONCE and restacked
        ONCE per chunk (not per token), and the weight streamer's prefetch
        window is opened over the whole chunk's layer sequence, so the copy
        stream rolls straight from step s's last layers into step s+1's
        first layers instead of restarting cold every token.

        cur:          (B,) int32 — next token each slot would emit.
        store_sched:  (n_steps, B) bool store_act flags.
        active_sched: (n_steps, B) bool — inactive slots keep their carried
                      token and frozen lengths and emit -1 (the scheduler's
                      masking contract; matches ``M.hybrid_decode_chunk``).
        kv_bound / act_bound: static region-occupancy bounds (see
                      ``M._hybrid_layer_step``).
        host_attn:    run each layer's KV-region attention on the cpu lane
                      over a per-chunk host mirror of the (bounded) region
                      (DESIGN.md §15).  The device cache stays source of
                      truth — admission, demotion and non-host-attend
                      chunks read it unchanged.
        -> (tokens (B, n_steps) int32, next cur (B,), final stacked cache).

        The compute thread's time is tiled by spans: ``host``/``unstack``,
        then per step ``host``/``pre``, per layer the streamer's
        ``host``/``w_wait`` and ``pcie``/``w`` hand-off and the ``gpu``/
        ``fwd`` forward, then ``host``/``post`` (ending at the readback of
        the step's next tokens), and last ``host``/``restack``.
        """
        cfg = self.cfg
        Lc = cfg.num_layers
        tl = self.timeline
        sched = np.asarray(store_sched, bool)
        act_np = np.asarray(active_sched, bool)
        sched = sched & act_np
        n_steps = int(sched.shape[0])
        B = int(cur.shape[0])
        # ONE prefetch window across the whole chunk's layer sequence,
        # opened first so layer 0's staging runs under the unstack
        self.streamer.begin([l for _ in range(n_steps) for l in range(Lc)])
        with tl.task(HOST, "unstack"):
            ks, vs, acs = self._unstack(cache)
        kv_len, act_len = cache["kv_len"], cache["act_len"]
        act_pos = cache["act_pos"]
        cur = jnp.asarray(cur, jnp.int32)
        cur_np = np.asarray(cur, np.int32)
        lane = hk_np = hv_np = kv_len_np = None
        if host_attn:
            # per-chunk host mirror of the KV region: ONE bulk D2H pull
            # replaces per-step re-uploads; rows appended during the chunk
            # keep it in lockstep with the device writes.  kv_bound covers
            # max(len) + steps_in_dispatch by the scheduler's contract, so
            # appended rows always fit the mirror.
            lane = self._ensure_host_lane()
            S_kv = ks[0].shape[1]
            kv_b = S_kv if kv_bound is None else min(int(kv_bound), S_kv)
            self.timeline.begin_step("mirror")
            t0 = time.perf_counter()
            hk_np = [np.array(ks[l][:, :kv_b]) for l in range(Lc)]
            hv_np = [np.array(vs[l][:, :kv_b]) for l in range(Lc)]
            self.blocking_syncs += 1
            nbytes = sum(a.nbytes for a in hk_np) + \
                sum(a.nbytes for a in hv_np)
            self.timeline.record("pcie", "kv", t0, time.perf_counter(),
                                 nbytes)
            self.timeline.end_step()
            kv_len_np = np.asarray(cache["kv_len"]).copy()
        toks: List[np.ndarray] = []
        seq = 0
        for s in range(n_steps):
            tl.begin_step("decode")
            with tl.task(HOST, "pre"):
                store = jnp.asarray(sched[s])
                active = jnp.asarray(act_np[s])
                x, act_pos, sn, sa = self._pre(self.resident, cur[:, None],
                                               kv_len, act_len, act_pos,
                                               store)
            self.dispatches += 1
            for l in range(Lc):
                lp = self.streamer.acquire(seq)
                if host_attn:
                    x, ks[l], vs[l], acs[l], rk, rv = self._ha_layer_kv(
                        lane, lp, x, ks[l], vs[l], acs[l], hk_np[l],
                        hv_np[l], kv_len_np, kv_len, act_len, store, sn,
                        sa, act_bound)
                    self._mirror_append(hk_np[l], hv_np[l], rk, rv,
                                        kv_len_np, sched[s])
                    self.streamer.release(seq)
                    seq += 1
                    continue
                with tl.task("gpu", "fwd"):
                    x, ks[l], vs[l], acs[l] = self._layer(
                        lp, ks[l], vs[l], acs[l], x, kv_len, act_len, store,
                        sn, sa, kv_bound=kv_bound, act_bound=act_bound)
                    jax.block_until_ready(x)
                self.blocking_syncs += 1
                self.dispatches += 1
                self.streamer.release(seq)
                seq += 1
            with tl.task(HOST, "post"):
                toks.append(np.where(act_np[s], cur_np, -1))
                _, cur, (kv_len, act_len) = self._post(
                    self.resident, x, cur, kv_len, act_len, store, active)
                cur_np = np.asarray(cur, np.int32)
            self.blocking_syncs += 1
            self.dispatches += 1
            if host_attn:
                kv_len_np = kv_len_np + ((~sched[s]) & act_np[s]).astype(
                    kv_len_np.dtype)
            tl.end_step()
        out = (np.stack(toks, axis=1).astype(np.int32) if toks
               else np.zeros((B, 0), np.int32))
        final: Cache = dict(cache)
        with tl.task(HOST, "restack"):
            final.update(k=jnp.stack(ks, 0), v=jnp.stack(vs, 0),
                         act=jnp.stack(acs, 0), act_pos=act_pos,
                         kv_len=kv_len, act_len=act_len)
        return out, cur_np, final

    # ================================================================== misc
    def drain_timeline(self, tag: Optional[str] = "decode"):
        """Collect-and-reset the measured per-step ``TimelineResult``s (the
        controller-consumable surface: each result carries per-tag lane
        seconds in ``tag_busy`` next to the traffic bytes, so a consumer can
        regress (tokens, seconds) per lane without touching spans).  Note
        the measured GPU spans fuse KV Gen into the layer forward ("fwd"
        tag); ``HybridCacheController.observe`` attributes the gen share
        from the simulated prediction (DESIGN.md §9)."""
        return self.timeline.drain(tag)

    def close(self) -> None:
        """Deterministic teardown: joins the copy-stream thread(s) and the
        cpu attention lane's worker.  Also the context-manager exit, so
        engine teardown can't leak threads."""
        self.streamer.close()
        if self.host_lane is not None:
            self.host_lane.close()

    def __enter__(self) -> "OffloadExecutor":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    @property
    def lane_health(self) -> str:
        """"healthy" | "degraded" — the weight lane(s)' current state."""
        return self.streamer.lane_health

    @property
    def fault_counters(self) -> Dict[str, int]:
        """Cumulative robustness counters from the weight lane(s)."""
        return self.streamer.fault_counters

    @property
    def host_fault_counters(self) -> Dict[str, int]:
        """Cumulative robustness counters from the cpu attention lane
        (all-zero until the first host-attend decode creates it)."""
        if self.host_lane is None:
            from repro.offload.streamer import FAULT_COUNTER_KEYS
            return {k: 0 for k in FAULT_COUNTER_KEYS}
        return self.host_lane.fault_counters


def stack_cache(cache: Cache) -> Cache:
    """Executor-native (per-layer lists) → monolithic stacked layout."""
    out = dict(cache)
    for key in ("k", "v", "act"):
        if isinstance(cache.get(key), list):
            out[key] = jnp.stack(cache[key], 0)
    return out
