"""Hybrid paged decode attention — the paper's kernel contribution, TPU-native.

HybridServe extends vLLM's PagedAttention CUDA kernel to attend over "diverse
KV buffer types" (KV pages + recomputed-from-ACT pages).  The TPU adaptation
goes one step further than the paper (DESIGN.md §7): the ACT->KV projection
(Eq. 7) is FUSED into the attention kernel, so a 16-token activation page is
read into VMEM once, normed + projected on the MXU, and consumed by the
online-softmax accumulator without a round trip of the recomputed K/V through
HBM.  On a GPU the paper runs KV-Gen as a separate GEMM; on TPU the fusion
removes 2 * T * kv_dim bytes of HBM traffic per page.

Page-blocked grid (DESIGN.md §7.4): page tables are COMPACTED before launch —
``argsort(page_type == 2)`` moves every used page of a request to the front,
and the per-request used-page count rides the scalar-prefetch channel.  The
grid is (B, PB, KVH) with the KV-head dimension innermost so that

  * iterations past a request's used-page count skip all compute AND clamp
    EVERY coordinate of their block index maps (page -> physical 0, head
    -> 0) — after compaction the dead tail is contiguous, so from the second
    dead iteration on no index changes and Pallas elides the copies (at most
    one page-0 DMA per operand per request is wasted), and
  * an ACT page is loaded + normed ONCE per (request, page) into VMEM scratch
    and re-projected per KV head from there, instead of re-loading and
    re-norming it KVH times as the (B, KVH, MAXP) grid did.

A static ``pages_bound`` (the scheduler knows the longest request's page
count) shrinks the grid itself below MAXP.

Trade-off of the h-innermost order: the per-head wk/wv slices (d_model, D)
re-stream once per LIVE page instead of once per head, while ACT pages
(T, d_model) stream once per page instead of once per head.  That wins for
ACT-heavy tables with few KV heads (GQA) and for every dead iteration; for
MHA models with many KV heads over KV-heavy tables the weight restreaming
dominates and the (B, KVH, pages) order is preferable — keeping the per-head
weights resident in VMEM via manual DMA would remove the trade-off entirely
and is listed as future work (DESIGN.md §7.5).

Layout:
  q            (B, KVH, G, D)    one query token per request (GQA grouped)
  k/v_pages    (P_kv, T, KVH, D) physical KV page pools (post-positional)
  act_pages    (P_act, T, d_model) physical ACT page pool (raw residuals)
  page_table   (B, MAXP) int32   physical index into the type's pool
  page_type    (B, MAXP) int32   0 = KV page, 1 = ACT page, 2 = empty
  page_ntok    (B, MAXP) int32   valid tokens in page
Positions are assumed already applied to q and k_pages (learned-positional
models — OPT — need nothing for ACT pages; RoPE models use the ops.py XLA
path, see DESIGN.md §7.5).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

PAGE = 16
NEG_INF = -1e30


def _head_column(scales, h):
    """Column ``h`` of a (T, KVH) scale tile as (T, 1) float32 (a lane
    select: a width-1 block over the head axis is not a legal TPU tile)."""
    s = scales.astype(jnp.float32)
    col = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.sum(jnp.where(col == h, s, 0.0), axis=1, keepdims=True)


def _hybrid_attn_kernel(
        # scalar prefetch
        page_table, page_type, page_ntok, n_used,
        # inputs (+3 scale refs between wv_ref and o_ref when quantized)
        q_ref, k_ref, v_ref, act_ref, scale_ref, wk_ref, wv_ref,
        # outputs / scratch
        *rest,
        norm_type: str, eps: float, sm_scale: float, quantized: bool,
        return_lse: bool):
    if quantized:
        ks_ref, vs_ref, as_ref, *rest = rest
    else:
        ks_ref = vs_ref = as_ref = None
    if return_lse:
        o_ref, m_ref, l_ref, acc, m_s, l_s, a_norm = rest
    else:
        m_ref = l_ref = None
        o_ref, acc, m_s, l_s, a_norm = rest
    b = pl.program_id(0)
    p = pl.program_id(1)
    h = pl.program_id(2)
    n_pages = pl.num_programs(1)

    @pl.when((p == 0) & (h == 0))
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    ptype = page_type[b, p]
    ntok = page_ntok[b, p]
    live = p < n_used[b]

    # --- ACT norm hoist: once per (request, page), NOT once per KV head -----
    @pl.when(live & (ptype == 1) & (h == 0))
    def _norm_act():
        a = act_ref[0].astype(jnp.float32)               # (T, d_model)
        if quantized:
            # int8 ACT page dequant rides the once-per-page hoist: the page
            # is widened to fp32 in VMEM only, never materialized in HBM
            a = a * as_ref[0].astype(jnp.float32)        # (T, 1) per-token
        s = scale_ref[...].astype(jnp.float32)           # (1, d_model)
        if norm_type == "rmsnorm":
            var = jnp.mean(a * a, axis=-1, keepdims=True)
            a = a * lax.rsqrt(var + eps) * (1.0 + s)
        elif norm_type == "layernorm":
            mu = jnp.mean(a, axis=-1, keepdims=True)
            var = jnp.mean((a - mu) ** 2, axis=-1, keepdims=True)
            a = (a - mu) * lax.rsqrt(var + eps) * s
        a_norm[...] = a

    @pl.when(live)
    def _attend():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale      # (G, D)

        def kv_path():
            k = k_ref[0].astype(jnp.float32)                 # (T, D)
            v = v_ref[0].astype(jnp.float32)
            if quantized:
                # per-(token, head) scales: the block holds every head's
                # (T, KVH) column; dequant on the VMEM tile with head h's
                k = k * _head_column(ks_ref[0], h)
                v = v * _head_column(vs_ref[0], h)
            return k, v

        def act_path():
            wk = wk_ref[...].astype(jnp.float32)             # (d_model, D)
            wv = wv_ref[...].astype(jnp.float32)
            a = a_norm[...]
            return (jnp.dot(a, wk, preferred_element_type=jnp.float32),
                    jnp.dot(a, wv, preferred_element_type=jnp.float32))

        k, v = lax.cond(ptype == 1, act_path, kv_path)

        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (G, T)
        valid = lax.broadcasted_iota(jnp.int32, s.shape, 1) < ntok
        s = jnp.where(valid, s, NEG_INF)

        m_prev, l_prev = m_s[h], l_s[h]                       # (G, 1)
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_cur)
        pexp = jnp.exp(s - m_cur)
        pexp = jnp.where(valid, pexp, 0.0)
        l_s[h] = l_prev * corr + pexp.sum(axis=-1, keepdims=True)
        m_s[h] = m_cur
        acc[h] = acc[h] * corr + jnp.dot(
            pexp, v, preferred_element_type=jnp.float32)

    @pl.when(p == n_pages - 1)
    def _finalize():
        o_ref[0, 0] = (acc[h] / jnp.maximum(l_s[h], 1e-30)).astype(o_ref.dtype)
        if return_lse:
            # partial-softmax statistics in the sm_scale'd score basis: m is
            # the running masked max (NEG_INF when the request attends over
            # zero tokens), l the sum of exp(s - m).  Enough to merge this
            # partition with any disjoint partition's (out, m, l) exactly.
            m_ref[0, 0] = m_s[h]
            l_ref[0, 0] = l_s[h]


@functools.partial(jax.jit,
                   static_argnames=("norm_type", "eps", "pages_bound",
                                    "interpret", "return_lse"))
def hybrid_paged_attention(q, k_pages, v_pages, act_pages, norm_scale, wk, wv,
                           page_table, page_type, page_ntok, *,
                           k_scales=None, v_scales=None, act_scales=None,
                           norm_type: str = "layernorm", eps: float = 1e-5,
                           pages_bound: int | None = None,
                           interpret: bool = False,
                           return_lse: bool = False):
    """-> (B, KVH, G, D) attention output over the hybrid paged cache.

    return_lse: also return the per-request log-sum-exp partials
    ``(m, l)``, each (B, KVH, G, 1) float32, where m is the masked score
    max (NEG_INF basis) and l the sum of exp(s - m) over this partition's
    tokens — the statistics needed to merge with a disjoint partition.

    pages_bound: static upper bound on any request's USED page count; the
    page grid dimension shrinks to it (default: MAXP).  The caller (which
    owns the page tables) knows this bound exactly.

    Quantized pages (DESIGN.md §14): pass int8 k/v/act pools plus their
    absmax scale sidecars — k/v_scales (P_kv, T, KVH, 1) per (token, head),
    act_scales (P_act, T, 1) per token, all float16.  The scale blocks ride
    the SAME index maps as their payload pools, and dequant happens on the
    VMEM tile: KV pages widen inside the per-head kv path, ACT pages inside
    the once-per-page h==0 norm hoist — the fp32 cache is never
    materialized in HBM.  Either pass all three scales or none.
    """
    quantized = k_scales is not None
    if quantized and (v_scales is None or act_scales is None):
        raise ValueError("quantized path needs k_scales, v_scales AND "
                         "act_scales")
    B, KVH, G, D = q.shape
    P_kv, T, _, _ = k_pages.shape
    d_model = act_pages.shape[-1]
    MAXP = page_table.shape[1]
    PB = MAXP if pages_bound is None else min(pages_bound, MAXP)
    PB = max(PB, 1)
    sm_scale = 1.0 / (D ** 0.5)
    scale2d = norm_scale.reshape(1, d_model)

    # page compaction: used pages first (stable), empty tail clamps its block
    # index maps so no fresh page DMA is issued for dead grid iterations
    order = jnp.argsort((page_type == 2).astype(jnp.int32), axis=1,
                        stable=True)
    pt = jnp.take_along_axis(page_table, order, axis=1)
    pty = jnp.take_along_axis(page_type, order, axis=1)
    pn = jnp.take_along_axis(page_ntok, order, axis=1)
    n_used = jnp.sum((page_type != 2).astype(jnp.int32), axis=1)

    def k_index(b, p, h, pt, pty, pn, nu):
        # ACT/dead pages clamp to physical page 0 (loaded but unused); dead
        # iterations ALSO clamp the head coordinate — h is the innermost grid
        # dim, so leaving it live would change the block index every dead
        # iteration and re-issue the page-0 DMA KVH times per dead page
        live = p < nu[b]
        return (jnp.where(live & (pty[b, p] == 0), pt[b, p], 0), 0,
                jnp.where(live, h, 0))

    def ks_index(b, p, h, pt, pty, pn, nu):
        # scale tiles carry every head: only the page coordinate moves
        return (jnp.where((p < nu[b]) & (pty[b, p] == 0), pt[b, p], 0), 0, 0)

    def act_index(b, p, h, pt, pty, pn, nu):
        return (jnp.where((p < nu[b]) & (pty[b, p] == 1), pt[b, p], 0), 0, 0)

    def w_index(b, p, h, pt, pty, pn, nu):
        return (0, jnp.where(p < nu[b], h, 0))

    def q_index(b, p, h, pt, pty, pn, nu):
        return (b, jnp.where(p < nu[b], h, 0), 0, 0)

    def o_index(b, p, h, pt, pty, pn, nu):
        # dead iterations clamp h like every other operand, EXCEPT on the
        # finalize page (p == PB-1): each head must flush to its own block
        # there.  Intermediate flushes of stale content to a clamped block
        # are always overwritten by that block's later finalize flush.
        return (b, jnp.where((p < nu[b]) | (p == PB - 1), h, 0), 0, 0)

    # the TPU tiles a block's last two dims by (8, 128) unless a dim is
    # whole, so a width-1 block over the head axis is refused.  Heads are
    # folded into the lane axis instead — (P, T, KVH*D), (d_model, KVH*D),
    # free row-major reshapes — and head h is the h-th D-wide lane block.
    in_specs = [
        pl.BlockSpec((1, 1, G, D), q_index),
        pl.BlockSpec((1, T, D), k_index),
        pl.BlockSpec((1, T, D), k_index),
        pl.BlockSpec((1, T, d_model), act_index),
        pl.BlockSpec((1, d_model), lambda b, p, h, pt, pty, pn, nu: (0, 0)),
        pl.BlockSpec((d_model, D), w_index),
        pl.BlockSpec((d_model, D), w_index),
    ]
    operands = [q, k_pages.reshape(P_kv, T, KVH * D),
                v_pages.reshape(P_kv, T, KVH * D), act_pages, scale2d,
                wk.reshape(d_model, KVH * D), wv.reshape(d_model, KVH * D)]
    if quantized:
        # scale sidecars follow their payload's page coordinate: a
        # dead/clamped page clamps its scale block identically, so payload
        # and scale DMAs always refer to the same physical page
        in_specs += [
            pl.BlockSpec((1, T, KVH), ks_index),
            pl.BlockSpec((1, T, KVH), ks_index),
            pl.BlockSpec((1, T, 1), act_index),
        ]
        # widened to f32 here: the chip cannot load an f16 tile whose last
        # dim is 1 (the per-token ACT scale), and the sidecars are tiny
        operands += [k_scales.reshape(P_kv, T, KVH).astype(jnp.float32),
                     v_scales.reshape(P_kv, T, KVH).astype(jnp.float32),
                     act_scales.astype(jnp.float32)]

    out_specs = pl.BlockSpec((1, 1, G, D), o_index)
    out_shape = jax.ShapeDtypeStruct((B, KVH, G, D), q.dtype)
    if return_lse:
        # m/l flush per-head on the finalize page exactly like o, so their
        # blocks ride the same clamped index map with a width-1 last dim
        lse_spec = pl.BlockSpec((1, 1, G, 1), o_index)
        out_specs = [out_specs, lse_spec, lse_spec]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((B, KVH, G, 1), jnp.float32),
                     jax.ShapeDtypeStruct((B, KVH, G, 1), jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, PB, KVH),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((KVH, G, D), jnp.float32),
            pltpu.VMEM((KVH, G, 1), jnp.float32),
            pltpu.VMEM((KVH, G, 1), jnp.float32),
            pltpu.VMEM((T, d_model), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_hybrid_attn_kernel, norm_type=norm_type, eps=eps,
                          sm_scale=sm_scale, quantized=quantized,
                          return_lse=return_lse),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(pt, pty, pn, n_used, *operands)
    if return_lse:
        return tuple(out)
    return out
