"""Flash attention Pallas kernel — the prefill/train compute hot spot.

TPU-native blockwise attention with online softmax: grid (B, H, n_q, n_k)
with the key-block dimension innermost and sequential; the (q_chunk, D)
accumulator and the running max/denominator live in VMEM scratch.  GQA is
handled by indexing the kv-head pool at h // G in the BlockSpec index map —
no repeated K/V ever materialises.

Causal / sliding-window masking is positional (broadcasted_iota per tile);
fully-masked tiles short-circuit via pl.when on the tile indices, so the
causal kernel does ~S^2/2 work like the jnp pair-list path (models/layers.py
blockwise_attention is the oracle-equivalent XLA formulation used under
pjit; this kernel is the single-chip TPU form).

VMEM per step (qc=kc=512, D=128): q/k/v tiles 3*512*128*4B = 768 KiB,
acc + stats ~260 KiB.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s, *,
                  causal: bool, window: int, q_chunk: int, k_chunk: int,
                  sm_scale: float):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    # static-shape tile skip: any unmasked entry possible?
    live = jnp.bool_(True)
    if causal:
        live &= kj * k_chunk <= (qi + 1) * q_chunk - 1
    if window > 0:
        live &= (kj + 1) * k_chunk - 1 > qi * q_chunk - window

    @pl.when(live)
    def _attend():
        q = q_ref[0].astype(jnp.float32) * sm_scale             # (qc, D)
        k = k_ref[0].astype(jnp.float32)                        # (kc, D)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (qc, kc)
        qpos = qi * q_chunk + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = kj * k_chunk + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_s[...], l_s[...]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        p = jnp.where(mask, p, 0.0)
        l_s[...] = l_prev * corr + p.sum(axis=-1, keepdims=True)
        m_s[...] = m_cur
        acc[...] = acc[...] * corr + jnp.dot(p, v,
                                             preferred_element_type=jnp.float32)

    @pl.when(kj == n_k - 1)
    def _finalize():
        o_ref[0] = (acc[...] /
                    jnp.maximum(l_s[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_chunk",
                                             "k_chunk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_chunk: int = 512, k_chunk: int = 512,
                    interpret: bool = False):
    """q (B,S,H,D); k,v (B,S,KVH,D) -> (B,S,H,D).  S % chunk == 0 (caller pads).

    Heads ride the lane axis — (B, S, H*D) views, head h is the h-th D-wide
    lane block — because the TPU refuses a width-1 block over the head axis.
    """
    B, S, H, D = q.shape
    KVH = k.shape[2]
    G = H // KVH
    q_chunk = min(q_chunk, S)
    k_chunk = min(k_chunk, S)
    assert S % q_chunk == 0 and S % k_chunk == 0
    n_q, n_k = S // q_chunk, S // k_chunk
    sm_scale = 1.0 / math.sqrt(D)

    grid = (B, H, n_q, n_k)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, causal=causal, window=window,
                          q_chunk=q_chunk, k_chunk=k_chunk, sm_scale=sm_scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_chunk, D), lambda b, h, qi, kj: (b, qi, h)),
            pl.BlockSpec((1, k_chunk, D), lambda b, h, qi, kj: (b, kj, h // G)),
            pl.BlockSpec((1, k_chunk, D), lambda b, h, qi, kj: (b, kj, h // G)),
        ],
        out_specs=pl.BlockSpec((1, q_chunk, D), lambda b, h, qi, kj: (b, qi, h)),
        out_shape=jax.ShapeDtypeStruct((B, S, H * D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_chunk, D), jnp.float32),
            pltpu.VMEM((q_chunk, 1), jnp.float32),
            pltpu.VMEM((q_chunk, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q.reshape(B, S, H * D), k.reshape(B, S, KVH * D),
      v.reshape(B, S, KVH * D))
    return out.reshape(B, S, H, D)
