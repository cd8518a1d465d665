"""KV-Gen Pallas kernel: blockwise ACT -> (K, V) projection (paper Eq. 7).

TPU mapping of HybridServe's activation recomputation: each grid step reads
one 16-token ACT page from VMEM, applies the pre-attention RMS/LayerNorm and
projects against a (d_model, head_dim) weight tile on the MXU — the hot loop
the paper overlaps with PCIe weight streaming.

Grid: (n_pages, n_kv_heads).  VMEM per step:
  act   (PAGE, d_model)       <= 16*8192*2B   = 256 KiB
  wk/wv (d_model, head_dim)   <= 8192*128*2B  = 2 MiB each
  out   (PAGE, head_dim)      tiny
All matmul dims are multiples of (16, 128) — MXU-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

PAGE = 16  # tokens per ACT page (= core.blocks.BLOCK_TOKENS)


def _kv_gen_kernel(act_ref, scale_ref, wk_ref, wv_ref, k_ref, v_ref, *,
                   norm_type: str, eps: float):
    act = act_ref[0].astype(jnp.float32)              # (PAGE, d_model)
    scale = scale_ref[...].astype(jnp.float32)        # (1, d_model)
    if norm_type == "rmsnorm":
        var = jnp.mean(act * act, axis=-1, keepdims=True)
        act = act * lax.rsqrt(var + eps) * (1.0 + scale)
    elif norm_type == "layernorm":
        mu = jnp.mean(act, axis=-1, keepdims=True)
        var = jnp.mean((act - mu) ** 2, axis=-1, keepdims=True)
        act = (act - mu) * lax.rsqrt(var + eps) * scale
    wk = wk_ref[...].astype(jnp.float32)              # (d_model, hd)
    wv = wv_ref[...].astype(jnp.float32)
    k = jnp.dot(act, wk, preferred_element_type=jnp.float32)
    v = jnp.dot(act, wv, preferred_element_type=jnp.float32)
    k_ref[0] = k.astype(k_ref.dtype)
    v_ref[0] = v.astype(v_ref.dtype)


@functools.partial(jax.jit, static_argnames=("norm_type", "eps", "interpret"))
def kv_gen(act_pages, norm_scale, wk, wv, *, norm_type: str = "rmsnorm",
           eps: float = 1e-6, interpret: bool = False):
    """act_pages (N, PAGE, d) , wk/wv (d, KVH, hd) -> k, v (N, PAGE, KVH, hd).

    ``interpret=True`` executes the kernel body in the Pallas interpreter
    (validation on CPU).

    Heads ride the lane axis: weights are viewed as (d, KVH*hd) and outputs
    written as (N, PAGE, KVH*hd), so every block's last two dims are
    (sublane, hd) tiles the TPU accepts — a width-1 block over the head axis
    is refused unless hd-wide blocks are lane-aligned (hd % 128 == 0).
    """
    n, page, d = act_pages.shape
    _, kvh, hd = wk.shape
    assert page == PAGE and wk.shape[0] == d
    scale2d = norm_scale.reshape(1, d)

    grid = (n, kvh)
    out_shape = [
        jax.ShapeDtypeStruct((n, page, kvh * hd), act_pages.dtype),
        jax.ShapeDtypeStruct((n, page, kvh * hd), act_pages.dtype),
    ]
    k, v = pl.pallas_call(
        functools.partial(_kv_gen_kernel, norm_type=norm_type, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, page, d), lambda i, h: (i, 0, 0)),
            pl.BlockSpec((1, d), lambda i, h: (0, 0)),
            pl.BlockSpec((d, hd), lambda i, h: (0, h)),
            pl.BlockSpec((d, hd), lambda i, h: (0, h)),
        ],
        out_specs=[
            pl.BlockSpec((1, page, hd), lambda i, h: (i, 0, h)),
            pl.BlockSpec((1, page, hd), lambda i, h: (i, 0, h)),
        ],
        out_shape=out_shape,
        interpret=interpret,
    )(act_pages, scale2d, wk.reshape(d, kvh * hd), wv.reshape(d, kvh * hd))
    return k.reshape(n, page, kvh, hd), v.reshape(n, page, kvh, hd)
