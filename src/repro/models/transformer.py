"""Architecture assembly: parameter init + forward for all six families.

Families (selected from ModelConfig):
  uniform   — dense / moe / vlm / OPT: one homogeneous stack, lax.scan
  windowed  — gemma3: period scan (5 local + 1 global) + local tail scan
  hybrid    — jamba: period scan (7 SSD + 1 attn, alternating dense/MoE FFN)
  ssm       — mamba2: homogeneous SSD stack
  encdec    — whisper: bidirectional encoder + causal decoder w/ cross-attn

Three modes per family:
  full(x)                     -> hidden states (training / logits over all S)
  prefill(x)                  -> hidden + cache (fills KV/SSD caches)
  decode(x_1, cache)          -> hidden_1 + updated cache (serve_step)

The hybrid KV/ACT cache decode (the paper's technique) lives in
``hybrid_decode`` for uniform-family models; the serving engine drives it.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import shardhints as SH


def pad_vocab(v: int, multiple: int = 256) -> int:
    return (v + multiple - 1) // multiple * multiple


# =============================================================================
# parameter init
# =============================================================================

def _norm_p(rng, cfg, d=None):
    d = d or cfg.d_model
    if cfg.norm_type == "rmsnorm":
        return {"scale": jnp.zeros((d,), _dt(cfg))}
    return {"scale": jnp.ones((d,), _dt(cfg)), "bias": jnp.zeros((d,), _dt(cfg))}


def _dt(cfg):
    return jnp.dtype(cfg.dtype)


def _dense(rng, shape, cfg, scale=None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(rng, shape, jnp.float32) * std).astype(_dt(cfg))


def init_attn(rng, cfg: ModelConfig, cross: bool = False) -> Dict[str, Any]:
    r = jax.random.split(rng, 8)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    out_scale = 1.0 / math.sqrt(qd) / math.sqrt(2 * max(cfg.num_layers, 1))
    p = {
        "wq": _dense(r[0], (d, qd), cfg),
        "wk": _dense(r[1], (d, kvd), cfg),
        "wv": _dense(r[2], (d, kvd), cfg),
        "wo": _dense(r[3], (qd, d), cfg, scale=out_scale),
    }
    if cfg.qk_norm:
        p["qnorm"] = jnp.zeros((cfg.head_dim,), _dt(cfg))
        p["knorm"] = jnp.zeros((cfg.head_dim,), _dt(cfg))
    return p


def init_ffn(rng, cfg: ModelConfig) -> Dict[str, Any]:
    r = jax.random.split(rng, 3)
    d, f = cfg.d_model, cfg.d_ff
    out_scale = 1.0 / math.sqrt(f) / math.sqrt(2 * max(cfg.num_layers, 1))
    p = {"w1": _dense(r[0], (d, f), cfg), "w2": _dense(r[1], (f, d), cfg, scale=out_scale)}
    if cfg.ffn_type.startswith("gated"):
        p["w3"] = _dense(r[2], (d, f), cfg)
    return p


def init_moe(rng, cfg: ModelConfig) -> Dict[str, Any]:
    r = jax.random.split(rng, 4)
    d, f, E = cfg.d_model, cfg.d_ff, cfg.moe_num_experts
    out_scale = 1.0 / math.sqrt(f) / math.sqrt(2 * max(cfg.num_layers, 1))
    p = {
        "router": _dense(r[0], (d, E), cfg).astype(jnp.float32),
        "we1": _dense(r[1], (E, d, f), cfg),
        "we2": _dense(r[2], (E, f, d), cfg, scale=out_scale),
    }
    if cfg.ffn_type.startswith("gated"):
        p["we3"] = _dense(r[3], (E, d, f), cfg)
    return p


def init_ssd(rng, cfg: ModelConfig) -> Dict[str, Any]:
    r = jax.random.split(rng, 6)
    d, inner = cfg.d_model, cfg.ssm_inner
    h, n, w = cfg.ssm_num_heads, cfg.ssm_state_size, cfg.ssm_conv_width
    conv_ch = inner + 2 * n                       # x, B, C go through the conv
    return {
        "in_proj": _dense(r[0], (d, 2 * inner + 2 * n + h), cfg),  # z,x,B,C,dt
        "conv_w": _dense(r[1], (conv_ch, w), cfg, scale=1.0 / math.sqrt(w)),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "norm": jnp.zeros((inner,), _dt(cfg)),
        "out_proj": _dense(r[2], (inner, d), cfg,
                           scale=1.0 / math.sqrt(inner) / math.sqrt(2 * cfg.num_layers)),
    }


def _layer(rng, cfg, kind: str, moe: bool, cross: bool = False) -> Dict[str, Any]:
    r = jax.random.split(rng, 6)
    p: Dict[str, Any] = {"ln1": _norm_p(r[0], cfg)}
    if kind == "attn":
        p["attn"] = init_attn(r[1], cfg)
    else:
        p["ssd"] = init_ssd(r[1], cfg)
    if cfg.d_ff > 0:
        p["ln2"] = _norm_p(r[2], cfg)
        p["ffn"] = init_moe(r[3], cfg) if moe else init_ffn(r[3], cfg)
    if cross:
        p["ln_x"] = _norm_p(r[4], cfg)
        p["xattn"] = init_attn(r[5], cfg, cross=True)
    return p


def _stack(rng, n: int, make, on_host: bool = False) -> Any:
    """Stack n independently-initialised param subtrees along axis 0.

    ``on_host``: copy each subtree out to host numpy as soon as it is made,
    into one preallocated stacked array per leaf — the device holds one
    layer at a time instead of n layers plus their stacked copy."""
    rngs = jax.random.split(rng, max(n, 1))
    if not on_host:
        trees = [make(rngs[i], i) for i in range(n)]
        if not trees:
            return None
        return jax.tree.map(lambda *xs: jnp.stack(xs, 0), *trees)
    out = None
    for i in range(n):
        tree = jax.device_get(make(rngs[i], i))
        if out is None:
            out = jax.tree.map(
                lambda a: np.empty((n,) + a.shape, a.dtype), tree)
        for dst, a in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
            dst[i] = a
    return out


def init_params(cfg: ModelConfig, rng, *, on_host: bool = False
                ) -> Dict[str, Any]:
    """Random parameters for ``cfg``.

    ``on_host=True`` returns every leaf as a host numpy array with the same
    values: each layer is made on the default device and copied out before
    the next, so a model larger than the device can be built for the
    offload runtime (which streams layers from host memory) or placed
    straight onto a mesh, and a device-resident model costs the device one
    copy of its weights instead of two."""
    if on_host and family(cfg) != "uniform":
        raise NotImplementedError("on_host init covers uniform-family models")
    r = jax.random.split(rng, 8)
    V = pad_vocab(cfg.vocab_size)
    params: Dict[str, Any] = {
        "embed": _dense(r[0], (V, cfg.d_model), cfg, scale=0.02),
        "final_norm": _norm_p(r[1], cfg),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = _dense(r[2], (cfg.d_model, V), cfg)
    if cfg.pos_type == "learned":
        params["pos_embed"] = _dense(r[3], (cfg.max_seq_len, cfg.d_model), cfg, scale=0.02)

    fam = family(cfg)
    moe_flags = cfg.layer_is_moe()
    if on_host:
        params = jax.device_get(params)
    if fam == "uniform":
        params["layers"] = _stack(
            r[4], cfg.num_layers,
            lambda rg, i: _layer(rg, cfg, "attn", moe_flags[i]), on_host)
    elif fam == "ssm":
        params["layers"] = _stack(
            r[4], cfg.num_layers, lambda rg, i: _layer(rg, cfg, "ssd", False))
    elif fam == "windowed":
        period, n_per, tail = _window_split(cfg)
        def mk_period(rg, i):
            rr = jax.random.split(rg, period)
            return {
                "local": jax.tree.map(
                    lambda *xs: jnp.stack(xs, 0),
                    *[_layer(rr[j], cfg, "attn", False) for j in range(period - 1)]),
                "global": _layer(rr[period - 1], cfg, "attn", False),
            }
        params["periods"] = _stack(r[4], n_per, mk_period)
        if tail:
            params["tail"] = _stack(r[5], tail, lambda rg, i: _layer(rg, cfg, "attn", False))
    elif fam == "hybrid":
        period = cfg.attn_period
        n_per = cfg.num_layers // period
        kinds = cfg.layer_kinds()[:period]
        # SSD layers with dense FFN and with MoE FFN have different param
        # structure -> keep two stacks; `hybrid_slots` gives the walk order.
        def mk_period(rg, i):
            rr = jax.random.split(rg, period)
            ssd_dense, ssd_moe, attn_layer = [], [], None
            for j in range(period):
                lp = _layer(rr[j], cfg, kinds[j], moe_flags[j])
                if kinds[j] == "attn":
                    attn_layer = lp
                elif moe_flags[j]:
                    ssd_moe.append(lp)
                else:
                    ssd_dense.append(lp)
            out = {"attn": attn_layer}
            if ssd_dense:
                out["ssd_dense"] = jax.tree.map(lambda *xs: jnp.stack(xs, 0), *ssd_dense)
            if ssd_moe:
                out["ssd_moe"] = jax.tree.map(lambda *xs: jnp.stack(xs, 0), *ssd_moe)
            return out
        params["periods"] = _stack(r[4], n_per, mk_period)
    elif fam == "encdec":
        params["enc_pos"] = _dense(r[3], (cfg.enc_seq_len, cfg.d_model), cfg, scale=0.02)
        params["enc_layers"] = _stack(
            r[5], cfg.enc_num_layers, lambda rg, i: _layer(rg, cfg, "attn", False))
        params["enc_norm"] = _norm_p(r[6], cfg)
        params["layers"] = _stack(
            r[4], cfg.num_layers,
            lambda rg, i: _layer(rg, cfg, "attn", False, cross=True))
    else:
        raise ValueError(fam)
    return params


def family(cfg: ModelConfig) -> str:
    if cfg.is_encoder_decoder:
        return "encdec"
    if cfg.arch_type == "ssm":
        return "ssm"
    if cfg.is_hybrid:
        return "hybrid"
    if cfg.window_period > 0:
        return "windowed"
    return "uniform"


def _window_split(cfg) -> Tuple[int, int, int]:
    period = cfg.window_period
    n_per = cfg.num_layers // period
    tail = cfg.num_layers - n_per * period
    return period, n_per, tail


def hybrid_slots(cfg) -> Tuple[Tuple[str, int, bool], ...]:
    """Walk order inside one hybrid period: (stack_name, index, is_moe)."""
    period = cfg.attn_period
    kinds = cfg.layer_kinds()[:period]
    moe_flags = cfg.layer_is_moe()[:period]
    slots, nd, nm = [], 0, 0
    for j in range(period):
        if kinds[j] == "attn":
            slots.append(("attn", 0, moe_flags[j]))
        elif moe_flags[j]:
            slots.append(("ssd_moe", nm, True)); nm += 1
        else:
            slots.append(("ssd_dense", nd, False)); nd += 1
    return tuple(slots)


# =============================================================================
# block applications
# =============================================================================

def _rope_for(cfg: ModelConfig, positions):
    if cfg.pos_type == "rope":
        return L.rope_sin_cos(positions, cfg.head_dim, cfg.rope_theta)
    if cfg.pos_type == "mrope":
        return L.mrope_sin_cos(positions, cfg.head_dim, cfg.rope_theta)
    return None


def _qk(p, cfg, x):
    B, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = (x @ p["wk"]).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = (x @ p["wv"]).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    q = SH.constrain(q, SH.BATCH, None, SH.MODEL, None)
    k = SH.constrain(k, SH.BATCH, None, SH.MODEL, None)
    v = SH.constrain(v, SH.BATCH, None, SH.MODEL, None)
    if cfg.qk_norm:
        q = L.rms_norm(q, p["qnorm"])
        k = L.rms_norm(k, p["knorm"])
    return q, k, v


def attn_full(p, cfg: ModelConfig, x, sincos, *, causal=True, window=0,
              q_chunk=1024, k_chunk=1024):
    """Full-sequence attention (train / prefill). Returns (out, (k, v))."""
    q, k, v = _qk(p, cfg, x)
    if sincos is not None:
        q = L.apply_rope(q, *sincos)
        k = L.apply_rope(k, *sincos)
    o = L.blockwise_attention(q, k, v, causal=causal, window=window,
                              q_chunk=q_chunk, k_chunk=k_chunk)
    o = SH.constrain(o, SH.BATCH, None, SH.MODEL, None)
    return o.reshape(x.shape[0], x.shape[1], cfg.q_dim) @ p["wo"], (k, v)


def attn_decode(p, cfg: ModelConfig, x, sincos, k_cache, v_cache, kv_len,
                *, window=0, ring=False):
    """One-token attention against a cache.

    kv_len (B,): number of tokens already in the cache (the new token is
    written at kv_len, then attended).  ``ring=True`` treats the cache as a
    ring buffer of size cache_S (sliding-window layers).
    """
    B = x.shape[0]
    q, k, v = _qk(p, cfg, x)                                   # S = 1
    if sincos is not None:
        q = L.apply_rope(q, *sincos)
        k = L.apply_rope(k, *sincos)
    S = k_cache.shape[1]
    if ring:
        slot = kv_len % S
    else:
        slot = kv_len
    k_cache = k_cache.at[jnp.arange(B), slot].set(k[:, 0])
    v_cache = v_cache.at[jnp.arange(B), slot].set(v[:, 0])
    if ring:
        # position held by slot j: largest p <= kv_len with p % S == j
        pos = kv_len[:, None] - (kv_len[:, None] - jnp.arange(S)[None, :]) % S
        valid = (pos >= 0) & (pos >= kv_len[:, None] + 1 - window)
        o = _masked_decode_attn(q, k_cache, v_cache, valid)
    else:
        o = L.decode_attention(q, k_cache, v_cache, kv_len=kv_len + 1, window=window)
    return o.reshape(B, 1, cfg.q_dim) @ p["wo"], k_cache, v_cache


def _masked_decode_attn(q, k_cache, v_cache, valid):
    B, _, H, D = q.shape
    KVH = k_cache.shape[2]
    G = H // KVH
    qr = q.reshape(B, KVH, G, D).astype(jnp.float32)
    s = jnp.einsum("bhgd,bshd->bhgs", qr, k_cache.astype(jnp.float32)) / math.sqrt(D)
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, D).astype(q.dtype)


#: finite masked-score basis shared with the host attention lane and the
#: hybrid kernel ref oracle — an all-masked partition yields (m=NEG_INF,
#: l=0), the identity element under partial merging (DESIGN.md §15)
NEG_INF = -1e30


def _partial_masked_attn(q, k_cache, v_cache, valid):
    """``_masked_decode_attn`` exposing flash-attention partials: returns
    the NORMALISED partition output plus its (m, l) log-sum-exp stats, so
    two disjoint key partitions merge exactly (``host_attn.merge_partials``)
    into what the dense softmax over their union would produce.

    -> (o (B,KVH,G,D) f32, m (B,KVH,G,1) f32, l (B,KVH,G,1) f32).
    """
    B, _, H, D = q.shape
    KVH = k_cache.shape[2]
    G = H // KVH
    qr = q.reshape(B, KVH, G, D).astype(jnp.float32)
    s = jnp.einsum("bhgd,bshd->bhgs", qr, k_cache.astype(jnp.float32)) / math.sqrt(D)
    vm = valid[:, None, None, :]
    s = jnp.where(vm, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(vm, jnp.exp(s - m), 0.0)
    l = jnp.sum(e, axis=-1, keepdims=True)
    o = jnp.einsum("bhgs,bshd->bhgd", e, v_cache.astype(jnp.float32))
    return o / jnp.maximum(l, 1e-30), m, l


def ffn_apply(p, cfg: ModelConfig, x, is_moe: bool, expert_sharding=None):
    if cfg.d_ff == 0:
        return x * 0, 0.0
    if is_moe:
        B, S, d = x.shape
        y, aux = L.moe_ffn(p, x.reshape(B * S, d),
                           num_experts=cfg.moe_num_experts, top_k=cfg.moe_top_k,
                           capacity_factor=cfg.moe_capacity_factor,
                           ffn_type=cfg.ffn_type, expert_sharding=expert_sharding)
        return y.reshape(B, S, d), aux
    return L.dense_ffn(p, x, cfg.ffn_type), 0.0


def ssd_full(p, cfg: ModelConfig, x, conv_cache=None, state=None):
    """Full-sequence SSD mixer. Returns (out, (final_state, conv_cache))."""
    B, S, d = x.shape
    inner, h, n = cfg.ssm_inner, cfg.ssm_num_heads, cfg.ssm_state_size
    proj = x @ p["in_proj"]                                    # (B,S,2i+2n+h)
    z, xbc, dt_raw = jnp.split(proj, [inner, 2 * inner + 2 * n], axis=-1)
    z = SH.constrain(z, SH.BATCH, None, SH.MODEL)
    xbc = SH.constrain(xbc, SH.BATCH, None, SH.MODEL)
    xbc, new_conv = L.causal_conv1d(xbc, p["conv_w"], conv_cache)
    xbc = jax.nn.silu(xbc)
    xs, Bc, Cc = jnp.split(xbc, [inner, inner + n], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xh = SH.constrain(xs.reshape(B, S, h, cfg.ssm_head_dim),
                      SH.BATCH, None, SH.MODEL, None)
    y, final = L.ssd_chunked(
        xh, dt, A,
        Bc.reshape(B, S, 1, n), Cc.reshape(B, S, 1, n),
        chunk=cfg.ssm_chunk, initial_state=state)
    y = y + xs.reshape(B, S, h, cfg.ssm_head_dim) * p["D"][None, None, :, None]
    y = (y.reshape(B, S, inner) * jax.nn.silu(z)).astype(x.dtype)
    y = L.rms_norm(y, p["norm"])
    return y @ p["out_proj"], (final.astype(_dt(cfg)), new_conv)


def ssd_decode(p, cfg: ModelConfig, x, state, conv_cache):
    """One-token SSD step. x (B,1,d)."""
    B = x.shape[0]
    inner, h, n = cfg.ssm_inner, cfg.ssm_num_heads, cfg.ssm_state_size
    proj = x @ p["in_proj"]
    z, xbc, dt_raw = jnp.split(proj, [inner, 2 * inner + 2 * n], axis=-1)
    xbc, new_conv = L.causal_conv1d(xbc, p["conv_w"], conv_cache)
    xbc = jax.nn.silu(xbc)
    xs, Bc, Cc = jnp.split(xbc, [inner, inner + n], axis=-1)
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"])  # (B,h)
    A = -jnp.exp(p["A_log"])
    y, new_state = L.ssd_decode_step(
        state.astype(jnp.float32), xs[:, 0].reshape(B, h, cfg.ssm_head_dim),
        dt, A, Bc[:, 0].reshape(B, 1, n), Cc[:, 0].reshape(B, 1, n))
    y = y + xs[:, 0].reshape(B, h, cfg.ssm_head_dim) * p["D"][None, :, None]
    y = (y.reshape(B, 1, inner) * jax.nn.silu(z)).astype(x.dtype)
    y = L.rms_norm(y, p["norm"])
    return y @ p["out_proj"], new_state.astype(_dt(cfg)), new_conv


# --- single transformer layer (pre-norm residual) -----------------------------

def layer_full(p, cfg, x, sincos, *, kind="attn", is_moe=False, causal=True,
               window=0, want_cache=False, expert_sharding=None,
               q_chunk=1024, k_chunk=1024):
    cache = None
    h = L.apply_norm(x, p["ln1"], cfg.norm_type)
    if kind == "attn":
        a, kv = attn_full(p["attn"], cfg, h, sincos, causal=causal, window=window,
                          q_chunk=q_chunk, k_chunk=k_chunk)
        cache = kv if want_cache else None
    else:
        a, st = ssd_full(p["ssd"], cfg, h)
        cache = st if want_cache else None
    x = x + a
    aux = 0.0
    if cfg.d_ff > 0:
        h = L.apply_norm(x, p["ln2"], cfg.norm_type)
        f, aux = ffn_apply(p["ffn"], cfg, h, is_moe, expert_sharding)
        x = x + f
    return x, cache, aux


def layer_decode(p, cfg, x, sincos, cache, kv_len, *, kind="attn", is_moe=False,
                 window=0, ring=False):
    h = L.apply_norm(x, p["ln1"], cfg.norm_type)
    if kind == "attn":
        a, k_c, v_c = attn_decode(p["attn"], cfg, h, sincos, cache[0], cache[1],
                                  kv_len, window=window, ring=ring)
        new_cache = (k_c, v_c)
    else:
        a, st, conv = ssd_decode(p["ssd"], cfg, h, cache[0], cache[1])
        new_cache = (st, conv)
    x = x + a
    if cfg.d_ff > 0:
        h = L.apply_norm(x, p["ln2"], cfg.norm_type)
        f, _ = ffn_apply(p["ffn"], cfg, h, is_moe)
        x = x + f
    return x, new_cache
