"""The plain reference: a decoder's forward pass in float32 ``jax.numpy``.

It follows the published architecture as the configuration file states it
(``bench/configs/<name>.json``) and imports nothing of the program: no cache,
no kernels, no batching across requests of different lengths.  It reads the
weights the harness made, layer by layer, so it fits beside nothing else on
the chip.  Matrix products run at ``highest`` precision.

Departures from the published models, all shared with the program and
stated in the configuration files: OPT without linear biases and without its
position offset of 2; RMSNorm gains stored as offsets from 1 (zeros = unit
gain); the embedding padded to a multiple of 256 rows, whose extra rows take
part in the logits.

``precision="fp8"`` is the control: every matrix product of a linear layer
(the LM head too) with its weights and its input in float8 e4m3, scaled per
output channel and per token, accumulated in float32.  It stands for the
lower precision a later change could be tempted to serve in.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

BUCKET = 512            # sequences are padded to a multiple of this
Q_CHUNK = 512           # query rows per attention block


def _fp8(x, axis):
    """x rounded through float8 e4m3 with an absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _linear(x, w, precision: str):
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _norm(x, p, cfg):
    x = x.astype(jnp.float32)
    if cfg["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return ((x - mu) / jnp.sqrt(var + cfg["norm_eps"])
                * p["scale"].astype(jnp.float32)
                + p["bias"].astype(jnp.float32))
    var = (x * x).mean(-1, keepdims=True)
    gain = p["scale"].astype(jnp.float32) + cfg["rms_gain_offset"]
    return x / jnp.sqrt(var + cfg["norm_eps"]) * gain


def _rope(x, pos, cfg):
    """Rotary embedding, half-split layout, on (S, heads, D)."""
    D = x.shape[-1]
    half = D // 2
    freq = cfg["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(lp, x, cfg, precision):
    """One pre-norm decoder layer over x (B, S, d), causal."""
    B, S, _ = x.shape
    H, KVH, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    h = _norm(x, lp["ln1"], cfg)
    q = _linear(h, lp["attn"]["wq"], precision).reshape(B, S, H, D)
    k = _linear(h, lp["attn"]["wk"], precision).reshape(B, S, KVH, D)
    v = _linear(h, lp["attn"]["wv"], precision).reshape(B, S, KVH, D)
    pos = jnp.arange(S)
    if cfg["positions"] == "rope":
        q = jax.vmap(lambda t: _rope(t, pos, cfg))(q)
        k = jax.vmap(lambda t: _rope(t, pos, cfg))(k)
    G = H // KVH
    outs = []
    for q0 in range(0, S, Q_CHUNK):
        qc = q[:, q0:q0 + Q_CHUNK].reshape(B, -1, KVH, G, D)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qc, k,
                       precision=jax.lax.Precision.HIGHEST) / math.sqrt(D)
        qi = q0 + jnp.arange(qc.shape[1])
        s = jnp.where(jnp.arange(S)[None, :] <= qi[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v,
                       precision=jax.lax.Precision.HIGHEST)
        outs.append(o.reshape(B, -1, H * D))
    x = x + _linear(jnp.concatenate(outs, 1), lp["attn"]["wo"], precision)
    h = _norm(x, lp["ln2"], cfg)
    f = lp["ffn"]
    if cfg["ffn"] == "gated_silu":
        a = (jax.nn.silu(_linear(h, f["w1"], precision))
             * _linear(h, f["w3"], precision))
    elif cfg["ffn"] == "relu":
        a = jax.nn.relu(_linear(h, f["w1"], precision))
    else:
        raise ValueError(cfg["ffn"])
    return x + _linear(a, f["w2"], precision)


def hidden(cfg: Dict, rest: Dict, layer: Callable[[int], Dict],
           seqs: Sequence[np.ndarray], precision: str = "f32") -> List:
    """Last layer's float32 hidden states of each sequence, (len, d), on
    the device.

    rest:  the weights outside the layers (``embed``, ``final_norm``,
           ``pos_embed`` or ``unembed`` as the configuration has them);
    layer: l -> that layer's weights (host or device arrays);
    seqs:  token ids, one array per sequence.
    Sequences are padded at the end to a multiple of ``BUCKET`` and run in
    groups of one padded length; causality keeps the padding out of every
    position that is returned.  Each layer's weights reach the chip once."""
    groups: Dict[int, List[int]] = {}
    for i, s in enumerate(seqs):
        groups.setdefault(-(-len(s) // BUCKET) * BUCKET, []).append(i)
    layer_fn = jax.jit(lambda lp, x: _layer(lp, x, cfg, precision))
    with jax.default_matmul_precision("highest"):
        xs = {}
        for S, idx in groups.items():
            toks = np.zeros((len(idx), S), np.int32)
            for r, i in enumerate(idx):
                toks[r, :len(seqs[i])] = seqs[i]
                toks[r, len(seqs[i]):] = seqs[i][-1]
            xs[S] = _embed(cfg, rest, jnp.asarray(toks))
        for l in range(cfg["num_hidden_layers"]):
            lp = layer(l)
            xs = {S: layer_fn(lp, x) for S, x in xs.items()}
            del lp
    out: List = [None] * len(seqs)
    for S, idx in groups.items():
        for r, i in enumerate(idx):
            out[i] = xs[S][r, :len(seqs[i])]
    return out


def head(cfg: Dict, rest: Dict, h, precision: str = "f32"):
    """Float32 logits (n, vocab rows) of hidden states h (n, d), on the
    device."""
    w = rest["embed"].T if cfg["tie_word_embeddings"] else rest["unembed"]
    with jax.default_matmul_precision("highest"):
        return _linear(_norm(h, rest["final_norm"], cfg), w, precision)


def logits(cfg: Dict, rest: Dict, layer: Callable[[int], Dict],
           seqs: Sequence[np.ndarray], want: Sequence[np.ndarray],
           precision: str = "f32") -> List[np.ndarray]:
    """Float32 logits of each sequence at the positions in ``want``."""
    hs = hidden(cfg, rest, layer, seqs, precision)
    return [np.asarray(head(cfg, rest, h[np.asarray(w)], precision))
            for h, w in zip(hs, want)]


def _embed(cfg, rest, toks):
    x = jnp.take(rest["embed"], toks, axis=0).astype(jnp.float32)
    if cfg["positions"] == "learned":
        x = x + rest["pos_embed"][:toks.shape[1]].astype(jnp.float32)[None]
    return x
