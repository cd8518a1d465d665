"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.kv_gen.kernel import kv_gen
from repro.kernels.kv_gen.ref import kv_gen_ref
from repro.kernels.hybrid_attention.kernel import hybrid_paged_attention
from repro.kernels.hybrid_attention.ref import hybrid_paged_attention_ref
from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_ref_sequential


@pytest.mark.parametrize("d,kvh,hd,n", [(128, 1, 64, 2), (256, 2, 64, 3),
                                        (512, 4, 128, 4)])
@pytest.mark.parametrize("norm", ["rmsnorm", "layernorm", "none"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kv_gen_sweep(d, kvh, hd, n, norm, dtype):
    rng = jax.random.PRNGKey(0)
    act = jax.random.normal(rng, (n, 16, d)).astype(dtype)
    sc = (jax.random.normal(jax.random.PRNGKey(1), (d,)) * 0.1 + 1).astype(dtype)
    wk = (jax.random.normal(jax.random.PRNGKey(2), (d, kvh, hd)) * 0.05).astype(dtype)
    wv = (jax.random.normal(jax.random.PRNGKey(3), (d, kvh, hd)) * 0.05).astype(dtype)
    k1, v1 = kv_gen(act, sc, wk, wv, norm_type=norm, interpret=True)
    k2, v2 = kv_gen_ref(act, sc, wk, wv, norm_type=norm)
    tol = 1e-5 if dtype == jnp.float32 else 8e-2   # bf16 mantissa at d=512
    np.testing.assert_allclose(np.asarray(k1, np.float32),
                               np.asarray(k2, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(v1, np.float32),
                               np.asarray(v2, np.float32), atol=tol)


@pytest.mark.parametrize("kvh,g,d_model", [(1, 4, 128), (2, 3, 256)])
@pytest.mark.parametrize("norm", ["layernorm", "rmsnorm"])
def test_hybrid_attention_sweep(kvh, g, d_model, norm):
    rng = jax.random.PRNGKey(0)
    B, D, T = 2, 32, 16
    P_kv, P_act, MAXP = 4, 3, 5
    ks = jax.random.normal(rng, (P_kv, T, kvh, D)) * 0.3
    vs = jax.random.normal(jax.random.PRNGKey(1), (P_kv, T, kvh, D)) * 0.3
    ap = jax.random.normal(jax.random.PRNGKey(2), (P_act, T, d_model)) * 0.5
    q = jax.random.normal(jax.random.PRNGKey(3), (B, kvh, g, D))
    sc = jnp.ones((d_model,))
    wk = jax.random.normal(jax.random.PRNGKey(4), (d_model, kvh, D)) * 0.05
    wv = jax.random.normal(jax.random.PRNGKey(5), (d_model, kvh, D)) * 0.05
    pt = jnp.array([[0, 1, 0, 2, 3], [2, 1, 0, 0, 0]], jnp.int32)
    pty = jnp.array([[0, 1, 0, 1, 0], [0, 0, 1, 2, 2]], jnp.int32)
    pn = jnp.array([[16, 16, 16, 16, 9], [16, 16, 5, 0, 0]], jnp.int32)
    o1 = hybrid_paged_attention(q, ks, vs, ap, sc, wk, wv, pt, pty, pn,
                                norm_type=norm, interpret=True)
    o2 = hybrid_paged_attention_ref(q, ks, vs, ap, sc, wk, wv, pt, pty, pn,
                                    norm_type=norm)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


@pytest.mark.parametrize("kvh,g,d_model", [(1, 4, 128), (2, 3, 256)])
@pytest.mark.parametrize("norm", ["layernorm", "rmsnorm"])
def test_hybrid_attention_quantized_matches_dequant_ref(kvh, g, d_model, norm):
    """int8 pools + f16 scale sidecars: the kernel's on-tile dequant (KV in
    the kv path, ACT inside the once-per-page norm hoist) must agree with
    the reference's dense dequantize-then-attend oracle, and stay close to
    the fp kernel on the same values (DESIGN.md §14)."""
    from repro.models.quant_ops import quantize
    rng = jax.random.PRNGKey(0)
    B, D, T = 2, 32, 16
    P_kv, P_act = 4, 3
    ks = jax.random.normal(rng, (P_kv, T, kvh, D)) * 0.3
    vs = jax.random.normal(jax.random.PRNGKey(1), (P_kv, T, kvh, D)) * 0.3
    ap = jax.random.normal(jax.random.PRNGKey(2), (P_act, T, d_model)) * 0.5
    q = jax.random.normal(jax.random.PRNGKey(3), (B, kvh, g, D))
    sc = jnp.ones((d_model,))
    wk = jax.random.normal(jax.random.PRNGKey(4), (d_model, kvh, D)) * 0.05
    wv = jax.random.normal(jax.random.PRNGKey(5), (d_model, kvh, D)) * 0.05
    pt = jnp.array([[0, 1, 0, 2, 3], [2, 1, 0, 0, 0]], jnp.int32)
    pty = jnp.array([[0, 1, 0, 1, 0], [0, 0, 1, 2, 2]], jnp.int32)
    pn = jnp.array([[16, 16, 16, 16, 9], [16, 16, 5, 0, 0]], jnp.int32)
    kq, ksc = quantize(ks)
    vq, vsc = quantize(vs)
    aq, asc = quantize(ap)
    scales = dict(k_scales=ksc, v_scales=vsc, act_scales=asc)
    o1 = hybrid_paged_attention(q, kq, vq, aq, sc, wk, wv, pt, pty, pn,
                                norm_type=norm, interpret=True, **scales)
    o2 = hybrid_paged_attention_ref(q, kq, vq, aq, sc, wk, wv, pt, pty, pn,
                                    norm_type=norm, **scales)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)
    # int8 error is bounded: close to (but not equal to) the fp kernel
    ofp = hybrid_paged_attention(q, ks, vs, ap, sc, wk, wv, pt, pty, pn,
                                 norm_type=norm, interpret=True)
    err = float(jnp.max(jnp.abs(o1 - ofp)))
    assert 0.0 < err < 0.05


@pytest.mark.parametrize("kvh,g,d_model", [(1, 4, 128), (2, 3, 256)])
def test_hybrid_attention_return_lse_matches_ref(kvh, g, d_model):
    """return_lse: kernel and oracle agree on the (m, l) softmax partials,
    and merging the partials of a split page table reproduces the full
    table's output (DESIGN.md §15 — what the cpu lane's merge relies on)."""
    from repro.offload.host_attn import merge_partials
    rng = jax.random.PRNGKey(0)
    B, D, T = 2, 32, 16
    P_kv, P_act = 4, 3
    ks = jax.random.normal(rng, (P_kv, T, kvh, D)) * 0.3
    vs = jax.random.normal(jax.random.PRNGKey(1), (P_kv, T, kvh, D)) * 0.3
    ap = jax.random.normal(jax.random.PRNGKey(2), (P_act, T, d_model)) * 0.5
    q = jax.random.normal(jax.random.PRNGKey(3), (B, kvh, g, D))
    sc = jnp.ones((d_model,))
    wk = jax.random.normal(jax.random.PRNGKey(4), (d_model, kvh, D)) * 0.05
    wv = jax.random.normal(jax.random.PRNGKey(5), (d_model, kvh, D)) * 0.05
    pt = jnp.array([[0, 1, 0, 2, 3], [2, 1, 0, 0, 0]], jnp.int32)
    pty = jnp.array([[0, 1, 0, 1, 0], [0, 0, 1, 2, 2]], jnp.int32)
    pn = jnp.array([[16, 16, 16, 16, 9], [16, 16, 5, 0, 0]], jnp.int32)
    kw = dict(norm_type="layernorm")
    o1, m1, l1 = hybrid_paged_attention(q, ks, vs, ap, sc, wk, wv, pt, pty,
                                        pn, return_lse=True, interpret=True,
                                        **kw)
    o2, m2, l2 = hybrid_paged_attention_ref(q, ks, vs, ap, sc, wk, wv, pt,
                                            pty, pn, return_lse=True, **kw)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=1e-5)
    # the partials MERGE: split the table at page 2, mask the other half
    # dead (type 2), and fold the two partitions back together
    def half(keep):
        mask = jnp.zeros_like(pty) + 2
        cols = jnp.arange(pty.shape[1])
        sel = (cols[None, :] < 2) if keep == 0 else (cols[None, :] >= 2)
        return jnp.where(sel, pty, mask)
    pa = hybrid_paged_attention_ref(q, ks, vs, ap, sc, wk, wv, pt, half(0),
                                    pn, return_lse=True, **kw)
    pb = hybrid_paged_attention_ref(q, ks, vs, ap, sc, wk, wv, pt, half(1),
                                    pn, return_lse=True, **kw)
    om, _, _ = merge_partials(np.asarray(pa[0], np.float32), np.asarray(pa[1]),
                              np.asarray(pa[2]), np.asarray(pb[0], np.float32),
                              np.asarray(pb[1]), np.asarray(pb[2]))
    np.testing.assert_allclose(om, np.asarray(o2, np.float32), atol=1e-5)


def test_hybrid_attention_quantized_requires_all_scales():
    B, kvh, g, D, T, d_model = 1, 1, 2, 16, 16, 32
    ks = jnp.zeros((1, T, kvh, D), jnp.int8)
    ap = jnp.zeros((1, T, d_model), jnp.int8)
    q = jnp.ones((B, kvh, g, D))
    pt = jnp.zeros((1, 1), jnp.int32)
    with pytest.raises(ValueError, match="k_scales"):
        hybrid_paged_attention(q, ks, ks, ap, jnp.ones(d_model),
                               jnp.zeros((d_model, kvh, D)),
                               jnp.zeros((d_model, kvh, D)),
                               pt, pt, pt, norm_type="none", interpret=True,
                               k_scales=jnp.ones((1, T, kvh, 1),
                                                 jnp.float16))


@pytest.mark.parametrize("pages_bound", [None, 3, 5])
def test_hybrid_attention_empty_page_compaction(pages_bound):
    """Interleaved empty pages + a static pages_bound: the compacted grid
    must agree with the oracle, which walks the uncompacted table."""
    rng = jax.random.PRNGKey(0)
    B, kvh, g, D, T, d_model = 3, 2, 2, 32, 16, 128
    P_kv, P_act, MAXP = 4, 3, 8
    ks = jax.random.normal(rng, (P_kv, T, kvh, D)) * 0.3
    vs = jax.random.normal(jax.random.PRNGKey(1), (P_kv, T, kvh, D)) * 0.3
    ap = jax.random.normal(jax.random.PRNGKey(2), (P_act, T, d_model)) * 0.5
    q = jax.random.normal(jax.random.PRNGKey(3), (B, kvh, g, D))
    sc = jnp.ones((d_model,))
    wk = jax.random.normal(jax.random.PRNGKey(4), (d_model, kvh, D)) * 0.05
    wv = jax.random.normal(jax.random.PRNGKey(5), (d_model, kvh, D)) * 0.05
    # empty pages interleaved mid-table; used-page counts 3 / 2 / 1
    pt = jnp.array([[0, 0, 1, 0, 2, 0, 0, 0],
                    [1, 0, 3, 0, 0, 0, 0, 0],
                    [2, 0, 0, 0, 0, 0, 0, 0]], jnp.int32)
    pty = jnp.array([[0, 2, 1, 2, 0, 2, 2, 2],
                     [1, 2, 0, 2, 2, 2, 2, 2],
                     [0, 2, 2, 2, 2, 2, 2, 2]], jnp.int32)
    pn = jnp.array([[16, 0, 16, 0, 9, 0, 0, 0],
                    [16, 0, 5, 0, 0, 0, 0, 0],
                    [12, 0, 0, 0, 0, 0, 0, 0]], jnp.int32)
    o1 = hybrid_paged_attention(q, ks, vs, ap, sc, wk, wv, pt, pty, pn,
                                norm_type="layernorm",
                                pages_bound=pages_bound, interpret=True)
    o2 = hybrid_paged_attention_ref(q, ks, vs, ap, sc, wk, wv, pt, pty, pn,
                                    norm_type="layernorm")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


def test_hybrid_attention_pages_bound_guard():
    """ops wrapper rejects a pages_bound below the real used-page count
    (silent context truncation) when tables are concrete."""
    from repro.kernels.hybrid_attention.ops import paged_hybrid_attention
    B, kvh, g, D, T, d_model = 1, 1, 2, 16, 16, 32
    ks = jnp.zeros((2, T, kvh, D))
    vs = jnp.zeros((2, T, kvh, D))
    ap = jnp.zeros((1, T, d_model))
    q = jnp.ones((B, kvh, g, D))
    pt = jnp.array([[0, 1]], jnp.int32)
    pty = jnp.zeros((1, 2), jnp.int32)           # both pages used
    pn = jnp.full((1, 2), 16, jnp.int32)
    with pytest.raises(ValueError, match="pages_bound"):
        paged_hybrid_attention(q, ks, vs, ap, jnp.ones(d_model),
                               jnp.zeros((d_model, kvh, D)),
                               jnp.zeros((d_model, kvh, D)),
                               pt, pty, pn, norm_type="none", pages_bound=1,
                               interpret=True)


def test_hybrid_attention_act_heavy_table():
    """All-ACT page tables exercise the hoisted once-per-page norm path."""
    B, kvh, g, D, T, d_model = 2, 3, 2, 16, 16, 64
    ks = jnp.zeros((1, T, kvh, D))
    vs = jnp.zeros((1, T, kvh, D))
    ap = jax.random.normal(jax.random.PRNGKey(0), (6, T, d_model)) * 0.5
    q = jax.random.normal(jax.random.PRNGKey(1), (B, kvh, g, D))
    sc = 1 + jax.random.normal(jax.random.PRNGKey(2), (d_model,)) * 0.1
    wk = jax.random.normal(jax.random.PRNGKey(3), (d_model, kvh, D)) * 0.05
    wv = jax.random.normal(jax.random.PRNGKey(4), (d_model, kvh, D)) * 0.05
    pt = jnp.array([[0, 1, 2], [3, 4, 5]], jnp.int32)
    pty = jnp.ones((2, 3), jnp.int32)
    pn = jnp.array([[16, 16, 16], [16, 16, 7]], jnp.int32)
    for norm in ("rmsnorm", "layernorm"):
        o1 = hybrid_paged_attention(q, ks, vs, ap, sc, wk, wv, pt, pty, pn,
                                    norm_type=norm, interpret=True)
        o2 = hybrid_paged_attention_ref(q, ks, vs, ap, sc, wk, wv, pt, pty, pn,
                                        norm_type=norm)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


def test_hybrid_attention_pure_kv_matches_plain():
    """With only KV pages the kernel reduces to standard paged attention."""
    rng = jax.random.PRNGKey(0)
    B, kvh, g, D, T, d_model = 1, 2, 2, 16, 16, 64
    ks = jax.random.normal(rng, (3, T, kvh, D)) * 0.3
    vs = jax.random.normal(jax.random.PRNGKey(1), (3, T, kvh, D)) * 0.3
    ap = jnp.zeros((1, T, d_model))
    q = jax.random.normal(jax.random.PRNGKey(2), (B, kvh, g, D))
    wk = jnp.zeros((d_model, kvh, D))
    pt = jnp.array([[0, 1, 2]], jnp.int32)
    pty = jnp.zeros((1, 3), jnp.int32)
    pn = jnp.array([[16, 16, 16]], jnp.int32)
    o = hybrid_paged_attention(q, ks, vs, ap, jnp.ones(d_model), wk, wk,
                               pt, pty, pn, norm_type="none", interpret=True)
    # plain softmax reference over concatenated pages
    kcat = ks.reshape(48, kvh, D)
    vcat = vs.reshape(48, kvh, D)
    s = jnp.einsum("bhgd,shd->bhgs", q / np.sqrt(D), kcat)
    ref = jnp.einsum("bhgs,shd->bhgd", jax.nn.softmax(s, -1), vcat)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 64, 3, 16, 32, 16), (1, 128, 2, 32, 64, 32), (2, 32, 1, 8, 16, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_ssd_scan_sweep(b, s, h, p, n, chunk, dtype):
    rng = lambda i: jax.random.PRNGKey(i)
    x = (jax.random.normal(rng(0), (b, s, h, p)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(rng(1), (b, s, h))) * 0.5
    A = -jnp.exp(jax.random.normal(rng(2), (h,)) * 0.3)
    B = jax.random.normal(rng(3), (b, s, n)) * 0.3
    C = jax.random.normal(rng(4), (b, s, n)) * 0.3
    y1 = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    y2 = ssd_ref_sequential(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), atol=2e-3)


def test_ssd_scan_bf16():
    b, s, h, p, n = 1, 64, 2, 16, 32
    rng = lambda i: jax.random.PRNGKey(i)
    x = (jax.random.normal(rng(0), (b, s, h, p)) * 0.5).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(rng(1), (b, s, h))) * 0.5
    A = -jnp.exp(jax.random.normal(rng(2), (h,)) * 0.3)
    B = jax.random.normal(rng(3), (b, s, n)) * 0.3
    C = jax.random.normal(rng(4), (b, s, n)) * 0.3
    y1 = ssd_scan(x, dt, A, B, C, chunk=16, interpret=True)
    y2 = ssd_ref_sequential(x.astype(jnp.float32), dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), atol=5e-2)


# ---------------------------------------------------------------- flash attn

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref


@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 24)])
@pytest.mark.parametrize("H,KVH", [(4, 4), (4, 2), (4, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(causal, window, H, KVH, dtype):
    B, S, D = 2, 64, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D)).astype(dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KVH, D)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KVH, D)).astype(dtype)
    o1 = flash_attention(q, k, v, causal=causal, window=window,
                         q_chunk=16, k_chunk=16, interpret=True)
    o2 = flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=tol)


def test_flash_attention_matches_model_path():
    """Kernel == the pjit-path blockwise_attention used by the models."""
    from repro.models.layers import blockwise_attention
    B, S, H, KVH, D = 1, 96, 4, 2, 32
    q = jax.random.normal(jax.random.PRNGKey(3), (B, S, H, D))
    k = jax.random.normal(jax.random.PRNGKey(4), (B, S, KVH, D))
    v = jax.random.normal(jax.random.PRNGKey(5), (B, S, KVH, D))
    o1 = flash_attention(q, k, v, causal=True, q_chunk=32, k_chunk=32,
                         interpret=True)
    o2 = blockwise_attention(q, k, v, causal=True, q_chunk=32, k_chunk=32)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)
