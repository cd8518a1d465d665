"""Ahead-of-time compiles for a TPU v5e at published widths.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached (``v5e:2x2``), so these tests need no accelerator:
they catch what interpret mode cannot — block shapes the chip's tiling
refuses, and programs that do not fit its memory.  Nothing runs, so nothing
here is a timing or a result.

Every chip compile of the suite lives in this one file.  The topology is
described inside a module-scoped fixture, never at import: only one process
may load the TPU library, and the suite runs under several workers.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.models import model as M
from repro.models import transformer as T

# 15.75 GiB: the allocator limit of one v5e chip, as its compiler reports it
V5E_HBM_LIMIT = int(15.75 * 2**30)

WIDTHS = ["opt-6.7b", "yi-6b"]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                     # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back without one, so
    # keep these compiles out of any persistent cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _on(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _paged_args(cfg, sharding, *, quantized):
    """hybrid_paged_attention operands at ``cfg``'s widths: 8 requests, 32
    pages of 16 tokens each, KV and ACT pools of 64 pages."""
    B, MAXP, P, T_ = 8, 32, 64, 16
    KVH, D, d = cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    G = cfg.num_heads // KVH
    pool = "int8" if quantized else cfg.dtype
    args = [_spec((B, KVH, G, D), cfg.dtype, sharding),
            _spec((P, T_, KVH, D), pool, sharding),
            _spec((P, T_, KVH, D), pool, sharding),
            _spec((P, T_, d), pool, sharding),
            _spec((d,), cfg.dtype, sharding),
            _spec((d, KVH, D), cfg.dtype, sharding),
            _spec((d, KVH, D), cfg.dtype, sharding)]
    args += [_spec((B, MAXP), "int32", sharding)] * 3
    kw = {}
    if quantized:
        kw = dict(k_scales=_spec((P, T_, KVH, 1), "float16", sharding),
                  v_scales=_spec((P, T_, KVH, 1), "float16", sharding),
                  act_scales=_spec((P, T_, 1), "float16", sharding))
    return args, kw


@pytest.mark.parametrize("variant", ["plain", "quantized", "return_lse"])
@pytest.mark.parametrize("name", WIDTHS)
def test_hybrid_paged_attention_compiles(one_chip, name, variant):
    from repro.kernels.hybrid_attention.kernel import hybrid_paged_attention
    cfg = get_config(name)
    args, kw = _paged_args(cfg, one_chip, quantized=variant == "quantized")
    fn = functools.partial(hybrid_paged_attention, norm_type=cfg.norm_type,
                           return_lse=variant == "return_lse")
    compiled = jax.jit(lambda *a, **k: fn(*a, **k)).lower(*args, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", WIDTHS)
def test_kv_gen_compiles(one_chip, name):
    from repro.kernels.kv_gen.kernel import kv_gen
    cfg = get_config(name)
    n, d, KVH, D = 64, cfg.d_model, cfg.num_kv_heads, cfg.head_dim
    args = (_spec((n, 16, d), cfg.dtype, one_chip),
            _spec((d,), cfg.dtype, one_chip),
            _spec((d, KVH, D), cfg.dtype, one_chip),
            _spec((d, KVH, D), cfg.dtype, one_chip))
    fn = functools.partial(kv_gen, norm_type=cfg.norm_type)
    compiled = jax.jit(lambda *a: fn(*a)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    """Full-sequence causal attention at yi-6b widths (GQA 32/4, D 128)."""
    from repro.kernels.flash_attention.kernel import flash_attention
    cfg = get_config("yi-6b")
    S, D = 2048, cfg.head_dim
    q = _spec((1, S, cfg.num_heads, D), cfg.dtype, one_chip)
    kv = _spec((1, S, cfg.num_kv_heads, D), cfg.dtype, one_chip)
    compiled = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=True)).lower(
            q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_offload_layer_step_compiles(one_chip):
    """The offload executor's per-layer decode dispatch at opt-6.7b width
    (one layer's weights, KV and ACT regions of 512 tokens, 8 requests)."""
    cfg = get_config("opt-6.7b")
    B, cap = 8, 512
    lp = jax.eval_shape(
        lambda r: T._layer(r, cfg, "attn", False), jax.random.PRNGKey(0))
    kv = _spec((B, cap, cfg.num_kv_heads, cfg.head_dim), cfg.dtype, one_chip)
    args = (_on(lp, one_chip), kv, kv,
            _spec((B, cap, cfg.d_model), cfg.dtype, one_chip),
            _spec((B, 1, cfg.d_model), cfg.dtype, one_chip),
            _spec((B,), "int32", one_chip), _spec((B,), "int32", one_chip),
            _spec((B,), "bool", one_chip))

    def step(lp, kc, vc, ac, h, kv_len, act_len, store):
        return M._hybrid_layer_step(lp, cfg, h, kc, vc, ac, kv_len, act_len,
                                    store, None, None, False,
                                    kv_bound=cap, act_bound=cap)

    compiled = jax.jit(step, donate_argnums=(1, 2, 3)).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < \
        V5E_HBM_LIMIT


def test_yi_decode_chunk_fits_one_chip(one_chip):
    """yi-6b device-resident: the chunked decode dispatch at the smoke's
    batch and caps (4 slots, 512-token KV and ACT regions, 8 steps) must
    fit one chip's memory with its weights as arguments."""
    cfg = get_config("yi-6b")
    slots, cap, steps = 4, 512, 8
    params = _on(jax.eval_shape(
        lambda r: M.init_params(cfg, r), jax.random.PRNGKey(0)), one_chip)
    cache = _on(jax.eval_shape(
        lambda: M.init_hybrid_cache(cfg, slots, cap, cap)), one_chip)
    cur = _spec((slots,), "int32", one_chip)
    sched = _spec((steps, slots), "bool", one_chip)

    def chunk(params, cur, cache, store, active):
        return M.hybrid_decode_chunk(params, cfg, cur, cache, store, active,
                                     kv_bound=cap, act_bound=cap)

    compiled = jax.jit(chunk, donate_argnums=(2,)).lower(
        params, cur, cache, sched, sched).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_LIMIT, (
        f"arguments {mem.argument_size_in_bytes} + temporaries "
        f"{mem.temp_size_in_bytes} B exceed one v5e chip")
