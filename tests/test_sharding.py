"""PartitionSpec rules checked on abstract 16x16 and 2x16x16 meshes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.launch import specs as SP
from repro.sharding import batch_specs, cache_specs, params_specs

MESH1 = AbstractMesh((16, 16), ("data", "model"))
MESH2 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _check_divisibility(shapes, specs, mesh):
    flat_sh = jax.tree_util.tree_leaves(shapes)
    flat_sp = jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_sh) == len(flat_sp)
    for arr, spec in zip(flat_sh, flat_sp):
        for dim, ax in zip(arr.shape, tuple(spec) + (None,) * 10):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = int(np.prod([dict(zip(mesh.axis_names, mesh.axis_sizes))[a]
                                for a in axes]))
            assert dim % size == 0, (arr.shape, spec)


@pytest.mark.parametrize("arch", ["yi-6b", "whisper-base", "grok-1-314b",
                                  "jamba-1.5-large-398b", "mamba2-2.7b",
                                  "gemma3-27b"])
@pytest.mark.parametrize("mesh", [MESH1, MESH2])
@pytest.mark.parametrize("train", [False, True])
def test_param_specs_divisible(arch, mesh, train):
    cfg = get_config(arch)
    p_shape = SP.params_shape(cfg)
    specs = params_specs(cfg, p_shape, mesh, train=train)
    _check_divisibility(p_shape, specs, mesh)


def test_tp_shards_ffn():
    cfg = get_config("yi-6b")
    p_shape = SP.params_shape(cfg)
    specs = params_specs(cfg, p_shape, MESH1, train=False)
    assert "model" in jax.tree_util.tree_leaves(
        specs["layers"]["ffn"]["w1"], is_leaf=lambda x: isinstance(x, P))[0]


def test_fsdp_only_in_train():
    cfg = get_config("yi-6b")
    p_shape = SP.params_shape(cfg)
    serve = params_specs(cfg, p_shape, MESH1, train=False)
    train = params_specs(cfg, p_shape, MESH1, train=True)
    leaf = lambda t: t["layers"]["ffn"]["w1"]
    assert "data" not in tuple(leaf(serve))
    assert "data" in tuple(leaf(train))


def test_small_heads_replicate():
    """whisper's 8 heads can't shard on a 16-way model axis -> replicated wq
    output dim is still sharded via the flat q_dim (512 divides 16)."""
    cfg = get_config("whisper-base")
    p_shape = SP.params_shape(cfg)
    specs = params_specs(cfg, p_shape, MESH1, train=False)
    spec = specs["layers"]["attn"]["wq"]
    _check_divisibility(p_shape["layers"]["attn"]["wq"], spec, MESH1)


def test_cache_specs_decode_batch_sharded():
    cfg = get_config("yi-6b")
    c_shape = SP.cache_shape(cfg, 128, 1024)
    specs = cache_specs(cfg, c_shape, MESH1)
    assert tuple(specs["k"])[1] is not None          # batch axis sharded
    # yi-6b has 4 kv heads < 16-way model axis -> the SEQUENCE dim picks up
    # the idle 'model' axis instead (§Perf iteration 1)
    assert tuple(specs["k"])[2] == "model"
    assert tuple(specs["k"])[3] is None


def test_cache_specs_kv_heads_shard_when_divisible():
    cfg = get_config("gemma3-27b")                   # 16 kv heads
    c_shape = SP.cache_shape(cfg, 128, 1024)
    specs = cache_specs(cfg, c_shape, MESH1)
    assert tuple(specs["global_k"])[-2] == "model"   # kv heads shard
    assert tuple(specs["global_k"])[2] is None       # seq stays unsharded


def test_cache_specs_context_parallel_for_batch1():
    cfg = get_config("gemma3-27b")
    c_shape = SP.cache_shape(cfg, 1, 524288)
    specs = cache_specs(cfg, c_shape, MESH1)
    gk = tuple(specs["global_k"])
    assert gk[1] is None and gk[2] == "data"         # sequence-sharded cache


def test_batch_specs_multi_pod():
    cfg = get_config("yi-6b")
    b = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
    sp = batch_specs(cfg, b, MESH2)
    assert tuple(sp["tokens"])[0] == ("pod", "data")


# =============================================================================
# decision-log coverage: the silent-replication blind spot is closed
# =============================================================================

from repro.sharding import ShardLog, check_plan      # noqa: E402

REDUCED = ["opt-6.7b-reduced", "yi-6b-reduced", "minitron-4b-reduced"]
SHAPES_MATRIX = [(1, 1), (1, 2), (2, 2), (16, 16)]


@pytest.mark.parametrize("arch", REDUCED)
@pytest.mark.parametrize("mesh_shape", SHAPES_MATRIX)
def test_param_and_cache_decisions_fully_covered(arch, mesh_shape):
    """Every reduced config x mesh shape must produce a fully-covered,
    contradiction-free plan: every dim of every PARAM and CACHE leaf has
    exactly one logged decision, no mesh axis shards two dims of a leaf,
    and every wanted-but-dropped axis is an explicit drop record —
    ``explain()`` no longer records param decisions only."""
    cfg = get_config(arch)
    mesh = AbstractMesh(mesh_shape, ("data", "model"))
    p_shape = SP.params_shape(cfg)
    plog = ShardLog()
    p_specs = params_specs(cfg, p_shape, mesh, train=False, log=plog)
    check_plan(p_specs, plog)

    # the serving hybrid cache AND the plain decode cache both leave trails
    for c_shape in (SP.hybrid_cache_shape(cfg, 4, 128, 128),
                    SP.cache_shape(cfg, 4, 256)):
        clog = ShardLog()
        c_specs = cache_specs(cfg, c_shape, mesh, log=clog)
        check_plan(c_specs, clog)

    # drops are loud: on the 16x16 mesh SOME dim of a reduced config cannot
    # divide — the log must carry the drop with its reason
    if mesh_shape == (16, 16):
        drops = [d for d in plog.decisions + clog.decisions if d.dropped]
        assert drops, "a 16-way axis over a reduced config must drop somewhere"
        assert all("replicated" in d.reason for d in drops)


def test_explain_includes_decision_trail():
    cfg = get_config("opt-6.7b-reduced")
    mesh = AbstractMesh((1, 2), ("data", "model"))
    log = ShardLog()
    c_shape = SP.hybrid_cache_shape(cfg, 4, 128, 128)
    specs = cache_specs(cfg, c_shape, mesh, log=log)
    from repro.sharding import explain
    txt = explain(cfg, specs, log)
    assert "-- decisions" in txt
    # the KV-head dim of the hybrid cache is a logged 'model' shard
    assert any(d.key == "k" and d.got == "model" for d in log.decisions)
