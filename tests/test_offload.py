"""Host-offload runtime: pool invariants, streamer exactness, measured
timelines (DESIGN.md §8).

The offload executor must be a bit-for-bit stand-in for the device-resident
decode loop — same tokens at every prefetch depth, with and without KV
spill — while its pools' physical accounting mirrors the BlockManager's
logical accounting.
"""
import jax
import numpy as np
import pytest
from _compat import given, settings, st

from repro.configs import get_config
from repro.configs.offload import OffloadBudget, offload_budget
from repro.core import costmodel as cm
from repro.core.blocks import (BlockManager, BlockType, Location,
                               kv_block_bytes)
from repro.core.pipeline import MiniBatchSpec, TimelineResult, simulate_steps
from repro.data import request_trace
from repro.models import model as M
from repro.offload import HostBlockPool, MeasuredTimeline
from repro.serving import HybridServeEngine


@pytest.fixture(scope="module")
def setup_opt():
    cfg = get_config("opt-6.7b-reduced")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    reqs = request_trace(cfg.vocab_size, 4, prompt_mean=40, gen_tokens=8,
                         seed=3)
    eng = HybridServeEngine(cfg, params, mode="hybrid", max_minibatch=4,
                            kv_cap=128, act_cap=128)
    ref, _ = eng.generate(reqs)          # the device-resident scan loop
    return cfg, params, reqs, ref


@pytest.fixture(scope="module")
def setup_yi():
    cfg = get_config("yi-6b-reduced")
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    reqs = request_trace(cfg.vocab_size, 3, prompt_mean=30, gen_tokens=6,
                         seed=7)
    eng = HybridServeEngine(cfg, params, mode="hybrid", max_minibatch=3,
                            kv_cap=128, act_cap=128)
    ref, _ = eng.generate(reqs)
    return cfg, params, reqs, ref


# =============================================================================
# token exactness vs the device-resident hybrid_decode_loop
# =============================================================================

@pytest.mark.parametrize("depth", [0, 1, 2])
def test_offload_token_exact_prefetch_depths(setup_opt, depth):
    """Streamed execution at prefetch depth 0 (synchronous), 1 (double
    buffered) and 2 must emit the exact tokens of the monolithic scan."""
    cfg, params, reqs, ref = setup_opt
    budget = offload_budget(cfg, cm.TPU_V5E)
    eng = HybridServeEngine(
        cfg, params, mode="hybrid", max_minibatch=4, kv_cap=128, act_cap=128,
        offload=True,
        budget=OffloadBudget(budget.dev_bytes, prefetch_depth=depth))
    out, stats = eng.generate(reqs)
    for r in reqs:
        np.testing.assert_array_equal(out[r.rid], ref[r.rid])
    assert stats.measured_time > 0 and stats.measured_gpu_busy > 0
    assert eng.executor.streamer.uploads > 0


def test_offload_token_exact_gqa_rope(setup_yi):
    """Second reduced config (GQA + RoPE): the per-layer sincos/act_pos
    staging must match the monolithic step exactly."""
    cfg, params, reqs, ref = setup_yi
    eng = HybridServeEngine(cfg, params, mode="hybrid", max_minibatch=3,
                            kv_cap=128, act_cap=128, offload=True)
    out, _ = eng.generate(reqs)
    for r in reqs:
        np.testing.assert_array_equal(out[r.rid], ref[r.rid])


def test_offload_spill_and_resident_paths_exact(setup_opt):
    """mode='kv' maximises the KV region.  Under the tight config-driven
    budget it physically spills to the pinned host arena (kv_load traffic >
    0); under a generous budget it stays device-resident (migrations
    counted, no kv traffic).  Both paths must match the monolithic loop."""
    cfg, params, reqs, _ = setup_opt
    eng_ref = HybridServeEngine(cfg, params, mode="kv", max_minibatch=4,
                                kv_cap=128, act_cap=128)
    ref, _ = eng_ref.generate(reqs)

    tight = HybridServeEngine(cfg, params, mode="kv", max_minibatch=4,
                              kv_cap=128, act_cap=128, offload=True)
    out, _ = tight.generate(reqs)
    for r in reqs:
        np.testing.assert_array_equal(out[r.rid], ref[r.rid])
    kv_traffic = sum(m.traffic["kv_load"] for m in tight.measured_steps)
    store_traffic = sum(m.traffic["store"] for m in tight.measured_steps)
    assert kv_traffic > 0, "tight budget must force real spill"
    assert store_traffic > 0, "spilled KV must store new rows upstream"
    assert tight.spill_kv_pool.allocated_blocks == 0   # regions returned
    tight.spill_kv_pool.check_invariants()

    roomy = HybridServeEngine(cfg, params, mode="kv", max_minibatch=4,
                              kv_cap=128, act_cap=128, offload=True,
                              budget=OffloadBudget(dev_bytes=1 << 30))
    out2, _ = roomy.generate(reqs)
    for r in reqs:
        np.testing.assert_array_equal(out2[r.rid], ref[r.rid])
    assert sum(m.traffic["kv_load"] for m in roomy.measured_steps) == 0
    moved = roomy.blockman.transitions.get(
        (BlockType.KV, Location.HOST, Location.DEVICE), 0)
    assert moved > 0, "device-resident groups must migrate KV blocks"
    for pool in roomy.blockman.pools.values():
        assert pool.allocated == 0


def test_offload_scheduler_exact(setup_opt):
    """Continuous batching with the layer-streamed decode step stays
    token-exact while requests churn through the slot pool."""
    from repro.serving.scheduler import ContinuousBatchingServer
    cfg, params, reqs, _ = setup_opt
    srv_ref = ContinuousBatchingServer(cfg, params, slots=2, kv_cap=128,
                                       act_cap=128)
    ref, _ = srv_ref.run(reqs)
    with ContinuousBatchingServer(cfg, params, slots=2, kv_cap=128,
                                  act_cap=128, offload=True) as srv:
        out, stats = srv.run(reqs)
        for r in reqs:
            np.testing.assert_array_equal(out[r.rid], ref[r.rid])
        assert stats.generated_tokens == sum(r.max_new_tokens for r in reqs)
        meas = srv.measured_steps
        assert len(meas) >= stats.steps
        assert all(m.gpu_busy > 0 for m in meas)


# =============================================================================
# measured timeline schema vs the analytic simulator
# =============================================================================

def test_measured_timeline_schema_matches_simulate_steps(setup_opt):
    cfg, params, reqs, _ = setup_opt
    eng = HybridServeEngine(cfg, params, mode="hybrid", max_minibatch=4,
                            kv_cap=128, act_cap=128, offload=True)
    _, stats = eng.generate(reqs)
    sim = simulate_steps(cfg, eng.hw,
                         [[MiniBatchSpec(2, 32, 32, 0, ctx_tokens=64)]])[0]
    assert len(eng.measured_steps) == stats.steps
    for m in eng.measured_steps:
        assert isinstance(m, TimelineResult) and type(m) is type(sim)
        assert set(m.traffic) == set(sim.traffic)      # same categories
        assert m.total > 0
        assert 0 <= m.gpu_busy and 0 <= m.pcie_busy
        assert 0.0 <= m.gpu_util <= 1.0 + 1e-9
        assert m.traffic["weights"] > 0                # weights streamed
        assert all(f <= m.total + 1e-9 for f in m.finish)
    # measured aggregates line up with the per-step results
    assert stats.measured_time == pytest.approx(
        sum(m.total for m in eng.measured_steps))


def test_timeline_step_attribution():
    tl = MeasuredTimeline()
    tl.begin_step("decode")
    with tl.task("gpu", "fwd"):
        pass
    with tl.task("pcie", "w", nbytes=100):
        pass
    tl.begin_step("decode")
    with tl.task("pcie_up", "st", nbytes=7):
        pass
    assert len(tl.results("decode")) == 1      # in-flight step not included
    tl.end_step()
    res = tl.results("decode")
    assert len(res) == 2
    assert res[0].traffic["weights"] == 100 and res[0].gpu_busy > 0
    assert res[1].traffic["store"] == 7
    assert res[1].gpu_busy == 0.0
    assert tl.drain() and not tl.results()             # drain resets


# =============================================================================
# overlap: the acceptance criterion, measured
# =============================================================================

def test_weight_stream_overlap_beats_serial():
    """Overlapped streaming must be strictly faster than stream-only +
    compute-only on the same workload — the copy stream genuinely hides
    the staging transfers behind compute.  (Runs in a subprocess pinning
    compute to one core so the two lanes map to distinct resources; see
    offload/microbench.py:BENCH_XLA_FLAGS.)"""
    from repro.offload.microbench import weight_stream_microbench
    r = weight_stream_microbench()
    assert r["overlap_s"] < r["stream_s"] + r["compute_s"], r
    assert r["saving_s"] > 0


# =============================================================================
# host pool alloc/free invariants vs BlockManager accounting
# =============================================================================

@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_host_pool_matches_blockmanager_accounting(seed):
    """Random open/close request traffic driven through BOTH allocators:
    the pinned arena's physical block count must track the BlockManager's
    host-KV accounting exactly, regions must never overlap (byte patterns
    survive neighbours' churn), and the free list must conserve capacity."""
    cfg = get_config("opt-6.7b-reduced")
    rng = np.random.default_rng(seed)
    cap = int(rng.integers(8, 40))
    pool = HostBlockPool(cap, kv_block_bytes(cfg))
    bm = BlockManager(cfg, host_kv_blocks=cap, host_act_blocks=1,
                      dev_kv_blocks=0, dev_act_blocks=0)
    live = {}                                   # rid -> (region, n, fill byte)
    next_rid = 0
    for _ in range(60):
        if live and (rng.random() < 0.4 or len(live) > 10):
            rid = int(rng.choice(list(live)))
            region, n, fill = live.pop(rid)
            view = region.view((region.nbytes,), np.uint8)
            assert (view == fill).all(), "neighbour overwrote live region"
            region.free()
            bm.free_request(rid)
        else:
            n = int(rng.integers(1, 6))
            rid = next_rid
            next_rid += 1
            bm.new_request(rid)
            ok = all(bm.append_token(rid, BlockType.KV) is not None
                     for _ in range(n * 16))
            region = pool.alloc(n) if ok else None
            if region is None:                  # either side full: roll back
                bm.free_request(rid)
            else:
                fill = rid % 251 + 1
                region.view((region.nbytes,), np.uint8)[:] = fill
                live[rid] = (region, n, fill)
        pool.check_invariants()
        host_kv = bm.pools[(BlockType.KV, Location.HOST)]
        assert pool.allocated_blocks == host_kv.allocated
        assert pool.allocated_blocks == sum(n for _, n, _ in live.values())
    for rid, (region, n, fill) in list(live.items()):
        region.free()
        bm.free_request(rid)
    pool.check_invariants()
    assert pool.allocated_blocks == 0 and pool.free_blocks == cap


def test_host_pool_alloc_edge_cases():
    cfg = get_config("opt-6.7b-reduced")
    pool = HostBlockPool(4, kv_block_bytes(cfg))
    a = pool.alloc(3)
    assert a is not None and pool.alloc(2) is None     # only 1 left
    b = pool.alloc(1)
    assert b is not None and pool.free_blocks == 0
    a.free()
    with pytest.raises(ValueError):
        a.free()                                        # double free
    c = pool.alloc(3)                                   # coalesced reuse
    assert c is not None and c.offset == 0
    with pytest.raises(ValueError):
        pool.alloc(0)
    with pytest.raises(ValueError):
        c.view((c.nbytes + 1,), np.uint8)               # oversized view


def test_blockmanager_move_block_accounting():
    cfg = get_config("opt-6.7b-reduced")
    bm = BlockManager(cfg, host_kv_blocks=4, host_act_blocks=4,
                      dev_kv_blocks=1, dev_act_blocks=4)
    bm.new_request(0)
    for _ in range(3 * 16):
        assert bm.append_token(0, BlockType.KV) is not None
    # only one device slot: first move lands, second refuses, nothing leaks
    assert bm.move_block(0, 0, Location.DEVICE)
    assert not bm.move_block(0, 1, Location.DEVICE)
    assert bm.counts(0)["dev_blocks"] == 1
    assert bm.transitions[(BlockType.KV, Location.HOST,
                           Location.DEVICE)] == 1
    assert bm.migrate(0, BlockType.KV, Location.HOST) == 1  # move it back
    assert bm.counts(0)["dev_blocks"] == 0
    bm.free_request(0)
    for pool in bm.pools.values():
        assert pool.allocated == 0
