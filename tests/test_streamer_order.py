"""Weight streamer hand-off order, slot safety and staging engagement
(DESIGN.md §8.2).

``WeightStreamer.acquire(i)`` waits for staging ``i``, tops the prefetch
window up to ``i + d``, and only then hands slot ``i`` to the device, so the
next layers' staging copies run under the hand-off.  These tests pin that
order (by what is in flight when ``device_put`` is called, not by timing),
that the earlier top-up never overwrites a slot whose device tree is still
live on a backend where ``device_put`` aliases host memory, and the
``streamer_acquires{key,shard}`` counter that says how often a staging had
landed before the caller asked for it.
"""
import jax
import numpy as np
import pytest

from repro.obs import MetricsRegistry
from repro.offload import FaultPlan, MeasuredTimeline
from repro.offload import streamer as streamer_mod
from repro.offload.streamer import WeightStreamer

N_LAYERS = 5
STEPS = 3


class PatternPool:
    """A ``HostWeightPool`` stand-in whose every layer holds a distinct
    byte pattern, so a staging copy landing in the wrong slot shows."""

    def __init__(self, n_layers: int = N_LAYERS):
        self._layers = [
            {"w": np.full((64, 128), l + 1, np.float32),
             "b": np.full((128,), 1000 + l, np.int32)}
            for l in range(n_layers)]
        self.layer_nbytes = [sum(a.nbytes for a in t.values())
                             for t in self._layers]

    def layer(self, l: int):
        return self._layers[l]


def _schedule():
    return [l for _ in range(STEPS) for l in range(N_LAYERS)]


def _land_in_flight(ws: WeightStreamer) -> None:
    """Wait out every in-flight staging: a forward long enough for the
    copy stream to finish the window."""
    for fut in list(ws._staging.values()):
        fut.result(timeout=30)


def _aligned_like(tree):
    """64-byte-aligned empty buffers shaped like ``tree``: the CPU
    backend's ``device_put`` aliases such host memory instead of copying."""
    def one(a):
        raw = np.empty(a.nbytes + 64, np.uint8)
        off = (-raw.ctypes.data) % 64
        return raw[off:off + a.nbytes].view(a.dtype).reshape(a.shape)
    return jax.tree.map(one, tree)


@pytest.fixture
def handoffs(monkeypatch):
    """Record, at every hand-off ``device_put`` the streamer makes, the
    schedule positions then in flight or landed but not yet acquired."""
    seen = []
    real = jax.device_put

    def recorder(x, *a, **kw):
        seen.append(set(recorder.streamer._staging))
        return real(x, *a, **kw)

    monkeypatch.setattr(streamer_mod.jax, "device_put", recorder)
    return recorder, seen


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_window_is_topped_up_before_the_handoff(handoffs, depth):
    """When position i's hand-off begins, positions i+1 .. min(i+d, n-1)
    are already submitted to the copy stream."""
    recorder, seen = handoffs
    sched = _schedule()
    n = len(sched)
    with WeightStreamer(PatternPool(), timeline=MeasuredTimeline(),
                        prefetch_depth=depth) as ws:
        recorder.streamer = ws
        ws.begin(sched)
        for i in range(n):
            ws.acquire(i)
            ws.release(i)
    assert len(seen) == n
    for i, ahead in enumerate(seen):
        assert ahead == set(range(i + 1, min(i + depth, n - 1) + 1)), i


def test_depth_zero_submits_nothing_ahead(handoffs):
    recorder, seen = handoffs
    with WeightStreamer(PatternPool(), timeline=MeasuredTimeline(),
                        prefetch_depth=0) as ws:
        recorder.streamer = ws
        ws.begin(_schedule())
        for i in range(len(_schedule())):
            ws.acquire(i)
            ws.release(i)
    assert seen and all(ahead == set() for ahead in seen)


def test_degraded_lane_submits_nothing_ahead(handoffs):
    """A stall that trips the watchdog on the pass's first staging drops
    the lane to degraded: that acquire and every later one hand off with
    nothing in flight."""
    recorder, seen = handoffs
    plan = FaultPlan(0, stall_p=1.0, stall_s=1.0, max_events=1)
    with WeightStreamer(PatternPool(), timeline=MeasuredTimeline(),
                        prefetch_depth=2, faults=plan,
                        watchdog_s=0.05) as ws:
        recorder.streamer = ws
        ws.begin(_schedule())
        for i in range(len(_schedule())):
            ws.acquire(i)
            ws.release(i)
        assert ws.lane_health == "degraded"
        assert ws.counters["watchdog_timeouts"] == 1
    assert seen and all(ahead == set() for ahead in seen)


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_live_trees_survive_the_earlier_topup(depth):
    """Slot safety under the new order on a backend that aliases host
    memory: once the stagings an acquire submitted have landed, and before
    the acquired position is released, every live device tree still holds
    its own layer's bytes."""
    pool = PatternPool()
    sched = _schedule()
    with WeightStreamer(pool, timeline=MeasuredTimeline(),
                        prefetch_depth=depth) as ws:
        ws._slots = [_aligned_like(pool.layer(0)) for _ in ws._slots]
        ws.begin(sched)
        for i in range(len(sched)):
            dev = ws.acquire(i)
            if i == 0:      # the premise: the hand-off aliases its slot
                assert (dev["w"].unsafe_buffer_pointer()
                        == ws._slots[0]["w"].ctypes.data)
            _land_in_flight(ws)
            for j, tree in ws._live.items():
                want = pool.layer(sched[j])
                for k in want:
                    np.testing.assert_array_equal(np.asarray(tree[k]),
                                                  want[k], err_msg=f"{i} {j}")
            ws.release(i)


# =============================================================================
# streamer_acquires: the staging engagement counter
# =============================================================================

def _run_pass(ws, sched, *, land: bool):
    """acquire -> (forward) -> release over ``sched``; -> the key each
    acquire counted."""
    keys = []
    ws.begin(sched)
    for i in range(len(sched)):
        before = dict(ws.acquires)
        ws.acquire(i)
        keys.append(next(k for k in before if ws.acquires[k] > before[k]))
        if land:
            _land_in_flight(ws)
        ws.release(i)
    return keys


def test_acquires_count_landed_stagings():
    """With staging done inside every forward at depth 1, every acquire
    after a pass's first finds its staging landed; counts carry across
    passes."""
    sched = _schedule()
    with WeightStreamer(PatternPool(), timeline=MeasuredTimeline(),
                        prefetch_depth=1) as ws:
        for _ in range(2):
            keys = _run_pass(ws, sched, land=True)
            assert keys[1:] == ["landed"] * (len(sched) - 1)
        assert ws.acquires["landed"] + ws.acquires["waited"] == 2 * len(sched)
        assert ws.acquires["landed"] >= 2 * (len(sched) - 1)


def test_acquires_count_a_staging_in_flight_as_waited():
    """A staging still copying when the caller asks counts as waited: the
    pass's first two stagings are slowed by half a second each."""
    plan = FaultPlan(0, slow_p=1.0, slow_s=0.5, max_events=2)
    with WeightStreamer(PatternPool(), timeline=MeasuredTimeline(),
                        prefetch_depth=1, faults=plan) as ws:
        keys = _run_pass(ws, _schedule(), land=False)
    assert keys[:2] == ["waited", "waited"]


def test_acquires_count_none_landed_inline_or_degraded():
    """An inline depth-0 stage and a degraded lane's emergency stage never
    count as landed."""
    sched = _schedule()
    with WeightStreamer(PatternPool(), timeline=MeasuredTimeline(),
                        prefetch_depth=0) as ws:
        _run_pass(ws, sched, land=True)
        assert dict(ws.acquires) == {"landed": 0, "waited": len(sched)}
    plan = FaultPlan(0, stall_p=1.0, stall_s=1.0, max_events=1)
    with WeightStreamer(PatternPool(), timeline=MeasuredTimeline(),
                        prefetch_depth=1, faults=plan,
                        watchdog_s=0.05) as ws:
        _run_pass(ws, sched, land=True)
        assert ws.lane_health == "degraded"
        assert dict(ws.acquires) == {"landed": 0, "waited": len(sched)}


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs XLA_FLAGS=--xla_force_host_platform_"
                           "device_count>=2")
def test_acquires_count_per_shard_on_sharded_lanes():
    """Each mesh position's lane counts its own acquires in the shared
    registry, under its ``shard`` label."""
    from repro.configs import get_config
    from repro.launch.mesh import make_test_mesh
    from repro.models import model as M
    from repro.offload.host_pool import HostWeightPool
    from repro.offload.streamer import ShardedWeightLanes
    from repro.sharding import make_shard_plan

    cfg = get_config("opt-6.7b-reduced")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    plan = make_shard_plan(cfg, make_test_mesh(1, 2), params)
    reg = MetricsRegistry()
    sched = [l for _ in range(STEPS) for l in range(cfg.num_layers)]
    with ShardedWeightLanes(HostWeightPool(cfg, params, plan=plan), plan,
                            timeline=MeasuredTimeline(), prefetch_depth=1,
                            metrics=reg) as lanes:
        lanes.begin(sched)
        for i in range(len(sched)):
            lanes.acquire(i)
            for lane in lanes.lanes:
                _land_in_flight(lane)
            lanes.release(i)
    snap = reg.snapshot()
    for shard in (0, 1):
        landed = snap[f"streamer_acquires{{key=landed,shard={shard}}}"]
        waited = snap[f"streamer_acquires{{key=waited,shard={shard}}}"]
        assert landed + waited == len(sched)
        assert landed >= len(sched) - 1
