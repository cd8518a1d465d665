"""The offload decode step's critical path in the program's own spans.

  * the compute thread of a served offload chunk is tiled by lane spans:
    per decode step ``pre``, then per layer the weight wait, the hand-off
    and the forward, then ``post``, in order and disjoint;
  * ``host`` lane spans reach the tracer but never a ``TimelineResult``'s
    lane totals;
  * the same spans are profiler annotations (``offload.*``/``serve.*``) in
    the ``.xplane.pb`` the benchmark reads;
  * the scheduler's ``chunk``/``admit`` spans end after the host readback,
    and the registry's ``ttft_s``/``tbt_s``/``queue_wait_s`` histograms
    observe wall-clock seconds.
"""
import dataclasses
import itertools
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.pipeline import Request, _zipf
from repro.models import model as M
from repro.obs import MetricsRegistry, PID_LANES, Tracer
from repro.offload.timeline import HOST, MeasuredTimeline
from repro.serving.scheduler import ContinuousBatchingServer

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("opt-6.7b-reduced")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=_zipf(rng, 1.2, cfg.vocab_size, 24 + 8 * i)
                    .astype(np.int32), max_new_tokens=6) for i in range(2)]
    return cfg, params, reqs


def _serve(cfg, params, reqs, **kw):
    with ContinuousBatchingServer(cfg, params, slots=2, kv_cap=64,
                                  act_cap=64, **kw) as srv:
        out, stats = srv.run(reqs)
    return out, stats


def test_decode_step_is_tiled_on_the_compute_thread(tiny):
    cfg, params, reqs = tiny
    tracer = Tracer()
    _serve(cfg, params, reqs, chunk_steps=2, offload=True, tracer=tracer)
    lane = [e for e in tracer.events()
            if e["pid"] == PID_LANES and e["ph"] == "X"]
    compute = sorted(
        (e for e in lane if e["cat"] == f"lane:{HOST}"
         or e["cat"] == "lane:gpu"
         or (e["cat"] == "lane:pcie" and e["name"] == "w"
             and e["args"]["nbytes"] == 0)),
        key=lambda e: e["ts"])
    # disjoint: one thread, one span at a time
    for a, b in zip(compute, compute[1:]):
        assert a["ts"] + a["dur"] <= b["ts"], (a, b)
    names = [e["name"] if e["cat"] != "lane:pcie" else "handoff"
             for e in compute]
    # decode steps: pre, L x (w_wait, hand-off, fwd), post
    L = cfg.num_layers
    step = ["pre"] + ["w_wait", "handoff", "fwd"] * L + ["post"]
    decode = [i for i, n in enumerate(names) if n == "pre"]
    assert decode, names
    for i in decode:
        assert names[i:i + len(step)] == step, names[i:i + len(step)]
    # a chunk opens with the unstack and closes with the restack
    assert names.count("unstack") == names.count("restack") > 0
    # admissions stream their layers too: wait, hand-off, forward
    prefill = names[:names.index("unstack")]
    assert prefill == ["w_wait", "handoff", "fwd"] * L


def test_host_spans_leave_the_lane_totals_alone():
    def build(with_host):
        tl = MeasuredTimeline()
        tl.begin_step("decode", now=0.0)
        if with_host:
            tl.record(HOST, "pre", 0.0, 0.05)
            tl.record(HOST, "w_wait", 0.05, 0.4)
        tl.record("pcie", "w", 0.4, 0.6)
        tl.record("gpu", "fwd", 0.6, 0.9)
        if with_host:
            tl.record(HOST, "post", 0.9, 1.2)
        tl.end_step(now=1.0)
        if with_host:                     # outside any step: no new step
            tl.record(HOST, "restack", 1.0, 1.1)
        return tl

    a, b = build(False), build(True)
    assert a.step_tags() == b.step_tags() == ["decode"]
    ra, rb = a.results(), b.results()
    assert [dataclasses.asdict(r) for r in ra] == \
        [dataclasses.asdict(r) for r in rb]
    assert (rb[0].pcie_busy, rb[0].gpu_busy, rb[0].cpu_busy) == \
        pytest.approx((0.2, 0.3, 0.0))
    assert HOST not in rb[0].tag_busy and "w_wait" not in rb[0].tag_busy


def test_host_spans_reach_the_tracer():
    tracer = Tracer()
    tl = MeasuredTimeline(tracer=tracer)
    with tl.task(HOST, "w_wait"):
        pass
    (ev,) = tracer.events()
    assert (ev["name"], ev["cat"]) == ("w_wait", f"lane:{HOST}")
    assert tl.step_tags() == [] and tl.results() == []


def test_annotations_reach_the_profiler_trace(tiny, tmp_path):
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench import devtrace
    cfg, params, reqs = tiny
    _serve(cfg, params, reqs[:1], chunk_steps=2, offload=True)   # compile
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(cfg, params, reqs[:1], chunk_steps=2, offload=True)
    finally:
        jax.profiler.stop_trace()
    ev = devtrace.read_events(str(tmp_path))
    host = {(e.plane, e.name) for e in ev if not devtrace.is_device(e)}
    names = {n for p, n in host if p.startswith("/host:")}
    for want in ("offload.w_wait", "offload.w_handoff", "offload.w_stage",
                 "offload.fwd", "offload.pre", "offload.post",
                 "serve.chunk", "serve.admit", "serve.replay"):
        assert want in names, (want, sorted(n for n in names
                                            if "." in n)[:40])


class _Readback:
    """A device result whose host readback is stamped on ``clock``."""

    def __init__(self, value, clock, log):
        self.value, self.clock, self.log = value, clock, log

    def __array__(self, dtype=None, copy=None):
        self.log.append(self.clock())
        return np.asarray(self.value, dtype)


def test_resident_spans_end_after_the_readback(tiny):
    cfg, params, reqs = tiny
    ticks = itertools.count()
    clock = lambda: float(next(ticks))
    tracer, reads = Tracer(clock=clock), {"chunk": [], "admit": []}
    srv = ContinuousBatchingServer(cfg, params, slots=2, kv_cap=64,
                                   act_cap=64, chunk_steps=2, tracer=tracer)
    decode, admit = srv._decode_chunk_jit, srv._admit_jit

    def decode_probe(*a, **k):
        toks, cur, cache = decode(*a, **k)
        return _Readback(toks, clock, reads["chunk"]), cur, cache

    def admit_probe(*a, **k):
        cur, cache = admit(*a, **k)
        return _Readback(cur, clock, reads["admit"]), cache

    srv._decode_chunk_jit, srv._admit_jit = decode_probe, admit_probe
    srv.run(reqs)
    for name in ("chunk", "admit"):
        spans = [e for e in tracer.events()
                 if e["name"] == name and e["cat"] == "server"]
        assert len(spans) == len(reads[name]) > 0
        for e, t in zip(spans, reads[name]):
            assert e["ts"] < t < e["ts"] + e["dur"]


def test_latency_histograms_read_the_wall_clock(tiny):
    cfg, params, reqs = tiny
    reg = MetricsRegistry()
    _, stats = _serve(cfg, params, reqs, chunk_steps=2, metrics=reg)
    snap = reg.snapshot()
    for name in ("ttft_s", "tbt_s", "queue_wait_s"):
        assert snap[name]["count"] == len(reqs), name
    # wall-clock: every first token is delivered at or after its queue
    # entry, and the simulator's guesses are no longer what they report
    for r in reqs:
        assert stats.first_token_at[r.rid] >= stats.queued_at[r.rid]
    wall = [stats.first_token_at[r.rid] - stats.queued_at[r.rid]
            for r in reqs]
    assert snap["ttft_s"]["mean"] == pytest.approx(np.mean(wall))
    assert snap["ttft_s"]["mean"] != pytest.approx(
        np.mean(list(stats.ttft.values())))
