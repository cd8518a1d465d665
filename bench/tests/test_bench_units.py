"""The benchmark's yardstick on small inputs: counts against hand counts, the
trace reduction on a synthetic trace, latencies from a synthetic delivery
log, the traffic generator, and discovery of data files by name."""
import dataclasses
import json
import math
from pathlib import Path

import numpy as np
import pytest

from bench import correct, counts, devtrace, spec, traffic
from bench.devtrace import Event
from bench.tap import Chunk, Tap
from bench.window import Window

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"


def _config(name):
    """A benchmark configuration, or a test's own (Yi-6B, for its GQA and
    gated FFN counts)."""
    path = BENCH / "configs" / f"{name}.json"
    with open(path if path.is_file() else DATA / f"{name}.json") as f:
        return json.load(f)


# ------------------------------------------------------------------- counts
@pytest.mark.parametrize("name,layer_params,kv,act", [
    # OPT-6.7B: 4 d^2 attention + 2 d 4d ReLU FFN + two LayerNorms (gain,
    # bias); MHA K/V: 32 layers x 2 x 4096 x 2 bytes
    ("opt-6.7b-offload", 4 * 4096 ** 2 + 2 * 4096 * 16384 + 4 * 4096,
     32 * 2 * 4096 * 2, 32 * 4096 * 2),
    # Yi-6B: q, o 4096^2; k, v 4096 x 512 (4 KV heads); SwiGLU 3 x 4096 x
    # 11008; two RMSNorm gains; GQA K/V: 32 x 2 x 512 x 2 bytes
    ("yi-6b-resident", 2 * 4096 ** 2 + 2 * 4096 * 512 + 3 * 4096 * 11008
     + 2 * 4096, 32 * 2 * 512 * 2, 32 * 4096 * 2),
])
def test_counts_match_hand_counts(name, layer_params, kv, act):
    c = _config(name)
    assert counts.layer_params(c) == layer_params
    assert counts.layer_bytes(c) == 2 * layer_params
    assert counts.kv_bytes_per_token(c) == kv
    assert counts.act_bytes_per_token(c) == act


def test_opt_layer_is_403_mb_and_kv_twice_act():
    c = _config("opt-6.7b-offload")
    assert counts.layer_bytes(c) == 402_685_952
    assert counts.kv_bytes_per_token(c) == 2 * counts.act_bytes_per_token(c)


@pytest.mark.parametrize("name", ["opt-6.7b-offload", "yi-6b-resident"])
def test_decode_and_prefill_flops_by_hand(name):
    c = _config(name)
    L, d, H, D = 32, 4096, 32, 128
    norms = 2 * d * (2 if c["norm"] == "layernorm" else 1)
    linear = 2 * L * (counts.layer_params(c) - norms)
    head = 2 * d * c["vocab_rows"]
    ctx = 1000
    assert counts.decode_flops(c, ctx) == linear + 4 * L * H * D * (ctx + 1) \
        + head
    S = 64
    assert counts.prefill_flops(c, S) == pytest.approx(
        S * linear + 4 * L * H * D * S * (S + 1) / 2 + head)
    # regenerating K/V from ACT: one d x (2 KVH D) product per layer
    kvd = 2 * c["num_key_value_heads"] * D
    assert counts.regen_flops(c, 10) == 2 * L * 10 * d * kvd


def test_decode_step_bytes_by_hand():
    c = _config("yi-6b-resident")
    weights = 32 * counts.layer_bytes(c) + 4096 * 64000 * 2
    got = counts.decode_step_bytes(c, kv_tokens=100, act_tokens=10, slots=2)
    assert got == weights + 100 * 65536 + 10 * 262144 + 2 * 65536


# ------------------------------------------------------------- device trace
def _synthetic_trace():
    dev, host = "/device:TPU:0", "/host:CPU"
    return [
        Event(dev, "XLA Ops", "fusion.1", 0, 10),
        Event(dev, "XLA Ops", "fusion.2", 5, 15),       # overlaps: [0, 20)
        Event(dev, "XLA Ops", "dot.3", 30, 10),         # [30, 40)
        Event(dev, "XLA Ops", "fusion.1", 100, 20),     # [100, 120)
        Event(dev, "XLA Modules", "jit__decode_chunk_impl(1)", 0, 40),
        Event(dev, "XLA Modules", "jit__admit_impl(2)", 100, 20),
        Event(host, "python", "run", 0, 130),           # an outer frame
        Event(host, "python", "PjitFunction(_admit_impl)", 45, 50),
        Event(host, "python", "np.asarray", 22, 6),
        Event(host, "python", "tiny", 31, 1),           # covers too little
    ]


def test_busy_union_and_idle_share():
    ev = _synthetic_trace()
    assert devtrace.busy_seconds(ev) == pytest.approx(50e-9)
    assert devtrace.union([(0, 10), (5, 20), (30, 40)]) == [(0, 20), (30, 40)]


def test_module_seconds_and_top_ops():
    ev = _synthetic_trace()
    assert devtrace.module_seconds(ev, "_decode_chunk_impl") == \
        pytest.approx((40e-9, 1))
    assert devtrace.module_seconds(ev, "_admit_impl")[1] == 1
    top = devtrace.top_ops(ev, 2)
    assert [t[0] for t in top] == ["fusion.1", "fusion.2"]
    assert top[0][1] == pytest.approx(30e-9)


def test_idle_gaps_are_labelled_by_host_work():
    gaps = devtrace.idle_gaps(_synthetic_trace())
    assert gaps[0] == ["PjitFunction(_admit_impl)", pytest.approx(60e-9)]
    assert gaps[1] == ["np.asarray", pytest.approx(10e-9)]
    assert len(gaps) == 2


def test_no_device_plane_reads_nothing():
    host_only = [e for e in _synthetic_trace() if not devtrace.is_device(e)]
    assert devtrace.busy_seconds(host_only) == 0.0
    assert devtrace.idle_gaps(host_only) == []


# ------------------------------------------------------------ delivery log
def _window():
    tap = Tap()
    # chunk deliveries at t = 1, 2, 3, 4; (rid, tokens, kv, act)
    tap.chunks = [
        Chunk(start=0.5, steps=2, slots=[(0, 2, 10, 2), (1, 1, 5, 1)],
              end=1.0, tokens=3),
        Chunk(start=1.5, steps=2, slots=[(0, 2, 12, 2), (1, 2, 6, 1)],
              end=2.0, tokens=4),
        Chunk(start=2.5, steps=2, slots=[(1, 2, 8, 1), (2, 2, 0, 4)],
              end=3.0, tokens=4),
        Chunk(start=3.5, steps=2, slots=[(2, 1, 1, 5)], end=4.5, tokens=1),
    ]
    cell = spec.Cell("c", {"chips": 1}, {"slots": 2}, {}, {}, [], [])
    return Window(cell=cell, tap=tap, t_open=1.0, t_close=4.5, setup_s=1.0,
                  peaks={}, prompt_len={})


def test_itl_from_a_delivery_log():
    w = _window()
    # chunk 2 (t=2): rid 0 gap 1 then 0; rid 1 gap 1 then 0
    # chunk 3 (t=3): rid 1 gap 1 then 0; rid 2 first delivery: 0 (2nd token)
    # chunk 4 (t=4.5): rid 2 gap 1.5
    assert sorted(w.gaps().tolist()) == [0, 0, 0, 0, 1, 1, 1, 1.5]
    assert w.tokens() == 9
    assert w.seconds == 3.5
    assert w.decode_contexts()[0] == (12, 2, 2)
    assert w.served_requests() == [0, 1, 2]


def test_act_held_is_the_most_a_request_held():
    w = _window()
    assert w.tap.act_held("x") == {("x", 0): 2, ("x", 1): 1, ("x", 2): 5}


def test_metric_readers_on_a_delivery_log():
    from bench.run import readers
    r = readers(["tokens_per_s", "itl_p50_ms", "slot_occupancy"])
    w = _window()
    assert r["tokens_per_s"](w) == pytest.approx(9 / 3.5)
    assert r["itl_p50_ms"](w) == pytest.approx(
        np.percentile([0, 0, 0, 0, 1, 1, 1, 1.5], 50) * 1e3)
    assert r["slot_occupancy"](w) == pytest.approx(100 * 9 / (6 * 2))


# ------------------------------------------------------------------ traffic
MIX = {"loop": "closed",
       "prompt": {"dist": "lognormal", "median": 100, "sigma": 0.6,
                  "min": 20, "max": 400},
       "output": {"dist": "uniform", "min": 4, "max": 12},
       "token_ids": {"dist": "zipf", "a": 1.2}, "strata": 8,
       "prompt_multiple_of": 16}


def test_traffic_is_deterministic_by_seed():
    a = traffic.generate(MIX, 1000, 2**33 + 5, 20)
    b = traffic.generate(MIX, 1000, 2**33 + 5, 20)
    c = traffic.generate(MIX, 1000, 6, 20)
    assert all(np.array_equal(x.prompt, y.prompt)
               and x.max_new_tokens == y.max_new_tokens for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


def test_every_seed_serves_the_same_sizes_in_another_order():
    a = traffic.generate(MIX, 1000, 1, 16)
    b = traffic.generate(MIX, 1000, 2, 16)
    for lo in (0, 8):
        assert sorted(len(x.prompt) for x in a[lo:lo + 8]) == \
            sorted(len(x.prompt) for x in b[lo:lo + 8])
        assert sorted(x.max_new_tokens for x in a[lo:lo + 8]) == \
            sorted(x.max_new_tokens for x in b[lo:lo + 8])
    assert all(len(x.prompt) % 16 == 0 for x in a)
    assert all(0 <= x.prompt.min() and x.prompt.max() < 1000 for x in a)


def test_order_seed_fixes_the_order_of_lengths():
    fixed = dict(MIX, order_seed=0)
    a = traffic.generate(fixed, 1000, 1, 16)
    b = traffic.generate(fixed, 1000, 2**40 + 3, 16)
    assert [len(x.prompt) for x in a] == [len(x.prompt) for x in b]
    assert [x.max_new_tokens for x in a] == [x.max_new_tokens for x in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_streams_share_lengths_not_token_ids():
    """The warm-up's stream 1 takes the server through stream 0's lengths
    in stream 0's order, with token ids of its own; the order follows the
    run's seed where the mix fixes none."""
    for mix in (MIX, dict(MIX, order_seed=3)):
        a = traffic.generate(mix, 1000, 2**31 + 9, 24)
        b = traffic.generate(mix, 1000, 2**31 + 9, 24, stream=1)
        assert [len(x.prompt) for x in a] == [len(x.prompt) for x in b]
        assert [x.max_new_tokens for x in a] == [x.max_new_tokens for x in b]
        assert all(not np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(a, b))
    a = traffic.generate(MIX, 1000, 2**31 + 9, 24)
    c = traffic.generate(MIX, 1000, 5, 24)
    assert [len(x.prompt) for x in c] != [len(x.prompt) for x in a]


def test_quantile_lengths_follow_the_distribution():
    q = traffic.quantile_lengths({"dist": "lognormal", "median": 1536,
                                  "sigma": 0.6, "min": 512, "max": 3072}, 32)
    assert q.min() >= 512 and q.max() <= 3072
    assert abs(np.median(q) - 1536) < 100
    u = traffic.quantile_lengths({"dist": "uniform", "min": 16, "max": 64},
                                 49)
    assert u.tolist() == list(range(16, 65))


def test_closed_loop_arrivals_stagger_the_clients():
    assert traffic.client_arrivals(6, 3, 2) == [0, 2, 4, 4, 4, 4]


# ---------------------------------------------------- discovery by file name
def test_new_data_files_are_found_by_name(tmp_path):
    """A configuration, a mix, a cell and a per-layer metric are each added
    by adding a file, with no edit to any file that is there."""
    for sub in ("cells", "configs", "traffic", "metrics"):
        (tmp_path / sub).mkdir()
    (tmp_path / "traffic" / "new_mix.json").write_text(json.dumps(MIX))
    (tmp_path / "configs" / "new_cfg.json").write_text(
        json.dumps(_config("yi-6b-resident")))
    (tmp_path / "cells" / "new.cell.json").write_text(json.dumps({"slots": 2}))
    (tmp_path / "metrics" / "new_metric.py").write_text(
        "def read(w):\n    return 41.0 + w.slots\n")
    bench = {"workloads": [{"name": "new.cell", "config": "new_cfg",
                            "traffic": "new_mix", "chips": 1, "why": "x"}],
             "end_to_end": [{"name": "tokens_per_s"}],
             "per_layer": [{"name": "new_metric", "workloads": ["new.cell"]},
                           {"name": "other", "workloads": ["elsewhere"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("new.cell", tmp_path / "BENCHMARK.json", tmp_path)
    assert cell.mix["strata"] == 8
    assert cell.config["num_key_value_heads"] == 4
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    from bench.run import readers
    read = readers(["new_metric"], tmp_path / "metrics")["new_metric"]
    w = _window()
    w.cell = cell
    assert read(w) == 43.0
    items = traffic.generate(cell.mix, 64000, 3, 4)
    assert len(items) == 4


def test_benchmark_names_files_that_exist():
    with open(BENCH.parent / "BENCHMARK.json") as f:
        b = json.load(f)
    assert b["paths"] == ["bench"]
    for c in b["configs"]:
        assert (BENCH.parent / c["file"]).is_file()
    for w in b["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.sizes["slots"] >= 1
    for m in b["end_to_end"] + b["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    assert math.isclose(
        sum(1 for w in b["workloads"] if w["chips"] == 4), 0)
    for w in b["workloads"]:
        config = spec.load_cell(w["name"]).config
        assert "family" not in config or \
            (BENCH / "families" / f"{config['family']}.py").is_file()


# ---------------------------------------------------------- the comparison
def test_checks_hold_only_under_the_limit():
    ok = correct.checks(np.asarray([0.0, 0.05]), 0.1, 10, 0)
    assert all(c["holds"] for c in ok.values())
    assert ok["served_tokens_compared"]["value"] == 2
    wide = correct.checks(np.asarray([0.0, 0.2]), 0.1, 10, 0)
    assert not wide["widest_logit_gap"]["holds"]
    assert not correct.checks(np.asarray([0.0]), 0.1, 0, 0)[
        "act_tokens_in_compared"]["holds"]
    assert not correct.checks(np.asarray([0.0]), 0.1, 5, 1)[
        "failed_requests"]["holds"]
    assert not correct.checks(np.zeros((0,)), 0.1, 5, 0)[
        "served_tokens_compared"]["holds"]


def test_sample_takes_the_longest_then_draws_by_seed():
    served = {("warm-up", 0): (np.zeros(5), [1, 2]),
              ("window", 0): (np.zeros(9), [1]),
              ("window", 1): (np.zeros(3), [1, 2, 3])}
    assert correct.sample(served, 4, 1) == [("window", 0)]
    every = correct.sample(served, 4, 100)
    assert every[0] == ("window", 0) and sorted(every) == sorted(served)
    assert correct.sample(served, 4, 100) == every


def test_configuration_file_sets_the_position_table():
    """The program's OPT tables are longer than the published 2048
    positions; the harness serves the configuration file's length, and
    refuses a file whose widths differ from the program's."""
    from bench.run import program_config
    cell = spec.load_cell("opt-6.7b.offload.longprompt16")
    cfg = program_config(cell)
    assert cfg.max_seq_len == cell.config["max_position_embeddings"] == 2048
    wrong = dataclasses.replace(cell, config=dict(cell.config,
                                                  hidden_size=4097))
    with pytest.raises(ValueError, match="d_model"):
        program_config(wrong)
