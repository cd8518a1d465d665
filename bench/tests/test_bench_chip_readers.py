"""Readers that know the cell's chip count, on a synthetic window and trace:
on one chip they read what they read before chips were counted (the
numbers below are those readings), on four they divide by four chips'
peaks and average over the device planes; and ``collective_share`` on a
synthetic four-plane trace."""
import json
from pathlib import Path

import pytest

from bench import spec
from bench.devtrace import Event
from bench.peaks import PEAKS
from bench.run import readers
from bench.tap import Chunk, Span, Tap
from bench.window import Window

BENCH = Path(__file__).resolve().parents[1]
MS = 1_000_000
READ = readers(["step_mfu", "decode_roofline", "device_idle_share",
                "upload_gbps", "collective_share"])


def _config(path):
    return json.loads((BENCH / f"{path}.json").read_text())


def _window(config, chips, program, extra=()):
    tap = Tap()
    tap.chunks = [Chunk(start=1.0, steps=1, slots=[(0, 1, 1300, 650),
                                                   (1, 1, 600, 300)],
                        end=1.5, tokens=2),
                  Chunk(start=1.5, steps=2, slots=[(0, 2, 1301, 650),
                                                   (2, 1, 0, 48)],
                        end=2.25, tokens=3)]
    tap.admits = [Span("admit", 1.6, 1.7, {"rids": [2]})]
    tap.lanes = [Span("pcie/w", 1.1, 1.4, {"nbytes": 0, "shard": 0}),
                 Span("pcie/w", 1.6, 1.9, {"nbytes": 0, "shard": 0}),
                 Span("pcie/w", 1.6, 1.9, {"nbytes": 4096, "shard": 0})]
    cell = spec.Cell("c", {"chips": chips}, {"slots": 2}, config, {}, [], [])
    trace = [Event("/host:CPU", "python", "serve.chunk", 0, 900 * MS)]
    for i in range(chips):
        p = f"/device:TPU:{i}"
        trace += [Event(p, "XLA Ops", "fusion.1", 0, 200 * MS),
                  Event(p, "XLA Ops", "fusion.2", 100 * MS, 250 * MS),
                  Event(p, "XLA Ops", "fusion.3", 600 * MS,
                        100 * MS + i * MS),
                  Event(p, "XLA Modules", f"jit_{program}(1)", 0, 350 * MS),
                  Event(p, "XLA Modules", f"jit_{program}(1)", 600 * MS,
                        100 * MS)]
        trace += [e._replace(plane=p) for e in extra]
    return Window(cell=cell, tap=tap, t_open=1.0, t_close=2.25,
                  setup_s=1.0, peaks=PEAKS["TPU v5 lite"],
                  prompt_len={2: 1984}, trace=trace, trace_window_s=1.0)


# the readings before the readers counted chips, on this window
BEFORE = {
    ("configs/opt-6.7b-offload", "_layer_impl"): {
        "step_mfu": 10.829036742978275,
        "decode_roofline": 11.630217186270519,
        "device_idle_share": 55.00000000000001,
        "upload_gbps": 1.3422865066666674},
    ("tests/data/yi-6b-resident", "_decode_chunk_impl"): {
        "step_mfu": 9.365775655036751,
        "decode_roofline": 9.684213198751866,
        "device_idle_share": 55.00000000000001,
        "upload_gbps": 1.153488213333334},
}


@pytest.mark.parametrize("config,program", sorted(BEFORE))
def test_one_chip_reads_as_before(config, program):
    w = _window(_config(config), 1, program)
    for name, value in BEFORE[(config, program)].items():
        assert READ[name](w) == value, name
    assert READ["collective_share"](w) is None


@pytest.mark.parametrize("config,program", sorted(BEFORE))
def test_four_chips_divide_by_four_chips_peaks(config, program):
    """The same work on four chips whose planes each ran what the one chip
    ran: the model FLOPs and the least time are four chips', the device
    time is each plane's."""
    before = BEFORE[(config, program)]
    w = _window(_config(config), 4, program)
    assert READ["step_mfu"](w) == pytest.approx(before["step_mfu"] / 4)
    assert READ["decode_roofline"](w) == pytest.approx(
        before["decode_roofline"] / 4)
    # planes busy 450, 451, 452, 453 ms of 1 s
    assert READ["device_idle_share"](w) == pytest.approx(
        100 * (1 - 0.4515))


def test_device_idle_share_is_the_mean_of_the_planes():
    w = _window(_config("configs/opt-6.7b-offload"), 2, "_layer_impl")
    w.trace.append(Event("/device:TPU:1", "XLA Ops", "fusion.9",
                         800 * MS, 100 * MS))
    # plane 0 busy 450 ms, plane 1 busy 451 + 100 ms of a 1-s window
    assert READ["device_idle_share"](w) == pytest.approx(
        100 * ((1 - 0.45) + (1 - 0.551)) / 2)


COLLECTIVES = (
    Event("", "XLA Ops", "%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} "
          "%p), channel_id=1, to_apply=%add", 360 * MS, 20 * MS),
    # an asynchronous all-gather runs from its start to its done
    Event("", "XLA Ops", "all-gather-start.1", 380 * MS, 5 * MS),
    Event("", "XLA Ops", "%all-gather-done.1 = bf16[4,128]{1,0} "
          "all-gather-done(%all-gather-start.1)", 395 * MS, 5 * MS),
    Event("", "XLA Ops", "reduce-scatter.2", 610 * MS, 30 * MS),
    # overlaps the all-reduce: counted once in the share
    Event("", "XLA Ops", "collective-permute.4", 370 * MS, 20 * MS),
    # known by the HLO instruction the trace gives for it: a fused
    # all-reduce half, and an all-to-all
    Event("", "XLA Ops", "fusion.183", 800 * MS, 10 * MS,
          "%fusion.183 = bf16[12,1280]{1,0} fusion(%fusion.182), "
          "kind=kCustom, calls=%all-reduce-scatter.clone.clone"),
    Event("", "XLA Ops", "a2a", 820 * MS, 5 * MS,
          "%a2a = bf16[4,8]{1,0} all-to-all(%x), dimensions={0}"),
    # not collectives: fusions fed by one, and a copy
    Event("", "XLA Ops", "%fusion.7 = bf16[4]{0} fusion(%all-gather-done.1)",
          400 * MS, 10 * MS),
    Event("", "XLA Ops", "fusion.9", 830 * MS, 10 * MS,
          "%fusion.9 = f32[12]{0} fusion(%all-reduce.28), kind=kLoop, "
          "calls=%fused_computation.121 loop fusion"),
    Event("", "XLA Ops", "copy.2", 700 * MS, 10 * MS),
    Event("", "XLA Modules", "jit__admit_impl(2)", 355 * MS, 60 * MS),
)


def test_collective_share_on_four_planes(capsys):
    w = _window(_config("configs/opt-6.7b-offload"), 4, "_decode_chunk_impl",
                COLLECTIVES)
    # per plane the union [360, 400) + [610, 640) + [800, 810) + [820, 825)
    # = 85 ms of a 1-s window
    assert READ["collective_share"](w) == pytest.approx(8.5)
    err = capsys.readouterr().err
    for part in ("all-reduce 0.030000000", "all-gather 0.020000000",
                 "reduce-scatter 0.030000000",
                 "collective-permute 0.020000000", "all-to-all 0.005000000",
                 "_admit_impl 0.060000000", "_decode_chunk_impl 0.030000000",
                 "no program 0.015000000", "4 chips"):
        assert part in err, err
