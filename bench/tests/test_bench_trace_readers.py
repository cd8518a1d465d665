"""The readers of the offload step's own spans on synthetic inputs with
known answers: ``stage_wait_share`` on a tap's lane spans,
``idle_waiting_weights_share`` on a profiler trace's events."""
import pytest

from bench import spec
from bench.devtrace import Event
from bench.run import readers
from bench.tap import Chunk, Span, Tap
from bench.window import Window

READ = readers(["stage_wait_share", "idle_waiting_weights_share",
                "upload_share"])


def _window(lanes, trace=None, trace_window_s=0.0):
    tap = Tap()
    tap.chunks = [Chunk(start=1.0, steps=1, end=2.0, tokens=4),
                  Chunk(start=2.0, steps=1, end=3.0, tokens=4)]
    tap.lanes = [Span(name, a, b, {"nbytes": n, "shard": 0})
                 for name, a, b, n in lanes]
    cell = spec.Cell("c", {"chips": 1}, {"slots": 4}, {}, {}, [], [])
    return Window(cell=cell, tap=tap, t_open=1.0, t_close=3.0, setup_s=1.0,
                  peaks={}, prompt_len={}, trace=trace,
                  trace_window_s=trace_window_s)


# (name, start, end, nbytes): chunk 1 tiled end to end, chunk 2 to 60 %
LANES = [
    ("host/w_wait", 0.5, 0.9, 0),             # before the window: not read
    ("host/unstack", 1.0, 1.05, 0), ("host/pre", 1.05, 1.1, 0),
    ("host/w_wait", 1.1, 1.5, 0), ("pcie/w", 1.2, 1.6, 1000),  # copy thread
    ("pcie/w", 1.5, 1.8, 0), ("gpu/fwd", 1.8, 1.9, 0),
    ("host/post", 1.9, 1.95, 0), ("host/restack", 1.95, 2.0, 0),
    ("host/w_wait", 2.0, 2.2, 0), ("pcie/w", 2.2, 2.5, 0),
    ("gpu/fwd", 2.5, 2.6, 0),
]


def test_stage_wait_share_adds_to_upload_share(capsys):
    w = _window(LANES)
    assert READ["stage_wait_share"](w) == pytest.approx(100 * 0.6 / 2.0)
    assert READ["upload_share"](w) == pytest.approx(100 * 0.6 / 2.0)
    err = capsys.readouterr().err
    assert "tile 60.000 % of a decode chunk at least, 80.000 % on mean" \
        in err


def test_stage_wait_share_reads_nothing_without_waits():
    w = _window([s for s in LANES if s[0] != "host/w_wait"])
    assert READ["stage_wait_share"](w) is None


DEV, HOST = "/device:TPU:0", "/host:CPU"
TRACE = [
    Event(DEV, "XLA Ops", "fusion.1", 0, 10),
    Event(DEV, "XLA Ops", "fusion.2", 40, 10),
    Event(DEV, "XLA Ops", "fusion.3", 90, 10),
    Event(DEV, "XLA Modules", "jit__layer_impl(1)", 40, 10),
    Event(HOST, "python", "serve.chunk", 0, 100),
    Event(HOST, "python", "offload.w_wait", 10, 15),
    Event(HOST, "python", "offload.w_handoff", 25, 15),
    Event(HOST, "python", "offload.fwd", 40, 10),
    Event(HOST, "python", "offload.w_stage", 45, 20),   # the copy thread
    Event(HOST, "python", "offload.w_wait", 50, 20),
    Event(HOST, "python", "run", 0, 110),     # no annotation over 100-110
]


def test_idle_waiting_weights_share_on_a_synthetic_trace(capsys):
    # idle [10, 40) and [50, 90) and [100, 110); the weight waits and
    # hand-offs cover [10, 40) and [50, 70): 50 ns of a 110-ns window
    w = _window(LANES, TRACE, 110e-9)
    assert READ["idle_waiting_weights_share"](w) == \
        pytest.approx(100 * 50 / 110)
    err = capsys.readouterr().err
    # innermost: the wait opened at 50 over the stage opened at 45
    for part in ("offload.w_wait 0.000000035", "offload.w_handoff "
                 "0.000000015", "serve.chunk 0.000000020",
                 "under none 0.000000010 of 0.000000080 s idle",
                 "(87.500 % under an annotation)"):
        assert part in err, err


def test_idle_waiting_weights_share_reads_nothing_without_annotations():
    bare = [e for e in TRACE if not e.name.startswith(("offload.",
                                                      "serve."))]
    assert READ["idle_waiting_weights_share"](
        _window(LANES, bare, 110e-9)) is None
    host_only = [e for e in TRACE if e.plane == HOST]
    assert READ["idle_waiting_weights_share"](
        _window(LANES, host_only, 110e-9)) is None
    assert READ["idle_waiting_weights_share"](_window(LANES)) is None
