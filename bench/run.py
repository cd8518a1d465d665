#!/usr/bin/env python3
"""Run one benchmark cell once and print one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Steps, in order:

1. Refuse (exit 2, no result) unless the default device is a TPU whose
   ``device_kind`` is in ``bench/peaks.py``, with as many chips as the cell
   asks for.
2. Turn on the program's persistent compilation cache (inside the checkout,
   or ``$JAX_COMPILATION_CACHE_DIR``).
3. Make the weights from the seed on the device (``bench/weights.py``).
   A cell that asks for more than one chip is served tensor-parallel on a
   (1, chips) mesh (the program's ``make_test_mesh``): the program's shard
   plan (``make_shard_plan``) is built from the weights' shapes, the
   weights are made in its shardings, and the server is given the plan.
   A configuration that names a ``family`` takes its weight scales, its
   reference and its counts from ``bench/families/<family>.py``
   (``bench/spec.py``).
4. Warm up: serve stream 1 of the cell's traffic (``bench/traffic.py``)
   through ``ContinuousBatchingServer.run`` for ``warm_chunks`` chunks, then
   stop it and release its slots with the server's own failure-path
   cleanup.  Stream 1 has the lengths of stream 0 in the same order and
   token ids of its own.  The server's schedule is a function of request
   lengths and step counts alone, so serving stream 0 replays the warm-up's
   shapes: the window compiles nothing the warm-up did not.
5. Serve stream 0 on the same server, on a backlog the window cannot
   drain: a closed loop of ``slots`` clients with zero think time.  The
   window opens at the delivery of chunk ``ramp_chunks``, measures for
   ``--seconds`` (whole chunks; see ``bench/window.py``) and the run stops
   there without draining.  With ``--trace 1`` the profiler records the
   window.
6. Read the devices' peak memory, free the server, and compare the tokens
   it served in the warm-up and in the window with the plain reference
   (``bench/correct.py``), which reads the weights layer by layer (on a
   mesh, each layer's sharded slices).

The last line of stdout is the result; the numbers compared, each beside its
limit, are the last lines of stderr and the result's last key.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402
from bench.peaks import PEAKS  # noqa: E402

OUT = ROOT / "bench" / "out"

#: program configuration fields and the configuration-file keys they match
_SIZE_KEYS = (("num_layers", "num_hidden_layers"), ("d_model", "hidden_size"),
              ("num_heads", "num_attention_heads"),
              ("num_kv_heads", "num_key_value_heads"),
              ("head_dim", "head_dim"), ("d_ff", "intermediate_size"),
              ("vocab_size", "vocab_size"),
              ("max_seq_len", "max_position_embeddings"),
              ("dtype", "torch_dtype"))
#: fields the configuration file sets on the program's configuration: the
#: learned position table's length (the program's own OPT tables are longer
#: than the published 2048 positions)
_SET_KEYS = (("max_seq_len", "max_position_embeddings"),)


class Refused(Exception):
    """The run cannot measure what it was asked to (exit 2, no result)."""


def check_device(chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise Refused(f"no TPU: the default device is {d.platform!r}")
    if d.device_kind not in PEAKS:
        raise Refused(f"no peaks known for device kind {d.device_kind!r}")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX finds "
                      f"{len(devs)}")
    return d


def program_config(cell: spec.Cell):
    """The program's configuration, checked against the cell's file."""
    from repro.configs import get_config
    cfg = get_config(cell.config["model"])
    cfg = dataclasses.replace(
        cfg, **{a: cell.config[k] for a, k in _SET_KEYS})
    for attr, key in _SIZE_KEYS:
        if getattr(cfg, attr) != cell.config[key]:
            raise ValueError(f"{cell.config['model']}: the program's {attr} "
                             f"is {getattr(cfg, attr)!r}, the configuration "
                             f"file's {key} is {cell.config[key]!r}")
    return cfg


METRICS = ROOT / "bench" / "metrics"


def readers(names, directory: Path = METRICS):
    """name -> the ``read`` function of ``<directory>/<name>.py``."""
    out = {}
    for n in names:
        loc = importlib.util.spec_from_file_location(
            f"bench.metrics.{n}", directory / f"{n}.py")
        mod = importlib.util.module_from_spec(loc)
        loc.loader.exec_module(mod)
        out[n] = mod.read
    return out


def note(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _peak_bytes() -> List[int]:
    """Each local device's peak bytes in use."""
    import jax
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.local_devices()]


def shard_plan(cfg, chips: int):
    """The program's shard plan on a (1, ``chips``) mesh, or None for one
    chip."""
    if chips == 1:
        return None
    from bench import weights
    from repro.launch.mesh import make_test_mesh
    from repro.sharding import make_shard_plan
    return make_shard_plan(cfg, make_test_mesh(1, chips),
                           weights.shapes_of(cfg))


def execute(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
            device_check: bool = True, mutate=None, readings=None,
            t_start: float = T_START) -> Dict:
    """One run of ``cell``; -> the result dict.  ``mutate(server)`` (tests
    only) breaks the timed path before the warm-up; ``readings(...)``
    (``bench/control.py`` only) adds numbers read against the reference,
    which the result carries under ``readings``."""
    import jax
    from bench import correct, traffic, weights
    from bench.compiles import CompileClock
    from bench.tap import Stop, Tap
    from bench.window import Window
    from repro.data.pipeline import Request
    from repro.serving import ContinuousBatchingServer

    if device_check:
        dev = check_device(cell.chips)
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    else:
        dev = jax.devices()[0]
    peaks = PEAKS.get(dev.device_kind, {})
    note(f"device {dev.platform} {dev.device_kind}, memory "
         f"{(dev.memory_stats() or {}).get('bytes_limit')} bytes")
    clock = CompileClock()
    sz = cell.sizes
    cfg = program_config(cell)
    offload = cell.config["regime"] == "offload"
    plan = shard_plan(cfg, cell.chips)
    if offload and plan is not None:
        raise ValueError(f"{cell.name}: the harness serves offload cells on "
                         f"one chip only")
    if offload:
        made = weights.make_offload(cfg, cell.config, seed,
                                    family=cell.family)
    else:
        made = weights.make_resident(cfg, cell.config, seed, plan=plan,
                                     family=cell.family)
    note(f"weights made at {time.perf_counter() - t_start:.1f} s")

    def requests_of(stream: int):
        return [Request(rid=it.index, prompt=it.prompt,
                        max_new_tokens=it.max_new_tokens)
                for it in traffic.generate(cell.mix, cfg.vocab_size, seed,
                                           int(sz["backlog"]), stream)]

    requests, warm_requests = requests_of(0), requests_of(1)
    arrivals = traffic.client_arrivals(len(requests), int(sz["slots"]),
                                       int(sz["client_start_every"]))
    tap = Tap()
    server = ContinuousBatchingServer(
        cfg, made, slots=int(sz["slots"]), kv_cap=int(sz["kv_cap"]),
        act_cap=int(sz["act_cap"]), chunk_steps=int(sz["chunk_steps"]),
        offload=offload, plan=plan, tracer=tap, metrics=tap.registry)
    tap.server = server
    if mutate is not None:
        mutate(server)

    # --- warm-up: stream 1's first warm_chunks chunks -----------------------
    def end_warm_up(t: Tap) -> None:
        if len(t.chunks) >= int(sz["warm_chunks"]):
            raise Stop("warm-up done")

    tap.on_delivery = end_warm_up
    try:
        server.run(warm_requests, arrivals)
    except Stop:
        pass
    served = {("warm-up", r): (warm_requests[r].prompt, toks)
              for r, toks in tap.served().items()}
    warm_failed = list(tap.failed)
    # the server's own failure-path cleanup: every slot and parked request
    # released, so the window's stream starts on an empty server
    server._release_slots(range(server.n_slots))
    server._release_parked()
    warm_chunks = len(tap.chunks)
    act_held = tap.act_held("warm-up")
    note(f"warm-up done at {time.perf_counter() - t_start:.1f} s: "
         f"{warm_chunks} chunks, {clock.compiles} compiles "
         f"({clock.seconds:.1f} s)")
    tap.reset()

    # --- the window ----------------------------------------------------------
    ramp = int(sz["ramp_chunks"])
    trace_dir = OUT / "trace" / cell.name
    st: Dict[str, Optional[float]] = {"open": None, "close": None,
                                      "trace_t0": None, "trace_t1": None}

    def on_delivery(t: Tap) -> None:
        c = t.chunks[-1]
        if st["open"] is None:
            if len(t.chunks) >= ramp:
                st["open"] = c.end
                if trace:
                    shutil.rmtree(trace_dir, ignore_errors=True)
                    jax.profiler.start_trace(str(trace_dir))
                    st["trace_t0"] = time.perf_counter()
            return
        if c.end >= st["open"] + seconds:
            st["close"] = c.end
            if trace:
                st["trace_t1"] = time.perf_counter()
                jax.profiler.stop_trace()
            raise Stop("window closed")

    tap.on_delivery = on_delivery
    try:
        server.run(requests, arrivals)
    except Stop:
        pass
    if st["close"] is None:
        raise RuntimeError("the stream ran out before the window closed; "
                           "raise the cell's backlog")
    note(f"window {st['open'] - t_start:.1f}-{st['close'] - t_start:.1f} s "
         f"after start, {len(tap.chunks)} chunks served, warm-up "
         f"{warm_chunks} chunks")
    peaks_by_device = _peak_bytes()
    peak = max(peaks_by_device)
    served.update({("window", r): (requests[r].prompt, toks)
                   for r, toks in tap.served().items()})
    act_held.update(tap.act_held("window"))
    note(f"act_frac {server.act_frac}, preemptions {tap.preempts}, peak "
         f"{peak} bytes")
    server.close()
    del server
    gc.collect()

    w = Window(cell=cell, tap=tap, t_open=st["open"], t_close=st["close"],
               setup_s=st["open"] - t_start, peaks=peaks,
               prompt_len={r.rid: len(r.prompt) for r in requests},
               compiles=len(clock.between(st["open"], st["close"])))
    if w.compiles:
        note("compiled in the window: "
             f"{clock.between(st['open'], st['close'])}")
    clock.close()
    busy = None
    if trace:
        from bench import devtrace
        t_read = time.perf_counter()
        w.trace = devtrace.read_events(str(trace_dir))
        w.trace_window_s = st["trace_t1"] - st["trace_t0"]
        busy = devtrace.busy_seconds(w.trace)
        note(f"trace: {len(w.trace)} events read in "
             f"{time.perf_counter() - t_read:.1f} s")

    # --- correctness ---------------------------------------------------------
    rids = correct.sample(served, seed, int(sz["check_tokens"]))
    seqs, want = correct.sequences(served, rids)
    rest = {k: v for k, v in made.items() if k != "layers"}
    layers = made["layers"]

    def layer(l):
        return jax.tree.map(lambda a: jax.numpy.asarray(a[l]), layers)

    ref = cell.reference.logits(cell.config, rest, layer, seqs, want)
    gaps = correct.served_gaps(ref, served, rids)
    act = int(sum(act_held.get(r, 0) for r in rids))
    failed = len(warm_failed) + len(tap.failed)
    checks = correct.checks(gaps, float(sz["gap_limit"]), act, failed)
    ok = all(c["holds"] for c in checks.values())
    extra = (readings(cell=cell, rest=rest, layer=layer, finished=served,
                      rids=rids, ref=ref, act=act)
             if readings is not None else None)

    metrics = {}
    wanted = cell.per_layer if trace else cell.end_to_end
    for m, fn in readers([m["name"] for m in wanted]).items():
        v = fn(w)
        if v is not None:
            unit = next(x["unit"] for x in wanted if x["name"] == m)
            metrics[m] = {"value": float(v), "unit": unit}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": peak,
              "memory_peak_bytes_by_device": peaks_by_device[:cell.chips]}
    out = {"correct": bool(ok), "attempted": len(w.served_requests()),
           "failed": len(tap.failed), "metrics": metrics, "device": device}
    if trace:
        from bench import devtrace
        device["busy_s"] = busy
        device["window_s"] = w.trace_window_s
        out["breakdown"] = {"device_ops": devtrace.top_ops(w.trace),
                            "idle_gaps": devtrace.idle_gaps(w.trace)}
    if extra is not None:
        out["readings"] = extra
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(args.workload)
        out = execute(cell, args.seed, args.seconds, bool(args.trace))
    except Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
