#!/usr/bin/env python3
"""Bring-up smoke of hybrid KV/ACT cache serving on a TPU, at published widths.

    python3 chip_smoke.py             # phases 1 and 2, one chip
    python3 chip_smoke.py --chips 4   # phase 3 only, one host with four chips

Phase 1, opt-6.7b with host offload (the paper's own model and regime: 32
layers of 4096-wide MHA with learned positions, bf16).  The layer weights
stay in host memory and stream to the chip per layer; the continuous-batching
server runs the hybrid cache.  Reference: the same requests through the same
offload runtime with a plain KV cache (``HybridServeEngine(mode="kv")``).
The hybrid run must have stored ACT checkpoints, or the comparison would
check nothing.

Phase 2, yi-6b device-resident (GQA 32/4 with RoPE, bf16).  The server keeps
the weights on the chip and runs several decode steps per dispatch.
Reference: ``exact_reference_generate``, full-KV decode on the same params.

Phase 3 (``--chips 4``), opt-13b tensor-parallel on a (1, 4) mesh — 26 GB
of bf16 weights, which no single chip holds.  The weights go from host
memory straight to their shards.  Reference: ``exact_reference_generate`` on
the same mesh-placed params.

Correctness is judged on logits: each request is teacher-forced through the
hybrid cache and through its reference for ``N_FORCED`` positions (the first
generated position, then the reference's own tokens), and the largest logit
difference, over the largest reference logit, must stay within the phase's
tolerance below.  The hybrid side keeps half of each prompt as ACT
checkpoints and alternates ACT and KV appends, so the check reaches K/V
regenerated from checkpoints.  Greedy-token agreement with the reference is
printed but not gated: a bf16 near-tie can flip a token.

Each phase prints its compile and wall seconds, tokens generated, the
device's peak bytes in use (since the process started) and its reference
error.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The script exits non-zero and prints no such line when the default device
is not a TPU, a phase raises, a reference error misses its tolerance, or the
offload runtime took a degraded mode (arena denials, synchronous fallbacks,
watchdog timeouts, copy failures).  Weights and prompts are generated from
``--seed``; nothing is read from disk.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.data.pipeline import Request  # noqa: E402
from repro.serving.util import bucket  # noqa: E402

#: teacher-forced positions compared per request
N_FORCED = 4
#: max |hybrid - reference| / max |reference| over those logits.  bf16
#: weights and caches: the two sides differ only in accumulation order and
#: in which path (prefill or decode) computed a K/V row, each a few bf16
#: ulps (2^-8 relative); a wrong position, norm or region gives O(1).
TOL_OPT_6_7B = 2e-2
TOL_YI_6B = 2e-2
TOL_OPT_13B = 2e-2
#: robustness counters whose nonzero value means a degraded mode was taken
DEGRADED = ("arena_denials", "sync_fallbacks", "watchdog_timeouts",
            "copy_failures")


class CompileClock:
    """Sums JAX's trace, lowering and backend-compile durations."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
            self.compiles += event == self.EVENTS[-1]

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._on)


def make_requests(vocab: int, n: int, seed: int, prompt=(128, 512),
                  new=(16, 32)):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, vocab, int(rng.integers(
                        prompt[0], prompt[1] + 1))).astype(np.int32),
                    max_new_tokens=int(rng.integers(new[0], new[1] + 1)))
            for i in range(n)]


def _forced_inputs(reqs, ref_tokens, n):
    """Prompts minus their last token, padded to one bucket; the last
    prompt token and the reference's first n-1 tokens to feed after."""
    plen = np.asarray([len(r.prompt) - 1 for r in reqs], np.int32)
    S = bucket(int(plen.max()))
    toks = np.zeros((len(reqs), S), np.int32)
    feed = np.zeros((len(reqs), n), np.int32)
    for b, r in enumerate(reqs):
        toks[b, :plen[b]] = r.prompt[:-1]
        toks[b, plen[b]:] = r.prompt[-2]
        feed[b, 0] = r.prompt[-1]
        feed[b, 1:] = ref_tokens[r.rid][:n - 1]
    # hybrid split: the first half of each prefix (block-aligned) as KV, the
    # rest as ACT checkpoints; decode appends alternate ACT and KV
    kv_keep = (plen // 2) // 16 * 16
    store = np.zeros((n, len(reqs)), bool)
    store[::2] = True
    return toks, plen, feed, kv_keep, store


def forced_logits_offload(ex, toks, last_pos, feed, kv_keep, store, *,
                          kv_cap, act_cap):
    """(B, n, V) logits through the offload runtime's layer-streamed
    prefill and decode steps."""
    _, cache = ex.prefill_batched(toks, kv_keep, last_pos, kv_cap=kv_cap,
                                  act_cap=act_cap)
    out = []
    for s in range(feed.shape[1]):
        lg, cache = ex.decode_step(jnp.asarray(feed[:, s:s + 1]), cache,
                                   store[s])
        out.append(np.asarray(lg[:, 0], np.float32))
    return np.stack(out, 1)


def forced_logits_hybrid(cfg, params, toks, last_pos, feed, kv_keep, store,
                         *, kv_cap, act_cap):
    """(B, n, V) logits through the model's hybrid prefill and decode step
    (the functions the device-resident server's dispatches run)."""
    from repro.models import model as M
    prefill = jax.jit(lambda p, t, kk, lp: M.hybrid_prefill_batched(
        p, cfg, {"tokens": t}, kv_cap=kv_cap, act_cap=act_cap, kv_keep=kk,
        last_pos=lp))
    step = jax.jit(lambda p, t, c, s: M.hybrid_decode_step(p, cfg, t, c, s),
                   donate_argnums=(2,))
    _, cache = prefill(params, jnp.asarray(toks), jnp.asarray(kv_keep),
                       jnp.asarray(last_pos))
    out = []
    for s in range(feed.shape[1]):
        lg, cache = step(params, jnp.asarray(feed[:, s:s + 1]), cache,
                         jnp.asarray(store[s]))
        out.append(np.asarray(lg[:, 0], np.float32))
    return np.stack(out, 1)


def forced_logits_full_kv(cfg, params, toks, last_pos, feed):
    """(B, n, V) logits through the plain full-KV prefill and decode step
    that ``exact_reference_generate`` runs."""
    from repro.models import model as M
    max_len = toks.shape[1] + feed.shape[1] + 8
    prefill = jax.jit(lambda p, t: M.prefill(p, cfg, {"tokens": t},
                                             max_len=max_len))
    step = jax.jit(lambda p, t, c: M.decode_step(p, cfg, t, c),
                   donate_argnums=(2,))
    _, cache = prefill(params, jnp.asarray(toks))
    cache["kv_len"] = jnp.asarray(last_pos)     # per-row prompt lengths
    out = []
    for s in range(feed.shape[1]):
        lg, cache = step(params, jnp.asarray(feed[:, s:s + 1]), cache)
        out.append(np.asarray(lg[:, 0], np.float32))
    return np.stack(out, 1)


def progress(res, what, t0) -> None:
    print(f"  [{res['phase']}] {what} at {time.perf_counter() - t0:.1f}s",
          flush=True)


def logits_error(got, ref) -> float:
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def token_agreement(out, ref, reqs) -> float:
    same = sum(int(np.sum(out[r.rid] == ref[r.rid])) for r in reqs)
    return same / sum(len(ref[r.rid]) for r in reqs)


def peak_bytes(device=None) -> int:
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def _act_blocks(cache) -> int:
    """ACT blocks the slot cache holds (16-token blocks per slot)."""
    return int(np.sum(-(-np.asarray(cache["act_len"]) // 16)))


def phase_offload(cfg, reqs, *, slots=4, cap=512, chunk_steps=8,
                  n_forced=N_FORCED, tol=TOL_OPT_6_7B, seed=0, hw=None):
    """Phase 1: hybrid serving through host offload vs a plain-KV run of
    the same offload runtime."""
    from repro.models import model as M
    from repro.serving import ContinuousBatchingServer, HybridServeEngine
    res = {"phase": f"{cfg.name}/offload"}
    start = t0 = time.perf_counter()
    params = M.init_params(cfg, jax.random.PRNGKey(seed), on_host=True)
    res["init_s"] = time.perf_counter() - t0
    progress(res, "weights built on the host", start)
    kv_cap_ref = bucket(max(len(r.prompt) + r.max_new_tokens for r in reqs))
    with ContinuousBatchingServer(cfg, params, slots=slots, kv_cap=cap,
                                  act_cap=cap, chunk_steps=chunk_steps,
                                  offload=True, hw=hw) as server, \
            HybridServeEngine(cfg, params, mode="kv", offload=True, hw=hw,
                              max_minibatch=slots, kv_cap=kv_cap_ref,
                              act_cap=16) as engine:
        t0 = time.perf_counter()
        out, stats = server.run(reqs)
        res["serve_s"] = time.perf_counter() - t0
        res["tokens"] = stats.generated_tokens
        res["act_frac"] = server.act_frac
        res["act_blocks"] = _act_blocks(server.cache)
        progress(res, "hybrid serving done", start)
        t0 = time.perf_counter()
        ref, _ = engine.generate(reqs)
        res["reference_s"] = time.perf_counter() - t0
        progress(res, "plain-KV reference done", start)
        res["token_agreement"] = token_agreement(out, ref, reqs)
        server.cache = None                      # free the slot pools
        toks, last_pos, feed, kv_keep, store = _forced_inputs(
            reqs, ref, n_forced)
        kw = dict(kv_cap=kv_cap_ref, act_cap=cap)
        hyb = forced_logits_offload(server.executor, toks, last_pos, feed,
                                    kv_keep, store, **kw)
        base = forced_logits_offload(server.executor, toks, last_pos, feed,
                                     last_pos, np.zeros_like(store), **kw)
        res["first_pos_err"] = logits_error(hyb[:, 0], base[:, 0])
        res["logits_err"] = logits_error(hyb, base)
        counters = dict(server.executor.fault_counters)
        for k, v in engine.executor.fault_counters.items():
            counters[k] += v
        counters["arena_denials"] = engine.arena_denials
    res["degraded"] = {k: counters[k] for k in DEGRADED}
    res["tol"] = tol
    res["ok"] = (res["logits_err"] <= tol and res["act_blocks"] > 0
                 and not any(res["degraded"].values()))
    return res


def phase_resident(cfg, reqs, *, slots=4, cap=512, chunk_steps=8,
                   n_forced=N_FORCED, tol=TOL_YI_6B, seed=0, hw=None,
                   plan=None):
    """Phase 2 (and 3 with a ``plan``): device-resident hybrid serving vs
    full-KV decode on the same params."""
    from repro.models import model as M
    from repro.serving import (ContinuousBatchingServer,
                               exact_reference_generate)
    from repro.serving.util import trace_ctx
    where = "device" if plan is None else \
        "mesh" + "x".join(str(s) for s in plan.mesh.devices.shape)
    res = {"phase": f"{cfg.name}/{where}"}
    start = t0 = time.perf_counter()
    host = M.init_params(cfg, jax.random.PRNGKey(seed), on_host=True)
    params = (jax.device_put(host) if plan is None
              else plan.place_params(host))
    del host
    jax.block_until_ready(params)
    res["init_s"] = time.perf_counter() - t0
    progress(res, "weights placed", start)
    server = ContinuousBatchingServer(cfg, params, slots=slots, kv_cap=cap,
                                      act_cap=cap, chunk_steps=chunk_steps,
                                      hw=hw, plan=plan)
    t0 = time.perf_counter()
    out, stats = server.run(reqs)
    res["serve_s"] = time.perf_counter() - t0
    res["tokens"] = stats.generated_tokens
    res["act_frac"] = server.act_frac
    res["act_blocks"] = _act_blocks(server.cache)
    if plan is not None:
        # weights and cache must really be spread over the mesh: every leaf
        # placed on every device, and each device holding its share
        res["param_devices"] = min(len(a.sharding.device_set)
                                   for a in jax.tree.leaves(server.params))
        res["cache_devices"] = len(server.cache["k"].sharding.device_set)
        res["device_bytes"] = [int((d.memory_stats() or {}).get(
            "bytes_in_use", 0)) for d in plan.mesh.devices.flat]
        share = sum(a.nbytes for a in jax.tree.leaves(params)) / plan.mesh.size
    del server
    gc.collect()
    progress(res, "hybrid serving done", start)
    t0 = time.perf_counter()
    ref = exact_reference_generate(cfg, params, reqs)
    res["reference_s"] = time.perf_counter() - t0
    progress(res, "full-KV reference done", start)
    res["token_agreement"] = token_agreement(out, ref, reqs)
    toks, last_pos, feed, kv_keep, store = _forced_inputs(reqs, ref,
                                                          n_forced)
    with trace_ctx(plan):
        hyb = forced_logits_hybrid(cfg, params, toks, last_pos, feed,
                                   kv_keep, store, kv_cap=cap, act_cap=cap)
        base = forced_logits_full_kv(cfg, params, toks, last_pos, feed)
    res["first_pos_err"] = logits_error(hyb[:, 0], base[:, 0])
    res["logits_err"] = logits_error(hyb, base)
    res["degraded"] = {k: 0 for k in DEGRADED}
    res["tol"] = tol
    res["ok"] = res["logits_err"] <= tol
    if plan is not None:
        res["ok"] = (res["ok"] and res["param_devices"] == plan.mesh.size
                     and res["cache_devices"] == plan.mesh.size
                     and min(res["device_bytes"]) >= share / 2)
    return res


def run_phase(fn, *args, **kw):
    """Run one phase with its compile clock; a raise fails the phase."""
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        res = fn(*args, **kw)
    except Exception:
        traceback.print_exc()
        res = {"phase": fn.__name__, "ok": False}
    finally:
        clock.close()
    res["wall_s"] = time.perf_counter() - t0
    res["compile_s"] = clock.seconds
    res["compiles"] = clock.compiles
    res["peak_bytes"] = peak_bytes()
    gc.collect()
    return res


def report(res) -> None:
    keys = ("compile_s", "compiles", "wall_s", "init_s", "serve_s",
            "reference_s", "tokens", "act_frac", "act_blocks", "peak_bytes",
            "first_pos_err", "logits_err", "tol", "token_agreement",
            "param_devices", "cache_devices", "device_bytes", "degraded",
            "ok")
    print(f"phase {res['phase']}: " + " ".join(
        f"{k}={res[k]}" for k in keys if k in res), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the tensor-parallel opt-13b phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: the default device is {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.core import costmodel as cm
    from repro.launch.compile_cache import use_compile_cache

    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache: {use_compile_cache()}", flush=True)
    hw = cm.hardware_for(dev)                    # unknown kinds raise
    results = []
    if args.chips == 4:
        from repro.launch.mesh import make_test_mesh
        from repro.sharding import make_shard_plan
        cfg = get_config("opt-13b")
        plan = make_shard_plan(cfg, make_test_mesh(1, 4))
        results.append(run_phase(
            phase_resident, cfg, make_requests(cfg.vocab_size, 4, args.seed),
            tol=TOL_OPT_13B, seed=args.seed, hw=hw, plan=plan))
        for d in devices:
            print(f"  device {d.id}: peak_bytes_in_use={peak_bytes(d)}",
                  flush=True)
    else:
        cfg = get_config("opt-6.7b")
        results.append(run_phase(
            phase_offload, cfg, make_requests(cfg.vocab_size, 4, args.seed),
            seed=args.seed, hw=hw))
        report(results[-1])
        cfg = get_config("yi-6b")
        results.append(run_phase(
            phase_resident, cfg,
            make_requests(cfg.vocab_size, 4, args.seed + 1),
            seed=args.seed, hw=hw))
    report(results[-1])
    if not all(r["ok"] for r in results):
        print("FAILED: " + ", ".join(r["phase"] for r in results
                                     if not r["ok"]), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
