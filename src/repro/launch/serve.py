"""Serving launcher: the HybridServe engine or the continuous server.

  PYTHONPATH=src python -m repro.launch.serve --arch opt-6.7b-reduced \
      --requests 8 --mode hybrid

Runs on whatever JAX's default backend is: a reduced config on CPU, or a
published one on a TPU.  Weights are built on the host; when they would take
more than ``OFFLOAD_FRACTION`` of the device's memory they stay there and
stream per layer (the offload runtime, DESIGN.md §8), otherwise they are
placed on the device (or its mesh) once.  The persistent compilation cache
is ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.

Mesh-sharded serving (DESIGN.md §11): pass ``--mesh data,model`` to run the
same engine tensor-parallel.  On a CPU-only box force host devices first:

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      PYTHONPATH=src python -m repro.launch.serve --arch opt-6.7b-reduced \
      --mesh 2,2 --verify

Observability (DESIGN.md §13): ``--trace out.json`` records the full
request/lane lifecycle and writes a Chrome-trace file (open it in
https://ui.perfetto.dev or chrome://tracing); ``--snapshot`` prints the
unified metrics snapshot after the run.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core import costmodel as cm
from repro.data import request_trace
from repro.launch.compile_cache import use_compile_cache
from repro.models import model as M
from repro.serving import HybridServeEngine, exact_reference_generate

#: weights above this share of device memory leave too little room for the
#: cache and the step's temporaries: serve them from host memory instead
OFFLOAD_FRACTION = 0.75


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--mode", default="hybrid", choices=["hybrid", "kv", "act"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-mean", type=int, default=64)
    ap.add_argument("--gen-tokens", type=int, default=16)
    ap.add_argument("--verify", action="store_true",
                    help="check token-exactness against the plain-KV reference")
    ap.add_argument("--continuous", action="store_true",
                    help="iteration-level continuous batching (Orca-style)")
    ap.add_argument("--chunk-steps", type=int, default=1,
                    help="decode iterations per jitted dispatch in the "
                         "continuous server (1 = classic step server; "
                         "larger chunks amortize the dispatch tax at the "
                         "cost of admission latency, DESIGN.md §10)")
    ap.add_argument("--mesh", default="1,1", metavar="DATA,MODEL",
                    help="serving mesh shape; the ShardPlan built from it "
                         "drives every subsystem (DESIGN.md §11).  Needs "
                         "data*model devices — on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N first")
    ap.add_argument("--explain-plan", action="store_true",
                    help="print the ShardPlan decision log and exit")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record request-lifecycle + lane spans and export "
                         "a Chrome-trace/Perfetto JSON file (DESIGN.md §13)")
    ap.add_argument("--snapshot", action="store_true",
                    help="print the unified metrics snapshot after the run")
    args = ap.parse_args(argv)

    tracer, metrics = None, None
    if args.trace or args.snapshot:
        from repro.obs import MetricsRegistry, Tracer
        metrics = MetricsRegistry()
        if args.trace:
            tracer = Tracer()

    use_compile_cache()
    cfg = get_config(args.arch)
    hw = cm.local_hardware()
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{jax.device_count()}")
    params = M.init_params(cfg, jax.random.PRNGKey(0), on_host=True)
    data, model_ax = (int(x) for x in args.mesh.split(","))
    plan = None
    if (data, model_ax) != (1, 1) or args.explain_plan:
        from repro.launch.mesh import make_test_mesh
        from repro.sharding import make_shard_plan
        mesh = make_test_mesh(data, model_ax)
        plan = make_shard_plan(cfg, mesh, params)
        print(plan.explain() if args.explain_plan else
              plan.explain().splitlines()[0])
        if args.explain_plan:
            return None, None
    shards = plan.shard_factor if plan is not None else 1
    offload = (cfg.num_params() * cfg.bytes_per_param()
               > OFFLOAD_FRACTION * hw.device_mem * shards)
    if offload:
        print("weights exceed the device budget: streaming from host memory")
    else:
        params = (plan.place_params(params) if plan is not None
                  else jax.device_put(params))
    reqs = request_trace(cfg.vocab_size, args.requests,
                         prompt_mean=args.prompt_mean,
                         gen_tokens=args.gen_tokens, seed=1)
    if args.continuous:
        from repro.serving import ContinuousBatchingServer
        eng = ContinuousBatchingServer(cfg, params, slots=4,
                                       chunk_steps=args.chunk_steps,
                                       plan=plan, offload=offload,
                                       tracer=tracer, metrics=metrics)
        print(f"continuous batching: 4 slots, chunk_steps="
              f"{args.chunk_steps}, act_frac={eng.act_frac:.2f}")
        t0 = time.time()
        out, stats = eng.run(reqs)
        wall = time.time() - t0
        print(f"{stats.generated_tokens} tokens in {stats.steps} iterations, "
              f"{stats.device_calls} dispatches "
              f"({stats.dispatches_per_token:.2f}/token, {wall:.1f}s wall); "
              f"simulated {stats.throughput:.1f} tok/s")
        if args.verify:
            ref = exact_reference_generate(cfg, params, reqs)
            ok = all(np.array_equal(out[r.rid], ref[r.rid]) for r in reqs)
            print(f"token-exact: {ok}")
            assert ok
        _export_obs(args, eng, tracer)
        return out, stats
    eng = HybridServeEngine(cfg, params, mode=args.mode, plan=plan,
                            offload=offload, tracer=tracer, metrics=metrics)
    print(f"engine: mode={args.mode} host ACT:KV ratio="
          f"{eng.alloc.act_blocks}:{eng.alloc.kv_blocks} (act_frac={eng.act_frac:.2f})")
    t0 = time.time()
    out, stats = eng.generate(reqs)
    wall = time.time() - t0
    print(f"generated {stats.generated_tokens} tokens in {stats.steps} steps "
          f"({wall:.1f}s wall on {dev.platform})")
    print(f"simulated on {eng.hw.name}: throughput={stats.sim_throughput:.1f} tok/s "
          f"gpu_util={stats.sim_gpu_util:.1%}")
    if stats.traffic:
        tr = {k: f"{v/2**20:.1f}MiB" for k, v in stats.traffic.items()}
        print(f"simulated PCIe traffic: {tr}")
    if args.verify:
        ref = exact_reference_generate(cfg, params, reqs)
        ok = all(np.array_equal(out[r.rid], ref[r.rid]) for r in reqs)
        print(f"token-exact vs full-KV reference: {ok}")
        assert ok
    _export_obs(args, eng, tracer)
    return out, stats


def _export_obs(args, eng, tracer):
    if tracer is not None:
        tracer.export(args.trace)
        print(f"trace: {len(tracer.events())} events -> {args.trace} "
              f"(open in https://ui.perfetto.dev)")
    if args.snapshot:
        snap = eng.snapshot()
        print("metrics snapshot:")
        for k in sorted(snap):
            print(f"  {k} = {snap[k]}")


if __name__ == "__main__":
    main()
