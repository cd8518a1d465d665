"""Kernel micro-bench: wall-clock of the pure-jnp oracle vs the Pallas
interpreter on CPU.  Interpreter timings are NOT TPU performance — this
exists to (a) exercise the kernels end-to-end and (b) report the analytic
MXU-time estimate for the target chip.

Emits ``BENCH_kernels.json`` (cwd) so the perf trajectory — hybrid-attention
page-grid behaviour and the engine's host<->device sync count per request —
is tracked across PRs.
"""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import CHIP_FLOPS, emit

RECORDS = []


def _emit(name, us_per_call, derived="", **extra):
    emit(name, us_per_call, derived)
    RECORDS.append(dict(name=name, us_per_call=us_per_call, derived=derived,
                        **extra))


def _time(f, *args, reps=3):
    f(*args)[0].block_until_ready() if isinstance(f(*args), tuple) else None
    t0 = time.perf_counter()
    for _ in range(reps):
        r = f(*args)
        jax.tree.leaves(r)[0].block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e6


def _bench_kv_gen():
    from repro.kernels.kv_gen.kernel import kv_gen
    from repro.kernels.kv_gen.ref import kv_gen_ref
    d, kvh, hd, n = 512, 4, 128, 8
    act = jax.random.normal(jax.random.PRNGKey(0), (n, 16, d))
    sc = jnp.ones((d,))
    wk = jax.random.normal(jax.random.PRNGKey(1), (d, kvh, hd)) * 0.05
    wv = jax.random.normal(jax.random.PRNGKey(2), (d, kvh, hd)) * 0.05
    us_ref = _time(lambda *a: kv_gen_ref(*a), act, sc, wk, wv)
    flops = 2 * n * 16 * d * 2 * kvh * hd
    tpu_us = flops / CHIP_FLOPS * 1e6
    _emit("kernel.kv_gen.ref_cpu", us_ref,
          f"analytic_tpu_v5e={tpu_us:.3f}us_per_call flops={flops:.2e}")


def _bench_ssd():
    from repro.kernels.ssd_scan.kernel import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_ref_chunked
    b, s, h, p, nn, c = 1, 256, 4, 32, 64, 32
    x = jax.random.normal(jax.random.PRNGKey(3), (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(4), (b, s, h)))
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(5), (h,)) * 0.3)
    B = jax.random.normal(jax.random.PRNGKey(6), (b, s, nn)) * 0.3
    C = jax.random.normal(jax.random.PRNGKey(7), (b, s, nn)) * 0.3
    us_ref = _time(lambda *a: ssd_ref_chunked(*a, chunk=c), x, dt, A, B, C)
    us_ker = _time(lambda *a: ssd_scan(*a, chunk=c, interpret=True),
                   x, dt, A, B, C)
    _emit("kernel.ssd_scan.ref_cpu", us_ref, "pure-jnp chunked")
    _emit("kernel.ssd_scan.interp_cpu", us_ker,
          "pallas interpreter (correctness mode, not perf)")


def _hybrid_tables(kind, B, MAXP, used, rng):
    """Page tables for the two decode regimes the kernel must not waste grid
    iterations on: mostly-empty tables (long MAXP, short requests) and
    ACT-heavy tables (deep into a hybrid-cached generation)."""
    pt = np.zeros((B, MAXP), np.int32)
    pty = np.full((B, MAXP), 2, np.int32)
    pn = np.zeros((B, MAXP), np.int32)
    n_kv = n_act = 0
    for b in range(B):
        slots = sorted(rng.choice(MAXP, size=used, replace=False))
        for j, p in enumerate(slots):
            is_act = (j % 4 != 3) if kind == "act_heavy" else (j % 4 == 3)
            pty[b, p] = 1 if is_act else 0
            if is_act:
                pt[b, p] = n_act % 8
                n_act += 1
            else:
                pt[b, p] = n_kv % 8
                n_kv += 1
            pn[b, p] = 16 if j < used - 1 else int(rng.integers(1, 17))
    return jnp.asarray(pt), jnp.asarray(pty), jnp.asarray(pn)


def _bench_hybrid_attention():
    from repro.kernels.hybrid_attention.kernel import hybrid_paged_attention
    from repro.kernels.hybrid_attention.ref import hybrid_paged_attention_ref
    B, kvh, G, D, T, d_model = 4, 2, 4, 64, 16, 256
    rng = np.random.default_rng(0)
    q = jax.random.normal(jax.random.PRNGKey(0), (B, kvh, G, D))
    ks = jax.random.normal(jax.random.PRNGKey(1), (8, T, kvh, D)) * 0.3
    vs = jax.random.normal(jax.random.PRNGKey(2), (8, T, kvh, D)) * 0.3
    ap = jax.random.normal(jax.random.PRNGKey(3), (8, T, d_model)) * 0.5
    sc = jnp.ones((d_model,))
    wk = jax.random.normal(jax.random.PRNGKey(4), (d_model, kvh, D)) * 0.05
    wv = jax.random.normal(jax.random.PRNGKey(5), (d_model, kvh, D)) * 0.05

    for kind, MAXP, used in (("empty_heavy", 48, 6), ("act_heavy", 12, 10)):
        pt, pty, pn = _hybrid_tables(kind, B, MAXP, used, rng)
        args = (q, ks, vs, ap, sc, wk, wv, pt, pty, pn)
        us_full = _time(lambda *a: hybrid_paged_attention(
            *a, norm_type="layernorm", interpret=True), *args, reps=2)
        us_bound = _time(lambda *a: hybrid_paged_attention(
            *a, norm_type="layernorm", pages_bound=used, interpret=True),
            *args, reps=2)
        us_ref = _time(lambda *a: hybrid_paged_attention_ref(
            *a, norm_type="layernorm"), *args, reps=2)
        # analytic TPU estimate: QK^T+PV over used pages + one Eq.7
        # projection per ACT page (norm hoisted: counted once per page)
        n_act_pages = int((np.asarray(pty) == 1).sum())
        attn_flops = 2 * 2 * B * used * T * kvh * G * D
        gen_flops = 2 * n_act_pages * T * d_model * 2 * kvh * D
        tpu_us = (attn_flops + gen_flops) / CHIP_FLOPS * 1e6
        _emit(f"kernel.hybrid_attention.{kind}.interp_cpu", us_full,
              f"grid=(B,{MAXP},{kvh}) used={used}", maxp=MAXP, used=used)
        _emit(f"kernel.hybrid_attention.{kind}.interp_cpu_bound", us_bound,
              f"grid=(B,{used},{kvh}) pages_bound={used} "
              f"analytic_tpu_v5e={tpu_us:.3f}us", maxp=MAXP, used=used,
              grid_iters_full=B * MAXP * kvh, grid_iters_bound=B * used * kvh)
        _emit(f"kernel.hybrid_attention.{kind}.ref_cpu", us_ref, "pure-jnp")


def _bench_sharded_hybrid_attention():
    """§7.4 hybrid-attention kernel under the mesh (DESIGN.md §11): the
    kernel's KV-head grid dimension is embarrassingly parallel, so a 2-way
    ``shard_map`` over 'model' runs each head half on its own device with
    the page tables replicated — output bit-identical to the replicated
    kernel (per-head math is untouched; only placement changes).  The row
    tracks kernel-level shard overhead (interpreter wall time is NOT TPU
    perf, but a 10x regression in the sharded wrapper would show).  Skipped
    below 2 devices — the shard-invariance CI lane runs it under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``."""
    if jax.device_count() < 2:
        _emit("kernel.hybrid_attention.sharded.skipped", 0.0,
              "needs 2 devices (XLA_FLAGS="
              "--xla_force_host_platform_device_count=4)")
        return
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.kernels.hybrid_attention.kernel import hybrid_paged_attention
    B, kvh, G, D, T, d_model = 4, 2, 4, 64, 16, 256
    rng = np.random.default_rng(0)
    q = jax.random.normal(jax.random.PRNGKey(0), (B, kvh, G, D))
    ks = jax.random.normal(jax.random.PRNGKey(1), (8, T, kvh, D)) * 0.3
    vs = jax.random.normal(jax.random.PRNGKey(2), (8, T, kvh, D)) * 0.3
    ap = jax.random.normal(jax.random.PRNGKey(3), (8, T, d_model)) * 0.5
    sc = jnp.ones((d_model,))
    wk = jax.random.normal(jax.random.PRNGKey(4), (d_model, kvh, D)) * 0.05
    wv = jax.random.normal(jax.random.PRNGKey(5), (d_model, kvh, D)) * 0.05
    pt, pty, pn = _hybrid_tables("act_heavy", B, 12, 10, rng)
    pt, pty, pn = jnp.asarray(pt), jnp.asarray(pty), jnp.asarray(pn)
    args = (q, ks, vs, ap, sc, wk, wv, pt, pty, pn)

    kern = lambda *a: hybrid_paged_attention(*a, norm_type="layernorm",
                                             interpret=True)
    mesh = jax.make_mesh((2,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    rep = P(None)
    f_sharded = shard_map(
        kern, mesh=mesh,
        in_specs=(P(None, "model", None, None),      # q: kv-head sharded
                  P(None, None, "model", None),      # k pages
                  P(None, None, "model", None),      # v pages
                  P(None, None, None),               # act pages: replicated
                  rep,                               # norm scale
                  P(None, "model", None),            # wk
                  P(None, "model", None),            # wv
                  P(None, None), P(None, None), P(None, None)),  # tables
        out_specs=P(None, "model", None, None),
        check_rep=False)
    out_rep = kern(*args)
    out_sh = f_sharded(*args)
    np.testing.assert_array_equal(np.asarray(out_rep), np.asarray(out_sh))
    us_rep = _time(lambda *a: kern(*a), *args, reps=2)
    us_sh = _time(lambda *a: f_sharded(*a), *args, reps=2)
    _emit("kernel.hybrid_attention.sharded.replicated", us_rep,
          f"grid=(B,12,{kvh}) 1 device", kvh=kvh)
    _emit("kernel.hybrid_attention.sharded.head_sharded_2way", us_sh,
          f"grid=(B,12,{kvh // 2}) x2 devices, bit-identical, "
          f"overhead={us_sh / max(us_rep, 1e-9):.2f}x",
          kvh=kvh, overhead_ratio=us_sh / max(us_rep, 1e-9))


def _bench_engine_syncs():
    """Host<->device round trips per request: the scan-based engine does ONE
    batched prefill + ONE decode-loop dispatch per group, vs (B prefills +
    max_new decode steps + max_new argmax pulls) for the seed's per-token
    loop — the Fig. 12 hot-path overhead the tentpole removes."""
    from repro.configs import get_config
    from repro.data import request_trace
    from repro.models import model as M
    from repro.serving import HybridServeEngine
    cfg = get_config("opt-6.7b-reduced")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    gen_tokens, n_req = 12, 4
    reqs = request_trace(cfg.vocab_size, n_req, prompt_mean=40,
                         gen_tokens=gen_tokens, seed=3)
    eng = HybridServeEngine(cfg, params, mode="hybrid", max_minibatch=4,
                            kv_cap=128, act_cap=128)
    n_groups = len(eng.plan_groups(reqs))    # independent of measured stats
    out, stats = eng.generate(reqs)          # compile
    t0 = time.perf_counter()
    out, stats = eng.generate(reqs)
    wall_us = (time.perf_counter() - t0) * 1e6
    # seed engine: one prefill per request + one decode dispatch per token
    # per group; the scan engine does 2 dispatches per group
    seed_calls = n_req + n_groups * gen_tokens
    ratio = seed_calls / max(stats.device_calls, 1)
    _emit("engine.decode.device_calls", float(stats.device_calls),
          f"per_group seed_equiv={seed_calls} reduction={ratio:.1f}x "
          f"wall={wall_us:.0f}us gen_tokens={stats.generated_tokens}",
          seed_equiv_calls=seed_calls, reduction=ratio,
          generated_tokens=stats.generated_tokens, wall_us=wall_us)


def _bench_weight_stream():
    """Host-offload runtime lanes (DESIGN.md §8): weight uploads back-to-back
    (stream-only), the layer loop with resident shards (compute-only), and
    the double-buffered executor (overlapped).  The overlapped wall time
    must come in under stream+compute — the copy stream actually hides
    transfers behind KV-Gen + forward compute, the paper's Fig. 8 overlap
    measured rather than simulated."""
    from repro.offload.microbench import weight_stream_microbench
    r = weight_stream_microbench()
    _emit("offload.weight_stream.stream_only", r["stream_s"] * 1e6,
          f"bytes={r['weight_bytes_streamed']:.2e}")
    _emit("offload.weight_stream.compute_only", r["compute_s"] * 1e6, "")
    _emit("offload.weight_stream.overlapped", r["overlap_s"] * 1e6,
          f"saving={r['saving_s']*1e6:.0f}us "
          f"overlap_eff={r['overlap_efficiency']:.2f} "
          f"depth={int(r['prefetch_depth'])} "
          f"overlap_lt_sum={r['overlap_s'] < r['stream_s'] + r['compute_s']}",
          **r)


def run():
    RECORDS.clear()
    _bench_kv_gen()
    _bench_ssd()
    _bench_hybrid_attention()
    _bench_sharded_hybrid_attention()
    _bench_engine_syncs()
    _bench_weight_stream()
    with open("BENCH_kernels.json", "w") as f:
        json.dump(RECORDS, f, indent=2)
    print("wrote BENCH_kernels.json")
