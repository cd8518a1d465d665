"""A reduced-size cell for whole runs of the harness on the CPU, and the
faults the tests plant in its timed path.  A cell on ``chips`` > 1 needs
that many devices (``XLA_FLAGS=--xla_force_host_platform_device_count``,
set before JAX starts: ``in_devices``)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec

DATA = Path(__file__).resolve().parent / "data"
ROOT = DATA.parents[2]


def cell(config: str, chips: int = 1) -> spec.Cell:
    load = lambda n: json.loads((DATA / n).read_text())
    return spec.Cell(
        name="tiny", entry={"chips": chips}, sizes=load("tiny-sizes.json"),
        config=load(config), mix=load("tiny-mix.json"),
        end_to_end=[{"name": "tokens_per_s", "unit": "tokens/s"},
                    {"name": "itl_p50_ms", "unit": "ms"},
                    {"name": "setup_s", "unit": "s"}],
        per_layer=[])


def run(config: str, mutate=None, chips: int = 1):
    from bench.run import execute
    return execute(cell(config, chips), 2**32 + 17, 0.3, False,
                   device_check=False, mutate=mutate)


def in_devices(code: str, devices: int = 4, timeout: int = 900) -> str:
    """Run ``code`` in a fresh CPU process with ``devices`` devices; its
    stdout (it fails the caller's test where the process fails)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices}"),
        PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=timeout, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-4000:]
    return p.stdout


# --- faults planted in the timed path ---------------------------------------
def alter_tokens(server):
    """Every served token altered where the decode chunk produces it."""
    V = server.cfg.vocab_size
    if server.executor is not None:
        orig = server.executor.decode_chunk

        def bad(*a, **k):
            toks, cur, cache = orig(*a, **k)
            return np.where(toks >= 0, (toks + 1) % V, toks), cur, cache
        server.executor.decode_chunk = bad
    else:
        orig = server._decode_chunk_jit

        def bad(*a, **k):
            toks, cur, cache = orig(*a, **k)
            return jnp.where(toks >= 0, (toks + 1) % V, toks), cur, cache
        server._decode_chunk_jit = bad


def state_unchanged(server):
    """The decode chunk returns the cache rows it was given: lengths move on,
    nothing is written."""
    def keep(orig):
        def bad(*a, **k):
            cache = a[2] if server.executor is None else a[1]
            old = {n: jnp.array(cache[n], copy=True)
                   for n in ("k", "v", "act")}
            toks, cur, new = orig(*a, **k)
            new = dict(new, **old)
            return toks, cur, new
        return bad
    if server.executor is not None:
        server.executor.decode_chunk = keep(server.executor.decode_chunk)
    else:
        server._decode_chunk_jit = keep(server._decode_chunk_jit)


def exchange_left_out(server):
    """The exchange between chips left out: the products whose input is
    split over the chips (the attention's output projection ``wo`` and the
    FFN's ``w2``, split over their rows) sum only the first chip's share,
    what that chip holds before the all-reduce.  Mesh cells only."""
    n = server.plan.model_shards

    def first_share(a):
        return jax.device_put(a.at[:, a.shape[1] // n:].set(0), a.sharding)
    layers = dict(server.params["layers"])
    layers["attn"] = dict(layers["attn"],
                          wo=first_share(layers["attn"]["wo"]))
    layers["ffn"] = dict(layers["ffn"], w2=first_share(layers["ffn"]["w2"]))
    server.params = dict(server.params, layers=layers)


def check_sound_and_broken(config: str, chips: int = 1) -> None:
    ok = run(config, chips=chips)
    assert ok["correct"], ok["checks"]
    assert ok["checks"]["widest_logit_gap"]["value"] <= 1e-3
    assert ok["checks"]["act_tokens_in_compared"]["value"] > 0
    assert set(ok["metrics"]) == {"tokens_per_s", "itl_p50_ms", "setup_s"}
    assert list(ok)[-1] == "checks"
    faults = (alter_tokens, state_unchanged)
    if chips > 1:
        faults += (exchange_left_out,)
    for fault in faults:
        bad = run(config, mutate=fault, chips=chips)
        assert not bad["correct"], (fault.__name__, bad["checks"])
        assert bad["checks"]["widest_logit_gap"]["value"] > \
            bad["checks"]["widest_logit_gap"]["limit"]
