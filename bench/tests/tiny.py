"""A reduced-size cell for whole runs of the harness on the CPU, and the
faults the tests plant in its timed path."""
import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np

from bench import spec

DATA = Path(__file__).resolve().parent / "data"


def cell(config: str) -> spec.Cell:
    load = lambda n: json.loads((DATA / n).read_text())
    return spec.Cell(
        name="tiny", entry={"chips": 1}, sizes=load("tiny-sizes.json"),
        config=load(config), mix=load("tiny-mix.json"),
        end_to_end=[{"name": "tokens_per_s", "unit": "tokens/s"},
                    {"name": "itl_p50_ms", "unit": "ms"},
                    {"name": "setup_s", "unit": "s"}],
        per_layer=[])


def run(config: str, mutate=None):
    from bench.run import execute
    return execute(cell(config), 2**32 + 17, 0.3, False,
                   device_check=False, mutate=mutate)


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


def check_sound_and_broken(config: str) -> None:
    ok = run(config)
    assert ok["correct"], ok["checks"]
    assert ok["checks"]["widest_logit_gap"]["value"] <= 1e-3
    assert ok["checks"]["act_tokens_in_compared"]["value"] > 0
    assert set(ok["metrics"]) == {"tokens_per_s", "itl_p50_ms", "setup_s"}
    assert list(ok)[-1] == "checks"
    for fault in (alter_tokens, state_unchanged):
        bad = run(config, mutate=fault)
        assert not bad["correct"], (fault.__name__, bad["checks"])
        assert bad["checks"]["widest_logit_gap"]["value"] > \
            bad["checks"]["widest_logit_gap"]["limit"]
