"""What the chip bring-up relies on, checked on the CPU backend.

Host-built weights, device-derived budgets and hardware specs, the compile
cache location, the microbenchmark's child process, and the phases of
``chip_smoke.py`` itself at reduced size (its chip-only guard included).
"""
import dataclasses
import json
import os
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.offload import offload_budget, resident_weight_bytes
from repro.core import costmodel as cm
from repro.models import model as M

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@pytest.mark.parametrize("name", ["opt-6.7b-reduced", "yi-6b-reduced"])
def test_init_params_on_host_matches_device_init(name):
    cfg = get_config(name)
    dev = M.init_params(cfg, jax.random.PRNGKey(3))
    host = M.init_params(cfg, jax.random.PRNGKey(3), on_host=True)
    assert jax.tree.structure(dev) == jax.tree.structure(host)
    for a, b in zip(jax.tree.leaves(dev), jax.tree.leaves(host)):
        assert isinstance(b, np.ndarray)
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), b)


class _FakeDevice:
    def __init__(self, kind, stats):
        self.device_kind, self._stats = kind, stats

    def memory_stats(self):
        return self._stats


def test_hardware_for_reads_the_device():
    hw = cm.hardware_for(_FakeDevice("TPU v5 lite", {"bytes_limit": 12345}))
    assert hw.device_mem == 12345
    assert hw.flops == cm.TPU_V5E.flops           # peaks from the table
    assert cm.hardware_for(_FakeDevice("TPU v5 lite", None)) is cm.TPU_V5E
    with pytest.raises(KeyError, match="TPU v9"):
        cm.hardware_for(_FakeDevice("TPU v9", {"bytes_limit": 1}))
    # the CPU backend plans with the v5e prior
    assert cm.local_hardware() is cm.TPU_V5E


def test_offload_budget_is_device_memory_less_resident_weights():
    cfg = get_config("opt-6.7b")
    hw = dataclasses.replace(cm.TPU_V5E, device_mem=15 * 2**30)
    budget = offload_budget(cfg, hw)
    assert budget.dev_bytes == 15 * 2**30 - resident_weight_bytes(cfg)
    # embedding (vocab padded to 50432) + 32k learned positions + norm, bf16
    assert resident_weight_bytes(cfg) == (50432 + 32768 + 2) * 4096 * 2


def test_compile_cache_location(monkeypatch):
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/cache")
    assert compile_cache.use_compile_cache() == "/somewhere/cache"
    assert calls == []                  # JAX's own reading of the env stands
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.use_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]


def test_microbench_child_is_held_to_cpu(monkeypatch):
    from repro.offload import microbench
    seen = {}

    class _Done:
        returncode = 0
        stderr = ""
        stdout = "BENCH_JSON " + json.dumps({"saving_s": 1.0})

    def fake_run(cmd, env, **kw):
        seen.update(env)
        return _Done()

    monkeypatch.setattr(microbench.subprocess, "run", fake_run)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setenv("XLA_FLAGS", "")
    r = microbench.weight_stream_microbench()
    assert seen["JAX_PLATFORMS"] == "cpu"
    assert r["saving_s"] == 1.0


def test_offload_server_keeps_weights_off_device():
    """The offload server admits through the layer-streamed prefill: the
    full parameter set is never a jit argument, and tokens match the
    device-resident server."""
    from repro.serving import ContinuousBatchingServer
    from repro.data.pipeline import open_loop_trace
    cfg = get_config("opt-6.7b-reduced")
    host = M.init_params(cfg, jax.random.PRNGKey(0), on_host=True)
    reqs, _ = open_loop_trace(cfg.vocab_size, 4, seed=5)
    with ContinuousBatchingServer(cfg, host, offload=True,
                                  chunk_steps=4) as srv:
        out, _ = srv.run(reqs)
        assert srv.params is None
        assert not any(isinstance(a, np.ndarray)
                       for a in jax.tree.leaves(srv.executor.resident))
    ref, _ = ContinuousBatchingServer(cfg, jax.device_put(host),
                                      chunk_steps=4).run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(out[r.rid], ref[r.rid])


def test_chip_smoke_refuses_cpu(capsys):
    import chip_smoke
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr().out
    assert '"ok"' not in out


@pytest.mark.parametrize("phase", ["offload", "resident"])
def test_chip_smoke_phase_at_reduced_size(phase):
    """The smoke's phases end to end at reduced widths: the hybrid run
    stores ACT checkpoints and its teacher-forced logits match the
    reference within the smoke's tolerance."""
    import chip_smoke
    name = "opt-6.7b-reduced" if phase == "offload" else "yi-6b-reduced"
    cfg = get_config(name)
    reqs = chip_smoke.make_requests(cfg.vocab_size, 4, 0, prompt=(32, 96),
                                    new=(4, 12))
    fn = (chip_smoke.phase_offload if phase == "offload"
          else chip_smoke.phase_resident)
    res = chip_smoke.run_phase(fn, cfg, reqs, n_forced=3)
    assert res["ok"], res
    assert res["act_blocks"] > 0
    assert res["tokens"] == sum(r.max_new_tokens for r in reqs)
    assert res["logits_err"] < 1e-4          # float32 at reduced size
