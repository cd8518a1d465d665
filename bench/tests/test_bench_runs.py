"""Whole runs of the harness on the CPU at the program's reduced smoke size:
the refusal off the chip, a sound offload run (``correct`` true), the same
run with the timed path broken underneath (``correct`` false), and the
float8 control at small size."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np

from bench import correct, reference, weights
from bench.tests import tiny

BENCH = Path(__file__).resolve().parents[1]


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "opt-6.7b.offload.longprompt16", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_offload_sound_and_broken_runs():
    tiny.check_sound_and_broken("tiny-opt-offload.json")


def test_float8_control_reads_wider_than_the_limit():
    """The control (the reference in float8 in the server's place) comes
    out not correct under the tiny cell's checks, while the reference in
    the server's place reads 0 and holds."""
    from repro.configs import get_config
    cfg_file = json.loads((tiny.DATA / "tiny-yi-resident.json").read_text())
    cfg = get_config(cfg_file["model"])
    params = weights.make_resident(cfg, cfg_file, 5)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, 1024, n).astype(np.int32) for n in (40, 77)]
    want = [np.arange(20, 40), np.arange(50, 77)]
    rest = {k: v for k, v in params.items() if k != "layers"}
    layer = lambda l: jax.tree.map(lambda a: a[l], params["layers"])
    ref = reference.logits(cfg_file, rest, layer, seqs, want)
    ctl = reference.logits(cfg_file, rest, layer, seqs, want, "fp8")
    sizes = json.loads((tiny.DATA / "tiny-sizes.json").read_text())
    limit = sizes["gap_limit"]
    same = correct.checks(correct.control_gaps(ref, ref), limit, 1, 0)
    assert same["widest_logit_gap"]["value"] == 0.0
    assert all(c["holds"] for c in same.values())
    low = correct.checks(correct.control_gaps(ref, ctl), limit, 1, 0)
    assert low["widest_logit_gap"]["value"] > limit
    assert not low["widest_logit_gap"]["holds"]
