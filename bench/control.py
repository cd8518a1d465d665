#!/usr/bin/env python3
"""Readings that set and test a cell's limit, on the chip at the cell's size.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 51 \\
        [--traffic <mix>]

For each seed, one run of the cell as ``bench/run.py`` makes it (the
cell's own window and load), then, against the same float32 reference over
the same served tokens (warm-up and window):

- ``program``: what ``run.py`` compares, its ``checks`` and ``correct``;
- ``control``: the reference computed in float8 put in the server's place
  (``bench/reference.py``): at each served position, the gap of the token
  the float8 forward ranks first, judged by the same ``checks`` against the
  same limit.  ``control_correct`` has to come out false;
- ``padded_prompt``: the widest gap of a served token against the reference
  run over each prompt padded, as the server pads it, to a multiple of 16
  tokens with its last token.  With ``--traffic`` naming a mix whose
  prompts are not multiples of 16, ``program`` reading far above
  ``padded_prompt`` shows that the served tokens continue the padded prompt
  rather than the prompt that was sent (read only where some prompt is
  not a multiple of 16).

The benchmark's own runs never run this.  Prints one JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as bench_run  # noqa: E402  (puts src/ on the path)
from bench import correct, spec, traffic  # noqa: E402

PAD = 16


def _readings(*, cell, rest, layer, finished, rids, ref, act):
    seqs, want = correct.sequences(finished, rids)
    ctl = cell.reference.logits(cell.config, rest, layer, seqs, want, "fp8")
    ctl_checks = correct.checks(correct.control_gaps(ref, ctl),
                                float(cell.sizes["gap_limit"]), act, 0)
    out = {"control": ctl_checks["widest_logit_gap"]["value"],
           "control_correct": all(c["holds"] for c in ctl_checks.values()),
           "prompts_not_multiple_of_16": int(sum(
               len(finished[r][0]) % PAD != 0 for r in rids))}
    if out["prompts_not_multiple_of_16"]:
        padded = {}
        for r in rids:
            p, s = finished[r]
            pb = -(-len(p) // PAD) * PAD
            padded[r] = (np.concatenate([p, np.full(pb - len(p), p[-1],
                                                    np.int32)]), s)
        pseqs, pwant = correct.sequences(padded, rids)
        pref = cell.reference.logits(cell.config, rest, layer, pseqs, pwant)
        out["padded_prompt"] = float(
            correct.served_gaps(pref, padded, rids).max())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--traffic", default=None)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    if args.traffic:
        cell.mix = traffic.load_mix(args.traffic)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        out = bench_run.execute(cell, seed, args.seconds, False,
                                readings=_readings, t_start=t0)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "program": out["checks"]["widest_logit_gap"]
                          ["value"], "readings": out["readings"],
                          "checks": out["checks"],
                          "metrics": out["metrics"],
                          "peak": out["device"]["memory_peak_bytes"],
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
