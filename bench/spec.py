"""What a cell is, read from data files found by name.

- ``BENCHMARK.json`` (the checkout's root): the cell's entry (its config,
  traffic and chips) and the metrics, with the cells each one covers;
- ``bench/cells/<cell>.json``: the server's sizes for the cell (``slots``,
  ``kv_cap``, ``act_cap``, ``chunk_steps``), how its clients start
  (``client_start_every`` steps apart), how long its warm-up and ramp are
  (``warm_chunks``, ``ramp_chunks``), how many requests it queues
  (``backlog``), and how many served tokens its check compares
  (``check_tokens``) under which limit (``gap_limit``);
- ``bench/configs/<config>.json``: the model and its regime, its sizes and
  its source;
- ``bench/traffic/<mix>.json``: the traffic mix (see ``bench/traffic.py``).
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

from bench import traffic

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    entry: Dict
    sizes: Dict
    config: Dict
    mix: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])


def _load(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def covers(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, benchmark: Path = ROOT / "BENCHMARK.json",
              base: Path = BENCH) -> Cell:
    """The cell ``name`` of ``benchmark``, its files found by name under
    ``base``."""
    bench = _load(benchmark)
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise KeyError(f"no workload {name!r} in {benchmark}")
    entry = entries[0]
    return Cell(
        name=name, entry=entry,
        sizes=_load(base / "cells" / f"{name}.json"),
        config=_load(base / "configs" / f"{entry['config']}.json"),
        mix=traffic.load_mix(entry["traffic"], base / "traffic"),
        end_to_end=[m for m in bench["end_to_end"] if covers(m, name)],
        per_layer=[m for m in bench["per_layer"] if covers(m, name)])
