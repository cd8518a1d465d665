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
- ``bench/traffic/<mix>.json``: the traffic mix (see ``bench/traffic.py``);
- ``bench/families/<family>.py``, where the configuration file names a
  ``"family"``: the architecture's own plain reference, weight scales and
  counts (see ``Cell.reference``, ``Cell.counts``, ``bench/weights.py``).
  A configuration with no ``family`` is a dense decoder, served by
  ``bench/reference.py``, ``weights.scale_for`` and ``bench/counts.py``.

A cell whose entry asks for more than one chip is served on a mesh of that
many chips: ``bench/run.py`` builds it and the program's shard plan, and
makes the weights in the plan's shardings.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

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
    family: Optional[ModuleType] = None   # bench/families/<family>.py

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])

    @property
    def reference(self) -> ModuleType:
        """The module whose ``logits`` is the plain float32 reference."""
        if self.family is not None:
            return self.family
        from bench import reference
        return reference

    @property
    def counts(self) -> ModuleType:
        """The module whose functions count the work's FLOPs and bytes."""
        if self.family is not None:
            return self.family
        from bench import counts
        return counts


def _load(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_family(name: str, directory: Path = BENCH / "families"
                ) -> ModuleType:
    """The module ``<directory>/<name>.py``: ``logits`` (the float32
    reference, as ``bench/reference.py``'s), ``scale_for`` (as
    ``weights.scale_for``'s, or None to defer to it) and the functions of
    ``bench/counts.py`` that the readers call."""
    path = directory / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no family module {path}")
    loc = importlib.util.spec_from_file_location(f"bench.families.{name}",
                                                 path)
    mod = importlib.util.module_from_spec(loc)
    loc.loader.exec_module(mod)
    return mod


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
    config = _load(base / "configs" / f"{entry['config']}.json")
    return Cell(
        name=name, entry=entry,
        sizes=_load(base / "cells" / f"{name}.json"),
        config=config,
        mix=traffic.load_mix(entry["traffic"], base / "traffic"),
        end_to_end=[m for m in bench["end_to_end"] if covers(m, name)],
        per_layer=[m for m in bench["per_layer"] if covers(m, name)],
        family=(load_family(config["family"], base / "families")
                if "family" in config else None))
