"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``bench/traffic/<name>.json``) gives the prompt and output length
distributions, the token-id distribution and the loop.  Lengths are drawn at
the quantiles of their distributions (``strata`` of them), so every seed
serves the same multiset of sizes; the seed only sets their order (a fresh
permutation per block of ``strata`` requests, prompts and outputs permuted
independently) and the token ids.  Two seeds therefore do the same work in
another order, and the spread between seeds is not a spread of work.

Distributions:

- ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``:
  a lognormal with median m, clipped to [a, b];
- ``{"dist": "uniform", "min": a, "max": b}``: uniform on the integers a..b.

``prompt_multiple_of`` (optional) rounds every prompt length up to a
multiple of that many tokens (down where that would pass ``max``).
``order_seed`` (optional) draws the order of the lengths from that fixed
seed instead of the run's, so every seed serves the same lengths in the
same order and only the token ids (and the weights) change with the seed:
for a cell whose window holds only a handful of requests, where the order
decides how much work falls in the window.

``stream`` numbers independent streams of one run: each has its own token
ids, and all have the lengths of stream 0 in the same order, so serving
stream 1 takes the server through the same schedule and the same shapes as
stream 0 (the warm-up serves stream 1, the window stream 0).

Token ids are Zipf(a) over the model's vocabulary, drawn by inverse CDF on
ranks as the repo's ``data/pipeline._zipf`` draws them (copied here, so that
program changes cannot move the yardstick).
"""
from __future__ import annotations

import json
import math
import statistics
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclass(frozen=True)
class Item:
    """One request of the stream: its index, prompt ids and output length."""
    index: int
    prompt: np.ndarray
    max_new_tokens: int


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> Dict:
    """The mix file ``<directory>/<name>.json``, found by name."""
    path = directory / f"{name}.json"
    with open(path) as f:
        mix = json.load(f)
    if mix.get("loop") != "closed":
        raise ValueError(f"{path}: only closed-loop mixes are generated")
    return mix


def quantile_lengths(spec: Dict, n: int) -> np.ndarray:
    """n lengths at the quantiles (i + 0.5) / n of the distribution."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.asarray([statistics.NormalDist().inv_cdf(float(p)) for p in q])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + q * (spec["max"] - spec["min"] + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _zipf_cdf(a: float, vocab: int) -> np.ndarray:
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** (-a))
    return cdf / cdf[-1]


def generate(mix: Dict, vocab: int, seed: int, n: int,
             stream: int = 0) -> List[Item]:
    """The first ``n`` requests of stream ``stream`` of the mix for
    ``seed``."""
    strata = int(mix["strata"])
    prompts = quantile_lengths(mix["prompt"], strata)
    step = int(mix.get("prompt_multiple_of", 1))
    prompts = np.minimum(-(-prompts // step) * step,
                         mix["prompt"]["max"] // step * step)
    outputs = quantile_lengths(mix["output"], strata)
    order = np.random.default_rng(np.random.SeedSequence(
        [int(mix.get("order_seed", seed)), 0]))
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(seed), 1 + int(stream)]))
    ids = mix["token_ids"]
    if ids["dist"] != "zipf":
        raise ValueError(f"unknown token-id distribution {ids['dist']!r}")
    cdf = _zipf_cdf(float(ids["a"]), vocab)
    items: List[Item] = []
    for block in range(math.ceil(n / strata)):
        p_order = order.permutation(strata)
        o_order = order.permutation(strata)
        for j in range(strata):
            i = block * strata + j
            if i >= n:
                break
            plen = int(prompts[p_order[j]])
            toks = np.searchsorted(cdf, rng.random(plen), side="right")
            items.append(Item(i, np.minimum(toks, vocab - 1).astype(np.int32),
                              int(outputs[o_order[j]])))
    return items


def client_arrivals(n: int, clients: int, start_every: int) -> List[int]:
    """Scheduler steps at which each request joins the queue.

    A closed loop of ``clients`` clients with zero think time.  Client c
    sends its first request at step ``c * start_every`` (so the clients do
    not all start in one burst); every later request is queued from the
    last client's start on and is admitted as a slot frees, which is the
    moment its client's previous request completed."""
    last = (clients - 1) * start_every
    return [i * start_every if i < clients else last for i in range(n)]
