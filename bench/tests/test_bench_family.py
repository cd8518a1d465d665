"""A configuration's own architecture, found by the name its file gives
under ``"family"``: without one, a cell takes the dense decoder's reference,
weight scales and counts; with one, the family module's, and the shared
weight scales where it defers.  A leaf nobody knows still raises."""
import json
import shutil

import jax
import numpy as np
import pytest

from bench import counts, reference, spec, weights
from bench.run import readers
from bench.tests import tiny

FAMILY = "tiny_family"


def _bench(tmp_path, config):
    for sub in ("cells", "configs", "traffic", "families"):
        (tmp_path / sub).mkdir()
    shutil.copy(tiny.DATA / f"{FAMILY}.py", tmp_path / "families")
    shutil.copy(tiny.DATA / "tiny-mix.json", tmp_path / "traffic" / "mix.json")
    shutil.copy(tiny.DATA / "tiny-sizes.json", tmp_path / "cells" / "c.json")
    (tmp_path / "configs" / "cfg.json").write_text(json.dumps(config))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "c", "config": "cfg", "traffic": "mix",
                       "chips": 1}],
        "end_to_end": [], "per_layer": []}))
    return spec.load_cell("c", tmp_path / "BENCHMARK.json", tmp_path)


def _dense():
    return json.loads((tiny.DATA / "tiny-yi-resident.json").read_text())


def test_no_family_is_the_dense_decoder(tmp_path):
    cell = _bench(tmp_path, _dense())
    assert cell.family is None
    assert cell.reference is reference
    assert cell.counts is counts
    assert weights._scale(("layers", "attn", "wq"), (8, 4), cell.config,
                          None) == weights.scale_for(
        ("layers", "attn", "wq"), (8, 4), cell.config)


def test_a_named_family_is_found_and_used(tmp_path):
    cell = _bench(tmp_path, dict(_dense(), family=FAMILY))
    fam = cell.family
    assert fam.__name__ == f"bench.families.{FAMILY}"
    assert cell.reference is fam and cell.counts is fam
    # weights: the family's scale for wq, the shared ones elsewhere
    from bench.run import program_config
    made = weights.make_resident(program_config(cell), cell.config, 3,
                                 family=fam)
    plain = weights.make_resident(program_config(cell), cell.config, 3)
    assert np.all(np.asarray(made["layers"]["attn"]["wq"]) == 1)
    assert not np.all(np.asarray(plain["layers"]["attn"]["wq"]) == 1)
    np.testing.assert_array_equal(np.asarray(made["layers"]["ffn"]["w1"]),
                                  np.asarray(plain["layers"]["ffn"]["w1"]))
    # counts: a reader reaches the family's through the window
    w = tiny_window(cell)
    assert readers(["step_mfu"])["step_mfu"](w) == pytest.approx(
        100 * (1e12 * 3) / (1.0 * 197e12))
    # the reference: the family's logits
    rest = {k: v for k, v in made.items() if k != "layers"}
    layer = lambda l: jax.tree.map(lambda a: a[l], made["layers"])
    seq = np.arange(1, 20, dtype=np.int32)
    out = cell.reference.logits(cell.config, rest, layer, [seq],
                                [np.arange(10, 19)])
    assert fam.CALLS == ["f32"] and out[0].shape == (9, 1024)


def tiny_window(cell):
    from bench.peaks import PEAKS
    from bench.tap import Chunk, Tap
    from bench.window import Window
    tap = Tap()
    tap.chunks = [Chunk(start=0.5, steps=1, slots=[(0, 1, 10, 2),
                                                   (1, 2, 5, 1)],
                        end=1.0, tokens=3)]
    return Window(cell=cell, tap=tap, t_open=0.0, t_close=1.0, setup_s=1.0,
                  peaks=PEAKS["TPU v5 lite"], prompt_len={})


def test_an_unknown_leaf_still_raises(tmp_path):
    cell = _bench(tmp_path, dict(_dense(), family=FAMILY))
    with pytest.raises(ValueError, match="no init scale known"):
        weights._scale(("layers", "moe", "router"), (8, 4), cell.config,
                       cell.family)
    with pytest.raises(FileNotFoundError):
        spec.load_family("no_such_family", tmp_path / "families")
