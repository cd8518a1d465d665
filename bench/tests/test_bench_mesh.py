"""A cell on a (1, 4) mesh, on four CPU devices (each test in a process of
its own, since JAX fixes its device count when it starts): a tiny resident
OPT cell run by the harness is correct and comes out not correct under
each planted fault (a token altered, the cache left unchanged, the
exchange between chips left out), and its weights are made in the shard
plan's shardings, equal to the one-device weights of the same seed, each
device holding about a quarter of them."""
from bench.tests import tiny


def test_mesh_sound_and_broken_runs():
    tiny.in_devices("from bench.tests import tiny\n"
                    "tiny.check_sound_and_broken('tiny-opt-resident.json', "
                    "chips=4)\n")


WEIGHTS = """
import jax, numpy as np
from bench import run, weights
from bench.tests import tiny

cell = tiny.cell("tiny-opt-resident.json", 4)
cfg = run.program_config(cell)
plan = run.shard_plan(cfg, 4)
assert run.shard_plan(cfg, 1) is None
seed = 2**33 + 1
made = weights.make_resident(cfg, cell.config, seed, plan=plan)
alone = weights.make_resident(cfg, cell.config, seed)
for a, b in zip(jax.tree.leaves(made), jax.tree.leaves(alone)):
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert len(a.sharding.device_set) == 4
layers = jax.tree.leaves(made["layers"])
total = sum(a.nbytes for a in layers)
held = {}
for a in layers:
    for s in a.addressable_shards:
        held[s.device] = held.get(s.device, 0) + s.data.nbytes
assert len(held) == 4
for n in held.values():
    assert 0.24 * total <= n <= 0.26 * total, (n, total)
# the maker's own memory on a device: its share of the tree and the
# temporaries of one layer's draws, never the whole tree
mesh = weights.resident_maker(cfg, cell.config, plan=plan).lower(
    weights._key(seed)).compile().memory_analysis()
one = weights.resident_maker(cfg, cell.config).lower(
    weights._key(seed)).compile().memory_analysis()
assert mesh.output_size_in_bytes <= 0.26 * one.output_size_in_bytes
assert mesh.output_size_in_bytes + mesh.temp_size_in_bytes < \\
    one.output_size_in_bytes
print("ok")
"""


def test_mesh_weights_are_the_seeds_in_the_plans_shardings():
    assert tiny.in_devices(WEIGHTS).strip().endswith("ok")
