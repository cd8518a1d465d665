"""Weights from the seed, made on the device by jitted makers.

The tree is the one ``jax.eval_shape`` gives of the program's
``init_params``: the same leaves, shapes and dtypes, so the server takes it
as it would take its own.  The values are normal draws at that function's
scales (``scale_for``, or the configuration's family's own first, see
``bench/spec.py``), in the type they are served in, made on the device:

- ``resident``: one jitted call makes the whole tree on the device; on a
  mesh, in the shard plan's shardings, each layer's draws constrained to
  its layer's shardings, so no device holds more than its share of the
  tree and of one layer's float32 draws;
- ``offload``: one jitted call per layer makes that layer on the device and
  it is copied to host memory at once, into one preallocated stacked host
  array per leaf; the rest of the tree stays on the device.

The draws differ from ``init_params``'s own; their scales do not.  They do
not depend on the shardings (JAX's partitionable Threefry), so a seed makes
the same weights on one chip and on a mesh.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _names(path) -> Tuple[str, ...]:
    return tuple(getattr(k, "key", getattr(k, "name", str(k))) for k in path)


def scale_for(names: Tuple[str, ...], shape, sizes: Dict[str, int]):
    """(kind, std) of a leaf of ``init_params``: "normal" with its std, or
    "zeros"/"ones".  ``sizes`` has the config's ``num_hidden_layers``.
    Unknown leaves raise, so a change of the program's tree cannot slip
    through with a wrong scale."""
    leaf = names[-1]
    parent = names[-2] if len(names) > 1 else ""
    depth = math.sqrt(2 * max(sizes["num_hidden_layers"], 1))
    if leaf in ("embed", "pos_embed"):
        return "normal", 0.02
    if leaf == "unembed":
        return "normal", 1.0 / math.sqrt(shape[-2])
    if parent in ("ln1", "ln2", "final_norm") or leaf == "final_norm":
        if leaf == "bias":
            return "zeros", 0.0
        ones = sizes["norm"] == "layernorm"
        return ("ones" if ones else "zeros"), 0.0
    if leaf in ("wq", "wk", "wv", "w1", "w3"):
        return "normal", 1.0 / math.sqrt(shape[-2])
    if leaf in ("wo", "w2"):
        return "normal", 1.0 / math.sqrt(shape[-2]) / depth
    raise ValueError(f"no init scale known for leaf {'/'.join(names)}")


def _scale(names, shape, sizes, family):
    """The family's scale for a leaf where it gives one, else ours."""
    got = family.scale_for(names, shape, sizes) if family is not None \
        else None
    return got if got is not None else scale_for(names, shape, sizes)


def _make_tree(key, shapes, sizes, family=None):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (path, sd) in zip(keys, leaves):
        kind, std = _scale(_names(path), sd.shape, sizes, family)
        if kind == "normal":
            out.append((jax.random.normal(k, sd.shape, jnp.float32)
                        * std).astype(sd.dtype))
        elif kind == "ones":
            out.append(jnp.ones(sd.shape, sd.dtype))
        else:
            out.append(jnp.zeros(sd.shape, sd.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _make_stacked(key, shapes, sizes, family=None, shardings=None):
    """The stacked layer tree, one layer per iteration of a ``lax.map`` so
    that no float32 copy of a whole stacked leaf is ever held.  With
    ``shardings`` (one layer's), each layer is made in them."""
    n = jax.tree.leaves(shapes)[0].shape[0]
    one = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
                       shapes)

    def layer(k):
        made = _make_tree(k, one, sizes, family)
        if shardings is not None:
            made = jax.lax.with_sharding_constraint(made, shardings)
        return made
    return jax.lax.map(layer, jax.random.split(key, n))


def shapes_of(cfg) -> Dict[str, Any]:
    from repro.models import model as M
    return jax.eval_shape(functools.partial(M.init_params, cfg),
                          jax.random.PRNGKey(0))


def plan_shardings(plan, shapes):
    """The ``NamedSharding`` of each leaf of ``shapes`` under the program's
    shard plan (``make_shard_plan(cfg, mesh, shapes)``)."""
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.tree.map(lambda p: NamedSharding(plan.mesh, p),
                        plan.param_specs_for(shapes),
                        is_leaf=lambda x: isinstance(x, PartitionSpec))


def resident_maker(cfg, sizes, *, plan=None, family=None):
    """The jitted call that makes the whole tree from a key, on the device;
    with the program's shard ``plan``, in its shardings on the plan's
    mesh."""
    shapes = shapes_of(cfg)
    rest = {k: v for k, v in shapes.items() if k != "layers"}
    out_shardings = one_layer = None
    if plan is not None:
        out_shardings = plan_shardings(plan, shapes)
        one_layer = jax.tree.map(
            lambda s: jax.sharding.NamedSharding(
                plan.mesh, plan.layer_leaf_spec(s.spec)),
            out_shardings["layers"])

    def make(key):
        k1, k2 = jax.random.split(key)
        out = _make_tree(k1, rest, sizes, family)
        out["layers"] = _make_stacked(k2, shapes["layers"], sizes, family,
                                      one_layer)
        return out
    return jax.jit(make, out_shardings=out_shardings)


def make_resident(cfg, sizes, seed: int, *, plan=None, family=None):
    """The whole tree on the device, made by one jitted call
    (``resident_maker``)."""
    params = resident_maker(cfg, sizes, plan=plan, family=family)(_key(seed))
    jax.block_until_ready(params)
    return params


def make_offload(cfg, sizes, seed: int, *, family=None):
    """Layers in host memory (one stacked host array per leaf, each layer
    copied there as soon as it is made on the device); the rest of the tree
    on the device."""
    shapes = shapes_of(cfg)
    rest = {k: v for k, v in shapes.items() if k != "layers"}
    one = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
                       shapes["layers"])
    k_rest, k_layers = jax.random.split(_key(seed))
    params = jax.jit(lambda k: _make_tree(k, rest, sizes, family))(k_rest)
    host = jax.tree.map(lambda s: np.empty(s.shape, s.dtype),
                        shapes["layers"])
    make_layer = jax.jit(lambda k: _make_tree(k, one, sizes, family))
    layer_keys = jax.random.split(k_layers, jax.tree.leaves(host)[0].shape[0])
    for l in range(layer_keys.shape[0]):
        made = jax.device_get(make_layer(layer_keys[l]))
        for dst, a in zip(jax.tree.leaves(host), jax.tree.leaves(made)):
            dst[l] = a
        del made
    params["layers"] = host
    jax.block_until_ready(params)
    return params


def _key(seed: int):
    """A PRNG key from any whole number, beyond 32 bits too."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)
