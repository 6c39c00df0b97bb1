"""Random weights from the seed, made on the device in one jitted call.

The benchmark makes the weights, not the program: the reference then checks
the program against weights that it did not make. The tree has the
program's parameter layout (``api.init_params`` traced for shapes only, no
compute); each leaf is uniform with the program's own init scale, so the
served model behaves as the program's random models do.

The seed is an argument of the compiled program, so every seed is served
from the one cached executable.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def leaf_scale(path: tuple, shape: tuple) -> float | None:
    """Standard deviation of a leaf by its name; None means ones (norms)
    and 0.0 zeros (biases)."""
    name = str(getattr(path[-1], "key", path[-1]))
    if name.startswith("norm"):
        return None
    if name.startswith("b"):                # qkv biases
        return 0.0
    if name in ("tok", "router"):
        return 0.02
    return 1.0 / math.sqrt(shape[-2])       # 1/sqrt(fan_in)


def key_of(seed: int):
    """Raw threefry key data for any seed up to 64 bits."""
    return jnp.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                       jnp.uint32)


def make_params(cfg, seed: int):
    """The served weights for ``cfg`` from ``seed``, on the default device
    in their stated dtype."""
    return _filler(cfg)(key_of(seed))


@functools.lru_cache(maxsize=None)
def _filler(cfg):
    """One jitted program per configuration, whatever the seed."""
    from repro.models import api
    shapes = jax.eval_shape(lambda: api.init_params(cfg,
                                                    jax.random.PRNGKey(0)))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def fill(key_data):
        key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
        out = []
        for i, (path, leaf) in enumerate(leaves):
            s = leaf_scale(path, leaf.shape)
            if s is None:
                out.append(jnp.ones(leaf.shape, leaf.dtype))
            elif s == 0.0:
                out.append(jnp.zeros(leaf.shape, leaf.dtype))
            else:
                a = s * math.sqrt(3.0)      # uniform(-a, a) has std s
                u = jax.random.uniform(jax.random.fold_in(key, i),
                                       leaf.shape, jnp.float32, -a, a)
                out.append(u.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return fill
