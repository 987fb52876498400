"""Random weights from a seed, in the serving program's parameter layout.

The benchmark, not the program, makes the weights: the layout (shapes,
dtypes, leaf names) comes from the program's abstract parameter tree, the
values from the configuration family's initialisation table and the seed.
The whole tree is drawn on the device by one jitted call, in the dtype each
leaf is served in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def key_for(seed: int) -> jax.Array:
    """A PRNG key from a whole number of any size up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _leaf(kind: tuple, key, shape, dtype):
    f32 = jnp.float32
    name = kind[0]
    if name == "normal":
        v = jax.random.normal(key, shape, f32) * kind[1]
    elif name == "uniform":                 # symmetric, +-bound
        v = jax.random.uniform(key, shape, f32, -kind[1], kind[1])
    elif name == "const":
        v = jnp.full(shape, kind[1], f32)
    elif name == "log_of_uniform":          # log(U(lo, hi)), e.g. A_log
        v = jnp.log(jax.random.uniform(key, shape, f32, kind[1], kind[2]))
    elif name == "inv_softplus_log_uniform":  # dt bias: dt log-uniform
        lo, hi, floor = kind[1:]
        u = jax.random.uniform(key, shape, f32)
        dt = jnp.maximum(jnp.exp(u * (jnp.log(hi) - jnp.log(lo))
                                 + jnp.log(lo)), floor)
        v = dt + jnp.log(-jnp.expm1(-dt))
    else:
        raise ValueError(f"unknown initialisation {kind!r}")
    return v.astype(dtype)


def draw(abstract, table: dict, seed: int):
    """Fill ``abstract`` (a tree of ShapeDtypeStruct) leaf by leaf name
    (the last dict key on its path) from ``table``, on the device."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    specs = []
    for path, leaf in flat:
        name = next(p.key for p in reversed(path)
                    if isinstance(p, jax.tree_util.DictKey))
        if name not in table:
            raise KeyError(f"no initialisation for parameter {name!r}")
        specs.append((table[name], leaf.shape, leaf.dtype))

    def make(key):
        keys = jax.random.split(key, len(specs))
        return jax.tree_util.tree_unflatten(
            treedef, [_leaf(k, kk, s, d) for kk, (k, s, d) in zip(keys, specs)])

    return jax.jit(make)(key_for(seed))
