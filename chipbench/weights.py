"""Weights and other inputs drawn from the seed, on the device.

Every leaf is drawn from its own key, folded from the run's seed and the
leaf's path, so the benchmark and the reference draw the same values from
the same seed without either taking anything from the other. The whole
tree comes from one jitted call, in the dtype it is served in.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key for any whole number below 2**64 (seeds may pass
    32 bits)."""
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def path_str(path) -> str:
    """``['layers'][0][1]['w_in']`` -> ``layers/0/1/w_in``."""
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(parts)


def spec_of(tree) -> dict:
    """``{path: (shape, dtype name)}`` of a pytree of arrays or shapes."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {path_str(p): (tuple(x.shape), jnp.dtype(x.dtype).name)
            for p, x in flat}


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _draw_leaf(key, path: str, shape, dtype):
    """Norm scales near one, the embedding at 0.02, matrices at
    1/sqrt(fan-in); stacked leaves hold one draw per layer."""
    k = _leaf_key(key, path)
    if path.endswith("scale"):
        x = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
    elif path == "embed":
        x = 0.02 * jax.random.normal(k, shape, jnp.float32)
    else:
        x = jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[-2])
    return x.astype(dtype)


def draw(spec: dict, seed: int, out_sharding=None) -> dict:
    """``{path: array}`` for every leaf of ``spec`` in one jitted call."""
    paths = sorted(spec)

    def body(key):
        return {p: _draw_leaf(key, p, spec[p][0], jnp.dtype(spec[p][1]))
                for p in paths}

    return jax.jit(body, out_shardings=out_sharding)(seed_key(seed))


def as_tree(flat: dict, like):
    """Put ``flat`` (by path) into the structure of ``like``."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(like)
    return jax.tree_util.tree_unflatten(
        treedef, [flat[path_str(p)] for p, _ in leaves])


def device_rows(spec: dict, seed: int, mesh, axis: str) -> dict:
    """``{path: (n, *shape)}`` for the ``n`` devices of ``axis``: one
    standard-normal draw per device, made on that device (``shard_map``
    over ``axis``), for payloads such as per-device gradients."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    paths = sorted(spec)

    def body(key):
        i = jax.lax.axis_index(axis)
        k = jax.random.fold_in(key, i)
        return {p: jax.random.normal(_leaf_key(k, p), spec[p][0],
                                     jnp.dtype(spec[p][1]))[None]
                for p in paths}

    f = jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(axis))
    out = NamedSharding(mesh, P(axis))
    return jax.jit(f, out_shardings={p: out for p in paths})(seed_key(seed))
