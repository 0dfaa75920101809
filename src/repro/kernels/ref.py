"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

_OPS = {"add": jnp.add, "max": jnp.maximum, "min": jnp.minimum,
        "mul": jnp.multiply}


def combine2_ref(a: jax.Array, b: jax.Array, *, op: str = "add") -> jax.Array:
    return _OPS[op](a, b)


def combine3_ref(a: jax.Array, b: jax.Array, c: jax.Array, *,
                 op: str = "add") -> jax.Array:
    f = _OPS[op]
    return f(f(a, b), c)


def compress_bf16_ref(x: jax.Array) -> jax.Array:
    return x.astype(jnp.bfloat16)


def decompress_bf16_ref(x: jax.Array, dtype=jnp.float32) -> jax.Array:
    return x.astype(dtype)


def quantize_int8_ref(x: jax.Array):
    x32 = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x32), axis=1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8_ref(q: jax.Array, scale: jax.Array, dtype=jnp.float32):
    return (q.astype(jnp.float32) * scale).astype(dtype)
