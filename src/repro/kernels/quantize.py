"""Pallas TPU kernels: lossy payload compression for caches and collectives.

Two families:

* symmetric int8 (de)quantization with per-row scales — (a) KV-cache
  compression in the serving path, (b) optional compressed payloads in the
  collective stack. Scales are per (ROWS x 128) tile row, computed in-kernel
  from the tile's absmax — one HBM pass for quantize, one for dequantize.
* bf16 compress/decompress (:func:`compress_bf16` / :func:`decompress_bf16`)
  — the wire format of the hierarchical allreduce's slow inter-group stage
  (``CollectiveConfig(compress_inter_group=True)``). A plain round-to-nearest
  cast streamed HBM->VMEM in (ROWS x 128) tiles: bf16 keeps f32's exponent
  range, so no scale rows are needed, and the relative error per cast is at
  most 2^-9 (see ``docs/algorithms.md`` for the end-to-end bound).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["quantize_int8", "dequantize_int8", "compress_bf16",
           "decompress_bf16"]

LANES = 128
DEFAULT_ROWS = 256


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    q_ref[...] = q
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, o_ref, *, dtype_name: str):
    q = q_ref[...].astype(jnp.float32)
    o_ref[...] = (q * s_ref[...]).astype(o_ref.dtype)


def _pad_rows(x: jax.Array, rows: int):
    r, c = x.shape
    n_tiles = max(1, -(-r // rows))
    padded = n_tiles * rows
    if padded != r:
        x = jnp.concatenate([x, jnp.zeros((padded - r, c), x.dtype)])
    return x, n_tiles


def quantize_int8(x: jax.Array, *, rows: int = DEFAULT_ROWS,
                  interpret: bool = False):
    """x: (R, 128) float -> (q: (R,128) int8, scale: (R,1) float32)."""
    assert x.ndim == 2 and x.shape[1] == LANES
    r0 = x.shape[0]
    x, n_tiles = _pad_rows(x, rows)
    spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    sspec = pl.BlockSpec((rows, 1), lambda i: (i, 0))
    vma = jax.typeof(x).vma
    q, s = pl.pallas_call(
        _quant_kernel,
        out_shape=(jax.ShapeDtypeStruct(x.shape, jnp.int8, vma=vma),
                   jax.ShapeDtypeStruct((x.shape[0], 1), jnp.float32,
                                        vma=vma)),
        grid=(n_tiles,),
        in_specs=[spec],
        out_specs=(spec, sspec),
        interpret=interpret,
        name="quantize_int8",     # the kernel's name in the device trace
    )(x)
    return q[:r0], s[:r0]


def _cast_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...].astype(o_ref.dtype)


def _cast_1d(x: jax.Array, dtype, rows: int, interpret: bool,
             name: str) -> jax.Array:
    """Tiled elementwise cast of a 1-D vector: pad to (ROWS x 128) tiles,
    stream one tile per grid step. One HBM read + one write, no gather."""
    (m,) = x.shape
    per_tile = rows * LANES
    n_tiles = max(1, -(-m // per_tile))
    padded = n_tiles * per_tile
    if padded != m:
        x = jnp.concatenate([x, jnp.zeros((padded - m,), x.dtype)])
    mat = x.reshape(n_tiles * rows, LANES)
    spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        _cast_kernel,
        out_shape=jax.ShapeDtypeStruct(mat.shape, dtype,
                                       vma=jax.typeof(mat).vma),
        grid=(n_tiles,),
        in_specs=[spec],
        out_specs=spec,
        interpret=interpret,
        name=name,                # the kernel's name in the device trace
    )(mat)
    return out.reshape(-1)[:m]


def compress_bf16(x: jax.Array, *, rows: int = DEFAULT_ROWS,
                  interpret: bool = False) -> jax.Array:
    """f32 -> bf16 wire compression (round-to-nearest-even, 1-D payloads).

    Used by the hierarchical allreduce before the slow inter-group stage;
    numerically identical to ``x.astype(jnp.bfloat16)`` — the kernel only buys
    the tiled single-pass HBM schedule on real TPUs.
    """
    assert x.ndim == 1
    return _cast_1d(x, jnp.bfloat16, rows, interpret, "compress_bf16")


def decompress_bf16(x: jax.Array, dtype=jnp.float32, *,
                    rows: int = DEFAULT_ROWS,
                    interpret: bool = False) -> jax.Array:
    """bf16 -> f32 wire decompression; exact (bf16 embeds into f32)."""
    assert x.ndim == 1
    return _cast_1d(x, dtype, rows, interpret, "decompress_bf16")


def dequantize_int8(q: jax.Array, scale: jax.Array, dtype=jnp.float32, *,
                    rows: int = DEFAULT_ROWS, interpret: bool = False):
    """Inverse of :func:`quantize_int8`."""
    assert q.ndim == 2 and q.shape[1] == LANES
    r0 = q.shape[0]
    q, n_tiles = _pad_rows(q, rows)
    scale, _ = _pad_rows(scale, rows)
    spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    sspec = pl.BlockSpec((rows, 1), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_dequant_kernel, dtype_name=jnp.dtype(dtype).name),
        out_shape=jax.ShapeDtypeStruct(
            q.shape, dtype,
            vma=jax.typeof(q).vma | jax.typeof(scale).vma),
        grid=(n_tiles,),
        in_specs=[spec, sspec],
        out_specs=spec,
        interpret=interpret,
        name="dequantize_int8",   # the kernel's name in the device trace
    )(q, scale)
    return out[:r0]
