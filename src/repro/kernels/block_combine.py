"""Pallas TPU kernel: blockwise elementwise combine for the pipelined allreduce.

The compute hot-spot of the paper's algorithm is the blockwise reduction
``Y[j] <- t (.) Y[j]`` (``MPI_Reduce_local`` in the paper's MPI sketch). Each
non-leaf applies it twice per round, the roots three times. On TPU this is a
pure VPU/memory-bound op: the kernel streams HBM->VMEM tiles and combines
in-register.

Two entry points:

* ``combine2``  — ``op(a, b)``          (Algorithm 1 lines 4/6/9)
* ``combine3``  — ``op(op(a, b), c)``   (fused A+B rounds: child0's and
  child1's partials combined with the local block in ONE pass — saves one full
  HBM round-trip of the block vs. two ``combine2`` calls; a beyond-paper,
  TPU-memory-hierarchy optimization)

Payloads are 1-D pipeline blocks (length ``m/b``). We pad to a multiple of the
(ROWS x 128) VMEM tile and launch a 1-D grid over row-tiles. Lane width 128 is
the VPU register width; ROWS is chosen so the working set (2-3 operands + out)
stays well inside the ~16 MiB/core VMEM budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["combine2", "combine3", "LANES", "DEFAULT_ROWS"]

LANES = 128
DEFAULT_ROWS = 512  # 512x128 f32 = 256 KiB per operand per tile


def _op_fn(op: str):
    return {
        "add": jnp.add,
        "max": jnp.maximum,
        "min": jnp.minimum,
        "mul": jnp.multiply,
    }[op]


def _combine2_kernel(a_ref, b_ref, o_ref, *, op: str):
    o_ref[...] = _op_fn(op)(a_ref[...], b_ref[...])


def _combine3_kernel(a_ref, b_ref, c_ref, o_ref, *, op: str):
    f = _op_fn(op)
    o_ref[...] = f(f(a_ref[...], b_ref[...]), c_ref[...])


def _pad_2d(x: jax.Array, rows: int):
    (m,) = x.shape
    per_tile = rows * LANES
    n_tiles = max(1, -(-m // per_tile))
    padded = n_tiles * per_tile
    if padded != m:
        x = jnp.concatenate([x, jnp.zeros((padded - m,), x.dtype)])
    return x.reshape(n_tiles * rows, LANES), n_tiles


def _run(kernel, args, rows: int, interpret: bool, op: str, name: str):
    mats = []
    n_tiles = None
    for a in args:
        mat, n_tiles = _pad_2d(a, rows)
        mats.append(mat)
    spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    # inside a shard_map the operands and the output must all vary over
    # every manual axis any operand varies over
    vma = frozenset().union(*(jax.typeof(m).vma for m in mats))
    mats = [m if jax.typeof(m).vma == vma else
            jax.lax.pcast(m, tuple(vma - jax.typeof(m).vma), to="varying")
            for m in mats]
    out = pl.pallas_call(
        functools.partial(kernel, op=op),
        out_shape=jax.ShapeDtypeStruct(mats[0].shape, mats[0].dtype,
                                       vma=vma),
        grid=(n_tiles,),
        in_specs=[spec] * len(mats),
        out_specs=spec,
        interpret=interpret,
        name=name,            # the kernel's name in the device trace
    )(*mats)
    return out.reshape(-1)[: args[0].shape[0]]


def combine2(a: jax.Array, b: jax.Array, *, op: str = "add",
             rows: int = DEFAULT_ROWS, interpret: bool = False) -> jax.Array:
    """``op(a, b)`` elementwise over 1-D blocks via a VMEM-tiled Pallas kernel."""
    assert a.shape == b.shape and a.ndim == 1
    return _run(_combine2_kernel, (a, b), rows, interpret, op, "combine2")


def combine3(a: jax.Array, b: jax.Array, c: jax.Array, *, op: str = "add",
             rows: int = DEFAULT_ROWS, interpret: bool = False) -> jax.Array:
    """Fused ``op(op(a, b), c)`` — one HBM pass instead of two."""
    assert a.shape == b.shape == c.shape and a.ndim == 1
    return _run(_combine3_kernel, (a, b, c), rows, interpret, op,
                "combine3")
