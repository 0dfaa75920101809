"""Pallas TPU kernel: blocked online-softmax (flash) attention.

This is the VMEM-tiled counterpart of the XLA-level ``_flash_sdpa`` scan in
``repro.models.layers`` — the model uses the XLA form (it partitions under
GSPMD for the dry-run), while this kernel is the single-chip hot-loop form:
one (bq x dh) query tile resident in VMEM, streaming (bk x dh) key/value
tiles, carrying the running (max, denom, accumulator) in registers/VMEM
scratch. Grid = (batch*heads, num_q_blocks); the kv loop is a fori_loop with
``pl.ds`` ref slices so the K/V stream never exceeds one tile of VMEM beyond
the block inputs.

Masking supports causal, sliding-window and chunked (local) attention — the
three variants the architecture pool needs (mixtral SWA, llama4 chunked).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["flash_attention"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, bk: int, scale: float,
            causal: bool, window: int | None, chunk: int | None, bq: int,
            tk: int):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # (bq, dh)
    m = jnp.full((bq,), NEG_INF, jnp.float32)
    l = jnp.zeros((bq,), jnp.float32)
    acc = jnp.zeros(q.shape, jnp.float32)
    nk = tk // bk

    def body(ki, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(ki * bk, bk), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(ki * bk, bk), :].astype(jnp.float32)
        s = q @ k.T                                    # (bq, bk)
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        msk = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            msk &= kpos <= qpos
        if window is not None:
            msk &= kpos > qpos - window
        if chunk is not None:
            msk &= (kpos // chunk) == (qpos // chunk)
        s = jnp.where(msk, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + p @ v
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, nk, body, (m, l, acc))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    chunk: int | None = None, bq: int = 128, bk: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (BH, Tq, dh); k/v: (BH, Tk, dh) — GQA heads pre-broadcast.

    Returns (BH, Tq, dh). Tq must be a multiple of bq and Tk of bk (the ops.py
    wrapper pads); dh should be a multiple of 128 on real TPUs.
    """
    BH, Tq, dh = q.shape
    Tk = k.shape[1]
    assert Tq % bq == 0 and Tk % bk == 0, (Tq, bq, Tk, bk)
    grid = (BH, Tq // bq)
    kern = functools.partial(
        _kernel, bk=bk, scale=1.0 / np.sqrt(dh), causal=causal,
        window=window, chunk=chunk, bq=bq, tk=Tk)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((1, bq, dh), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, Tk, dh), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((1, Tk, dh), lambda b, i: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, i: (b, i, 0)),
        interpret=interpret,
    )(q, k, v)
