"""Shared neural layers for the model zoo (pure-functional JAX).

Everything is config-driven and initializer-explicit; parameters are plain
nested dicts so they can be flattened into the collective stack's gradient
buckets without any framework adapter. Sharding intent is expressed with
``maybe_shard`` (a ``with_sharding_constraint`` that no-ops outside a mesh),
so the same model code runs on 1 CPU device and on the 512-chip dry-run mesh.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

Params = dict

# --------------------------------------------------------------------------
# sharding helper
# --------------------------------------------------------------------------

_MESH_STACK: list = []


class mesh_ctx:
    """Make a mesh visible to ``maybe_shard`` during tracing.

    Inside ``shard_map`` JAX exposes an abstract mesh automatically; under a
    plain ``jit`` (the fsdp-auto regime) it does not, and every constraint
    would silently no-op. Step builders wrap their traced bodies in this."""

    def __init__(self, mesh):
        self.mesh = mesh

    def __enter__(self):
        _MESH_STACK.append(self.mesh)

    def __exit__(self, *exc):
        _MESH_STACK.pop()


_TP_STACK: list = []


@dataclasses.dataclass(frozen=True)
class TPInfo:
    """Tensor-parallel execution context: the mesh axis the model's weight
    shards live on, its size, and the CollectiveConfig the per-token partial
    sum reduction runs with."""
    axis: str
    size: int
    collective: Any


class tp_ctx:
    """Make a tensor-parallel axis visible to ``tp_all_reduce`` while a
    sharded model body is being traced.

    The TP step builders (:mod:`repro.launch.step_fns`) trace the model
    inside a shard_map manual over ``axis`` with attention heads and FFN
    columns split across it; every sharded sublayer's output projection then
    produces a PARTIAL sum that ``tp_all_reduce`` completes. Outside this
    context the model is unsharded and the reduction no-ops, so the same
    model code serves tp=1 and tp>1."""

    def __init__(self, axis: str, size: int, collective: Any):
        self.info = TPInfo(axis, int(size), collective)

    def __enter__(self):
        _TP_STACK.append(self.info)
        return self.info

    def __exit__(self, *exc):
        _TP_STACK.pop()


def tp_info() -> TPInfo | None:
    """The innermost active tensor-parallel context, or None."""
    return _TP_STACK[-1] if _TP_STACK else None


def tp_all_reduce(x: jax.Array) -> jax.Array:
    """Complete a tensor-parallel partial sum across the TP axis.

    No-op outside a :class:`tp_ctx`. Inside one, this is the per-token
    allreduce at the end of every sharded sublayer — a tiny (B*T*d_model)
    payload in the paper's latency-bound regime, routed through
    :func:`repro.core.collectives.all_reduce` so ``method="auto"`` picks the
    dual-root dptree (or a measured autotune winner) per message size. The
    payload is flattened because 1-D vectors pipeline directly regardless
    of batch divisibility."""
    info = tp_info()
    if info is None or info.size <= 1:
        return x
    from repro.core.collectives import all_reduce  # local: avoids cycle
    return all_reduce(x.reshape(-1), info.axis, info.size,
                      info.collective).reshape(x.shape)


def maybe_shard(x: jax.Array, spec: P | None) -> jax.Array:
    """Apply a sharding constraint if we are tracing under a mesh.

    Entries naming axes that are absent or not GSPMD-Auto (e.g. the manual
    'data' axis inside a partial-manual shard_map) are dropped per-entry, so
    the same model code states its FULL layout intent — batch over 'data',
    features over 'model' — and each deployment mode keeps the applicable
    part. NOTE: a kept entry of None means "explicitly replicated", which is
    why batch dims must be named here rather than left None."""
    if spec is None:
        return x
    env = jax.sharding.get_abstract_mesh()
    concrete = None
    if env.empty or not env.shape_tuple:
        if not _MESH_STACK:
            return x
        concrete = _MESH_STACK[-1]
        env = concrete.abstract_mesh
    auto = {a for a, t in zip(env.axis_names, env.axis_types)
            if t == jax.sharding.AxisType.Auto}

    def fix(entry):
        if entry is None:
            return None
        names = entry if isinstance(entry, tuple) else (entry,)
        kept = tuple(n for n in names if n in auto)
        if not kept:
            return None
        return kept if len(kept) > 1 else kept[0]

    fixed = P(*(fix(e) for e in spec))
    if concrete is not None:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(concrete, fixed))
    return jax.lax.with_sharding_constraint(x, fixed)


# --------------------------------------------------------------------------
# initialization
# --------------------------------------------------------------------------

def dense_init(key, shape, scale: float | None = None, dtype=jnp.float32):
    fan_in = shape[0] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# --------------------------------------------------------------------------
# norms / activations
# --------------------------------------------------------------------------

def rmsnorm_init(dim: int) -> Params:
    return {"scale": jnp.ones((dim,), jnp.float32)}

def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * p["scale"]).astype(dt)

def layernorm_init(dim: int) -> Params:
    return {"scale": jnp.ones((dim,), jnp.float32),
            "bias": jnp.zeros((dim,), jnp.float32)}

def layernorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return (((x32 - mu) * jax.lax.rsqrt(var + eps)) * p["scale"]
            + p["bias"]).astype(dt)

def act_fn(name: str) -> Callable:
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "relu2":          # squared ReLU (nemotron-4)
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(name)


# --------------------------------------------------------------------------
# rotary embeddings (standard + M-RoPE)
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))

def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0,
               mrope_sections: tuple | None = None) -> jax.Array:
    """x: (B, T, H, Dh); positions: (B, T) or (B, T, 3) for M-RoPE.

    M-RoPE (qwen2-vl): the head-dim frequency bands are split into
    ``mrope_sections`` (temporal/height/width); each band uses its own
    position component. Text tokens carry identical components, recovering
    standard RoPE exactly.
    """
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                      # (dh/2,)
    if mrope_sections is None:
        if positions.ndim == 3:
            positions = positions[..., 0]
        ang = positions[..., None].astype(jnp.float32) * freqs  # (B,T,dh/2)
    else:
        if positions.ndim == 2:  # text-only stream: all components equal
            positions = jnp.broadcast_to(positions[..., None],
                                         positions.shape + (3,))
        assert positions.ndim == 3 and positions.shape[-1] == 3
        secs = mrope_sections
        assert sum(secs) == dh // 2
        comp = jnp.concatenate(
            [jnp.full((s,), i, jnp.int32) for i, s in enumerate(secs)])
        pos = jnp.take_along_axis(
            positions.astype(jnp.float32),
            jnp.broadcast_to(comp[None, None, :], positions.shape[:2] + (dh // 2,)),
            axis=-1)                                    # (B,T,dh/2)
        ang = pos * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    # rotate-half convention: contiguous slices only (strided lane slices
    # trip XLA's SPMD gather partitioner at high device counts)
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    dt = x.dtype
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    return jnp.concatenate([o1, o2], axis=-1).astype(dt)


# --------------------------------------------------------------------------
# attention (GQA, sliding-window, chunked, KV cache)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    sliding_window: int | None = None     # SWA width (mixtral)
    chunk_size: int | None = None         # chunked attention (llama4-scout)
    causal: bool = True                   # False for encoder self-attn
    mrope_sections: tuple | None = None   # (t, h, w) bands for M-RoPE
    use_rope: bool = True


def attn_init(key, cfg: AttnConfig, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 4)
    D, H, KV, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        "wq": dense_init(ks[0], (D, H * dh), dtype=dtype),
        "wk": dense_init(ks[1], (D, KV * dh), dtype=dtype),
        "wv": dense_init(ks[2], (D, KV * dh), dtype=dtype),
        "wo": dense_init(ks[3], (H * dh, D), scale=1.0 / np.sqrt(H * dh),
                         dtype=dtype),
    }

ATTN_SPECS = {"wq": P(None, "model"), "wk": P(None, "model"),
              "wv": P(None, "model"), "wo": P("model", None)}


def _attn_mask(Tq: int, Tk: int, causal: bool, window: int | None,
               chunk: int | None, q_off: int = 0) -> jax.Array:
    qi = jnp.arange(Tq)[:, None] + q_off
    ki = jnp.arange(Tk)[None, :]
    m = jnp.ones((Tq, Tk), bool)
    if causal:
        m &= ki <= qi
    if window is not None:
        m &= ki > qi - window
    if chunk is not None:
        m &= (ki // chunk) == (qi // chunk)
    return m


def _qkv(p, cfg: AttnConfig, x, positions):
    B, T, _ = x.shape
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"].astype(x.dtype)).reshape(B, T, H, dh)
    k = (x @ p["wk"].astype(x.dtype)).reshape(B, T, KV, dh)
    v = (x @ p["wv"].astype(x.dtype)).reshape(B, T, KV, dh)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    return q, k, v


def _sdpa(q, k, v, mask, n_heads, n_kv):
    """q: (B,Tq,H,dh); k/v: (B,Tk,KV,dh); mask: (Tq,Tk) or None.

    Direct form — materializes (Tq,Tk) logits. Used for short sequences and
    as the oracle for the flash path."""
    B, Tq, H, dh = q.shape
    rep = n_heads // n_kv
    qg = q.reshape(B, Tq, n_kv, rep, dh)
    logits = jnp.einsum("btgrd,bsgd->bgrts", qg, k) / np.sqrt(dh)
    logits = logits.astype(jnp.float32)
    if mask is not None:
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrts,bsgd->btgrd", w, v)
    return out.reshape(B, Tq, H * dh)


FLASH_THRESHOLD = 1024   # direct sdpa below, two-level-scan flash above
FLASH_BLOCK_Q = 512
FLASH_BLOCK_K = 512


def _flash_sdpa(q, k, v, n_heads, n_kv, *, causal, window, chunk,
                bq=FLASH_BLOCK_Q, bk=FLASH_BLOCK_K):
    """Online-softmax attention: scan over query blocks, inner scan over key
    blocks with running (max, denom, accumulator). Never materializes more
    than a (bq, bk) logit tile per head group — the TPU adaptation of flash
    attention at the XLA level (the Pallas kernel in repro.kernels mirrors
    this blocking in VMEM)."""
    B, Tq, H, dh = q.shape
    Tk = k.shape[1]
    rep = H // n_kv
    scale = 1.0 / np.sqrt(dh)
    bq = min(bq, Tq)
    bk = min(bk, Tk)
    pad_q = (-Tq) % bq
    pad_k = (-Tk) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq, nk = (Tq + pad_q) // bq, (Tk + pad_k) // bk
    qb = jnp.moveaxis(q.reshape(B, nq, bq, n_kv, rep, dh), 1, 0)
    kb = jnp.moveaxis(k.reshape(B, nk, bk, n_kv, dh), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nk, bk, n_kv, dh), 1, 0)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def q_step(_, qi_and_blk):
        qi, qblk = qi_and_blk

        def kv_step(carry, ki_and_blks):
            m, l, acc = carry
            ki, kblk, vblk = ki_and_blks
            s = jnp.einsum("bqgrd,bkgd->bqgrk", qblk, kblk) * scale
            s = s.astype(jnp.float32)
            qpos = qi * bq + jnp.arange(bq)
            kpos = ki * bk + jnp.arange(bk)
            msk = (kpos[None, :] < Tk)
            if causal:
                msk &= kpos[None, :] <= qpos[:, None]
            if window is not None:
                msk &= kpos[None, :] > qpos[:, None] - window
            if chunk is not None:
                msk &= (kpos[None, :] // chunk) == (qpos[:, None] // chunk)
            s = jnp.where(msk[None, :, None, None, :], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bqgrk,bkgd->bqgrd", p.astype(qblk.dtype), vblk
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), ()

        m0 = jnp.full((B, bq, n_kv, rep), -1e30, jnp.float32)
        l0 = jnp.zeros((B, bq, n_kv, rep), jnp.float32)
        a0 = jnp.zeros((B, bq, n_kv, rep, dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nk, dtype=jnp.int32), kb, vb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return (), out.astype(qblk.dtype)

    _, outs = jax.lax.scan(q_step, (),
                           (jnp.arange(nq, dtype=jnp.int32), qb))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * bq, H, dh)[:, :Tq]
    return out.reshape(B, Tq, H * dh)


def attention(p: Params, cfg: AttnConfig, x: jax.Array, positions: jax.Array,
              kv_mask: jax.Array | None = None) -> jax.Array:
    """Full-sequence attention (training / prefill)."""
    q, k, v = _qkv(p, cfg, x, positions)
    T = x.shape[1]
    if T > FLASH_THRESHOLD:
        out = _flash_sdpa(q, k, v, cfg.n_heads, cfg.n_kv_heads,
                          causal=cfg.causal, window=cfg.sliding_window,
                          chunk=cfg.chunk_size)
    else:
        mask = _attn_mask(T, T, cfg.causal, cfg.sliding_window,
                          cfg.chunk_size)
        out = _sdpa(q, k, v, mask, cfg.n_heads, cfg.n_kv_heads)
    out = maybe_shard(out, P(("pod", "data"), None, "model"))
    return out @ p["wo"].astype(x.dtype)


def cross_attention(p: Params, cfg: AttnConfig, x: jax.Array,
                    memory: jax.Array) -> jax.Array:
    """Decoder->encoder cross attention (no RoPE, no causal mask)."""
    B, T, _ = x.shape
    S = memory.shape[1]
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"].astype(x.dtype)).reshape(B, T, H, dh)
    k = (memory @ p["wk"].astype(x.dtype)).reshape(B, S, KV, dh)
    v = (memory @ p["wv"].astype(x.dtype)).reshape(B, S, KV, dh)
    if max(T, S) > FLASH_THRESHOLD:
        out = _flash_sdpa(q, k, v, H, KV, causal=False, window=None,
                          chunk=None)
    else:
        out = _sdpa(q, k, v, None, H, KV)
    return out @ p["wo"].astype(x.dtype)


def quantize_kv_rows(x: jax.Array):
    """Symmetric int8 per-(token, head) row quantization of K/V entries.

    Mirrors the Pallas ``repro.kernels.quantize`` kernel (which fuses this on
    TPU); the jnp form here keeps the model code backend-agnostic."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def _flat_kv(x: jax.Array) -> jax.Array:
    """(..., KV, d) -> (..., KV * d): a token's K or V, or its int8 scales,
    as the ring stores them."""
    return x.reshape(x.shape[:-2] + (-1,))


def _cache_write(cache_arr, scale_arr, val, slot):
    """Write one token's K or V into a (possibly int8-quantized) ring cache."""
    if cache_arr.dtype == jnp.int8:
        q, s = quantize_kv_rows(val)
        cache_arr = jax.lax.dynamic_update_slice(cache_arr, _flat_kv(q),
                                                 (0, slot, 0))
        scale_arr = jax.lax.dynamic_update_slice(scale_arr, _flat_kv(s),
                                                 (0, slot, 0))
    else:
        cache_arr = jax.lax.dynamic_update_slice(
            cache_arr, _flat_kv(val).astype(cache_arr.dtype), (0, slot, 0))
    return cache_arr, scale_arr


def _cache_read(cache_arr, scale_arr, dtype):
    if cache_arr.dtype == jnp.int8:
        dh = cache_arr.shape[-1] // scale_arr.shape[-1]
        return (cache_arr.astype(jnp.float32)
                * jnp.repeat(scale_arr, dh, axis=-1)).astype(dtype)
    return cache_arr.astype(dtype)


RING_QUERY_BLOCK = 512


def _ring_attend(q: jax.Array, ring_k: jax.Array, ring_v: jax.Array,
                 valid: jax.Array, n_kv: int) -> jax.Array:
    """Attention of queries ``q`` (B, T, H, dh) over rings stored as
    ``(B, S, KV * dh)`` (a token's heads side by side); ``valid``
    broadcasts to (B, T, S). Returns (B, T, H * dh).

    One query per row (decode) contracts the rings as they are stored: the
    query enters block-diagonal over the KV heads (zero outside its own
    head's ``dh`` columns), and each head's output is the diagonal block of
    the product with V. Both products then read each ring once in the
    layout it is stored in. Splitting the ring's last axis into heads
    instead makes the compiler relay the ring out, a copy of the whole
    cache every step. Several queries per row (prefill, verify) take that
    per-head view: there the extra work of the block-diagonal form would
    grow with the queries. More than ``RING_QUERY_BLOCK * 2`` queries (a
    long prompt's prefill) go through in blocks of ``RING_QUERY_BLOCK``,
    so that the scores of one block at a time are held: a 4096-token
    prompt's whole (H, T, S) float32 scores would take 2 GB."""
    B, T, H, dh = q.shape
    S = ring_k.shape[1]
    if T > 2 * RING_QUERY_BLOCK and T % RING_QUERY_BLOCK == 0:
        n = T // RING_QUERY_BLOCK
        blocks = (q.reshape(B, n, RING_QUERY_BLOCK, H, dh),
                  jnp.broadcast_to(valid, (B, T, S)).reshape(
                      B, n, RING_QUERY_BLOCK, S))
        out = jax.lax.map(
            lambda qv: _ring_attend(qv[0], ring_k, ring_v, qv[1], n_kv),
            jax.tree.map(lambda a: jnp.swapaxes(a, 0, 1), blocks))
        return jnp.swapaxes(out, 0, 1).reshape(B, T, H * dh)
    rep = H // n_kv
    qg = q.reshape(B, T, n_kv, rep, dh)
    if T == 1:
        # own[j, g]: column j of a token's K/V belongs to KV head g
        own = (jnp.arange(n_kv * dh)[:, None] // dh
               == jnp.arange(n_kv)[None]).astype(q.dtype)
        # (B, KV * dh, KV, rep): the query, block-diagonal over the heads
        qbd = (jnp.swapaxes(qg[:, 0], 2, 3).reshape(B, n_kv * dh, 1, rep)
               * own[None, :, :, None])
        logits = jnp.einsum("bsj,bjgr->bgrs", ring_k, qbd)[:, :, :, None]
    else:
        logits = jnp.einsum("btgrd,bsgd->bgrts", qg,
                            ring_k.reshape(B, S, n_kv, dh))
    logits = (logits / np.sqrt(dh)).astype(jnp.float32)
    logits = jnp.where(jnp.broadcast_to(valid, (B, T, S))[:, None, None],
                       logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if T == 1:
        full = jnp.einsum("bgrs,bsj->bgrj", w[:, :, :, 0], ring_v)
        out = jnp.einsum("bgrhd,gh->bgrd",
                         full.reshape(B, n_kv, rep, n_kv, dh),
                         jnp.eye(n_kv, dtype=full.dtype))[:, None]
    else:
        out = jnp.einsum("bgrts,bsgd->btgrd", w,
                         ring_v.reshape(B, S, n_kv, dh))
    return out.reshape(B, T, H * dh)


def row_slice(leaf: jax.Array, slot) -> jax.Array:
    """One batch row of a stacked ``(n_periods, batch, ...)`` cache leaf,
    kept as a batch-of-1 slice (works for every cache-leaf rank, including
    the per-slot ``pos`` counters at ``(n_periods, batch)``). The slicing
    primitive behind chunked-prefill resume and the cross-request prefix
    cache's row snapshots."""
    return jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=1)


def row_splice(full: jax.Array, row: jax.Array, slot) -> jax.Array:
    """Write a batch-of-1 ``row`` back into a stacked cache leaf at
    ``slot`` — the inverse of :func:`row_slice`. Every other batch row
    passes through bit-unchanged, which is what lets prefix adoption and
    chunked-prefill splices interleave with in-flight decode in the other
    slots. Casts to the cache dtype (identity for same-dtype rows,
    including int8-quantized K/V and their fp32 scales)."""
    return jax.lax.dynamic_update_slice_in_dim(
        full, row.astype(full.dtype), slot, axis=1)


def attention_decode(p: Params, cfg: AttnConfig, x: jax.Array,
                     cache: Params, cache_pos: jax.Array):
    """One-token decode against a ring KV cache.

    x: (B, 1, D); cache = {"k","v"[,"ks","vs"]} with k/v (B, S, KV * dh)
    (bf16, or int8 with (B, S, KV) scales); cache_pos: () int32 — tokens
    already cached.
    Returns (out, new_cache_dict).
    """
    B, _, _ = x.shape
    S = cache["k"].shape[1]
    positions = jnp.full((B, 1), cache_pos, jnp.int32)
    q, k, v = _qkv(p, cfg, x, positions)
    slot = jnp.mod(cache_pos, S)
    ck, ks = _cache_write(cache["k"], cache.get("ks"), k, slot)
    cv, vs = _cache_write(cache["v"], cache.get("vs"), v, slot)
    new_cache = {"k": ck, "v": cv}
    if ks is not None:
        new_cache["ks"], new_cache["vs"] = ks, vs
    # ring cache: slot s currently holds absolute position
    # pos - ((pos - s) mod S) (negative -> not yet written)
    ki = cache_pos - jnp.mod(cache_pos - jnp.arange(S), S)
    valid = ki >= 0
    if cfg.sliding_window is not None:
        valid &= ki > cache_pos - cfg.sliding_window
    if cfg.chunk_size is not None:
        valid &= (ki // cfg.chunk_size) == (cache_pos // cfg.chunk_size)
    out = _ring_attend(q, _cache_read(ck, ks, q.dtype),
                       _cache_read(cv, vs, q.dtype), valid, cfg.n_kv_heads)
    return out @ p["wo"].astype(x.dtype), new_cache


def attention_decode_slots(p: Params, cfg: AttnConfig, x: jax.Array,
                           cache: Params, cache_pos: jax.Array,
                           lengths: jax.Array | None = None,
                           active: jax.Array | None = None,
                           layer: jax.Array | None = None):
    """Decode/prefill against a ring KV cache with PER-ROW positions.

    The continuous-batching serving engine packs independent requests into
    the batch rows of one cache ("slots"); each row advances at its own pace
    and resets to position 0 when its slot is re-admitted, so one jitted step
    serves any mix of in-flight requests.

    x: (B, T, D) — T == 1 for a decode tick, T == the prompt bucket length
    for slot prefill; cache as in :func:`attention_decode`; cache_pos: (B,)
    int32 — tokens already cached per row.
    Token t of row b is written at ring slot ``(cache_pos[b] + t) % S`` and
    attends causally to absolute positions ``<= cache_pos[b] + t``. Requires
    ``T <= S`` per CALL (otherwise one call would write a ring slot twice) —
    not per prompt: chunked prefill feeds a long prompt through successive
    calls that resume at the carried ``cache_pos``, writing the ring
    contiguously across calls, so windowed/ring reads see exactly the same
    (slot, position) layout a one-shot prefill would have produced.

    ``lengths`` (B,) int32 — prefill only: tokens ``t >= lengths[b]`` are
    bucket padding and their ring WRITES are suppressed (the old cache
    value is written back). A fresh (pos=0) prefill could leave pads in
    never-valid slots, but a RESUMED chunk's bucket can wrap the ring past
    the row's earliest live position — an unsuppressed pad write there
    would clobber real prompt K/V that position arithmetic still reads as
    valid. ``active`` (B,) bool suppresses every write of the rows where it
    is False the same way.

    ``layer`` (one-token decode): ``cache``'s leaves are the whole stacked
    ``(n_periods, B, S, ...)`` rings and this call is layer ``layer`` of
    them. Each row's new token is written in place at ``[layer, b,
    slot_b]`` and the ring is read at ``layer``, so no layer's ring is
    copied out or stacked again; the returned leaves are the whole stacked
    rings.
    Returns (out (B, T, d_model), new_cache_dict).
    """
    B, T, _ = x.shape
    S = cache["k"].shape[1 if layer is None else 2]
    positions = cache_pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    q, k, v = _qkv(p, cfg, x, positions)
    slots = jnp.mod(positions, S)                         # (B, T)
    keep = (None if lengths is None else
            (jnp.arange(T, dtype=jnp.int32)[None] < lengths[:, None]))
    if active is not None:
        keep = active[:, None] if keep is None else keep & active[:, None]

    brow = jnp.arange(B)[:, None]
    at = (brow, slots) if layer is None else (layer, brow, slots)

    def put(arr, val):
        if keep is not None:
            val = jnp.where(keep[..., None], val, arr[at])
        return arr.at[at].set(val)

    def write(arr, scale, val):
        if arr.dtype == jnp.int8:
            qv, sv = quantize_kv_rows(val)
            return put(arr, _flat_kv(qv)), put(scale, _flat_kv(sv))
        return put(arr, _flat_kv(val).astype(arr.dtype)), scale

    ck, ks = write(cache["k"], cache.get("ks"), k)
    cv, vs = write(cache["v"], cache.get("vs"), v)
    new_cache = {"k": ck, "v": cv}
    if ks is not None:
        new_cache["ks"], new_cache["vs"] = ks, vs
    ring = (new_cache if layer is None else
            jax.tree.map(lambda a: a[layer], new_cache))
    cache_k = _cache_read(ring["k"], ring.get("ks"), q.dtype)
    cache_v = _cache_read(ring["v"], ring.get("vs"), q.dtype)
    # ring cache: after this call's writes the newest absolute position in
    # row b is cache_pos[b] + T - 1 — or + lengths[b] - 1 when pad writes
    # are suppressed; slot s holds last - ((last - s) mod S)
    # (negative -> never written for this request)
    newest = T if lengths is None else lengths[:, None]
    last = cache_pos[:, None] + newest - 1                # (B, 1)
    ki = last - jnp.mod(last - jnp.arange(S)[None], S)    # (B, S)
    qpos = positions[..., None]                           # (B, T, 1)
    valid = (ki[:, None, :] >= 0) & (ki[:, None, :] <= qpos)
    if cfg.sliding_window is not None:
        valid &= ki[:, None, :] > qpos - cfg.sliding_window
    if cfg.chunk_size is not None:
        valid &= (ki[:, None, :] // cfg.chunk_size) == (qpos // cfg.chunk_size)
    out = _ring_attend(q, cache_k, cache_v, valid, cfg.n_kv_heads)
    return out @ p["wo"].astype(x.dtype), new_cache


def ring_restore_mask(cache_pos: jax.Array, S: int, n_call: int,
                      accept: jax.Array) -> jax.Array:
    """Which ring slots a partially-rejected verify call must roll back.

    A speculative verify call writes ``n_call`` tokens of row ``b`` at ring
    slots ``(cache_pos[b] + t) % S`` (``attention_decode_slots`` semantics,
    ``n_call <= S``). Once acceptance is known, only tokens
    ``t < accept[b]`` may stay: a REJECTED token's write must be restored to
    the pre-call value, because on a wrapped ring (sliding-window caches
    with more than ``S`` tokens decoded) it can land on a slot holding live
    earlier K/V that position arithmetic still reads as valid after the
    position is rewound — the same hazard the ``lengths=`` pad-write
    suppression closes for resumed prefill chunks, resolved after the fact
    here because acceptance is only known once the pass is scored.

    cache_pos: (..., B) int32 PRE-call positions; accept: (B,) int32 in
    ``[1, n_call]``. Returns bool (..., B, S): True where the slot was
    written by a rejected token and must take the old cache value.
    """
    t = jnp.mod(jnp.arange(S, dtype=jnp.int32) - cache_pos[..., None], S)
    return (t >= accept[:, None]) & (t < n_call)


def attention_decode_partials(p: Params, cfg: AttnConfig, x: jax.Array,
                              cache_k: jax.Array, cache_v: jax.Array,
                              cache_pos: jax.Array, shard_start: jax.Array):
    """Split-KV decode: this device holds a LENGTH-shard of the cache.

    Returns flash-decoding partials (m, s, o) to be combined across the
    sequence-parallel axis with ``structured_all_reduce`` — the log-latency
    dual-root tree is the right collective for this small, latency-critical
    payload. The new token's K/V are written only by the owning shard.
    """
    B = x.shape[0]
    S = cache_k.shape[1]  # local shard length
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    positions = jnp.full((B, 1), cache_pos, jnp.int32)
    q, k, v = _qkv(p, cfg, x, positions)
    slot = cache_pos - shard_start
    owns = (slot >= 0) & (slot < S)
    cslot = jnp.clip(slot, 0, S - 1)
    new_k = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype),
                                         (0, cslot, 0, 0))
    new_v = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype),
                                         (0, cslot, 0, 0))
    cache_k = jnp.where(owns, new_k, cache_k)
    cache_v = jnp.where(owns, new_v, cache_v)
    ki = shard_start + jnp.arange(S)
    valid = ki <= cache_pos
    rep = H // KV
    qg = q.reshape(B, 1, KV, rep, dh)
    logits = jnp.einsum("btgrd,bsgd->bgrts", qg,
                        cache_k.astype(q.dtype)) / np.sqrt(dh)
    logits = logits.astype(jnp.float32)
    logits = jnp.where(valid[None, None, None, None, :], logits, -1e30)
    m = jnp.max(logits, axis=-1)                           # (B,KV,rep,1)
    e = jnp.exp(logits - m[..., None])
    s = jnp.sum(e, axis=-1)
    o = jnp.einsum("bgrts,bsgd->bgrtd", e.astype(q.dtype),
                   cache_v.astype(q.dtype))                # (B,KV,rep,1,dh)
    return {"m": m, "s": s, "o": o}, cache_k, cache_v


def softmax_partials_combine(a, b):
    """Associative combine for flash-decoding partials."""
    m = jnp.maximum(a["m"], b["m"])
    ea = jnp.exp(a["m"] - m)
    eb = jnp.exp(b["m"] - m)
    return {"m": m,
            "s": a["s"] * ea + b["s"] * eb,
            "o": a["o"] * ea[..., None].astype(a["o"].dtype)
                 + b["o"] * eb[..., None].astype(b["o"].dtype)}


def finish_partials(p: Params, cfg: AttnConfig, parts, dtype) -> jax.Array:
    B = parts["o"].shape[0]
    H, dh = cfg.n_heads, cfg.head_dim
    out = parts["o"] / jnp.maximum(parts["s"], 1e-30)[..., None].astype(parts["o"].dtype)
    out = out.reshape(B, 1, H * dh).astype(dtype)
    return out @ p["wo"].astype(dtype)


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, gated: bool,
             dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 3)
    p = {"w_in": dense_init(ks[0], (d_model, d_ff), dtype=dtype),
         "w_out": dense_init(ks[1], (d_ff, d_model), dtype=dtype)}
    if gated:
        p["w_gate"] = dense_init(ks[2], (d_model, d_ff), dtype=dtype)
    return p

MLP_SPECS = {"w_in": P(None, "model"), "w_out": P("model", None),
             "w_gate": P(None, "model")}


def mlp(p: Params, x: jax.Array, activation: str) -> jax.Array:
    h = x @ p["w_in"].astype(x.dtype)
    if "w_gate" in p:
        h = act_fn(activation)(x @ p["w_gate"].astype(x.dtype)) * h
    else:
        h = act_fn(activation)(h)
    h = maybe_shard(h, P(("pod", "data"), None, "model"))
    return h @ p["w_out"].astype(x.dtype)


# --------------------------------------------------------------------------
# Mixture of Experts parameters (the layers are in models/transformer.py)
# --------------------------------------------------------------------------

def moe_init(key, d_model: int, d_ff: int, n_experts: int, gated: bool,
             dtype=jnp.float32, n_held: int | None = None) -> Params:
    """Router over all ``n_experts``; expert weights for the ``n_held`` this
    chip holds (all of them when None)."""
    ks = jax.random.split(key, 4)
    E = n_experts if n_held is None else n_held
    p = {
        "router": dense_init(ks[0], (d_model, n_experts), scale=0.02,
                             dtype=jnp.float32),
        "w_in": dense_init(ks[1], (E, d_model, d_ff), dtype=dtype),
        "w_out": dense_init(ks[2], (E, d_ff, d_model), dtype=dtype),
    }
    if gated:
        p["w_gate"] = dense_init(ks[3], (E, d_model, d_ff), dtype=dtype)
    return p

# Experts shard d_ff over 'model' (always divisible — expert counts like
# mixtral's 8 are smaller than the 16-way model axis). Expert-dim sharding
# (EP) is the §Perf ablation for the 16-expert archs.
MOE_SPECS = {"router": P(None, None),
             "w_in": P(None, None, "model"), "w_out": P(None, "model", None),
             "w_gate": P(None, None, "model")}
