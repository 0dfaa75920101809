"""Attention-free sequence mixers: RWKV6 ("Finch") and Mamba selective SSM.

Both are linear-recurrence layers; both get two implementations:

* an exact token-recurrent form (``*_recurrent``) — O(1) state per token,
  used for decode and as the correctness oracle;
* a chunkwise form (``*_chunked``) — the sequential dependency is carried
  between chunks while all within-chunk work is dense matmul/associative-scan,
  i.e. MXU-shaped. This is the TPU adaptation of the papers' CUDA kernels:
  instead of warp-level scans we choose chunk sizes so the per-chunk
  working set fits VMEM and the contraction dims are lane-aligned.

Shapes follow (B, T, ...) with multi-head layouts (B, T, H, K).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import dense_init, rmsnorm, rmsnorm_init, Params
from jax.sharding import PartitionSpec as P

# ==========================================================================
# RWKV6 time mix (WKV) + channel mix
# ==========================================================================

@dataclasses.dataclass(frozen=True)
class RWKVConfig:
    d_model: int
    head_dim: int = 64
    decay_lora: int = 64
    chunk_size: int = 32

    @property
    def n_heads(self) -> int:
        return self.d_model // self.head_dim


def rwkv_block_init(key, cfg: RWKVConfig, dtype=jnp.float32) -> Params:
    D, H, K = cfg.d_model, cfg.n_heads, cfg.head_dim
    ks = jax.random.split(key, 12)
    return {
        "ln1": rmsnorm_init(D),
        "tmix": {
            "mu_r": jnp.full((D,), 0.5, jnp.float32),
            "mu_k": jnp.full((D,), 0.5, jnp.float32),
            "mu_v": jnp.full((D,), 0.5, jnp.float32),
            "mu_w": jnp.full((D,), 0.5, jnp.float32),
            "mu_g": jnp.full((D,), 0.5, jnp.float32),
            "wr": dense_init(ks[0], (D, D), dtype=dtype),
            "wk": dense_init(ks[1], (D, D), dtype=dtype),
            "wv": dense_init(ks[2], (D, D), dtype=dtype),
            "wg": dense_init(ks[3], (D, D), dtype=dtype),
            # data-dependent decay LoRA (the "Finch" novelty)
            "w_lora_a": dense_init(ks[4], (D, cfg.decay_lora), dtype=dtype),
            "w_lora_b": dense_init(ks[5], (cfg.decay_lora, D), scale=0.01,
                                   dtype=dtype),
            "w_bias": jnp.full((D,), -6.0, jnp.float32),
            "u": jnp.zeros((H, K), jnp.float32),           # current-token bonus
            "ln_x": rmsnorm_init(D),
            "wo": dense_init(ks[6], (D, D), dtype=dtype),
        },
        "ln2": rmsnorm_init(D),
        "cmix": {
            "mu_k": jnp.full((D,), 0.5, jnp.float32),
            "mu_r": jnp.full((D,), 0.5, jnp.float32),
            "wk": dense_init(ks[7], (D, int(3.5 * D) // 32 * 32), dtype=dtype),
            "wv": dense_init(ks[8], (int(3.5 * D) // 32 * 32, D), dtype=dtype),
            "wr": dense_init(ks[9], (D, D), dtype=dtype),
        },
    }

RWKV_SPECS = {
    "tmix": {"wr": P(None, "model"), "wk": P(None, "model"),
             "wv": P(None, "model"), "wg": P(None, "model"),
             "wo": P("model", None)},
    "cmix": {"wk": P(None, "model"), "wv": P("model", None),
             "wr": P(None, "model")},
}


def _token_shift(x: jax.Array, prev: jax.Array | None) -> jax.Array:
    """xx[t] = x[t-1]; position 0 takes ``prev`` (decode state) or zeros."""
    if prev is None:
        prev = jnp.zeros_like(x[:, :1])
    return jnp.concatenate([prev, x[:, :-1]], axis=1)


def _tmix_inputs(p: Params, x: jax.Array, shifted: jax.Array, cfg: RWKVConfig):
    def mix(mu):
        return x + (shifted - x) * mu.astype(x.dtype)
    B, T, D = x.shape
    H, K = cfg.n_heads, cfg.head_dim
    r = (mix(p["mu_r"]) @ p["wr"].astype(x.dtype)).reshape(B, T, H, K)
    k = (mix(p["mu_k"]) @ p["wk"].astype(x.dtype)).reshape(B, T, H, K)
    v = (mix(p["mu_v"]) @ p["wv"].astype(x.dtype)).reshape(B, T, H, K)
    g = mix(p["mu_g"]) @ p["wg"].astype(x.dtype)
    wl = mix(p["mu_w"]).astype(jnp.float32)
    w_log = -jnp.exp(jnp.clip(
        (jnp.tanh(wl @ p["w_lora_a"].astype(jnp.float32))
         @ p["w_lora_b"].astype(jnp.float32)) + p["w_bias"], -8.0, 2.0))
    # per-channel log-decay in (-inf, 0); clip keeps the chunked form stable
    w_log = jnp.clip(w_log, -8.0, -1e-4).reshape(B, T, H, K)
    return r, k, v, g, w_log


def wkv_recurrent(r, k, v, w_log, u, state, valid=None, collect=False):
    """Exact recurrence. r/k/v/w_log: (B,T,H,K); u: (H,K); state: (B,H,K,K).

    S_t = diag(w_t) S_{t-1} + k_t (x) v_t ;  o_t = r_t . (S_{t-1} + diag(u) k_t (x) v_t)
    Returns (o: (B,T,H,K), new_state).

    ``valid`` (B, T) bool gates the state update per token: an invalid
    (padding) step carries ``S_t = S_{t-1}`` through a ``where``, so right-pad
    tokens leave the state BIT-unchanged — the invariant bucketed serving
    prefill relies on (the pad outputs are still computed; callers discard
    them). Because the carry is per-token either way, splitting a sequence
    across calls (chunked prefill) reproduces the one-shot states exactly.

    ``collect=True`` returns ``(o, states)`` with the EVERY-step states
    stacked on a token axis: ``states[:, t]`` is S after consuming token
    ``t`` (so ``states[:, -1]`` equals the normal ``new_state``). The
    speculative-decoding verify step uses this to roll the slot back to the
    state at the ACCEPTED length, which is only known after the whole pass
    has been scored.
    """
    w = jnp.exp(w_log.astype(jnp.float32))

    def step(S, inp):
        r_t, k_t, v_t, w_t, m_t = inp
        kv = jnp.einsum("bhk,bhv->bhkv", k_t, v_t)
        o = jnp.einsum("bhk,bhkv->bhv", r_t, S + u[None, :, :, None] * kv)
        S_new = w_t[..., None] * S + kv
        S = jnp.where(m_t[:, None, None, None], S_new, S)
        return S, (S, o) if collect else o

    if valid is None:
        valid = jnp.ones(r.shape[:2], bool)
    rs, ks_, vs, ws = (jnp.moveaxis(t.astype(jnp.float32), 1, 0)
                       for t in (r, k, v, w))
    ms = jnp.moveaxis(valid, 1, 0)
    state, out = jax.lax.scan(step, state, (rs, ks_, vs, ws, ms))
    if collect:
        states, out = out
        return (jnp.moveaxis(out, 0, 1).astype(r.dtype),
                jnp.moveaxis(states, 0, 1))
    return jnp.moveaxis(out, 0, 1).astype(r.dtype), state


def wkv_chunked(r, k, v, w_log, u, state, chunk: int):
    """Chunkwise-parallel WKV6.

    Within a chunk of C tokens the contribution of token j<i to output i is
    ``r_i . diag(exp(cw_{i-1} - cw_j)) k_j  v_j`` with ``cw`` the in-chunk
    cumulative log-decay; all exponents are <= 0 so the (C,C,K) tensor is
    numerically safe. Cross-chunk history flows through the (K,V) state.
    """
    B, T, H, K = r.shape
    C = chunk
    assert T % C == 0, (T, C)
    n = T // C
    f32 = jnp.float32
    rc = r.astype(f32).reshape(B, n, C, H, K)
    kc = k.astype(f32).reshape(B, n, C, H, K)
    vc = v.astype(f32).reshape(B, n, C, H, K)
    wc = w_log.astype(f32).reshape(B, n, C, H, K)

    def chunk_step(S, inp):
        rr, kk, vv, ww = inp                     # (B,C,H,K)
        cw = jnp.cumsum(ww, axis=1)              # cw_i = sum_{s<=i} log w_s
        cw_im1 = cw - ww                         # sum_{s<i}
        # intra-chunk pairwise decays: exp(cw_{i-1} - cw_j), j < i
        diff = cw_im1[:, :, None] - cw[:, None, :, :]     # (B,C,C,H,K)
        mask = (jnp.arange(C)[:, None] > jnp.arange(C)[None, :])
        A = jnp.einsum("bihk,bjhk,bijhk->bhij", rr, kk,
                       jnp.exp(jnp.where(mask[:, :, None, None], diff, -1e30)))
        # current-token (diagonal) bonus term
        diag = jnp.einsum("bihk,bihk->bhi", rr * u[None, None], kk)
        o = jnp.einsum("bhij,bjhv->bihv", A, vc_cur := vv) \
            + diag.transpose(0, 2, 1)[..., None] * vv
        # contribution of the carried state
        o = o + jnp.einsum("bihk,bhkv->bihv", rr * jnp.exp(cw_im1), S)
        # state update: S' = diag(exp(cw_C)) S + sum_j exp(cw_C - cw_j) k_j v_j
        wtot = cw[:, -1]                          # (B,H,K)
        kscal = kk * jnp.exp(wtot[:, None] - cw)
        S = jnp.exp(wtot)[..., None] * S + jnp.einsum("bjhk,bjhv->bhkv",
                                                      kscal, vv)
        return S, o

    seq = (jnp.moveaxis(t, 1, 0) for t in (rc, kc, vc, wc))
    state, out = jax.lax.scan(chunk_step, state, tuple(seq))
    out = jnp.moveaxis(out, 0, 1).reshape(B, T, H, K)
    return out.astype(r.dtype), state


def _checkpoint_row(seq: jax.Array, lengths: jax.Array | None) -> jax.Array:
    """seq: (B, T, D). Returns (B, 1, D): token ``lengths-1`` per row — the
    last REAL token — or the last token when ``lengths`` is None.

    This selection is what makes recurrent carries SHARABLE across
    requests: the checkpointed carry at length L depends on tokens
    ``t[0:L]`` only (pads past L are masked out of every state update), so
    a carry snapshotted after prefilling a shared prompt prefix is
    bit-identical to the one any later request would have computed over the
    same tokens — the split-point state the cross-request prefix cache
    (:mod:`repro.serving.prefix`) stores for SSM/hybrid slots."""
    if lengths is None:
        return seq[:, -1:]
    idx = jnp.clip(lengths - 1, 0)[:, None, None]
    return jnp.take_along_axis(
        seq, jnp.broadcast_to(idx, (seq.shape[0], 1, seq.shape[2])), axis=1)


def rwkv_block(p: Params, x: jax.Array, cfg: RWKVConfig,
               cache: Params | None = None, use_chunked: bool = True,
               lengths: jax.Array | None = None,
               collect_states: bool = False):
    """Full RWKV6 block (time mix + channel mix) with optional decode cache.

    cache = {"shift1": (B,1,D), "shift2": (B,1,D), "state": (B,H,K,K)}.

    ``lengths`` (B,) int32 activates the serving-prefill contract: the input
    is right-padded to T, only tokens ``t < lengths[b]`` are real, and the
    new cache checkpoints the recurrent state AT the true length — the WKV
    state update is masked past ``lengths`` (pads leave it bit-unchanged)
    and the token-shift carries are gathered at ``lengths-1`` instead of
    ``T-1``. This path always runs the exact token recurrence (never the
    chunkwise form), so splitting a prompt across successive calls with the
    carried cache is bit-identical to one call over the whole prompt.

    ``collect_states=True`` (speculative verify; requires a cache) also
    runs the exact recurrence, but the returned cache carries a per-TOKEN
    checkpoint axis right after batch: leaf ``[:, t]`` is the cache as if
    the call had ended at token ``t`` (``shift1``/``shift2``: (B,T,1,D);
    ``state``: (B,T,H,K,K)). The caller gathers the accepted length's
    entry once acceptance is known — rejected draft tokens then leave the
    carry bit-unchanged, the same invariant ``lengths`` gives prefill,
    just resolved after the fact. The two compose: with both set, tokens
    past ``lengths`` are verify-buffer padding whose checkpoints are
    frozen (acceptance never reaches them).
    """
    B, T, D = x.shape
    H, K = cfg.n_heads, cfg.head_dim
    tm, cm = p["tmix"], p["cmix"]

    xn = rmsnorm(p["ln1"], x)
    shifted = _token_shift(xn, cache["shift1"] if cache else None)
    r, k, v, g, w_log = _tmix_inputs(tm, xn, shifted, cfg)
    state = (cache["state"] if cache else
             jnp.zeros((B, H, K, K), jnp.float32))
    u = tm["u"].astype(jnp.float32)
    states_all = None
    if collect_states:
        valid = (None if lengths is None else
                 jnp.arange(T, dtype=jnp.int32)[None] < lengths[:, None])
        o, states_all = wkv_recurrent(r, k, v, w_log, u, state, valid=valid,
                                      collect=True)
    elif lengths is not None:
        valid = jnp.arange(T, dtype=jnp.int32)[None] < lengths[:, None]
        o, state = wkv_recurrent(r, k, v, w_log, u, state, valid=valid)
    elif T == 1 or not use_chunked or T % cfg.chunk_size != 0:
        o, state = wkv_recurrent(r, k, v, w_log, u, state)
    else:
        o, state = wkv_chunked(r, k, v, w_log, u, state, cfg.chunk_size)
    o = rmsnorm(tm["ln_x"], o.reshape(B, T, D))
    o = (jax.nn.silu(g) * o) @ tm["wo"].astype(x.dtype)
    x = x + o

    xn2 = rmsnorm(p["ln2"], x)
    shifted2 = _token_shift(xn2, cache["shift2"] if cache else None)
    def mix(mu):
        return xn2 + (shifted2 - xn2) * mu.astype(x.dtype)
    kk = jnp.square(jax.nn.relu(mix(cm["mu_k"]) @ cm["wk"].astype(x.dtype)))
    cout = jax.nn.sigmoid(mix(cm["mu_r"]) @ cm["wr"].astype(x.dtype)) \
        * (kk @ cm["wv"].astype(x.dtype))
    x = x + cout

    if collect_states:
        # per-token checkpoints: the shift carry after token t is simply the
        # normed activation AT t, so the full (B,T,D) rows are the stack
        new_cache = {"shift1": xn[:, :, None, :], "shift2": xn2[:, :, None, :],
                     "state": states_all}
    else:
        new_cache = {"shift1": _checkpoint_row(xn, lengths),
                     "shift2": _checkpoint_row(xn2, lengths), "state": state}
    return x, new_cache


def rwkv_cache_spec(cfg: RWKVConfig, batch: int, dtype):
    H, K, D = cfg.n_heads, cfg.head_dim, cfg.d_model
    return {"shift1": jax.ShapeDtypeStruct((batch, 1, D), dtype),
            "shift2": jax.ShapeDtypeStruct((batch, 1, D), dtype),
            "state": jax.ShapeDtypeStruct((batch, H, K, K), jnp.float32)}


# ==========================================================================
# Mamba (selective SSM)
# ==========================================================================

@dataclasses.dataclass(frozen=True)
class MambaConfig:
    d_model: int
    d_state: int = 16
    expand: int = 2
    d_conv: int = 4
    chunk_size: int = 32

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def dt_rank(self) -> int:
        return max(1, self.d_model // 16)


def mamba_init(key, cfg: MambaConfig, dtype=jnp.float32) -> Params:
    D, Di, N, R = cfg.d_model, cfg.d_inner, cfg.d_state, cfg.dt_rank
    ks = jax.random.split(key, 6)
    A = jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32)[None], (Di, 1))
    return {
        "in_proj": dense_init(ks[0], (D, 2 * Di), dtype=dtype),
        "conv_w": dense_init(ks[1], (cfg.d_conv, Di), scale=0.5, dtype=dtype),
        "conv_b": jnp.zeros((Di,), jnp.float32),
        "x_proj": dense_init(ks[2], (Di, R + 2 * N), dtype=dtype),
        "dt_proj": dense_init(ks[3], (R, Di), dtype=dtype),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((Di,), 0.01, jnp.float32))),
        "A_log": jnp.log(A),
        "D_skip": jnp.ones((Di,), jnp.float32),
        "out_proj": dense_init(ks[4], (Di, D), dtype=dtype),
        # Jamba's RMS norms of dt, B and C ahead of dt_proj and the scan
        "dt_norm": rmsnorm_init(R),
        "b_norm": rmsnorm_init(N),
        "c_norm": rmsnorm_init(N),
    }

MAMBA_SPECS = {"in_proj": P(None, "model"), "conv_w": P(None, "model"),
               "x_proj": P("model", None), "dt_proj": P(None, "model"),
               "out_proj": P("model", None)}


def _mamba_inner(p, xin, cfg: MambaConfig):
    """xin: (B,T,Di) post-conv, post-silu.

    Returns the COMPACT selective-SSM inputs (dt, dt*x, B, C) — the rank-4
    ``a_log``/``bx`` tensors are (d_state x) larger and are built per-chunk
    inside the scan instead of being materialized over the whole sequence.
    As in Jamba, the x_proj outputs dt (``dt_rank`` wide), B and C
    (``d_state`` each) are RMS-normed (eps 1e-6) before ``dt_proj`` and
    the scan."""
    R, N = cfg.dt_rank, cfg.d_state
    proj = xin @ p["x_proj"].astype(xin.dtype)
    dt, Bc, Cc = jnp.split(proj.astype(jnp.float32), [R, R + N], axis=-1)
    dt = rmsnorm(p["dt_norm"], dt)
    Bc, Cc = rmsnorm(p["b_norm"], Bc), rmsnorm(p["c_norm"], Cc)
    dt = jax.nn.softplus(dt @ p["dt_proj"].astype(jnp.float32) + p["dt_bias"])
    return dt, dt * xin.astype(jnp.float32), Bc, Cc


def mamba_scan_chunked(dt, dtx, Bc, C, A, state, chunk: int):
    """h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) B_t ; y_t = C_t . h_t.

    Chunked: ``associative_scan`` over the chunk axis (log-depth, in-VMEM),
    sequential carry across chunks. The rank-4 per-step coefficients are
    built per CHUNK from the compact inputs — materializing them over the
    full sequence would cost d_state x the activation memory.
    dt/dtx: (B,T,Di); Bc/C: (B,T,N); A: (Di,N).
    """
    B, T, Di = dt.shape
    N = A.shape[1]
    Cn = chunk
    assert T % Cn == 0
    n = T // Cn

    def combine(x, y):
        (la1, b1), (la2, b2) = x, y
        return la1 + la2, b2 + jnp.exp(la2) * b1

    def chunk_step(h, inp):
        dtc, dtxc, bb, cc = inp                    # (B,Cn,Di), (B,Cn,N)
        la = dtc[..., None] * A[None, None]        # (B,Cn,Di,N) in-chunk only
        b = dtxc[..., None] * bb[:, :, None, :]
        pla, pb = jax.lax.associative_scan(combine, (la, b), axis=1)
        h_all = jnp.exp(pla) * h[:, None] + pb     # (B,Cn,Di,N)
        y = jnp.einsum("bcdn,bcn->bcd", h_all, cc)
        return h_all[:, -1], y

    seq = tuple(jnp.moveaxis(t.reshape(B, n, Cn, -1), 1, 0)
                for t in (dt, dtx, Bc, C))
    state, ys = jax.lax.scan(chunk_step, state, seq)
    return jnp.moveaxis(ys, 0, 1).reshape(B, T, Di), state


def mamba_scan_recurrent(dt, dtx, Bc, C, A, state, valid=None,
                         collect=False):
    """Exact token recurrence — op-for-op the T==1 decode step, scanned.

    Used by serving prefill: because the carry is advanced one token at a
    time with the same arithmetic as single-token decode, (a) splitting a
    prompt across calls (chunked admission) reproduces the one-shot state
    bit-exactly, and (b) ``valid`` (B, T) masks the state update behind a
    ``where`` so right-pad tokens leave the carry bit-unchanged. The
    chunkwise associative-scan form trades this exactness for MXU shape —
    its reduction tree depends on T, so it stays the training/one-shot path.

    ``collect=True`` returns ``(y, states)`` with every step's state stacked
    on a token axis (``states[:, t]`` = h after token ``t``) — the
    speculative verify step gathers the accepted length's entry after
    scoring (see :func:`wkv_recurrent`).
    """
    def step(h, inp):
        dt_t, dtx_t, b_t, c_t, m_t = inp
        a0 = dt_t[:, :, None] * A[None]
        b0 = dtx_t[:, :, None] * b_t[:, None, :]
        h_new = jnp.exp(a0) * h + b0
        h = jnp.where(m_t[:, None, None], h_new, h)
        y = jnp.einsum("bdn,bn->bd", h_new, c_t)
        return h, (h, y) if collect else y

    if valid is None:
        valid = jnp.ones(dt.shape[:2], bool)
    seq = tuple(jnp.moveaxis(t, 1, 0) for t in (dt, dtx, Bc, C, valid))
    state, ys = jax.lax.scan(step, state, seq)
    if collect:
        states, ys = ys
        return jnp.moveaxis(ys, 0, 1), jnp.moveaxis(states, 0, 1)
    return jnp.moveaxis(ys, 0, 1), state


MAMBA_TIME_BLOCK = 1024


def mamba_block(p: Params, x: jax.Array, cfg: MambaConfig,
                cache: Params | None = None,
                lengths: jax.Array | None = None,
                collect_states: bool = False):
    """Mamba block with optional decode cache
    {"conv": (B, d_conv-1, Di), "ssm": (B, Di, N)}.

    ``lengths`` (B,) int32 is the serving-prefill contract (see
    :func:`rwkv_block`): inputs are right-padded to T, the selective-scan
    state update is masked past ``lengths`` and the scan runs the exact
    token recurrence, and the depthwise-conv window is checkpointed at the
    true length (``xcat[:, len:len+d_conv-1]``, not the padded tail).

    ``collect_states=True`` (speculative verify; requires a cache) runs the
    exact recurrence and returns per-TOKEN cache checkpoints on an axis
    after batch — ``conv``: (B,T,Kc-1,Di) with entry ``t`` the window
    ending at token ``t``; ``ssm``: (B,T,Di,N) — so the caller can commit
    the accepted length's state after scoring. Composes with ``lengths``
    (verify-buffer padding) as in :func:`rwkv_block`.

    A prompt longer than ``2 * MAMBA_TIME_BLOCK`` goes through in blocks
    of ``MAMBA_TIME_BLOCK`` tokens, the state carried from block to block
    exactly as chunked admission carries it from call to call: one block's
    float32 scan inputs are held at a time, not the whole prompt's.
    """
    B, T, D = x.shape
    Di, N, Kc = cfg.d_inner, cfg.d_state, cfg.d_conv
    if (lengths is not None and not collect_states
            and T > 2 * MAMBA_TIME_BLOCK and T % MAMBA_TIME_BLOCK == 0):
        n, tb = T // MAMBA_TIME_BLOCK, MAMBA_TIME_BLOCK
        if cache is None:
            cache = jax.tree.map(
                lambda sd: jnp.zeros(sd.shape, sd.dtype),
                mamba_cache_spec(cfg, B, x.dtype))
        def block(c, i):
            xb = jax.lax.dynamic_slice_in_dim(x, i * tb, tb, axis=1)
            lb = jnp.clip(lengths - i * tb, 0, tb).astype(lengths.dtype)
            out, c = mamba_block(p, xb, cfg, c, lengths=lb)
            return c, out

        cache, outs = jax.lax.scan(block, cache, jnp.arange(n))
        return jnp.swapaxes(outs, 0, 1).reshape(B, T, D), cache
    zx = x @ p["in_proj"].astype(x.dtype)
    z, xin = jnp.split(zx, 2, axis=-1)
    # depthwise causal conv1d
    prev = (cache["conv"] if cache else
            jnp.zeros((B, Kc - 1, Di), x.dtype))
    xcat = jnp.concatenate([prev.astype(x.dtype), xin], axis=1)
    if Kc <= 1:
        new_conv = (jnp.broadcast_to(prev[:, None], (B, T) + prev.shape[1:])
                    if collect_states else prev)
    elif collect_states:
        # window ending at token t: xcat[t+1 : t+Kc) for every t at once
        idx = (jnp.arange(T, dtype=jnp.int32)[:, None] + 1
               + jnp.arange(Kc - 1, dtype=jnp.int32)[None])      # (T, Kc-1)
        new_conv = xcat[:, idx]                                  # (B,T,Kc-1,Di)
    elif lengths is None:
        new_conv = xcat[:, -(Kc - 1):]
    else:
        # conv state after the true length: the Kc-1 inputs ENDING at token
        # lengths-1 (xcat position lengths-1+Kc-1), i.e. window [len, len+Kc-1)
        idx = lengths[:, None] + jnp.arange(Kc - 1, dtype=jnp.int32)[None]
        new_conv = jnp.take_along_axis(xcat, idx[..., None], axis=1)
    w = p["conv_w"].astype(x.dtype)
    xc = sum(xcat[:, k:k + T] * w[k][None, None] for k in range(Kc))
    xc = jax.nn.silu(xc + p["conv_b"].astype(x.dtype))

    dt, dtx, Bc, Cc = _mamba_inner(p, xc, cfg)
    A = -jnp.exp(p["A_log"])                       # (Di,N), negative
    state = (cache["ssm"] if cache else jnp.zeros((B, Di, N), jnp.float32))
    if collect_states:
        valid = (None if lengths is None else
                 jnp.arange(T, dtype=jnp.int32)[None] < lengths[:, None])
        y, state = mamba_scan_recurrent(dt, dtx, Bc, Cc, A, state,
                                        valid=valid,
                                        collect=True)   # state: (B,T,Di,N)
    elif lengths is not None:
        valid = jnp.arange(T, dtype=jnp.int32)[None] < lengths[:, None]
        y, state = mamba_scan_recurrent(dt, dtx, Bc, Cc, A, state,
                                        valid=valid)
    elif T == 1:
        a0 = dt[:, 0, :, None] * A[None]
        b0 = dtx[:, 0, :, None] * Bc[:, 0, None, :]
        h = jnp.exp(a0) * state + b0
        y = jnp.einsum("bdn,bn->bd", h, Cc[:, 0])[:, None]
        state = h
    else:
        ck = cfg.chunk_size if T % cfg.chunk_size == 0 else T
        y, state = mamba_scan_chunked(dt, dtx, Bc, Cc, A, state, ck)
    y = (y + p["D_skip"][None, None] * xc.astype(jnp.float32)).astype(x.dtype)
    out = (jax.nn.silu(z) * y) @ p["out_proj"].astype(x.dtype)
    return out, {"conv": new_conv.astype(x.dtype), "ssm": state}


def mamba_cache_spec(cfg: MambaConfig, batch: int, dtype):
    return {"conv": jax.ShapeDtypeStruct((batch, cfg.d_conv - 1, cfg.d_inner),
                                         dtype),
            "ssm": jax.ShapeDtypeStruct((batch, cfg.d_inner, cfg.d_state),
                                        jnp.float32)}
