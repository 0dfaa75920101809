"""Plain float32 forward of the repository's dense decoder block.

The equations, as ``models/transformer.py`` runs them for a dense
attention + gated-MLP stack (written here again, importing nothing of the
program): token embedding; per layer ``x += attn(rms(x))`` then
``x += mlp(rms(x))``; a final RMS norm; logits against the tied embedding.
Attention is causal multi-head attention with rotate-half rotary
embeddings (theta from the config) and a ``1/sqrt(head_dim)`` scale; the
MLP is ``(silu(x Wg) * (x Win)) Wout``; RMS norm is
``x / sqrt(mean(x^2) + eps) * scale``.

Everything is float32 with matmuls at ``highest`` precision. The weights
are the served (bfloat16) values, widened. The forward runs layer by layer
over batches of rows, so that it fits next to nothing else on one chip.

``lowp=True`` is the control: every matmul against a weight (and the
logits) takes both operands through float8 e4m3 with per-row and
per-column scales, the precision below the configuration's bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ATTN, MLP = "layers/0/0", "layers/0/1"


def sizes(config: dict) -> dict:
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    return {"L": config["num_hidden_layers"], "D": d, "H": h,
            "KV": config["num_key_value_heads"], "dh": d // h,
            "F": config["intermediate_size"], "V": config["vocab_size"],
            "eps": config["rms_norm_eps"], "theta": config["rope_theta"]}


def spec(config: dict) -> dict:
    """``{path: (shape, dtype)}`` of the weights this forward reads, in the
    layout the program takes them."""
    s = sizes(config)
    L, D, H, KV, dh, F, V = (s[k] for k in ("L", "D", "H", "KV", "dh", "F",
                                           "V"))
    w = config["torch_dtype"]
    return {
        "embed": ((V, D), w),
        "final_norm/scale": ((D,), "float32"),
        f"{ATTN}/norm/scale": ((L, D), "float32"),
        f"{ATTN}/wq": ((L, D, H * dh), w),
        f"{ATTN}/wk": ((L, D, KV * dh), w),
        f"{ATTN}/wv": ((L, D, KV * dh), w),
        f"{ATTN}/wo": ((L, H * dh, D), w),
        f"{MLP}/norm/scale": ((L, D), "float32"),
        f"{MLP}/w_in": ((L, D, F), w),
        f"{MLP}/w_gate": ((L, D, F), w),
        f"{MLP}/w_out": ((L, F, D), w),
    }


def _fp8(x, axis):
    """Round to float8 e4m3 (3 mantissa bits, largest 448) after scaling
    each slice along ``axis`` to that largest value."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    y = x / s
    step = jnp.exp2(jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 2.0 ** -6)))
                    - 3)
    return jnp.clip(jnp.round(y / step) * step, -448.0, 448.0) * s


def _mm(x, w, lowp):
    if lowp:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision="highest")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (B, T, H, dh); positions 0..T-1, rotate-half convention."""
    T, dh = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs     # (T, dh/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("s", "lowp"))
def _layer(x, w, l, *, s, lowp):
    """One decoder layer at index ``l`` of the stacked weights ``w``."""
    B, T, D = x.shape
    H, KV, dh = s["H"], s["KV"], s["dh"]
    g = {k: w[k][l].astype(jnp.float32) for k in w}
    h = _rms(x, g[f"{ATTN}/norm/scale"], s["eps"])
    q = _rope(_mm(h, g[f"{ATTN}/wq"], lowp).reshape(B, T, H, dh), s["theta"])
    k = _rope(_mm(h, g[f"{ATTN}/wk"], lowp).reshape(B, T, KV, dh),
              s["theta"])
    v = _mm(h, g[f"{ATTN}/wv"], lowp).reshape(B, T, KV, dh)
    rep = H // KV
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    a = jnp.einsum("bthd,bshd->bhts", q, k, precision="highest") / np.sqrt(dh)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    a = jax.nn.softmax(jnp.where(causal, a, -jnp.inf), axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", a, v, precision="highest")
    x = x + _mm(o.reshape(B, T, H * dh), g[f"{ATTN}/wo"], lowp)
    h = _rms(x, g[f"{MLP}/norm/scale"], s["eps"])
    u = jax.nn.silu(_mm(h, g[f"{MLP}/w_gate"], lowp)) * _mm(
        h, g[f"{MLP}/w_in"], lowp)
    return x + _mm(u, g[f"{MLP}/w_out"], lowp)


@jax.jit
def _embed(embed, tokens):
    return embed.astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("eps", "lowp"))
def _logits(x, at, norm, embed, *, eps, lowp):
    """Logits at positions ``at`` (B, K) of each row."""
    x = jnp.take_along_axis(x, at[..., None], axis=1)           # (B, K, D)
    x = _rms(x, norm, eps)
    return _mm(x, embed.astype(jnp.float32).T, lowp)            # (B, K, V)


def logits(weights: dict, config: dict, tokens, at, *, lowp: bool = False,
           rows: int = 4) -> list:
    """Reference logits on the device, one (rows, K, V) float32 array per
    batch of ``rows`` rows (the last batch padded with empty rows).

    ``tokens`` (B, T) int32: each row a prompt followed by its served
    tokens, right-padded (causal attention leaves padding unread).
    ``at`` (B, K) int32: positions whose next-token logits are wanted."""
    s = _frozen(sizes(config))
    stacked = {k: v for k, v in weights.items() if k.startswith("layers/")}
    n = tokens.shape[0]
    pad = -n % rows                 # every batch of rows has one shape
    tokens = np.concatenate([tokens, np.zeros((pad,) + tokens.shape[1:],
                                              tokens.dtype)])
    at = np.concatenate([at, np.zeros((pad,) + at.shape[1:], at.dtype)])
    out = []
    with jax.default_matmul_precision("highest"):
        for r in range(0, n, rows):
            x = _embed(weights["embed"], jnp.asarray(tokens[r:r + rows]))
            for l in range(s["L"]):
                x = _layer(x, stacked, jnp.int32(l), s=s, lowp=lowp)
            out.append(_logits(
                x, jnp.asarray(at[r:r + rows]), weights["final_norm/scale"],
                weights["embed"], eps=s["eps"], lowp=lowp))
    return out


@jax.jit
def _gaps(ref, tok):
    top = ref.max(-1)
    got = jnp.take_along_axis(ref, tok[..., None], -1)[..., 0]
    return (top - got) / ref.std(-1)


def gaps(ref: list, tokens) -> np.ndarray:
    """How far below the reference's best logit each token's logit lies,
    in standard deviations of the reference logits at that position:
    ``ref`` as :func:`logits` returns it, ``tokens`` (B, K) int32 or a list
    of per-batch device arrays of token ids (as :func:`argmax` gives)."""
    rows = ref[0].shape[0]
    out = []
    for i, r in enumerate(ref):
        tok = (tokens[i] if isinstance(tokens, list)
               else _rows(tokens, i * rows, rows))
        out.append(np.asarray(_gaps(r, jnp.asarray(tok))))
    return np.concatenate(out)


def argmax(batches: list) -> list:
    return [jnp.argmax(b, -1).astype(jnp.int32) for b in batches]


def _rows(a, start, n):
    a = a[start:start + n]
    return np.concatenate([a, np.zeros((n - len(a),) + a.shape[1:],
                                       a.dtype)])


class _frozen(dict):
    """A hashable dict, for a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))
