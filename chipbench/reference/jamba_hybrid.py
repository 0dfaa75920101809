"""Plain float32 forward of one chip's share of a Jamba stack.

The equations, written out here from the published model (transformers'
``JambaForCausalLM``) and importing nothing of the program. Layer ``i`` of
``L``:

    x += mixer_i(rms(x));   x += ffn_i(rms(x))

with ``mixer_i`` attention where ``i % attn_layer_period ==
attn_layer_offset`` and Mamba otherwise, ``ffn_i`` the sparse MoE where
``i % expert_layer_period == expert_layer_offset`` and a gated MLP
otherwise; then a final RMS norm and logits against the untied head.
``rms(x) = x / sqrt(mean(x^2) + eps) * scale``, eps ``rms_norm_eps``.

* Attention: grouped-query causal attention, query head ``h`` reading KV
  head ``h // (H / KV)``, scale ``1/sqrt(head_dim)``, no positional
  embedding.
* Mamba-1, the plain token recurrence: ``(z, u) = h W_in`` (the gate
  first), ``u_t`` through the causal depthwise conv of width ``d_conv``
  with its bias, then ``silu``; ``(dt, B, C) = u W_x``, each RMS-normed;
  ``dt = softplus(dt W_dt + b_dt)``; ``A = -exp(A_log)``;
  ``s_t = exp(dt_t A) s_{t-1} + (dt_t u_t) B_t``; ``y_t = s_t C_t + D u_t``;
  out ``(y * silu(z)) W_out``.
* MoE: router logits ``h W_r`` over all ``router_experts`` in float32,
  ``p = softmax(logits)``, the top ``num_experts_per_tok`` of ``p`` as
  gates (not renormalised). The chip holds experts ``held_experts_first``
  to ``+ num_experts``; each held expert ``e`` adds ``p_e (silu(h W_g^e)
  * (h W_i^e)) W_o^e`` to the tokens that chose it. The experts held on
  other chips add nothing here, as in the program: this is the chip's
  share of the layer.
* MLP: ``(silu(h W_g) * (h W_i)) W_o``.

Departures from the published model, each shared with the program: the
depth, the experts and the vocabulary are this chip's share (see the
configuration's ``reduced``); the Mamba input projection holds the gate's
columns before the input's (the checkpoint holds them the other way
round, which only permutes weights drawn at random).

Everything is float32 with matmuls at ``highest`` precision, on the served
(bfloat16) weights widened a block of columns at a time (one expert's at a
time for the experts), so that the whole runs in the memory the engine
leaves once freed: rows one at a time, tokens in blocks of ``CHUNK``.
Routing follows this forward's own float32 logits, never the program's.

``lowp=True`` is the control: every matmul against a weight (the router
and the logits too) takes both operands through float8 e4m3 with per-row
and per-column scales, the precision below the configuration's bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.dense_decoder import _fp8, argmax, gaps

__all__ = ["sizes", "spec", "logits", "gaps", "argmax", "layer_kind"]

CHUNK = 512          # tokens of a row through a layer at once
COLUMNS = 2048       # columns of a weight widened at once
F32 = jnp.float32


def sizes(config: dict) -> dict:
    D = config["hidden_size"]
    H = config["num_attention_heads"]
    return {"L": config["num_hidden_layers"], "D": D, "H": H,
            "KV": config["num_key_value_heads"], "dh": D // H,
            "F": config["intermediate_size"], "V": config["vocab_held"],
            "E": config["router_experts"], "C": config["num_experts"],
            "first": config["held_experts_first"],
            "k": config["num_experts_per_tok"],
            "Di": config["mamba_expand"] * D, "N": config["mamba_d_state"],
            "R": config["mamba_dt_rank"], "Kc": config["mamba_d_conv"],
            "eps": config["rms_norm_eps"],
            "attn": (config["attn_layer_period"],
                     config["attn_layer_offset"]),
            "moe": (config["expert_layer_period"],
                    config["expert_layer_offset"])}


def layer_kind(s: dict, i: int) -> tuple:
    """(mixer, ffn) of layer ``i``: ``attn``/``mamba``, ``moe``/``mlp``."""
    (ap, ao), (ep, eo) = s["attn"], s["moe"]
    return ("attn" if i % ap == ao else "mamba",
            "moe" if i % ep == eo else "mlp")


def _period(s: dict) -> int:
    return int(np.lcm(s["attn"][0], s["moe"][0]))


def spec(config: dict) -> dict:
    """``{path: (shape, dtype)}`` of the weights this forward reads, in the
    layout the program takes them: the layers of one period side by side
    (``layers/<i>/<0 mixer | 1 ffn>/...``), each leaf stacked over the
    periods."""
    s = sizes(config)
    D, H, KV, dh, F, V = (s[k] for k in ("D", "H", "KV", "dh", "F", "V"))
    Di, N, R, Kc, E, C = (s[k] for k in ("Di", "N", "R", "Kc", "E", "C"))
    w, f = config["torch_dtype"], "float32"
    P = _period(s)
    n = s["L"] // P
    out = {"embed": ((V, D), w), "final_norm/scale": ((D,), f),
           "unembed": ((D, V), w)}
    for i in range(P):
        mixer, ffn = layer_kind(s, i)
        a, b = f"layers/{i}/0", f"layers/{i}/1"
        leaves = {f"{a}/norm/scale": ((D,), f), f"{b}/norm/scale": ((D,), f)}
        if mixer == "attn":
            leaves.update({f"{a}/wq": ((D, H * dh), w),
                           f"{a}/wk": ((D, KV * dh), w),
                           f"{a}/wv": ((D, KV * dh), w),
                           f"{a}/wo": ((H * dh, D), w)})
        else:
            leaves.update({
                f"{a}/in_proj": ((D, 2 * Di), w),
                f"{a}/conv_w": ((Kc, Di), w), f"{a}/conv_b": ((Di,), f),
                f"{a}/x_proj": ((Di, R + 2 * N), w),
                f"{a}/dt_proj": ((R, Di), w), f"{a}/dt_bias": ((Di,), f),
                f"{a}/A_log": ((Di, N), f), f"{a}/D_skip": ((Di,), f),
                f"{a}/out_proj": ((Di, D), w),
                f"{a}/dt_norm/scale": ((R,), f),
                f"{a}/b_norm/scale": ((N,), f),
                f"{a}/c_norm/scale": ((N,), f)})
        if ffn == "moe":
            leaves.update({f"{b}/router": ((D, E), f),
                           f"{b}/w_in": ((C, D, F), w),
                           f"{b}/w_gate": ((C, D, F), w),
                           f"{b}/w_out": ((C, F, D), w)})
        else:
            leaves.update({f"{b}/w_in": ((D, F), w),
                           f"{b}/w_gate": ((D, F), w),
                           f"{b}/w_out": ((F, D), w)})
        out.update({k: ((n,) + shape, dt) for k, (shape, dt) in
                    leaves.items()})
    return out


# ------------------------------------------------------------- the pieces

@functools.partial(jax.jit, static_argnames=("n", "lowp"))
def _mm_cols(x, w, idx, col, *, n, lowp):
    """``x @ w[idx][:, col:col + n]`` in float32: only that block of the
    (stacked) weight is widened."""
    lead = w.ndim - 2
    blk = jax.lax.dynamic_slice(w, tuple(idx) + (0, col),
                                (1,) * lead + (w.shape[-2], n))
    blk = blk.reshape(w.shape[-2], n).astype(F32)
    if lowp:
        x, blk = _fp8(x, -1), _fp8(blk, 0)
    return jnp.matmul(x, blk, precision="highest")


def _mm(x, w, idx=(), *, lowp):
    """``x @ w[idx]`` (x float32), a block of ``COLUMNS`` at a time."""
    idx = tuple(jnp.int32(i) for i in idx)
    m = w.shape[-1]
    return jnp.concatenate(
        [_mm_cols(x, w, idx, jnp.int32(c), n=min(COLUMNS, m - c), lowp=lowp)
         for c in range(0, m, COLUMNS)], axis=-1)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, scale, *, eps):
    return _rms(x, scale.astype(F32), eps)


@jax.jit
def _add(a, b):
    return a + b


@jax.jit
def _mamba_conv(u, window, conv_w, conv_b):
    """The causal depthwise conv of ``u`` after ``window`` (the previous
    ``d_conv - 1`` inputs), its bias and ``silu``; and the next window."""
    Kc, T = conv_w.shape[0], u.shape[0]
    cat = jnp.concatenate([window, u], axis=0)
    w = conv_w.astype(F32)
    xc = sum(cat[j:j + T] * w[j] for j in range(Kc)) + conv_b
    return jax.nn.silu(xc), cat[-(Kc - 1):]


@functools.partial(jax.jit, static_argnames=("R", "N", "eps"))
def _mamba_split(proj, dt_s, b_s, c_s, *, R, N, eps):
    dt, Bc, Cc = jnp.split(proj, [R, R + N], axis=-1)
    return _rms(dt, dt_s, eps), _rms(Bc, b_s, eps), _rms(Cc, c_s, eps)


@jax.jit
def _mamba_scan(dt_low, xc, Bc, Cc, dt_bias, A_log, D_skip, z, state):
    """The token recurrence over one block; ``dt_low`` is ``dt W_dt``."""
    dt = jax.nn.softplus(dt_low + dt_bias)
    A = -jnp.exp(A_log)

    def step(s, inp):
        dt_t, u_t, b_t, c_t = inp
        s = jnp.exp(dt_t[:, None] * A) * s + (dt_t * u_t)[:, None] * b_t[None]
        return s, s @ c_t

    state, y = jax.lax.scan(step, state, (dt, xc, Bc, Cc))
    return (y + D_skip * xc) * jax.nn.silu(z), state


@functools.partial(jax.jit, static_argnames=("H", "KV"))
def _attend(q, k, v, q0, *, H, KV):
    """Causal attention of the queries at positions ``q0 ..`` over every
    key; (Tq, H*dh)."""
    Tq, S = q.shape[0], k.shape[0]
    dh = q.shape[1] // H
    q = q.reshape(Tq, H, dh)
    k = jnp.repeat(k.reshape(S, KV, dh), H // KV, axis=1)
    v = jnp.repeat(v.reshape(S, KV, dh), H // KV, axis=1)
    a = jnp.einsum("thd,shd->hts", q, k, precision="highest") / np.sqrt(dh)
    causal = (q0 + jnp.arange(Tq))[:, None] >= jnp.arange(S)[None, :]
    a = jax.nn.softmax(jnp.where(causal[None], a, -jnp.inf), axis=-1)
    return jnp.einsum("hts,shd->thd", a, v,
                      precision="highest").reshape(Tq, H * dh)


@jax.jit
def _silu_gate(g, i):
    return jax.nn.silu(g) * i


@functools.partial(jax.jit, static_argnames=("k", "lowp"))
def _route(h, router, idx, *, k, lowp):
    """Gates (T, k) and experts (T, k) from this forward's own logits."""
    r = jax.lax.dynamic_index_in_dim(router, idx, keepdims=False)
    if lowp:
        h, r = _fp8(h, -1), _fp8(r, 0)
    p = jax.nn.softmax(jnp.matmul(h, r, precision="highest"), axis=-1)
    return jax.lax.top_k(p, k)


@jax.jit
def _gather(h, rows):
    return h[rows]


@jax.jit
def _scatter_add(out, rows, y, gate):
    return out.at[rows].add(y * gate[:, None])


# --------------------------------------------------------------- layers

def _mamba(w, a, p, h, s, lowp):
    S = h.shape[0]
    Kc, Di, N = s["Kc"], s["Di"], s["N"]
    window = jnp.zeros((Kc - 1, Di), F32)
    state = jnp.zeros((Di, N), F32)
    sc = {k: w[f"{a}/{k}"][p] for k in ("conv_b", "dt_bias", "A_log",
                                        "D_skip", "dt_norm/scale",
                                        "b_norm/scale", "c_norm/scale")}
    conv_w = w[f"{a}/conv_w"][p]
    outs = []
    for c in range(0, S, CHUNK):
        zu = _mm(h[c:c + CHUNK], w[f"{a}/in_proj"], (p,), lowp=lowp)
        z, u = zu[:, :Di], zu[:, Di:]
        xc, window = _mamba_conv(u, window, conv_w, sc["conv_b"])
        proj = _mm(xc, w[f"{a}/x_proj"], (p,), lowp=lowp)
        dt, Bc, Cc = _mamba_split(proj, sc["dt_norm/scale"],
                                  sc["b_norm/scale"], sc["c_norm/scale"],
                                  R=s["R"], N=N, eps=s["eps"])
        dt_low = _mm(dt, w[f"{a}/dt_proj"], (p,), lowp=lowp)
        y, state = _mamba_scan(dt_low, xc, Bc, Cc, sc["dt_bias"],
                               sc["A_log"], sc["D_skip"], z, state)
        outs.append(_mm(y, w[f"{a}/out_proj"], (p,), lowp=lowp))
    return jnp.concatenate(outs)


def _attention(w, a, p, h, s, lowp):
    k = _mm(h, w[f"{a}/wk"], (p,), lowp=lowp)
    v = _mm(h, w[f"{a}/wv"], (p,), lowp=lowp)
    outs = []
    for c in range(0, h.shape[0], CHUNK):
        q = _mm(h[c:c + CHUNK], w[f"{a}/wq"], (p,), lowp=lowp)
        o = _attend(q, k, v, jnp.int32(c), H=s["H"], KV=s["KV"])
        outs.append(_mm(o, w[f"{a}/wo"], (p,), lowp=lowp))
    return jnp.concatenate(outs)


def _mlp(w, b, idx, h, lowp):
    g = _mm(h, w[f"{b}/w_gate"], idx, lowp=lowp)
    u = _silu_gate(g, _mm(h, w[f"{b}/w_in"], idx, lowp=lowp))
    return _mm(u, w[f"{b}/w_out"], idx, lowp=lowp)


def _moe(w, b, p, h, s, lowp):
    """The held experts' share, one expert at a time over the tokens that
    chose it (their count padded to a power of two, at least 128, with a
    token whose gate is zero)."""
    gates, experts = _route(h, w[f"{b}/router"], jnp.int32(p), k=s["k"],
                            lowp=lowp)
    gates, experts = np.asarray(gates), np.asarray(experts)
    out = jnp.zeros_like(h)
    for e in range(s["first"], s["first"] + s["C"]):
        tok, slot = np.nonzero(experts == e)
        if not len(tok):
            continue
        n = max(128, 1 << (len(tok) - 1).bit_length())
        rows = np.zeros(n, np.int32)
        rows[:len(tok)] = tok
        gate = np.zeros(n, np.float32)
        gate[:len(tok)] = gates[tok, slot]
        y = _mlp(w, b, (p, e - s["first"]), _gather(h, jnp.asarray(rows)),
                 lowp)
        out = _scatter_add(out, jnp.asarray(rows), y, jnp.asarray(gate))
    return out


def _row(w, s, tokens, at, lowp):
    x = w["embed"][jnp.asarray(tokens)].astype(F32)
    P = _period(s)
    for i in range(s["L"]):
        p, j = divmod(i, P)
        mixer, ffn = layer_kind(s, i)
        a, b = f"layers/{j}/0", f"layers/{j}/1"
        h = _norm(x, w[f"{a}/norm/scale"][p], eps=s["eps"])
        f = _attention if mixer == "attn" else _mamba
        x = _add(x, f(w, a, p, h, s, lowp))
        h = _norm(x, w[f"{b}/norm/scale"][p], eps=s["eps"])
        o = (_moe(w, b, p, h, s, lowp) if ffn == "moe"
             else _mlp(w, b, (p,), h, lowp))
        x = _add(x, o)
    x = _norm(x[jnp.asarray(at)], w["final_norm/scale"], eps=s["eps"])
    return _mm(x, w["unembed"], lowp=lowp)


def logits(weights: dict, config: dict, tokens, at, *,
           lowp: bool = False) -> list:
    """Reference logits on the device, one (1, K, V) float32 array per row.

    ``tokens`` (B, T) int32: each row a prompt followed by its served
    tokens, right-padded (causal attention and the recurrence leave
    padding unread). ``at`` (B, K) int32: positions whose next-token
    logits are wanted. Each row runs up to the whole ``CHUNK`` of tokens
    that holds its last wanted position."""
    s = sizes(config)
    pad = -tokens.shape[1] % CHUNK
    tokens = np.pad(tokens, ((0, 0), (0, pad)))
    out = []
    with jax.default_matmul_precision("highest"):
        for r in range(tokens.shape[0]):
            T = (int(at[r].max()) // CHUNK + 1) * CHUNK
            out.append(_row(weights, s, tokens[r, :T], at[r], lowp)[None])
    return out
