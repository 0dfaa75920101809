"""Config-driven transformer stack covering all assigned architectures.

A model is a *layer pattern*: a period of layers, each a tuple of sublayers
(``attn`` / ``xattn`` / ``mlp`` / ``moe`` / ``mamba`` / ``rwkv``). The full
depth is ``n_periods`` repetitions of the pattern, executed under
``lax.scan`` with parameters stacked along a leading period axis — this keeps
the HLO size O(pattern) instead of O(depth), which is what makes the 512-chip
dry-run compile in seconds even for 56-layer models.

Examples:
  dense (minicpm/granite/...):   period = [ (attn, mlp) ]
  mixtral-8x22b:                 period = [ (attn{swa}, moe) ]
  llama4-scout (iRoPE):          period = [ (attn{chunk,rope}, moe) x3,
                                            (attn{global,norope}, moe) ]
  jamba (1:7 attn:mamba, moe/2): period of 8, attn at index 4, moe on odd
  rwkv6:                         period = [ (rwkv,) ]  (block includes FFN)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models import ssm
from repro.models.layers import Params, maybe_shard

# --------------------------------------------------------------------------
# configuration
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MoESettings:
    """Routing and layout of a sparse expert layer.

    ``impl``: ``'dispatch'`` (sort-based, capacity ``capacity_factor``:
    tokens that overflow an expert are dropped), ``'masked'`` (every
    expert sees every token) or ``'grouped'`` (the held-experts layer: each
    (token, expert) pair routed to a held expert goes through one grouped
    matmul, none dropped; see :func:`_moe_grouped`).

    ``gate``: ``'topk_softmax'`` takes a softmax over the top-k router
    logits (Mixtral's renormalised rule); ``'softmax_topk'`` takes a
    softmax over all ``n_experts`` logits, then the top k of those
    probabilities, not renormalised (Jamba's).

    ``held``: ``(first, count)``, the experts this chip holds
    (``'grouped'`` only): the router keeps all ``n_experts`` outputs, the
    expert weights are ``(count, ...)``, and the layer returns only those
    experts' share of the result. None holds them all.
    """
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    impl: str = "dispatch"
    gate: str = "topk_softmax"
    held: tuple | None = None

    def __post_init__(self):
        if self.impl not in ("dispatch", "masked", "grouped"):
            raise ValueError(f"unknown MoE impl {self.impl!r}")
        if self.gate not in ("topk_softmax", "softmax_topk"):
            raise ValueError(f"unknown MoE gate rule {self.gate!r}")
        if self.held is not None:
            first, count = self.held
            if self.impl != "grouped":
                raise ValueError("held experts need impl='grouped'")
            if not (0 <= first and count >= 1
                    and first + count <= self.n_experts):
                raise ValueError(f"held {self.held} outside the "
                                 f"{self.n_experts} experts")

    @property
    def held_range(self) -> tuple:
        return self.held if self.held is not None else (0, self.n_experts)


@dataclasses.dataclass(frozen=True)
class SubSpec:
    kind: str                        # attn|xattn|mlp|moe|mamba|rwkv
    use_rope: bool = True
    sliding_window: int | None = None
    chunk_size: int | None = None
    causal: bool = True


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    pattern: tuple = (("attn", "mlp"),)   # tuple of layers; each layer is a
                                          # tuple of SubSpec or kind-strings
    head_dim: int | None = None
    activation: str = "silu"
    gated_mlp: bool = True
    rope_theta: float = 10000.0
    mrope_sections: tuple | None = None
    moe: MoESettings | None = None
    tie_embeddings: bool = True
    input_mode: str = "tokens"            # tokens | embeds (stub frontends)
    # encoder-decoder (seamless): encoder layers use its own pattern
    n_enc_layers: int = 0
    enc_pattern: tuple = ()
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "full"      # full | dots (save matmul outputs)
    kv_quant: bool = False          # int8 KV cache (+ per-row scales)
    rwkv_head_dim: int = 64
    mamba_d_state: int = 16

    def __post_init__(self):
        object.__setattr__(self, "pattern", _norm_pattern(self.pattern))
        if self.enc_pattern:
            object.__setattr__(self, "enc_pattern",
                               _norm_pattern(self.enc_pattern))
        assert self.n_layers % len(self.pattern) == 0, \
            (self.name, self.n_layers, len(self.pattern))

    @property
    def hdim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def n_periods(self) -> int:
        return self.n_layers // len(self.pattern)

    def attn_cfg(self, s: SubSpec) -> L.AttnConfig:
        return L.AttnConfig(
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, head_dim=self.hdim,
            rope_theta=self.rope_theta, sliding_window=s.sliding_window,
            chunk_size=s.chunk_size, causal=s.causal,
            mrope_sections=self.mrope_sections,
            use_rope=s.use_rope)

    def rwkv_cfg(self) -> ssm.RWKVConfig:
        return ssm.RWKVConfig(d_model=self.d_model,
                              head_dim=self.rwkv_head_dim)

    def mamba_cfg(self) -> ssm.MambaConfig:
        return ssm.MambaConfig(d_model=self.d_model,
                               d_state=self.mamba_d_state)

    def param_count(self) -> int:
        zeros = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), self))
        return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(zeros))

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE counts top_k of n_experts)."""
        total = self.param_count()
        if self.moe is None:
            return total
        zeros = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), self))
        inactive = 0
        for lp in zeros["layers"]:
            for sp in lp:
                if "w_in" in sp and sp["w_in"].ndim == 4:  # stacked moe
                    frac = 1.0 - self.moe.top_k / self.moe.n_experts
                    inactive += sum(int(np.prod(sp[k].shape)) * frac
                                    for k in ("w_in", "w_out", "w_gate")
                                    if k in sp)
        return int(total - inactive)


def _norm_pattern(pattern):
    out = []
    for layer in pattern:
        subs = []
        for s in layer:
            subs.append(SubSpec(kind=s) if isinstance(s, str) else s)
        out.append(tuple(subs))
    return tuple(out)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _sub_init(key, cfg: ModelConfig, s: SubSpec) -> Params:
    dt = cfg.param_dtype
    if s.kind in ("attn", "xattn"):
        k1, k2 = jax.random.split(key)
        return {"norm": L.rmsnorm_init(cfg.d_model),
                **L.attn_init(k1, cfg.attn_cfg(s), dtype=dt)}
    if s.kind == "mlp":
        return {"norm": L.rmsnorm_init(cfg.d_model),
                **L.mlp_init(key, cfg.d_model, cfg.d_ff, cfg.gated_mlp, dt)}
    if s.kind == "moe":
        m = cfg.moe
        return {"norm": L.rmsnorm_init(cfg.d_model),
                **L.moe_init(key, cfg.d_model, cfg.d_ff, m.n_experts,
                             cfg.gated_mlp, dt, n_held=m.held_range[1])}
    if s.kind == "mamba":
        return {"norm": L.rmsnorm_init(cfg.d_model),
                **ssm.mamba_init(key, cfg.mamba_cfg(), dt)}
    if s.kind == "rwkv":
        return ssm.rwkv_block_init(key, cfg.rwkv_cfg(), dt)
    raise ValueError(s.kind)


def _stack_layer_params(key, cfg: ModelConfig, pattern, n_periods) -> list:
    """Per pattern position: params stacked over periods (leading axis).

    One vmapped init per sublayer writes the stacked leaves directly: a
    per-period list stacked afterwards would hold every leaf twice, which
    does not fit one chip at published widths."""
    out = []
    for pos, layer in enumerate(pattern):
        subs = []
        for si, s in enumerate(layer):
            keys = jax.random.split(
                jax.random.fold_in(key, pos * 31 + si), n_periods)
            subs.append(jax.vmap(lambda k, s=s: _sub_init(k, cfg, s))(keys))
        out.append(tuple(subs))
    return out


def init_params(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 6)
    p: Params = {
        "embed": L.dense_init(ks[0], (cfg.vocab_size, cfg.d_model),
                              scale=0.02, dtype=cfg.param_dtype),
        "final_norm": L.rmsnorm_init(cfg.d_model),
        "layers": _stack_layer_params(ks[1], cfg, cfg.pattern, cfg.n_periods),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = L.dense_init(ks[2], (cfg.d_model, cfg.vocab_size),
                                    scale=0.02, dtype=cfg.param_dtype)
    if cfg.n_enc_layers:
        n_enc_periods = cfg.n_enc_layers // len(cfg.enc_pattern)
        p["enc_layers"] = _stack_layer_params(ks[3], cfg, cfg.enc_pattern,
                                              n_enc_periods)
        p["enc_norm"] = L.rmsnorm_init(cfg.d_model)
    return p


PARAM_SPECS_BY_KIND = {
    "attn": L.ATTN_SPECS, "xattn": L.ATTN_SPECS, "mlp": L.MLP_SPECS,
    "moe": L.MOE_SPECS, "mamba": ssm.MAMBA_SPECS, "rwkv": ssm.RWKV_SPECS,
}


def param_pspecs(cfg: ModelConfig) -> Params:
    """PartitionSpec pytree matching init_params (model/tensor parallelism)."""
    def sub_spec(s: SubSpec, params_like):
        table = PARAM_SPECS_BY_KIND[s.kind]
        def pick(path, leaf):
            d = table
            for q in path:
                d = d.get(q.key, {}) if isinstance(d, dict) else {}
            base = d if isinstance(d, P) else P()
            # stacked leading period axis
            return P(*((None,) + tuple(base)))
        return jax.tree_util.tree_map_with_path(pick, params_like)

    zeros = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    specs: Params = {
        # shard d_model: the token gather stays local; the tied unembed
        # contraction reduces over the sharded dim (one psum per loss chunk)
        "embed": P(None, "model"),
        "final_norm": jax.tree.map(lambda _: P(), zeros["final_norm"]),
        "layers": [tuple(sub_spec(s, sp) for s, sp in zip(layer, stacked))
                   for layer, stacked in zip(cfg.pattern, zeros["layers"])],
    }
    if "unembed" in zeros:
        specs["unembed"] = P(None, "model")
    if "enc_layers" in zeros:
        n_enc_p = cfg.n_enc_layers // len(cfg.enc_pattern)
        specs["enc_layers"] = [
            tuple(sub_spec(s, sp) for s, sp in zip(layer, stacked))
            for layer, stacked in zip(cfg.enc_pattern, zeros["enc_layers"])]
        specs["enc_norm"] = jax.tree.map(lambda _: P(), zeros["enc_norm"])
    return specs


# --------------------------------------------------------------------------
# tensor parallelism
# --------------------------------------------------------------------------

# Sublayer kinds whose weights shard across the TP axis (attention heads /
# FFN columns); their output projections contract over the sharded dim and
# meet in the per-token allreduce at the end of ``_apply_sub``. The
# recurrent mixers (mamba/rwkv: cross-channel recurrences, token shift)
# are replicated per rank — redundant compute, zero extra collectives —
# which keeps every arch in the zoo runnable under TP.
TP_SHARDED_KINDS = ("attn", "xattn", "mlp", "moe")


def _tp_kinds(cfg: ModelConfig) -> set:
    pats = cfg.pattern + (cfg.enc_pattern if cfg.enc_pattern else ())
    return {s.kind for layer in pats for s in layer}


def validate_tp(cfg: ModelConfig, tp: int) -> None:
    """Reject infeasible tensor-parallel shardings with a clear error.

    Heads (query AND kv) and FFN columns must divide evenly across the
    ``tp`` ranks; there is no padding/uneven-shard path.
    """
    if tp <= 1:
        return
    kinds = _tp_kinds(cfg)
    bad = []
    if kinds & {"attn", "xattn"}:
        if cfg.n_heads % tp:
            bad.append(f"n_heads={cfg.n_heads}")
        if cfg.n_kv_heads % tp:
            bad.append(f"n_kv_heads={cfg.n_kv_heads}")
    if kinds & {"mlp", "moe"} and cfg.d_ff % tp:
        bad.append(f"d_ff={cfg.d_ff}")
    if bad:
        raise ValueError(
            f"config {cfg.name!r} cannot be tensor-parallel sharded "
            f"tp={tp} ways: " + ", ".join(bad) + f" not divisible by {tp} "
            "(attention heads and FFN columns split evenly across the tp "
            "mesh axis; pick tp dividing all of them)")


def tp_shard_config(cfg: ModelConfig, tp: int) -> ModelConfig:
    """The per-rank local view of ``cfg`` under ``tp``-way tensor
    parallelism: each rank runs the unchanged model code with 1/tp of the
    heads and FFN columns (head_dim pinned so shrinking n_heads does not
    change it). Parameters/caches sliced per :func:`tp_param_specs` /
    :func:`tp_cache_specs` match these shapes exactly."""
    if tp <= 1:
        return cfg
    validate_tp(cfg, tp)
    return dataclasses.replace(
        cfg, n_heads=cfg.n_heads // tp, n_kv_heads=cfg.n_kv_heads // tp,
        d_ff=cfg.d_ff // tp, head_dim=cfg.hdim)


def tp_param_specs(cfg: ModelConfig, axis: str = "tp") -> Params:
    """PartitionSpec pytree over the TP mesh axis, matching init_params.

    Mirrors :func:`param_pspecs` (which marks exactly the shardable dims
    with the GSPMD ``'model'`` name) but renames ``'model'`` -> ``axis``
    for the :data:`TP_SHARDED_KINDS` and replicates everything else:
    embed/unembed stay replicated (the token gather and the tied-unembed
    contraction then need no extra collective on the decode path), as do
    norms, routers, and the recurrent mixers."""
    def rename(base: P) -> P:
        return P(*(axis if n == "model" else None for n in base))

    def sub_spec(s: SubSpec, params_like):
        if s.kind not in TP_SHARDED_KINDS:
            return jax.tree.map(lambda _: P(), params_like)
        table = PARAM_SPECS_BY_KIND[s.kind]
        def pick(path, leaf):
            d = table
            for q in path:
                d = d.get(q.key, {}) if isinstance(d, dict) else {}
            base = rename(d) if isinstance(d, P) else P()
            return P(*((None,) + tuple(base)))   # leading period axis
        return jax.tree_util.tree_map_with_path(pick, params_like)

    zeros = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    specs: Params = {
        "embed": P(),
        "final_norm": jax.tree.map(lambda _: P(), zeros["final_norm"]),
        "layers": [tuple(sub_spec(s, sp) for s, sp in zip(layer, stacked))
                   for layer, stacked in zip(cfg.pattern, zeros["layers"])],
    }
    if "unembed" in zeros:
        specs["unembed"] = P()
    if "enc_layers" in zeros:
        specs["enc_layers"] = [
            tuple(sub_spec(s, sp) for s, sp in zip(layer, stacked))
            for layer, stacked in zip(cfg.enc_pattern, zeros["enc_layers"])]
        specs["enc_norm"] = jax.tree.map(lambda _: P(), zeros["enc_norm"])
    return specs


def tp_cache_specs(cfg: ModelConfig, axis: str = "tp"):
    """PartitionSpec pytree matching :func:`init_cache`: attention K/V
    rings (and their int8 scales) shard the KV-head dim — dim 3 of the
    stacked ``(n_periods, batch, S, KV * dh)`` layout, whose heads lie in
    contiguous blocks of ``dh`` — across the TP axis;
    position counters and recurrent-state caches are replicated (specs
    shorter than rank mean 'remaining dims replicated')."""
    kv = P(None, None, None, axis)
    def sub(kind: str):
        if kind == "attn":
            d = {"k": kv, "v": kv, "pos": P()}
            if cfg.kv_quant:
                d.update(ks=kv, vs=kv)
            return d
        spec = (ssm.mamba_cache_spec(cfg.mamba_cfg(), 1, cfg.compute_dtype)
                if kind == "mamba"
                else ssm.rwkv_cache_spec(cfg.rwkv_cfg(), 1, cfg.compute_dtype))
        return jax.tree.map(lambda _: P(), spec)
    return tuple(sub(k) for k in cache_layer_kinds(cfg))


# --------------------------------------------------------------------------
# MoE implementations
# --------------------------------------------------------------------------

def moe_route(logits, m: MoESettings) -> tuple:
    """``(gates, experts)``, each ``(..., top_k)``, from router logits by
    the configuration's gate rule (see :class:`MoESettings`)."""
    if m.gate == "softmax_topk":
        return jax.lax.top_k(jax.nn.softmax(logits, axis=-1), m.top_k)
    topv, topi = jax.lax.top_k(logits, m.top_k)
    return jax.nn.softmax(topv, axis=-1), topi


def _balance_loss(logits, topi, E: int):
    """Switch-style auxiliary load-balancing loss."""
    onehot = jax.nn.one_hot(topi, E, dtype=jnp.float32)
    me = jnp.mean(jax.nn.softmax(logits, -1), axis=(0, 1))
    ce_frac = jnp.mean(jnp.sum(onehot, axis=2), axis=(0, 1))
    return E * jnp.sum(me * ce_frac)


def _moe_masked(p, x, cfg: ModelConfig):
    """Loop-over-experts with combine masking: simple, compile-safe, E/k x
    FLOP overhead (the §Perf baseline)."""
    m = cfg.moe
    E = m.n_experts
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    gates, topi = moe_route(logits, m)
    onehot = jax.nn.one_hot(topi, E, dtype=jnp.float32)
    comb = jnp.einsum("btk,btke->bte", gates, onehot)

    def expert(carry, ep):
        acc = carry
        w_in, w_out, w_gate, ce = ep
        h = x @ w_in.astype(x.dtype)
        if w_gate is not None:
            h = L.act_fn(cfg.activation)(x @ w_gate.astype(x.dtype)) * h
        else:
            h = L.act_fn(cfg.activation)(h)
        y = h @ w_out.astype(x.dtype)
        return acc + y * ce[..., None].astype(x.dtype), ()

    gate_stack = p.get("w_gate")
    xs = (p["w_in"], p["w_out"],
          gate_stack if gate_stack is not None else p["w_in"],
          jnp.moveaxis(comb, -1, 0))
    if gate_stack is None:
        acc, _ = jax.lax.scan(
            lambda c, s: expert(c, (s[0], s[1], None, s[3])), jnp.zeros_like(x), xs)
    else:
        acc, _ = jax.lax.scan(lambda c, s: expert(c, s), jnp.zeros_like(x), xs)
    return acc, _balance_loss(logits, topi, E)


def _moe_dispatch(p, x, cfg: ModelConfig):
    """Sort-based capacity dispatch (per batch row): exact active-FLOPs.

    Tokens are routed to ``(expert, slot)`` buffers of static capacity
    ``C = ceil(T * k * cf / E)``; overflow drops (Switch-style). The batch dim
    stays data-sharded; expert FFN weights shard their d_ff over 'model'.
    """
    m = cfg.moe
    B, T, D = x.shape
    E, k = m.n_experts, m.top_k
    C = int(np.ceil(T * k * m.capacity_factor / E))
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    gates, topi = moe_route(logits, m)                  # (B,T,k)

    eid = topi.reshape(B, T * k)
    gat = gates.reshape(B, T * k)
    order = jnp.argsort(eid, axis=1, stable=True)       # (B,Tk)
    seid = jnp.take_along_axis(eid, order, axis=1)
    tok = order // k                                    # source token per slot
    # rank within expert group
    starts = jax.vmap(lambda row: jnp.searchsorted(row, jnp.arange(E)))(seid)
    rank = jnp.arange(T * k)[None, :] - jnp.take_along_axis(
        starts, seid, axis=1)
    keep = rank < C
    dest = jnp.where(keep, seid * C + rank, E * C)      # OOB sentinel drops
    xg = jnp.take_along_axis(x, tok[..., None], axis=1)  # (B,Tk,D)
    buf = jnp.zeros((B, E * C + 1, D), x.dtype).at[
        jnp.arange(B)[:, None], dest].set(xg)[:, :-1]
    # batch-sharding pins on the expert buffers keep the fsdp-auto layouts
    # batch-parallel. NOTE: at microbatch sizes > 1/chip these pins trip an
    # XLA SPMD gather-partitioner bug (invalid dynamic-slice); the dry-run
    # uses accum=16 (1 seq/chip/microbatch) where they compile and save ~2x
    # temp memory (see EXPERIMENTS.md §Perf).
    buf = maybe_shard(buf.reshape(B, E, C, D), P(("pod", "data")))

    h = jnp.einsum("becd,edf->becf", buf, p["w_in"].astype(x.dtype))
    if "w_gate" in p:
        g = jnp.einsum("becd,edf->becf", buf, p["w_gate"].astype(x.dtype))
        h = L.act_fn(cfg.activation)(g) * h
    else:
        h = L.act_fn(cfg.activation)(h)
    h = maybe_shard(h, P(("pod", "data"), None, None, "model"))
    y = jnp.einsum("becf,efd->becd", h, p["w_out"].astype(x.dtype))
    y = maybe_shard(y, P(("pod", "data"), None, None, None))
    y = y.reshape(B, E * C, D)
    yg = jnp.take_along_axis(
        jnp.concatenate([y, jnp.zeros((B, 1, D), y.dtype)], axis=1),
        jnp.where(keep, dest, E * C)[..., None], axis=1)  # (B,Tk,D)
    sg = jnp.take_along_axis(gat, order, axis=1)
    contrib = yg * (sg * keep)[..., None].astype(y.dtype)
    out = jnp.zeros_like(x).at[jnp.arange(B)[:, None], tok].add(contrib)
    return out, _balance_loss(logits, topi, E)


# Per held-experts layer and step, the counts the layer reports (see
# :func:`_moe_grouped`), in this order.
MOE_COUNTS = ("experts_touched", "expert_tokens", "expert_tokens_max")

# Rows of (token, expert) pairs the held-experts layer runs at once: a
# 4096-token prompt's 8192 pairs at once hold three (8192, d_ff) bf16
# intermediates, 0.7 GB at Jamba's widths.
MOE_ROW_BLOCK = 2048


def _moe_grouped(p, x, cfg: ModelConfig, valid=None):
    """The held-experts layer: drop-free, and only the held experts' share.

    The router scores all ``n_experts`` and the gate rule picks each
    token's top k. Every (token, expert) pair whose expert this chip holds
    (``cfg.moe.held``) is sorted by expert, and the rows go through one
    grouped matmul per weight (``jax.lax.ragged_dot``; on a TPU XLA's
    ``ragged-dot`` kernels, which name the ops in a device trace): work
    follows the routed pairs, with no capacity, no padding and no token
    dropped. A pair of an expert held elsewhere, or of a token that
    ``valid`` (B, T) marks as not real (a pad, an idle slot), joins no
    group and adds nothing. Each token's result is the gate-weighted sum
    of its held experts' outputs, so the shares of all chips add up to the
    whole layer.

    Returns ``(out, aux, counts)``: ``counts`` int32 ``(3,)`` as
    :data:`MOE_COUNTS` names them: held experts that received a pair,
    pairs routed to held experts, and the most any held expert received.
    """
    m = cfg.moe
    B, T, D = x.shape
    first, C = m.held_range
    k, N = m.top_k, B * T
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    gates, experts = moe_route(logits, m)               # (B, T, k)
    local = experts - first
    mine = (local >= 0) & (local < C)
    if valid is not None:
        mine = mine & valid[..., None]
    group = jnp.where(mine, local, C).reshape(N * k)    # C: no group
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=C + 1)[:C].astype(jnp.int32)
    rows = x.reshape(N, D)[order // k]                  # (N k, D)
    act = L.act_fn(cfg.activation)

    def experts_ffn(rows, sizes):
        h = jax.lax.ragged_dot(rows, p["w_in"].astype(x.dtype), sizes)
        if "w_gate" in p:
            h = act(jax.lax.ragged_dot(rows, p["w_gate"].astype(x.dtype),
                                       sizes)) * h
        else:
            h = act(h)
        return jax.lax.ragged_dot(h, p["w_out"].astype(x.dtype), sizes)

    M, R = N * k, MOE_ROW_BLOCK
    if M > R and M % R == 0:
        # a long prompt's rows in blocks, each with its share of every
        # group: the rows are sorted, so a block touches few experts and
        # each expert's weights are read about once in all
        ends = jnp.cumsum(sizes)
        lo = jnp.arange(0, M, R, dtype=jnp.int32)[:, None]
        per = (jnp.clip(ends[None] - lo, 0, R)
               - jnp.clip(ends[None] - sizes[None] - lo, 0, R))
        y = jax.lax.map(lambda a: experts_ffn(*a),
                        (rows.reshape(M // R, R, D), per)).reshape(M, D)
    else:
        y = experts_ffn(rows, sizes)
    # rows past the last group belong to none: the kernel leaves them be
    y = jnp.where((jnp.arange(M) < sizes.sum())[:, None], y, 0)
    y = jnp.zeros_like(y).at[order].set(y).reshape(N, k, D)
    w = jnp.where(mine, gates, 0.0).reshape(N, k, 1)
    out = jnp.sum(y.astype(jnp.float32) * w, axis=1).astype(x.dtype)
    counts = jnp.stack([jnp.sum(sizes > 0), sizes.sum(),
                        sizes.max()]).astype(jnp.int32)
    return (out.reshape(B, T, D), _balance_loss(logits, experts,
                                                m.n_experts), counts)


# --------------------------------------------------------------------------
# forward pass
# --------------------------------------------------------------------------

def _apply_sub(sp: Params, s: SubSpec, cfg: ModelConfig, x, positions,
               memory, cache, lengths=None, collect_states=False,
               active=None, layer=None):
    """One sublayer; returns (x, aux_loss, new_cache).

    ``lengths`` (B,) activates the serving-prefill contract: the
    recurrent-state mixers leave right-pad tokens out of the carried state
    (bit-unchanged) and checkpoint at the true prompt length (see
    :mod:`repro.models.ssm`); the attention slot path suppresses pad ring
    WRITES and anchors read validity at the true last position — a fresh
    prefill's pads would only land in never-valid slots, but a RESUMED
    chunk's bucket can wrap the ring over live early-prompt K/V.

    ``collect_states`` (speculative verify, decode caches only): the
    recurrent mixers return per-TOKEN cache checkpoints instead of one
    final carry, so the verify step can commit the accepted length's state
    after scoring (see :func:`verify_step`). Attention is unaffected here —
    its rollback is a post-hoc ring restore (:func:`commit_verify_caches`).

    ``active`` / ``layer`` (one-token slot decode): ``cache`` is the whole
    stacked ring and this is its layer ``layer``; the new token is written
    in place, and not at all on rows where ``active`` is False (see
    :func:`repro.models.layers.attention_decode_slots`). The held-experts
    MoE routes no pair of a row where ``active`` is False, nor of a token
    at or past ``lengths``.

    Returns ``(x, aux, new_cache, counts)``; ``counts`` is the held-experts
    layer's (see :data:`MOE_COUNTS`), None for every other sublayer.
    """
    aux = jnp.zeros((), jnp.float32)
    counts = None
    if s.kind == "rwkv":
        x, new_cache = ssm.rwkv_block(sp, x, cfg.rwkv_cfg(), cache,
                                      lengths=lengths,
                                      collect_states=collect_states)
        return x, aux, new_cache, counts
    h = L.rmsnorm(sp["norm"], x)
    new_cache = cache
    if s.kind == "attn":
        acfg = cfg.attn_cfg(s)
        if cache is not None:
            # per-slot caches (pos is (B,), serving engine) take the slot
            # path (a decode tick writes into the whole stacked ring in
            # place); scalar pos keeps the original decode op
            if cache["pos"].ndim:
                pos = cache["pos"] if layer is None else cache["pos"][layer]
                o, kv = L.attention_decode_slots(sp, acfg, h, cache, pos,
                                                 lengths=lengths,
                                                 active=active, layer=layer)
            else:
                o, kv = L.attention_decode(sp, acfg, h, cache, cache["pos"])
            new_cache = {**kv, "pos": cache["pos"]}
        else:
            o = L.attention(sp, acfg, h, positions)
    elif s.kind == "xattn":
        o = L.cross_attention(sp, cfg.attn_cfg(s), h, memory)
    elif s.kind == "mlp":
        o = L.mlp(sp, h, cfg.activation)
    elif s.kind == "moe" and cfg.moe.impl == "grouped":
        valid = None
        if active is not None:
            valid = jnp.broadcast_to(active[:, None], h.shape[:2])
        elif lengths is not None:
            valid = (jnp.arange(h.shape[1], dtype=jnp.int32)[None]
                     < lengths[:, None])
        o, aux, counts = _moe_grouped(sp, h, cfg, valid)
    elif s.kind == "moe":
        fn = _moe_dispatch if cfg.moe.impl == "dispatch" else _moe_masked
        o, aux = fn(sp, h, cfg)
    elif s.kind == "mamba":
        o, new_cache = ssm.mamba_block(sp, h, cfg.mamba_cfg(), cache,
                                       lengths=lengths,
                                       collect_states=collect_states)
    else:
        raise ValueError(s.kind)
    if s.kind in TP_SHARDED_KINDS:
        # Under tensor parallelism these sublayers' output projections
        # contract over a TP-sharded dim, so ``o`` is a partial sum; this is
        # the per-token allreduce. No-op outside a ``L.tp_ctx``. The
        # recurrent mixers (mamba/rwkv) are replicated per rank and skip it.
        o = L.tp_all_reduce(o)
    return x + o, aux, new_cache, counts


def _run_stack(layer_params, pattern, cfg: ModelConfig, x, positions,
               memory=None, caches=None, lengths=None, collect_states=False,
               active=None):
    """Scan over periods; returns (x, aux_sum, new_caches, moe_counts).

    ``moe_counts`` is int32 ``(n_layers_moe, 3)``, one row per held-experts
    MoE layer in depth order (:data:`MOE_COUNTS`), or None when the stack
    has none.

    ``active`` (B,) bool marks a one-token slot decode. Then every per-row
    attention ring (:func:`_is_slot_ring`) rides the scan's carry whole,
    and each layer writes its rows' one new token into it in place, rows
    where ``active`` is False keeping theirs bit-unchanged: no layer's ring
    is sliced out, copied, or stacked again as the scan's output. The other caches
    (recurrent state: small, no sequence axis) still pass through as the
    scan's xs/ys.
    """
    decode = caches is not None
    carried = tuple(active is not None and _is_slot_ring(c)
                    for c in caches) if decode else ()

    # Per-SUBLAYER remat: a multi-layer pattern period (jamba's is 8 layers)
    # would otherwise keep every sublayer's backward intermediates live at
    # once inside the scanned body.
    sub_fn = _apply_sub
    if collect_states:
        sub_fn = functools.partial(_apply_sub, collect_states=True)
    elif cfg.remat and not decode:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat_policy == "dots" else None)
        sub_fn = jax.checkpoint(_apply_sub, prevent_cse=False, policy=policy,
                                static_argnums=(1, 2))

    def body(carry, xs):
        h, aux, rings = carry
        params_slice, cache_slice, i = xs
        rings = list(rings)
        new_cs, counts = [], []
        ci = 0
        for pos, layer in enumerate(pattern):
            for si, s in enumerate(layer):
                has_cache = decode and s.kind in ("attn", "mamba", "rwkv")
                c, kw = None, {}
                if has_cache and carried[ci]:
                    c, kw = rings[ci], {"active": active, "layer": i}
                elif has_cache:
                    c = cache_slice[ci]
                elif s.kind == "moe":
                    kw = {"active": active}
                h, a, nc, n = sub_fn(params_slice[pos][si], s, cfg, h,
                                     positions, memory, c, lengths, **kw)
                aux = aux + a
                if n is not None:
                    counts.append(n)
                if has_cache:
                    if carried[ci]:
                        rings[ci] = nc
                    new_cs.append(None if carried[ci] else nc)
                    ci += 1
        counts = jnp.stack(counts) if counts else None
        return (h, aux, tuple(rings)), (tuple(new_cs) if decode else (),
                                        counts)

    def per_layer(counts):
        # (n_periods, per period, 3) -> depth order
        return None if counts is None else counts.reshape(-1, 3)

    zero = jnp.zeros((), jnp.float32)
    if not decode:
        fwd_body = lambda c, lp: body(c, (lp, None, None))
        (x, aux, _), (_, counts) = jax.lax.scan(fwd_body, (x, zero, ()),
                                                layer_params)
        return x, aux, None, per_layer(counts)
    rings = tuple(c if r else None for c, r in zip(caches, carried))
    through = tuple(None if r else c for c, r in zip(caches, carried))
    n = jax.tree.leaves(layer_params)[0].shape[0]
    (x, aux, rings), (ys, counts) = jax.lax.scan(
        body, (x, zero, rings),
        (layer_params, through, jnp.arange(n, dtype=jnp.int32)))
    new_caches = tuple(r if c else y for r, y, c in zip(rings, ys, carried))
    return x, aux, new_caches, per_layer(counts)


def _is_slot_ring(cache) -> bool:
    """A stacked attention ring with a position per batch row: the serving
    engine's slots, as :func:`init_cache` builds them with ``per_slot``."""
    return isinstance(cache, dict) and "pos" in cache \
        and cache["pos"].ndim == 2


def embed_inputs(params, cfg: ModelConfig, inputs) -> tuple:
    if cfg.input_mode == "embeds":
        x = inputs["embeds"].astype(cfg.compute_dtype)
    else:
        x = params["embed"].astype(cfg.compute_dtype)[inputs["tokens"]]
    B, T = x.shape[:2]
    positions = inputs.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None],
                                     (B, T))
    return x, positions


def forward(params, cfg: ModelConfig, inputs) -> tuple:
    """Full-sequence forward -> (final hidden states, aux loss)."""
    x, positions = embed_inputs(params, cfg, inputs)
    # NOTE: no constraint on the residual stream — it propagates into the
    # MoE dispatch gather whose partitioning is fragile at high device counts
    # and measurably worsens temp liveness; per-sublayer pins suffice.
    memory = None
    if cfg.n_enc_layers:
        src = inputs["src_embeds"].astype(cfg.compute_dtype)
        sp = jnp.broadcast_to(
            jnp.arange(src.shape[1], dtype=jnp.int32)[None], src.shape[:2])
        memory, _, _, _ = _run_stack(params["enc_layers"], cfg.enc_pattern,
                                     cfg, src, sp)
        memory = L.rmsnorm(params["enc_norm"], memory)
    x, aux, _, _ = _run_stack(params["layers"], cfg.pattern, cfg, x,
                              positions, memory)
    return L.rmsnorm(params["final_norm"], x), aux


def unembed(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    w = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    return x @ w.astype(x.dtype)


def chunked_ce_loss(params, cfg: ModelConfig, x, labels,
                    chunk: int = 512) -> jax.Array:
    """Cross-entropy without materializing (B,T,V) at once: scan over
    sequence chunks, vocab-sharded logits inside."""
    B, T, D = x.shape
    chunk = min(chunk, T)
    n = T // chunk
    rem = T - n * chunk

    def one(xc, yc):
        logits = unembed(params, cfg, xc).astype(jnp.float32)
        logits = maybe_shard(logits, P(("pod", "data"), None, "model"))
        lse = jax.nn.logsumexp(logits, axis=-1)
        # label pick via iota-compare (a gather over the vocab-sharded dim
        # trips XLA's SPMD gather partitioner at high device counts)
        oh = jnp.arange(logits.shape[-1], dtype=yc.dtype) == yc[..., None]
        ll = jnp.sum(jnp.where(oh, logits, 0.0), axis=-1)
        return jnp.sum(lse - ll)

    if n:
        xm = x[:, :n * chunk].reshape(B, n, chunk, D)
        ym = labels[:, :n * chunk].reshape(B, n, chunk)
        tot, _ = jax.lax.scan(
            lambda acc, s: (acc + one(s[0], s[1]), ()),
            jnp.zeros((), jnp.float32),
            (jnp.moveaxis(xm, 1, 0), jnp.moveaxis(ym, 1, 0)))
    else:
        tot = jnp.zeros((), jnp.float32)
    if rem:
        tot = tot + one(x[:, n * chunk:], labels[:, n * chunk:])
    return tot / (B * T)


def loss_fn(params, cfg: ModelConfig, inputs, aux_weight: float = 0.01):
    x, aux = forward(params, cfg, inputs)
    ce = chunked_ce_loss(params, cfg, x, inputs["labels"])
    return ce + aux_weight * aux, {"ce": ce, "aux": aux}


# --------------------------------------------------------------------------
# decode (serving)
# --------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               kv_dtype=jnp.bfloat16, abstract: bool = False,
               per_slot: bool = False, ring_slack: int = 0):
    """Stacked (n_periods, ...) cache pytree matching the scan layout.

    With ``per_slot=True`` the attention position counters are per batch row
    (shape ``(batch,)`` instead of scalar): each row is an independently
    paced KV-cache *slot* for the continuous-batching serving engine, and
    decode dispatches to the slot path.

    ``ring_slack`` widens window/chunk-BOUNDED rings (never full-attention
    ones) by that many slots. One-token decode never needs it: writing
    position ``p`` overwrites ``p - W``, exactly one past the window. A
    T-token speculative verify call is different — its later writes land up
    to T-1 slots further around the ring, overwriting window positions the
    call's EARLIEST queries still read. With ``ring_slack >= T - 1`` every
    in-call write lands on a slot whose old position is already outside
    every in-call query's window, so the one-pass verify is bit-identical
    to sequential decode on bounded rings too. The validity masks derive
    windows from config, not ring size, so a wider ring changes no
    read/write semantics — only how much history physically survives.
    """
    KV, dh = cfg.n_kv_heads, cfg.hdim
    pos_shape = (batch,) if per_slot else ()
    mk = (lambda s, d: jax.ShapeDtypeStruct(s, d)) if abstract else \
         (lambda s, d: jnp.zeros(s, d))

    def sub_cache(s: SubSpec):
        if s.kind == "attn":
            S = max_len
            if s.sliding_window is not None:
                S = min(S, s.sliding_window + ring_slack)
            if s.chunk_size is not None:
                S = min(S, s.chunk_size + ring_slack)
            # a token's heads side by side: (S, KV * dh) is a layout the
            # compiler stores unpadded and writes one token into in place
            if cfg.kv_quant:
                return {"k": mk((batch, S, KV * dh), jnp.int8),
                        "v": mk((batch, S, KV * dh), jnp.int8),
                        "ks": mk((batch, S, KV), jnp.float32),
                        "vs": mk((batch, S, KV), jnp.float32),
                        "pos": mk(pos_shape, jnp.int32)}
            return {"k": mk((batch, S, KV * dh), kv_dtype),
                    "v": mk((batch, S, KV * dh), kv_dtype),
                    "pos": mk(pos_shape, jnp.int32)}
        if s.kind == "mamba":
            spec = ssm.mamba_cache_spec(cfg.mamba_cfg(), batch,
                                        cfg.compute_dtype)
            return spec if abstract else jax.tree.map(
                lambda sd: jnp.zeros(sd.shape, sd.dtype), spec)
        if s.kind == "rwkv":
            spec = ssm.rwkv_cache_spec(cfg.rwkv_cfg(), batch,
                                       cfg.compute_dtype)
            return spec if abstract else jax.tree.map(
                lambda sd: jnp.zeros(sd.shape, sd.dtype), spec)
        return None

    def stack(tree):
        if tree is None:
            return None
        return jax.tree.map(
            lambda l: (jax.ShapeDtypeStruct((cfg.n_periods,) + l.shape, l.dtype)
                       if abstract else jnp.tile(l[None], (cfg.n_periods,)
                                                 + (1,) * l.ndim)), tree)

    caches = []
    for layer in cfg.pattern:
        for s in layer:
            c = stack(sub_cache(s))
            if c is not None:
                caches.append(c)
    return tuple(caches)


def moe_counted_layers(cfg: ModelConfig) -> int:
    """Held-experts MoE layers in the stack: the rows of its counts."""
    if cfg.moe is None or cfg.moe.impl != "grouped":
        return 0
    return cfg.n_periods * sum(s.kind == "moe" for layer in cfg.pattern
                               for s in layer)


def cache_layer_kinds(cfg: ModelConfig) -> tuple:
    """Kind of each entry of the caches tuple, in cache order.

    One entry per cached sublayer per pattern period: ``"attn"`` (ring KV +
    per-row ``pos``), ``"mamba"`` (conv window + selective-scan state) or
    ``"rwkv"`` (token-shift carries + WKV state). The serving paths dispatch
    on this instead of assuming attention-only caches.
    """
    return tuple(s.kind for layer in cfg.pattern for s in layer
                 if s.kind in ("attn", "mamba", "rwkv"))


def merge_cache_rows(new_caches, old_caches, active):
    """Row-wise cache merge: rows where ``active`` is True take ``new``,
    every other row keeps ``old`` bit-unchanged.

    Works on the stacked (n_periods, batch, ...) layout for every cache
    kind — attention K/V rings, SSM states, token-shift carries — which is
    what lets one jitted step serve any busy/free slot mix: inactive rows
    may compute garbage, but none of it survives the merge.
    """
    return tuple(_merge_rows(nc, oc, active)
                 for nc, oc in zip(new_caches, old_caches))


def _merge_rows(new_cache, old_cache, active):
    """:func:`merge_cache_rows` for one entry of the caches tuple."""
    def merge(new, old):
        if new.ndim < 2:
            return new
        m = active.reshape((1, -1) + (1,) * (new.ndim - 2))
        return jnp.where(m, new, old)

    return jax.tree.map(merge, new_cache, old_cache)


def decode_step(params, cfg: ModelConfig, inputs, caches, memory=None,
                active=None, counts: bool = False):
    """One-token decode. inputs: {'tokens': (B,1)} or {'embeds': (B,1,D)},
    plus optional 'positions'. Returns (logits (B,V), new_caches), and with
    ``counts`` the held-experts layers' counts as a third value (see
    :func:`_run_stack`).

    ``active`` (per-slot caches only): (B,) bool — rows whose slot currently
    holds an in-flight request. Inactive rows still compute (one jitted step
    serves any slot mix) but their cache rows keep their old values and
    their position does NOT advance. Attention rings take each active row's
    one new token in place and nothing on inactive rows (see
    :func:`_run_stack`); recurrent-state rows are merged back to their old
    values, which is what keeps a free/prefilling slot's carried state
    intact across decode ticks.
    """
    x, _ = embed_inputs(params, cfg, inputs)
    x, _, new_caches, n = _run_stack(params["layers"], cfg.pattern, cfg, x,
                                     None, memory, caches, active=active)
    x = L.rmsnorm(params["final_norm"], x)
    logits = unembed(params, cfg, x)[:, -1]
    if active is not None:
        # the rings took only the active rows' writes inside the stack
        new_caches = tuple(
            nc if _is_slot_ring(oc) else _merge_rows(nc, oc, active)
            for nc, oc in zip(new_caches, caches))
    out = (logits.astype(jnp.float32),
           advance_pos_stacked(new_caches, active))
    return out + (n,) if counts else out


def advance_pos_stacked(caches, active=None):
    """Scan outputs stack new caches over periods already; bump positions."""
    return advance_pos(caches, active)


def advance_pos(caches, active=None):
    """Increment attention cache positions post-step: by one everywhere, or
    (per-slot caches) only on rows where ``active`` is True."""
    step = 1 if active is None else active.astype(jnp.int32)

    def bump(c):
        if isinstance(c, dict) and "pos" in c:
            return {**c, "pos": c["pos"] + step}
        return c
    return tuple(bump(c) for c in caches)


# --------------------------------------------------------------------------
# KV-cache slot ops (continuous-batching serving)
# --------------------------------------------------------------------------

def prefill_call_bound(cfg: ModelConfig, max_len: int) -> int:
    """Longest single slot prefill/verify CALL the cache geometry allows:
    every attention sublayer must fit the call's tokens in its (possibly
    window/chunk-bounded) ring, or one call would write a ring slot twice.
    The single source of this rule — the engine's per-call chunk bound and
    the draft-model drafter's prefill chunking both derive from it, so
    they can never disagree."""
    s_min = max_len
    for layer in cfg.pattern:
        for s in layer:
            if s.kind == "attn":
                if s.sliding_window is not None:
                    s_min = min(s_min, s.sliding_window)
                if s.chunk_size is not None:
                    s_min = min(s_min, s.chunk_size)
    return s_min


def supports_slot_serving(cfg: ModelConfig) -> bool:
    """Whether the continuous-batching engine can drive this architecture.

    Any decoder-only token-prompt architecture qualifies — attention, MLP,
    MoE, and the recurrent-state mixers (mamba/rwkv). Attention masks pad
    positions out of every future read; SSM prefill masks the state update
    past the true prompt length and checkpoints the carry there
    (``lengths``-aware paths in :mod:`repro.models.ssm`), so bucketed
    right-padding never leaks into either cache kind. Only the stub-embed
    and encoder-decoder frontends stay out: they have no token prompts to
    prefill.
    """
    kinds = {s.kind for layer in cfg.pattern for s in layer}
    return (cfg.input_mode == "tokens" and not cfg.n_enc_layers
            and kinds <= {"attn", "mlp", "moe", "mamba", "rwkv"})


def reset_cache_slots(caches, free_mask):
    """Free the cache rows where ``free_mask`` (B,) is True.

    Per-slot caches only. Resetting a row's ``pos`` to zero is what
    invalidates it — the ring-validity mask derives every readable position
    from ``pos``, so stale K/V behind a zeroed counter can never be attended
    again and the slot is reusable without touching the jitted step
    (admission overwrites ring slots ``0..len-1`` on the next prefill).
    Non-``pos`` leaves are zeroed too so a freed slot holds no request data.
    """
    def fix(c):
        def leaf(v):
            if v.ndim < 2:  # stacked scalar counters never reach here
                return v
            m = free_mask.reshape((1, -1) + (1,) * (v.ndim - 2))
            return jnp.where(m, jnp.zeros_like(v), v)
        if isinstance(c, dict) and "pos" in c:
            return {**c, "pos": jnp.where(free_mask[None], 0, c["pos"]),
                    **{k: leaf(c[k]) for k in c if k != "pos"}}
        return jax.tree.map(leaf, c)
    return tuple(fix(c) for c in caches)


def extract_cache_row(caches, slot):
    """Copy one slot's row out of per-slot caches as a batch-of-1 pytree.

    The row is the slot's COMPLETE serving state — attention K/V rings with
    their per-row ``pos``, int8 K/V scales under ``kv_quant``, and the
    recurrent carries (mamba conv window + scan state, rwkv token-shift +
    WKV state) the ``lengths=`` prefill paths checkpoint at the true token
    count. After prefilling tokens ``t[0:p]`` into the slot, the row is a
    pure function of exactly those tokens (pads never leak — see
    :func:`prefill_step`), which is the invariant that makes rows sharable
    ACROSS requests: the cross-request prefix cache
    (:mod:`repro.serving.prefix`) snapshots rows at prefill-chunk-grid
    boundaries and :func:`adopt_prefix` copies them into a later request's
    slot. Also the resume-slice half of chunked prefill
    (:mod:`repro.launch.step_fns`)."""
    return jax.tree.map(lambda leaf: L.row_slice(leaf, slot), caches)


def adopt_prefix(caches, row, slot):
    """Splice a batch-of-1 cache ``row`` into ``slot`` — copy-on-admit.

    The inverse of :func:`extract_cache_row` and the row-targeted sibling
    of :func:`merge_cache_rows` (which merges by boolean mask instead of
    slot index): every other slot's in-flight state passes through
    bit-unchanged. Used twice: the chunked-prefill splice that writes a
    finished prompt-chunk row back into its slot, and cross-request prefix
    adoption, where a trie-cached row (state after ``p`` shared prompt
    tokens, ``pos == p``) lands in a fresh slot so admission resumes at the
    first divergent chunk instead of token 0. Because the row is a pure
    function of the tokens that produced it, the adopting request's
    continued prefill and decode are bit-identical to a cold prefill of the
    same tokens — on full-attention rings and (boundary-aligned) bounded
    SWA/chunked rings alike."""
    return jax.tree.map(lambda full, r: L.row_splice(full, r, slot),
                        caches, row)


def prefill_step(params, cfg: ModelConfig, inputs, caches, lengths, active,
                 resume: bool = False, counts: bool = False):
    """Prefill prompts into per-slot caches (continuous-batching admission).

    inputs: {'tokens': (B, Tc)} right-padded prompts; lengths: (B,) int32
    true prompt lengths (<= Tc); active: (B,) bool — rows being admitted
    this call. With ``resume=False`` active rows restart from scratch:
    attention positions zero (ring slots ``0..len-1`` take the prompt K/V),
    recurrent-state caches zeroed. With ``resume=True`` (chunked admission,
    chunks 2..n of a long prompt) active rows CONTINUE from their current
    cache — attention writes ring slots ``pos..pos+len-1``, SSM carries
    advance from the checkpointed state — and ``pos`` grows by ``lengths``.
    Either way inactive rows' caches pass through bit-unchanged: in-flight
    decode state in other slots is never disturbed, which is what lets
    prefill interleave with decode.
    Returns (logits (B, V) at each row's LAST real token of this chunk —
    for the final chunk, the first generated token's distribution — and the
    merged caches).

    Pad positions ``t >= len`` never leak: attention writes them to ring
    slots the validity mask keeps unreadable (their ``ki`` exceeds the
    row's ``pos``), and the SSM paths mask the state update past ``len``
    (``lengths``-aware :mod:`repro.models.ssm`); the held-experts MoE
    routes no pad. Capacity-dispatch MoE rows may drop differently per
    bucket length, so admission must bucket and chunk by prompt length
    deterministically. With ``counts`` the held-experts layers' counts
    come back as a third value.
    """
    if resume:
        start = caches
    else:
        # run every row from scratch; rows not being admitted compute
        # garbage that the merge below discards. Attention needs only
        # pos=0 (ring overwrite + validity hide stale K/V); recurrent
        # caches are the state itself and must be zeroed.
        start = tuple(
            ({**c, "pos": jnp.zeros_like(c["pos"])}
             if isinstance(c, dict) and "pos" in c
             else jax.tree.map(jnp.zeros_like, c))
            for c in caches)
    x, _ = embed_inputs(params, cfg, inputs)
    x, _, new_caches, n = _run_stack(params["layers"], cfg.pattern, cfg, x,
                                     None, None, start, lengths=lengths)
    x = L.rmsnorm(params["final_norm"], x)
    idx = jnp.clip(lengths - 1, 0)[:, None, None]
    last = jnp.take_along_axis(x, jnp.broadcast_to(
        idx, (x.shape[0], 1, x.shape[2])), axis=1)
    logits = unembed(params, cfg, last)[:, 0].astype(jnp.float32)

    merged = merge_cache_rows(new_caches, caches, active)
    # _run_stack leaves attention ``pos`` at its start value; set admitted
    # rows to their post-chunk token counts explicitly
    out = []
    for new_c, old_c in zip(merged, caches):
        if isinstance(new_c, dict) and "pos" in new_c:
            base = old_c["pos"] if resume else jnp.zeros_like(old_c["pos"])
            pos = jnp.where(active[None], base + lengths[None], old_c["pos"])
            out.append({**new_c, "pos": pos})
        else:
            out.append(new_c)
    return (logits, tuple(out)) + ((n,) if counts else ())


# --------------------------------------------------------------------------
# speculative decoding: one-pass verify + rollback-safe commit
# --------------------------------------------------------------------------

def supports_speculation(cfg: ModelConfig) -> bool:
    """Whether the speculative-decoding verify step can drive this arch.

    Requires slot serving plus a rollback rule for every cached sublayer
    kind: attention rings roll back by restoring rejected-slot writes and
    rewinding ``pos`` (:func:`commit_verify_caches`); mamba/rwkv expose the
    exact token recurrence with per-token state collection, so the carry at
    the accepted length is available after scoring. All current kinds
    qualify — the gate exists so a future cache kind without an exact
    per-token checkpoint fails loudly instead of committing rejected state.
    """
    return (supports_slot_serving(cfg)
            and set(cache_layer_kinds(cfg)) <= {"attn", "mamba", "rwkv"})


def verify_forward(params, cfg: ModelConfig, inputs, caches, lengths=None,
                   counts: bool = False):
    """Score a verify call's tokens in ONE pass against per-slot caches.

    inputs: {'tokens': (B, T)} — per row, token 0 is the request's last
    emitted (not yet cached) token, tokens ``1..lengths[b]-1`` its draft
    proposals, and the rest buffer padding (every row shares the compiled
    width T). ``lengths`` (B,) int32 routes the padding through the SAME
    pad-suppression machinery bucketed prefill uses: pad columns write
    nothing to the attention rings — without this a row near its ring
    capacity would let pad writes wrap over live prompt K/V and corrupt
    the REAL columns' logits mid-call, silently breaking bit-identity —
    and leave the recurrent per-token checkpoints frozen past the real
    drafts. Returns (logits (B, T, V) float32 — position ``t`` scores the
    model's next-token distribution AFTER consuming input token ``t`` —
    and the RAW caches: attention rings with the real columns' writes
    applied (positions not yet advanced) and recurrent leaves carrying a
    per-token checkpoint axis). The raw caches are NOT safe to serve
    from — they contain speculative writes — and must go through
    :func:`commit_verify_caches` with the accepted lengths. With
    ``counts`` the held-experts layers' counts come back as a third value.
    """
    x, _ = embed_inputs(params, cfg, inputs)
    x, _, raw, n = _run_stack(params["layers"], cfg.pattern, cfg, x, None,
                              None, caches, lengths=lengths,
                              collect_states=True)
    x = L.rmsnorm(params["final_norm"], x)
    out = (unembed(params, cfg, x).astype(jnp.float32), raw)
    return out + (n,) if counts else out


def commit_verify_caches(raw_caches, old_caches, n_call: int, accept,
                         active):
    """Commit exactly the accepted prefix of a verify call, per slot.

    ``accept`` (B,) int32 in ``[1, n_call]``: how many of this call's input
    tokens each row keeps (the matched drafts plus the always-committed
    position-0 token). Attention rings: ring slots written by rejected
    tokens are restored bit-exact from ``old_caches``
    (:func:`repro.models.layers.ring_restore_mask` — no live ring write can
    survive a rejection) and ``pos`` advances by ``accept`` only; recurrent
    leaves gather the per-token checkpoint at ``accept - 1``, i.e. the
    carry as produced by the exact token recurrences at the accepted
    length. Rows where ``active`` is False keep their old caches
    bit-unchanged (same contract as :func:`decode_step`).
    """
    committed = []
    for new_c, old_c in zip(raw_caches, old_caches):
        if isinstance(new_c, dict) and "pos" in new_c:
            S = old_c["k"].shape[2]
            restore = L.ring_restore_mask(old_c["pos"], S, n_call, accept)

            def fix(nv, ov, _m=restore):
                m = _m.reshape(_m.shape + (1,) * (nv.ndim - _m.ndim))
                return jnp.where(m, ov, nv)

            c = {k: fix(new_c[k], old_c[k]) for k in new_c if k != "pos"}
            c["pos"] = old_c["pos"] + jnp.where(active[None], accept[None], 0)
            committed.append(c)
        else:
            # recurrent leaves: (n_periods, B, T, ...) -> entry accept-1
            def gather(nv):
                idx = jnp.clip(accept - 1, 0).reshape(
                    (1, -1) + (1,) * (nv.ndim - 2)).astype(jnp.int32)
                shape = nv.shape[:2] + (1,) + nv.shape[3:]
                took = jnp.take_along_axis(
                    nv, jnp.broadcast_to(idx, shape), axis=2)
                return took[:, :, 0]

            committed.append(jax.tree.map(gather, new_c))
    return merge_cache_rows(tuple(committed), old_caches, active)


def verify_accept(pred, tokens, n_draft):
    """Longest-matching-prefix acceptance for one verify call.

    pred: (B, T) int32 — the committed sampler's (or argmax's) token after
    each input position; tokens: (B, T) the call inputs (token 0 = last
    emitted, 1..T-1 = drafts); n_draft: (B,) how many drafts are real.
    Returns (emitted (B, T) int32, accept (B,) int32): row ``b`` emits
    ``emitted[b, :accept[b]]`` — the matched drafts followed by the model's
    own token at the first mismatch (the correction, or the bonus token
    when every draft matched) — and commits ``accept[b]`` call tokens to
    cache. ``accept == 1 + matched`` always, so a row with no drafts
    degenerates to exactly one plain decode step.
    """
    B, T = tokens.shape
    k = T - 1
    ok = (pred[:, :-1] == tokens[:, 1:]) & \
        (jnp.arange(k, dtype=jnp.int32)[None] < n_draft[:, None])
    n_match = (jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(axis=1)
               if k else jnp.zeros((B,), jnp.int32))
    accept = (n_match + 1).astype(jnp.int32)
    corr = jnp.take_along_axis(pred, n_match[:, None], axis=1)     # (B, 1)
    drafts = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)    # (B, T)
    idx = jnp.arange(T, dtype=jnp.int32)[None]
    emitted = jnp.where(idx < n_match[:, None], drafts,
                        jnp.where(idx == n_match[:, None], corr, -1))
    return emitted.astype(jnp.int32), accept
