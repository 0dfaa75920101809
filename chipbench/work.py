"""The work an algorithm needs, computed from shapes.

These counts do not depend on how the program does the work: a decode step
needs every weight once and the K/V of each active slot's live positions,
however much of the ring the program reads; a prompt needs its real tokens,
however far the program pads it. A program that stops wasting work then
raises its share of the roofline rather than moving the yardstick.

The per-token FLOP arithmetic for a dense attention + gated-MLP layer is
the one ``analysis/flops.py`` uses: projections ``2 D (H + 2 KV) dh + 2 H dh
D``, attention ``4 ctx H dh``, MLP ``6 D F``, logits ``2 D V``.
"""

from __future__ import annotations


def _s(config: dict) -> tuple:
    D = config["hidden_size"]
    H = config["num_attention_heads"]
    return (config["num_hidden_layers"], D, H, config["num_key_value_heads"],
            D // H, config["intermediate_size"], config["vocab_size"])


def layer_matmul_params(config: dict) -> int:
    L, D, H, KV, dh, F, V = _s(config)
    return D * (H + 2 * KV) * dh + H * dh * D + 3 * D * F


def token_flops(config: dict, ctx: int) -> float:
    """Forward FLOPs of one token that attends to ``ctx`` positions,
    logits included."""
    L, D, H, KV, dh, F, V = _s(config)
    return L * (2 * layer_matmul_params(config) + 4 * ctx * H * dh) \
        + 2 * D * V


def decode_flops(config: dict, ctxs) -> float:
    """One decode step over the active slots, each at its live context."""
    return sum(token_flops(config, c) for c in ctxs)


def prefill_flops(config: dict, n: int) -> float:
    """A prompt of ``n`` real tokens, causal: token ``t`` attends to
    ``t + 1`` positions; logits at the last token only."""
    L, D, H, KV, dh, F, V = _s(config)
    attn = 4 * H * dh * n * (n + 1) / 2
    return L * (2 * layer_matmul_params(config) * n + attn) + 2 * D * V


def weight_bytes(config: dict, itemsize: int = 2) -> float:
    """Every weight a decode step reads once: the layers and the tied
    embedding (as the logits' matrix); norm scales at float32."""
    L, D, H, KV, dh, F, V = _s(config)
    return itemsize * (L * layer_matmul_params(config) + V * D) \
        + 4 * D * (2 * L + 1)


def kv_bytes(config: dict, ctx: int, itemsize: int = 2) -> float:
    """K and V of ``ctx`` live positions of one slot, every layer."""
    L, D, H, KV, dh, F, V = _s(config)
    return 2 * L * ctx * KV * dh * itemsize


def decode_bytes(config: dict, ctxs) -> float:
    """What one decode step must move: every weight once, and the K/V of
    each active slot's live positions (each new position written once)."""
    return weight_bytes(config) + sum(kv_bytes(config, c) for c in ctxs)


def allreduce_bus_bytes(nbytes: float, p: int) -> float:
    """Bytes one device must send (and receive) in an allreduce of
    ``nbytes`` over ``p`` devices: ``2 (p - 1) / p`` of the payload."""
    return nbytes * 2 * (p - 1) / p
