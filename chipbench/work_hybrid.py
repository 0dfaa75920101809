"""The work a hybrid Mamba + attention + held-experts step needs, from the
configuration's shapes and the step's own held-experts counts.

As in ``work.py`` the counts do not depend on how the program does the
work. A decode step needs every weight outside the experts once, the
embedding rows of its tokens, each expert that a token chose once (its
``experts_touched``), the K/V of each active slot's live positions in the
attention layers, and each active slot's Mamba state read and written
once. The experts' own share: the weights of the experts touched, and each
routed (token, expert) pair's row read and its result written; ``6 D F``
FLOPs a pair (the gate, input and output products).

The counts per step are the engine's (``transformer.MOE_COUNTS``): an int
array with one row per held-experts layer, columns ``experts_touched``,
``expert_tokens``, ``expert_tokens_max``.

``EXPERT_OPS`` names the device ops of the held-experts layer in a trace:
XLA lowers its grouped matmuls (``jax.lax.ragged_dot``) on a TPU to
kernels named ``ragged-dot-*``.
"""

from __future__ import annotations

import importlib

import numpy as np

EXPERT_OPS = "ragged-dot"
_EXPERT_LEAVES = ("w_in", "w_gate", "w_out")


def _spec(config: dict) -> dict:
    ref = importlib.import_module(
        f"chipbench.reference.{config['reference']}")
    return ref.spec(config)


def _nbytes(shape, dtype: str) -> int:
    return int(np.prod(shape)) * np.dtype(dtype).itemsize


def _is_expert(path: str, shape) -> bool:
    return path.rsplit("/", 1)[-1] in _EXPERT_LEAVES and len(shape) == 4


def expert_bytes(config: dict) -> float:
    """One held expert's weights, every layer's expert the same size."""
    for path, (shape, dtype) in _spec(config).items():
        if _is_expert(path, shape):
            return 3 * _nbytes(shape[-2:], dtype)
    raise ValueError("the configuration has no held experts")


def moe_work(config: dict, counts) -> tuple:
    """(bytes, FLOPs) the held-experts layers of one step need, from its
    counts ``(layers, 3)``."""
    counts = np.asarray(counts)
    D, F = config["hidden_size"], config["intermediate_size"]
    touched, pairs = counts[:, 0].sum(), counts[:, 1].sum()
    act = np.dtype(config["torch_dtype"]).itemsize
    return (float(touched * expert_bytes(config) + pairs * 2 * D * act),
            float(6 * D * F * pairs))


def non_expert_bytes(config: dict, n_tokens: int) -> float:
    """Every weight outside the experts once, of the embedding only the
    rows of ``n_tokens`` tokens."""
    total = 0
    for path, (shape, dtype) in _spec(config).items():
        if path == "embed":
            total += n_tokens * _nbytes(shape[1:], dtype)
        elif not _is_expert(path, shape):
            total += _nbytes(shape, dtype)
    return float(total)


def _layers(config: dict, kind: str) -> int:
    ref = importlib.import_module(
        f"chipbench.reference.{config['reference']}")
    s = ref.sizes(config)
    return sum(ref.layer_kind(s, i)[0] == kind for i in range(s["L"]))


def kv_bytes(config: dict, ctx: int) -> float:
    """K and V of ``ctx`` live positions of one slot, every attention
    layer, in the served dtype."""
    D, H = config["hidden_size"], config["num_attention_heads"]
    kv = config["num_key_value_heads"] * (D // H)
    act = np.dtype(config["torch_dtype"]).itemsize
    return float(2 * _layers(config, "attn") * ctx * kv * act)


def mamba_state_bytes(config: dict) -> float:
    """One slot's Mamba state, every Mamba layer: the conv window in the
    served dtype and the scan state in float32."""
    Di = config["mamba_expand"] * config["hidden_size"]
    act = np.dtype(config["torch_dtype"]).itemsize
    one = ((config["mamba_d_conv"] - 1) * Di * act
           + Di * config["mamba_d_state"] * 4)
    return float(_layers(config, "mamba") * one)


def decode_bytes(config: dict, ctxs, counts) -> float:
    """What one decode step must move: the weights outside the experts
    and the embedding rows of its tokens, the experts it touched with
    their rows, the live K/V of each active slot, and each active slot's
    Mamba state read and written."""
    return (non_expert_bytes(config, len(ctxs))
            + moe_work(config, counts)[0]
            + sum(kv_bytes(config, c) for c in ctxs)
            + 2 * len(ctxs) * mamba_state_bytes(config))
