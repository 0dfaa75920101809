"""Jamba [arXiv:2403.19887, arXiv:2408.12570; hf] — hybrid Mamba+attention
1:7, MoE. The same block serves Jamba v0.1 and the Jamba 1.5/1.6/1.7/2 Mini
family (52B total, 12B active; ``ai21labs/AI21-Jamba2-Mini``).

32L, d_model=4096, 32 heads, kv=8, d_ff=14336, vocab=65536, MoE 16e top-2 on
every other layer. Period-8 pattern with attention at index 4 (1 attention per
8 layers, no positional embedding), Mamba-1 elsewhere (d_state 16, d_conv 4,
expand 2, dt_rank 256, RMS norms on dt/B/C). The router's gates are a
softmax over all 16 experts, then the top 2, not renormalised; the experts
run through the drop-free held-experts layer. O(1)-state Mamba layers + 4
attention layers make long_500k decode tractable.
"""

from repro.configs.base import ParallelConfig
from repro.models.transformer import (ModelConfig, MoESettings, SubSpec)


def _pattern():
    layers = []
    for idx in range(8):
        mixer = SubSpec("attn", use_rope=False) if idx == 4 else "mamba"
        ffn = "moe" if idx % 2 == 1 else "mlp"
        layers.append((mixer, ffn))
    return tuple(layers)


def moe(n_experts: int, held=None) -> MoESettings:
    """Jamba's routing: top-2, softmax over all experts before the top-k."""
    return MoESettings(n_experts=n_experts, top_k=2, impl="grouped",
                       gate="softmax_topk", held=held)


def config() -> ModelConfig:
    return ModelConfig(
        name="jamba-v0.1-52b",
        n_layers=32, d_model=4096, n_heads=32, n_kv_heads=8,
        d_ff=14336, vocab_size=65536,
        pattern=_pattern(),
        moe=moe(16),
        activation="silu", gated_mlp=True, tie_embeddings=False,
        mamba_d_state=16,
    )


def reduced() -> ModelConfig:
    return ModelConfig(
        name="jamba-reduced",
        n_layers=8, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=96, vocab_size=512,
        pattern=_pattern(),
        moe=moe(4),
        activation="silu", gated_mlp=True, tie_embeddings=False,
        mamba_d_state=8, remat=False,
    )


def parallel() -> ParallelConfig:
    return ParallelConfig(dp_mode="fsdp")
