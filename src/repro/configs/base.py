"""Config substrate: architecture registry, shape suites, input specs.

Every assigned architecture is a module ``repro.configs.<id>`` exporting
``config()`` (the exact published figures) and ``reduced()`` (a tiny
same-family variant for CPU smoke tests). The registry here resolves
``--arch`` names; ``input_specs`` builds ShapeDtypeStruct stand-ins for the
dry-run (no allocation, weak-type-correct, shardable).
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.collectives import CollectiveConfig
from repro.models.transformer import ModelConfig

ARCHS = (
    "minicpm_2b",
    "nemotron_4_15b",
    "granite_3_8b",
    "minitron_8b",
    "rwkv6_7b",
    "mixtral_8x22b",
    "llama4_scout_17b_a16e",
    "jamba_v0_1_52b",
    "qwen2_vl_7b",
    "seamless_m4t_large_v2",
)

# canonical external ids (dashes) -> module names
ALIASES = {a.replace("_", "-"): a for a in ARCHS}


@dataclasses.dataclass(frozen=True)
class ShapeSuite:
    name: str
    seq_len: int
    global_batch: int
    kind: str                    # 'train' | 'prefill' | 'decode'


SHAPES = {
    "train_4k": ShapeSuite("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSuite("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSuite("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSuite("long_500k", 524288, 1, "decode"),
}

# long_500k requires a sub-quadratic/bounded-window mixer (see DESIGN.md §5).
LONG_OK = {"rwkv6_7b", "jamba_v0_1_52b", "mixtral_8x22b",
           "llama4_scout_17b_a16e"}


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """How a model maps onto the production mesh.

    dp_mode:
      'manual'  — partial-manual shard_map over (pod, data); gradients are
                  synchronized with the paper's dptree collective (hierarchical:
                  dual-tree over 'data', dual-root exchange over 'pod').
      'fsdp'    — params/optimizer sharded over (data, model) via GSPMD (the
                  giant-MoE regime); cross-pod grad sync still runs the paper's
                  collective over the 'pod' axis in multi-pod meshes.
    """
    dp_mode: str = "manual"
    collective: CollectiveConfig = CollectiveConfig(method="dptree")
    zero1: bool = True             # flat-band master/moment sharding (manual)
    grad_accum: int = 1            # microbatches per step (bounds activations)
    # cross-pod gradient sync in fsdp mode: 'dptree' = the paper's collective
    # over the manual pod axis; 'auto' = let GSPMD handle it (workaround for
    # an XLA SPMD gather-partitioner check failure that certain dim
    # combinations trip under subgrouped manual axes — see DESIGN.md).
    pod_sync: str = "dptree"
    # tensor parallelism (serving decode path): shard attention heads / FFN
    # columns across a 'tp' mesh axis; every decode tick then ends in a tiny
    # per-token allreduce — the paper's latency-bound regime. method='auto'
    # lets the autotuner/cost model pick dptree vs ring per message size
    # (docs/tensor_parallel.md).
    tp_shards: int = 1
    tp_collective: CollectiveConfig = CollectiveConfig(method="auto")


def get_arch(name: str):
    mod_name = ALIASES.get(name, name)
    if mod_name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ALIASES)}")
    return importlib.import_module(f"repro.configs.{mod_name}")


def get_config(name: str, reduced: bool = False) -> ModelConfig:
    mod = get_arch(name)
    return mod.reduced() if reduced else mod.config()


def get_parallel(name: str) -> ParallelConfig:
    mod = get_arch(name)
    return getattr(mod, "parallel", lambda: ParallelConfig())()


def supports_shape(name: str, shape: str) -> bool:
    mod_name = ALIASES.get(name, name)
    if shape == "long_500k":
        return mod_name in LONG_OK
    return True


def _tok(shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ModelConfig, suite: ShapeSuite,
                batch_override: int | None = None) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of a given shape cell.

    For 'decode' suites, ``seq_len`` is the KV-cache length and the step input
    is a single new token per sequence (the shape of ``serve_step``'s batch).
    """
    B = batch_override or suite.global_batch
    T = suite.seq_len
    emb_dt = jnp.bfloat16
    if suite.kind in ("train", "prefill"):
        if cfg.n_enc_layers:                       # enc-dec (seamless)
            return {"src_embeds": _tok((B, T, cfg.d_model), emb_dt),
                    "tokens": _tok((B, T)), "labels": _tok((B, T))}
        if cfg.input_mode == "embeds":             # VLM/audio stub frontend
            spec = {"embeds": _tok((B, T, cfg.d_model), emb_dt),
                    "labels": _tok((B, T))}
            if cfg.mrope_sections:
                spec["positions"] = _tok((B, T, 3))
            return spec
        return {"tokens": _tok((B, T)), "labels": _tok((B, T))}
    # decode: one new token against a seq_len cache
    if cfg.n_enc_layers:
        return {"tokens": _tok((B, 1)),
                "memory": _tok((B, 4096, cfg.d_model), emb_dt)}
    if cfg.input_mode == "embeds":
        spec = {"embeds": _tok((B, 1, cfg.d_model), emb_dt)}
        if cfg.mrope_sections:
            spec["positions"] = _tok((B, 1, 3))
        return spec
    return {"tokens": _tok((B, 1))}


def concrete_inputs(cfg: ModelConfig, suite: ShapeSuite, key,
                    batch_override: int | None = None) -> dict:
    """Random concrete inputs matching :func:`input_specs` (for smoke/e2e)."""
    specs = input_specs(cfg, suite, batch_override)
    out = {}
    for k, s in specs.items():
        key, sub = jax.random.split(key)
        if jnp.issubdtype(s.dtype, jnp.integer):
            hi = cfg.vocab_size if k in ("tokens", "labels") else max(
                suite.seq_len, 2)
            out[k] = jax.random.randint(sub, s.shape, 0, hi, s.dtype)
        else:
            out[k] = jax.random.normal(sub, s.shape, s.dtype)
    return out
