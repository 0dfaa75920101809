"""Serving a hybrid Mamba + attention + held-experts MoE model: the open
loop of ``serving`` (``play``, ``warm``, ``_sample``, ``peak_bytes``) on a
configuration that names its own reference (``config["reference"]``,
a module of ``chipbench/reference``).

The program's model config is built from the file's keys (layer order
from the offsets and periods, the experts held from ``num_experts`` and
``held_experts_first``, the router's width from ``router_experts``, the
vocabulary slice from ``vocab_held``). The
weights are drawn on the device from the seed by path, as ``weights.draw``
draws them, but the slices of a stacked leaf (one expert, one period) are
drawn one at a time from keys folded from the leaf's: a 4096 x 14336
expert stack drawn whole would hold a float32 copy beside 14 GB of
weights. The reference reads the same values.

Each tick's record also holds the held-experts counts the engine fetched
in it (``EngineSession.moe_counts``), which the ``moe_*`` readers use.

Correctness is ``serving``'s (``served_check``): a sample of finished
requests, the longest among them, through the float32 reference over the
prompt and the served tokens; the widest gap of a served token's logit
below the reference's best, in the reference's standard deviations,
against the workload's limit, and no request with a wrong token count.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import served_check, traffic, weights
from chipbench.drivers import serving
from chipbench.harness import log


def reference(config: dict):
    return importlib.import_module(
        f"chipbench.reference.{config['reference']}")


def program_config(config: dict):
    """The program's model config for the file's sizes, served in
    bfloat16; refuses a file whose layer order or Mamba shapes the
    program's block does not have."""
    from repro.launch.serve import serving_config
    ref = reference(config)
    s = ref.sizes(config)
    base = serving_config(config["repro_arch"])
    kinds = [tuple(sub.kind for sub in layer) for layer in base.pattern]
    want = [ref.layer_kind(s, i) for i in range(len(kinds))]
    if kinds != want or config["num_hidden_layers"] % len(kinds):
        raise ValueError(f"the program's layer period {kinds} is not the "
                         f"file's {want}")
    cfg = dataclasses.replace(
        base, n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_held"],
        mamba_d_state=config["mamba_d_state"],
        tie_embeddings=config["tie_word_embeddings"],
        moe=dataclasses.replace(
            base.moe, n_experts=config["router_experts"],
            top_k=config["num_experts_per_tok"],
            held=(config["held_experts_first"], config["num_experts"])))
    m = cfg.mamba_cfg()
    got = (m.d_conv, m.expand, m.dt_rank)
    if got != (config["mamba_d_conv"], config["mamba_expand"],
               config["mamba_dt_rank"]):
        raise ValueError(f"the program's Mamba (d_conv, expand, dt_rank) "
                         f"{got} is not the file's")
    return cfg


def draw(spec: dict, seed: int) -> dict:
    """``{path: array}`` for every leaf of ``spec``, in one jitted call: a
    leaf of rank 3 or more is drawn a trailing matrix at a time, slice
    ``i`` from the leaf's key folded with ``i``; the rest as
    ``weights.draw`` draws them."""
    paths = sorted(spec)

    def leaf(key, path):
        shape, dtype = spec[path][0], jnp.dtype(spec[path][1])
        if len(shape) < 3:
            return weights._draw_leaf(key, path, shape, dtype)
        n = int(np.prod(shape[:-2]))
        out = jax.lax.map(
            lambda i: weights._draw_leaf(jax.random.fold_in(key, i), path,
                                         shape[-2:], dtype),
            jnp.arange(n, dtype=jnp.uint32))
        return out.reshape(shape)

    return jax.jit(lambda key: {p: leaf(key, p) for p in paths})(
        weights.seed_key(seed))


def draw_params(config: dict, seed: int, cfg):
    """The weights in the program's tree, refusing a layout that is not
    the one the reference reads."""
    from repro.models import transformer as tf
    like = jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0), cfg))
    want = {k: (tuple(s), d) for k, (s, d) in
            reference(config).spec(config).items()}
    got = weights.spec_of(like)
    if got != want:
        raise RuntimeError(f"the program's parameter layout {got} is not the "
                           f"one the reference reads {want}")
    return weights.as_tree(draw(want, seed), like)


def build(config: dict, workload: dict, seed: int, devices):
    from repro.configs.base import get_parallel
    from repro.launch.mesh import make_mesh
    from repro.serving import ServingEngine
    cfg = program_config(config)
    mesh = make_mesh((1, 1), ("data", "model"), devices=devices[:1])
    eng = workload["engine"]
    return ServingEngine(cfg, get_parallel(config["repro_arch"]), mesh,
                         draw_params(config, seed, cfg),
                         n_slots=eng["n_slots"], max_len=eng["max_len"])


@dataclasses.dataclass
class Tick(serving.Tick):
    # (program, int array (MoE layers, 3)) per step whose held-experts
    # counts the tick fetched; columns as ``transformer.MOE_COUNTS``
    moe: list = dataclasses.field(default_factory=list)


class _Counted:
    """The session, keeping each tick's held-experts counts."""

    def __init__(self, session):
        self.session, self.counts = session, []

    def __getattr__(self, name):
        return getattr(self.session, name)

    def tick(self):
        out = self.session.tick()
        self.counts.append(list(self.session.moe_counts))
        return out


def run(run, devices, tracer=None, control: bool = False) -> None:
    """Play the cell; fill ``run`` (see ``harness.Run``)."""
    config, wl = run.config, run.workload
    spec = wl["traffic"]
    engine = build(config, wl, run.seed, devices)
    cfg = engine.cfg
    arrivals = traffic.open_loop(spec, run.seconds, run.seed,
                                 cfg.vocab_size)
    serving.warm(engine, arrivals)
    session = _Counted(engine.start())
    compiles = serving._Compiles()
    compiles.counting = True
    run.setup_s = time.perf_counter() - run.t_start
    recs, ticks, window, late = serving.play(session, arrivals, run.seconds,
                                             wl["drain_s"], tracer)
    compiles.counting = False
    ticks = [Tick(**vars(t), moe=c) for t, c in zip(ticks, session.counts)]
    finished = [r for r in recs if r.done is not None]
    run.attempted, run.failed = len(recs), len(recs) - len(finished)
    run.memory_peak_bytes = serving.peak_bytes(devices[:1])
    lateness = np.array(late) * 1e3
    log(f"[serving_hybrid] {len(recs)} requests due in {run.seconds:g} s "
        f"({spec['rate_per_s']} req/s), {len(finished)} finished, "
        f"{run.failed} not within {wl['drain_s']} s after the window; "
        f"{len(ticks)} ticks; generator late by p50 "
        f"{np.percentile(lateness, 50):.3f} ms, max {lateness.max():.3f} ms;"
        f" {compiles.n} compiles inside the window")
    run.data.update(records=recs, ticks=ticks, window=window,
                    compiles_in_window=compiles.n)
    prompts = {a.rid: a.prompt for a in arrivals}
    served = {r.rid: list(r.tokens) for r in session.sched.finished}
    flat = {weights.path_str(p): x for p, x in
            jax.tree_util.tree_flatten_with_path(engine.params)[0]}
    del session, engine
    gc.collect()
    check = wl["check"]
    if not finished:
        run.compared.append(("served_logit_gap", float("nan"),
                             check["limit"]))
        return
    sample = serving._sample(finished, run.seed, check["sample_requests"])
    bad = [r.rid for r in finished
           if len(served[r.rid]) != r.max_new
           or not all(0 <= t < cfg.vocab_size for t in served[r.rid])]
    gap, n, ctl = served_check.served_gap(reference(config), config, flat,
                                          sample, prompts, served,
                                          control=control)
    del flat
    log(f"[serving_hybrid] reference over {len(sample)} requests, {n} "
        f"served tokens: widest gap {gap:.4f} std of the reference logits"
        + ("" if ctl is None else f"; float8 control {ctl:.4f}"))
    run.data["control_gap"] = ctl
    run.compared.append(("served_logit_gap", gap, check["limit"]))
    run.compared.append(("requests_with_wrong_token_count", float(len(bad)),
                         0.0))
