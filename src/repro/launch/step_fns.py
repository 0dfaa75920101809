"""Train/serve step builders: where the paper's collective meets the models.

Two data-parallel regimes (see DESIGN.md §3):

* ``manual``  — gradient computation + the paper's collective run inside a
  *partial-manual* ``shard_map`` (manual over ('pod','data'), GSPMD-auto over
  'model'). Per-replica gradients are reduced explicitly with the
  doubly-pipelined dual-root tree, hierarchically: dual-tree allreduce over
  the 16-way 'data' axis, then the dual-root exchange over the 2-way 'pod'
  axis (which *is* the paper's two-roots structure). The optimizer update
  runs OUTSIDE the manual region with ZeRO-1 moment sharding: Adam's mu/nu
  shard over (data x model) per leaf via GSPMD while bf16 params keep their
  TP-only specs (XLA re-broadcasts updated leaves across 'data').
* ``fsdp``    — parameters and optimizer state shard over ('data','model')
  via GSPMD (the >50B MoE regime, where the partitioner reduce-scatters
  gradients); in multi-pod meshes cross-pod gradient sync still runs the
  paper's collective manually over the 'pod' axis (``pod_sync='dptree'``).

Scalar training metrics are reduced with the b=1 dual-root tree in both modes —
the latency-bound regime where the tree beats ring by O(p/log p).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ParallelConfig, ShapeSuite
from repro.core.collectives import (CollectiveConfig, all_reduce,
                                    bucketed_all_reduce)
from repro.models import transformer as tf
from repro.optim.optimizers import Optimizer

# --------------------------------------------------------------------------
# sharding helpers
# --------------------------------------------------------------------------

def _dp_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _sanitize(specs, zeros, mesh) -> Any:
    """Drop sharding entries whose dim isn't divisible by the axis group
    (e.g. seamless's vocab 256206 over a 16-way model axis)."""
    def fix(spec, leaf):
        entries = list(spec) + [None] * (leaf.ndim - len(spec))
        outs = []
        for d, e in enumerate(entries[:leaf.ndim]):
            if e is None:
                outs.append(None)
                continue
            names = e if isinstance(e, tuple) else (e,)
            n = int(np.prod([mesh.shape[a] for a in names]))
            outs.append(e if leaf.shape[d] % n == 0 else None)
        return P(*outs)

    return jax.tree.map(fix, specs, zeros, is_leaf=lambda v: isinstance(v, P))


def model_pspecs(cfg, mesh=None) -> Any:
    specs = tf.param_pspecs(cfg)
    if mesh is None:
        return specs
    zeros = jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0), cfg))
    return _sanitize(specs, zeros, mesh)


def fsdp_pspecs(cfg, mesh, data_axis: str = "data") -> Any:
    """Add 'data' sharding on the largest free divisible dim of each param."""
    zeros = jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0), cfg))
    base = _sanitize(tf.param_pspecs(cfg), zeros, mesh)
    n_data = mesh.shape[data_axis]

    def add(spec, leaf):
        entries = list(spec) + [None] * (leaf.ndim - len(spec))
        cands = [(leaf.shape[d], d) for d in range(leaf.ndim)
                 if entries[d] is None and leaf.shape[d] % n_data == 0
                 and leaf.shape[d] >= 2 * n_data]
        if cands:
            entries[max(cands)[1]] = data_axis
        return P(*entries)

    return jax.tree.map(add, base, zeros, is_leaf=lambda v: isinstance(v, P))


def opt_pspecs(param_specs, opt_state_like) -> Any:
    """Optimizer-state specs: moments mirror the params; counters replicate."""
    def pick(k, sub):
        if k in ("mu", "nu", "m"):
            return param_specs
        return jax.tree.map(lambda _: P(), sub)
    return {k: pick(k, v) for k, v in opt_state_like.items()}


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda v: isinstance(v, P))


# --------------------------------------------------------------------------
# tensor parallelism (serving decode/prefill/verify)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _TPSetup:
    tp: int
    axis: str
    scfg: Any          # per-rank shard config (heads/FFN divided by tp)
    pspecs: Any        # tp_param_specs
    cspecs: Any        # tp_cache_specs
    collective: CollectiveConfig


def _tp_setup(cfg, pcfg: ParallelConfig, mesh) -> _TPSetup | None:
    """Resolve the tensor-parallel regime for the serving step builders.

    Returns None when ``pcfg.tp_shards <= 1`` (the builders then compile
    their usual GSPMD-auto bodies). Otherwise the model body is traced
    inside a shard_map manual over EVERY mesh axis: each rank sees the
    parameter/cache shards named by :func:`repro.models.transformer.
    tp_param_specs` / ``tp_cache_specs`` and runs the unchanged model code
    under the per-rank :func:`~repro.models.transformer.tp_shard_config`,
    with the per-token partial-sum allreduce supplied by ``L.tp_ctx``. The
    mesh must be fully covered (use :func:`repro.launch.mesh.make_tp_mesh`).
    """
    tp = int(getattr(pcfg, "tp_shards", 1) or 1)
    if tp <= 1:
        return None
    if "tp" not in mesh.axis_names:
        raise ValueError(
            f"tp_shards={tp} needs a 'tp' mesh axis, mesh has "
            f"{mesh.axis_names}; build one with launch.mesh.make_tp_mesh")
    if mesh.shape["tp"] != tp:
        raise ValueError(f"tp_shards={tp} but the mesh 'tp' axis has "
                         f"{mesh.shape['tp']} devices")
    tf.validate_tp(cfg, tp)
    return _TPSetup(tp=tp, axis="tp", scfg=tf.tp_shard_config(cfg, tp),
                    pspecs=tf.tp_param_specs(cfg, "tp"),
                    cspecs=tf.tp_cache_specs(cfg, "tp"),
                    collective=pcfg.tp_collective)


def _tp_model_ctx(tps: _TPSetup | None, mesh):
    """The tracing context for a serving model body: the TP reduction hook
    when tensor parallelism is on, else the mesh for ``maybe_shard``."""
    from repro.models import layers as L
    if tps is not None:
        return L.tp_ctx(tps.axis, tps.tp, tps.collective)
    return L.mesh_ctx(mesh)


def _reduce_metrics(vec, axes, sizes, collective: CollectiveConfig):
    ptot = 1
    cfg1 = CollectiveConfig(method="dptree", num_blocks=1,
                            comm_model=collective.comm_model)
    for ax in axes:
        vec = all_reduce(vec, ax, sizes[ax], cfg1)
        ptot *= sizes[ax]
    return vec / ptot


# --------------------------------------------------------------------------
# train step
# --------------------------------------------------------------------------

def zero1_opt_pspecs(cfg, mesh, param_specs) -> Any:
    """ZeRO-1 moment sharding: Adam's mu/nu shard over (data x model) per
    leaf (GSPMD partitions the elementwise update); the params keep their
    model-only specs and XLA re-broadcasts updated leaves across 'data'.
    With bf16 params + fp32 moments this is the DeepSpeed-stage-1 memory
    profile without a separate fp32 master copy (documented trade-off)."""
    moment_specs = fsdp_pspecs(cfg, mesh)
    return moment_specs


def make_train_step(cfg, pcfg: ParallelConfig, mesh,
                    optimizer: Optimizer | None = None, accum: int = 1):
    """Returns (jitted_step, shardings):
    step(params, opt_state, batch) -> (params, opt_state, metrics_vec) with
    metrics_vec = [loss, ce, aux, grad_norm] replicated and DP-averaged.
    ``accum`` > 1 splits the local batch into microbatches (gradient
    accumulation bounds the remat-saved activation footprint).
    """
    if optimizer is None:
        from repro.optim.optimizers import adamw, cosine_schedule
        optimizer = adamw(cosine_schedule(3e-4, 100, 10000))
    dp = _dp_axes(mesh)
    manual = dp if pcfg.dp_mode == "manual" else tuple(
        a for a in dp if a == "pod" and pcfg.pod_sync == "dptree")
    sizes = {a: mesh.shape[a] for a in mesh.axis_names}
    ptot = int(np.prod([sizes[a] for a in manual])) if manual else 1
    pspecs = (model_pspecs(cfg, mesh) if pcfg.dp_mode == "manual"
              else fsdp_pspecs(cfg, mesh))
    zeros_p = jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0), cfg))

    def _value_and_grads(params, batch):
        vg = jax.value_and_grad(
            lambda p, mb: tf.loss_fn(p, cfg, mb), has_aux=True)
        if accum == 1:
            return vg(params, batch)
        mbs = jax.tree.map(
            lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]),
            batch)

        def mstep(carry, mb):
            lacc, cacc, aacc, gacc = carry
            (loss, mets), g = vg(params, mb)
            gacc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                gacc, g)
            return (lacc + loss, cacc + mets["ce"], aacc + mets["aux"],
                    gacc), ()

        g0 = jax.tree.map(lambda q: jnp.zeros(q.shape, jnp.float32), params)
        z = jnp.zeros((), jnp.float32)
        (loss, ce, aux, gacc), _ = jax.lax.scan(mstep, (z, z, z, g0), mbs)
        grads = jax.tree.map(lambda g, q: (g / accum).astype(q.dtype),
                             gacc, params)
        return (loss / accum, {"ce": ce / accum, "aux": aux / accum}), grads

    def grad_body(params, batch):
        """Inside the partial-manual region: local grads + the paper's
        hierarchical pipelined allreduce ('data' dual-tree, then the
        dual-root 'pod' exchange). Returns replicated, averaged grads."""
        from repro.models.layers import mesh_ctx
        with mesh_ctx(mesh):
            return _grad_body_inner(params, batch)

    def _grad_body_inner(params, batch):
        (loss, metrics), grads = _value_and_grads(params, batch)
        if manual:
            for ax in (a for a in ("data", "pod") if a in manual):
                grads = bucketed_all_reduce(grads, ax, sizes[ax],
                                            pcfg.collective,
                                            leaf_specs=pspecs)
            grads = jax.tree.map(lambda g: g / ptot, grads)
        vec = jnp.stack([loss, metrics["ce"],
                         metrics["aux"]]).astype(jnp.float32)
        if manual:
            vec = _reduce_metrics(vec, manual, sizes, pcfg.collective)
        return grads, vec

    if manual:
        bspec = P(manual if len(manual) > 1 else manual[0])
        grad_fn = shard_map(
            grad_body, mesh=mesh, in_specs=(P(), bspec),
            out_specs=(P(), P()), axis_names=set(manual), check_vma=False)
    else:
        grad_fn = grad_body

    def body(params, opt_state, batch):
        grads, vec = grad_fn(params, batch)
        new_params, new_opt, om = optimizer.update(grads, opt_state, params)
        vec = jnp.concatenate([vec, om["grad_norm"][None]])
        return new_params, new_opt, vec

    # optimizer state shards over (data x model) in the auto domain (ZeRO-1)
    zeros_o = jax.eval_shape(optimizer.init, zeros_p)
    mspecs = zero1_opt_pspecs(cfg, mesh, pspecs) if pcfg.zero1 else pspecs
    ospecs = opt_pspecs(mspecs, zeros_o)
    in_sh = (_named(mesh, pspecs), _named(mesh, ospecs), None)
    out_sh = (_named(mesh, pspecs), _named(mesh, ospecs),
              NamedSharding(mesh, P()))
    step = jax.jit(body, in_shardings=in_sh, out_shardings=out_sh,
                   donate_argnums=(0, 1))
    shardings = {"params": pspecs, "opt": ospecs,
                 "batch": P(dp if dp else None), "opt_init": optimizer.init}
    return step, shardings


# --------------------------------------------------------------------------
# prefill + serve (decode) steps
# --------------------------------------------------------------------------

def make_prefill_step(cfg, pcfg: ParallelConfig, mesh, suite: ShapeSuite,
                      into_slots: bool = False, donate: bool = True,
                      ring_slack: int = 0):
    """Prefill step builder, two regimes:

    * ``into_slots=False`` — full-sequence forward + last-position logits
      (the dry-run's serving prefill proxy; see EXPERIMENTS.md §Dry-run for
      the KV-cache-materialization caveat). step(params, inputs) -> logits.
    * ``into_slots=True`` — the serving engine's cache-writing prefill:
      step(params, tokens (1, Tc), caches, slot (), length (), resume=bool,
      sampling_row={key (2,), temperature (), top_k (), top_p ()}) ->
      (first-token (), caches). The prompt CHUNK runs through the stack as
      a SINGLE row — prefill cost scales with the admitted chunk, not with
      ``n_slots`` — and the finished row is spliced into the slot with one
      dynamic-update per cache leaf, leaving every in-flight slot untouched
      (admission interleaves with decode). ``resume=False`` starts the row
      from a fresh zero cache (first chunk); ``resume=True`` extracts the
      slot's CURRENT row and continues it (chunks 2..n of a long prompt:
      attention keeps writing the ring at the carried ``pos``, SSM carries
      advance from the checkpointed state). The emitted token is the
      sampled first generated token — meaningful on the FINAL chunk, where
      the engine consumes it (greedy rows are a bit-exact argmax; the
      sampled path derives its key from the request seed at step 0, see
      repro.serving.sampling). One compilation per (bucket Tc, resume)
      pair; ``slot`` is traced, so slot churn never re-jits. A model
      with held-experts MoE layers gets (first-token, counts, caches):
      their counts (``tf.MOE_COUNTS``) ride out with the token.
    """
    tps = _tp_setup(cfg, pcfg, mesh)
    pspecs = (tps.pspecs if tps is not None
              else fsdp_pspecs(cfg, mesh) if pcfg.dp_mode == "fsdp"
              else model_pspecs(cfg, mesh))
    mcfg = tps.scfg if tps is not None else cfg
    dp = _dp_axes(mesh)
    moe_counts = tf.moe_counted_layers(cfg) > 0

    if into_slots:
        from repro.serving.sampling import sample_tokens
        cspecs = (tps.cspecs if tps is not None
                  else cache_pspecs(cfg, mesh, suite.global_batch,
                                    suite.seq_len, per_slot=True,
                                    ring_slack=ring_slack))

        def _prefill_fwd(params, tokens, caches, slot, length, resume):
            with _tp_model_ctx(tps, mesh):
                if resume:
                    # continue the slot's CURRENT row — chunks 2..n of a
                    # long prompt, or chunk 1 after a prefix-cache adoption
                    # wrote a shared-prefix row (tf.adopt_prefix)
                    row_in = tf.extract_cache_row(caches, slot)
                else:
                    # under TP this allocates the RANK-LOCAL fresh row
                    # (mcfg's KV heads are already divided by tp)
                    row_in = tf.init_cache(mcfg, 1, suite.seq_len,
                                           per_slot=True,
                                           ring_slack=ring_slack)
                logits, row, *n = tf.prefill_step(
                    params, mcfg, {"tokens": tokens}, row_in,
                    length.reshape(1), jnp.ones((1,), bool), resume=resume,
                    counts=moe_counts)
            return logits, tuple(n), tf.adopt_prefix(caches, row, slot)

        # the bodies' names are their programs' names on the device
        # (``jit_prefill_step``, ``jit_prefill_step_sampled``)
        def prefill_step(params, tokens, caches, slot, length, resume):
            logits, n, out = _prefill_fwd(params, tokens, caches, slot,
                                          length, resume)
            return (jnp.argmax(logits).astype(jnp.int32),) + n + (out,)

        def prefill_step_sampled(params, tokens, caches, slot, length,
                                 sampling_row, resume):
            logits, n, out = _prefill_fwd(params, tokens, caches, slot,
                                          length, resume)
            tok = sample_tokens(
                logits.reshape(1, -1), sampling_row["key"][None],
                jnp.zeros((1,), jnp.int32),            # first token: step 0
                sampling_row["temperature"].reshape(1),
                sampling_row["top_k"].reshape(1),
                sampling_row["top_p"].reshape(1))[0]
            return (tok,) + n + (out,)

        # greedy (the default) compiles without the sampler pipeline;
        # sampled variants compile lazily on first sampled admission.
        # ``donate=False`` keeps the input caches alive past the call — the
        # draft-model drafter snapshots its caches before proposing and
        # restores them on rejection, which donation would invalidate.
        dn = (2,) if donate else ()
        n_small = 1 + moe_counts          # the token (and the counts)

        def _mk(body, n_args):
            # TP: the whole cache-writing prefill (row slice/init, the
            # sharded-model forward, the splice, the first-token pick) runs
            # inside ONE fully-manual shard_map — params/caches enter as
            # per-rank shards, tokens/slot/length/sampling replicate, and
            # the emitted token + spliced caches come back out.
            if tps is None:
                return body
            ins = (tps.pspecs, P(), tps.cspecs) + (P(),) * (n_args - 3)
            return shard_map(body, mesh=mesh, in_specs=ins,
                             out_specs=(P(),) * n_small + (tps.cspecs,),
                             axis_names=set(mesh.axis_names),
                             check_vma=False)

        def _resumed(body, resume):
            # a partial has no name of its own, and its program would
            # show on the device as ``jit__unknown``
            fn = functools.partial(body, resume=resume)
            fn.__name__ = body.__name__
            return fn

        out_sh = ((NamedSharding(mesh, P()),) * n_small
                  + (_named(mesh, cspecs),))
        jitted = {}
        for resume in (False, True):
            jitted[resume, False] = jax.jit(
                _mk(_resumed(prefill_step, resume), 5),
                in_shardings=(_named(mesh, pspecs), None,
                              _named(mesh, cspecs), None, None),
                out_shardings=out_sh, donate_argnums=dn)
            jitted[resume, True] = jax.jit(
                _mk(_resumed(prefill_step_sampled, resume), 6),
                in_shardings=(_named(mesh, pspecs), None,
                              _named(mesh, cspecs), None, None, None),
                out_shardings=out_sh, donate_argnums=dn)

        def step(params, tokens, caches, slot, length, *, resume=False,
                 sampling_row=None):
            if sampling_row is None:                  # greedy default
                return jitted[bool(resume), False](params, tokens, caches,
                                                   slot, length)
            return jitted[bool(resume), True](params, tokens, caches, slot,
                                              length, sampling_row)

        return step, {"params": pspecs, "cache": cspecs}

    def body(params, inputs):
        with _tp_model_ctx(tps, mesh):
            hs, _ = tf.forward(params, mcfg, inputs)
            return tf.unembed(params, mcfg,
                              hs[:, -1:]).astype(jnp.float32)[:, 0]

    bspec = P() if tps is not None else P(dp)
    if tps is not None:
        body = shard_map(body, mesh=mesh, in_specs=(tps.pspecs, P()),
                         out_specs=P(), axis_names=set(mesh.axis_names),
                         check_vma=False)
    step = jax.jit(body, in_shardings=(_named(mesh, pspecs), None),
                   out_shardings=NamedSharding(mesh, bspec))
    return step, {"params": pspecs, "batch": bspec}


def cache_pspecs(cfg, mesh, batch: int, max_len: int = 8,
                 per_slot: bool = False, ring_slack: int = 0) -> Any:
    """Sharding for the stacked KV/state caches.

    Shard batch over the DP axes when divisible; otherwise (long-context B=1)
    shard the cache length over ('data','model') — split-KV decode, where
    GSPMD reduces the attention partials across shards.
    """
    dp = _dp_axes(mesh)
    n_dp = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    shard_batch = bool(dp) and batch % n_dp == 0 and batch >= n_dp
    caches = tf.init_cache(cfg, batch, max_len, abstract=True,
                           per_slot=per_slot, ring_slack=ring_slack)

    def spec(leaf):
        nd = leaf.ndim
        entries = [None] * nd
        if nd >= 3 and shard_batch:
            entries[1] = dp if len(dp) > 1 else dp[0]
        if nd >= 3:
            cand_groups = ([("model",)] if shard_batch
                           else [("data", "model"), ("model",), ("data",)])
            for cand in cand_groups:
                if not all(a in mesh.axis_names for a in cand):
                    continue
                n = int(np.prod([mesh.shape[a] for a in cand]))
                if leaf.shape[2] % n == 0 and leaf.shape[2] >= n:
                    entries[2] = cand if len(cand) > 1 else cand[0]
                    break
        return P(*entries)

    return jax.tree.map(spec, caches)


def make_serve_step(cfg, pcfg: ParallelConfig, mesh, suite: ShapeSuite,
                    slots: bool = False, donate: bool = True,
                    ring_slack: int = 0):
    """Returns (jitted_step, shardings).

    ``slots=False``: step(params, inputs, caches) -> (logits, new_caches) —
    the fixed-batch decode step (every row advances every call).

    ``slots=True``: step(params, inputs, caches, active, sampling) ->
    (tokens (B,), new_caches) against per-slot caches (``pos`` per batch
    row; SSM rows carry their recurrent state). ``active`` (B,) bool marks
    rows holding in-flight requests; inactive rows compute but neither
    advance nor mutate their cache rows (see ``tf.decode_step``),
    so one compiled step serves any mix of busy/free/prefilling slots — the
    continuous-batching engine's decode tick. ``sampling`` threads the
    per-request seeded sampler through the jitted step: {key (B,2) u32,
    step (B,) i32, temperature (B,), top_k (B,), top_p (B,)}; rows with
    temperature 0 take the bit-exact greedy argmax
    (see repro.serving.sampling). For a model with held-experts MoE layers
    the slot step returns (tokens, counts, new_caches): their counts
    (``tf.MOE_COUNTS``) ride out with the tokens.
    """
    tps = _tp_setup(cfg, pcfg, mesh)
    mcfg = tps.scfg if tps is not None else cfg
    pspecs = (tps.pspecs if tps is not None
              else fsdp_pspecs(cfg, mesh) if pcfg.dp_mode == "fsdp"
              else model_pspecs(cfg, mesh))
    cspecs = (tps.cspecs if tps is not None
              else cache_pspecs(cfg, mesh, suite.global_batch, suite.seq_len,
                                per_slot=slots, ring_slack=ring_slack))
    dp = _dp_axes(mesh)
    n_dp = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    shard_batch = dp and not tps and suite.global_batch % max(n_dp, 1) == 0 \
        and suite.global_batch >= n_dp
    bspec = P(dp if len(dp) > 1 else (dp[0] if dp else None)) \
        if shard_batch else P(None)
    moe_counts = tf.moe_counted_layers(cfg) > 0

    if slots:
        from repro.serving.sampling import sample_tokens

        def _guard(logits, tokens):
            # decode-logits guard: a row whose logits contain NaN/Inf
            # (poisoned cache, numerical blow-up) reports the -1 sentinel
            # instead of an in-vocab token — argmax/categorical over
            # non-finite logits silently yield a plausible-looking id, so
            # the corruption MUST be flagged in-graph for the engine to
            # refuse the commit and quarantine (docs/robustness.md)
            ok = jnp.isfinite(logits).all(axis=-1)
            return jnp.where(ok, tokens, jnp.int32(-1))

        # the bodies' names are their programs' names on the device
        # (``jit_decode_step``, ``jit_decode_step_sampled``)
        def _forward(params, inputs, caches, active):
            with _tp_model_ctx(tps, mesh):
                logits, new_caches, *n = tf.decode_step(
                    params, mcfg, inputs, caches, active=active,
                    counts=moe_counts)
            return logits, tuple(n), new_caches

        def decode_step(params, inputs, caches, active):
            logits, n, new_caches = _forward(params, inputs, caches, active)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (_guard(logits, tok),) + n + (new_caches,)

        def decode_step_sampled(params, inputs, caches, active, sampling):
            logits, n, new_caches = _forward(params, inputs, caches, active)
            tokens = sample_tokens(logits, sampling["key"], sampling["step"],
                                   sampling["temperature"],
                                   sampling["top_k"], sampling["top_p"])
            return (_guard(logits, tokens),) + n + (new_caches,)

        def _mk(body, n_args):
            # TP decode tick: one fully-manual shard_map per body — each
            # rank runs its head/FFN shard of the stack, the per-token
            # allreduce completes the logits, and argmax/sampling replicate
            # per rank (bit-identical inputs -> bit-identical tokens).
            if tps is None:
                return body
            ins = (tps.pspecs, P(), tps.cspecs) + (P(),) * (n_args - 3)
            return shard_map(body, mesh=mesh, in_specs=ins,
                             out_specs=(P(),) * (1 + moe_counts)
                             + (tps.cspecs,),
                             axis_names=set(mesh.axis_names),
                             check_vma=False)

        # all-greedy ticks (the default and the bench path) keep the hot
        # decode step at a plain argmax — the full-vocab sort/softmax of
        # the sampler pipeline compiles only into the sampled variant,
        # whose greedy rows still take the identical argmax inside
        # sample_tokens, so mixing policies never changes greedy streams
        out_sh = ((NamedSharding(mesh, bspec),)
                  + (NamedSharding(mesh, P()),) * moe_counts
                  + (_named(mesh, cspecs),))
        dn = (2,) if donate else ()       # see make_prefill_step on donate
        greedy_step = jax.jit(
            _mk(decode_step, 4),
            in_shardings=(_named(mesh, pspecs), None, _named(mesh, cspecs),
                          None),
            out_shardings=out_sh, donate_argnums=dn)
        sampled_step = jax.jit(
            _mk(decode_step_sampled, 5),
            in_shardings=(_named(mesh, pspecs), None, _named(mesh, cspecs),
                          None, None),
            out_shardings=out_sh, donate_argnums=dn)

        def step(params, inputs, caches, active, sampling=None):
            if sampling is None:
                return greedy_step(params, inputs, caches, active)
            return sampled_step(params, inputs, caches, active, sampling)

        return step, {"params": pspecs, "cache": cspecs, "batch": bspec}

    def body(params, inputs, caches):
        inputs = dict(inputs)
        memory = inputs.pop("memory", None)
        with _tp_model_ctx(tps, mesh):
            logits, new_caches = tf.decode_step(params, mcfg, inputs, caches,
                                                memory)
        return logits, new_caches

    if tps is not None:
        body = shard_map(body, mesh=mesh,
                         in_specs=(tps.pspecs, P(), tps.cspecs),
                         out_specs=(P(), tps.cspecs),
                         axis_names=set(mesh.axis_names), check_vma=False)
    step = jax.jit(
        body,
        in_shardings=(_named(mesh, pspecs), None, _named(mesh, cspecs)),
        out_shardings=(NamedSharding(mesh, bspec), _named(mesh, cspecs)),
        donate_argnums=(2,))
    return step, {"params": pspecs, "cache": cspecs, "batch": bspec}


# --------------------------------------------------------------------------
# speculative verify step
# --------------------------------------------------------------------------

def make_verify_step(cfg, pcfg: ParallelConfig, mesh, suite: ShapeSuite,
                     draft_k: int, ring_slack: int = 0):
    """Returns (jitted_step, shardings) for one-pass speculative verification.

    step(params, tokens, caches, active, n_draft, sampling=None) ->
    (emitted (B, K+1) int32, accept (B,) int32, new_caches), with
    ``tokens`` (B, K+1) int32 — per row, column 0 the request's last
    emitted token and columns 1..n_draft[b] its draft proposals (the rest
    padding) — against the serving engine's per-slot caches. The whole
    accept/reject tick is ONE compiled call per active-slot batch:

    * the stack scores all K+1 positions in a single forward
      (:func:`repro.models.transformer.verify_forward` — attention slots
      take the T>=1 query path, recurrent mixers run the exact token
      recurrences with per-token state checkpoints);
    * acceptance is the longest draft prefix matching the model's own
      next-token choice per position — the bit-exact argmax for greedy
      rows, or the request's committed ``fold_in(seed, token_index)``
      sampler for sampled rows (``sampling`` as in ``make_serve_step``,
      with ``sampling["step"]`` the first position's token index), so the
      emitted stream is IDENTICAL to the non-speculative engine's under any
      accept/reject schedule;
    * the commit is rollback-safe: rejected ring writes are restored
      bit-exact, positions advance by the accepted length only, recurrent
      carries take the accepted length's checkpoint
      (:func:`repro.models.transformer.commit_verify_caches`).

    A row with ``n_draft == 0`` is exactly one decode step (accept == 1,
    emitted[0] == the next token); inactive rows pass through untouched.
    Compiled once per draft budget K = ``draft_k`` (the adaptive controller
    varies the per-request k *within* K via ``n_draft``, never re-jitting).
    ``ring_slack`` must match the caches' (window/chunk-bounded rings need
    ``ring_slack >= draft_k`` — see ``init_cache``). A model with
    held-experts MoE layers gets (emitted, accept, counts, new_caches).
    """
    from repro.serving.sampling import sample_tokens_block
    tps = _tp_setup(cfg, pcfg, mesh)
    mcfg = tps.scfg if tps is not None else cfg
    pspecs = (tps.pspecs if tps is not None
              else fsdp_pspecs(cfg, mesh) if pcfg.dp_mode == "fsdp"
              else model_pspecs(cfg, mesh))
    cspecs = (tps.cspecs if tps is not None
              else cache_pspecs(cfg, mesh, suite.global_batch, suite.seq_len,
                                per_slot=True, ring_slack=ring_slack))
    T = draft_k + 1
    moe_counts = tf.moe_counted_layers(cfg) > 0

    def _verify(params, tokens, caches, active, n_draft, pred_fn):
        with _tp_model_ctx(tps, mesh):
            # columns past each row's own drafts are buffer padding: the
            # lengths= machinery keeps their ring writes suppressed (a pad
            # write can wrap over live K/V near ring capacity)
            lengths = jnp.clip(n_draft, 0, T - 1).astype(jnp.int32) + 1
            logits, raw, *n = tf.verify_forward(
                params, mcfg, {"tokens": tokens}, caches, lengths=lengths,
                counts=moe_counts)
            pred = pred_fn(logits)                             # (B, T) int32
            emitted, accept = tf.verify_accept(pred, tokens, n_draft)
            new_caches = tf.commit_verify_caches(raw, caches, T, accept,
                                                 active)
        return (emitted, accept) + tuple(n) + (new_caches,)

    def _vguard(lg, pred):
        # same non-finite-logits sentinel as the decode step: a poisoned
        # position predicts -1, which never matches a draft (ids >= 0), so
        # acceptance stops before it — and the engine refuses any emitted
        # -1 rather than committing a token argmaxed out of NaNs
        return jnp.where(jnp.isfinite(lg).all(axis=-1), pred, jnp.int32(-1))

    # the bodies' names are their programs' names on the device
    # (``jit_verify_step``, ``jit_verify_step_sampled``)
    def verify_step(params, tokens, caches, active, n_draft):
        return _verify(params, tokens, caches, active, n_draft,
                       lambda lg: _vguard(lg, jnp.argmax(lg, axis=-1)
                                          .astype(jnp.int32)))

    def verify_step_sampled(params, tokens, caches, active, n_draft,
                            sampling):
        def pred_fn(lg):
            pred = sample_tokens_block(lg, sampling["key"], sampling["step"],
                                       sampling["temperature"],
                                       sampling["top_k"], sampling["top_p"])
            return _vguard(lg, pred)
        return _verify(params, tokens, caches, active, n_draft, pred_fn)

    def _mk(body, n_args):
        # TP verify: the whole one-pass score/accept/commit tick runs in a
        # fully-manual shard_map (same shape as the decode tick's _mk)
        if tps is None:
            return body
        ins = (tps.pspecs, P(), tps.cspecs) + (P(),) * (n_args - 3)
        return shard_map(body, mesh=mesh, in_specs=ins,
                         out_specs=(P(),) * (2 + moe_counts)
                         + (tps.cspecs,),
                         axis_names=set(mesh.axis_names), check_vma=False)

    # the same greedy/sampled split as make_serve_step: the default path
    # never compiles the sampler's full-vocab sorts
    out_sh = ((NamedSharding(mesh, P()),) * (2 + moe_counts)
              + (_named(mesh, cspecs),))
    greedy_step = jax.jit(
        _mk(verify_step, 5),
        in_shardings=(_named(mesh, pspecs), None, _named(mesh, cspecs),
                      None, None),
        out_shardings=out_sh, donate_argnums=(2,))
    sampled_step = jax.jit(
        _mk(verify_step_sampled, 6),
        in_shardings=(_named(mesh, pspecs), None, _named(mesh, cspecs),
                      None, None, None),
        out_shardings=out_sh, donate_argnums=(2,))

    def step(params, tokens, caches, active, n_draft, sampling=None):
        if sampling is None:
            return greedy_step(params, tokens, caches, active, n_draft)
        return sampled_step(params, tokens, caches, active, n_draft,
                            sampling)

    return step, {"params": pspecs, "cache": cspecs}
