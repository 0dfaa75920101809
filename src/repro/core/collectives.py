"""Public collective API: every reduction in the framework goes through here.

The central entry points are :func:`all_reduce` (flat vectors) and
:func:`bucketed_all_reduce` (gradient pytrees). Algorithm selection follows the
paper's experimental lesson — Table 2 shows OpenMPI collapsing in the mid-range
because of a bad internal algorithm switch — so the ``auto`` method picks the
algorithm *and* the pipeline block count from the alpha-beta cost model
(:mod:`repro.core.cost_model`), and both can be overridden per call site.

Must be called inside a ``shard_map`` that is manual over ``axis_name``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import autotune
from repro.core import cost_model as cm
from repro.core.dptree import (_COMMUTATIVE_OPS, dptree_allreduce,
                               hier_allreduce, redbcast_allreduce,
                               ring_allreduce, sptree_allreduce, tree_rounds)
from repro.core.topology import build_dual_tree, build_single_tree
from repro.obs import probe as _obs_probe

__all__ = [
    "CollectiveConfig",
    "Plan",
    "plan",
    "all_reduce",
    "bucketed_all_reduce",
    "structured_all_reduce",
    "bucket_sizes",
]


@dataclasses.dataclass(frozen=True)
class _Method:
    """One runnable method: ``run(x, axis_name, p, plan, op, carry_spec)``
    and the operators it accepts (None: any associative op). An explicit
    request with another op raises in :func:`plan` only when ``strict``.
    ``tree(p)`` is the topology a tree-engine method runs over."""

    run: Callable[..., jax.Array]
    ops: Any = None
    strict: bool = False
    tree: Callable[[int], Any] | None = None


# XLA primitive equivalent per supported elementwise op (psum-family).
_PRIMITIVE_REDUCE = {jnp.add: jax.lax.psum, jnp.maximum: jax.lax.pmax,
                     jnp.minimum: jax.lax.pmin}


def _psum(x, axis_name, p, pl, op, carry_spec):
    # route through the matching primitive: psum with op=max would silently
    # sum.
    try:
        prim = _PRIMITIVE_REDUCE[op]
    except KeyError:
        raise ValueError(
            "method='psum' supports only jnp.add/maximum/minimum ops; "
            "use a schedule-based method for custom operators") from None
    return prim(x, axis_name)


# THE table of runnable methods. A method is added or removed here and in
# ``cost_model.ALGORITHMS`` (which prices every method but psum).
_RUNNERS = {
    "dptree": _Method(lambda x, ax, p, pl, op, carry_spec: dptree_allreduce(
        x, ax, p, num_blocks=pl.num_blocks, op=op, carry_spec=carry_spec),
        tree=build_dual_tree),
    "sptree": _Method(lambda x, ax, p, pl, op, carry_spec: sptree_allreduce(
        x, ax, p, num_blocks=pl.num_blocks, op=op, carry_spec=carry_spec),
        tree=build_single_tree),
    "redbcast": _Method(
        lambda x, ax, p, pl, op, carry_spec: redbcast_allreduce(
            x, ax, p, num_blocks=pl.num_blocks, op=op)),
    "ring": _Method(lambda x, ax, p, pl, op, carry_spec: ring_allreduce(
        x, ax, p, op=op), _COMMUTATIVE_OPS),
    "hier": _Method(lambda x, ax, p, pl, op, carry_spec: hier_allreduce(
        x, ax, p, group_size=pl.group_size, num_blocks=pl.num_blocks, op=op,
        carry_spec=carry_spec, compress_inter_group=pl.compress),
        _COMMUTATIVE_OPS, strict=True),
    "psum": _Method(_psum, _PRIMITIVE_REDUCE),
}


def _hierarchical(algo: str) -> bool:
    """A hierarchy, as its pricing says (it pipelines a stage): it replays an
    autotune hit's level spec, may ride the bf16 inter-group wire and notes
    its own probe sample."""
    pricing = cm.ALGORITHMS.get(algo)
    return pricing is not None and pricing.stage is not None


METHODS = ("auto", *_RUNNERS)


@dataclasses.dataclass(frozen=True)
class CollectiveConfig:
    """How gradient/activation reductions are executed.

    ``method``       one of METHODS. ``auto`` = measured-autotuner hit if one
                     exists for (p, bytes, dtype, fabric), else the cost-model
                     switch per size.
    ``num_blocks``   pipeline block count; None = Pipelining-Lemma optimum
                     refined by local descent (and by the autotuner's measured
                     pick under ``auto``).
    ``compression``  None | 'bf16' — cast the WHOLE payload before any wire,
                     cast back at the end (every stage rides bf16). For the
                     hierarchical slow-stage-only variant with f32
                     accumulation, use ``compress_inter_group`` instead.
    ``bucket_bytes`` split grad pytrees into buckets of at most this many
                     bytes; XLA's scheduler can overlap bucket k's collective
                     with bucket k+1's producers.
    ``comm_model``   alpha-beta constants for the INTER-group (slowest) fabric,
                     used by the auto switch/tuner.
    ``group_size``   hierarchy spec for the hierarchical method: ranks per
                     fast-link group (int), or a tuple of per-level ring
                     sizes innermost-first for 3+-level shapes (e.g.
                     ``(4, 2)`` = chip ring, node ring, dual tree over pods);
                     None = 4, then 2, then flat. Also gates whether 'hier'
                     competes in the ``auto`` switch.
    ``intra_model``  alpha-beta constants for the intra-group fast links
                     (every intra level; the cost model also accepts
                     per-level models, see ``cost_model.hier_time``).
    ``compress_inter_group``
                     hierarchical method only: bf16-compress the slow
                     inter-group stage's wire (intra stages and the final
                     result stay full precision; tree combines accumulate in
                     f32). Lossy — the autotuner times it as extra candidates
                     only when this flag opts in.
    """

    method: str = "dptree"
    num_blocks: int | None = None
    compression: str | None = None
    bucket_bytes: int = 1 << 30
    comm_model: cm.CommModel = cm.TPU_V5E
    group_size: int | tuple | None = None
    intra_model: cm.CommModel = cm.TPU_V5E
    compress_inter_group: bool = False

    def __post_init__(self):
        if self.method not in METHODS:
            raise ValueError(f"unknown method {self.method!r}; want {METHODS}")
        if self.compression not in (None, "bf16"):
            raise ValueError(f"unknown compression {self.compression!r}")
        if isinstance(self.group_size, (list, tuple)):
            object.__setattr__(self, "group_size",
                               tuple(int(s) for s in self.group_size))


@dataclasses.dataclass(frozen=True)
class Plan:
    """How one reduction runs: the algorithm, its pipeline block count, the
    hierarchy spec handed to a hierarchical runner (int, level tuple or
    None), and whether its slow inter-group stage rides the bf16 wire."""

    algorithm: str
    num_blocks: int
    group_size: int | tuple | None
    compress: bool


def _auto_choice(config: CollectiveConfig, p: int, nbytes: int, dtype,
                 axis_name: str | None) -> tuple:
    """``auto``'s ``(algorithm, measured_num_blocks | None, group_size,
    compress)``: a replayable autotune hit, else the cost-model switch."""
    # Empirical closed loop first: a measured (algorithm, blocks) for this
    # exact (p, bytes, dtype, fabric, axis) beats any model prediction — but
    # only if the recorded setting is actually runnable here ('auto' must
    # degrade, never raise, on a stale or foreign cache entry).
    hit = autotune.lookup(p, int(max(nbytes, 1)), str(dtype),
                          config.comm_model.name, axis=axis_name)
    if hit is not None and hit.algorithm in _RUNNERS:
        if not _hierarchical(hit.algorithm):
            return hit.algorithm, max(1, int(hit.num_blocks)), None, False
        # Replay ONLY the configuration the entry was measured with: the
        # exact group shape, and compression only if (a) it was timed
        # compressed and (b) this config opts into the lossy wire. An entry
        # without a shape (old schema), with an infeasible shape, or timed
        # compressed without local opt-in is stale here — fall through to
        # the model rather than execute an un-measured or un-consented
        # configuration.
        from repro.core.topology import resolve_levels
        lv = (resolve_levels(p, hit.group_size)
              if hit.group_size is not None else None)
        if lv is not None and (not hit.compressed
                               or config.compress_inter_group):
            return hit.algorithm, max(1, int(hit.num_blocks)), lv, \
                hit.compressed
    # psum is XLA's own allreduce; we only auto-pick among algorithms whose
    # cost we model. The paper's point stands: never let the library guess.
    algo = cm.best_algorithm(p, float(max(nbytes, 1)), config.comm_model,
                             group_size=config.group_size,
                             intra_model=config.intra_model)
    return (algo, None, config.group_size,
            _hierarchical(algo) and config.compress_inter_group)


def plan(config: CollectiveConfig, p: int, nbytes: int, dtype,
         op: Callable = jnp.add, axis_name: str | None = None) -> Plan:
    """How :func:`all_reduce` runs one ``nbytes`` reduction of ``dtype``
    over ``p`` ranks under ``op``, resolved on the host in three steps:

    1. the method: an explicit one as given; under ``auto`` an autotune hit
       for ``(p, nbytes, dtype, fabric, axis_name)`` runnable here, else
       ``cost_model.best_algorithm``. ``axis_name`` keeps TP, gradient and
       stats tunings apart (axis-less entries match any axis);
    2. the operator: a pick that cannot run ``op`` degrades under ``auto``
       to the rank-ordered dptree and drops its measured block count (auto
       never raises); an explicit strict method raises;
    3. the block count: the config's, else the measured one, else the
       Pipelining-Lemma optimum for a pipelined algorithm, else 1.
    """
    if config.method != "auto":
        algo, measured = config.method, None
        spec, compress = config.group_size, config.compress_inter_group
    else:
        algo, measured, spec, compress = _auto_choice(config, p, nbytes,
                                                      dtype, axis_name)
    method = _RUNNERS[algo]
    if method.ops is not None and op not in method.ops:
        if config.method == "auto":
            algo, measured = "dptree", None
        elif method.strict:
            raise ValueError(
                f"method={algo!r} requires a commutative op (jnp.add/"
                "maximum/minimum/multiply); use dptree for merely-"
                "associative ops")
    if config.num_blocks is not None:
        nb = int(config.num_blocks)
    elif measured is not None:
        nb = measured
    elif algo in cm.ALGORITHMS and cm.ALGORITHMS[algo].form is not None:
        nb = cm.optimal_blocks(p, float(max(nbytes, 1)), config.comm_model,
                               algo, group_size=spec,
                               compression="bf16" if compress else None)
    else:
        nb = 1
    return Plan(algo, nb, spec, compress)


def _lane_shard(x: jax.Array) -> jax.Array:
    """Keep 2-D (rows, lanes) payloads sharded on the lane dim over the (auto)
    'model' axis. No-op outside a mesh or when 'model' is absent."""
    if x.ndim != 2:
        return x
    from repro.models.layers import maybe_shard  # local: avoids import cycle
    from jax.sharding import PartitionSpec as _P
    return maybe_shard(x, _P(None, "model"))


def all_reduce(x: jax.Array, axis_name: str, p: int,
               config: CollectiveConfig = CollectiveConfig(),
               op: Callable = jnp.add,
               shard_spec=None) -> jax.Array:
    """Allreduce an array over ``axis_name``: the reduction over all ``p``
    devices of the axis lands on every device.

    Must be called inside a ``shard_map`` manual over ``axis_name``. The
    algorithm, pipeline block count, hierarchy shape, and compression all
    come from ``config`` through :func:`plan`; ``op`` must be associative,
    and the ring-order methods (``ring``/``hier``) additionally require
    commutativity — under ``auto`` unsupported picks silently degrade to the
    rank-ordered dptree, an explicit ``hier`` raises.

    Payload layout: 1-D payloads pipeline directly; 2-D ``(rows, lanes)``
    payloads pipeline over rows with the lane dim left to GSPMD (the
    gradient-bucket layout: lanes shard over 'model' so no buffer is ever
    replicated). Higher-rank payloads pipeline over dim 0 *without
    flattening* — flattening a tensor with GSPMD-sharded trailing dims would
    all-gather it to full size — and ``shard_spec`` (the leaf's own
    PartitionSpec) is pinned on the scan carry.
    """
    if p == 1:
        return x
    shape, dtype = x.shape, x.dtype
    carry_spec = None
    if x.ndim <= 1:
        flat = x.reshape(-1)
    elif x.ndim == 2:
        flat = _lane_shard(x)
    else:
        flat = x
        if shard_spec is not None:
            from jax.sharding import PartitionSpec as _P
            entries = list(shard_spec) + [None] * (x.ndim - len(shard_spec))
            carry_spec = _P(None, *entries)   # blockify splits dim 0
    if config.compression == "bf16" and flat.dtype == jnp.float32:
        flat = flat.astype(jnp.bfloat16)
    nbytes = flat.size * flat.dtype.itemsize
    pl = plan(config, p, nbytes, flat.dtype, op, axis_name)
    probe = _obs_probe.active()
    if probe is not None and not _hierarchical(pl.algorithm):
        # Trace-time note: this Python body runs once per compilation, so
        # the sample records WHAT was picked (algorithm, blocks, shape) —
        # wall time comes from host-boundary timed samples (repro.obs.probe).
        tree = _RUNNERS[pl.algorithm].tree
        rounds = None if tree is None else tree_rounds(
            tree(p), max(1, min(pl.num_blocks, flat.shape[0])), op)
        probe.note(pl.algorithm, p, nbytes, pl.num_blocks,
                   dtype=str(flat.dtype), kind="trace", levels=pl.group_size,
                   axis=axis_name, rounds=rounds)
    out = _RUNNERS[pl.algorithm].run(flat, axis_name, p, pl, op, carry_spec)
    if out.ndim == 2:
        out = _lane_shard(out)
    return out.astype(dtype).reshape(shape)


def bucketed_all_reduce(tree: Any, axis_name: str, p: int,
                        config: CollectiveConfig = CollectiveConfig(),
                        leaf_specs: Any = None) -> Any:
    """Gradient-pytree allreduce with flat bucketing.

    Leaves are grouped by dtype, concatenated into contiguous buckets of at
    most ``config.bucket_bytes``, reduced as single long vectors (the paper's
    ``m``), and scattered back. One long pipelined vector amortizes the latency
    term far better than per-tensor reductions — this is the framework analogue
    of the paper reducing one m-element vector. ``bucket_bytes`` also bounds
    the replicated concat buffer per chip.

    ``leaf_specs`` (optional PartitionSpec pytree matching ``tree``) re-pins
    each reduced leaf to its original GSPMD sharding — without it the slices
    of the (replicated) bucket would leave the whole gradient tree replicated.
    """
    if p == 1:
        return tree
    from repro.models.layers import maybe_shard  # local: avoids import cycle
    leaves, treedef = jax.tree.flatten(tree)
    specs = (jax.tree.leaves(leaf_specs,
                             is_leaf=lambda v: isinstance(v, jax.sharding.PartitionSpec))
             if leaf_specs is not None else [None] * len(leaves))
    out = [None] * len(leaves)
    n_model = _mesh_axis_size("model")

    # Partition leaves into: model-sharded (shard-major bucket), replicated
    # (plain flat bucket), and other-sharded (reduced per leaf, no bucketing).
    # Shard-major layout: moveaxis the 'model' dim first, split it into
    # (n_model, S/n_model * rest) — every reshape is partition-LOCAL, so no
    # leaf is ever gathered to full size just to enter a bucket (flattening a
    # sharded tensor directly would all-gather it: element order interleaves).
    by_kind = {"model": [], "repl": [], "other": []}
    for k in range(len(leaves)):
        d = _model_dim(leaves[k], specs[k], n_model)
        if d is None:
            by_kind["repl"].append(k)
        elif d < 0:
            by_kind["other"].append(k)
        else:
            by_kind["model"].append((k, d))

    for k in by_kind["other"]:
        red = all_reduce(leaves[k], axis_name, p, config,
                         shard_spec=specs[k])
        out[k] = maybe_shard(red, specs[k]) if specs[k] is not None else red

    def buckets(items, size_of):
        return _bucket_groups(items, size_of, config.bucket_bytes)

    # --- model-sharded leaves: (n_model, L) pieces, concat on dim 1 --------
    for group in buckets(by_kind["model"],
                         lambda it: (leaves[it[0]].size, leaves[it[0]].dtype)):
        pieces = []
        for k, d in group:
            v = jnp.moveaxis(leaves[k], d, 0)
            v = v.reshape(n_model, v.size // n_model)
            pieces.append(maybe_shard(v, jax.sharding.PartitionSpec("model")))
        mat = jnp.concatenate(pieces, axis=1) if len(pieces) > 1 else pieces[0]
        # pipeline over the unsharded dim: (L_total, n_model) lanes-sharded
        mat = maybe_shard(mat.T, jax.sharding.PartitionSpec(None, "model"))
        red = all_reduce(mat, axis_name, p, config)
        red = maybe_shard(red, jax.sharding.PartitionSpec(None, "model")).T
        red = maybe_shard(red, jax.sharding.PartitionSpec("model"))
        off = 0
        for k, d in group:
            n = leaves[k].size // n_model
            shp = leaves[k].shape
            v = red[:, off:off + n].reshape(
                (shp[d],) + shp[:d] + shp[d + 1:])
            leaf = jnp.moveaxis(v, 0, d)
            out[k] = maybe_shard(leaf, specs[k]) if specs[k] is not None \
                else leaf
            off += n

    # --- replicated leaves: plain flat bucket ------------------------------
    for group in buckets(by_kind["repl"],
                         lambda k: (leaves[k].size, leaves[k].dtype)):
        flat = jnp.concatenate([leaves[k].reshape(-1) for k in group]) \
            if len(group) > 1 else leaves[group[0]].reshape(-1)
        red = all_reduce(flat, axis_name, p, config)
        off = 0
        for k in group:
            n = leaves[k].size
            out[k] = red[off:off + n].reshape(leaves[k].shape)
            off += n
    return jax.tree.unflatten(treedef, out)


def _bucket_groups(items, size_of, bucket_bytes):
    """Greedy dtype-homogeneous bucketing shared by :func:`bucketed_all_reduce`
    and :func:`bucket_sizes`. ``size_of(item) -> (nelems, dtype)``."""
    items = sorted(items, key=lambda it: str(size_of(it)[1]))
    i = 0
    while i < len(items):
        dt = size_of(items[i])[1]
        group, sz = [], 0
        while i < len(items) and size_of(items[i])[1] == dt \
                and (not group or sz < bucket_bytes):
            group.append(items[i])
            sz += size_of(items[i])[0] * dt.itemsize
            i += 1
        yield group


def _model_dim(leaf, spec, n_model):
    """Index of the leaf dim sharded exactly over 'model' (shard-major bucket
    member), None for replicated leaves, -1 for any other sharding (per-leaf
    reduction). THE single classifier — :func:`bucketed_all_reduce` and
    :func:`bucket_sizes` must agree on it or warm-up-measured sizes would
    miss the trace-time cache keys."""
    if spec is None or n_model is None:
        return None
    entries = list(spec) + [None] * (leaf.ndim - len(spec))
    for d, e in enumerate(entries[:leaf.ndim]):
        names = e if isinstance(e, tuple) else ((e,) if e else ())
        if names == ("model",) and leaf.shape[d] % n_model == 0:
            return d
        if names and names != ("model",):
            return -1  # sharded some other way -> per-leaf path
    return None


def bucket_sizes(tree: Any, bucket_bytes: int = 1 << 30,
                 leaf_specs: Any = None, n_model: int | None = None) -> list:
    """The ``(nelems, dtype)`` of each reduction :func:`bucketed_all_reduce`
    would issue for this pytree — the vector lengths a per-mesh autotune
    warm-up should measure. Accepts concrete arrays or ``jax.eval_shape``
    structs.

    Mirrors the reduce path exactly: leaves are first partitioned by
    sharding kind (``leaf_specs`` + ``n_model``, the 'model' axis size —
    the same inputs ``bucketed_all_reduce`` classifies with), then
    model-sharded and replicated kinds are greedily bucketed per dtype
    while other-sharded leaves are reduced per leaf. Without
    ``leaf_specs``/``n_model`` every leaf counts as replicated — correct
    for meshes with no (or trivial) 'model' axis.
    """
    leaves = jax.tree.leaves(tree)
    specs = (jax.tree.leaves(leaf_specs,
                             is_leaf=lambda v: isinstance(v, jax.sharding.PartitionSpec))
             if leaf_specs is not None else [None] * len(leaves))
    by_kind = {"model": [], "repl": [], "other": []}
    for k in range(len(leaves)):
        d = _model_dim(leaves[k], specs[k], n_model)
        kind = "repl" if d is None else ("other" if d < 0 else "model")
        by_kind[kind].append(k)
    out = [(int(leaves[k].size), jnp.dtype(leaves[k].dtype))
           for k in by_kind["other"]]
    for kind in ("model", "repl"):
        for group in _bucket_groups(
                by_kind[kind],
                lambda k: (leaves[k].size, jnp.dtype(leaves[k].dtype)),
                bucket_bytes):
            n = sum(leaves[k].size for k in group)
            out.append((int(n), jnp.dtype(leaves[group[0]].dtype)))
    return out


def _mesh_axis_size(name: str) -> int | None:
    env = jax.sharding.get_abstract_mesh()
    if env.empty:
        return None
    shape = dict(env.shape_tuple)
    return shape.get(name)


def structured_all_reduce(tree: Any, axis_name: str, p: int,
                          combine: Callable[[Any, Any], Any],
                          method: str = "dptree") -> Any:
    """Latency-critical allreduce of a *structured* value under a custom
    associative ``combine`` (e.g. flash-decoding softmax partials: (max, sum,
    out) triples). Uses a single pipeline block (b=1), where the dual-root tree
    is the log-latency optimum — the regime the paper's algorithm targets.

    ``combine(a, b)`` takes and returns pytrees shaped like ``tree``;
    ``method`` must be one that runs any associative operator.
    """
    if p == 1:
        return tree
    leaves, treedef = jax.tree.flatten(tree)
    sizes = [l.size for l in leaves]
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    wide = jnp.result_type(*dtypes)
    flat = jnp.concatenate([l.astype(wide).reshape(-1) for l in leaves])

    def unpack(v):
        out, off = [], 0
        for s, sh, dt in zip(sizes, shapes, dtypes):
            out.append(v[off:off + s].reshape(sh).astype(dt))
            off += s
        return jax.tree.unflatten(treedef, out)

    def pack(t):
        ls = jax.tree.leaves(t)
        return jnp.concatenate([l.astype(wide).reshape(-1) for l in ls])

    def op(a, b):
        return pack(combine(unpack(a), unpack(b)))

    runner = _RUNNERS[method]
    if runner.ops is not None:
        raise ValueError("structured_all_reduce needs a method that runs "
                         "any associative combine")
    red = runner.run(flat, axis_name, p, Plan(method, 1, None, False), op,
                     None)
    return unpack(red)
