"""JAX (shard_map + ppermute) implementations of the paper's collectives.

All functions here must be called *inside* a ``jax.shard_map`` region that is
manual over ``axis_name``. Device-varying control is expressed with
``axis_index`` + gathers from host-built topology constants; the three
static edge classes become up to three pairs of ``ppermute`` permutations
inside a ``lax.scan``.

The tree engine has two schedules, chosen by the operator:

* commutative operators (add, max, min, mul) run *concurrent rounds*
  (:func:`_rounds_allreduce`): each round issues every edge class's up and
  down permutations together, each carrying a block finished in an earlier
  round, so a node's links to its children, its parent and the other root
  all move at once; the blocks that arrived in the previous round are folded
  in while these are on the wire, the children's partials and the node's own
  block in one elementwise pass that XLA fuses into the block's in-place
  write. About ``b + 4 * max_depth`` rounds
  (:meth:`~repro.core.topology.TreeTopology.num_rounds`).
* merely associative operators keep the paper's one-ported schedule in its
  exact seed order (Algorithm 1's ``t (.) Y`` / lower-root ``Y (.) t``
  rules): per macro-round one step per active edge class, each step's
  transfer waiting on the previous step's write.

Both mask their writes into a scratch block row ``b`` instead of
read-modifying the current value, so an idle write costs no extra read.
(The concurrent round's slice budget is pinned by
``test_fused_engine_hlo_slice_count``.)

Block layout (:func:`_blockify`): the ``b`` blocks and a scratch block lie in
one buffer with the block index first. A TPU tiles an array's two minor dims
in (8, 128) tiles, so a 1-D payload cut as ``(b + 1, m/b)`` would put the
block index among the tiled dims: each one-block slice or update would touch
eight blocks' tiles, and the vector would enter and leave through relayout
copies. A 1-D payload under an elementwise operator whose blocks span at
least one tile is therefore laid out block-major, ``(b + 1, blk/128, 128)``
with ``blk`` rounded up to whole tiles: one pad in, bitcasts in and out, and
each take or put touches its own block only. Shorter blocks, 2-D lane-sharded
and higher-rank payloads, and unknown operators keep ``(b + 1, blk, ...)``.

Implemented algorithms:

* :func:`dptree_allreduce`  — doubly-pipelined dual-root (the paper, Alg. 1)
* :func:`sptree_allreduce`  — single-tree doubly-pipelined variant (§1.2)
* :func:`redbcast_allreduce`— pipelined reduce + pipelined bcast (User-Allreduce1)
* :func:`ring_allreduce`    — bidirectional ring reduce-scatter + all-gather
* :func:`hier_allreduce`    — hierarchical (2..N levels): per-level ring
  reduce-scatter down, dptree over shard stripes at the slowest level
  (optionally on a bf16 wire with f32 accumulation), per-level all-gather up
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.topology import (NO_NODE, HierarchicalTopology, TreeTopology,
                                 build_dual_tree, build_hierarchy,
                                 build_single_tree, mirror_upper_trees)

__all__ = [
    "tree_rounds",
    "dptree_allreduce",
    "sptree_allreduce",
    "redbcast_allreduce",
    "ring_allreduce",
    "hier_allreduce",
]

Op = Callable[[jax.Array, jax.Array], jax.Array]

# Operators the concurrent rounds may reassociate and commute, by kernel name.
_COMMUTATIVE_OPS = {jnp.add: "add", jnp.maximum: "max", jnp.minimum: "min",
                    jnp.multiply: "mul"}


def _op_identity(op_name: str, dtype) -> jax.Array:
    if op_name == "add":
        return jnp.zeros((), dtype)
    if op_name == "mul":
        return jnp.ones((), dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        # True infinities, not finfo.min/max: payloads legitimately contain
        # -inf (masked logits), which must win against the identity.
        return jnp.asarray(-jnp.inf if op_name == "max" else jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.min if op_name == "max" else info.max, dtype)


# One native TPU tile is (8, 128) 32-bit words: 1024 f32, 2048 bf16 (packed
# two to a word), 4096 int8 elements.
_LANES = 128
_TILE_BYTES = 8 * _LANES * 4


def _blockify(x: jax.Array, b: int, elementwise: bool) -> tuple:
    """Split dim 0 into ``b`` pipeline blocks plus a zero scratch block row
    ``b``, from ONE pad of ``x``: ``(Y, m)``, ``Y`` shaped ``(b + 1, ...)``.

    Block-major ``(b + 1, blk / 128, 128)`` for a 1-D payload under an
    ``elementwise`` operator whose blocks span a tile (module docstring);
    else ``(b + 1, blk, ...)``: 2-D ``(R, W)`` payloads keep their
    GSPMD-sharded trailing lanes, an unknown operator sees exactly the
    blocks it was given, and a block shorter than a tile is not padded to
    one (that would only add wire bytes)."""
    m = x.shape[0]
    blk = -(-m // b)
    tile = _TILE_BYTES // x.dtype.itemsize
    block_major = elementwise and x.ndim == 1 and blk >= tile
    if block_major:
        blk = -(-blk // tile) * tile
    x = jnp.pad(x, [(0, (b + 1) * blk - m)] + [(0, 0)] * (x.ndim - 1))
    if block_major:
        return x.reshape(b + 1, blk // _LANES, _LANES), m
    return x.reshape((b + 1, blk) + x.shape[1:]), m


def _unblockify(Y: jax.Array, m: int, trail: tuple) -> jax.Array:
    """The first ``m`` rows of the blocks, scratch row dropped: one slice
    (after a bitcast in the block-major layout)."""
    return Y.reshape((-1,) + trail)[:m]


def _const(arr: np.ndarray, i: jax.Array) -> jax.Array:
    """Per-device lookup into a host-built topology constant."""
    return jnp.asarray(arr)[i]


def _varying(x: jax.Array, axis_name: str) -> jax.Array:
    """Mark a scan's initial carry as varying over ``axis_name``: the body's
    ppermutes make the carry it returns device-varying, and ``lax.scan``
    requires the carry to enter with the type it leaves with (a replicated
    payload or an identity fill otherwise fails to trace)."""
    if axis_name in jax.typeof(x).vma:
        return x
    return jax.lax.pcast(x, (axis_name,), to="varying")


def _pin_lanes(x: jax.Array, spec=None) -> jax.Array:
    """Pin the carry sharding INSIDE scan bodies — GSPMD does not reliably
    propagate it into while-loops, and an unpinned carry replicates the whole
    bucket on every chip. ``spec`` (a PartitionSpec over the blockified carry
    dims) overrides the default lanes-over-'model' heuristic."""
    if x.ndim < 2:
        return x
    from jax.sharding import PartitionSpec as _P
    from repro.models.layers import maybe_shard  # lazy: no import cycle
    if spec is None:
        spec = _P(*([None] * (x.ndim - 1) + ["model"]))
    return maybe_shard(x, spec)


def _lane_pinner(Y: jax.Array, x: jax.Array):
    """``_pin_lanes`` for blocks that keep ``x``'s trailing dims; a no-op for
    the block-major layout of a 1-D payload, which has no lanes to pin."""
    if Y.ndim == x.ndim + 1:
        return _pin_lanes
    return lambda v, spec=None: v


def _take(Y: jax.Array, idx: jax.Array, b: int) -> jax.Array:
    # dynamic_slice, not gather: scalar-index gathers over arrays with
    # GSPMD-sharded trailing dims crash XLA's gather partitioner at
    # high device counts; dynamic-slice partitions cleanly. Reads clip to
    # the real blocks [0, b-1]; the scratch row b is write-only.
    return jax.lax.dynamic_slice_in_dim(
        Y, jnp.clip(idx, 0, b - 1), 1, axis=0)[0]


def _put(Y: jax.Array, val: jax.Array, row: jax.Array) -> jax.Array:
    return jax.lax.dynamic_update_slice(Y, val[None],
                                        (row,) + (0,) * (Y.ndim - 1))


# --------------------------------------------------------------------------
# Shared ring machinery. ring_allreduce runs it over the whole axis
# (idx = rank, size = p); hier_allreduce runs it within each group
# (idx = local rank, size = group_size, per-group perms). One schedule and
# one chunk layout, one implementation — a fix to either applies to both.
# --------------------------------------------------------------------------

def _ring_layout(x: jax.Array, n: int, bidirectional: bool) -> tuple:
    """Chunk a vector for an n-way ring: (halves, chunk, m, trail).

    An odd per-rank chunk is padded up to even under ``bidirectional`` so the
    two opposite-direction half-schedules move the same byte count (unequal
    halves would make one direction the straggler on every step).
    """
    m = x.shape[0]
    trail = x.shape[1:]
    chunk = -(-m // n)
    if bidirectional and chunk >= 2 and chunk % 2:
        chunk += 1
    pad = n * chunk - m
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + trail, x.dtype)], axis=0)
    X = x.reshape((n, chunk) + trail)
    halves = ([X[:, :chunk // 2], X[:, chunk // 2:]]
              if (bidirectional and chunk >= 2) else [X])
    return halves, chunk, m, trail


def _ring_unlayout(out_halves, n: int, chunk: int, m: int, trail) -> jax.Array:
    X = (jnp.concatenate(out_halves, axis=1) if len(out_halves) > 1
         else out_halves[0])
    return X.reshape((n * chunk,) + trail)[:m]

def _ring_reduce_scatter(H, axis_name, idx, size, perm, sg, op,
                         carry_spec=None):
    """size-1 steps; afterwards the chunk ``mod(idx + sg, size)`` is fully
    reduced on this rank."""
    def rs_step(H, t):
        send_idx = jnp.mod(idx - sg * t, size)
        buf = jax.lax.dynamic_slice_in_dim(H, send_idx, 1, axis=0)[0]
        buf = jax.lax.ppermute(buf, axis_name, perm)
        recv_idx = jnp.mod(idx - sg * (t + 1), size)
        cur = jax.lax.dynamic_slice_in_dim(H, recv_idx, 1, axis=0)[0]
        return jax.lax.dynamic_update_slice(
            H, op(cur, buf)[None], (recv_idx,) + (0,) * (H.ndim - 1))

    H, _ = jax.lax.scan(
        lambda hh, t: (_pin_lanes(rs_step(hh, t), carry_spec), ()),
        _varying(_pin_lanes(H, carry_spec), axis_name),
        jnp.arange(size - 1, dtype=jnp.int32))
    return H


def _ring_all_gather(H, axis_name, idx, size, perm, sg, carry_spec=None):
    """size-1 steps circulating each rank's owned chunk ``mod(idx+sg, size)``."""
    def ag_step(H, t):
        send_idx = jnp.mod(idx + sg * (1 - t), size)
        buf = jax.lax.dynamic_slice_in_dim(H, send_idx, 1, axis=0)[0]
        buf = jax.lax.ppermute(buf, axis_name, perm)
        recv_idx = jnp.mod(idx - sg * t, size)
        return jax.lax.dynamic_update_slice(
            H, buf[None], (recv_idx,) + (0,) * (H.ndim - 1))

    H, _ = jax.lax.scan(
        lambda hh, t: (_pin_lanes(ag_step(hh, t), carry_spec), ()),
        _varying(_pin_lanes(H, carry_spec), axis_name),
        jnp.arange(size - 1, dtype=jnp.int32))
    return H


def _commutes(op: Op, op_rev: Op | None = None) -> bool:
    """Whether the tree engine may run the concurrent rounds for ``op``: an
    operator it knows to commute, with no separate reversed form."""
    return (op_rev or op) is op and op in _COMMUTATIVE_OPS


def _tree_allreduce(x: jax.Array, axis_name: str, topo: TreeTopology,
                    num_blocks: int, op: Op, op_rev: Op | None,
                    carry_spec=None) -> jax.Array:
    """Shared engine for the dual-root and single-tree variants: the
    concurrent rounds for commutative operators, else the seed-ordered
    steps."""
    p = topo.p
    if p == 1:
        return x
    b = int(num_blocks)
    if _commutes(op, op_rev):
        return _rounds_allreduce(x, axis_name, topo, b, op, carry_spec)
    op_rev = op_rev or op
    # Scratch block row b: masked writes land here instead of paying a
    # read-modify-write of the current value (two extra dynamic slices).
    Y, m = _blockify(x, b, elementwise=False)
    pin = _lane_pinner(Y, x)

    i = jax.lax.axis_index(axis_name)
    phi = _const(topo.phi, i)
    dep = _const(topo.depth, i)
    has_c0 = _const(topo.child0 != NO_NODE, i)
    has_c1 = _const(topo.child1 != NO_NODE, i)
    has_par = _const(topo.parent != NO_NODE, i)
    is_root = _const(topo.parent == NO_NODE, i)
    is_lower_root = is_root & (_const(topo.tree_id, i) == 0)
    dual_active = topo.dual and len(topo.roots) == 2

    classes = topo.active_classes()
    R = topo.num_macro_rounds(b)
    in_range = lambda j: (j >= 0) & (j < b)

    def step_general(Y, s, e):
        """Seed-ordered path for non-commutative operators (Alg. 1 rules)."""
        rel = s - phi
        mod = jnp.mod(rel, 3)
        jA = jnp.floor_divide(rel, 3)
        jB = jnp.floor_divide(rel - 1, 3)
        jC = jnp.floor_divide(rel - 2, 3)
        amA = (mod == 0) & has_c0
        amB = (mod == 1) & has_c1
        amC_par = (mod == 2) & has_par
        amC_root = (mod == 2) & is_root & dual_active
        amAB = amA | amB
        jAB = jnp.where(amA, jA, jB)

        up_out = _take(Y, jC, b)          # C-role payload AND current block
        down_out = _take(Y, jAB - dep - 1, b)
        t_up = jax.lax.ppermute(up_out, axis_name, topo.up_pairs[e])
        t_down = (jax.lax.ppermute(down_out, axis_name, topo.down_pairs[e])
                  if topo.down_pairs[e] else jnp.zeros_like(down_out))
        cur_ab = _take(Y, jAB, b)
        red_ab = op(t_up, cur_ab)         # Alg. 1 lines 4/6: t (.) Y
        red_root = jnp.where(is_lower_root, op_rev(up_out, t_up),  # Y (.) t
                             op(t_up, up_out))                     # t (.) Y
        jRecv = jC - dep
        upd_idx = jnp.where(amAB, jAB, jnp.where(amC_root, jC, jRecv))
        upd_val = jnp.where(amAB, red_ab,
                            jnp.where(amC_root, red_root, t_down))
        do_upd = ((amAB & in_range(jAB))
                  | (amC_root & in_range(jC))
                  | (amC_par & in_range(jRecv)))
        row = jnp.where(do_upd, jnp.clip(upd_idx, 0, b - 1), b)
        return _put(Y, upd_val, row)

    def macro_round(Y, r):
        s0 = 3 * r
        for e in classes:
            Y = step_general(Y, s0 + e, e)
        return pin(Y, carry_spec), ()

    Y, _ = jax.lax.scan(macro_round,
                        _varying(pin(Y, carry_spec), axis_name),
                        jnp.arange(R, dtype=jnp.int32))
    return _unblockify(Y, m, x.shape[1:])


def _rounds_allreduce(x: jax.Array, axis_name: str, topo: TreeTopology,
                      b: int, op: Op, carry_spec=None) -> jax.Array:
    """The tree engine for commutative operators: one transfer round per
    pipeline block, every edge class at once (module docstring).

    Round ``r`` sends blocks finished in earlier rounds, at the offsets of
    :meth:`TreeTopology.round_offsets`: each node's partial up (the roots'
    to each other) and its result down, through one ``ppermute`` per edge
    class and direction, none waiting on another. While they are on the
    wire, the blocks that arrived in round ``r - 1`` are folded in: the
    children's partials into the node's own block (one pass, fused by XLA
    into the block's in-place write), and the other root's partial into a
    root's block, or the parent's result block written in place.

    The upper trees are placed in reverse (:func:`mirror_upper_trees`), so
    the roots are rank neighbours: on a ring-ordered TPU axis their exchange
    then takes one hop of its own instead of sharing a link with a root's
    down send, which all-at-once rounds would otherwise wait on."""
    topo = mirror_upper_trees(topo)
    p = topo.p
    op_name = _COMMUTATIVE_OPS[op]
    Y, m = _blockify(x, b, elementwise=True)
    pin = _lane_pinner(Y, x)
    blk_spec = None
    if carry_spec is not None:
        from jax.sharding import PartitionSpec as _P
        blk_spec = _P(*tuple(carry_spec)[1:])  # carry_spec covers (b, ...)
    ident = jnp.full(Y.shape[1:], _op_identity(op_name, Y.dtype), Y.dtype)

    # Who receives what in each permutation: a child's partial or the other
    # root's partial (both ride the up-permutations), the parent's result.
    up = [e for e in range(3) if topo.up_pairs[e]]
    down = [e for e in range(3) if topo.down_pairs[e]]
    recv = {}
    for e in up:
        for src, dst in topo.up_pairs[e]:
            kind = "child" if topo.parent[src] == dst else "partner"
            recv.setdefault(f"{kind}{e}", np.zeros(p, bool))[dst] = True
    for e in down:
        for src, dst in topo.down_pairs[e]:
            recv.setdefault(f"parent{e}", np.zeros(p, bool))[dst] = True
    of = lambda kind: [e for e in range(3) if f"{kind}{e}" in recv]

    # Per-node constants, one lookup: the block each round sends up
    # (r - lift) and down (r - drop), and the blocks it folds in from the
    # children (r - lift + 1) and from the other root or the parent
    # (r - off_d).
    lift, drop = topo.round_offsets()
    is_root = topo.parent == NO_NODE
    cols = {"lift": lift, "drop": drop,
            "fold_c": np.any([np.zeros(p, bool)]
                             + [recv[f"child{e}"] for e in of("child")], 0),
            "off_d": np.where(is_root, lift + 1, drop - 1),
            "fold_d": ~is_root | topo.exchanges,
            "root": is_root, **recv}
    table = np.stack([np.asarray(v, np.int32) for v in cols.values()], 1)
    row = _const(table, jax.lax.axis_index(axis_name))
    c = {k: row[n] for n, k in enumerate(cols)}
    on = lambda k: c[k] != 0
    in_range = lambda j: (j >= 0) & (j < b)

    def pick(kind, vals):
        """The value from the one permutation a node receives it in."""
        es = of(kind)
        out = vals[es[-1]]
        for e in es[:-1]:
            out = jnp.where(on(f"{kind}{e}"), vals[e], out)
        return out

    def fold(Y, got, r):
        """Write what arrived in round ``r - 1`` into its blocks."""
        t_up, t_down = got
        jc = r - c["lift"] + 1
        acc = _take(Y, jc, b)
        for e in of("child"):
            acc = op(acc, jnp.where(on(f"child{e}"), t_up[e], ident))
        do = on("fold_c") & in_range(jc)
        Y = _put(Y, acc, jnp.where(do, jnp.clip(jc, 0, b - 1), b))

        jd = r - c["off_d"]
        vals = []
        if of("partner"):
            vals.append(op(_take(Y, jd, b), pick("partner", t_up)))
        if of("parent"):
            vals.append(pick("parent", t_down))
        val = jnp.where(on("root"), *vals) if len(vals) == 2 else vals[0]
        do = on("fold_d") & in_range(jd)
        return _put(Y, val, jnp.where(do, jnp.clip(jd, 0, b - 1), b))

    def pins(got):
        return jax.tree.map(lambda v: pin(v, blk_spec), got)

    def round_(carry, r):
        Y, got = carry
        # The barrier keeps XLA from fusing these takes into the fold's
        # in-place writes, which would hold the permutes back behind them.
        up_out, down_out, Y = jax.lax.optimization_barrier(
            (_take(Y, r - c["lift"], b), _take(Y, r - c["drop"], b), Y))
        new = ({e: jax.lax.ppermute(up_out, axis_name, topo.up_pairs[e])
                for e in up},
               {e: jax.lax.ppermute(down_out, axis_name, topo.down_pairs[e])
                for e in down})
        return (pin(fold(Y, got, r), carry_spec), pins(new)), ()

    blank = _varying(jnp.zeros_like(ident), axis_name)
    T = topo.num_rounds(b)
    (Y, got), _ = jax.lax.scan(
        round_, (_varying(pin(Y, carry_spec), axis_name),
                 pins(({e: blank for e in up}, {e: blank for e in down}))),
        jnp.arange(T, dtype=jnp.int32))
    return _unblockify(fold(Y, got, jnp.int32(T)), m, x.shape[1:])


def tree_rounds(topo: TreeTopology, num_blocks: int, op: Op) -> int:
    """Transfer rounds :func:`_tree_allreduce` runs for ``op`` over ``b``
    blocks: the concurrent rounds for a commutative operator, else one
    one-ported step per active edge class per macro-round."""
    if topo.p == 1:
        return 0
    if _commutes(op):
        return topo.num_rounds(num_blocks)
    return topo.num_macro_rounds(num_blocks) * len(topo.active_classes())


def dptree_allreduce(x: jax.Array, axis_name: str, p: int, *,
                     num_blocks: int = 16,
                     op: Op = jnp.add, op_rev: Op | None = None,
                     topo: TreeTopology | None = None,
                     carry_spec=None) -> jax.Array:
    """The paper's doubly-pipelined, dual-root reduction-to-all (Algorithm 1).

    ``x`` is this device's flat vector; returns the elementwise reduction over
    all ``p`` devices of ``axis_name``, on every device. ``op`` must be
    associative; for non-commutative operators pass ``op_rev`` (same operator —
    the engine applies arguments in rank order; ``op_rev(a, b)`` must equal the
    operator applied as ``a (.) b``, which for plain functions is just ``op``).
    """
    topo = topo or build_dual_tree(p)
    nb = max(1, min(int(num_blocks), x.shape[0]))
    return _tree_allreduce(x, axis_name, topo, nb, op, op_rev, carry_spec)


def sptree_allreduce(x: jax.Array, axis_name: str, p: int, *,
                     num_blocks: int = 16,
                     op: Op = jnp.add, op_rev: Op | None = None,
                     topo: TreeTopology | None = None,
                     carry_spec=None) -> jax.Array:
    """Single doubly-pipelined binary tree (paper §1.2 remark): one tree over
    all p ranks, latency ``4h`` instead of ``4h-3``, but the root performs at
    most two reductions per round."""
    topo = topo or build_single_tree(p)
    nb = max(1, min(int(num_blocks), x.shape[0]))
    return _tree_allreduce(x, axis_name, topo, nb, op, op_rev, carry_spec)


# --------------------------------------------------------------------------
# Hierarchical (N-level) allreduce: per-level bidirectional-ring
# reduce-scatter down the fast levels -> dptree over the scattered shard
# stripes at the slowest level -> per-level all-gather back up. With
# S = prod(levels) ranks per top-level group, the slow inter-group fabric
# carries ~3*beta*m/S instead of 3*beta*m; each fast level j absorbs its
# 2*beta*(m/prod(levels[:j]))*(s_j-1)/s_j scatter/gather terms.
# --------------------------------------------------------------------------

def _compress_wire(x: jax.Array) -> jax.Array:
    """f32 -> bf16 for the slow-stage wire. Pallas tiled cast on real TPUs
    (1-D payloads), jnp cast elsewhere (interpret/CPU, lane-sharded 2-D
    payloads — where GSPMD owns the layout)."""
    if jax.default_backend() == "tpu" and x.ndim == 1:
        from repro.kernels import quantize
        return quantize.compress_bf16(x, interpret=False)
    return x.astype(jnp.bfloat16)


def _decompress_wire(x: jax.Array, dtype) -> jax.Array:
    if jax.default_backend() == "tpu" and x.ndim == 1:
        from repro.kernels import quantize
        return quantize.decompress_bf16(x, dtype, interpret=False)
    return x.astype(dtype)


def _bf16_wire_op(op: Op) -> Op:
    """Combine for bf16 wire payloads: decompress both operands to f32,
    reduce in full precision, recompress the result for the next hop."""
    def wire_op(a, b):
        return op(a.astype(jnp.float32),
                  b.astype(jnp.float32)).astype(jnp.bfloat16)
    return wire_op


def hier_allreduce(x: jax.Array, axis_name: str, p: int, *,
                   group_size=None,
                   num_blocks: int = 16,
                   op: Op = jnp.add,
                   htopo: HierarchicalTopology | None = None,
                   carry_spec=None,
                   bidirectional: bool = True,
                   compress_inter_group: bool = False) -> jax.Array:
    """Hierarchical allreduce (fabric-aware composition, 2..N levels).

    ``op`` must be commutative and associative (the ring stages reduce in
    ring order, not rank order) — sums, max/min, products. ``group_size`` is
    a hierarchy spec (see :func:`repro.core.topology.as_levels`): an int for
    the classic two-level split, a tuple of per-level ring sizes
    innermost-first for deeper shapes (e.g. ``(4, 2)`` = chip ring inside a
    node, node ring inside a pod, dual tree over pods), or ``None`` (4, then
    2, then flat). Stripe ``j`` — the ranks with local index ``j`` in each
    top-level group — runs its own inter-group dual-root tree, all stripes
    concurrently through the same three ppermute classes.

    ``compress_inter_group=True`` casts the (f32) shard stripes to bf16
    before the slow inter-group stage only; every tree combine decompresses
    to f32, reduces, and recompresses, and the result is decompressed before
    the full-precision all-gather back up. Non-f32 payloads pass through
    uncompressed.
    """
    if p == 1:
        return x
    h = htopo or build_hierarchy(p, group_size)
    assert h.p == p, (h.p, p)
    from repro.obs import probe as _obs_probe
    _probe = _obs_probe.active()
    if _probe is not None and h.levels:
        # Trace-time note for direct hier calls; all_reduce's hier branch
        # defers to this one so the sample is never double-counted. (The
        # degenerate no-level shape is a flat dptree; all_reduce notes it.)
        _probe.note("hier", p, x.size * x.dtype.itemsize,
                    num_blocks, dtype=str(x.dtype), kind="trace",
                    levels=tuple(h.levels), axis=axis_name)
    if not h.levels:  # one rank per group: plain flat dptree over all ranks
        nb = max(1, min(int(num_blocks), x.shape[0]))
        return _tree_allreduce(x, axis_name, h.inter_topo, nb, op, None,
                               carry_spec)
    i = jax.lax.axis_index(axis_name)

    # ---- stage down: per-level bidirectional ring reduce-scatter ---------
    # After level j each rank owns a fully-reduced (within its level-(<=j)
    # neighborhood) stripe of 1/s_j of the previous vector; the stripe a rank
    # ends up with depends only on its local coordinates, so ranks with equal
    # local index across groups — the inter-tree stripes — hold aligned data.
    vec, down = x, []
    for s, stride, (fwd, bwd) in zip(h.levels, h.strides, h.level_rings):
        li = jnp.mod(jnp.floor_divide(i, stride), s)
        halves, chunk, m, trail = _ring_layout(vec, s, bidirectional)
        perms = [fwd, bwd][: len(halves)]
        signs = [1, -1][: len(halves)]
        reduced, shards = [], []
        for H, perm, sg in zip(halves, perms, signs):
            H = _ring_reduce_scatter(H, axis_name, li, s, perm, sg, op,
                                     carry_spec)
            own = jnp.mod(li + sg, s)  # chunk this rank now fully owns
            reduced.append(H)
            shards.append(jax.lax.dynamic_slice_in_dim(H, own, 1, axis=0)[0])
        down.append((reduced, perms, signs, li, s, chunk, m, trail,
                     tuple(hh.shape[1] for hh in halves)))
        vec = (jnp.concatenate(shards, axis=0) if len(shards) > 1
               else shards[0])

    # ---- slowest stage: dptree allreduce over the shard stripes ----------
    if h.num_groups > 1:
        nb = max(1, min(int(num_blocks), vec.shape[0]))
        if compress_inter_group and vec.dtype == jnp.float32:
            wire_op = _bf16_wire_op(op)
            wire = _tree_allreduce(_compress_wire(vec), axis_name,
                                   h.inter_topo, nb, wire_op, wire_op,
                                   carry_spec)
            vec = _decompress_wire(wire, jnp.float32)
        else:
            vec = _tree_allreduce(vec, axis_name, h.inter_topo, nb, op, None,
                                  carry_spec)

    # ---- stage up: per-level ring all-gather, outermost level first ------
    for reduced, perms, signs, li, s, chunk, m, trail, widths in \
            reversed(down):
        pieces, off = [], 0
        for w in widths:
            pieces.append(vec[off:off + w])
            off += w
        outs = []
        for H, perm, sg, piece in zip(reduced, perms, signs, pieces):
            own = jnp.mod(li + sg, s)
            H = jax.lax.dynamic_update_slice(
                H, piece[None], (own,) + (0,) * (H.ndim - 1))
            outs.append(_ring_all_gather(H, axis_name, li, s, perm, sg,
                                         carry_spec))
        vec = _ring_unlayout(outs, s, chunk, m, trail)
    return vec


# --------------------------------------------------------------------------
# User-Allreduce1: pipelined binary-tree reduce followed by pipelined bcast.
# Period-2 schedules; sends to the parent overlap receives from a child in the
# same step (different partners — MPI_Sendrecv-style), so one permutation per
# step suffices in each phase.
# --------------------------------------------------------------------------

def _phase_classes(p, parent, key, roots):
    cls = [[], []]
    for i in range(p):
        pa = int(parent[i])
        if pa == NO_NODE:
            continue
        cls[int(key[i]) % 2].append((i, pa))
    return tuple(tuple(c) for c in cls)


def redbcast_allreduce(x: jax.Array, axis_name: str, p: int, *,
                       num_blocks: int = 16,
                       op: Op = jnp.add,
                       topo: TreeTopology | None = None) -> jax.Array:
    """Pipelined reduce-to-root then pipelined broadcast (User-Allreduce1)."""
    topo = topo or build_single_tree(p)
    if p == 1:
        return x
    b = max(1, min(int(num_blocks), x.shape[0]))
    # scratch row for masked writes (same trick as the tree engine)
    Y, m = _blockify(x, b, elementwise=op in _COMMUTATIVE_OPS)
    pin = _lane_pinner(Y, x)

    i = jax.lax.axis_index(axis_name)
    dmax = topo.max_depth

    # ---------------- reduce phase (period 2, up-traffic only) -----------
    # phi1 follows the same recursion as the dual-root schedule.
    phi1_np = np.zeros(p, np.int32)
    stack = [(topo.roots[0], 2 * dmax)]
    while stack:
        n, v = stack.pop()
        phi1_np[n] = v
        if topo.child0[n] != NO_NODE:
            stack.append((int(topo.child0[n]), v - 2))
        if topo.child1[n] != NO_NODE:
            stack.append((int(topo.child1[n]), v - 1))
    up_cls = _phase_classes(p, topo.parent, phi1_np, topo.roots)
    # child->parent edges, classed by phi1(child) mod 2
    phi1 = _const(phi1_np, i)
    has_c0 = _const(topo.child0 != NO_NODE, i)
    has_c1 = _const(topo.child1 != NO_NODE, i)
    has_par = _const(topo.parent != NO_NODE, i)
    S1 = int(phi1_np[topo.roots[0]]) + 2 * b
    R1 = -(-S1 // 2)

    def rstep(Y, s, e):
        rel = s - phi1
        even = jnp.mod(rel, 2) == 0
        j_send = jnp.floor_divide(rel - 2, 2)       # send up at phi1+2j+2
        j_r0 = jnp.floor_divide(rel, 2)             # recv child0 at phi1+2j
        j_r1 = jnp.floor_divide(rel - 1, 2)         # recv child1 at phi1+2j+1
        up_out = _take(Y, j_send, b)
        t = jax.lax.ppermute(up_out, axis_name, up_cls[e]) if up_cls[e] \
            else jnp.zeros_like(up_out)
        jr = jnp.where(even, j_r0, j_r1)
        ok = (((even & has_c0) | (~even & has_c1))
              & (jr >= 0) & (jr < b))
        cur = _take(Y, jr, b)
        row = jnp.where(ok, jnp.clip(jr, 0, b - 1), b)
        return _put(Y, op(t, cur), row)

    def rround(Y, r):
        for e in (0, 1):
            if up_cls[e]:
                Y = rstep(Y, 2 * r + e, e)
        return pin(Y), ()

    Y, _ = jax.lax.scan(rround, _varying(pin(Y), axis_name),
                        jnp.arange(R1, dtype=jnp.int32))

    # ---------------- broadcast phase (period 2, down-traffic only) ------
    sig_np = np.zeros(p, np.int32)
    stack = [(topo.roots[0], 0)]
    while stack:
        n, v = stack.pop()
        sig_np[n] = v
        if topo.child0[n] != NO_NODE:
            stack.append((int(topo.child0[n]), v + 1))
        if topo.child1[n] != NO_NODE:
            stack.append((int(topo.child1[n]), v + 2))
    # edge (i -> c0) active at sigma(i)+2j; (i -> c1) at sigma(i)+2j+1.
    dn_cls = [[], []]
    for n in range(p):
        for c, off in ((topo.child0[n], 0), (topo.child1[n], 1)):
            if c != NO_NODE:
                dn_cls[(int(sig_np[n]) + off) % 2].append((n, int(c)))
    dn_cls = tuple(tuple(c) for c in dn_cls)
    sig = _const(sig_np, i)
    S2 = int(sig_np.max()) + 2 * b
    R2 = -(-S2 // 2)

    def bstep(Y, s, e):
        rel = s - sig
        even = jnp.mod(rel, 2) == 0
        j_s0 = jnp.floor_divide(rel, 2)             # send c0 at sigma+2j
        j_s1 = jnp.floor_divide(rel - 1, 2)         # send c1 at sigma+2j+1
        j_rcv = jnp.floor_divide(rel + 1, 2)        # recv parent at sigma+2j-1
        out = _take(Y, jnp.where(even, j_s0, j_s1), b)
        t = jax.lax.ppermute(out, axis_name, dn_cls[e]) if dn_cls[e] \
            else jnp.zeros_like(out)
        ok = has_par & (jnp.mod(rel, 2) == 1) & (j_rcv >= 0) & (j_rcv < b)
        row = jnp.where(ok, jnp.clip(j_rcv, 0, b - 1), b)
        return _put(Y, t, row)

    def bround(Y, r):
        for e in (0, 1):
            if dn_cls[e]:
                Y = bstep(Y, 2 * r + e, e)
        return pin(Y), ()

    Y, _ = jax.lax.scan(bround, _varying(pin(Y), axis_name),
                        jnp.arange(R2, dtype=jnp.int32))
    return _unblockify(Y, m, x.shape[1:])


# --------------------------------------------------------------------------
# Bidirectional ring reduce-scatter + all-gather (the TPU-native baseline).
# --------------------------------------------------------------------------

def ring_allreduce(x: jax.Array, axis_name: str, p: int, *,
                   op: Op = jnp.add, bidirectional: bool = True) -> jax.Array:
    """Ring allreduce; with ``bidirectional=True`` the vector is split in two
    halves circulating in opposite directions, halving the beta term on
    full-duplex links. An odd per-rank chunk is padded up to even so the two
    half-schedules move the same byte count (unequal halves would make one
    direction the straggler on every step)."""
    if p == 1:
        return x
    halves, chunk, m, trail = _ring_layout(x, p, bidirectional)
    i = jax.lax.axis_index(axis_name)
    fwd = tuple((k, (k + 1) % p) for k in range(p))
    bwd = tuple(((k + 1) % p, k) for k in range(p))

    dirs = [fwd, bwd][: len(halves)]
    signs = [1, -1][: len(halves)]
    out_halves = []
    for H, perm, sg in zip(halves, dirs, signs):
        H = _ring_reduce_scatter(H, axis_name, i, p, perm, sg, op)
        out_halves.append(_ring_all_gather(H, axis_name, i, p, perm, sg))
    return _ring_unlayout(out_halves, p, chunk, m, trail)
