"""Property tests for the dual/single post-order tree topologies."""

import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core.topology import (NO_NODE, build_dual_tree, build_single_tree,
                                 validate_topology)


@settings(max_examples=60, deadline=None)
@given(p=st.integers(min_value=1, max_value=300))
def test_dual_tree_invariants(p):
    validate_topology(build_dual_tree(p))


@settings(max_examples=60, deadline=None)
@given(p=st.integers(min_value=1, max_value=300))
def test_single_tree_invariants(p):
    validate_topology(build_single_tree(p))


@settings(max_examples=40, deadline=None)
@given(p=st.integers(min_value=2, max_value=200))
def test_every_edge_in_exactly_one_class(p):
    topo = build_dual_tree(p)
    up_edges = [e for cls in topo.up_pairs for e in cls]
    # each non-root contributes one up edge; dual roots contribute two
    n_expected = (p - len(topo.roots)) + (2 if len(topo.roots) == 2 else 0)
    assert len(up_edges) == n_expected
    assert len(set(up_edges)) == len(up_edges)


@settings(max_examples=40, deadline=None)
@given(p=st.integers(min_value=2, max_value=200))
def test_depth_is_logarithmic(p):
    topo = build_dual_tree(p)
    half = (p + 1) // 2
    assert topo.max_depth <= int(np.ceil(np.log2(half + 1)))


@settings(max_examples=30, deadline=None)
@given(p=st.integers(min_value=2, max_value=128),
       b=st.integers(min_value=1, max_value=40))
def test_step_count_matches_paper_band(p, b):
    """num_steps is within the paper's 4h-3+3(b-1) budget (+3 sync slack)."""
    topo = build_dual_tree(p)
    h = topo.max_depth + 1
    paper = (4 * h - 3) + 3 * (b - 1)
    assert topo.num_steps(b) <= paper + 3


def test_balanced_case_exact():
    # p = 2^h - 2 gives two perfect trees; roots are p/2-1 and p-1
    for h in (2, 3, 4, 5):
        p = 2 ** h - 2
        topo = build_dual_tree(p)
        assert topo.roots == (p // 2 - 1, p - 1)
        assert topo.max_depth == h - 2


@settings(max_examples=30, deadline=None)
@given(g=st.integers(min_value=1, max_value=40),
       s=st.sampled_from([1, 2, 3, 4, 8]))
def test_hierarchy_stripe_expansion_invariants(g, s):
    from repro.core.topology import build_hierarchy
    p = g * s
    h = build_hierarchy(p, s)
    assert (h.num_groups, h.group_size) == (g, s)
    it, gt = h.inter_topo, h.group_tree
    assert it.p == p
    # per-rank schedule constants replicate the group tree's along stripes
    for q in range(g):
        for j in range(s):
            r = q * s + j
            assert it.phi[r] == gt.phi[q]
            assert it.depth[r] == gt.depth[q]
            pa = gt.parent[q]
            assert it.parent[r] == (NO_NODE if pa == NO_NODE else pa * s + j)
    # expanded ppermute classes stay valid permutations (stripes disjoint)
    for pairs in it.up_pairs + it.down_pairs:
        srcs = [a for a, _ in pairs]
        dsts = [c for _, c in pairs]
        assert len(set(srcs)) == len(srcs)
        assert len(set(dsts)) == len(dsts)
        # every edge stays inside its stripe
        for a, c in pairs:
            assert a % s == c % s
    # edge count: s stripes x group-tree edges
    n_up_group = sum(len(c) for c in gt.up_pairs)
    assert sum(len(c) for c in it.up_pairs) == s * n_up_group
    # intra-group ring never crosses a group boundary
    for a, c in h.ring_fwd:
        assert a // s == c // s


def test_hierarchy_rejects_bad_group_size():
    from repro.core.topology import build_hierarchy
    with pytest.raises(ValueError):
        build_hierarchy(8, 3)
    with pytest.raises(ValueError):
        build_hierarchy(8, 0)
    with pytest.raises(ValueError):
        build_hierarchy(8, (2, 3))  # prod 6 does not divide 8
    # default picks 4 | 2 | 1
    assert build_hierarchy(8).group_size == 4
    assert build_hierarchy(6).group_size == 2
    assert build_hierarchy(5).group_size == 1


@settings(max_examples=30, deadline=None)
@given(g=st.integers(min_value=1, max_value=12),
       s0=st.sampled_from([2, 3, 4]),
       s1=st.sampled_from([2, 3]))
def test_three_level_hierarchy_invariants(g, s0, s1):
    """N-level shape: strides nest little-endian, every level ring is a valid
    permutation that only moves one level coordinate, and the inter tree runs
    over stripes of the full prod(levels)."""
    from repro.core.topology import build_hierarchy
    p = g * s0 * s1
    h = build_hierarchy(p, (s0, s1))
    assert h.levels == (s0, s1)
    assert h.strides == (1, s0)
    assert (h.group_size, h.num_groups) == (s0 * s1, g)
    assert h.inter_topo.p == p and h.group_tree.p == g
    # legacy aliases point at the innermost level
    assert h.ring_fwd == h.level_rings[0][0]
    assert h.ring_bwd == h.level_rings[0][1]
    S = h.group_size
    for j, (s, t) in enumerate(zip(h.levels, h.strides)):
        fwd, bwd = h.level_rings[j]
        assert bwd == tuple((d, a) for a, d in fwd)
        srcs = [a for a, _ in fwd]
        dsts = [d for _, d in fwd]
        assert sorted(srcs) == list(range(p)) and len(set(dsts)) == p
        for a, d in fwd:
            # stays inside the same top-level group...
            assert a // S == d // S
            # ...advances exactly the level-j coordinate by +1 (mod s)...
            ca, cd = (a // t) % s, (d // t) % s
            assert cd == (ca + 1) % s
            # ...and touches no other coordinate
            assert a - ca * t == d - cd * t


def test_resolve_levels_rules():
    from repro.core.topology import as_levels, resolve_levels
    # normalization: ints become 1-tuples, size-1 levels are dropped
    assert as_levels(4) == (4,)
    assert as_levels((1, 2, 1, 4)) == (2, 4)
    assert as_levels(None) is None
    # feasibility: every level divides out, >= 2 groups remain
    assert resolve_levels(16, (2, 2)) == (2, 2)
    assert resolve_levels(16, (2, 4)) == (2, 4)
    assert resolve_levels(8, (2, 4)) is None     # g == 1
    assert resolve_levels(8, (2, 3)) is None     # 6 does not divide 8
    assert resolve_levels(8, None) == (4,)       # default two-level
    assert resolve_levels(5, None) is None       # flat only
    assert resolve_levels(8, "junk") is None     # malformed spec, no raise


def test_p1_p2_degenerate():
    t1 = build_dual_tree(1)
    assert t1.roots == (0,)
    t2 = build_dual_tree(2)
    assert t2.roots == (0, 1)
    assert t2.active_classes() == tuple(
        e for e in range(3) if t2.up_pairs[e])
    assert sum(len(c) for c in t2.up_pairs) == 2  # the dual exchange only


@settings(max_examples=40, deadline=None)
@given(g=st.integers(min_value=2, max_value=40),
       s=st.sampled_from([1, 2, 4]))
def test_mirrored_upper_trees_put_the_roots_side_by_side(g, s):
    """The concurrent rounds' placement: within each stripe the upper tree
    lies on its ranks in reverse, so the two roots are neighbours among the
    stripe's ranks (``s`` apart), no stripe meets another, every other edge
    keeps its length, and each class is still a matching."""
    from repro.core.topology import build_hierarchy, mirror_upper_trees
    if s == 1:
        topo = build_dual_tree(g)
    else:
        topo = build_hierarchy(g * s, s).inter_topo
    mir = mirror_upper_trees(topo)
    p0 = (g + 1) // 2
    roots = np.flatnonzero(mir.parent == NO_NODE)
    assert len(roots) == 2 * s
    for j in range(s):
        assert {int(r) for r in roots if r % s == j} == \
            {(p0 - 1) * s + j, p0 * s + j}
    lengths = lambda t: sorted(abs(a - c) for cls in t.up_pairs + t.down_pairs
                               for a, c in cls if t.parent[a] == c
                               or t.parent[c] == a)
    assert lengths(mir) == lengths(topo)
    for pairs in mir.up_pairs + mir.down_pairs:
        assert all(a % s == c % s for a, c in pairs)
        assert len({a for a, _ in pairs}) == len({c for _, c in pairs}) \
            == len(pairs)
    for i in range(g * s):
        if mir.parent[i] != NO_NODE:
            assert mir.depth[mir.parent[i]] == mir.depth[i] - 1
            assert mir.tree_id[mir.parent[i]] == mir.tree_id[i]
    assert sorted(mir.depth) == sorted(topo.depth)
    assert mir.num_rounds(7) == topo.num_rounds(7)
