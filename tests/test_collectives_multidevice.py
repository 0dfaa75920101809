"""Multi-device collective tests (8 virtual CPU devices via subprocess).

The smoke tests must see 1 device (per the dry-run contract), so anything
needing many devices runs in a subprocess with its own XLA_FLAGS.
"""

import os
import subprocess
import sys
import textwrap

import pytest

# every test here spawns an 8-virtual-device subprocess: the definition of
# the `slow` marker (see pytest.ini / `make test-fast`)
pytestmark = pytest.mark.slow

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, devices: int = 8, timeout: int = 560):
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import sys
        sys.path.insert(0, {ROOT + '/src'!r})
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        shard_map = jax.shard_map
        mesh = make_mesh(({devices},), ("data",))
        p = {devices}
    """) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=timeout)
    assert r.returncode == 0, f"\nSTDOUT:{r.stdout[-2000:]}\nERR:{r.stderr[-3000:]}"
    return r.stdout


def test_all_methods_match_sum():
    run_sub("""
        from repro.core.dptree import (dptree_allreduce, sptree_allreduce,
                                       redbcast_allreduce, ring_allreduce)
        rng = np.random.default_rng(42)
        m = 103
        X = rng.standard_normal((p, m)).astype(np.float32)
        want = X.sum(0)
        cases = [
            ("dptree b=1", lambda x: dptree_allreduce(x, "data", p, num_blocks=1)),
            ("dptree b=4", lambda x: dptree_allreduce(x, "data", p, num_blocks=4)),
            ("dptree b=103", lambda x: dptree_allreduce(x, "data", p, num_blocks=103)),
            ("sptree", lambda x: sptree_allreduce(x, "data", p, num_blocks=5)),
            ("redbcast", lambda x: redbcast_allreduce(x, "data", p, num_blocks=4)),
            ("ring", lambda x: ring_allreduce(x, "data", p)),
            ("ring-uni", lambda x: ring_allreduce(x, "data", p, bidirectional=False)),
        ]
        for name, fn in cases:
            body = lambda x: fn(x[0])[None]
            sm = shard_map(body, mesh=mesh, in_specs=P("data", None),
                               out_specs=P("data", None))
            out = np.asarray(jax.jit(sm)(jnp.asarray(X)))
            for r in range(p):
                np.testing.assert_allclose(out[r], want, rtol=2e-5, atol=2e-5,
                                           err_msg=name)
        print("ok")
    """)


def test_2d_row_pipelined_payloads():
    run_sub("""
        from repro.core.dptree import dptree_allreduce, ring_allreduce
        rng = np.random.default_rng(0)
        X = rng.standard_normal((p, 37, 8)).astype(np.float32)
        want = X.sum(0)
        for fn in (lambda x: dptree_allreduce(x, "data", p, num_blocks=5),
                   lambda x: ring_allreduce(x, "data", p)):
            body = lambda x: fn(x[0])[None]
            sm = shard_map(body, mesh=mesh, in_specs=P("data", None, None),
                               out_specs=P("data", None, None))
            out = np.asarray(jax.jit(sm)(jnp.asarray(X)))
            for r in range(p):
                np.testing.assert_allclose(out[r], want, rtol=2e-5, atol=2e-5)
        print("ok")
    """)


@pytest.mark.parametrize("n_mats", [12, 1536])
def test_dptree_non_commutative_matches_simulator(n_mats):
    """Rank-ordered 2x2 matrix products on the general path; at 1536
    matrices each of the 3 blocks is 2048 floats, longer than a TPU tile."""
    run_sub(f"""
        from repro.core.dptree import dptree_allreduce
        from repro.core.simulator import simulate_allreduce
        rng = np.random.default_rng(1)
        n = {n_mats}
        Xm = (rng.standard_normal((p, n, 2, 2)) * 0.3 + np.eye(2)).astype(np.float32)
        def mm_np(a, b):
            return np.einsum('mij,mjk->mik', a, b)
        sim = simulate_allreduce([Xm[i] for i in range(p)], 3, op=mm_np)
        def mm_flat(a, b):
            A = a.reshape(-1, 2, 2); B = b.reshape(-1, 2, 2)
            return jnp.einsum('mij,mjk->mik', A, B).reshape(-1)
        body = lambda x: dptree_allreduce(x[0].reshape(-1), "data", p,
                                          num_blocks=3, op=mm_flat,
                                          op_rev=mm_flat).reshape(n, 2, 2)[None]
        sm = shard_map(body, mesh=mesh, in_specs=P("data", None, None, None),
                           out_specs=P("data", None, None, None))
        out = np.asarray(jax.jit(sm)(jnp.asarray(Xm)))
        for r in range(p):
            np.testing.assert_allclose(out[r], sim.outputs[r], rtol=2e-4,
                                       atol=2e-4)
        print("ok")
    """)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int32"])
def test_block_major_payloads_match_plain_reduction(dtype):
    """1-D payloads through dptree, sptree, redbcast and hier's inter-group
    tree, summed and maxed: blocks long enough for the block-major layout
    (m a multiple of neither b nor a tile; for bf16 a block of 2056 just
    over its 2048-element tile), one block (b = 1), and blocks shorter than
    a tile. Integer-valued inputs keep every order of summation exact, so
    each result must EQUAL the plain reduction. Also a b = 1 structured
    reduction whose payload is longer than a tile: its operator must see
    exactly its own vector, unpadded."""
    run_sub(f"""
        from repro.core.collectives import structured_all_reduce
        from repro.core.dptree import (dptree_allreduce, sptree_allreduce,
                                       redbcast_allreduce, hier_allreduce)
        rng = np.random.default_rng(3)
        dt = jnp.dtype("{dtype}")
        methods = {{
            "dptree": dptree_allreduce, "sptree": sptree_allreduce,
            "redbcast": redbcast_allreduce,
            "hier": lambda x, a, p, num_blocks, op: hier_allreduce(
                x, a, p, group_size=2, num_blocks=num_blocks, op=op)}}
        for m, b in ((5 * 2048 + 37, 5), (3001, 1), (999, 8)):
            X = rng.integers(-16, 16, (p, m)).astype(dt)
            for op, ref in ((jnp.add, X.astype(np.float64).sum(0)),
                            (jnp.maximum, X.astype(np.float64).max(0))):
                for name, fn in methods.items():
                    f = lambda x: fn(x[0], "data", p, num_blocks=b,
                                     op=op)[None]
                    sm = shard_map(f, mesh=mesh, in_specs=P("data", None),
                                   out_specs=P("data", None))
                    out = np.asarray(jax.jit(sm)(jnp.asarray(X)))
                    assert out.dtype == dt, (name, out.dtype)
                    for r in range(p):
                        np.testing.assert_array_equal(
                            out[r].astype(np.float64), ref,
                            err_msg=f"{{name}} m={{m}} b={{b}} {{op.__name__}}")

        # affine maps composed: associative, not commutative
        def comb(a, b):
            return {{"n": a["n"] * b["n"], "s": a["s"] * b["n"] + b["s"]}}
        parts = {{"n": jnp.asarray(rng.integers(0, 2, (p, 3000)).astype(dt)),
                  "s": jnp.asarray(rng.integers(0, 4, (p, 3000)).astype(dt))}}
        want = {{k: np.asarray(v[0]).astype(np.float64)
                 for k, v in parts.items()}}
        for r in range(1, p):
            want = comb(want, {{k: np.asarray(v[r]).astype(np.float64)
                                for k, v in parts.items()}})
        # every method the collective layer runs under any associative op
        for method in ("dptree", "sptree", "redbcast"):
            sm = shard_map(lambda t: jax.tree.map(lambda l: l[None],
                               structured_all_reduce(
                                   jax.tree.map(lambda l: l[0], t),
                                   "data", p, comb, method=method)),
                           mesh=mesh, in_specs=P("data"), out_specs=P("data"))
            out = jax.jit(sm)(parts)
            for k in want:
                for r in range(p):
                    np.testing.assert_array_equal(
                        np.asarray(out[k][r]).astype(np.float64), want[k],
                        err_msg=method)
        print("ok")
    """)


def test_bucketed_and_structured_api():
    run_sub("""
        from repro.core.collectives import (CollectiveConfig,
                                            bucketed_all_reduce,
                                            structured_all_reduce)
        rng = np.random.default_rng(1)
        tree = {"a": rng.standard_normal((3, 7)).astype(np.float32),
                "b": rng.standard_normal((11,)).astype(np.float32)}
        trees = [jax.tree.map(lambda x: x + k, tree) for k in range(p)]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
        want = jax.tree.map(lambda *xs: np.sum(xs, axis=0), *trees)
        for method in ["dptree", "redbcast", "ring", "psum", "auto"]:
            cfg = CollectiveConfig(method=method)
            body = lambda t: jax.tree.map(lambda l: l[None],
                bucketed_all_reduce(jax.tree.map(lambda l: l[0], t),
                                    "data", p, cfg))
            sm = shard_map(body, mesh=mesh, in_specs=P("data"),
                               out_specs=P("data"))
            out = jax.jit(sm)(stacked)
            for k in tree:
                got = np.asarray(out[k])
                for r in range(p):
                    np.testing.assert_allclose(got[r], want[k], rtol=3e-5,
                                               atol=3e-5, err_msg=method)
        # structured flash-decoding combine
        def comb(a, b):
            m = jnp.maximum(a["m"], b["m"])
            ea, eb = jnp.exp(a["m"] - m), jnp.exp(b["m"] - m)
            return {"m": m, "s": a["s"] * ea + b["s"] * eb}
        parts = [{"m": rng.standard_normal((4,)).astype(np.float32),
                  "s": rng.random((4,)).astype(np.float32) + .5}
                 for _ in range(p)]
        want2 = parts[0]
        for q in parts[1:]:
            want2 = comb(jax.tree.map(jnp.asarray, want2),
                         jax.tree.map(jnp.asarray, q))
        stacked2 = jax.tree.map(lambda *xs: jnp.stack(xs), *parts)
        body = lambda t: jax.tree.map(lambda l: l[None],
            structured_all_reduce(jax.tree.map(lambda l: l[0], t),
                                  "data", p, comb))
        sm = shard_map(body, mesh=mesh, in_specs=P("data"),
                           out_specs=P("data"))
        out = jax.jit(sm)(stacked2)
        for k in want2:
            got = np.asarray(out[k])
            for r in range(p):
                np.testing.assert_allclose(got[r], np.asarray(want2[k]),
                                           rtol=1e-4, atol=1e-4)
        print("ok")
    """)


def test_fused_max_allreduce_with_infinities():
    """The fused engine's deferred-combine identity must be a true infinity:
    max-allreduce over payloads containing -inf (masked logits) has to return
    -inf, not finfo.min."""
    run_sub("""
        from repro.core.dptree import dptree_allreduce
        rng = np.random.default_rng(5)
        X = rng.standard_normal((p, 64)).astype(np.float32)
        X[:, :8] = -np.inf          # every rank masked -> max stays -inf
        X[1:, 8:16] = -np.inf       # one live rank
        want = X.max(0)
        for op, opname in ((jnp.maximum, "max"), (jnp.minimum, "min")):
            w = want if opname == "max" else (-X).min(0)
            Xi = X if opname == "max" else -X
            body = lambda x: dptree_allreduce(x[0], "data", p, num_blocks=4,
                                              op=op)[None]
            sm = shard_map(body, mesh=mesh, in_specs=P("data", None),
                           out_specs=P("data", None))
            out = np.asarray(jax.jit(sm)(jnp.asarray(Xi)))
            for r in range(p):
                np.testing.assert_array_equal(out[r], w, err_msg=opname)
        print("ok")
    """)


def test_hier_allreduce_matches_psum():
    """Two-level hierarchical allreduce vs psum ground truth: groups of 2 and
    4, odd/degenerate sizes, both bidirectional settings."""
    run_sub("""
        from repro.core.dptree import hier_allreduce
        rng = np.random.default_rng(7)
        for m in (1, 2, 5, 37, 103, 1001):
            X = rng.standard_normal((p, m)).astype(np.float32)
            want = X.sum(0)
            for gs in (2, 4):
                for bidi in (True, False):
                    fn = lambda x: hier_allreduce(x, "data", p, group_size=gs,
                                                  num_blocks=3,
                                                  bidirectional=bidi)
                    sm = shard_map(lambda x: fn(x[0])[None], mesh=mesh,
                                   in_specs=P("data", None),
                                   out_specs=P("data", None))
                    out = np.asarray(jax.jit(sm)(jnp.asarray(X)))
                    for r in range(p):
                        np.testing.assert_allclose(
                            out[r], want, rtol=1e-5, atol=1e-5,
                            err_msg=f"m={m} gs={gs} bidi={bidi}")
        print("ok")
    """)


def test_hier3_matches_reference_all_ops():
    """Acceptance: the 3-level hierarchy (chip ring -> node ring -> pod
    dual-root tree) matches the jnp reference on an 8-device CPU mesh for
    sum/max/min across multiple level-spec shapes, including all-intra
    degenerate ones (g == 1: pure nested rings, no slow stage)."""
    run_sub("""
        from repro.core.dptree import hier_allreduce
        rng = np.random.default_rng(11)
        ops = ((jnp.add, lambda X: X.sum(0)),
               (jnp.maximum, lambda X: X.max(0)),
               (jnp.minimum, lambda X: X.min(0)))
        for m in (1, 5, 37, 103):
            X = rng.standard_normal((p, m)).astype(np.float32)
            for spec in ((2, 2), (2, 4), (4, 2)):
                for op, ref in ops:
                    fn = lambda x: hier_allreduce(x, "data", p,
                                                  group_size=spec,
                                                  num_blocks=3, op=op)
                    sm = shard_map(lambda x: fn(x[0])[None], mesh=mesh,
                                   in_specs=P("data", None),
                                   out_specs=P("data", None))
                    out = np.asarray(jax.jit(sm)(jnp.asarray(X)))
                    want = ref(X)
                    for r in range(p):
                        np.testing.assert_allclose(
                            out[r], want, rtol=1e-5, atol=1e-5,
                            err_msg=f"m={m} spec={spec} op={op.__name__}")
        print("ok")
    """)


def test_hier3_via_collective_config_and_2d_payload():
    """A level-tuple group_size through the public all_reduce, incl. a 2-D
    lanes payload (the gradient-bucket layout)."""
    run_sub("""
        from repro.core.collectives import CollectiveConfig, all_reduce
        rng = np.random.default_rng(12)
        cfg = CollectiveConfig(method="hier", group_size=(2, 2))
        for shape in ((257,), (37, 8)):
            X = rng.standard_normal((p,) + shape).astype(np.float32)
            spec = P("data", *([None] * len(shape)))
            sm = shard_map(lambda x: all_reduce(x[0], "data", p, cfg)[None],
                           mesh=mesh, in_specs=spec, out_specs=spec)
            out = np.asarray(jax.jit(sm)(jnp.asarray(X)))
            for r in range(p):
                np.testing.assert_allclose(out[r], X.sum(0), rtol=1e-5,
                                           atol=1e-5)
        print("ok")
    """)


def test_compress_inter_group_bound_and_exact_off():
    """bf16 slow-stage compression stays within the documented relative-error
    bound for positive sums; compress_inter_group=False is bit-identical to
    the plain two-level path (PR 1's public entry, no new kwargs)."""
    run_sub("""
        from repro.core.collectives import CollectiveConfig, all_reduce
        from repro.core.dptree import hier_allreduce
        rng = np.random.default_rng(13)
        m = 4097
        X = (np.abs(rng.standard_normal((p, m))) + 0.1).astype(np.float32)
        want = X.sum(0)

        def run(fn, data=X):
            sm = shard_map(lambda x: fn(x[0])[None], mesh=mesh,
                           in_specs=P("data", None),
                           out_specs=P("data", None))
            return np.asarray(jax.jit(sm)(jnp.asarray(data)))

        legacy = run(lambda x: all_reduce(
            x, "data", p,
            CollectiveConfig(method="hier", group_size=4, num_blocks=4)))
        off = run(lambda x: hier_allreduce(x, "data", p, group_size=(4,),
                                           num_blocks=4,
                                           compress_inter_group=False))
        assert (legacy == off).all()   # bit-identical, not just close

        for spec in ((4,), (2, 2)):
            on = run(lambda x: hier_allreduce(x, "data", p, group_size=spec,
                                              num_blocks=4,
                                              compress_inter_group=True))
            g = p // int(np.prod(spec))
            # documented bound (docs/algorithms.md): positive-sum relative
            # error <= (2 + ceil(log2 g)) * 2^-8 through the bf16 wire
            bound = (2 + int(np.ceil(np.log2(max(g, 2))))) * 2.0 ** -8
            rel = np.max(np.abs(on - want[None]) / np.abs(want[None]))
            assert rel <= bound, (spec, rel, bound)
            assert rel > 0     # the flag really engaged the lossy wire
        # non-f32 payloads pass through uncompressed: flag is a no-op
        Xi = (X * 64).astype(np.int32)
        on_i = run(lambda x: hier_allreduce(x, "data", p, group_size=(2, 2),
                                            num_blocks=4,
                                            compress_inter_group=True),
                   data=Xi)
        off_i = run(lambda x: hier_allreduce(x, "data", p, group_size=(2, 2),
                                             num_blocks=4), data=Xi)
        assert (on_i == off_i).all() and (on_i[0] == Xi.sum(0)).all()
        print("ok")
    """)


def test_hier_via_collective_config():
    """method='hier' through the public all_reduce/bucketed API."""
    run_sub("""
        from repro.core.collectives import CollectiveConfig, all_reduce
        rng = np.random.default_rng(8)
        X = rng.standard_normal((p, 257)).astype(np.float32)
        cfg = CollectiveConfig(method="hier", group_size=4)
        sm = shard_map(lambda x: all_reduce(x[0], "data", p, cfg)[None],
                       mesh=mesh, in_specs=P("data", None),
                       out_specs=P("data", None))
        out = np.asarray(jax.jit(sm)(jnp.asarray(X)))
        for r in range(p):
            np.testing.assert_allclose(out[r], X.sum(0), rtol=1e-5, atol=1e-5)
        print("ok")
    """)


def test_ring_odd_chunk_and_odd_p():
    """Bidirectional ring at odd per-rank chunk (guarded by even-padding) and
    non-power-of-two p."""
    for d, m in ((5, 35), (7, 91), (8, 36)):  # chunk = 7, 13, 5 (odd)
        run_sub(f"""
            from repro.core.dptree import ring_allreduce
            rng = np.random.default_rng(3)
            m = {m}
            X = rng.standard_normal((p, m)).astype(np.float32)
            for bidi in (True, False):
                body = lambda x: ring_allreduce(x[0], "data", p,
                                                bidirectional=bidi)[None]
                sm = shard_map(body, mesh=mesh, in_specs=P("data", None),
                               out_specs=P("data", None))
                out = np.asarray(jax.jit(sm)(jnp.asarray(X)))
                for r in range(p):
                    np.testing.assert_allclose(out[r], X.sum(0), rtol=2e-5,
                                               atol=2e-5)
            print("ok")
        """, devices=d)


def test_fused_engine_hlo_slice_count():
    """The fused engine's scan body holds 3 dynamic slices per edge-class step
    (the seed's step had 5: the jC slice was materialized twice and every
    masked write paid a read-modify-write slice)."""
    run_sub("""
        from repro.core.dptree import dptree_allreduce
        X = jnp.ones((p, 999), jnp.float32)
        sm = shard_map(lambda x: dptree_allreduce(x[0], "data", p,
                                                  num_blocks=8)[None],
                       mesh=mesh, in_specs=P("data", None),
                       out_specs=P("data", None))
        txt = jax.jit(sm).lower(X).as_text()
        n_slice = txt.count("stablehlo.dynamic_slice")
        n_upd = txt.count("stablehlo.dynamic_update_slice")
        # fused: 3 classes x 3 takes in the scan body + 6 one-time topology
        # constant lookups = 15. The seed engine traced 3 x 5 takes (jC
        # twice + a read-modify-write slice per masked update) + 6 = 21.
        assert 0 < n_slice <= 15, (n_slice, n_upd)
        assert n_upd <= 3, n_upd
        print("ok", n_slice, n_upd)
    """)


def test_odd_device_counts():
    """Non-power-of-two p exercises the unbalanced tree paths."""
    for d in (3, 5, 7):
        run_sub("""
            from repro.core.dptree import dptree_allreduce
            rng = np.random.default_rng(2)
            X = rng.standard_normal((p, 29)).astype(np.float32)
            body = lambda x: dptree_allreduce(x[0], "data", p, num_blocks=4)[None]
            sm = shard_map(body, mesh=mesh, in_specs=P("data", None),
                               out_specs=P("data", None))
            out = np.asarray(jax.jit(sm)(jnp.asarray(X)))
            for r in range(p):
                np.testing.assert_allclose(out[r], X.sum(0), rtol=2e-5,
                                           atol=2e-5)
            print("ok")
        """, devices=d)
