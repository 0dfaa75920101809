"""Tensor-parallel decode correctness testbed (ISSUE 8).

Fast lane (no marker): TP config validation, shard-config math, the
PartitionSpec tables, the projection invariants (satellite 1; the
column-parallel one on a virtual-device ``tp`` mesh in a subprocess),
the autotune axis-scoped cache key (satellite 2), and the cost-model TP term.

Slow lane (``slow`` marker, 8 virtual devices in a subprocess — the
``make test-tp`` / CI ``test-tp`` entry point): token streams between
``tp=1`` and ``tp∈{2,4}`` engines across the arch × greedy/sampled ×
speculation-on/off matrix, with the per-token reduction routed through
``CollectiveConfig(method="auto")`` and a seeded autotuned dptree selection
exercised, plus the psum-baseline collective producing the same streams.

Numerical contract (documented per-op, see docs/tensor_parallel.md):

* column-parallel projections (wq/wk/wv, w_in, w_gate) are BIT-EXACT under
  sharding — each output column is the same dot product over the unsharded
  d_model, merely computed on one rank;
* row-parallel projections (wo, w_out) change the order of the contraction
  sum (tp partial sums + one allreduce), so they carry a ``2*K*eps`` error
  bound (K = contraction length, eps = f32 machine epsilon — the standard
  Higham summation bound for both orders, ~2K ulp of the magnitude sum);
* greedy token streams are nevertheless bit-identical in practice: argmax
  gaps of random-init logits dwarf the reassociation noise. The slow-lane
  matrix asserts exact stream equality.
"""

from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from _hyp import given, settings, st

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------------
# validation + shard-config math (fast)
# --------------------------------------------------------------------------

def _cfg(**kw):
    from repro.models.transformer import ModelConfig
    base = dict(name="tp-unit", n_layers=2, d_model=32, n_heads=4,
                n_kv_heads=2, d_ff=64, vocab_size=101, remat=False)
    base.update(kw)
    return ModelConfig(**base)


def test_validate_tp_accepts_divisible_and_tp1():
    from repro.models import transformer as tf
    tf.validate_tp(_cfg(), 1)
    tf.validate_tp(_cfg(), 2)
    tf.validate_tp(_cfg(n_heads=8, n_kv_heads=4, d_ff=64), 4)


def test_validate_tp_rejects_with_clear_error():
    from repro.models import transformer as tf
    with pytest.raises(ValueError, match=r"n_kv_heads=2.*not divisible.*4"):
        tf.validate_tp(_cfg(), 4)          # heads 4 ok, kv 2 not
    with pytest.raises(ValueError, match=r"n_heads=6"):
        tf.validate_tp(_cfg(n_heads=6, n_kv_heads=6), 4)
    with pytest.raises(ValueError, match=r"d_ff=60"):
        tf.validate_tp(_cfg(d_ff=60), 8)
    # pure-recurrent stacks have nothing to shard — any tp validates
    tf.validate_tp(_cfg(pattern=(("rwkv",),), n_layers=2), 8)


def test_tp_shard_config_divides_and_pins_head_dim():
    from repro.models import transformer as tf
    cfg = _cfg()
    assert tf.tp_shard_config(cfg, 1) is cfg
    s = tf.tp_shard_config(cfg, 2)
    assert (s.n_heads, s.n_kv_heads, s.d_ff) == (2, 1, 32)
    assert s.hdim == cfg.hdim          # head_dim pinned, not re-derived
    assert s.d_model == cfg.d_model and s.vocab_size == cfg.vocab_size


def test_tp_param_specs_mark_only_sharded_kinds():
    from jax.sharding import PartitionSpec as P
    from repro.models import transformer as tf
    cfg = _cfg(pattern=(("attn", "mlp"), ("mamba", "moe")), n_layers=2,
               moe=tf.MoESettings(n_experts=2, top_k=1))
    specs = tf.tp_param_specs(cfg)
    assert specs["embed"] == P()                       # replicated
    (attn, mlp), (mamba, moe) = specs["layers"]
    assert attn["wq"] == P(None, None, "tp")           # heads = columns
    assert attn["wo"] == P(None, "tp", None)           # row-parallel
    assert attn["norm"]["scale"] == P(None)
    assert mlp["w_in"] == P(None, None, "tp")
    assert mlp["w_out"] == P(None, "tp", None)
    assert moe["router"] == P(None, None, None)        # routing replicated
    assert moe["w_in"] == P(None, None, None, "tp")
    assert moe["w_out"] == P(None, None, "tp", None)
    # the recurrent mixer is fully replicated under TP
    assert all(s == P(*(None,) * len(s)) or s == P()
               for s in (v for v in _leaves(mamba)))


def _leaves(tree):
    import jax
    return jax.tree.leaves(tree, is_leaf=lambda v: hasattr(v, "index"))


def test_tp_cache_specs_shard_kv_heads_only():
    from jax.sharding import PartitionSpec as P
    from repro.models import transformer as tf
    cfg = _cfg(pattern=(("attn", "mamba"),), n_layers=1)
    attn_spec, mamba_spec = tf.tp_cache_specs(cfg)
    assert attn_spec["k"] == P(None, None, None, "tp")
    assert attn_spec["v"] == P(None, None, None, "tp")
    assert attn_spec["pos"] == P()
    import jax
    assert all(s == P() for s in jax.tree.leaves(
        mamba_spec, is_leaf=lambda v: isinstance(v, P)))


def test_engine_rejects_tp_without_tp_mesh():
    import jax
    from repro.configs.base import ParallelConfig
    from repro.launch.mesh import make_mesh
    from repro.models import transformer as tf
    from repro.serving import ServingEngine
    cfg = _cfg()
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="'tp' mesh axis"):
        ServingEngine(cfg, ParallelConfig(tp_shards=2), mesh, params)


# --------------------------------------------------------------------------
# satellite 1: property-based projection invariants (hypothesis via _hyp)
# --------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(heads=st.integers(1, 6), dh=st.integers(1, 16),
       d_model=st.integers(1, 24), tp_log2=st.integers(1, 3),
       seed=st.integers(0, 2**16))
def test_tp_row_parallel_projection_within_ulp_bound(heads, dh, d_model,
                                                     tp_log2, seed):
    """Sharded-then-allreduced row-parallel projection (the wo/w_out shape)
    matches the unsharded reference within the stated ``2*K*eps`` bound."""
    tp = 2 ** tp_log2
    K = heads * dh * tp                     # contraction length, tp-divisible
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((3, K)).astype(np.float32)
    w = rng.standard_normal((K, d_model)).astype(np.float32)
    ref = x @ w
    parts = [x[:, i * K // tp:(i + 1) * K // tp]
             @ w[i * K // tp:(i + 1) * K // tp, :] for i in range(tp)]
    sharded = np.sum(np.stack(parts), axis=0, dtype=np.float32)
    eps = np.finfo(np.float32).eps
    bound = 2 * K * eps * (np.abs(x) @ np.abs(w)) + 1e-30
    assert np.all(np.abs(sharded - ref) <= bound), \
        (np.max(np.abs(sharded - ref) / bound), K, tp)


def test_tp_column_parallel_projection_bit_exact():
    """Column-parallel projections (wq/wk/wv/w_in/w_gate) are BIT-exact
    under sharding: each output column is the same unsharded-d_model dot
    product, merely computed on one rank. Checked on a ``tp`` mesh of
    virtual devices, with every column-split weight that
    ``tp_param_specs`` names, over random head/width/tp shapes."""
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, {os.path.join(ROOT, "src")!r})
        import numpy as np
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_tp_mesh
        from repro.models import transformer as tf

        rng = np.random.default_rng(0)
        shapes = [(1, 1, 1, 2), (6, 16, 24, 8), (1, 1, 24, 8)] + [
            (int(rng.integers(1, 7)), int(rng.integers(1, 17)),
             int(rng.integers(1, 25)), 2 ** int(rng.integers(1, 4)))
            for _ in range(17)]
        checked = set()
        for i, (heads, dh, d_model, tp) in enumerate(shapes):
            cfg = tf.ModelConfig(name="tp-col", n_layers=1, d_model=d_model,
                                 n_heads=heads * tp, n_kv_heads=heads * tp,
                                 head_dim=dh, d_ff=heads * dh * tp,
                                 vocab_size=16, remat=False)
            params = tf.init_params(jax.random.PRNGKey(i), cfg)
            specs = tf.tp_param_specs(cfg)
            mesh = make_tp_mesh(tp)
            x = jax.random.normal(jax.random.PRNGKey(-i), (3, d_model),
                                  cfg.compute_dtype)
            proj = lambda x, w: x @ w.astype(x.dtype)    # as layers.py
            for sub, sub_specs in zip(params["layers"][0],
                                      specs["layers"][0]):
                for name, spec in sub_specs.items():
                    # drop the leading period axis
                    spec = P(*spec[1:]) if isinstance(spec, P) else None
                    if spec != P(None, "tp"):
                        continue
                    w = sub[name][0]
                    want = jax.jit(proj)(x, w)
                    got = jax.jit(jax.shard_map(
                        proj, mesh=mesh, in_specs=(P(), spec),
                        out_specs=spec))(x, w)
                    assert np.array_equal(np.asarray(got, np.float32),
                                          np.asarray(want, np.float32)), \
                        (name, heads, dh, d_model, tp)
                    checked.add(name)
        assert checked == {{"wq", "wk", "wv", "w_in", "w_gate"}}, checked
        print("COLUMNS OK")
    """)
    assert "COLUMNS OK" in _run_sub(script, timeout=300)


@settings(max_examples=16, deadline=None)
@given(heads=st.integers(1, 12), kv=st.integers(1, 12),
       d_ff=st.integers(1, 96), tp_log2=st.integers(1, 3))
def test_tp_infeasible_specs_rejected_with_offender_named(heads, kv, d_ff,
                                                          tp_log2):
    """Random head/FFN shard specs: infeasible ones raise naming the
    offending dim; feasible ones yield exactly-divided shard configs."""
    from repro.models import transformer as tf
    tp = 2 ** tp_log2
    cfg = _cfg(n_heads=heads, n_kv_heads=kv, d_ff=d_ff)
    feasible = heads % tp == 0 and kv % tp == 0 and d_ff % tp == 0
    if feasible:
        s = tf.tp_shard_config(cfg, tp)
        assert (s.n_heads * tp, s.n_kv_heads * tp, s.d_ff * tp) == \
            (heads, kv, d_ff)
    else:
        with pytest.raises(ValueError) as ei:
            tf.validate_tp(cfg, tp)
        msg = str(ei.value)
        assert f"tp={tp}" in msg
        offenders = [f"n_heads={heads}" if heads % tp else None,
                     f"n_kv_heads={kv}" if kv % tp else None,
                     f"d_ff={d_ff}" if d_ff % tp else None]
        assert all(o in msg for o in offenders if o), (msg, offenders)


# --------------------------------------------------------------------------
# satellite 2: axis-scoped autotune cache key
# --------------------------------------------------------------------------

def test_autotune_axis_scoped_key_and_roundtrip(tmp_path):
    """A decode-sized TP tuning must not replay onto a gradient-bucket
    config sharing (p, nbytes, dtype, topology); TuneResult round-trips the
    axis field through the JSON cache; legacy axis-less entries keep
    matching every axis (old cache files stay valid)."""
    from repro.core import autotune as at
    path = str(tmp_path / "at.json")
    cache = at.AutotuneCache(path)
    tp_win = at.TuneResult("dptree", 1, 1e-6, axis="tp")
    cache.put(4, 4096, "float32", "tpu_v5e_ici", tp_win)
    cache.save()

    fresh = at.AutotuneCache(path)                  # reload from disk
    assert fresh.get(4, 4096, "float32", "tpu_v5e_ici", axis="tp") == tp_win
    # the SAME (p, nbytes, dtype, fabric) probed for the data axis: miss
    assert fresh.get(4, 4096, "float32", "tpu_v5e_ici", axis="data") is None
    assert fresh.get(4, 4096, "float32", "tpu_v5e_ici") is None

    # legacy axis-less entry: matches any axis probe (backward compat)...
    legacy = at.TuneResult("sptree", 2, 2e-6)
    fresh.put(4, 4096, "float32", "tpu_v5e_ici", legacy)
    assert fresh.get(4, 4096, "float32", "tpu_v5e_ici", axis="data") == legacy
    # ...but the axis-tagged entry still wins for its own axis
    assert fresh.get(4, 4096, "float32", "tpu_v5e_ici", axis="tp") == tp_win


def test_autotune_tune_threads_axis_into_result(tmp_path):
    from repro.core import autotune as at, cost_model as cm
    cache = at.AutotuneCache(str(tmp_path / "at.json"))
    res = at.tune(lambda algo, b: {"dptree": 1.0, "ring": 9.0}.get(
        algo.split("+")[0], 5.0), 4, 1024, "float32", "t", cm.TPU_V5E,
        algorithms=("dptree", "ring"), cache=cache, save=False, axis="tp")
    assert res.algorithm == "dptree" and res.axis == "tp"
    assert cache.get(4, 1024, "float32", "t", axis="tp") == res
    assert cache.get(4, 1024, "float32", "t", axis="data") is None


def test_collectives_pick_consults_axis_scoped_entry(tmp_path, monkeypatch):
    """``plan`` under method='auto' probes the cache with the reduction's
    own axis name, so a 'tp' winner is replayed on the tp axis and ignored
    on 'data'."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    from repro.core import autotune as at
    from repro.core import collectives as C
    at.reset_cache()
    try:
        at.get_cache().put(4, 4096, "float32", "tpu_v5e_ici",
                           at.TuneResult("redbcast", 3, 1e-6, axis="tp"))
        cfg = C.CollectiveConfig(method="auto")
        model = C.plan(cfg, 4, 4096, "float32")  # no axis: legacy key only
        tp = C.plan(cfg, 4, 4096, "float32", axis_name="tp")
        assert (tp.algorithm, tp.num_blocks) == ("redbcast", 3)
        # model fallback, not replay
        assert C.plan(cfg, 4, 4096, "float32", axis_name="data") == model
    finally:
        at.reset_cache()


# --------------------------------------------------------------------------
# cost model: the TP term
# --------------------------------------------------------------------------

def test_cost_model_tp_term_additive_and_latency_bound():
    from repro.core import cost_model as cm
    m = cm.TPU_V5E
    decode_bytes = 4 * 256 * 4          # n_slots * d_model * f32
    assert cm.tp_time(1, decode_bytes, m) == 0.0
    t4 = cm.tp_time(4, decode_bytes, m)
    assert t4 > 0.0
    # additive over the hierarchy, and present even at p=1 (one TP replica)
    base = cm.hier_time(16, 1 << 24, 8, cm.TPU_V5E_INTERPOD)
    with_tp = cm.hier_time(16, 1 << 24, 8, cm.TPU_V5E_INTERPOD,
                           tp=4, tp_bytes=decode_bytes)
    assert with_tp == pytest.approx(base + t4)
    assert cm.hier_time(1, 1 << 24, 8, m, tp=4, tp_bytes=decode_bytes) == \
        pytest.approx(t4)

    # decode-sized messages are latency-bound: the dual-root tree's O(log p)
    # depth beats the ring's 2(p-1) steps once p is large enough to amortize
    # its constants (tp∈{2,8,16}; at tp=4 the model has the ring ahead by
    # ~8% and tp_time takes the min either way); at gradient-bucket sizes
    # the ring's bandwidth term wins everywhere
    for tp in (2, 8, 16):
        b = cm.optimal_blocks(tp, float(decode_bytes), m, "dptree")
        assert cm.dptree_time(tp, decode_bytes, b, m) < \
            cm.ring_time(tp, decode_bytes, m)
    for tp in (2, 4, 8):
        b = cm.optimal_blocks(tp, float(decode_bytes), m, "dptree")
        assert cm.tp_time(tp, decode_bytes, m) == min(
            cm.dptree_time(tp, decode_bytes, b, m),
            cm.ring_time(tp, decode_bytes, m))
    grad_bytes = 256 << 20
    assert cm.best_algorithm(8, float(decode_bytes), m,
                             group_size=None) in ("dptree", "sptree")
    assert cm.ring_time(8, grad_bytes, m) < cm.dptree_time(
        8, grad_bytes, cm.optimal_blocks(8, float(grad_bytes), m, "dptree"),
        m)


# --------------------------------------------------------------------------
# slow lane: 8-virtual-device stream-identity matrix (make test-tp)
# --------------------------------------------------------------------------

def _run_sub(script: str, timeout: int = 1200):
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=timeout)
    assert r.returncode == 0, \
        f"\nOUT:{r.stdout[-3000:]}\nERR:{r.stderr[-4000:]}"
    return r.stdout


_PRELUDE = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["REPRO_AUTOTUNE_CACHE"] = {cache_path!r}
    import sys
    sys.path.insert(0, {src!r})
    import dataclasses
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs.base import ParallelConfig, get_config
    from repro.core import autotune as at
    from repro.core import collectives as C
    from repro.core.collectives import CollectiveConfig
    from repro.launch.mesh import make_mesh, make_tp_mesh
    from repro.models import transformer as tf
    from repro.serving import NgramDrafter, Request, ServingEngine
    from repro.serving.sampling import SamplingParams
    from repro.serving.speculative import SpecParams

    # (method, p, nbytes, axis, algo, num_blocks, hits): ``hits`` are the
    # autotune cache probes made while planning that reduction which
    # returned an entry, as ((p, nbytes, dtype, fabric, axis), entry).
    picks = []
    lookups = []
    _orig_lookup = at.lookup
    def _rec_lookup(p, nbytes, dtype, topology, axis=None):
        out = _orig_lookup(p, nbytes, dtype, topology, axis)
        lookups.append(((p, nbytes, dtype, topology, axis), out))
        return out
    C.autotune.lookup = _rec_lookup
    _orig_plan = C.plan
    def _rec(config, p, nbytes, dtype, op=jnp.add, axis_name=None):
        n = len(lookups)
        out = _orig_plan(config, p, nbytes, dtype, op, axis_name)
        hits = [(k, e) for k, e in lookups[n:] if e is not None]
        picks.append((config.method, int(p), int(nbytes), axis_name,
                      out.algorithm, out.num_blocks, hits))
        return out
    C.plan = _rec

    def run_engine(cfg, tp, reqs, collective=None, drafter=True, seed=1):
        if tp == 1:
            mesh = make_mesh((1, 1), ("data", "model"))
            pcfg = ParallelConfig()
        else:
            mesh = make_tp_mesh(tp)
            kw = dict(tp_shards=tp)
            if collective is not None:
                kw["tp_collective"] = collective
            pcfg = ParallelConfig(**kw)
        params = tf.init_params(jax.random.PRNGKey(seed), cfg)
        eng = ServingEngine(cfg, pcfg, mesh, params, n_slots=4, max_len=32,
                            min_prefill_bucket=8,
                            drafter=NgramDrafter() if drafter else None)
        rep = eng.run(reqs())
        assert rep["tp"] == tp
        return rep["tokens"]
"""


def _prelude(tmp_path):
    return textwrap.dedent(_PRELUDE.format(
        cache_path=str(tmp_path / "at.json"), src=ROOT + "/src"))


@pytest.mark.slow          # 8-virtual-device subprocess (see pytest.ini)
def test_tp_streams_bit_identical_arch_sampling_spec_matrix(tmp_path):
    """tp=1 vs tp=2: greedy, sampled, and speculative token streams are
    bit-identical on a dense-attention arch (minicpm) AND an SSM-hybrid
    arch (jamba: mamba+attn+moe+mlp — the recurrent mixers replicate, the
    rest shards), with every per-token reduction routed through
    ``CollectiveConfig(method="auto")`` on the 'tp' axis, a seeded
    autotuned dptree selection replayed, and the explicit psum baseline
    producing the same streams."""
    script = _prelude(tmp_path) + textwrap.dedent("""
        def reqs():
            return [Request(0, (5, 6, 7), 5),
                    Request(1, (3, 1, 4, 1, 5), 6,
                            sampling=SamplingParams(temperature=0.8,
                                                    top_k=20, seed=7)),
                    Request(2, (2, 7, 1), 6, spec=SpecParams(draft_k=3)),
                    Request(3, (9, 9), 4)]

        for arch in ("minicpm_2b", "jamba_v0_1_52b"):
            cfg = dataclasses.replace(get_config(arch, reduced=True),
                                      compute_dtype=jnp.float32, remat=False)
            # seed a measured dptree winner for the decode-sized TP payload
            nb = 4 * cfg.d_model * 4          # n_slots * D * f32
            at.get_cache().put(2, nb, "float32", "tpu_v5e_ici",
                               at.TuneResult("dptree", 1, 1e-6, axis="tp"))
            at.get_cache().save()
            ref = run_engine(cfg, 1, reqs)
            got = run_engine(cfg, 2, reqs)
            assert got == ref, (arch, ref, got)
            # psum baseline: same streams through XLA's own allreduce
            psum = run_engine(cfg, 2, reqs,
                              collective=CollectiveConfig(method="psum"))
            assert psum == ref, (arch, ref, psum)
            # the seeded decode-payload entry was replayed as dptree: every
            # such pick found the entry in the cache (a cost-model pick of
            # the same setting would not) and ran what it holds
            hits = [pk for pk in picks
                    if pk[3] == "tp" and pk[2] == nb and pk[0] == "auto"]
            key = (2, nb, "float32", "tpu_v5e_ici", "tp")
            assert hits and all(
                a == "dptree" and b == 1 and [k for k, _ in found] == [key]
                and (found[0][1].algorithm, found[0][1].num_blocks)
                == ("dptree", 1)
                for (_, _, _, _, a, b, found) in hits), (arch, hits)
            picks.clear()
            print("ARCH-OK", arch)
        print("MATRIX OK")
    """)
    out = _run_sub(script)
    assert "MATRIX OK" in out and out.count("ARCH-OK") == 2


@pytest.mark.slow          # 8-virtual-device subprocess (see pytest.ini)
def test_tp_four_way_streams_and_auto_tree_selection(tmp_path):
    """tp∈{1,2,4} greedy streams bit-identical (heads bumped to divide 4;
    the zoo's reduced attn configs stop at 2-way kv), and with no cache
    seeded the cost-model fallback still routes the per-token reduction to
    a tree schedule — never psum — inside the fully-manual TP region."""
    script = _prelude(tmp_path) + textwrap.dedent("""
        cfg = dataclasses.replace(get_config("minicpm_2b", reduced=True),
                                  n_heads=8, n_kv_heads=8, head_dim=8,
                                  compute_dtype=jnp.float32, remat=False)
        def reqs():
            return [Request(i, (1 + i, 2, 3 + i), 4 + i % 2, arrival=i)
                    for i in range(4)]
        streams = {tp: run_engine(cfg, tp, reqs, drafter=False)
                   for tp in (1, 2, 4)}
        assert streams[1] == streams[2] == streams[4], streams
        tp_picks = [pk for pk in picks if pk[3] == "tp"]
        assert tp_picks and all(pk[0] == "auto" for pk in tp_picks)
        algos = {pk[4] for pk in tp_picks}
        assert algos <= {"dptree", "sptree", "redbcast", "ring"} \\
            and "dptree" in algos, algos
        print("TP4 OK", sorted(algos))
    """)
    assert "TP4 OK" in _run_sub(script)


@pytest.mark.slow          # 8-virtual-device subprocess (see pytest.ini)
def test_tp_replicated_recurrent_arch_exact(tmp_path):
    """A pure-recurrent arch (rwkv6) under TP replicates every sublayer:
    streams are trivially exact at tp∈{2,4} and no 'tp' reduction is ever
    traced (nothing shards, nothing needs completing)."""
    script = _prelude(tmp_path) + textwrap.dedent("""
        cfg = dataclasses.replace(get_config("rwkv6_7b", reduced=True),
                                  compute_dtype=jnp.float32, remat=False)
        def reqs():
            return [Request(0, (5, 6, 7), 5), Request(1, (2, 3), 4)]
        streams = {tp: run_engine(cfg, tp, reqs, drafter=False)
                   for tp in (1, 2, 4)}
        assert streams[1] == streams[2] == streams[4], streams
        assert not [pk for pk in picks if pk[3] == "tp"]
        print("RWKV OK")
    """)
    assert "RWKV OK" in _run_sub(script)
