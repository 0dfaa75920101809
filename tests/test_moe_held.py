"""The held-experts MoE layer and Jamba's block: the gate rule and the
dt/B/C norms against hand-written cases, the held shares adding up to the
whole layer, no token dropped, long prompts in blocks equal to one pass,
and the counts the layer reports."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import jamba_v0_1_52b as jamba
from repro.configs.base import get_config
from repro.models import layers as L
from repro.models import ssm
from repro.models import transformer as tf


def _jamba(**kw):
    cfg = get_config("jamba_v0_1_52b", reduced=True)
    return dataclasses.replace(cfg, compute_dtype=jnp.float32, **kw)


def _moe_params(cfg, key=0):
    p = tf._sub_init(jax.random.PRNGKey(key), cfg, tf.SubSpec("moe"))
    # a router that spreads the tokens (init's 0.02 scale ties them)
    p["router"] = jax.random.normal(jax.random.PRNGKey(key + 1),
                                    p["router"].shape)
    return p


def _held(p, first, count):
    """The share of ``p`` a chip holding experts ``first..+count`` has."""
    return {k: (v[first:first + count] if k in ("w_in", "w_gate", "w_out")
                else v) for k, v in p.items()}


# ------------------------------------------------------------------ gates

def test_gate_rules_by_hand():
    logits = jnp.array([[2.0, 1.0, 0.0, -1.0]])
    z = np.exp([2.0, 1.0, 0.0, -1.0]).sum()
    jamba_rule = tf.MoESettings(4, 2, gate="softmax_topk")
    mixtral_rule = tf.MoESettings(4, 2)
    g, e = tf.moe_route(logits, jamba_rule)
    np.testing.assert_allclose(g[0], [np.e ** 2 / z, np.e / z], rtol=1e-6)
    assert e[0].tolist() == [0, 1]
    g, e = tf.moe_route(logits, mixtral_rule)
    np.testing.assert_allclose(g[0], [np.e / (np.e + 1), 1 / (np.e + 1)],
                               rtol=1e-6)
    assert e[0].tolist() == [0, 1]
    # the configs keep their own rules
    assert get_config("jamba_v0_1_52b").moe.gate == "softmax_topk"
    assert get_config("mixtral_8x22b").moe.gate == "topk_softmax"
    assert get_config("llama4_scout_17b_a16e").moe.gate == "topk_softmax"


def test_layer_output_by_hand():
    """One token, four experts of width 1: the layer returns the gates
    (Jamba's rule) times each chosen expert's ``silu(x wg) (x wi) wo``."""
    cfg = _jamba(d_model=2, d_ff=1, moe=jamba.moe(4))
    x = jnp.array([[[1.0, 0.5]]])
    p = {"router": jnp.array([[2.0, 1.0, 0.0, -1.0], [0.0, 0.0, 0.0, 0.0]]),
         "w_in": jnp.arange(1.0, 9.0).reshape(4, 2, 1),
         "w_gate": jnp.ones((4, 2, 1)),
         "w_out": jnp.array([[[1.0, 0.0]], [[0.0, 1.0]], [[5.0, 5.0]],
                             [[5.0, 5.0]]])}
    out, _, counts = tf._moe_grouped(p, x, cfg)
    z = np.exp([2.0, 1.0, 0.0, -1.0]).sum()
    g0, g1 = np.e ** 2 / z, np.e / z
    silu = lambda v: v / (1 + np.exp(-v))
    h0 = silu(1.5) * (1 * 1.0 + 2 * 0.5)       # expert 0: w_in [1, 2]
    h1 = silu(1.5) * (3 * 1.0 + 4 * 0.5)       # expert 1: w_in [3, 4]
    np.testing.assert_allclose(out[0, 0], [g0 * h0, g1 * h1], rtol=1e-5)
    assert counts.tolist() == [2, 2, 1]


# ------------------------------------------------------------ Mamba norms

def test_mamba_dt_b_c_norms_by_hand():
    """x_proj's outputs dt, B and C are each RMS-normed with their own
    scale before dt_proj and the scan."""
    mcfg = ssm.MambaConfig(d_model=32, d_state=2)      # dt_rank 2, Di 64
    p = ssm.mamba_init(jax.random.PRNGKey(0), mcfg)
    xp = np.zeros((64, 6), np.float32)
    xp[0] = [3.0, 4.0, 1.0, -1.0, 2.0, 0.0]           # dt | B | C
    p.update(x_proj=jnp.asarray(xp),
             dt_norm={"scale": jnp.array([1.0, 2.0])},
             c_norm={"scale": jnp.array([0.5, 1.0])},
             dt_proj=jnp.ones((2, 64)), dt_bias=jnp.zeros(64))
    xin = jnp.zeros((1, 1, 64)).at[0, 0, 0].set(1.0)
    dt, dtx, B, C = ssm._mamba_inner(p, xin, mcfg)
    r = np.sqrt(12.5 + 1e-6)
    want_dt = np.log1p(np.exp(3 / r * 1 + 4 / r * 2))
    np.testing.assert_allclose(dt[0, 0], np.full(64, want_dt), rtol=1e-5)
    np.testing.assert_allclose(B[0, 0], [1.0, -1.0], rtol=1e-5)
    np.testing.assert_allclose(C[0, 0], [2 / np.sqrt(2) * 0.5, 0.0],
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(dtx[0, 0, 0], want_dt, rtol=1e-5)
    for leaf in ("dt_norm", "b_norm", "c_norm"):
        assert leaf in ssm.mamba_init(jax.random.PRNGKey(0), mcfg)


# ---------------------------------------------------------- held shares

@pytest.mark.parametrize("count", [1, 2])
def test_held_shares_add_up_to_the_whole_layer(count):
    """Each chip of an expert-parallel group runs the sublayer on its own
    experts; the residual every chip adds alike counted once, the shares
    add up to the uncut layer's output."""
    cfg = _jamba()
    E = cfg.moe.n_experts
    p = {"norm": L.rmsnorm_init(cfg.d_model), **_moe_params(cfg)}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 7, cfg.d_model))
    s = tf.SubSpec("moe")
    whole, *_ = tf._apply_sub(p, s, cfg, x, None, None, None)
    parts = []
    for first in range(0, E, count):
        c = dataclasses.replace(cfg, moe=jamba.moe(E, held=(first, count)))
        y, _, _, n = tf._apply_sub(_held(p, first, count), s, c, x, None,
                                   None, None)
        parts.append(y - x)
        assert 0 < int(n[0]) <= count
    np.testing.assert_allclose(x + sum(parts), whole, rtol=1e-5, atol=1e-5)


def test_held_layer_reads_only_its_experts():
    cfg = dataclasses.replace(get_config("jamba_v0_1_52b"),
                              moe=jamba.moe(16, held=(8, 8)))
    like = jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0), cfg))
    moe = like["layers"][1][1]
    assert moe["router"].shape == (cfg.n_periods, 4096, 16)
    assert moe["w_in"].shape == (cfg.n_periods, 8, 4096, 14336)
    with pytest.raises(ValueError):
        tf.MoESettings(16, 2, held=(0, 8))            # capacity dispatch
    with pytest.raises(ValueError):
        jamba.moe(16, held=(12, 8))


# -------------------------------------------------------------- no drops

def test_no_token_dropped_when_one_expert_takes_every_token():
    """Every token routes to experts 0 and 1: the capacity path drops most
    of them, the held-experts layer none (it equals the loop over every
    expert)."""
    cfg = _jamba()
    p = _moe_params(cfg)
    p["router"] = jnp.zeros_like(p["router"]).at[:, 0].set(10.0) \
        .at[:, 1].set(5.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (2, 16,
                                                          cfg.d_model)))
    out, _, counts = tf._moe_grouped(p, x, cfg)
    full = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, impl="masked"))
    want, _ = tf._moe_masked(p, x, full)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    assert counts.tolist() == [2, 64, 32]
    capped = dataclasses.replace(cfg, moe=tf.MoESettings(
        4, 2, gate="softmax_topk"))
    dropped, _ = tf._moe_dispatch(p, x, capped)
    assert not np.allclose(dropped, want, atol=1e-3)


def test_counts_leave_out_pads_and_idle_rows():
    cfg = _jamba()
    p = {"norm": L.rmsnorm_init(cfg.d_model), **_moe_params(cfg)}
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 6, cfg.d_model))
    s = tf.SubSpec("moe")
    *_, n = tf._apply_sub(p, s, cfg, x, None, None, None,
                          lengths=jnp.array([6, 2, 0]))
    assert int(n[1]) == 8 * cfg.moe.top_k            # 8 real tokens
    y, _, _, n = tf._apply_sub(p, s, cfg, x[:, :1], None, None, None,
                               active=jnp.array([True, False, True]))
    assert int(n[1]) == 2 * cfg.moe.top_k


# ------------------------------------------------ long prompts in blocks

def test_long_prompt_blocks_equal_one_pass(monkeypatch):
    """A prompt long enough to go through the experts, the Mamba scan and
    the ring attention in blocks gives the logits and the cache row one
    pass gives."""
    cfg = _jamba()
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    T, n = 32, 27
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, T), 1,
                              cfg.vocab_size, jnp.int32)

    def prefill():
        row = tf.init_cache(cfg, 1, 64, per_slot=True)
        return jax.jit(lambda p, t, r: tf.prefill_step(
            p, cfg, {"tokens": t}, r, jnp.array([n]), jnp.ones(1, bool),
            counts=True))(params, toks, row)

    want = prefill()
    monkeypatch.setattr(tf, "MOE_ROW_BLOCK", 8)
    monkeypatch.setattr(ssm, "MAMBA_TIME_BLOCK", 4)
    monkeypatch.setattr(L, "RING_QUERY_BLOCK", 4)
    got = prefill()
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    assert np.array_equal(got[2], want[2])
    # 4 MoE layers, every expert held: each real token's top-2 pairs
    assert int(want[2][:, 1].sum()) == 4 * n * cfg.moe.top_k


def test_chunked_prefill_equals_one_shot():
    """A prompt fed in two calls (the second resuming from the row the
    first left) gives the one-call prefill's logits and cache row."""
    cfg = _jamba()
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 24), 1,
                              cfg.vocab_size, jnp.int32)
    step = jax.jit(lambda p, t, r, n, resume: tf.prefill_step(
        p, cfg, {"tokens": t}, r, n, jnp.ones(1, bool), resume=resume),
        static_argnums=(4,))
    row = tf.init_cache(cfg, 1, 64, per_slot=True)
    want_logits, want = step(params, toks, row, jnp.array([24]), False)
    _, mid = step(params, toks[:, :16], row, jnp.array([16]), False)
    got_logits, got = step(params, jnp.pad(toks[:, 16:], ((0, 0), (0, 8))),
                           mid, jnp.array([8]), True)
    np.testing.assert_allclose(got_logits, want_logits, rtol=1e-5,
                               atol=1e-5)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_counts_reach_the_stats_span():
    """Every step's counts ride out with its tokens and land, per program
    and per MoE layer, on the tick's ``stats`` span."""
    from repro.configs.base import ParallelConfig
    from repro.launch.mesh import make_mesh
    from repro.obs import Tracer
    from repro.serving import Request, ServingEngine
    cfg = get_config("jamba_v0_1_52b", reduced=True)
    tracer = Tracer()
    eng = ServingEngine(cfg, ParallelConfig(), make_mesh((1, 1),
                                                         ("data", "model")),
                        tf.init_params(jax.random.PRNGKey(0), cfg),
                        n_slots=2, max_len=48, tracer=tracer)
    session = eng.start([Request(i, tuple(range(3 + i, 13 + 2 * i)),
                                 max_new_tokens=4) for i in range(3)])
    seen = []
    while session.running:
        session.tick()
        seen.append(list(session.moe_counts))
    stats = tracer.by_name("stats")
    assert len(stats) == len(seen)
    layers = tf.moe_counted_layers(cfg)
    for ev, fetched in zip(stats, seen):
        if not fetched:
            assert "moe_steps" not in ev.attrs
            continue
        assert ev.attrs["moe_steps"].split(",") == [p for p, _ in fetched]
        for i, name in enumerate(tf.MOE_COUNTS):
            per_step = [[int(v) for v in g.split(",")]
                        for g in ev.attrs[name].split(";")]
            assert per_step == [c[:, i].tolist() for _, c in fetched]
            assert all(len(g) == layers for g in per_step)
    programs = [p for f in seen for p, _ in f]
    assert programs.count("prefill") == 3 and "decode" in programs
