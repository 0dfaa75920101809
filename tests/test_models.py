"""Per-architecture smoke tests + model-level equivalences."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCHS, SHAPES, concrete_inputs, get_config
from repro.models import layers as L
from repro.models import ssm
from repro.models import transformer as tf


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_train_step_smoke(arch):
    """One forward/loss on CPU: correct shapes, finite values."""
    cfg = get_config(arch, reduced=True)
    params = tf.init_params(jax.random.PRNGKey(1), cfg)
    inputs = concrete_inputs(cfg, SHAPES["train_4k"], jax.random.PRNGKey(0),
                             batch_override=2)
    inputs = jax.tree.map(lambda x: x[:, :32] if x.ndim >= 2 else x, inputs)
    (loss, metrics) = jax.jit(lambda p, i: tf.loss_fn(p, cfg, i))(params,
                                                                  inputs)
    assert np.isfinite(float(loss))
    hs, aux = tf.forward(params, cfg, inputs)
    assert hs.shape[:2] == (2, 32) and hs.shape[2] == cfg.d_model
    assert np.isfinite(np.asarray(hs, np.float32)).all()


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_decode_smoke(arch):
    cfg = get_config(arch, reduced=True)
    params = tf.init_params(jax.random.PRNGKey(1), cfg)
    B = 2
    caches = tf.init_cache(cfg, B, 64)
    memory = (jax.random.normal(jax.random.PRNGKey(3), (B, 16, cfg.d_model),
                                cfg.compute_dtype)
              if cfg.n_enc_layers else None)
    if cfg.input_mode == "embeds":
        inp = {"embeds": jax.random.normal(jax.random.PRNGKey(2),
                                           (B, 1, cfg.d_model), jnp.bfloat16)}
        if cfg.mrope_sections:
            inp["positions"] = jnp.zeros((B, 1, 3), jnp.int32)
    else:
        inp = {"tokens": jnp.zeros((B, 1), jnp.int32)}
    step = jax.jit(lambda p, i, c: tf.decode_step(p, cfg, i, c, memory))
    for _ in range(3):
        logits, caches = step(params, inp, caches)
    assert logits.shape == (B, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("arch", ["granite_3_8b", "rwkv6_7b", "minicpm_2b",
                                  "seamless_m4t_large_v2"])
def test_prefill_equals_decode(arch):
    """Full forward and token-by-token decode agree at the last position."""
    cfg = dataclasses.replace(get_config(arch, reduced=True),
                              compute_dtype=jnp.float32)
    params = tf.init_params(jax.random.PRNGKey(1), cfg)
    B, T = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0,
                              cfg.vocab_size, jnp.int32)
    memory = (jax.random.normal(jax.random.PRNGKey(3), (B, 8, cfg.d_model),
                                jnp.float32) if cfg.n_enc_layers else None)
    if cfg.n_enc_layers:
        # enc-dec: drive the decoder stack directly with fixed memory
        x, _ = tf.embed_inputs(params, cfg, {"tokens": toks})
        hs, _, _, _ = tf._run_stack(
            params["layers"], cfg.pattern, cfg, x,
            jnp.broadcast_to(jnp.arange(T)[None], (B, T)), memory)
        hs = tf.L.rmsnorm(params["final_norm"], hs)
    else:
        hs, _ = tf.forward(params, cfg, {"tokens": toks})
    want = tf.unembed(params, cfg, hs)[:, -1]
    caches = tf.init_cache(cfg, B, 32, kv_dtype=jnp.float32)
    step = jax.jit(lambda p, i, c: tf.decode_step(p, cfg, i, c, memory))
    for t in range(T):
        logits, caches = step(params, {"tokens": toks[:, t:t + 1]}, caches)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=3e-3, atol=3e-3)


@pytest.mark.parametrize("arch", ["mixtral_8x22b", "jamba_v0_1_52b",
                                  "llama4_scout_17b_a16e"])
def test_moe_prefill_equals_decode_at_full_capacity(arch):
    """With no token dropping, MoE prefill == decode (dropping is the only
    train/serve divergence — the documented capacity semantics)."""
    cfg = get_config(arch, reduced=True)
    moe = dataclasses.replace(cfg.moe, capacity_factor=float(cfg.moe.n_experts))
    cfg = dataclasses.replace(cfg, compute_dtype=jnp.float32, moe=moe)
    params = tf.init_params(jax.random.PRNGKey(1), cfg)
    B, T = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0,
                              cfg.vocab_size, jnp.int32)
    hs, _ = tf.forward(params, cfg, {"tokens": toks})
    want = tf.unembed(params, cfg, hs)[:, -1]
    caches = tf.init_cache(cfg, B, 32, kv_dtype=jnp.float32)
    step = jax.jit(lambda p, i, c: tf.decode_step(p, cfg, i, c))
    for t in range(T):
        logits, caches = step(params, {"tokens": toks[:, t:t + 1]}, caches)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=3e-3, atol=3e-3)


def test_moe_dispatch_equals_masked():
    cfg = get_config("mixtral_8x22b", reduced=True)
    moe_hi = dataclasses.replace(cfg.moe,
                                 capacity_factor=float(cfg.moe.n_experts))
    cfg_d = dataclasses.replace(cfg, compute_dtype=jnp.float32,
                                moe=dataclasses.replace(moe_hi,
                                                        impl="dispatch"))
    cfg_m = dataclasses.replace(cfg, compute_dtype=jnp.float32,
                                moe=dataclasses.replace(moe_hi,
                                                        impl="masked"))
    params = tf.init_params(jax.random.PRNGKey(1), cfg_d)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                              cfg.vocab_size, jnp.int32)
    h1, _ = tf.forward(params, cfg_d, {"tokens": toks})
    h2, _ = tf.forward(params, cfg_m, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=1e-4, atol=1e-4)


def test_flash_equals_direct_attention():
    rng = jax.random.PRNGKey(0)
    B, T, H, KV, dh = 2, 260, 8, 2, 16
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, T, H, dh))
    k = jax.random.normal(ks[1], (B, T, KV, dh))
    v = jax.random.normal(ks[2], (B, T, KV, dh))
    for causal, window, chunk in [(True, None, None), (True, 33, None),
                                  (True, None, 64), (False, None, None)]:
        mask = L._attn_mask(T, T, causal, window, chunk)
        want = L._sdpa(q, k, v, mask, H, KV)
        got = L._flash_sdpa(q, k, v, H, KV, causal=causal, window=window,
                            chunk=chunk, bq=64, bk=96)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


def test_wkv_chunked_equals_recurrent():
    key = jax.random.PRNGKey(0)
    B, T, H, K = 2, 64, 3, 8
    ks = jax.random.split(key, 5)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, K)) * 0.5 for i in range(3))
    w_log = jnp.clip(-jnp.exp(jax.random.normal(ks[3], (B, T, H, K))),
                     -8, -1e-4)
    u = jax.random.normal(ks[4], (H, K)) * 0.1
    S0 = jnp.zeros((B, H, K, K))
    o1, s1 = ssm.wkv_recurrent(r, k, v, w_log, u, S0)
    o2, s2 = ssm.wkv_chunked(r, k, v, w_log, u, S0, chunk=16)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=2e-4, atol=2e-4)


def test_mamba_chunked_equals_naive():
    B, T, Di, N = 2, 32, 6, 4
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, T, Di)))
    dtx = jax.random.normal(ks[1], (B, T, Di)) * 0.3
    Bc = jax.random.normal(ks[2], (B, T, N)) * 0.5
    C = jax.random.normal(ks[3], (B, T, N))
    A = -jnp.exp(jax.random.normal(ks[4], (Di, N)) * 0.3)
    h = jnp.zeros((B, Di, N))
    ys = []
    for t in range(T):
        h = jnp.exp(dt[:, t, :, None] * A[None]) * h \
            + dtx[:, t, :, None] * Bc[:, t, None, :]
        ys.append(jnp.einsum("bdn,bn->bd", h, C[:, t]))
    want = jnp.stack(ys, 1)
    got, hc = ssm.mamba_scan_chunked(dt, dtx, Bc, C, A,
                                     jnp.zeros((B, Di, N)), chunk=8)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hc),
                               rtol=1e-4, atol=1e-4)


def test_ssm_blocks_pad_and_chunk_invariant():
    """The serving-prefill contract (docs/sampling_and_prefill.md): with
    ``lengths``, (a) right-pad tokens leave the carried state BIT-unchanged
    — running a padded buffer checkpoints the same cache as running exactly
    ``len`` tokens — and (b) splitting a sequence across calls reproduces
    the one-shot cache bit-for-bit (the exact token recurrence is the only
    path, so chunk boundaries are invisible)."""
    L_real, T = 11, 16
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, 32))
    lens = jnp.array([L_real, L_real], jnp.int32)

    rcfg = ssm.RWKVConfig(d_model=32, head_dim=8)
    rp = ssm.rwkv_block_init(jax.random.PRNGKey(0), rcfg)
    rc0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                       ssm.rwkv_cache_spec(rcfg, 2, jnp.float32))
    _, pad = ssm.rwkv_block(rp, x, rcfg, rc0, lengths=lens)
    _, exact = ssm.rwkv_block(rp, x[:, :L_real], rcfg, rc0, lengths=lens)
    _, c1 = ssm.rwkv_block(rp, x[:, :6], rcfg, rc0,
                           lengths=jnp.array([6, 6], jnp.int32))
    _, c2 = ssm.rwkv_block(rp, x[:, 6:L_real], rcfg, c1,
                           lengths=jnp.array([5, 5], jnp.int32))
    for k in ("shift1", "shift2", "state"):
        assert (np.asarray(pad[k]) == np.asarray(exact[k])).all(), k
        assert (np.asarray(c2[k]) == np.asarray(exact[k])).all(), ("chunk", k)

    mcfg = ssm.MambaConfig(d_model=32, d_state=8)
    mp = ssm.mamba_init(jax.random.PRNGKey(2), mcfg)
    mc0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                       ssm.mamba_cache_spec(mcfg, 2, jnp.float32))
    _, mpad = ssm.mamba_block(mp, x, mcfg, mc0, lengths=lens)
    _, mexact = ssm.mamba_block(mp, x[:, :L_real], mcfg, mc0, lengths=lens)
    _, m1 = ssm.mamba_block(mp, x[:, :6], mcfg, mc0,
                            lengths=jnp.array([6, 6], jnp.int32))
    _, m2 = ssm.mamba_block(mp, x[:, 6:L_real], mcfg, m1,
                            lengths=jnp.array([5, 5], jnp.int32))
    for k in ("conv", "ssm"):
        assert (np.asarray(mpad[k]) == np.asarray(mexact[k])).all(), k
        assert (np.asarray(m2[k]) == np.asarray(mexact[k])).all(), ("chunk", k)


def test_mamba_recurrent_prefill_matches_decode_branch_bitwise():
    """One token through the lengths-aware recurrent scan is op-for-op the
    T==1 decode branch — what makes chunked prefill then decode ticks one
    seamless bit-exact stream."""
    mcfg = ssm.MambaConfig(d_model=32, d_state=8)
    mp = ssm.mamba_init(jax.random.PRNGKey(2), mcfg)
    mc0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                       ssm.mamba_cache_spec(mcfg, 2, jnp.float32))
    x1 = jax.random.normal(jax.random.PRNGKey(3), (2, 1, 32))
    o_dec, c_dec = ssm.mamba_block(mp, x1, mcfg, mc0)
    o_pre, c_pre = ssm.mamba_block(mp, x1, mcfg, mc0,
                                   lengths=jnp.array([1, 1], jnp.int32))
    assert (np.asarray(o_dec) == np.asarray(o_pre)).all()
    for k in ("conv", "ssm"):
        assert (np.asarray(c_dec[k]) == np.asarray(c_pre[k])).all(), k


def test_mrope_reduces_to_rope_for_text():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 4, 16))
    pos = jnp.broadcast_to(jnp.arange(8)[None], (2, 8))
    plain = L.apply_rope(x, pos)
    mrope_text = L.apply_rope(x, pos, mrope_sections=(2, 3, 3))
    np.testing.assert_allclose(np.asarray(plain), np.asarray(mrope_text),
                               rtol=1e-6, atol=1e-6)


def test_param_counts_in_published_ballpark():
    """Full configs land near their published total parameter counts."""
    expect = {"minicpm_2b": (2.0e9, 3.3e9),
              "granite_3_8b": (7.0e9, 9.5e9),
              "nemotron_4_15b": (14e9, 17e9),
              "minitron_8b": (7.5e9, 10e9),
              "rwkv6_7b": (6.5e9, 8.5e9),
              "mixtral_8x22b": (130e9, 150e9),
              "jamba_v0_1_52b": (45e9, 60e9),
              "qwen2_vl_7b": (6.5e9, 9e9)}
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, (arch, n)
