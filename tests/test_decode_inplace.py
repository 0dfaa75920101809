"""The slot decode step writes each active row's one new K/V token into the
stacked ring in place. Rows that are not active keep every cache leaf bit
for bit; active rows, and their logits, match the formulation the step
replaced: the whole stack through the scan's xs/ys, then a row merge."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import layers as L
from repro.models import transformer as tf
from repro.models.transformer import ModelConfig

MAX_LEN = 8
ACTIVE = np.array([True, False, True, False, True, True])
# rows 1, 2 and 5 have decoded past the ring's length and wrapped it
POS = np.array([3, 11, 17, 6, 0, 8], np.int32)


def _dense(**kw):
    return ModelConfig(name="inplace-tiny", n_layers=3, d_model=32,
                       n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=97,
                       remat=False, **kw)


CASES = {
    "bf16": lambda: _dense(),
    "int8": lambda: _dense(kv_quant=True),
    "hybrid": lambda: get_config("jamba_v0_1_52b", reduced=True),
}


def _filled_caches(cfg, key):
    """Per-slot caches with every leaf drawn at random, so that a write
    where none belongs shows, and the ring positions ``POS``."""
    caches = tf.init_cache(cfg, len(POS), MAX_LEN, per_slot=True)
    leaves, tree = jax.tree_util.tree_flatten_with_path(caches)
    keys = jax.random.split(key, len(leaves))

    def fill(path, leaf, k):
        if path[-1].key == "pos":
            return jnp.broadcast_to(jnp.asarray(POS), leaf.shape)
        if leaf.dtype == jnp.int8:
            return jax.random.randint(k, leaf.shape, -127, 128, jnp.int8)
        if path[-1].key in ("ks", "vs"):
            return jax.random.uniform(k, leaf.shape, leaf.dtype, 1e-3, 0.05)
        return jax.random.normal(k, leaf.shape, leaf.dtype)

    return jax.tree_util.tree_unflatten(
        tree, [fill(p, l, k) for (p, l), k in zip(leaves, keys)])


def _merged_decode(params, cfg, inputs, caches, active):
    """The formulation the in-place step replaced: every cache through the
    layer scan as xs/ys, then rows merged back where ``active`` is False."""
    x, _ = tf.embed_inputs(params, cfg, inputs)
    x, _, new, _ = tf._run_stack(params["layers"], cfg.pattern, cfg, x,
                                 None, None, caches)
    logits = tf.unembed(params, cfg, L.rmsnorm(params["final_norm"], x))
    merged = tf.merge_cache_rows(new, caches, active)
    return logits[:, -1].astype(jnp.float32), tf.advance_pos(merged, active)


@pytest.mark.parametrize("case", sorted(CASES))
def test_slot_decode_in_place_matches_merge(case):
    cfg = CASES[case]()
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    caches = _filled_caches(cfg, jax.random.PRNGKey(1))
    active = jnp.asarray(ACTIVE)
    inputs = {"tokens": jax.random.randint(
        jax.random.PRNGKey(2), (len(POS), 1), 0, cfg.vocab_size, jnp.int32)}

    want_logits, want = jax.jit(
        lambda p, i, c, a: _merged_decode(p, cfg, i, c, a))(
        params, inputs, caches, active)
    got_logits, got = jax.jit(
        lambda p, i, c, a: tf.decode_step(p, cfg, i, c, active=a))(
        params, inputs, caches, active)

    flat_in = jax.tree_util.tree_leaves_with_path(caches)
    flat_want = jax.tree.leaves(want)
    flat_got = jax.tree.leaves(got)
    assert len(flat_in) == len(flat_got) == len(flat_want)
    for (path, old), w, g in zip(flat_in, flat_want, flat_got):
        old, w, g = np.asarray(old), np.asarray(w), np.asarray(g)
        name = jax.tree_util.keystr(path)
        assert g.shape == old.shape and g.dtype == old.dtype, name
        # batch rows are axis 1 of every stacked leaf
        np.testing.assert_array_equal(g[:, ~ACTIVE], old[:, ~ACTIVE],
                                      err_msg=name)
        np.testing.assert_array_equal(g[:, ACTIVE], w[:, ACTIVE],
                                      err_msg=name)
    # the active rows' rings did take a write
    ring = tf.cache_layer_kinds(cfg).index("attn")
    assert not np.array_equal(np.asarray(got[ring]["k"])[:, ACTIVE],
                              np.asarray(caches[ring]["k"])[:, ACTIVE])
    np.testing.assert_array_equal(np.asarray(got_logits)[ACTIVE],
                                  np.asarray(want_logits)[ACTIVE])
