"""The float32 reference against the program, on the CPU at reduced size.

The reference is written apart from ``models/``; here it must give the
program's own logits where both compute in float32, and the served-token
comparison must pass the engine's prefill-then-cached-decode streams and
refuse them when the prefill is told one token too few."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.tests import small
from chipbench import weights
from chipbench.drivers import serving
from chipbench.reference import dense_decoder as ref


def _f32(config):
    return dict(config, torch_dtype="float32")


def test_reference_equals_the_programs_forward_in_float32():
    """Same weights (drawn by path from one seed), f32 throughout: the
    reference's logits at every position are the uncached forward's."""
    from repro.models import transformer as tf
    _, _, config, _ = small.chat()
    config = _f32(config)
    cfg = dataclasses.replace(serving.program_config(config),
                              param_dtype=jnp.float32,
                              compute_dtype=jnp.float32)
    params = serving.draw_params(config, 5, cfg)
    toks = np.random.default_rng(0).integers(1, 512, (3, 24)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        hs, _ = tf.forward(params, cfg, {"tokens": jnp.asarray(toks)})
        want = np.asarray(tf.unembed(params, cfg, hs))
    at = np.tile(np.arange(24, dtype=np.int32), (3, 1))
    got = np.concatenate(ref.logits(weights.draw(ref.spec(config), 5),
                                    config, toks, at))[:3]
    scale = np.abs(want).max()
    assert np.abs(got - want).max() / scale < 1e-4


def _serve(control: bool = False):
    """Serve a few requests through the engine; the reference's gap (and
    the float8 control's, with ``control``)."""
    from repro.serving import Request
    _, _, config, wl = small.chat()
    engine = serving.build(config, wl, 9, jax.devices())
    rng = np.random.default_rng(1)
    reqs = [Request(i, tuple(rng.integers(1, 512, n)), max_new_tokens=12)
            for i, n in enumerate((5, 17, 33, 40))]
    report = engine.run(reqs)
    recs = [serving.Record(r.rid, 0.0, len(r.prompt), r.max_new_tokens)
            for r in reqs]
    gap, n, ctl = serving.served_gap(config, 9, recs,
                                     {r.rid: r.prompt for r in reqs},
                                     report["tokens"], control=control)
    assert n == 4 * 12
    return (ctl if control else gap), wl["check"]["limit"]


def test_served_streams_pass_the_comparison():
    gap, limit = _serve()
    assert gap <= limit


def test_the_float8_control_is_refused():
    """The reference in float8 in the program's place: the tokens it puts
    first lie further below the float32 reference's best than the limit
    lets a served token lie."""
    ctl, limit = _serve(control=True)
    assert ctl > limit


def test_prefill_one_token_short_is_refused(monkeypatch):
    from repro.models import transformer as tf
    prefill = tf.prefill_step
    monkeypatch.setattr(
        tf, "prefill_step", lambda p, cfg, inputs, row, lengths, *a, **kw:
        prefill(p, cfg, inputs, row, lengths - 1, *a, **kw))
    gap, limit = _serve()
    assert gap > limit


def test_float8_control_rounds_to_three_mantissa_bits():
    x = jnp.array([[1.0, 1.0625, 1.125, 448.0, -3.3]])
    y = np.asarray(ref._fp8(x, -1))
    # 1.0625 is halfway between 1 and 1.125 and rounds to even
    assert y.tolist()[0][:4] == [1.0, 1.0, 1.125, 448.0]
    assert y[0, 4] == pytest.approx(-3.25)
