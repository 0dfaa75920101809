"""Each cell's comparison sees ``correct`` come out false when the timed
path is broken underneath, once for each fault the cell can have: on the
CPU at reduced size, through the same ``run.execute`` the chip runs."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from chipbench.tests import small
from chipbench import run as cb


def _alter_tokens(monkeypatch):
    """Every token the decode step produces is replaced by the next id."""
    from repro.launch import step_fns
    make = step_fns.make_serve_step

    def make_altered(*a, **kw):
        step, sh = make(*a, **kw)

        def altered(params, inputs, caches, active, sampling=None):
            toks, caches = step(params, inputs, caches, active, sampling)
            vocab = a[0].vocab_size
            return jax.numpy.where(toks >= 0, (toks + 1) % vocab, toks), \
                caches
        return altered, sh
    monkeypatch.setattr(step_fns, "make_serve_step", make_altered)


def _state_unchanged(monkeypatch):
    """The decode step returns the caches it was given."""
    from repro.models import transformer as tf
    decode = tf.decode_step

    def stale(params, cfg, inputs, caches, *a, **kw):
        logits, _ = decode(params, cfg, inputs, caches, *a, **kw)
        return logits, caches
    monkeypatch.setattr(tf, "decode_step", stale)


def _prefill_one_short(monkeypatch):
    """The cache-writing prefill is told one token too few."""
    from repro.models import transformer as tf
    prefill = tf.prefill_step
    monkeypatch.setattr(
        tf, "prefill_step", lambda p, cfg, inputs, row, lengths, *a, **kw:
        prefill(p, cfg, inputs, row, lengths - 1, *a, **kw))


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged,
                                   _prefill_one_short])
def test_chat_comparison_fails_on_a_broken_path(fault, monkeypatch):
    fault(monkeypatch)
    bench, cell, config, wl = small.chat()
    _, res = cb.execute(bench, cell, 77, 2.0, False, jax.devices()[:1],
                        peaks=small.FAKE_PEAKS, config=config, workload=wl)
    gap = res["compared"]["served_logit_gap"]
    assert not res["correct"]
    assert gap["value"] > gap["limit"]


GRAD_SYNC = textwrap.dedent("""
    import json, sys
    sys.path[:0] = {paths!r}
    import jax, jax.numpy as jnp
    from chipbench.tests import small
    from chipbench import run as cb
    from repro.core import collectives
    reduce = collectives.all_reduce

    def exchange_left_out(x, axis, p, *a, **kw):
        return x

    def half_left_out(x, axis, p, *a, **kw):
        # the mean over the first half of the devices, scaled to a sum
        keep = jax.lax.axis_index(axis) < p // 2
        return reduce(jnp.where(keep, x, 0), axis, p, *a, **kw) * 2

    def answer_altered(x, axis, p, *a, **kw):
        out = reduce(x, axis, p, *a, **kw)
        return out.at[0].add(1.0)

    out = {{}}
    for fault in (None, exchange_left_out, half_left_out, answer_altered):
        collectives.all_reduce = fault or reduce
        bench, cell, config, wl = small.grad_sync()
        run, res = cb.execute(bench, cell, 11, 0.2, False,
                              jax.devices()[:4], peaks=small.FAKE_PEAKS,
                              config=config, workload=wl,
                              control=fault is None)
        out[fault.__name__ if fault else "clean"] = res
        if fault is None:
            out["control_error"] = run.data["control_error"]
    print(json.dumps(out))
""")


def test_grad_sync_comparison_fails_on_a_broken_path():
    code = GRAD_SYNC.format(paths=[small.ROOT,
                                   os.path.join(small.ROOT, "src")])
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["clean"]["correct"]
    # the control, a bfloat16 sum in the program's place, is refused
    assert out["control_error"] > out["clean"]["compared"]["sum_error"][
        "limit"]
    for fault in ("exchange_left_out", "half_left_out", "answer_altered"):
        err = out[fault]["compared"]["sum_error"]
        assert not out[fault]["correct"], fault
        assert err["value"] > err["limit"], fault
