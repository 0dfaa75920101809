"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, its
phases pass on CPU at reduced size (kernels interpreted, virtual devices
standing in for the four chips), and its logit checks fail on planted
faults."""

import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_refuses_without_a_tpu(argv):
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *argv],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_one_chip_phases_at_reduced_size():
    report, reqs = chip_smoke.serve_requests(reduced=True)
    assert sum(len(t) for t in report["tokens"].values()) == \
        chip_smoke.N_REQUESTS * chip_smoke.NEW_TOKENS
    worst = chip_smoke.check_served_stream(report, reqs, reduced=True)
    assert worst <= chip_smoke.LOGIT_TOL
    chip_smoke.check_kernels(interpret=True, block=4096)


@pytest.mark.parametrize("fault", ["prefill_length_short", "wrong_slot"])
def test_stream_check_fails_on_planted_engine_fault(fault, monkeypatch):
    """The engine's cache-writing prefill told one token too few of its
    chunk, or its finished row spliced into the next slot: the served
    tokens leave the uncached forward's argmax by more than the limit."""
    from repro.models import transformer as tf
    if fault == "prefill_length_short":
        prefill = tf.prefill_step
        monkeypatch.setattr(
            tf, "prefill_step", lambda p, cfg, inputs, row, lengths, *a,
            **kw: prefill(p, cfg, inputs, row, lengths - 1, *a, **kw))
    else:
        adopt = tf.adopt_prefix
        monkeypatch.setattr(
            tf, "adopt_prefix", lambda caches, row, slot: adopt(
                caches, row, (slot + 1) % chip_smoke.N_SLOTS))
    report, reqs = chip_smoke.serve_requests(reduced=True)
    with pytest.raises(RuntimeError, match="below the uncached forward"):
        chip_smoke.check_served_stream(report, reqs, reduced=True)


def test_four_chip_phases_on_virtual_devices():
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys
        sys.path.insert(0, {ROOT!r})
        sys.path.insert(0, {os.path.join(ROOT, 'src')!r})
        import jax
        import chip_smoke as cs
        cs.check_collectives(jax.devices(), payloads=(
            ("stats", 16), ("tp_partial", 4 * 72), ("grad_bucket", 1 << 14)))
        # the reduced model's 6 heads split two ways, not four
        cs.check_tp_decode(jax.devices()[:2], reduced=True)
        print("passed")
    """)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=560,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, f"\n{r.stdout[-2000:]}\n{r.stderr[-3000:]}"
    assert "passed" in r.stdout
    assert r.stdout.count("[collective]") == \
        3 * len(chip_smoke.collective_cases())


def test_tp_check_fails_when_one_rank_skips_its_partial_sum():
    """tp=2 decode whose per-token allreduce leaves out the last rank's
    partial sum: its logits leave tp=1's by more than the limit."""
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import sys
        sys.path.insert(0, {ROOT!r})
        sys.path.insert(0, {os.path.join(ROOT, 'src')!r})
        import jax
        import jax.numpy as jnp
        import chip_smoke as cs
        from repro.models import layers

        reduce = layers.tp_all_reduce

        def drop_last_rank(x):
            info = layers.tp_info()
            if info is None or info.size <= 1:
                return x
            last = jax.lax.axis_index(info.axis) == info.size - 1
            return reduce(jnp.where(last, jnp.zeros_like(x), x))

        layers.tp_all_reduce = drop_last_rank
        try:
            cs.check_tp_decode(jax.devices(), reduced=True)
        except RuntimeError as e:
            print("refused:", e)
    """)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, f"\n{r.stdout[-2000:]}\n{r.stderr[-3000:]}"
    assert "refused: check failed: tp=2 logits differ from tp=1" in r.stdout
