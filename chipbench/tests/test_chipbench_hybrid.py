"""The hybrid (Jamba) and closed-loop cells on the CPU at reduced size: the
float32 reference against the program, the engine's served tokens against
the reference (and the float8 control refused), both windows end to end,
the hybrid work counts at the cell's widths, and the three ``moe_*`` /
``hybrid_*`` readers on hand-made traces."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.tests import hybrid_small, small
from chipbench import harness, run as cb, served_check, work_hybrid
from chipbench import trace as tr
from chipbench.drivers import serving, serving_hybrid
from chipbench.reference import jamba_hybrid as ref


def _f32(config):
    return dict(config, torch_dtype="float32")


def _program_f32(config):
    return dataclasses.replace(serving_hybrid.program_config(config),
                               param_dtype=jnp.float32,
                               compute_dtype=jnp.float32)


def test_reference_equals_the_programs_forward_in_float32():
    """Same weights, float32 throughout: the reference's logits at every
    position are the program's uncached forward's, the held experts' share
    and all."""
    from repro.models import transformer as tf
    _, _, config, _ = hybrid_small.jamba_chat()
    config = _f32(config)
    cfg = _program_f32(config)
    assert cfg.moe.held == (1, 2) and cfg.moe.n_experts == 4
    params = serving_hybrid.draw_params(config, 5, cfg)
    toks = np.random.default_rng(0).integers(1, 512, (2, 20)).astype(
        np.int32)
    with jax.default_matmul_precision("highest"):
        hs, _ = tf.forward(params, cfg, {"tokens": jnp.asarray(toks)})
        want = np.asarray(tf.unembed(params, cfg, hs))
    at = np.tile(np.arange(20, dtype=np.int32), (2, 1))
    flat = serving_hybrid.draw(ref.spec(config), 5)
    got = np.concatenate(ref.logits(flat, config, toks, at))
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-4


def test_prefill_then_cached_decode_agrees_with_the_reference():
    """The slot prefill of a prompt, then one-token decode through the
    hybrid slot cache (ring K/V and Mamba state), give the logits the
    reference's full forward gives at every served position. The ring is
    float32 here, as everything else: the engine's bfloat16 ring alone
    moves the logits by about 6e-4 of their largest."""
    from repro.models import transformer as tf
    _, _, config, _ = hybrid_small.jamba_chat()
    config = _f32(config)
    cfg = _program_f32(config)
    params = serving_hybrid.draw_params(config, 6, cfg)
    rng = np.random.default_rng(2)
    prompt = rng.integers(1, 512, 11).astype(np.int32)
    n_slots, slot, max_len = 3, 1, 64
    caches = tf.init_cache(cfg, n_slots, max_len, per_slot=True,
                           kv_dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        row = tf.init_cache(cfg, 1, max_len, per_slot=True,
                            kv_dtype=jnp.float32)
        buf = np.zeros((1, 16), np.int32)
        buf[0, :len(prompt)] = prompt
        logits, row = tf.prefill_step(params, cfg, {"tokens": buf}, row,
                                      jnp.array([len(prompt)]),
                                      jnp.ones(1, bool))
        caches = tf.adopt_prefix(caches, row, slot)
        got, toks = [np.asarray(logits[0])], list(prompt)
        active = jnp.arange(n_slots) == slot
        step = jax.jit(lambda p, t, c: tf.decode_step(p, cfg, {"tokens": t},
                                                      c, active=active))
        for _ in range(6):
            toks.append(int(np.argmax(got[-1])))
            feed = np.zeros((n_slots, 1), np.int32)
            feed[slot] = toks[-1]
            logits, caches = step(params, feed, caches)
            got.append(np.asarray(logits[slot]))
    at = np.arange(len(prompt) - 1, len(toks))[None].astype(np.int32)
    want = ref.logits(serving_hybrid.draw(ref.spec(config), 6), config,
                      np.asarray(toks, np.int32)[None], at)[0][0]
    got = np.stack(got)
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-4


def _serve(control: bool):
    from repro.serving import Request
    _, _, config, wl = hybrid_small.jamba_chat()
    engine = serving_hybrid.build(config, wl, 9, jax.devices())
    rng = np.random.default_rng(1)
    reqs = [Request(i, tuple(rng.integers(1, 512, n)), max_new_tokens=10)
            for i, n in enumerate((5, 17, 33, 40))]
    report = engine.run(reqs)
    recs = [serving.Record(r.rid, 0.0, len(r.prompt), r.max_new_tokens)
            for r in reqs]
    flat = serving_hybrid.draw(ref.spec(config), 9)
    gap, n, ctl = served_check.served_gap(
        ref, config, flat, recs, {r.rid: r.prompt for r in reqs},
        report["tokens"], control=control)
    assert n == 40
    return gap, ctl, wl["check"]["limit"]


def test_served_tokens_pass_and_the_float8_control_is_refused():
    gap, ctl, limit = _serve(control=True)
    assert gap < limit < ctl


@pytest.mark.parametrize("traced", [False, True])
def test_hybrid_window_on_cpu(traced):
    bench, cell, config, wl = hybrid_small.jamba_chat()
    run, res = cb.execute(bench, cell, 2 ** 33 + 7, 2.0, traced,
                          jax.devices()[:1], peaks=small.FAKE_PEAKS,
                          config=config, workload=wl)
    assert res["correct"], res["compared"]
    assert res["attempted"] == 20 and res["failed"] == 0
    want = ({"queue_wait_p95_ms"} if traced else
            {"ttft_p95_ms", "itl_p95_ms", "tokens_per_s", "setup_s"})
    assert set(res["metrics"]) == want
    assert run.data["compiles_in_window"] == 0
    ticks = run.data["ticks"]
    steps = [p for t in ticks for p, _ in t.moe]
    assert steps.count("prefill") == 20 and "decode" in steps
    for t in ticks:
        for p, c in t.moe:
            # 4 MoE layers; 2 of the 4 experts held
            assert c.shape == (4, 3) and (c[:, 0] <= 2).all()
        decoded = [c for p, c in t.moe if p == "decode"]
        if decoded and len(t.decode_ctx):
            assert (decoded[0][:, 1] <= 2 * len(t.decode_ctx)).all()


def test_docs_window_on_cpu():
    bench, cell, config, wl = hybrid_small.docs()
    run, res = cb.execute(bench, cell, 2 ** 33 + 8, 2.0, False,
                          jax.devices()[:1], peaks=small.FAKE_PEAKS,
                          config=config, workload=wl)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 3
    assert set(res["metrics"]) == {"ttft_p95_ms", "itl_p95_ms",
                                   "tokens_per_s", "setup_s"}
    recs = run.data["records"]
    # a closed loop: never more requests out than clients
    t0 = run.data["window"][0]
    for r in recs:
        sent = t0 + r.due
        out = sum(1 for q in recs if t0 + q.due <= sent
                  and (q.done is None or q.done > sent))
        assert out <= wl["traffic"]["clients"]


# ------------------------------------------------------------ work counts

def _cell_config():
    bench = harness.Benchmark()
    return bench.config(bench.cell("jamba2_mini_ep2.chat"))


def test_hybrid_work_at_the_cells_widths():
    config = _cell_config()
    D, F = 4096, 14336
    assert work_hybrid.expert_bytes(config) == 3 * D * F * 2
    spec = ref.spec(config)
    total = sum(int(np.prod(s)) * np.dtype(d).itemsize
                for s, d in spec.values())
    experts = 4 * 8 * 3 * D * F * 2
    assert 14.37e9 < total < 14.39e9
    assert work_hybrid.non_expert_bytes(config, 3) == \
        total - experts - 8192 * D * 2 + 3 * D * 2
    # one attention layer, 8 KV heads of 128; seven Mamba layers
    assert work_hybrid.kv_bytes(config, 1000) == 2 * 1000 * 8 * 128 * 2
    assert work_hybrid.mamba_state_bytes(config) == \
        7 * (3 * 8192 * 2 + 8192 * 16 * 4)
    counts = np.array([[8, 20, 4], [7, 18, 5], [8, 22, 4], [6, 12, 3]])
    b, f = work_hybrid.moe_work(config, counts)
    assert b == 29 * 3 * D * F * 2 + 72 * 2 * D * 2
    assert f == 6 * D * F * 72


# ---------------------------------------------------- readers on a trace

RAGGED = "%ragged-dot-none.3 = bf16[32,14336]{1,0} custom-call(%a, %b)"
OTHER = "%fusion.7 = bf16[16,4096]{1,0} fusion(%c)"
PEAKS = small.FAKE_PEAKS
MS = 1e6


def _run(counts_in_tick=True):
    """Two ticks on a one-second trace: a prefill tick (the prefill
    program 0-40 ms holding two expert ops of 10 ms, and a decode program
    45-60 ms) and a decode tick (decode program 110-130 ms, two expert
    ops of 4 ms and one other op)."""
    E = tr.Event
    config = _cell_config()
    c_pre = np.array([[8, 300, 60]] * 4)
    c_dec = np.array([[6, 20, 4]] * 4)
    mods = {0: [E("jit_prefill_step(1)", 0, 40 * MS),
                E("jit_decode_step(2)", 45 * MS, 60 * MS),
                E("jit_decode_step(2)", 110 * MS, 130 * MS)]}
    ops = {0: [E(RAGGED, 5 * MS, 15 * MS), E(RAGGED, 20 * MS, 30 * MS),
               E(OTHER, 30 * MS, 39 * MS),
               E(RAGGED, 46 * MS, 49 * MS),
               E(RAGGED, 112 * MS, 116 * MS), E(RAGGED, 118 * MS, 122 * MS),
               E(OTHER, 122 * MS, 128 * MS)]}
    trace = tr.Trace(mods, ops, [], (0, 1e9))
    Tick = serving_hybrid.Tick
    moe = ([("prefill", c_pre), ("decode", c_dec)], [("decode", c_dec)])
    ticks = [Tick(0.0, 0.1, 1, 900, (1000, 2000),
                  moe=moe[0] if counts_in_tick else []),
             Tick(0.1, 0.2, 0, 0, (1001, 2001, 30),
                  moe=moe[1] if counts_in_tick else [])]
    tracer = types.SimpleNamespace(
        to_trace_ns=lambda trace, t: trace.window[0] + t * 1e9)
    run = harness.Run(cell={}, config=config, workload={}, seed=0,
                      seconds=1.0, traced=True, t_start=0.0)
    run.peaks, run.trace = PEAKS, trace
    run.data.update(records=[], ticks=ticks, tracer=tracer)
    return run, c_pre, c_dec


def _read(name, run):
    bench = harness.Benchmark()
    return bench.reader(name)(run)


def _roof(config, counts):
    b, f = work_hybrid.moe_work(config, counts)
    return max(b / PEAKS.hbm_bytes_per_s, f / PEAKS.bf16_flops)


def test_moe_decode_roofline_reads_the_expert_ops_of_decode_programs():
    run, _, c_dec = _run()
    # expert ops inside the decode programs: 3 + 4 + 4 ms
    want = 100 * 2 * _roof(run.config, c_dec) / 11e-3
    assert _read("moe_decode_roofline", run) == pytest.approx(want)


def test_moe_prefill_roofline_reads_the_expert_ops_of_prefill_programs():
    run, c_pre, _ = _run()
    want = 100 * _roof(run.config, c_pre) / 20e-3
    assert _read("moe_prefill_roofline", run) == pytest.approx(want)


def test_hybrid_decode_hbm_roofline_reads_the_whole_decode_step():
    run, _, c_dec = _run()
    need = (work_hybrid.decode_bytes(run.config, (1000, 2000), c_dec)
            + work_hybrid.decode_bytes(run.config, (1001, 2001, 30), c_dec))
    want = 100 * need / PEAKS.hbm_bytes_per_s / 35e-3
    assert _read("hybrid_decode_hbm_roofline", run) == pytest.approx(want)


@pytest.mark.parametrize("name", ["moe_decode_roofline",
                                  "moe_prefill_roofline",
                                  "hybrid_decode_hbm_roofline"])
def test_hybrid_readers_read_nothing_without_counts(name):
    """A program that reports no held-experts counts (as the parent's
    does) gives these readers nothing to read; nor does an untraced run."""
    run, _, _ = _run(counts_in_tick=False)
    assert _read(name, run) is None
    run.trace = None
    assert _read(name, run) is None
