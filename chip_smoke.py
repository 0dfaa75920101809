"""Smoke run of the main paths on a TPU, in one process.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # four chips, nothing else

One chip: serves ``minicpm_2b`` at its published width through
``launch/serve.py``'s continuous-batching path (6 greedy requests, 4 slots,
prompts of 16-128 tokens, 16 new tokens each), checks that every request
finishes with in-vocabulary tokens, checks every served token against
the model's uncached forward over the same prompt and tokens, and runs the
allreduce's Pallas kernels (``combine3``, ``compress_bf16`` /
``decompress_bf16``) at a pipeline-block size against ``kernels/ref.py``.

Four chips: ``collectives.all_reduce`` with every schedule method, each
named explicitly, against ``psum`` at three payloads (the 16-float stats
vector, one tensor-parallel decode partial sum, a 64 MiB gradient bucket),
then ``minicpm_2b`` decode at ``tp_shards=4`` against the same decode at
tp=1.

The script needs a TPU: where JAX finds none it exits non-zero before any
phase and prints no result. Each phase prints its compile and run seconds;
the last line of a passing run is one JSON object naming the device. Any
failed check raises, so the run exits non-zero without that line.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.metadata
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "minicpm_2b"
SEED = 0

# serving workload (one chip)
N_REQUESTS, N_SLOTS, CACHE_LEN = 6, 4, 256
PROMPT_LENS, NEW_TOKENS = (16, 128), 16

# Two bf16 computations of one model (the engine's cached steps against the
# uncached forward; tp=4 against tp=1) differ by rounding. As a share of the
# largest logit: a served token's logit in the uncached forward may sit this
# far below the largest, and tp=4 logits this far from tp=1. Rounding alone
# stayed at or under 0.031 in CPU rehearsals of the reduced model at 2 and
# 40 layers; faults planted there (tests/test_chip_smoke.py: prefill length
# one short, prefill row spliced into the wrong slot, one rank's TP partial
# sum dropped) read 0.5 to 1.95.
LOGIT_TOL = 0.1

# one pipeline block of a 64 MiB f32 gradient bucket cut into 16 blocks
KERNEL_BLOCK = 1 << 20

# (name, f32 elements per device) reduced on four chips
PAYLOADS = (("stats", 16),                  # serving per-tick stats vector
            ("tp_partial", 4 * 2304),       # 4 slots x minicpm_2b d_model
            ("grad_bucket", 16 << 20))      # 64 MiB gradient bucket
TP_PROMPT = 16                              # tokens decoded at tp=1 and tp=4


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def _max_rel(got, want) -> float:
    """Largest |got - want| over the largest |want|."""
    import numpy as np
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


# --------------------------------------------------------------- one chip

def serve_requests(reduced: bool = False) -> tuple:
    """Serve the synthetic workload through ``launch.serve``; returns the
    report and the requests it served."""
    from repro.configs.base import get_config
    from repro.launch import serve
    argv = ["--arch", ARCH, "--continuous",
            "--requests", str(N_REQUESTS), "--slots", str(N_SLOTS),
            "--cache-len", str(CACHE_LEN), "--arrival-gap", "1",
            "--prompt-len", *map(str, PROMPT_LENS),
            "--max-new", str(NEW_TOKENS), str(NEW_TOKENS),
            "--seed", str(SEED)] + (["--reduced"] if reduced else [])
    report = serve.main(argv)
    # the same stream serve_continuous built from these flags
    vocab = get_config(ARCH, reduced=reduced).vocab_size
    reqs = serve.synthetic_workload(N_REQUESTS, vocab, gap=1, seed=SEED + 1,
                                    prompt_lens=PROMPT_LENS,
                                    max_new=(NEW_TOKENS, NEW_TOKENS))
    _check(report["requests"] == N_REQUESTS,
           f"{report['requests']}/{N_REQUESTS} requests finished")
    for r in reqs:
        toks = report["tokens"].get(r.rid)
        _check(toks is not None and len(toks) == NEW_TOKENS,
               f"request {r.rid} emitted {toks and len(toks)} of "
               f"{NEW_TOKENS} tokens")
        _check(all(0 <= t < vocab for t in toks),
               f"request {r.rid} emitted a token outside [0, {vocab})")
    return report, reqs


def check_served_stream(report, reqs, reduced: bool = False) -> float:
    """Every token the engine served against the model's uncached forward,
    with the weights the serving run drew. One forward over each request's
    prompt and the tokens it was served gives, at every position, the
    logits the engine's cache-writing prefill (first token) and its decode
    steps (the rest) should have taken the argmax of. A served token must
    be that argmax, or where rounding splits a near tie, within
    ``LOGIT_TOL`` of the largest logit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import serve
    from repro.models import transformer as tf
    cfg = serve.serving_config(ARCH, reduced)
    gc.collect()        # the serving run's weights leave the device first
    params = tf.init_params(jax.random.PRNGKey(SEED), cfg)
    served = np.array([report["tokens"][r.rid] for r in reqs], np.int32)
    tokens = np.zeros((len(reqs), CACHE_LEN), np.int32)
    at = np.zeros(served.shape, np.int32)     # position predicting each
    for i, r in enumerate(reqs):
        n = len(r.prompt)
        tokens[i, :n + NEW_TOKENS - 1] = [*r.prompt, *served[i, :-1]]
        at[i] = n - 1 + np.arange(NEW_TOKENS)

    @jax.jit
    def forward_logits(params, tokens, at):
        hs, _ = tf.forward(params, cfg, {"tokens": tokens})
        hs = hs[jnp.arange(hs.shape[0])[:, None], at]
        return tf.unembed(params, cfg, hs).astype(jnp.float32)

    logits = np.asarray(forward_logits(params, tokens, at))
    top = logits.max(-1)
    margin = (top - np.take_along_axis(logits, served[..., None], -1)[..., 0]
              ) / np.abs(logits).max(-1)
    exact = int((logits.argmax(-1) == served).sum())
    worst = float(margin.max())
    print(f"[stream] {served.size} served tokens of {len(reqs)} requests: "
          f"{exact} are the uncached forward's argmax; largest gap to its "
          f"max logit {worst:.3e} of max |logit| (limit {LOGIT_TOL})")
    _check(np.isfinite(logits).all() and worst <= LOGIT_TOL,
           f"served tokens sit {worst:.3e} below the uncached forward's "
           f"max logit > {LOGIT_TOL}")
    return worst


def check_kernels(interpret: bool = False, block: int = KERNEL_BLOCK) -> None:
    """The collective's Pallas kernels against their jnp references."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import block_combine, quantize, ref
    keys = jax.random.split(jax.random.PRNGKey(SEED), 3)
    a, b, c = (jax.random.normal(k, (block,), jnp.float32) for k in keys)
    for dt in (jnp.float32, jnp.bfloat16):
        x, y, z = (v.astype(dt) for v in (a, b, c))
        got = block_combine.combine3(x, y, z, op="add", interpret=interpret)
        want = ref.combine3_ref(x, y, z, op="add")
        # one rounding of the dtype, relative to the largest result
        tol = float(jnp.finfo(dt).eps)
        err = _max_rel(got, want)
        print(f"[kernels] combine3 {jnp.dtype(dt).name} x{block}: "
              f"max rel err {err:.3e} (limit {tol:.3e})")
        _check(got.dtype == want.dtype and err <= tol,
               f"combine3 {jnp.dtype(dt).name} off by {err:.3e}")
    wire = quantize.compress_bf16(a, interpret=interpret)
    back = quantize.decompress_bf16(wire, interpret=interpret)
    _check(wire.dtype == jnp.bfloat16 and np.array_equal(
        np.asarray(wire, np.float32),
        np.asarray(ref.compress_bf16_ref(a), np.float32)),
        "compress_bf16 differs from the cast")
    _check(back.dtype == jnp.float32 and np.array_equal(
        np.asarray(back), np.asarray(ref.decompress_bf16_ref(wire))),
        "decompress_bf16 differs from the cast")
    print(f"[kernels] compress_bf16/decompress_bf16 x{block}: equal to the "
          "casts")


# ------------------------------------------------------------ four chips

def collective_cases() -> tuple:
    """(label, CollectiveConfig, bound) for every schedule method, each
    named explicitly: a method that fails must fail here, not hand over to
    psum. ``bound`` is the largest elementwise relative error against psum
    for positive payloads."""
    from repro.core.collectives import CollectiveConfig
    p = 4
    exact = (p - 1) * 2.0 ** -23    # two summation orders of p f32 terms
    # the bf16 wire's documented bound (docs/algorithms.md) for g groups
    bf16 = (2 + 1) * 2.0 ** -8      # g = 2: ceil(log2 g) = 1
    return (
        ("dptree", CollectiveConfig(method="dptree"), exact),
        ("sptree", CollectiveConfig(method="sptree"), exact),
        ("redbcast", CollectiveConfig(method="redbcast"), exact),
        ("ring", CollectiveConfig(method="ring"), exact),
        ("hier(2,2)", CollectiveConfig(method="hier", levels=(2, 2)), exact),
        # levels (2, 2) leave one group on four chips, so the slow-stage
        # tree and its bf16 wire only run with two groups of two
        ("hier(2)", CollectiveConfig(method="hier", levels=(2,)), exact),
        ("hier(2)+bf16", CollectiveConfig(method="hier", levels=(2,),
                                          compress_inter_group=True), bf16),
    )


def check_collectives(devices, payloads=PAYLOADS) -> None:
    """Every method against psum on a ``len(devices)``-device mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.collectives import CollectiveConfig, all_reduce
    from repro.launch.mesh import make_mesh
    p = len(devices)
    mesh = make_mesh((p,), ("data",), devices=devices)
    rows = NamedSharding(mesh, P("data"))

    def reducer(cfg):
        return jax.jit(jax.shard_map(
            lambda x: all_reduce(x[0], "data", p, cfg)[None], mesh=mesh,
            in_specs=P("data"), out_specs=P("data")))

    for name, m in payloads:
        # positive rows, drawn on the devices (the bf16 bound is for
        # positive sums)
        x = jax.jit(lambda k: jax.random.uniform(k, (p, m), jnp.float32,
                                                 0.1, 1.1),
                    out_shardings=rows)(jax.random.PRNGKey(m))
        want = np.asarray(reducer(CollectiveConfig(method="psum"))(x))
        for label, cfg, bound in collective_cases():
            t0 = time.perf_counter()
            fn = reducer(cfg)
            out = jax.block_until_ready(fn(x))       # compile + first run
            t1 = time.perf_counter()
            out = jax.block_until_ready(fn(x))
            t2 = time.perf_counter()
            got = np.asarray(out)
            rel = float(np.max(np.abs(got - want) / want))
            print(f"[collective] {name} ({m * 4} B/device) {label}: max rel "
                  f"err vs psum {rel:.3e} (limit {bound:.3e}); first call "
                  f"{t1 - t0:.3f} s, second {t2 - t1:.6f} s")
            _check(np.isfinite(got).all() and rel <= bound,
                   f"{label} at {name}: rel err {rel:.3e} > {bound:.3e}")


def _decode_logits(cfg, pcfg, mesh, prompt, check_spread=None):
    """Feed ``prompt`` one token at a time through the fixed-batch decode
    step; returns the (T, V) logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import ShapeSuite
    from repro.launch import step_fns
    from repro.models import transformer as tf
    suite = ShapeSuite("smoke", CACHE_LEN, 1, "decode")
    step, sh = step_fns.make_serve_step(cfg, pcfg, mesh, suite)
    # drawn straight into their shardings: same values on any mesh
    params = jax.jit(lambda k: tf.init_params(k, cfg),
                     out_shardings=step_fns._named(mesh, sh["params"]))(
                         jax.random.PRNGKey(SEED))
    caches = jax.device_put(tf.init_cache(cfg, 1, CACHE_LEN),
                            step_fns._named(mesh, sh["cache"]))
    if check_spread is not None:
        check_spread(params, caches)
    out = []
    for t in prompt:
        logits, caches = step(params, {"tokens": jnp.full((1, 1), t,
                                                          jnp.int32)},
                              caches)
        out.append(np.asarray(logits[0]))
    return np.stack(out)


def _check_spread(n: int):
    """A check that the tensor-parallel params and caches really sit on
    ``n`` devices, none holding more than its share plus what stays
    replicated."""
    import jax

    def check(params, caches):
        for what, tree in (("params", params), ("caches", caches)):
            per_dev, total, sharded = {}, 0, 0
            for leaf in jax.tree.leaves(tree):
                total += leaf.nbytes
                shards = leaf.addressable_shards
                if shards[0].data.shape != leaf.shape:
                    sharded += leaf.nbytes
                for s in shards:
                    per_dev[s.device] = per_dev.get(s.device, 0) \
                        + s.data.nbytes
            _check(len(per_dev) == n and sharded > 0,
                   f"{what} sit on {len(per_dev)} devices, want {n}")
            worst = max(per_dev.values())
            print(f"[tp] {what}: {total / 1e9:.3f} GB, "
                  f"{sharded / total:.1%} of it sharded; largest device "
                  f"share {worst / 1e9:.3f} GB")
            _check(worst <= (total - sharded) + sharded / n * 1.001,
                   f"{what}: one device holds {worst} of {total} bytes")
    return check


def check_tp_decode(devices, reduced: bool = False) -> float:
    """Decode at ``tp_shards=len(devices)`` against tp=1 for one prompt."""
    import numpy as np

    from repro.configs.base import get_parallel
    from repro.core.collectives import CollectiveConfig
    from repro.launch.mesh import make_mesh, make_tp_mesh
    from repro.launch.serve import serving_config
    tp = len(devices)
    cfg = serving_config(ARCH, reduced)
    prompt = np.random.default_rng(SEED).integers(1, cfg.vocab_size,
                                                  TP_PROMPT)
    pcfg = get_parallel(ARCH)
    ref = _decode_logits(cfg, pcfg, make_mesh((1, 1), ("data", "model"),
                                              devices=devices[:1]), prompt)
    gc.collect()        # the tp=1 weights leave device 0 first
    pcfg_tp = dataclasses.replace(
        pcfg, tp_shards=tp, tp_collective=CollectiveConfig(method="dptree"))
    got = _decode_logits(cfg, pcfg_tp, make_tp_mesh(tp), prompt,
                         check_spread=_check_spread(tp))
    err = _max_rel(got, ref)
    print(f"[tp] {TP_PROMPT} decode steps at tp={tp} (dptree partial sums) "
          f"vs tp=1: max |diff| = {err:.3e} of max |logit| "
          f"{float(np.abs(ref).max()):.3f} (limit {LOGIT_TOL})")
    _check(np.isfinite(got).all() and err <= LOGIT_TOL,
           f"tp={tp} logits differ from tp=1 by {err:.3e}")
    return err


# ------------------------------------------------------------------ main

class _CompileClock:
    """Seconds XLA spent compiling, from JAX's own monitoring events.
    Tracing is left out: its events nest (a jit traced inside another
    reports its own span), so their sum would count time twice."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration

    def phase(self, name, fn, *args, **kwargs):
        c0, t0 = self.seconds, time.perf_counter()
        out = fn(*args, **kwargs)
        wall = time.perf_counter() - t0
        compile_s = self.seconds - c0
        print(f"[{name}] passed: {wall:.1f} s wall, {compile_s:.1f} s "
              f"compiling in XLA, {wall - compile_s:.1f} s tracing and "
              f"running", flush=True)
        return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase: every allreduce "
                         "method against psum, then tp=4 decode against "
                         "tp=1")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found "
              f"{devices[0].platform} ({len(devices)} devices)",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}, jaxlib "
          f"{importlib.metadata.version('jaxlib')}, libtpu "
          f"{importlib.metadata.version('libtpu')}; compile cache "
          f"{enable_compile_cache()}", flush=True)
    clock = _CompileClock()
    if args.four_chips:
        clock.phase("collectives", check_collectives, devices[:4])
        clock.phase("tp decode", check_tp_decode, devices[:4])
    else:
        report, reqs = clock.phase("serve", serve_requests)
        clock.phase("stream", check_served_stream, report, reqs)
        clock.phase("kernels", check_kernels)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
