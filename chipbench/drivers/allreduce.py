"""Gradient sync: back-to-back data-parallel reductions of one pipeline
stage's gradient pytree through ``collectives.bucketed_all_reduce``.

Set-up draws each device's gradients from the seed (on that device), and
builds and warms the one reduction program: a ``shard_map`` over the
devices' ``data`` axis around ``bucketed_all_reduce`` under the training
default collective of the configuration's model
(``get_parallel(arch).collective``), resolved at run time. The window runs
one reduction after another, each blocked on before the next, and counts
those finished.

Correctness: the last reduction's output, every leaf on every device,
against a plain sum over the devices' inputs (XLA's own reduction, none of
``core/``): the largest ``|got - sum| / sum(|x_i|)`` over all elements.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from chipbench import weights
from chipbench.harness import log


def grad_spec(config: dict) -> dict:
    """``{path: (shape, dtype)}`` of the stage's gradients: the shapes of
    the program's parameters for these sizes, at the training dtype."""
    import jax

    from chipbench.drivers.serving import program_config
    from repro.models import transformer as tf
    import jax.numpy as jnp
    cfg = dataclasses.replace(program_config(config),
                              param_dtype=jnp.dtype(config["gradient_dtype"]))
    like = jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0), cfg))
    return weights.spec_of(like), like


def reducer(mesh, p: int, collective):
    """The program's reduction of a ``{path: (p, ...)}`` tree, one row per
    device, to every device's row."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.collectives import bucketed_all_reduce

    def body(tree):
        local = jax.tree.map(lambda x: x[0], tree)
        out = bucketed_all_reduce(local, "data", p, collective)
        return jax.tree.map(lambda x: x[None], out)

    rows = NamedSharding(mesh, P("data"))
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                                 out_specs=P("data")),
                   out_shardings=rows)


def plain_sum_error(x: dict, out: dict, precision=None) -> float:
    """Largest ``|out - sum_i x_i| / sum_i |x_i|`` over every element of
    every leaf on every device row of ``out``. ``precision="bfloat16"``
    is the control: the sum taken over bfloat16 inputs, in bfloat16, put
    in the program's place."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def err(xl, ol):
        want = xl.sum(0)
        den = jnp.abs(xl).sum(0) + jnp.finfo(jnp.float32).tiny
        return (jnp.abs(ol - want[None]) / den[None]).max()

    @jax.jit
    def low_sum(xl):
        s = xl.astype(jnp.bfloat16).sum(0, dtype=jnp.bfloat16)
        return jnp.broadcast_to(s.astype(xl.dtype)[None], xl.shape)

    worst = 0.0
    for k in sorted(x):
        got = out[k] if precision is None else low_sum(x[k])
        worst = max(worst, float(err(x[k], got)))
    return worst


def run(run, devices, tracer=None, control: bool = False) -> None:
    import jax

    from chipbench.drivers.serving import peak_bytes
    from repro.configs.base import get_parallel
    from repro.launch.mesh import make_mesh

    config, wl = run.config, run.workload
    p = wl["devices"]
    devs = devices[:p]
    mesh = make_mesh((p,), ("data",), devices=devs)
    collective = get_parallel(config["repro_arch"]).collective
    spec, _ = grad_spec(config)
    nbytes = sum(int(np.prod(s)) * np.dtype(d).itemsize
                 for s, d in spec.values())
    log(f"[allreduce] {len(spec)} leaves, {nbytes / 1e9:.3f} GB a device, "
        f"over {p} devices; collective {collective}")
    x = weights.device_rows(spec, run.seed, mesh, "data")
    fn = reducer(mesh, p, collective)
    out = jax.block_until_ready(fn(x))          # compile (or cache) + warm
    del out
    gc.collect()
    run.setup_s = time.perf_counter() - run.t_start
    n, t0 = 0, time.perf_counter()
    end = t0 + run.seconds
    stamps = []
    out = None
    while True:
        now = time.perf_counter()
        if tracer is not None:
            tracer.poll(now - t0)
        if now >= end:
            break
        out = None                              # free before the next
        out = jax.block_until_ready(fn(x))
        n += 1
        stamps.append(time.perf_counter())
    if tracer is not None:
        tracer.close()
    run.attempted = n
    run.memory_peak_bytes = peak_bytes(devs)
    run.data.update(reductions=n, window=(t0, stamps[-1]), stamps=stamps,
                    payload_bytes=nbytes, devices=p)
    log(f"[allreduce] {n} reductions in {stamps[-1] - t0:.3f} s")
    check = wl["check"]
    e = plain_sum_error(x, out)
    run.compared.append(("sum_error", e, check["limit"]))
    if control:
        run.data["control_error"] = plain_sum_error(x, out,
                                                    precision="bfloat16")
    log(f"[allreduce] largest |got - sum| / sum|x| over every element on "
        f"{p} devices: {e:.3e}")
