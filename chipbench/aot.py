"""Compile each cell's programs at their real size for a described TPU v5e,
with no chip attached, and print what the compiler says they need.

    JAX_PLATFORMS=cpu python3 chipbench/aot.py [chat] [grad_sync]

* chat: the serving decode step and the largest prefill bucket at the
  cell's slots and positions on one chip, with the caches donated as the
  engine donates them, and one layer of the float32 reference over a
  batch of rows;
* grad_sync: the gradient reduction of one pipeline stage on ``v5e:2x2``
  (on the CPU host JAX's default backend is the CPU, so the tree's combines
  compile as plain XLA here, not as the Pallas kernel the chip runs).

Nothing runs: the numbers are the compiler's ``memory_analysis()`` of one
program at a time (arguments, outputs, temporaries), not what a process
holds at its peak. Run by hand; one 40-layer step takes up to a minute
and a half to compile.
"""

from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

GB = 1e9


def _report(name: str, compiled, t0: float) -> None:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    print(f"[aot] {name}: arguments {m.argument_size_in_bytes / GB:.3f} GB, "
          f"outputs {m.output_size_in_bytes / GB:.3f} GB, aliased "
          f"{m.alias_size_in_bytes / GB:.3f} GB, temporaries "
          f"{m.temp_size_in_bytes / GB:.3f} GB: {total / GB:.3f} GB in all; "
          f"compiled in {time.perf_counter() - t0:.1f} s", flush=True)


def _shaped(tree, shardings):
    import jax
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, shardings)


def chat(topo) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from chipbench import harness
    from chipbench.drivers.serving import program_config
    from chipbench.reference import dense_decoder as ref
    from repro.configs.base import ShapeSuite, get_parallel
    from repro.launch import step_fns
    from repro.launch.mesh import make_mesh
    from repro.models import transformer as tf
    bench = harness.Benchmark()
    cell = bench.cell("minicpm_2b.chat")
    config, wl = bench.config(cell), bench.workload(cell)
    cfg = program_config(config)
    slots, max_len = wl["engine"]["n_slots"], wl["engine"]["max_len"]
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    pcfg = get_parallel(config["repro_arch"])
    suite = ShapeSuite("serve", max_len, slots, "decode")
    decode, sh = step_fns.make_serve_step(cfg, pcfg, mesh, suite,
                                          slots=True)
    prefill, _ = step_fns.make_prefill_step(cfg, pcfg, mesh, suite,
                                            into_slots=True)
    params = _shaped(
        jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0), cfg)),
        step_fns._named(mesh, sh["params"]))
    caches = _shaped(tf.init_cache(cfg, slots, max_len, abstract=True,
                                   per_slot=True),
                     step_fns._named(mesh, sh["cache"]))
    one = NamedSharding(mesh, P())
    arg = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    t0 = time.perf_counter()
    # the caches are donated, as the engine's steps donate them
    c = jax.jit(lambda p, t, c, a: decode(p, {"tokens": t}, c, a),
                donate_argnums=(2,)).lower(
        params, arg((slots, 1), jnp.int32), caches,
        arg((slots,), jnp.bool_)).compile()
    _report(f"decode step, {slots} slots x {max_len}", c, t0)
    t0 = time.perf_counter()
    c = jax.jit(lambda p, t, c, s, n: prefill(p, t, c, s, n),
                donate_argnums=(2,)).lower(
        params, arg((1, max_len), jnp.int32), caches, arg((), jnp.int32),
        arg((), jnp.int32)).compile()
    _report(f"prefill, bucket {max_len}", c, t0)
    s = ref.sizes(config)
    stacked = {k: jax.ShapeDtypeStruct(v[0], jnp.dtype(v[1]), sharding=one)
               for k, v in ref.spec(config).items()
               if k.startswith("layers/")}
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        c = ref._layer.lower(arg((4, max_len, s["D"]), jnp.float32),
                             stacked, arg((), jnp.int32),
                             s=ref._frozen(s), lowp=False).compile()
    _report(f"reference layer, 4 rows x {max_len}", c, t0)


def grad_sync(topo) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from chipbench import harness
    from chipbench.drivers.allreduce import grad_spec, reducer
    from repro.configs.base import get_parallel
    from repro.launch.mesh import make_mesh
    bench = harness.Benchmark()
    cell = bench.cell("minicpm_2b_dp4.grad_sync")
    config, wl = bench.config(cell), bench.workload(cell)
    p = wl["devices"]
    mesh = make_mesh((p,), ("data",), devices=topo.devices[:p])
    rows = NamedSharding(mesh, P("data"))
    spec, _ = grad_spec(config)
    x = {k: jax.ShapeDtypeStruct((p,) + s, np.dtype(d), sharding=rows)
         for k, (s, d) in spec.items()}
    t0 = time.perf_counter()
    c = reducer(mesh, p, get_parallel(config["repro_arch"]).collective
                ).lower(x).compile()
    _report(f"gradient reduction over {p} chips (per chip)", c, t0)
    text = c.as_text()
    print(f"[aot] grad_sync program: {text.count('collective-permute')} "
          f"collective-permute mentions, {text.count('tpu_custom_call')} "
          "custom calls", flush=True)


def main(argv) -> int:
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    which = argv or ["chat", "grad_sync"]
    for name in which:
        {"chat": chat, "grad_sync": grad_sync}[name](topo)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
