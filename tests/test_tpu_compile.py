"""Ahead-of-time compiles for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts (tile alignment,
on-chip memory, a kernel output without the ``vma`` a ``check_vma``
shard_map requires), so the allreduce's kernels are compiled here at real
sizes for the chip. Nothing runs: these tests say nothing about results or
times. The topology is described inside a fixture, never at import.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.dptree import dptree_allreduce
from repro.kernels import block_combine, quantize

# one pipeline block of a 64 MiB f32 gradient bucket cut into 16 blocks
BLOCK = 1 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_combine3_compiles_for_v5e(one_chip, dtype):
    x = jax.ShapeDtypeStruct((BLOCK,), dtype, sharding=one_chip)
    text = _compiled_text(
        lambda a, b, c: block_combine.combine3(a, b, c, op="add"), x, x, x)
    assert "tpu_custom_call" in text


def test_bf16_wire_kernels_compile_for_v5e(one_chip):
    x = jax.ShapeDtypeStruct((BLOCK,), jnp.float32, sharding=one_chip)
    wire = jax.ShapeDtypeStruct((BLOCK,), jnp.bfloat16, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(quantize.compress_bf16, x)
    assert "tpu_custom_call" in _compiled_text(quantize.decompress_bf16, wire)


def test_dptree_kernel_branch_compiles_in_checked_shard_map(topo,
                                                           monkeypatch):
    """The fused engine's TPU branch (Pallas combine3 inside the scan)
    compiles inside a 4-chip shard_map that checks varying manual axes."""
    p = 4
    mesh = Mesh(np.array(topo.devices[:p]), ("data",),
                axis_types=(jax.sharding.AxisType.Auto,))
    # the engine picks its kernel from the backend; steer it to the chip's
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn = jax.shard_map(
        lambda x: dptree_allreduce(x[0], "data", p, num_blocks=16)[None],
        mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=True)
    x = jax.ShapeDtypeStruct((p, 16 * BLOCK), jnp.float32,
                             sharding=NamedSharding(mesh, P("data")))
    text = _compiled_text(fn, x)
    assert "tpu_custom_call" in text
    assert "collective-permute" in text


def test_slot_decode_step_updates_the_cache_in_place(topo):
    """The serving decode step, its caches donated, writes each row's new
    token into the stacked K/V ring in place: its temporaries hold no
    second cache (a whole-cache select or a restacked scan output would)."""
    from repro.configs.base import ParallelConfig, ShapeSuite
    from repro.launch import step_fns
    from repro.launch.mesh import make_mesh
    from repro.models import transformer as tf
    # deep enough that the compiler keeps the layer loop a loop
    cfg = tf.ModelConfig(name="inplace-aot", n_layers=40, d_model=256,
                         n_heads=4, n_kv_heads=4, d_ff=512, vocab_size=512,
                         compute_dtype=jnp.bfloat16,
                         param_dtype=jnp.bfloat16, remat=False)
    slots, max_len = 8, 512
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    step, sh = step_fns.make_serve_step(
        cfg, ParallelConfig(), mesh, ShapeSuite("serve", max_len, slots,
                                                "decode"), slots=True)

    def shaped(tree, specs):
        return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, s)), tree, specs)

    params = shaped(jax.eval_shape(
        lambda: tf.init_params(jax.random.PRNGKey(0), cfg)), sh["params"])
    caches = shaped(tf.init_cache(cfg, slots, max_len, abstract=True,
                                  per_slot=True), sh["cache"])
    one = NamedSharding(mesh, P())
    compiled = jax.jit(
        lambda p, t, c, a: step(p, {"tokens": t}, c, a),
        donate_argnums=(2,)).lower(
        params, jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one),
        caches, jax.ShapeDtypeStruct((slots,), jnp.bool_,
                                     sharding=one)).compile()
    cache_bytes = sum(int(np.prod(c.shape)) * c.dtype.itemsize
                      for c in jax.tree.leaves(caches))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes / 8, (
        mem.temp_size_in_bytes, cache_bytes)


# the usable memory of one v5e as the benchmark counts it
V5E_BYTES = 15.75e9


def _cell_programs(topo, cell):
    """The decode, largest-bucket prefill and slot-reset programs of a
    serving cell, compiled at the cell's sizes; and its model config."""
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from chipbench import harness
    from chipbench.drivers import serving, serving_hybrid
    from repro.configs.base import ShapeSuite, get_parallel
    from repro.launch import step_fns
    from repro.launch.mesh import make_mesh
    from repro.models import transformer as tf
    from repro.serving.engine import make_reset
    bench = harness.Benchmark()
    c = bench.cell(cell)
    config, wl = bench.config(c), bench.workload(c)
    driver = serving_hybrid if wl["driver"] == "serving_hybrid" else serving
    cfg = driver.program_config(config)
    slots, max_len = wl["engine"]["n_slots"], wl["engine"]["max_len"]
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    pcfg = get_parallel(config["repro_arch"])
    suite = ShapeSuite("serve", max_len, slots, "decode")
    decode, sh = step_fns.make_serve_step(cfg, pcfg, mesh, suite, slots=True)
    prefill, _ = step_fns.make_prefill_step(cfg, pcfg, mesh, suite,
                                            into_slots=True)

    def shaped(tree, specs):
        return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, s)), tree, specs)

    params = shaped(jax.eval_shape(
        lambda: tf.init_params(jax.random.PRNGKey(0), cfg)), sh["params"])
    # the engine's caches: its default draft headroom widens no full ring
    caches = shaped(tf.init_cache(cfg, slots, max_len, abstract=True,
                                  per_slot=True), sh["cache"])
    one = NamedSharding(mesh, P())
    arg = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    out = {
        "decode": jax.jit(lambda p, t, c, a: decode(p, {"tokens": t}, c, a),
                          donate_argnums=(2,)).lower(
            params, arg((slots, 1), jnp.int32), caches,
            arg((slots,), jnp.bool_)).compile(),
        "prefill": jax.jit(prefill, donate_argnums=(2,)).lower(
            params, arg((1, max_len), jnp.int32), caches, arg((), jnp.int32),
            arg((), jnp.int32)).compile(),
        "reset": make_reset(step_fns._named(mesh, sh["cache"])).lower(
            caches, arg((slots,), jnp.bool_)).compile()}
    return out, cfg


@pytest.mark.parametrize("cell", ["minicpm_2b.docs", "jamba2_mini_ep2.chat"])
def test_serving_cells_fit_one_v5e(topo, cell):
    """Every program of the docs and hybrid chat cells fits one v5e with
    the weights and the caches it holds; a slot reset writes its donated
    caches in place. The hybrid decode program runs its experts through
    XLA's grouped-matmul kernels (the ops the benchmark's expert readers
    match by name) and copies no layer's stacked expert weights."""
    import re
    progs, cfg = _cell_programs(topo, cell)
    for name, c in progs.items():
        m = c.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 - m.alias_size_in_bytes + m.temp_size_in_bytes)
        assert total < V5E_BYTES, (cell, name, total)
    reset = progs["reset"].memory_analysis()
    # all but the few bytes of the position counters
    assert reset.alias_size_in_bytes > 0.99 * reset.output_size_in_bytes
    assert reset.temp_size_in_bytes < reset.output_size_in_bytes / 100
    if cfg.moe is None:
        return
    text = progs["decode"].as_text()
    assert re.search(r"%ragged-dot-\S* = .*custom-call", text)
    first, held = cfg.moe.held
    stacks = [f"[{held},{cfg.d_model},{cfg.d_ff}]",
              f"[{held},{cfg.d_ff},{cfg.d_model}]"]
    for line in text.splitlines():
        head = line.split(" = ", 1)
        if len(head) < 2 or not any(s in head[1].split(" ")[0]
                                    for s in stacks):
            continue
        op = head[1].split("{", 1)[1].split(" ", 1)[1].split("(")[0] \
            if "{" in head[1].split(" ")[0] else ""
        assert op in ("parameter", "bitcast"), line[:200]
