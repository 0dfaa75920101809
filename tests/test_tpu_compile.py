"""Ahead-of-time compiles for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts (tile alignment,
on-chip memory, a kernel output without the ``vma`` a ``check_vma``
shard_map requires), so the allreduce's kernels are compiled here at real
sizes for the chip. Nothing runs: these tests say nothing about results or
times. The topology is described inside a fixture, never at import.
"""

import os
import re

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
    """The fused engine on the chip's backend compiles inside a 4-chip
    shard_map that checks varying manual axes, with its blocks block-major
    and its three-operand combine fused by XLA into the in-place write: no
    Pallas call (a ``combine3`` over the block was the slower of the two in
    a gradient sync on a v5e 2x2)."""
    p = 4
    mesh = Mesh(np.array(topo.devices[:p]), ("data",),
                axis_types=(jax.sharding.AxisType.Auto,))
    # the engine would see the chip's backend there; steer it to it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn = jax.shard_map(
        lambda x: dptree_allreduce(x[0], "data", p, num_blocks=16)[None],
        mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=True)
    x = jax.ShapeDtypeStruct((p, 16 * BLOCK), jnp.float32,
                             sharding=NamedSharding(mesh, P("data")))
    text = _compiled_text(fn, x)
    assert "tpu_custom_call" not in text
    assert "collective-permute" in text
    assert f"f32[17,{BLOCK // 128},128]" in text
    assert "dynamic-update-slice_fusion" in text


_HEADER = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_CALLS = re.compile(r"(?:calls|body|condition|to_apply)=%([\w.\-]+)"
                    r"|branch_computations=\{([^}]*)\}")
_BLOCK_OP = re.compile(r"^(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* "
                       r"(pad|reduce|concatenate)\(")


def _computations(text: str) -> dict:
    """``{name: [instruction lines]}`` of a compiled HLO module's text."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = _HEADER.match(line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None and line.strip():
            cur.append(line.strip())
    return comps


def _reachable(comps: dict, root: str) -> set:
    """The computations ``root`` runs, itself and all it calls."""
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            for one, many in _CALLS.findall(line):
                todo += [one] if one else re.findall(r"%([\w.\-]+)", many)
    return seen


@pytest.mark.parametrize("branch", ["default", "kernel"])
def test_dptree_blocks_are_block_major_whole_tiles(topo, monkeypatch,
                                                   branch):
    """A 1-D f32 payload whose block length is no multiple of a tile is laid
    out block-major in whole (8, 128) tiles: one loop, whose carry is
    ``(b + 1, rows, 128)``; no pad, concatenate or reduce of a block inside
    it (a take or a put of one block touches that block alone, and the
    combine copies nothing); and temporaries under 1.2x the payload (the
    vector enters and leaves by bitcast, not through relayout loops). On the
    kernel branch too, with the engine steered to the chip's backend."""
    p, b, blk = 4, 16, 1224342       # blocks of the gradient's first bucket
    m = b * blk
    if branch == "kernel":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices[:p]), ("data",),
                axis_types=(jax.sharding.AxisType.Auto,))
    fn = jax.shard_map(
        lambda x: dptree_allreduce(x, "data", p, num_blocks=b),
        mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=True)
    x = jax.ShapeDtypeStruct((p * m,), jnp.float32,
                             sharding=NamedSharding(mesh, P("data")))
    compiled = jax.jit(fn).lower(x).compile()
    text = compiled.as_text()
    loops = re.findall(r" while\(.*?body=%([\w.\-]+)", text)
    assert len(loops) == 1, loops
    rows = -(-blk // 1024) * 1024 // 128
    carry = next(l for l in text.splitlines() if " while(" in l)
    assert f"f32[{b + 1},{rows},128]" in carry, carry[:300]
    comps = _computations(text)
    for name in _reachable(comps, loops[0]):
        for line in comps[name]:
            op = _BLOCK_OP.match(line)
            if op and op.group(1):
                n = int(np.prod([int(d) for d in op.group(1).split(",")]))
                assert n < blk, (name, line[:200])
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1.2 * m * 4, (temp, m * 4)


def test_grad_sync_rounds_issue_every_class_at_once(topo):
    """The gradient reduction of the grad_sync cell, compiled as
    ``chipbench/aot.py`` builds it: in each bucket's loop, the schedule
    issues all three permutations of a round (leaf-root up and down, root
    to root) before it waits on any, and the round's in-place writes of
    what arrived in the round before sit between the starts and the
    waits, under the wire. Temporaries stay those of the one-ported
    engine, 2.269 GB (the carried blocks are a few 5 MB blocks)."""
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
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
    compiled = reducer(mesh, p, get_parallel(config["repro_arch"]).collective
                       ).lower(x).compile()
    text = compiled.as_text()
    assert "is_scheduled=true" in text.split("\n", 1)[0]
    comps = _computations(text)
    loops = re.findall(r" while\(.*?body=%([\w.\-]+)", text)
    assert len(loops) == 3, loops              # one a bucket
    for body in loops:
        lines = comps[body]
        at = lambda key: [n for n, line in enumerate(lines) if key in line]
        starts = at("collective-permute-start(")
        waits = at("collective-permute-done(")
        writes = [n for n, line in enumerate(lines)
                  if re.match(r"%\S*dynamic-update-slice\S* = .* fusion\(",
                              line)]
        assert len(starts) == len(waits) == 3, (body, starts, waits)
        assert max(starts) < min(waits), (body, starts, waits)
        assert len(writes) == 2 and all(
            max(starts) < n < min(waits) for n in writes), (body, writes)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert abs(temp - 2.269e9) < 0.1e9, temp


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
