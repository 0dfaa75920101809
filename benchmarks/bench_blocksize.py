"""Block-size sweep — the paper's explicit open question ("determination of
the best pipeline block size").

Measured: time the dptree allreduce on 8 virtual devices across block counts
for a fixed message; report the empirical argmin next to the Pipelining-Lemma
analytic optimum for the same alpha-beta fit.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from repro.core import cost_model as cm

M_ELEMS = 1_000_000
BLOCKS = [1, 2, 4, 8, 16, 32, 64, 128, 256]


def measured(devices: int = 8, reps: int = 5):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import sys, time, json
        sys.path.insert(0, {root + '/src'!r})
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.dptree import dptree_allreduce
        from repro.launch.mesh import make_mesh
        shard_map = jax.shard_map
        mesh = make_mesh(({devices},), ("data",))
        p = {devices}
        X = jnp.asarray(np.random.default_rng(0).standard_normal((p, {M_ELEMS})),
                        jnp.float32)
        out = []
        for b in {BLOCKS}:
            body = lambda x: dptree_allreduce(x[0], "data", p, num_blocks=b)[None]
            f = jax.jit(shard_map(body, mesh=mesh, in_specs=P("data", None),
                                      out_specs=P("data", None)))
            f(X)[0].block_until_ready()
            ts = []
            for _ in range({reps}):
                t0 = time.perf_counter()
                f(X)[0].block_until_ready()
                ts.append(time.perf_counter() - t0)
            out.append((b, min(ts) * 1e6))
        print("RESULT " + json.dumps(out))
    """)
    # the child's virtual devices are CPU ones: it must never contend for an
    # accelerator that this (or another) process holds
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=560,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-2000:])
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def run(csv_out):
    rows = measured()
    best_b, best_t = min(rows, key=lambda r: r[1])
    for b, us in rows:
        csv_out(f"blocksize_sweep_cpu8/b={b}", us, "dptree, m=1M f32")
    csv_out("blocksize_empirical_argmin", best_b, f"{best_t:.0f}us")
    for p in (8, 64, 256):
        b_star = cm.optimal_blocks(p, M_ELEMS * 4, cm.TPU_V5E, "dptree")
        csv_out(f"blocksize_analytic_optimum/p={p}", b_star,
                "Pipelining Lemma, v5e constants, m=1M f32")
