"""Empirical autotune pass on the cpu8 virtual mesh.

Times every ``(algorithm, num_blocks)`` candidate the tuner proposes around
the analytic optimum, records the winner per message size in the on-disk
autotune cache (topology tag ``cpu8``), and emits the measured rows. After
this runs, ``CollectiveConfig(method="auto")`` on an 8-way mesh whose
``comm_model.name`` is ``cpu8`` resolves from measurements instead of the
model — the paper's "never let the library guess" lesson as a closed loop.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from repro.core import autotune as at
from repro.core import cost_model as cm

SIZES = [10_000, 1_000_000]  # f32 elements
DEVICES = 8
GROUP_SIZE = (2, 2)   # 3-level spec: 2-chip ring, 2-node ring, tree over 2
COMPRESS = True       # also time the bf16 slow-stage wire candidates
ALGORITHMS = ("dptree", "sptree", "redbcast", "ring", "hier")


def _measure_candidates(m_elems: int, cands, devices=DEVICES, reps=3):
    """One subprocess times every candidate for one size; returns dict."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import sys, time, json
        sys.path.insert(0, {root + '/src'!r})
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        shard_map = jax.shard_map
        from repro.core.collectives import CollectiveConfig, all_reduce
        p = {devices}
        mesh = make_mesh((p,), ("data",))
        X = jnp.asarray(np.random.default_rng(0).standard_normal((p, {m_elems})),
                        jnp.float32)
        out = {{}}
        for algo, b in {list(cands)}:
            base = algo.removesuffix("+bf16")
            cfg = CollectiveConfig(method=base, num_blocks=b,
                                   group_size={GROUP_SIZE!r} if base == "hier"
                                   else None,
                                   compress_inter_group=algo != base)
            body = lambda x: all_reduce(x[0], "data", p, cfg)[None]
            f = jax.jit(shard_map(body, mesh=mesh, in_specs=P("data", None),
                                  out_specs=P("data", None)))
            f(X)[0].block_until_ready()
            ts = []
            for _ in range({reps}):
                t0 = time.perf_counter()
                f(X)[0].block_until_ready()
                ts.append(time.perf_counter() - t0)
            out[f"{{algo}}/{{b}}"] = min(ts)
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
    raw = json.loads(line[len("RESULT "):])
    return {tuple(k.split("/", 1)): v for k, v in raw.items()}


def run(csv_out):
    model = cm.TPU_V5E  # analytic seed for the candidate sweep
    for m in SIZES:
        nbytes = m * 4
        cands = at.candidate_settings(DEVICES, nbytes, model,
                                      algorithms=ALGORITHMS,
                                      group_size=GROUP_SIZE,
                                      compress_inter_group=COMPRESS)
        measured = _measure_candidates(m, cands)
        for (algo, b), secs in sorted(measured.items(),
                                      key=lambda kv: kv[1]):
            csv_out(f"autotune_cpu8/candidate/{algo}/b={b}/m={m}",
                    secs * 1e6, "min-of-3 us")

        def runner(algo, b):
            return measured[(algo, str(b))]

        best = at.tune(runner, DEVICES, nbytes, "float32", "cpu8", model,
                       algorithms=ALGORITHMS, group_size=GROUP_SIZE,
                       compress_inter_group=COMPRESS)
        tag = "+bf16" if best.compressed else ""
        csv_out(f"autotune_cpu8/winner/m={m}",
                f"{best.algorithm}{tag}/b={best.num_blocks}",
                f"{best.time_s * 1e6:.1f} us -> cached for method='auto'")
    # round-trip proof: the cache hit is what auto would now use
    for m in SIZES:
        hit = at.lookup(DEVICES, m * 4, "float32", "cpu8")
        csv_out(f"autotune_cpu8/cache_hit/m={m}",
                "miss" if hit is None else f"{hit.algorithm}/b={hit.num_blocks}",
                at.get_cache().path)
