"""Paper Table 2 / Figure 1 reproduction: the four reduction-to-all
implementations measured across message sizes.

Two views:
  (a) MEASURED on virtual CPU devices (subprocess with 8 hosts) — validates
      the qualitative shape: pipelined dual-root beats reduce+bcast for large
      messages, native psum wins tiny messages. Absolute numbers are CPU
      emulation, not ICI.
  (b) PREDICTED from the alpha-beta model for the paper's 36x8-rank cluster
      (PAPER_HYDRA constants) and for a 256-chip v5e pod — the paper's
      Table 2 analogue at our target scale.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import numpy as np

from repro.core import cost_model as cm
from repro.core import collectives
from repro.core.topology import resolve_levels

SIZES = [1_000, 10_000, 100_000, 1_000_000, 4_000_000]  # f32 elements
METHODS = [m for m in collectives.METHODS if m != "auto"]
# label -> CollectiveConfig kwargs; the hierarchical variants measured next
# to the flat methods: two-level (4-chip groups), three-level (2-chip ring
# inside a 2-node ring inside the pod tree), and the bf16 slow-stage wire.
CASES = ([(m, {"method": m, "group_size": 4 if m == "hier" else None})
          for m in METHODS]
         + [("hier3", {"method": "hier", "group_size": (2, 2)}),
            ("hier3_bf16", {"method": "hier", "group_size": (2, 2),
                            "compress_inter_group": True})])


def measured_rows(devices: int = 8, reps: int = 5):
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
        mesh = make_mesh(({devices},), ("data",))
        p = {devices}
        out = []
        for m in {SIZES}:
            X = jnp.asarray(np.random.default_rng(0).standard_normal((p, m)),
                            jnp.float32)
            for method, kw in {CASES}:
                cfg = CollectiveConfig(**kw)
                body = lambda x: all_reduce(x[0], "data", p, cfg)[None]
                f = jax.jit(shard_map(body, mesh=mesh,
                                      in_specs=P("data", None),
                                      out_specs=P("data", None)))
                f(X)[0].block_until_ready()  # compile+warm
                ts = []
                for _ in range({reps}):
                    t0 = time.perf_counter()
                    f(X)[0].block_until_ready()
                    ts.append(time.perf_counter() - t0)
                out.append((m, method, min(ts) * 1e6))
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


def predicted_rows(p: int, model: cm.CommModel, group_size: int = 4,
                   levels3: tuple = (4, 4)):
    rows = []
    for m in SIZES:
        nbytes = m * 4
        rows.append((m, "dptree", cm.dptree_time(
            p, nbytes, cm.optimal_blocks(p, nbytes, model, "dptree"), model) * 1e6))
        rows.append((m, "sptree", cm.sptree_time(
            p, nbytes, cm.optimal_blocks(p, nbytes, model, "sptree"), model) * 1e6))
        rows.append((m, "redbcast", cm.redbcast_time(
            p, nbytes, cm.optimal_blocks(p, nbytes, model, "redbcast"), model) * 1e6))
        rows.append((m, "ring", cm.ring_time(p, nbytes, model) * 1e6))
        for label, spec in (("hier", group_size), ("hier3", levels3)):
            lv = resolve_levels(p, spec) if spec else None
            if lv is None:
                continue
            rows.append((m, label, cm.hier_time(
                p, nbytes,
                cm.optimal_blocks(p, nbytes, model, "hier", group_size=lv),
                model, group_size=lv) * 1e6))
            if label == "hier3":
                rows.append((m, "hier3_bf16", cm.hier_time(
                    p, nbytes,
                    cm.optimal_blocks(p, nbytes, model, "hier",
                                      group_size=lv, compression="bf16"),
                    model, group_size=lv, compression="bf16") * 1e6))
    return rows


def run(csv_out):
    for m, method, us in measured_rows():
        csv_out(f"collective_measured_cpu8/{method}/m={m}", us,
                f"min-of-5 us")
    for m, method, us in predicted_rows(288, cm.PAPER_HYDRA):
        csv_out(f"collective_predicted_hydra288/{method}/m={m}", us,
                "alpha-beta model, paper cluster")
    for m, method, us in predicted_rows(256, cm.TPU_V5E):
        csv_out(f"collective_predicted_v5e256/{method}/m={m}", us,
                "alpha-beta model, one pod")
    for m, method, us in predicted_rows(256, cm.TPU_V5E_INTERPOD):
        csv_out(f"collective_predicted_v5e256_interpod/{method}/m={m}", us,
                "alpha-beta model, slow inter-group links (hier's regime)")
    # headline ratio check (paper: dptree/redbcast -> 3/4 for large m)
    nbytes = SIZES[-1] * 4
    t_dp = cm.dptree_time(288, nbytes, cm.optimal_blocks(288, nbytes,
                          cm.PAPER_HYDRA, "dptree"), cm.PAPER_HYDRA)
    t_rb = cm.redbcast_time(288, nbytes, cm.optimal_blocks(288, nbytes,
                            cm.PAPER_HYDRA, "redbcast"), cm.PAPER_HYDRA)
    csv_out("paper_ratio_dptree_over_redbcast_large_m", t_dp / t_rb,
            "analysis predicts ~0.75; paper measured 0.88 (Hydra, 8.4M ints)")
