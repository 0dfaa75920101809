"""The paper's algorithm, standalone: run all four reduction-to-all
implementations on 8 virtual devices, check correctness, and time them.

  PYTHONPATH=src python examples/collective_playground.py

This is the closest analogue of the paper's own experiment (Figure 1) that a
laptop can run: User-Allreduce2 (doubly-pipelined dual-root) vs
User-Allreduce1 (pipelined reduce+bcast) vs ring vs native psum.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys  # noqa: E402

sys.path.insert(0, "src")

import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core.collectives import (METHODS, CollectiveConfig,  # noqa: E402
                                    all_reduce)
from repro.core.cost_model import TPU_V5E, optimal_blocks  # noqa: E402


def main():
    p = 8
    from jax import shard_map

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((p,), ("data",))
    rng = np.random.default_rng(0)
    for m in (10_000, 1_000_000):
        X = jnp.asarray(rng.standard_normal((p, m)), jnp.float32)
        want = np.asarray(X).sum(0)
        print(f"\nm = {m} f32 elements "
              f"(analytic optimal blocks for one v5e pod: "
              f"{optimal_blocks(256, m * 4, TPU_V5E, 'dptree')})")
        cases = [(m_, CollectiveConfig(method=m_, group_size=4
                                       if m_ == "hier" else None))
                 for m_ in METHODS if m_ != "auto"]
        cases += [("hier3", CollectiveConfig(method="hier",
                                             group_size=(2, 2))),
                  ("hier3+bf16", CollectiveConfig(method="hier",
                                                  group_size=(2, 2),
                                                  compress_inter_group=True))]
        for name, cfg in cases:
            body = lambda x: all_reduce(x[0], "data", p, cfg)[None]
            f = jax.jit(shard_map(body, mesh=mesh,
                                      in_specs=P("data", None),
                                      out_specs=P("data", None)))
            out = f(X)
            # the bf16 slow-stage wire is lossy by design; everything else
            # matches at f32 tolerance
            tol = 2e-2 if name.endswith("bf16") else 2e-5
            np.testing.assert_allclose(np.asarray(out[0]), want,
                                       rtol=tol, atol=tol * np.abs(want).max())
            ts = []
            for _ in range(5):
                t0 = time.perf_counter()
                f(X)[0].block_until_ready()
                ts.append(time.perf_counter() - t0)
            print(f"  {name:10s} {min(ts)*1e3:9.2f} ms   (correct)")


if __name__ == "__main__":
    main()
