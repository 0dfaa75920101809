"""Split-KV (flash-decoding style) long-context decode with the paper's
collective combining the attention partials.

  PYTHONPATH=src python examples/longctx_splitkv.py

Each of 8 virtual devices holds a LENGTH-shard of one long KV cache; a decode
step computes flash partials (m, s, o) locally and combines them across the
sequence-parallel axis with ``structured_all_reduce`` — a b=1 dual-root tree,
the log-latency regime the paper's algorithm wins. The result is checked
against single-device attention over the full cache.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys  # noqa: E402

sys.path.insert(0, "src")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core.collectives import structured_all_reduce  # noqa: E402
from repro.models import layers as L  # noqa: E402


def main():
    p = 8
    from jax import shard_map

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((p,), ("data",))
    cfg = L.AttnConfig(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16)
    params = L.attn_init(jax.random.PRNGKey(0), cfg)
    B, S_total = 2, 512  # cache length 512 split across 8 devices
    S_local = S_total // p
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    cache_k = jax.random.normal(ks[0], (B, S_total, 2, 16))
    cache_v = jax.random.normal(ks[1], (B, S_total, 2, 16))
    x = jax.random.normal(ks[2], (B, 1, 64))
    cache_pos = jnp.asarray(S_total - 1)  # decoding the last position

    # ---- reference: single-device full-cache decode ----------------------
    ref, _ = L.attention_decode(params, cfg, x,
                                {"k": cache_k, "v": cache_v}, cache_pos)

    # ---- split-KV: shard the length dim, tree-combine the partials -------
    def body(ck, cv):
        shard_start = jax.lax.axis_index("data") * S_local
        parts, _, _ = L.attention_decode_partials(
            params, cfg, x, ck, cv, cache_pos, shard_start)
        combined = structured_all_reduce(parts, "data", p,
                                         L.softmax_partials_combine)
        return L.finish_partials(params, cfg, combined, x.dtype)

    f = jax.jit(shard_map(body, mesh=mesh,
                              in_specs=(P(None, "data"), P(None, "data")),
                              out_specs=P(), check_vma=False))
    got = f(cache_k, cache_v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    print(f"split-KV decode across {p} shards == full-cache decode  "
          f"(max |diff| = {np.abs(np.asarray(got) - np.asarray(ref)).max():.2e})")


if __name__ == "__main__":
    main()
