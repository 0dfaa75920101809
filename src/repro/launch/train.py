"""End-to-end training driver.

Runs any registered architecture (reduced or full config) with: the
partial-manual train step (paper's collective in the gradient path where the
mesh has >1 DP rank), the synthetic deterministic data pipeline, async
checkpointing with exact resume, and the fault-tolerance supervisor.

CPU quickstart (the examples call this):
  PYTHONPATH=src python -m repro.launch.train --arch granite_3_8b --reduced \
      --steps 50 --seq-len 128 --global-batch 8

Multi-device (8 virtual hosts):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.train --arch minicpm_2b --reduced --steps 30 \
      --mesh 4x2
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.checkpoint.checkpointing import (CheckpointManager, latest_step,
                                            restore)
from repro.configs.base import get_config, get_parallel
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch import step_fns
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import transformer as tf
from repro.optim.optimizers import adamw, cosine_schedule, wsd_schedule
from repro.runtime.fault_tolerance import HostFailure, run_with_restarts


def autotune_warmup(mesh, pcfg, params, leaf_specs=None, *, reps: int = 3,
                    max_buckets: int = 4, verbose: bool = True) -> list:
    """Per-mesh autotune warm-up: measure the collective candidates at the
    ACTUAL gradient bucket sizes on this mesh's data-parallel axes, before
    step 0, and record the winners in the on-disk autotune cache.

    Bucket sizes come from :func:`repro.core.collectives.bucket_sizes` over
    the real parameter pytree — pass ``leaf_specs`` (the params'
    PartitionSpecs) so the sharding-kind partition matches what
    ``bucketed_all_reduce`` issues at trace time; each (axis, bucket) pair
    is timed on a dedicated one-axis mesh built from the devices that
    actually sit along that axis (other axes pinned at coordinate 0 — the
    links the training reduction crosses). Winners are keyed by
    ``(p, nbytes, dtype, comm_model.name)``, exactly the key
    ``CollectiveConfig(method="auto")`` probes at trace time, so the very
    first training step resolves from measurements — the ROADMAP's closed
    loop. Candidate failures are skipped by the tuner; this hook never
    raises on an unmeasurable candidate.

    Key-collision caveat: the cache key does not carry the axis, so when two
    DP axes have the SAME size they share keys. Axes are therefore tuned
    innermost-first ('data', then 'pod'), letting the slowest fabric's
    winner overwrite on collision — a slow-link winner replays safely (if
    suboptimally) on fast links, while the reverse can collapse. Distinct
    per-axis results need distinct ``comm_model`` names (one config per
    fabric), which is also what prices the auto switch correctly.

    Returns ``[(axis, nbytes, TuneResult), ...]`` for logging.
    """
    import time

    from jax.sharding import PartitionSpec as _P

    from repro.core import autotune, collectives
    from repro.core.topology import resolve_levels

    cfg = pcfg.collective
    # innermost (fast) first: on key collisions the slow axis wins
    dp_axes = [a for a in ("data", "pod")
               if a in mesh.axis_names and mesh.shape[a] > 1]
    sizes = collectives.bucket_sizes(
        params, cfg.bucket_bytes, leaf_specs=leaf_specs,
        n_model=dict(mesh.shape).get("model"))
    # largest buckets dominate step time; bound warm-up cost
    sizes = sorted(set(sizes), key=lambda t: -t[0])[:max_buckets]
    results = []
    for ax in dp_axes:
        p = mesh.shape[ax]
        pos = mesh.axis_names.index(ax)
        sel = [0] * mesh.devices.ndim
        sel[pos] = slice(None)
        axis_devs = mesh.devices[tuple(sel)]
        tune_mesh = make_mesh((p,), (ax,), devices=axis_devs)
        algorithms = autotune._ALGORITHMS
        if resolve_levels(p, cfg.hier_spec) is not None:
            algorithms = algorithms + ("hier",)
        for n, dtype in sizes:
            nbytes = n * dtype.itemsize
            X = jnp.zeros((n,), dtype)

            def runner(algo, b, _X=X, _p=p, _ax=ax, _mesh=tune_mesh):
                compress = algo.endswith(autotune.COMPRESSED_SUFFIX)
                base = algo[:-len(autotune.COMPRESSED_SUFFIX)] if compress \
                    else algo
                ccfg = dataclasses.replace(cfg, method=base,
                                           num_blocks=int(b),
                                           compress_inter_group=compress)
                f = jax.jit(jax.shard_map(
                    lambda x: collectives.all_reduce(x, _ax, _p, ccfg),
                    mesh=_mesh, in_specs=_P(), out_specs=_P(),
                    check_vma=False))
                f(_X).block_until_ready()  # compile + warm
                ts = []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    f(_X).block_until_ready()
                    ts.append(time.perf_counter() - t0)
                return min(ts)

            res = autotune.tune(
                runner, p, nbytes, str(jnp.dtype(dtype)),
                cfg.comm_model.name, cfg.comm_model,
                algorithms=algorithms, group_size=cfg.hier_spec,
                compress_inter_group=cfg.compress_inter_group)
            results.append((ax, nbytes, res))
            if verbose:
                tag = "+bf16" if res.compressed else ""
                print(f"warmup[{ax} p={p}] {nbytes}B {jnp.dtype(dtype).name}"
                      f" -> {res.algorithm}{tag}/b={res.num_blocks}"
                      f" ({res.time_s * 1e6:.0f}us)")
    return results


def build_optimizer(arch_mod, lr: float, steps: int):
    sched_name = getattr(arch_mod, "TRAIN_SCHEDULE", "cosine")
    warmup = max(5, steps // 20)
    if sched_name == "wsd":
        sched = wsd_schedule(lr, warmup, int(steps * 0.7),
                             steps - warmup - int(steps * 0.7) or 1)
    else:
        sched = cosine_schedule(lr, warmup, steps)
    return adamw(sched)


def train_loop(args, fail_at: int | None = None) -> dict:
    """One training attempt; raises HostFailure at step ``fail_at`` (tests)."""
    from repro.configs import base as cfgbase

    mesh_shape = tuple(int(x) for x in args.mesh.split("x"))
    n_need = int(np.prod(mesh_shape))
    axes = ("data", "model")[-len(mesh_shape):] if len(mesh_shape) == 2 \
        else ("pod", "data", "model")
    mesh = make_mesh(mesh_shape, axes)
    cfg = get_config(args.arch, reduced=args.reduced)
    pcfg = get_parallel(args.arch)
    if args.collective:
        pcfg = dataclasses.replace(
            pcfg, collective=dataclasses.replace(pcfg.collective,
                                                 method=args.collective))
    arch_mod = cfgbase.get_arch(args.arch)
    optimizer = build_optimizer(arch_mod, args.lr, args.steps)
    step, sh = step_fns.make_train_step(cfg, pcfg, mesh, optimizer,
                                        accum=args.accum)

    params = tf.init_params(jax.random.PRNGKey(args.seed), cfg)
    params = jax.device_put(params, step_fns._named(mesh, sh["params"]))
    if getattr(args, "autotune_warmup", False):
        autotune_warmup(mesh, pcfg, params, leaf_specs=sh["params"])
    opt_state = jax.device_put(sh["opt_init"](params),
                               step_fns._named(mesh, sh["opt"]))

    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                      global_batch=args.global_batch, seed=args.seed)
    ds = SyntheticLM(dcfg)
    mgr = CheckpointManager(args.ckpt_dir, keep=2) if args.ckpt_dir else None

    start = 0
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        state_like = {"params": params, "opt": opt_state}
        state, extra, start = restore(args.ckpt_dir, state_like)
        params = jax.device_put(state["params"],
                                step_fns._named(mesh, sh["params"]))
        opt_state = jax.device_put(state["opt"],
                                   step_fns._named(mesh, sh["opt"]))
        print(f"resumed from step {start}")

    bsharding = NamedSharding(mesh, sh["batch"])
    hist = []
    t0 = time.time()
    for i in range(start, args.steps):
        batch = ds.batch_at(i)
        batch = jax.device_put(batch, bsharding)
        params, opt_state, vec = step(params, opt_state, batch)
        if fail_at is not None and i == fail_at:
            raise HostFailure(0, f"injected failure at step {i}")
        if i % args.log_every == 0 or i == args.steps - 1:
            v = np.asarray(vec)
            hist.append((i, float(v[0])))
            print(f"step {i:5d} loss {v[0]:.4f} ce {v[1]:.4f} "
                  f"gnorm {v[3]:.3f} ({time.time()-t0:.1f}s)")
        if mgr and i and i % args.ckpt_every == 0:
            mgr.save_async(i + 1, {"params": params, "opt": opt_state},
                           extra={"data_step": i + 1})
    if mgr:
        mgr.save_async(args.steps, {"params": params, "opt": opt_state},
                       extra={"data_step": args.steps})
        mgr.wait()
        mgr.close()
    return {"history": hist, "final_loss": hist[-1][1] if hist else None,
            "params": params}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite_3_8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--mesh", default="1x1", help="e.g. 4x2 = data x model")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--collective", default=None,
                    help="override: dptree|sptree|redbcast|ring|hier|psum|auto")
    ap.add_argument("--autotune-warmup", action="store_true",
                    help="before step 0, measure the collective candidates at "
                         "the actual gradient bucket sizes on this mesh and "
                         "cache the winners for method='auto'")
    ap.add_argument("--autotune-cache", default=None, metavar="PATH",
                    help="per-deployment autotune cache file; overrides "
                         "REPRO_AUTOTUNE_CACHE and the XDG default — both "
                         "the warm-up's writes and method='auto' consults "
                         "go through it (one file per mesh/deployment)")
    ap.add_argument("--max-restarts", type=int, default=3)
    args = ap.parse_args(argv)
    if args.autotune_cache:
        from repro.core import autotune
        autotune.set_cache_path(args.autotune_cache)

    out = run_with_restarts(lambda attempt: train_loop(args),
                            max_restarts=args.max_restarts)
    print(f"done. final loss {out['final_loss']:.4f} "
          f"(restarts: {out['restarts']})")
    return out


if __name__ == "__main__":
    # the command line, not main(): tests drive main() in-process and keep
    # the compile cache off
    enable_compile_cache()
    main()
