"""Run one cell of the chip benchmark.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell's configuration, traffic and metrics are found by name from
``BENCHMARK.json`` (see ``chipbench/harness.py``). With ``--trace 0`` the
result carries the cell's end-to-end metrics; with ``--trace 1`` a stretch
of the window is traced with the JAX profiler and the result carries its
per-layer metrics, the device's busy and window seconds, and a breakdown.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (, ``breakdown``), and
last ``compared``, each number that decided ``correct`` beside its limit.
Those numbers are also the last lines of standard error.

Needs a TPU with at least as many chips as the cell asks for: elsewhere it
exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def execute(bench, cell: dict, seed: int, seconds: float, traced: bool,
            devices, *, peaks=None, config=None, workload=None,
            control: bool = False, t_start: float = T_START):
    """Play one cell on ``devices``; returns ``(run, result)``.

    ``config`` and ``workload`` replace the cell's files (the CPU tests
    run the same path at reduced size); ``control`` also reads the control
    the limits were set against (the benchmark's own runs do not)."""
    from chipbench import harness, profile
    from chipbench import peaks as peak_table
    from chipbench import trace as tr
    config = bench.config(cell) if config is None else config
    workload = bench.workload(cell) if workload is None else workload
    run = harness.Run(cell=cell, config=config, workload=workload,
                      seed=seed, seconds=seconds, traced=traced,
                      t_start=t_start)
    run.peaks = (peak_table.for_device(devices[0].device_kind)
                 if peaks is None else peaks)
    tracer = None
    if traced:
        t_on = min(workload["trace"]["start_s"], seconds / 3)
        tracer = profile.WindowTracer(
            t_on, min(t_on + workload["trace"]["seconds"], seconds))
    bench.driver(workload["driver"]).run(run, devices, tracer=tracer,
                                         control=control)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    if tracer is not None:
        run.trace = tracer.load()
        run.data["tracer"] = tracer
        device.update(busy_s=tr.busy_s(run.trace),
                      window_s=run.trace.window_s)
        busy = sorted(d for d, evs in run.trace.ops.items() if evs)
        if busy:
            run.breakdown = {
                "device_ops": tr.top_ops(run.trace, busy[0]),
                "idle_gaps": tr.longest_gaps(run.trace, busy[0])}
    result = {"correct": run.correct, "attempted": run.attempted,
              "failed": run.failed,
              "metrics": harness.read_metrics(bench, run),
              "device": device}
    if run.breakdown is not None:
        result["breakdown"] = run.breakdown
    result["compared"] = {name: {"value": _finite(v), "limit": lim}
                          for name, v, lim in run.compared}
    return run, result


def _finite(v):
    return v if isinstance(v, (int, float)) and math.isfinite(v) else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness
    bench = harness.Benchmark()
    cell = bench.cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        harness.log(f"chipbench: {cell['name']} needs {cell['chips']} TPU "
                    f"chip(s); JAX found {len(devices)} "
                    f"{devices[0].platform} device(s)")
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    # every program goes to the cache, however quick its compile, so that
    # only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    harness.log(f"chipbench: {cell['name']} seed {args.seed}, "
                f"{args.seconds:g} s, trace {args.trace}; compile cache "
                f"{enable_compile_cache()}")
    run, result = execute(bench, cell, args.seed, args.seconds,
                          bool(args.trace), devices[:cell["chips"]])
    for name, v, lim in run.compared:
        harness.log(f"compared {name}: {v!r} (limit {lim!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
