"""Find the knee of a serving cell once, by a sweep of offered rates on the
chip, in one process (weights drawn and programs warmed once).

    python3 chipbench/sweep.py --workload minicpm_2b.chat \
        --rates 0.5,0.75,1,1.25,1.5 --seconds 40 --seed 7

For each rate it plays the cell's traffic at that rate for ``--seconds``
and prints what the window completed: tokens/s, the TTFT and queue-wait
percentiles, and how many requests were still waiting or unserved when
the window closed (a queue that grows through the window is above the
knee). The cell's own rate, about four fifths of the knee, is then fixed
in its workload file; the benchmark's runs never search for it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    import jax
    import numpy as np

    from chipbench import harness, reduce, traffic
    from chipbench.drivers import serving
    from repro.launch.compile_cache import enable_compile_cache
    if jax.devices()[0].platform != "tpu":
        harness.log("sweep: needs a TPU")
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    enable_compile_cache()
    bench = harness.Benchmark()
    cell = bench.cell(args.workload)
    config, wl = bench.config(cell), bench.workload(cell)
    rates = [float(r) for r in args.rates.split(",")]
    engine = serving.build(config, wl, args.seed, jax.devices())
    vocab = engine.cfg.vocab_size
    top = dict(wl["traffic"], rate_per_s=max(rates))
    serving.warm(engine, traffic.open_loop(top, args.seconds, args.seed,
                                           vocab))
    for i, rate in enumerate(rates):
        spec = dict(wl["traffic"], rate_per_s=rate)
        arrivals = traffic.open_loop(spec, args.seconds, args.seed + i, vocab)
        t0 = time.perf_counter()
        recs, ticks, window, late = serving.play(
            engine.start(), arrivals, args.seconds, wl["drain_s"])
        run = harness.Run(cell=cell, config=config, workload=wl,
                          seed=args.seed + i, seconds=args.seconds,
                          traced=False, t_start=t0)
        run.data.update(records=recs, ticks=ticks, window=window)
        t1 = window[1]
        waiting = sum(1 for r in recs if r.admit is None or r.admit > t1)
        row = {"rate_per_s": rate, "requests": len(recs),
               "tokens_per_s": reduce.window_tokens(run) / (t1 - window[0]),
               "ttft_p50_ms": 1e3 * np.percentile(reduce.ttfts(run), 50),
               "ttft_p95_ms": 1e3 * np.percentile(reduce.ttfts(run), 95),
               "itl_p50_ms": 1e3 * np.percentile(reduce.token_gaps(run), 50),
               "itl_p95_ms": 1e3 * np.percentile(reduce.token_gaps(run), 95),
               "queue_wait_p95_ms":
                   1e3 * np.percentile(reduce.queue_waits(run), 95),
               "not_admitted_at_close": waiting,
               "unserved": sum(1 for r in recs if r.done is None),
               "generator_late_p50_ms": 1e3 * float(np.median(late))}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
