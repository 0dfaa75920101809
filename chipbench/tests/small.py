"""Reduced sizes for running the cells' paths on the CPU in tests."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import harness, peaks  # noqa: E402

WIDTHS = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
              num_key_value_heads=4, intermediate_size=128, vocab_size=512)

# made-up peaks: the CPU has no entry in the table, and these tests read
# no device metric
FAKE_PEAKS = peaks.Peaks(1e12, 1e11, 1e9, 1e10, "test")


def chat(seconds_rate: float = 10.0):
    """(bench, cell, config, workload) of the chat cell at reduced size."""
    bench = harness.Benchmark()
    cell = bench.cell("minicpm_2b.chat")
    wl = bench.workload(cell)
    wl = dict(wl, traffic=dict(
        wl["traffic"], rate_per_s=seconds_rate,
        prompt={"median": 16, "sigma": 0.8, "min": 4, "max": 48},
        output={"median": 6, "sigma": 0.6, "min": 2, "max": 16}),
        engine={"n_slots": 4, "max_len": 64}, drain_s=20,
        trace={"start_s": 0.5, "seconds": 1.0},
        check=dict(wl["check"], sample_requests=4))
    return bench, cell, dict(bench.config(cell), **WIDTHS), wl


def grad_sync():
    bench = harness.Benchmark()
    cell = bench.cell("minicpm_2b_dp4.grad_sync")
    wl = dict(bench.workload(cell), trace={"start_s": 0.2, "seconds": 0.5})
    return bench, cell, dict(bench.config(cell), **WIDTHS), wl
