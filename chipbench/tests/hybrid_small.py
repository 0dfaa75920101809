"""Reduced sizes of the hybrid and closed-loop cells, for the CPU tests."""

from __future__ import annotations

from chipbench.tests import small

# Jamba's block at toy widths: the file's keys with widths cut, 4 router
# outputs of which 2 are held, one whole period of 8 layers
JAMBA = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=96, vocab_held=512, num_hidden_layers=8,
             mamba_d_state=8, mamba_dt_rank=4, router_experts=4,
             num_experts=2, held_experts_first=1)


def jamba_chat(rate: float = 10.0, config: dict | None = None):
    """(bench, cell, config, workload) of the hybrid chat cell at reduced
    size."""
    bench = small.harness.Benchmark()
    cell = bench.cell("jamba2_mini_ep2.chat")
    wl = bench.workload(cell)
    wl = dict(wl, traffic=dict(
        wl["traffic"], rate_per_s=rate,
        prompt={"median": 16, "sigma": 0.8, "min": 4, "max": 48},
        output={"median": 6, "sigma": 0.6, "min": 2, "max": 16}),
        engine={"n_slots": 4, "max_len": 64}, drain_s=20,
        trace={"start_s": 0.5, "seconds": 1.0},
        check=dict(wl["check"], sample_requests=4))
    return bench, cell, dict(bench.config(cell), **(config or JAMBA)), wl


def docs():
    """(bench, cell, config, workload) of the closed-loop docs cell at
    reduced size."""
    bench = small.harness.Benchmark()
    cell = bench.cell("minicpm_2b.docs")
    wl = bench.workload(cell)
    wl = dict(wl, traffic=dict(
        wl["traffic"], clients=3, pool=64,
        prompt={"median": 24, "sigma": 0.4, "min": 12, "max": 40},
        output={"median": 4, "sigma": 0.6, "min": 2, "max": 8}),
        engine={"n_slots": 2, "max_len": 64}, drain_s=20,
        trace={"start_s": 0.5, "seconds": 1.0},
        check=dict(wl["check"], sample_requests=4))
    return bench, cell, dict(bench.config(cell), **small.WIDTHS), wl
