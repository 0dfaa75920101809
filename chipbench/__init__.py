"""On-chip benchmark of the repository's serving and collective paths.

``BENCHMARK.json`` at the repository root names the cells; everything that
belongs to one configuration, one traffic mix or one metric sits in a file
of its own under this directory, found by that name:

* ``configs/<config>.json``   sizes as run, source and cuts;
* ``workloads/<cell>.json``   the traffic mix and the driver that plays it;
* ``metrics/<metric>.py``     a reader ``read(run) -> float | None``.

Run a cell with ``python3 chipbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.
"""
