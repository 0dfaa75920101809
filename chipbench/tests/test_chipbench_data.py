"""The benchmark is data: every name in BENCHMARK.json resolves to its
files, a cell or metric added as files alone is found, the traffic is a
function of the seed, and the file keeps to its contract."""

import json
import os
import re
import shutil
import statistics

import numpy as np
import pytest

from chipbench.tests import small
from chipbench import harness, peaks, traffic, weights, work

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _spec():
    with open(os.path.join(small.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_name_resolves_to_its_files():
    bench = harness.Benchmark()
    spec = bench.spec
    for entry in spec["configs"]:
        assert os.path.isfile(os.path.join(small.ROOT, entry["file"]))
    for cell in spec["workloads"]:
        wl = bench.workload(cell)
        assert bench.driver(wl["driver"]).run
        assert bench.config(cell)["num_hidden_layers"] > 0
        for traced in (False, True):
            names = [m["name"] for m in bench.metrics(cell, traced)]
            assert names, (cell["name"], traced)
            for name in names:
                assert callable(bench.reader(name))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(bench.reader(m["name"]))


def test_a_cell_and_a_metric_added_as_files_are_found(tmp_path):
    """A new cell (workload file), configuration (config file) and
    per-layer metric (reader file) need only files and entries."""
    base = tmp_path / "chipbench"
    shutil.copytree(os.path.join(small.ROOT, "chipbench"), base,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = _spec()
    spec["configs"].append(dict(spec["configs"][0], name="other_model",
                                file="chipbench/configs/other_model.json"))
    spec["workloads"].append({"name": "other_model.burst",
                              "config": "other_model", "traffic": "burst",
                              "chips": 1, "why": "test"})
    spec["end_to_end"][0]["workloads"].append("other_model.burst")
    spec["per_layer"].append({"name": "dummy_share", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "device", "moves": "ttft_p95_ms",
                              "workloads": ["other_model.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    shutil.copy(base / "configs" / "minicpm_2b.json",
                base / "configs" / "other_model.json")
    shutil.copy(base / "workloads" / "minicpm_2b.chat.json",
                base / "workloads" / "other_model.burst.json")
    (base / "metrics" / "dummy_share.py").write_text(
        "def read(run):\n    return 42.0\n")

    bench = harness.Benchmark(root=str(tmp_path), base=str(base))
    cell = bench.cell("other_model.burst")
    assert bench.config(cell)["hidden_size"] == 2304
    assert bench.workload(cell)["driver"] == "serving"
    assert [m["name"] for m in bench.metrics(cell, False)] == \
        ["ttft_p95_ms", "setup_s"]
    assert [m["name"] for m in bench.metrics(cell, True)] == ["dummy_share"]
    run = harness.Run(cell=cell, config={}, workload={}, seed=0, seconds=1,
                      traced=True, t_start=0.0)
    assert harness.read_metrics(bench, run) == {
        "dummy_share": {"value": 42.0, "unit": "%"}}
    # the cells already there do not report the new metric
    assert "dummy_share" not in [
        m["name"] for m in bench.metrics(bench.cell("minicpm_2b.chat"), True)]


def test_traffic_is_a_function_of_the_seed():
    wl = harness.Benchmark().workload(
        harness.Benchmark().cell("minicpm_2b.chat"))["traffic"]
    seed = 2 ** 31 + 12345
    a = traffic.open_loop(wl, 45, seed, 122753)
    b = traffic.open_loop(wl, 45, seed, 122753)
    c = traffic.open_loop(wl, 45, seed + 1, 122753)
    assert a == b
    assert a != c
    # another seed: the same requests, the sequence turned to start at
    # another one
    n = len(a)
    shape = [(len(x.prompt), x.max_new) for x in a]
    other = [(len(x.prompt), x.max_new) for x in c]
    assert other == shape[1:] + shape[:1]
    gaps = np.diff([x.due for x in a])
    assert np.allclose(np.diff([x.due for x in c])[:n - 2], gaps[1:])
    assert all(wl["prompt"]["min"] <= len(x.prompt) <= wl["prompt"]["max"]
               for x in a)
    assert [x.due for x in a] == sorted(x.due for x in a)
    assert a[0].due == 0.0 and a[-1].due < 45
    assert len(a) == round(wl["rate_per_s"] * 45)


def test_lognormal_quantiles_keep_the_median():
    lengths = traffic.lognormal_lengths(
        1001, {"median": 256, "sigma": 0.8, "min": 1, "max": 10 ** 6})
    assert statistics.median(lengths) == 256


def test_unknown_device_kind_raises():
    assert peaks.for_device("TPU v5 lite").hbm_bytes_per_s == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.for_device("TPU v9 imaginary")


def test_work_counts():
    cfg = harness.Benchmark().config(
        harness.Benchmark().cell("minicpm_2b.chat"))
    # 40 layers of 61.05 M matmul weights and the 282.8 M embedding
    assert work.layer_matmul_params(cfg) == 61_046_784
    assert work.weight_bytes(cfg) == pytest.approx(5.450e9, rel=1e-3)
    assert work.kv_bytes(cfg, 1) == 368_640
    assert work.decode_bytes(cfg, [10, 20]) == \
        work.weight_bytes(cfg) + 30 * 368_640
    assert work.token_flops(cfg, 0) == 2 * (40 * 61_046_784 + 2304 * 122753)
    assert work.allreduce_bus_bytes(4.0, 4) == 6.0


def test_weights_are_drawn_from_the_seed_by_path():
    spec = {"embed": ((8, 4), "bfloat16"), "a/norm/scale": ((4,), "float32"),
            "layers/w": ((2, 4, 3), "bfloat16")}
    seed = 2 ** 40 + 7
    x, y = weights.draw(spec, seed), weights.draw(spec, seed)
    z = weights.draw(spec, seed + 1)
    for k in spec:
        assert x[k].shape == spec[k][0] and x[k].dtype.name == spec[k][1]
        assert np.array_equal(np.asarray(x[k], np.float32),
                              np.asarray(y[k], np.float32))
        assert not np.array_equal(np.asarray(x[k], np.float32),
                                  np.asarray(z[k], np.float32))
    # a leaf's draw does not depend on which other leaves are drawn
    one = weights.draw({"layers/w": spec["layers/w"]}, seed)
    assert np.array_equal(np.asarray(one["layers/w"], np.float32),
                          np.asarray(x["layers/w"], np.float32))
    with pytest.raises(ValueError):
        weights.seed_key(-1)


def test_benchmark_json_keeps_to_its_contract():
    spec = _spec()
    assert list(spec) == ["command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"]
    assert spec["command"] == ["python3", "chipbench/run.py"]
    assert spec["paths"] == ["chipbench"]
    assert 1 <= spec["run_seconds"] <= 51
    # a full check of 24 cells fits its 43,200 s
    assert (2 + 14 * 24) * (spec["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    names = set()
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/")
        for k in c["reduced"]:
            assert NAME.match(k)
            # never a width: a size, a head count, an expansion
            assert not re.search(r"(_size|_dim|_rank|heads|experts_per)$",
                                 k)
    cells = {w["name"] for w in spec["workloads"]}
    used = {w["config"] for w in spec["workloads"]}
    assert used == {c["name"] for c in spec["configs"]}
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= \
        max(1, len(cells) // 2)
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in e2e[m["moves"]].get("workloads", [cell])
    for entry in spec["configs"] + spec["workloads"] + spec["end_to_end"] \
            + spec["per_layer"]:
        assert NAME.match(entry["name"]) and entry["name"] not in names
        names.add(entry["name"])
    for cell in cells:
        assert any(cell in m.get("workloads", [cell])
                   for m in spec["end_to_end"] if m["name"] != "setup_s")
        assert any(cell in m["workloads"] for m in spec["per_layer"])
