"""The cells' window loops run end to end on the CPU at reduced size
through ``run.execute``; ``run.py`` itself refuses a CPU."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from chipbench.tests import small
from chipbench import run as cb

ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
       "PYTHONPATH": os.pathsep.join([small.ROOT,
                                      os.path.join(small.ROOT, "src")])}


@pytest.mark.parametrize("cell", ["minicpm_2b.chat",
                                  "minicpm_2b_dp4.grad_sync"])
def test_run_refuses_a_cpu(cell):
    r = subprocess.run(
        [sys.executable, os.path.join(small.ROOT, "chipbench", "run.py"),
         "--workload", cell, "--seed", "3", "--seconds", "1"],
        capture_output=True, text=True, timeout=300, env=ENV)
    assert r.returncode != 0
    assert "{" not in r.stdout
    assert "needs" in r.stderr


@pytest.mark.parametrize("traced", [False, True])
def test_chat_window_on_cpu(traced):
    bench, cell, config, wl = small.chat()
    run, res = cb.execute(bench, cell, 2 ** 33 + 1, 2.0, traced,
                          jax.devices()[:1], peaks=small.FAKE_PEAKS,
                          config=config, workload=wl)
    assert res["correct"], res["compared"]
    assert res["attempted"] == 20 and res["failed"] == 0
    assert list(res)[-1] == "compared"
    want = ({"queue_wait_p95_ms"} if traced else
            {"ttft_p95_ms", "itl_p95_ms", "tokens_per_s", "setup_s"})
    # the device metrics need a TPU trace: a CPU trace has no device plane,
    # and their readers find nothing to read
    assert set(res["metrics"]) == want
    assert ("busy_s" in res["device"]) == traced
    recs = run.data["records"]
    assert all(r.done is not None and len(r.token_times) == r.max_new
               for r in recs)
    assert run.data["compiles_in_window"] == 0


GRAD_SYNC = textwrap.dedent("""
    import json, sys
    sys.path[:0] = {paths!r}
    import jax
    from chipbench.tests import small
    from chipbench import run as cb
    bench, cell, config, wl = small.grad_sync()
    run, res = cb.execute(bench, cell, 2 ** 35 + 3, 1.0, {traced},
                          jax.devices()[:4], peaks=small.FAKE_PEAKS,
                          config=config, workload=wl)
    print(json.dumps(res))
""")


@pytest.mark.parametrize("traced", [False, True])
def test_grad_sync_window_on_virtual_devices(traced):
    code = GRAD_SYNC.format(paths=[small.ROOT,
                                   os.path.join(small.ROOT, "src")],
                            traced=traced)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, env={**ENV, "XLA_FLAGS":
                          "--xla_force_host_platform_device_count=4"})
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["correct"], res["compared"]
    assert res["device"]["count"] == 4 and res["attempted"] >= 1
    assert res["compared"]["sum_error"]["value"] < 1e-6
    if not traced:
        assert set(res["metrics"]) == {"grad_allreduce_ms", "setup_s"}
