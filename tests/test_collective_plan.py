"""Golden table for ``collectives.plan``: how one reduction is resolved.

``collective_plan_golden.json`` holds the ``(algorithm, num_blocks,
group_size, compress)`` the collective layer chose for a grid of configs,
rank counts, payloads and operators before the choice moved behind
``plan``: every explicit method and ``auto`` at p in {2, 4, 8}, payloads of
256 B, 64 KiB, 16 MiB and the three ``minicpm_2b_dp4`` gradient buckets,
``auto`` with an empty autotune cache and with flat, ``hier`` and
compressed-``hier`` entries, under ``jnp.add`` and a custom
(non-commutative to the engine) operator. ``"raises"`` marks a request
``plan`` must refuse. Any change to a pick, a block count or a replay rule
shows up here.

``cost_model_golden.json`` holds, from the same tree, what the pricing
layer gave for a grid of rank counts, payloads, fabrics and hierarchy
specs: ``optimal_blocks`` of every pipelined algorithm (``hier`` with and
without the bf16 wire), the ``best_algorithm`` switch and the
``predicted_table`` rows. Exact equality: a repriced or reordered formula
shows up here.
"""

import json
import os

import jax.numpy as jnp
import pytest

from repro.core import autotune as at
from repro.core import collectives as co
from repro.core import cost_model as cm


def _golden(name):
    with open(os.path.join(os.path.dirname(__file__), name)) as f:
        return json.load(f)


GOLDEN = _golden("collective_plan_golden.json")
CM_GOLDEN = _golden("cost_model_golden.json")


def _custom(a, b):
    return a + b  # unknown to the engine, so treated as non-commutative


_OPS = {"add": jnp.add, "custom": _custom}


def _config(kw):
    kw = dict(kw)
    if isinstance(kw.get("group_size"), list):
        kw["group_size"] = tuple(kw["group_size"])
    return co.CollectiveConfig(**kw)


@pytest.fixture()
def cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    at.reset_cache()
    yield at.get_cache()
    at.reset_cache()


@pytest.mark.parametrize("case", sorted(GOLDEN["cases"]))
def test_plan_matches_golden(case, cache):
    name, state = case.split("/")
    if state == "cached":
        for p, nbytes, entry in GOLDEN["cache"]:
            cache.put(p, nbytes, "float32", "tpu_v5e_ici",
                      at.TuneResult(time_s=1e-4, **entry))
    cfg = _config(GOLDEN["configs"][name])
    for p, nbytes, op, want in GOLDEN["cases"][case]:
        if want == "raises":
            with pytest.raises(ValueError, match="commutative"):
                co.plan(cfg, p, nbytes, "float32", _OPS[op])
            continue
        got = co.plan(cfg, p, nbytes, "float32", _OPS[op])
        spec = got.group_size
        got = [got.algorithm, got.num_blocks,
               list(spec) if isinstance(spec, tuple) else spec, got.compress]
        assert got == want, (case, p, nbytes, op)


def test_golden_buckets_are_grad_syncs():
    """The golden grid's bucket payloads are the ones the grad_sync cell
    reduces: ``bucket_sizes`` over the stage's float32 parameter shapes."""
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from chipbench.drivers.allreduce import grad_spec
    from chipbench.harness import Benchmark
    from repro.configs.base import get_parallel

    bench = Benchmark()
    config = bench.config(bench.cell("minicpm_2b_dp4.grad_sync"))
    _, like = grad_spec(config)
    collective = get_parallel(config["repro_arch"]).collective
    sizes = co.bucket_sizes(like, collective.bucket_bytes)
    assert [n * dt.itemsize for n, dt in sizes] == GOLDEN["grad_sync_buckets"]


def _spec(spec):
    return tuple(spec) if isinstance(spec, list) else spec


@pytest.mark.parametrize("kind", ["optimal_blocks", "best_algorithm",
                                  "predicted_table"])
@pytest.mark.parametrize("model", ["TPU_V5E", "PAPER_HYDRA"])
def test_cost_model_matches_golden(model, kind):
    fabric, want = getattr(cm, model), CM_GOLDEN[model][kind]
    ps, sizes, specs = (CM_GOLDEN["p"], CM_GOLDEN["m_bytes"],
                        CM_GOLDEN["specs"])
    if kind == "predicted_table":
        got = [cm.predicted_table(p, sizes, fabric).tolist() for p in ps]
    elif kind == "best_algorithm":
        got = [cm.best_algorithm(p, float(m), fabric, group_size=_spec(s),
                                 intra_model=cm.TPU_V5E)
               for p in ps for m in sizes for s in specs]
    else:
        got = [[cm.optimal_blocks(p, float(m), fabric, a)
                for a in ("dptree", "sptree", "redbcast")]
               + [cm.optimal_blocks(p, float(m), fabric, "hier",
                                    group_size=_spec(s), compression=c)
                  for s in specs for c in (None, "bf16")]
               for p in ps for m in sizes]
    assert got == want
