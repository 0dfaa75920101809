"""Autotuner: candidate generation, cache round-trip, auto-method override."""

import json
import os

import pytest

from repro.core import autotune as at
from repro.core import cost_model as cm


@pytest.fixture()
def tmp_cache(tmp_path, monkeypatch):
    path = str(tmp_path / "autotune.json")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
    at.reset_cache()
    yield path
    at.reset_cache()


def test_candidate_settings_cover_all_algorithms():
    cands = at.candidate_settings(64, 1 << 20, cm.TPU_V5E)
    algos = {a for a, _ in cands}
    assert algos == {"dptree", "sptree", "redbcast", "ring"}
    # sweep probes around the analytic optimum: the optimum itself is present
    b0 = cm.optimal_blocks(64, float(1 << 20), cm.TPU_V5E, "dptree")
    assert ("dptree", b0) in cands
    # and all candidates are valid block counts
    assert all(b >= 1 for _, b in cands)
    assert len(cands) == len(set(cands))


def test_tune_picks_fastest_and_persists(tmp_cache):
    fake = {("dptree", "any"): 5.0, ("sptree", "any"): 7.0,
            ("redbcast", "any"): 9.0, ("ring", "any"): 3.0}

    def runner(algo, b):
        return fake[(algo, "any")] + 0.001 * b

    res = at.tune(runner, p=8, nbytes=4096, dtype="float32",
                  topology="cpu8", model=cm.TPU_V5E)
    assert res.algorithm == "ring"
    assert os.path.exists(tmp_cache)
    doc = json.load(open(tmp_cache))
    assert doc["schema"] == at.AutotuneCache.SCHEMA
    assert len(doc["entries"]) == 1


def test_cache_roundtrip_write_reload_hit(tmp_cache):
    cache = at.AutotuneCache(tmp_cache)
    cache.put(8, 4096, "float32", "cpu8", at.TuneResult("dptree", 7, 1.5e-4))
    cache.put(8, 65536, "float32", "cpu8", at.TuneResult("ring", 1, 9e-4))
    cache.save()

    fresh = at.AutotuneCache(tmp_cache).load()
    hit = fresh.get(8, 4096, "float32", "cpu8")
    assert hit == at.TuneResult("dptree", 7, 1.5e-4)
    assert fresh.get(8, 65536, "float32", "cpu8").algorithm == "ring"
    # miss on every key component
    assert fresh.get(16, 4096, "float32", "cpu8") is None
    assert fresh.get(8, 4096, "bfloat16", "cpu8") is None
    assert fresh.get(8, 4096, "float32", "tpu_v5e_ici") is None
    # module-level lookup reads the same file via REPRO_AUTOTUNE_CACHE
    assert at.lookup(8, 4096, "float32", "cpu8") == hit
    assert at.lookup(16, 4096, "float32", "cpu8") is None


def test_corrupt_cache_file_starts_empty(tmp_cache):
    with open(tmp_cache, "w") as f:
        f.write("{not json")
    cache = at.AutotuneCache(tmp_cache).load()
    assert len(cache) == 0
    assert cache.get(8, 4096, "float32", "cpu8") is None


def test_runner_failures_are_skipped(tmp_cache):
    def runner(algo, b):
        if algo != "sptree":
            raise RuntimeError("unavailable")
        return 1.0 + b * 1e-3

    res = at.tune(runner, 8, 4096, "float32", "cpu8", cm.TPU_V5E)
    assert res.algorithm == "sptree"


def test_skipped_candidates_are_reported(tmp_cache, capsys):
    """A candidate the backend refuses is printed and listed in the result,
    never silently dropped from the race."""
    def runner(algo, b):
        if algo == "dptree":
            raise RuntimeError("kernel refused")
        return 1.0 + b * 1e-3

    cands = at.candidate_settings(8, 4096, cm.TPU_V5E)
    res = at.tune(runner, 8, 4096, "float32", "cpu8", cm.TPU_V5E)
    want = [(a, b) for a, b in cands if a == "dptree"]
    assert want and [(a, b) for a, b, _ in res.skipped] == want
    assert all("kernel refused" in err for _, _, err in res.skipped)
    out = capsys.readouterr().out
    assert all(f"skipped dptree b={b}" in out for _, b in want)
    # the skip list is run-local: the persisted entry carries none of it
    assert at.AutotuneCache(tmp_cache).load().get(
        8, 4096, "float32", "cpu8").skipped == ()


def test_all_candidates_failing_raises(tmp_cache):
    def all_fail(algo, b):
        raise RuntimeError("nope")

    with pytest.raises(RuntimeError, match="every candidate failed"):
        at.tune(all_fail, 8, 4096, "float32", "cpu8", cm.TPU_V5E)


def test_auto_method_uses_measured_hit(tmp_cache):
    """CollectiveConfig(method='auto') consults the cache at trace time."""
    from repro.core import collectives as co

    p, nbytes = 8, 1000 * 4
    cfg = co.CollectiveConfig(method="auto")
    pl0 = co.plan(cfg, p, nbytes, "float32")
    # no cache entry yet: analytic pick
    assert pl0.algorithm == cm.best_algorithm(p, float(nbytes),
                                              cfg.comm_model)
    model2 = co.plan(cfg, p, nbytes * 2, "float32")
    at.get_cache().put(p, nbytes, "float32", cfg.comm_model.name,
                       at.TuneResult("sptree", 11, 3.3e-5))
    pl = co.plan(cfg, p, nbytes, "float32")
    assert (pl.algorithm, pl.num_blocks) == ("sptree", 11)
    # other sizes still fall through to the model
    assert co.plan(cfg, p, nbytes * 2, "float32") == model2
    assert model2.algorithm in cm.FLAT_ALGORITHMS


def test_auto_degrades_on_stale_or_infeasible_hit(tmp_cache):
    """'auto' must never raise on a foreign cache entry: an infeasible 'hier'
    winner (group shape that doesn't divide p) or a malformed 'auto' entry
    falls through to the analytic switch."""
    from repro.core import collectives as co

    p, nbytes = 8, 2048
    cfg = co.CollectiveConfig(method="auto")
    model = co.plan(cfg, p, nbytes, "float32")      # empty cache
    assert model.algorithm != "hier"
    # hier measured with a group shape that can't run at p=8
    at.get_cache().put(p, nbytes, "float32", cfg.comm_model.name,
                       at.TuneResult("hier", 4, 1e-5, group_size=5))
    assert co.plan(cfg, p, nbytes, "float32") == model
    # malformed entry naming 'auto' itself
    at.get_cache().put(p, nbytes, "float32", cfg.comm_model.name,
                       at.TuneResult("auto", 1, 1e-5))
    assert co.plan(cfg, p, nbytes, "float32") == model
    # feasible hier hit replays ITS measured group size (as a level spec)
    at.get_cache().put(p, nbytes, "float32", cfg.comm_model.name,
                       at.TuneResult("hier", 2, 1e-5, group_size=2))
    assert co.plan(cfg, p, nbytes, "float32") == co.Plan("hier", 2, (2,),
                                                         False)
    # N-level hit replays its measured level tuple
    at.get_cache().put(p, nbytes, "float32", cfg.comm_model.name,
                       at.TuneResult("hier", 2, 1e-5, group_size=(2, 2)))
    assert co.plan(cfg, p, nbytes, "float32") == co.Plan("hier", 2, (2, 2),
                                                         False)


def test_compressed_hit_needs_local_opt_in(tmp_cache):
    """A hier entry timed with the bf16 inter-group wire replays compressed
    ONLY for configs that set compress_inter_group — the lossy wire is never
    applied on the strength of someone else's cache entry."""
    from repro.core import collectives as co

    p, nbytes = 8, 2048
    at.get_cache().put(p, nbytes, "float32", cm.TPU_V5E.name,
                       at.TuneResult("hier", 3, 1e-5, group_size=(2, 2),
                                     compressed=True))
    plain = co.CollectiveConfig(method="auto")
    pl = co.plan(plain, p, nbytes, "float32")
    assert pl.algorithm != "hier" and not pl.compress  # the model's pick
    opted = co.CollectiveConfig(method="auto", compress_inter_group=True)
    assert co.plan(opted, p, nbytes, "float32") == co.Plan("hier", 3, (2, 2),
                                                           True)


def test_compressed_candidates_and_tune_roundtrip(tmp_cache):
    """compress_inter_group doubles the hier candidates with '+bf16' twins;
    a compressed winner round-trips through the JSON cache with its level
    tuple and compressed flag intact."""
    cands = at.candidate_settings(16, 1 << 20, cm.TPU_V5E_INTERPOD,
                                  algorithms=("dptree", "hier"),
                                  group_size=(2, 2),
                                  compress_inter_group=True)
    algos = {a for a, _ in cands}
    assert "hier" in algos and "hier" + at.COMPRESSED_SUFFIX in algos
    # without the opt-in, no compressed candidates appear
    cands0 = at.candidate_settings(16, 1 << 20, cm.TPU_V5E_INTERPOD,
                                   algorithms=("dptree", "hier"),
                                   group_size=(2, 2))
    assert all(not a.endswith(at.COMPRESSED_SUFFIX) for a, _ in cands0)

    def runner(algo, b):  # compressed hier wins
        return 1.0 if algo == "hier" + at.COMPRESSED_SUFFIX else 2.0

    res = at.tune(runner, 16, 1 << 20, "float32", "cpu16",
                  cm.TPU_V5E_INTERPOD, algorithms=("dptree", "hier"),
                  group_size=(2, 2), compress_inter_group=True)
    assert res.algorithm == "hier" and res.compressed
    assert res.group_size == (2, 2)
    hit = at.AutotuneCache(at.get_cache().path).load().get(
        16, 1 << 20, "float32", "cpu16")
    assert hit == res


def test_hier_rejects_non_commutative_op(tmp_cache):
    """Explicit method='hier' with an unknown (possibly non-commutative) op
    raises instead of silently reducing in ring order."""
    import jax.numpy as jnp
    import pytest as _pytest
    from repro.core import collectives as co

    def custom(a, b):
        return a + b  # unknown to the engine, treated as non-commutative

    cfg = co.CollectiveConfig(method="hier", group_size=4)
    with _pytest.raises(ValueError, match="commutative"):
        co.all_reduce(jnp.ones((16,)), "data", 8, cfg, op=custom)


def test_degrade_for_op_gating(tmp_cache, monkeypatch):
    """Under 'auto', every pick that cannot run the operator falls back to
    the rank-ordered dptree; explicit requests keep/raise their contracts."""
    import jax.numpy as jnp
    import pytest as _pytest
    from repro.core import collectives as co

    def custom(a, b):
        return a + b

    p, nbytes = 8, 2048
    auto = co.CollectiveConfig(method="auto", group_size=2)

    def algo(config, op, algorithm=None):
        # an autotune hit makes 'auto' pick ``algorithm`` before the degrade
        if algorithm is not None:
            at.get_cache().put(p, nbytes, "float32", config.comm_model.name,
                               at.TuneResult(algorithm, 3, 1e-5,
                                             group_size=2))
        return co.plan(config, p, nbytes, "float32", op).algorithm

    # auto: degrade, never raise
    assert algo(auto, custom, "ring") == "dptree"
    assert algo(auto, custom, "hier") == "dptree"
    # psum is never an auto pick: the cost model does not price it, and a
    # cache entry naming it is a miss
    at.get_cache().put(p, nbytes, "float32", auto.comm_model.name,
                       at.TuneResult("psum", 3, 1e-5))
    assert at.lookup(p, nbytes, "float32", auto.comm_model.name) is None
    # ... and a psum pick that reached 'auto' anyway degrades on an op XLA
    # has no primitive for
    with monkeypatch.context() as m:
        m.setattr(co.autotune, "lookup",
                  lambda *a, **k: at.TuneResult("psum", 3, 1e-5))
        assert algo(auto, custom) == "dptree"
        assert algo(auto, jnp.multiply) == "dptree"
        assert algo(auto, jnp.minimum) == "psum"
    # supported combinations pass through untouched
    assert algo(auto, jnp.maximum, "ring") == "ring"
    assert algo(auto, jnp.add, "hier") == "hier"
    assert algo(co.CollectiveConfig(method="psum"), jnp.minimum) == "psum"
    assert algo(co.CollectiveConfig(method="dptree"), custom) == "dptree"
    # explicit hier with an unknown op is a loud error
    with _pytest.raises(ValueError, match="commutative"):
        algo(co.CollectiveConfig(method="hier"), custom)
    # explicit ring keeps its documented (commutative-ops) behavior
    assert algo(co.CollectiveConfig(method="ring"), custom) == "ring"


def test_lookup_respects_disable_env(tmp_cache, monkeypatch):
    at.get_cache().put(8, 4096, "float32", "cpu8",
                       at.TuneResult("ring", 1, 1e-4))
    assert at.lookup(8, 4096, "float32", "cpu8") is not None
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    assert at.lookup(8, 4096, "float32", "cpu8") is None
