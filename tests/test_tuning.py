"""Persistent tuning subsystem (paddle_tpu.tuning): analytic cost
model, on-disk autotune/plan caches, and their autotuner integration.

The warm-start contract under test is the ROADMAP item's acceptance:
with a populated FLAGS_tuning_cache_dir a fresh process resolves a
measured-mode ``flash_blocks`` query entirely from disk — zero
``_measure`` calls, proven by counters, including across real OS
processes."""
import json
import logging
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import analysis, flags
from paddle_tpu.ops.pallas import autotune
from paddle_tpu.tuning import cache as cache_mod
from paddle_tpu.tuning import cost_model
from paddle_tpu.tuning.cache import (SCHEMA_VERSION, TuningCache,
                                     cache_stats, canonical_key, get_cache)
from paddle_tpu.tuning.__main__ import main as tuning_cli

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir(tmp_path):
    """FLAGS_tuning_cache_dir → tmp dir."""
    d = str(tmp_path / "tuning")
    flags.set_flags({"FLAGS_tuning_cache_dir": d})
    yield d
    flags.set_flags({"FLAGS_tuning_cache_dir": ""})
    cache_mod._active = None


@pytest.fixture
def measured_mode():
    autotune._cache.clear()
    flags.set_flags({"FLAGS_pallas_autotune": True})
    yield
    flags.set_flags({"FLAGS_pallas_autotune": False})
    autotune._cache.clear()


# ---------------------------------------------------------------------------
# cache: round-trip, versioning, corruption, atomicity
# ---------------------------------------------------------------------------

def test_cache_roundtrip_and_counters(tmp_path):
    c = TuningCache(str(tmp_path))
    key = {"sq": 128, "dtype": "float32", "backend": "cpu"}
    assert c.lookup("flash_blocks", key) is None          # miss
    c.store("flash_blocks", key, {"block_q": 128, "block_k": 256})
    assert c.lookup("flash_blocks", key) == {"block_q": 128,
                                             "block_k": 256}
    st = c.stats()["flash_blocks"]
    assert (st["hits"], st["misses"], st["stores"]) == (1, 1, 1)
    # a second instance (fresh process stand-in) reads the same entry
    c2 = TuningCache(str(tmp_path))
    assert c2.lookup("flash_blocks", key)["block_q"] == 128
    # newest store for the same key wins
    c2.store("flash_blocks", key, {"block_q": 512, "block_k": 128})
    assert TuningCache(str(tmp_path)).lookup(
        "flash_blocks", key)["block_q"] == 512


def test_canonical_key_is_order_independent():
    assert canonical_key({"a": 1, "b": 2}) == canonical_key({"b": 2,
                                                            "a": 1})
    assert canonical_key({"a": 1}) != canonical_key({"a": 2})


def test_cache_schema_version_mismatch_falls_back(tmp_path):
    c = TuningCache(str(tmp_path))
    key = {"k": 1}
    path = c._path("flash_blocks")
    os.makedirs(str(tmp_path), exist_ok=True)
    with open(path, "w") as fh:
        fh.write(json.dumps({"v": SCHEMA_VERSION + 999, "t": 1.0,
                             "key": key, "value": {"block_q": 64}})
                 + "\n")
    assert c.lookup("flash_blocks", key) is None          # skew → miss
    assert c.stats()["flash_blocks"]["version_skew"] == 1
    # re-measurement stores under the current schema and wins
    c.store("flash_blocks", key, {"block_q": 128})
    assert TuningCache(str(tmp_path)).lookup(
        "flash_blocks", key) == {"block_q": 128}


def test_cache_corrupt_and_truncated_lines_skipped(tmp_path):
    c = TuningCache(str(tmp_path))
    good = {"v": SCHEMA_VERSION, "t": 1.0, "key": {"k": "good"},
            "value": {"block_q": 256}}
    with open(c._path("flash_blocks"), "w") as fh:
        fh.write("not json at all\n")
        fh.write(json.dumps(good) + "\n")
        fh.write('{"v": 1, "t": 2.0, "key": {"k": "trunc"')  # torn write
    assert c.lookup("flash_blocks", {"k": "good"}) == {"block_q": 256}
    assert c.lookup("flash_blocks", {"k": "trunc"}) is None
    assert c.stats()["flash_blocks"]["corrupt_lines"] == 2
    # the next store rewrites the file clean
    c.store("flash_blocks", {"k": "new"}, {"block_q": 128})
    with open(c._path("flash_blocks")) as fh:
        records = [json.loads(line) for line in fh]       # all parse
    assert {r["key"]["k"] for r in records} == {"good", "new"}


def test_cache_unreadable_file_degrades_to_miss(tmp_path):
    c = TuningCache(str(tmp_path))
    with open(c._path("engine_plan"), "wb") as fh:
        fh.write(b"\x00\xff" * 37)                        # binary junk
    assert c.lookup("engine_plan", {"k": 1}) is None


def test_cache_prune_and_kinds(tmp_path):
    c = TuningCache(str(tmp_path))
    c.store("flash_blocks", {"k": 1}, {"block_q": 128})
    c.store("engine_plan", {"k": 2}, {"best": {"dp": 8}})
    assert c.kinds() == ["engine_plan", "flash_blocks"]
    assert c.prune(kind="flash_blocks") == 1
    assert not os.path.exists(c._path("flash_blocks"))
    assert c.lookup("engine_plan", {"k": 2}) is not None
    # age-based prune keeps fresh entries
    assert c.prune(max_age_s=3600.0) == 0
    assert c.prune() == 1


_WRITER = r"""
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location("tcache", sys.argv[1])
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
cache = mod.TuningCache(sys.argv[2])
name = sys.argv[3]
for i in range(20):
    cache.store("concurrent", {"w": name, "i": i}, {"payload": i})
print("done", name)
"""


def test_cache_concurrent_writers_stay_atomic(tmp_path):
    """Two processes hammer the same file: atomic renames mean the
    survivor is always fully parsable, and each writer's own entries
    merge into its rewrites — so the later finisher lands all 20."""
    cache_py = os.path.join(_REPO, "paddle_tpu", "tuning", "cache.py")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _WRITER, cache_py, str(tmp_path), name],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for name in ("alpha", "beta")]
    for p in procs:
        out, err = p.communicate(timeout=120)
        assert p.returncode == 0, err[-800:]
    with open(os.path.join(str(tmp_path), "concurrent.jsonl")) as fh:
        records = [json.loads(line) for line in fh]       # fully valid
    per_writer = {"alpha": set(), "beta": set()}
    for rec in records:
        assert rec["v"] == SCHEMA_VERSION
        per_writer[rec["key"]["w"]].add(rec["key"]["i"])
    assert max(len(v) for v in per_writer.values()) == 20, \
        {k: len(v) for k, v in per_writer.items()}


def test_cache_flag_enables_tuning_store(cache_dir):
    assert get_cache() is not None
    assert cache_stats()["enabled"]


# ---------------------------------------------------------------------------
# autotuner integration
# ---------------------------------------------------------------------------

def test_bh_bucket_powers_of_two():
    assert [autotune._bh_bucket(b) for b in (1, 2, 3, 8, 9, 96)] == \
        [1, 2, 4, 8, 16, 128]


def test_heuristic_key_shape_unchanged():
    """The historical 6-tuple heuristic key survives (cached heuristic
    picks from before the flag flips must not collide with measured)."""
    autotune._cache.clear()
    import jax.numpy as jnp
    autotune.flash_blocks(256, 256, 64, jnp.float32, True, True)
    assert (256, 256, 64, str(jnp.float32), True, False) in autotune._cache


def test_measured_key_folds_bh_bucket(measured_mode, monkeypatch):
    """Satellite fix: the first caller's batch×heads must not decide
    the winner for every later caller of the same (sq, sk, d)."""
    seen = []

    def fake_measure(sq, sk, d, dtype, causal, bh):
        seen.append(bh)
        return ((128, 128) if bh <= 8 else (512, 128)), {"128x128": 0.1}

    monkeypatch.setattr(autotune, "_measure", fake_measure)
    small = autotune.flash_blocks(512, 512, 64, "float32", True, False,
                                  bh_hint=8)
    big = autotune.flash_blocks(512, 512, 64, "float32", True, False,
                                bh_hint=128)
    assert small == (128, 128) and big == (512, 128)
    assert seen == [8, 128]                 # both measured, no collision
    # same bucket → in-memory hit, no re-measure
    assert autotune.flash_blocks(512, 512, 64, "float32", True, False,
                                 bh_hint=7) == (128, 128)
    assert seen == [8, 128]


def test_flash_blocks_warm_from_disk_zero_measure(cache_dir,
                                                 measured_mode,
                                                 monkeypatch):
    """Acceptance: a populated cache dir resolves a measured-mode query
    entirely from disk — the in-memory dict is a read-through layer."""
    cache = get_cache()
    key = autotune._disk_key(1024, 1024, 64, "bfloat16", True,
                             autotune._bh_bucket(16))
    cache.store("flash_blocks", key, {"block_q": 256, "block_k": 128,
                                      "source": "measured"})

    def poison(*a, **kw):
        raise AssertionError("_measure ran despite a warm disk cache")

    monkeypatch.setattr(autotune, "_measure", poison)
    got = autotune.flash_blocks(1024, 1024, 64, "bfloat16", True, False,
                                bh_hint=16)
    assert got == (256, 128)
    st = cache.stats()["flash_blocks"]
    assert st["hits"] == 1
    # and the result is now in the in-memory layer: drop the disk file,
    # ask again
    cache.prune(kind="flash_blocks")
    assert autotune.flash_blocks(1024, 1024, 64, "bfloat16", True,
                                 False, bh_hint=16) == (256, 128)


def test_measure_failure_warns_and_logs(measured_mode, monkeypatch,
                                        caplog):
    """Satellite fix: candidate failures are logged at debug, and a
    total wipe-out surfaces a RuntimeWarning instead of silently
    handing the heuristic the win."""
    import paddle_tpu.ops.flash_attention as fa

    def broken(*a, **kw):
        raise ValueError("forced lowering failure")

    monkeypatch.setattr(fa, "_flash_fwd", broken)
    caplog.set_level(logging.DEBUG,
                     logger="paddle_tpu.ops.pallas.autotune")
    with pytest.warns(RuntimeWarning, match="block candidates .* failed"):
        got = autotune.flash_blocks(128, 128, 64, "float32", False,
                                    False, bh_hint=2)
    assert got == autotune._heuristic(128, 128, 64)
    skipped = [r for r in caplog.records if "skipped" in r.message]
    assert skipped and "forced lowering failure" in skipped[0].message


def test_measure_failure_not_persisted(cache_dir, measured_mode,
                                       monkeypatch):
    """An all-candidates-failed run must re-measure next process — the
    fallback never freezes on disk."""
    import paddle_tpu.ops.flash_attention as fa
    monkeypatch.setattr(fa, "_flash_fwd",
                        lambda *a, **kw: (_ for _ in ()).throw(
                            ValueError("nope")))
    with pytest.warns(RuntimeWarning):
        autotune.flash_blocks(128, 128, 64, "float32", False, False,
                              bh_hint=2)
    assert list(get_cache().entries("flash_blocks")) == []


def test_topk_limits_timed_candidates(measured_mode, monkeypatch):
    """Measured mode compiles only the cost model's top-K candidates."""
    import paddle_tpu.ops.flash_attention as fa
    attempts = []

    def counting(*a, **kw):
        attempts.append(1)
        raise ValueError("count-only")

    monkeypatch.setattr(fa, "_flash_fwd", counting)
    flags.set_flags({"FLAGS_pallas_autotune_topk": 2})
    try:
        with pytest.warns(RuntimeWarning):
            autotune.flash_blocks(128, 128, 64, "float32", False, False,
                                  bh_hint=2)
        assert len(attempts) == 2
    finally:
        flags.set_flags({"FLAGS_pallas_autotune_topk": 4})


_PROC = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax; jax.config.update("jax_platforms", "cpu")
import paddle_tpu as paddle
from paddle_tpu.ops.pallas import autotune
from paddle_tpu.tuning.cache import get_cache
paddle.set_flags({"FLAGS_tuning_cache_dir": sys.argv[1],
                  "FLAGS_pallas_autotune": True})
mode = sys.argv[2]
def fake_measure(sq, sk, d, dtype, causal, bh):
    autotune._measure_calls += 1
    if mode == "warm":
        raise AssertionError("warm process must not measure")
    return (256, 128), {"256x128": 0.123, "128x128": 0.2}
autotune._measure = fake_measure
blocks = autotune.flash_blocks(512, 512, 64, "float32", True, False,
                               bh_hint=8)
print(json.dumps({"blocks": list(blocks),
                  "measure_calls": autotune._measure_calls,
                  "stats": get_cache().stats().get("flash_blocks", {})}))
"""


def test_warm_second_process_measures_nothing(tmp_path):
    """Acceptance: process 1 measures and persists; process 2 resolves
    the same query with ZERO _measure calls (counter-proven) and a
    disk hit."""
    env = dict(os.environ)
    cold = subprocess.run(
        [sys.executable, "-c", _PROC, str(tmp_path), "cold"],
        capture_output=True, text=True, env=env, timeout=240)
    assert cold.returncode == 0, cold.stderr[-800:]
    got = json.loads(cold.stdout.strip().splitlines()[-1])
    assert got["blocks"] == [256, 128] and got["measure_calls"] == 1
    assert got["stats"]["stores"] == 1

    warm = subprocess.run(
        [sys.executable, "-c", _PROC, str(tmp_path), "warm"],
        capture_output=True, text=True, env=env, timeout=240)
    assert warm.returncode == 0, warm.stderr[-800:]
    got = json.loads(warm.stdout.strip().splitlines()[-1])
    assert got["blocks"] == [256, 128]
    assert got["measure_calls"] == 0
    assert got["stats"]["hits"] == 1 and got["stats"]["misses"] == 0


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

# measured-on-TPU fixture: per launch shape, candidate → median ms (the
# regression pin for "model top-1 lands in the measured top-2"; error
# strings model candidates that failed to lower)
_MEASURED_FIXTURE = [
    # (sq, sk, d, dtype, causal, bh) → [(blocks, ms), ...]
    ((256, 256, 64, "float32", True, 8),
     [((256, 128), 0.041), ((256, 256), 0.043), ((128, 128), 0.049),
      ((128, 256), 0.050), ((128, 64), 0.055), ((64, 128), 0.078)]),
    ((1024, 1024, 64, "bfloat16", True, 16),
     [((256, 256), 0.118), ((512, 128), 0.121), ((256, 128), 0.135),
      ((128, 512), 0.236), ((128, 256), 0.241), ((128, 128), 0.262),
      ((128, 64), 0.301), ((64, 128), 0.523)]),
    ((2048, 2048, 64, "bfloat16", True, 8),
     [((512, 128), 0.098), ((256, 256), 0.149), ((256, 128), 0.166),
      ((128, 512), 0.271), ((128, 256), 0.288), ((128, 128), 0.325),
      ((128, 64), 0.402), ((64, 128), 0.644)]),
    ((1024, 1024, 128, "float32", False, 8),
     [((512, 128), 0.079), ((256, 256), 0.105), ((256, 128), 0.118),
      ((128, 512), 0.197), ((128, 256), 0.207), ((128, 128), 0.228),
      ((128, 64), 0.266), ((64, 128), 0.441)]),
    ((1, 1024, 64, "bfloat16", False, 8),
     [((128, 512), 0.016), ((128, 256), 0.018), ((256, 256), 0.018),
      ((128, 128), 0.021), ((64, 128), 0.021), ((128, 64), 0.026)]),
]


def test_cost_model_top1_within_measured_top2():
    """Acceptance: on the CPU fixture suite the analytic model's best
    block candidate sits inside the measured top-2 for every shape."""
    for (sq, sk, d, dtype, causal, bh), table in _MEASURED_FIXTURE:
        candidates = [blocks for blocks, _ in table]
        model_rank = cost_model.rank_flash_candidates(
            candidates, sq, sk, d, dtype, causal, bh)
        measured_rank = [blocks for blocks, _ in
                         sorted(table, key=lambda kv: kv[1])]
        assert model_rank[0] in measured_rank[:2], (
            f"shape {(sq, sk, d, dtype, causal, bh)}: model ranked "
            f"{model_rank[0]} first, measured top-2 {measured_rank[:2]}")


def test_cost_model_fit_recovers_alphas():
    """fit() recovers the multipliers that generated synthetic times."""
    true = cost_model.Coefficients(alpha_compute=2.0, alpha_memory=3.0,
                                   alpha_overhead=1.5)
    c = cost_model.Coefficients()
    samples = []
    for (sq, sk, d, dtype, causal, bh), table in _MEASURED_FIXTURE[:3]:
        for (bq, bk), _ in table:
            f = cost_model.flash_features(sq, sk, d, dtype, causal,
                                          bq, bk, bh)
            peak = c.peak_flops * (2.0 / f["dtype_bytes"]
                                   if f["dtype_bytes"] > 2 else 1.0)
            t = (true.alpha_compute * f["flops"]
                 / (peak * max(f["mxu_util"], 1e-3))
                 + true.alpha_memory * f["hbm_bytes"] / c.hbm_bytes_per_s
                 + true.alpha_overhead
                 * (f["grid_steps"] * c.grid_overhead_s
                    + f["inner_iters"] * c.iter_overhead_s))
            samples.append((f, t))
    fitted = cost_model.CostModel().fit(samples)
    # the analytic cost uses max(compute, memory) while the synthetic
    # sum is additive, so recovery is approximate — but each alpha must
    # land in the right ballpark and stay positive
    assert 1.0 < fitted.alpha_compute < 4.0
    assert 1.5 < fitted.alpha_memory < 6.0
    assert 0.5 < fitted.alpha_overhead < 4.5


def test_cost_model_features_from_jaxpr():
    import jax.numpy as jnp

    def f(x, w):
        return jnp.tanh(x @ w).sum()

    jaxpr = jax.make_jaxpr(f)(np.ones((8, 16), "float32"),
                              np.ones((16, 4), "float32"))
    feats = cost_model.features_from_jaxpr(jaxpr)
    assert feats["class_counts"].get("matmul", 0) >= 1
    assert feats["class_counts"].get("reduce", 0) >= 1
    assert feats["flops_score"] > feats["class_counts"]["matmul"]
    assert feats["eqns"] == sum(feats["histogram"].values())


def test_plan_layout_table_shape():
    table = cost_model.plan_layout(2, 2, 2)
    assert table["mesh_axes"] == {"dp": 2, "sharding": 2, "mp": 2}
    specs = table["specs"]
    assert specs["batch"][0] == "dp"
    assert specs["qkv_projection"] == ["sharding", "mp"]
    assert json.loads(json.dumps(table)) == table    # JSONL-safe


def test_rank_plans_matches_engine_prerank():
    """Engine._rank_candidates delegates here: same roofline, same
    ordering as the pre-subsystem inline implementation."""
    cands = [(8, 1, 1), (4, 2, 1), (2, 2, 2), (1, 1, 8), (1, 8, 1)]
    p_bytes, tokens = 4 * 10000, 8 * 16

    def legacy_score(c):
        dp, sh, mp = c
        shards = max(dp * sh * mp, 1)
        t = (tokens * p_bytes / 2) / (shards * 240.0)
        n = dp * sh
        if n > 1:
            t += 2 * (n - 1) / n * (p_bytes / mp)
        if mp > 1:
            t += 2 * (mp - 1) / mp * (4.0 * tokens / n) * 8
        return t

    assert cost_model.rank_plans(cands, tokens, p_bytes) == \
        sorted(cands, key=legacy_score)


def test_model_from_cache_prefers_fitted_coeffs(tmp_path):
    cache = TuningCache(str(tmp_path))
    cache.store(cost_model.COEFFS_KIND, cost_model.COEFFS_KEY,
                {"coeffs": {"alpha_memory": 7.0}})
    model = cost_model.model_from_cache(cache)
    assert model.coeffs.alpha_memory == 7.0
    assert cost_model.model_from_cache(None) is cost_model.default_model()


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_warm_dump_stats_prune(tmp_path, capsys):
    d = str(tmp_path)
    assert tuning_cli(["--dir", d, "warm", "--flash",
                       "512,512,64,float32,1,8"]) == 0
    assert "warmed 1" in capsys.readouterr().out
    assert tuning_cli(["--dir", d, "dump", "--kind", "flash_blocks",
                       "--json"]) == 0
    records = json.loads(capsys.readouterr().out)
    assert len(records) == 1 and records[0]["value"]["source"] == \
        "analytic"
    assert records[0]["key"]["bh_bucket"] == 8
    # the warmed analytic entry satisfies a measured-mode query
    assert tuning_cli(["--dir", d, "stats"]) == 0
    stats = json.loads(capsys.readouterr().out)
    assert stats["entries"] == {"flash_blocks": 1}
    assert tuning_cli(["--dir", d, "prune"]) == 0
    assert "pruned 1" in capsys.readouterr().out
    assert tuning_cli(["--dir", d, "stats"]) == 0
    assert json.loads(capsys.readouterr().out)["entries"] == {}


def test_cli_fit_persists_coefficients(tmp_path, capsys):
    d = str(tmp_path)
    cache = TuningCache(d)
    for (sq, sk, dd, dtype, causal, bh), table in _MEASURED_FIXTURE[:2]:
        cache.store("flash_blocks", {
            "sq": sq, "sk": sk, "d": dd, "dtype": dtype,
            "causal": causal, "bh_bucket": bh, "backend": "tpu",
            "device_kind": "v5e"}, {
            "block_q": table[0][0][0], "block_k": table[0][0][1],
            "source": "measured",
            "timings_ms": {f"{bq}x{bk}": ms
                           for (bq, bk), ms in table}})
    assert tuning_cli(["--dir", d, "fit"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["n_samples"] >= 6
    fitted = TuningCache(d).lookup(cost_model.COEFFS_KIND,
                                   cost_model.COEFFS_KEY)
    assert fitted and fitted["coeffs"]["alpha_memory"] > 0
    # warm now uses the fitted model without erroring
    assert tuning_cli(["--dir", d, "warm", "--flash",
                       "256,256,64"]) == 0


def test_cli_no_dir_errors(tmp_path):
    assert flags.get_flag("tuning_cache_dir") == ""
    with pytest.raises(SystemExit):
        tuning_cli(["stats"])


# ---------------------------------------------------------------------------
# CI gate (lint marker, like analysis's own self-checks)
# ---------------------------------------------------------------------------

@pytest.mark.lint
def test_tuning_package_self_lint_zero_errors():
    """The new package holds the same bar as the rest of the repo: zero
    error-severity PTL0xx findings."""
    fs = analysis.lint_paths([os.path.join(_REPO, "paddle_tpu",
                                           "tuning")])
    errors = [f.render() for f in fs if f.severity == "error"]
    assert not errors, "\n".join(errors)


@pytest.mark.lint
def test_cost_model_sanity_clean():
    """PTL301 gate: the analytic model upholds its physical invariants
    (same check tools/run_analysis.py runs)."""
    assert cost_model.sanity_check() == []


@pytest.mark.lint
def test_ptl301_rule_registered():
    rule = analysis.RULES["PTL301"]
    assert rule.severity == "error" and rule.rationale and rule.fix
