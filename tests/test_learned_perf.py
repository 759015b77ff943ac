"""Telemetry-fed learned performance model (paddle_tpu.tuning.learned):
head fit/round-trip, versioned persistence, cold-cache flash/plan
prediction with zero timing runs, predicted-cost serving admission,
model-divergence watchdog + perf_regression events, the
`fit --from-events` CLI, event-log self-health metrics, and the PTL302
fixture gate."""
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.flags import get_flags, set_flags
from paddle_tpu.tuning import learned
from paddle_tpu.tuning.learned import (LearnedPerfModel, _Head,
                                       _fixture_corpus,
                                       plan_feature_dict)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def flags_guard():
    keep = get_flags(["FLAGS_tuning_cache_dir", "FLAGS_pallas_autotune",
                      "FLAGS_learned_perf_model",
                      "FLAGS_observability_dir",
                      "FLAGS_serving_predicted_admission"])
    yield
    set_flags(keep)


def _flash_model() -> LearnedPerfModel:
    return LearnedPerfModel({"flash": _Head.fit("flash",
                                                _fixture_corpus())})


def _batch_step_samples(scale=0.001):
    out = []
    for b in range(1, 17):
        feats = {"batch": float(b), "prefill_seqs": 1.0,
                 "decode_seqs": float(b - 1), "q_width": 8.0,
                 "tokens": float(8 + b), "queue_depth": 0.0,
                 "page_occupancy": 0.2}
        out.append((feats, scale * (8 + b)))
    return out


def _batch_step_model(version=1) -> LearnedPerfModel:
    return LearnedPerfModel(
        {"batch_step": _Head.fit("batch_step", _batch_step_samples())},
        version=version)


def _batch_step_record(b, scale=1.0, run="r1"):
    return {"kind": "batch_step", "run": run, "batch": b,
            "prefill_seqs": 1, "decode_seqs": b - 1, "q_width": 8,
            "tokens": 8 + b, "queue_depth": 0, "page_occupancy": 0.2,
            "step_s": 0.001 * (8 + b) * scale}


# ---------------------------------------------------------------------------
# model core
# ---------------------------------------------------------------------------

def test_head_fit_beats_analytic_and_roundtrips():
    head = _Head.fit("flash", _fixture_corpus())
    st = head.stats
    assert st["improved"] and not st["in_sample"]
    assert st["holdout_male"] < 0.5 * st["baseline_male"]
    model = LearnedPerfModel({"flash": head}, version=7)
    clone = LearnedPerfModel.from_dict(
        json.loads(json.dumps(model.to_dict())))
    assert clone.version == 7
    f = _fixture_corpus()[3][0]
    assert clone.predict("flash", f) == \
        pytest.approx(model.predict("flash", f), rel=1e-12)
    # unknown family / malformed features degrade to None, never raise
    assert model.predict("plan", {}) is None
    assert model.predict("flash", {"flops": "junk"}) is None


def test_save_load_versioning_and_corruption(tmp_path):
    d = str(tmp_path)
    m = _flash_model()
    learned.save_model(m, d)
    assert learned.load_model(d).version == 1
    learned.save_model(_flash_model(), d)
    assert learned.load_model(d).version == 2  # monotonic bump
    with open(learned.model_path(d), "w") as fh:
        fh.write("{not json")
    assert learned.load_model(d) is None       # corrupt -> analytic
    assert learned.load_model(str(tmp_path / "nope")) is None


def test_save_emits_perf_model_event(tmp_path, flags_guard):
    from paddle_tpu.observability import events
    obs = tmp_path / "obs"
    set_flags({"FLAGS_observability_dir": str(obs)})
    learned.save_model(_flash_model(), str(tmp_path / "cache"))
    set_flags({"FLAGS_observability_dir": ""})
    recs = events.read_events(str(obs), kinds=["perf_model"])
    assert recs and recs[0]["action"] == "save"
    assert recs[0]["heads"] == ["flash"]
    assert recs[0]["version"] == 1


# ---------------------------------------------------------------------------
# consumer 1a: flash_blocks cold-cache prediction
# ---------------------------------------------------------------------------

def test_flash_blocks_cold_prediction_zero_measure(tmp_path,
                                                   flags_guard,
                                                   monkeypatch):
    """A shape nobody ever measured resolves from the learned model
    with ZERO timing runs; with no model file the same call falls back
    to measurement (which ranks via the analytic CostModel)."""
    from paddle_tpu.ops.pallas import autotune
    from paddle_tpu.tuning.cache import get_cache
    learned.save_model(_flash_model(), str(tmp_path))
    set_flags({"FLAGS_tuning_cache_dir": str(tmp_path),
               "FLAGS_pallas_autotune": True,
               "FLAGS_learned_perf_model": True})
    monkeypatch.setattr(autotune, "_cache", {})
    before = autotune._measure_calls
    blocks = autotune.flash_blocks(8192, 8192, 64, "bfloat16", True,
                                   False, 8)
    assert autotune._measure_calls == before       # zero timing runs
    assert blocks in autotune._CANDIDATES
    rec = next(r for r in get_cache().entries("flash_blocks")
               if r["key"]["sq"] == 8192)
    assert rec["value"]["source"] == "learned"
    assert rec["value"]["model_version"] == 1
    assert "timings_ms" not in rec["value"]  # never mistaken for data

    # warm second call: disk hit, model not even consulted
    monkeypatch.setattr(autotune, "_cache", {})
    monkeypatch.setattr(learned, "load_model",
                        lambda *a, **k: pytest.fail("model consulted "
                                                    "on a disk hit"))
    assert autotune.flash_blocks(8192, 8192, 64, "bfloat16", True,
                                 False, 8) == blocks


def test_flash_blocks_falls_back_to_measurement(tmp_path, flags_guard,
                                                monkeypatch):
    from paddle_tpu.ops.pallas import autotune
    set_flags({"FLAGS_tuning_cache_dir": str(tmp_path),
               "FLAGS_pallas_autotune": True,
               "FLAGS_learned_perf_model": True})
    monkeypatch.setattr(autotune, "_cache", {})
    called = []

    def fake_measure(sq, sk, d, dtype, causal, bh):
        called.append((sq, sk))
        return (128, 128), {"128x128": 1.0}

    monkeypatch.setattr(autotune, "_measure", fake_measure)
    # no perf_model.json in the cache dir -> measurement path
    assert autotune.flash_blocks(8192, 8192, 64, "bfloat16", True,
                                 False, 8) == (128, 128)
    assert called == [(8192, 8192)]

    # flag off forces measurement even with a model present
    learned.save_model(_flash_model(), str(tmp_path))
    set_flags({"FLAGS_learned_perf_model": False})
    monkeypatch.setattr(autotune, "_cache", {})
    autotune.flash_blocks(4096, 8192, 64, "bfloat16", True, False, 8)
    assert called[-1] == (4096, 8192)


# ---------------------------------------------------------------------------
# consumer 1b: Engine.tune plan prediction
# ---------------------------------------------------------------------------

def _plan_model() -> LearnedPerfModel:
    cands = [(8, 1, 1), (4, 2, 1), (2, 2, 2), (2, 4, 1), (1, 2, 4),
             (1, 1, 8)]
    samples = []
    for bt in (128, 1024, 8192):
        for c in cands:
            f = plan_feature_dict(c, bt, 1 << 20)
            samples.append((f, 1e-9 * f["analytic_s"] * 2.0))
    return LearnedPerfModel({"plan": _Head.fit("plan", samples)})


def test_engine_tune_predicts_plan_with_zero_trials(tmp_path,
                                                    flags_guard):
    """On a plan-cache miss with a trained plan head, tune() installs
    the predicted winner without building a single trial step."""
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed.auto_parallel.engine import Engine
    from paddle_tpu.distributed.auto_parallel.strategy import Strategy
    from paddle_tpu.distributed.mesh import get_mesh, reset_mesh
    from paddle_tpu import nn
    from paddle_tpu.tuning import cache as tcache_mod
    reset_mesh()
    learned.save_model(_plan_model(), str(tmp_path))
    set_flags({"FLAGS_tuning_cache_dir": str(tmp_path),
               "FLAGS_learned_perf_model": True})
    tcache_mod._active = None
    paddle.seed(0)
    model = nn.Linear(16, 8)
    o = opt.SGD(learning_rate=0.1, parameters=model.parameters())
    eng = Engine(model, loss=lambda out, y: ((out - y) ** 2).mean(),
                 optimizer=o, strategy=Strategy())
    rs = np.random.RandomState(0)
    x = rs.randn(8, 16).astype(np.float32)
    y = rs.randn(8, 8).astype(np.float32)

    ts_mod = sys.modules["paddle_tpu.jit.train_step"]
    orig_ts = ts_mod.TrainStep

    def _poisoned(*a, **kw):
        raise AssertionError("trial step built despite a trained "
                             "plan head")

    ts_mod.TrainStep = _poisoned
    try:
        got = eng.tune(x, y, candidates=[(8, 1, 1), (2, 2, 2),
                                         (1, 1, 8)])
    finally:
        ts_mod.TrainStep = orig_ts
        reset_mesh()
    assert got["predicted"] is True
    assert all(r["source"] == "learned" and "predicted_s" in r
               for r in got["report"])
    assert "compile_plus_step_s" not in json.dumps(got["report"])
    # the prediction persisted: an identical search is now a cache hit
    entry = next(tcache_mod.get_cache().entries("engine_plan"))
    assert entry["value"]["source"] == "learned"
    assert (entry["value"]["best"]["dp"], entry["value"]["best"]["mp"]) \
        == (got["dp"], got["mp"])


def test_engine_tune_measurement_records_training_scale(tmp_path,
                                                        flags_guard):
    """The measured path stores batch_tokens/param_bytes so its report
    rows become plan-head training samples."""
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed.auto_parallel.engine import Engine
    from paddle_tpu.distributed.auto_parallel.strategy import Strategy
    from paddle_tpu.distributed.mesh import reset_mesh
    from paddle_tpu import nn
    from paddle_tpu.tuning import cache as tcache_mod
    reset_mesh()
    set_flags({"FLAGS_tuning_cache_dir": str(tmp_path),
               "FLAGS_learned_perf_model": True})   # no model file yet
    tcache_mod._active = None
    paddle.seed(0)
    model = nn.Linear(16, 8)
    o = opt.SGD(learning_rate=0.1, parameters=model.parameters())
    eng = Engine(model, loss=lambda out, y: ((out - y) ** 2).mean(),
                 optimizer=o, strategy=Strategy())
    rs = np.random.RandomState(0)
    x = rs.randn(8, 16).astype(np.float32)
    y = rs.randn(8, 8).astype(np.float32)
    try:
        eng.tune(x, y, candidates=[(8, 1, 1), (2, 2, 2)])
    finally:
        reset_mesh()
    samples = learned.plan_samples_from_cache(tcache_mod.get_cache())
    assert len(samples) == 2
    feats, secs = samples[0]
    assert feats["batch_tokens"] == x.size and secs > 0
    assert "analytic_s" in feats


# ---------------------------------------------------------------------------
# consumer 2: predicted-cost serving admission
# ---------------------------------------------------------------------------

class _FakeBatchModel:
    version = 1

    def __init__(self, per_token_s=0.01):
        self.per_token_s = per_token_s

    def has(self, family):
        return family == "batch_step"

    def predict(self, family, feats):
        return self.per_token_s * feats["tokens"]


def test_scheduler_admission_respects_cost_budget():
    from paddle_tpu.serving.scheduler import (PagePool, Request,
                                              Scheduler)
    pool = PagePool(64, 4)
    sched = Scheduler(pool, max_batch=8, max_pages_per_seq=8,
                      perf_model=_FakeBatchModel(),
                      max_step_cost_s=0.25)
    for _ in range(5):
        sched.submit(Request([1] * 10, max_new_tokens=2))
    plan, admitted, _ = sched.plan_step()
    # 10 tokens -> 0.1s, 20 -> 0.2s, 30 -> 0.3s > budget: 2 admit
    assert len(admitted) == 2 and plan is not None
    assert sched.deferred_admissions >= 1
    assert [round(s.predicted_cost_s, 3) for s in admitted] == \
        [0.1, 0.2]
    assert sched.queue_depth() == 3


def test_scheduler_admission_budget_never_starves():
    from paddle_tpu.serving.scheduler import (PagePool, Request,
                                              Scheduler)
    pool = PagePool(64, 4)
    sched = Scheduler(pool, max_batch=8, max_pages_per_seq=8,
                      perf_model=_FakeBatchModel(per_token_s=1.0),
                      max_step_cost_s=0.001)   # everything over budget
    sched.submit(Request([1] * 10, max_new_tokens=2))
    _, admitted, _ = sched.plan_step()
    assert len(admitted) == 1   # an empty batch always admits


def test_scheduler_model_error_falls_back_to_raw_caps():
    from paddle_tpu.serving.scheduler import (PagePool, Request,
                                              Scheduler)

    class Broken:
        def has(self, family):
            return True

        def predict(self, family, feats):
            raise RuntimeError("boom")

    pool = PagePool(64, 4)
    sched = Scheduler(pool, max_batch=8, max_pages_per_seq=8,
                      perf_model=Broken(), max_step_cost_s=0.1)
    for _ in range(3):
        sched.submit(Request([1] * 10, max_new_tokens=2))
    _, admitted, _ = sched.plan_step()
    assert len(admitted) == 3   # a broken model must never wedge


# ---------------------------------------------------------------------------
# satellite: the serving engine's telemetry is a training matrix
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gpt_model():
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    paddle.seed(0)
    cfg = GPTConfig(num_layers=2, hidden_size=64, num_heads=4,
                    vocab_size=128, max_position_embeddings=128,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    m = GPTForPretraining(cfg)
    m.eval()
    return m


def test_engine_run_yields_training_matrix(gpt_model, tmp_path,
                                           flags_guard):
    """Drive the real serving engine with the event log on: the rows it
    writes (batch_step with step_s/occupancy, compile,
    dispatch_summary) must round-trip the schema and build a dense
    training matrix with no NaN cell — the fit --from-events
    contract."""
    import math
    from paddle_tpu.analysis.perf_features import training_matrix
    from paddle_tpu.observability import events as obs_events
    from paddle_tpu.observability.events import (ENVELOPE_FIELDS,
                                                 EVENT_SCHEMA)
    from paddle_tpu.serving import ServingEngine
    rs = np.random.RandomState(5)
    set_flags({"FLAGS_observability_dir": str(tmp_path)})
    try:
        engine = ServingEngine(gpt_model, max_batch=2, page_size=8)
        with engine:
            reqs = [engine.submit(rs.randint(0, 128, (n,)).tolist(),
                                  max_new_tokens=4)
                    for n in (9, 5)]
            for r in reqs:
                r.wait(timeout=60)
        obs_events.emit_dispatch_summary()
    finally:
        set_flags({"FLAGS_observability_dir": ""})
    recs = obs_events.read_events(str(tmp_path))
    kinds = {r["kind"] for r in recs}
    assert {"batch_step", "dispatch_summary"} <= kinds
    assert "compile" in kinds    # jax.monitoring backend-compile rows
    steps = [r for r in recs if r["kind"] == "batch_step"]
    for r in steps:
        assert r["step_s"] > 0
        assert 0.0 <= r["page_occupancy"] <= 1.0
        # schema round-trip: every field documented
        for field in r:
            assert field in EVENT_SCHEMA["batch_step"] \
                or field in ENVELOPE_FIELDS
    # program-cache-miss steps are marked and EXCLUDED from training
    # (their step_s is trace+compile, not steady-state work)
    cold = [r for r in steps if r.get("cold_start")]
    warm = [r for r in steps if not r.get("cold_start")]
    assert cold and warm
    assert max(c["step_s"] for c in cold) > \
        max(w["step_s"] for w in warm)
    mat = training_matrix(recs)
    assert len(mat["batch_step"]["rows"]) == len(warm)
    for row in mat["batch_step"]["rows"]:
        assert all(math.isfinite(v) for v in row)
    assert all(math.isfinite(t) and t > 0
               for t in mat["batch_step"]["targets"])


def test_engine_admission_emits_predicted_cost(gpt_model, tmp_path,
                                               flags_guard):
    from paddle_tpu.observability import events as obs_events
    from paddle_tpu.serving import ServingEngine
    rs = np.random.RandomState(5)
    set_flags({"FLAGS_observability_dir": str(tmp_path)})
    try:
        engine = ServingEngine(gpt_model, max_batch=2, page_size=8,
                               perf_model=_FakeBatchModel(1e-6),
                               max_step_cost_s=10.0)
        assert engine.scheduler.perf_model is not None
        with engine:
            engine.submit(rs.randint(0, 128, (9,)).tolist(),
                          max_new_tokens=3).wait(timeout=60)
    finally:
        set_flags({"FLAGS_observability_dir": ""})
    admits = obs_events.read_events(str(tmp_path),
                                    kinds=["serving_admit"])
    assert admits and admits[0]["predicted_cost_s"] > 0


# ---------------------------------------------------------------------------
# consumer 3: divergence watchdog
# ---------------------------------------------------------------------------

def test_model_check_clean_then_regressed(tmp_path, flags_guard):
    from paddle_tpu.observability import events as obs_events
    from paddle_tpu.observability import watchdog
    model = _batch_step_model(version=3)
    clean = [_batch_step_record(b) for b in range(1, 9)]
    slow = [_batch_step_record(b, scale=4.0) for b in range(1, 9)]
    assert watchdog.model_check(clean, model, emit_events=False) == []
    set_flags({"FLAGS_observability_dir": str(tmp_path)})
    try:
        findings = watchdog.model_check(slow, model)
    finally:
        set_flags({"FLAGS_observability_dir": ""})
    assert len(findings) == 1
    f = findings[0]
    assert f["key"] == "batch_step" and f["ratio"] > 3.5
    assert f["model_version"] == 3
    emitted = obs_events.read_events(str(tmp_path),
                                     kinds=["perf_regression"])
    assert len(emitted) == 1
    assert emitted[0]["ratio"] == f["ratio"]
    assert emitted[0]["tolerance"] == watchdog.DEFAULT_TOLERANCE


def test_watchdog_cli_perf_model_exit_codes(tmp_path, flags_guard):
    """Exit 3 on divergence, 0 on a clean replay of the same shapes,
    2 when no trained model exists."""
    from paddle_tpu.observability import events as obs_events
    from paddle_tpu.observability.__main__ import main as obs_main
    cache_dir = tmp_path / "cache"
    learned.save_model(_batch_step_model(), str(cache_dir))
    clean_dir, slow_dir = tmp_path / "clean", tmp_path / "slow"
    for d, scale in ((clean_dir, 1.0), (slow_dir, 4.0)):
        set_flags({"FLAGS_observability_dir": str(d)})
        for b in range(1, 9):
            r = _batch_step_record(b, scale=scale)
            r.pop("kind"), r.pop("run")
            obs_events.emit("batch_step", **r)
        set_flags({"FLAGS_observability_dir": ""})
    assert obs_main(["watchdog", "--dir", str(clean_dir),
                     "--perf-model", str(cache_dir)]) == 0
    assert obs_main(["watchdog", "--dir", str(slow_dir),
                     "--perf-model", str(cache_dir)]) == 3
    assert obs_main(["watchdog", "--dir", str(slow_dir),
                     "--perf-model", str(cache_dir),
                     "--warn-only"]) == 0
    assert obs_main(["watchdog", "--dir", str(slow_dir),
                     "--perf-model", str(tmp_path / "empty")]) == 2


# ---------------------------------------------------------------------------
# fit --from-events end to end
# ---------------------------------------------------------------------------

def test_fit_from_events_cli_trains_and_persists(tmp_path,
                                                 flags_guard, capsys):
    from paddle_tpu.observability import events as obs_events
    from paddle_tpu.tuning.__main__ import main as tuning_main
    obs_dir, cache_dir = tmp_path / "obs", tmp_path / "cache"
    set_flags({"FLAGS_observability_dir": str(obs_dir)})
    for b in range(1, 17):
        r = _batch_step_record(b)
        r.pop("kind"), r.pop("run")
        obs_events.emit("batch_step", **r)
    set_flags({"FLAGS_observability_dir": ""})
    rc = tuning_main(["--dir", str(cache_dir), "fit",
                      "--from-events", str(obs_dir), "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["perf_model_version"] == 1
    assert out["perf_model"]["batch_step"]["improved"] is True
    model = learned.load_model(str(cache_dir))
    assert model.has("batch_step")
    # the trained head predicts the durations it was fed
    pred = model.batch_step_seconds(_batch_step_samples()[4][0])
    assert pred == pytest.approx(_batch_step_samples()[4][1], rel=0.2)


def test_fit_with_nothing_trainable_errors(tmp_path, flags_guard):
    from paddle_tpu.tuning.__main__ import main as tuning_main
    rc = tuning_main(["--dir", str(tmp_path / "cache"), "fit",
                      "--from-events", str(tmp_path / "empty")])
    assert rc == 1


# ---------------------------------------------------------------------------
# satellites: exclusions, report quantiles, log self-health
# ---------------------------------------------------------------------------

def test_load_shaped_kinds_promoted_into_default_exclude():
    from paddle_tpu.observability import watchdog
    assert "trace_span:queue" in watchdog.DEFAULT_EXCLUDE
    assert "trace_span:serving_request" in watchdog.DEFAULT_EXCLUDE
    # a load test whose request spans balloon must NOT read as a
    # regression under the defaults
    recs = [{"kind": "trace_span", "name": "serving_request",
             "dur_s": 0.01 * (1 + (i // 6) * 50)} for i in range(12)]
    assert watchdog.self_check(recs) == []
    assert watchdog.self_check(recs, exclude=()) != []


def test_report_gains_duration_quantile_columns(tmp_path, flags_guard,
                                                capsys):
    from paddle_tpu.observability import events as obs_events
    from paddle_tpu.observability.__main__ import aggregate
    from paddle_tpu.observability.__main__ import main as obs_main
    set_flags({"FLAGS_observability_dir": str(tmp_path)})
    for b in range(1, 9):
        r = _batch_step_record(b)
        r.pop("kind"), r.pop("run")
        obs_events.emit("batch_step", **r)
    set_flags({"FLAGS_observability_dir": ""})
    recs = obs_events.read_events(str(tmp_path))
    agg = aggregate(recs)
    d = agg["durations"]["batch_step"]
    assert d["count"] == 8
    assert 0 < d["p50"] <= d["p90"] <= d["p99"]
    assert obs_main(["report", "--dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "per-kind durations" in out and "p99" in out
    assert "batch_step" in out


def test_event_log_self_health_metrics(tmp_path, flags_guard):
    from paddle_tpu.observability import events as obs_events
    from paddle_tpu.observability import metrics

    def value(name):
        fam = metrics.default_registry().get(name)
        return fam.value if fam is not None else 0.0

    r0 = value("paddle_observability_log_records_total")
    b0 = value("paddle_observability_log_bytes_total")
    set_flags({"FLAGS_observability_dir": str(tmp_path)})
    try:
        for i in range(5):
            obs_events.emit("serving", action="start",
                            url=f"http://x/{i}")
    finally:
        set_flags({"FLAGS_observability_dir": ""})
    assert value("paddle_observability_log_records_total") == r0 + 5
    assert value("paddle_observability_log_bytes_total") > b0
    # rotation is counted too
    rot0 = value("paddle_observability_log_rotations_total")
    log = obs_events.EventLog(str(tmp_path / "rot"), rotate_bytes=256,
                              keep_rotated=2)
    for i in range(40):
        log.write("serving", {"action": "start", "url": "u" * 20})
    assert value("paddle_observability_log_rotations_total") > rot0


def test_flight_ring_drops_are_counted():
    from collections import deque
    from paddle_tpu.observability import metrics, tracing
    fam = tracing._flight_drop_counter()
    before = fam.value
    old = tracing._FLIGHT
    try:
        tracing._FLIGHT = deque(maxlen=4)   # fresh, empty ring
        for i in range(10):
            tracing._record_flight({"i": i})
    finally:
        tracing._FLIGHT = old
    assert fam.value == before + 6
    assert "paddle_observability_flight_ring_dropped_total" in \
        metrics.default_registry().prometheus_text()


# ---------------------------------------------------------------------------
# CI gates (lint marker, like PTL301/501/502/503)
# ---------------------------------------------------------------------------

@pytest.mark.lint
def test_ptl302_rule_registered():
    from paddle_tpu.analysis.rules import RULES
    assert "PTL302" in RULES
    assert RULES["PTL302"].severity == "error"


@pytest.mark.lint
def test_learned_model_sanity_gate_clean():
    assert learned.sanity_check() == []


@pytest.mark.lint
def test_run_analysis_wires_and_skips_perf_model_gate(monkeypatch,
                                                      capsys):
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    import run_analysis
    monkeypatch.setattr(learned, "sanity_check",
                        lambda: ["synthetic violation"])
    rc = run_analysis.main(["--no-registry", "--no-pass-verify",
                            "--no-cost-model", "--no-metrics-schema",
                            os.path.join(_REPO, "paddle_tpu", "tuning",
                                         "learned.py")])
    out = capsys.readouterr().out
    assert rc == 1 and "PTL302" in out
    rc = run_analysis.main(["--no-registry", "--no-pass-verify",
                            "--no-cost-model", "--no-metrics-schema",
                            "--no-perf-model",
                            os.path.join(_REPO, "paddle_tpu", "tuning",
                                         "learned.py")])
    assert rc == 0


@pytest.mark.lint
def test_learned_package_self_lint_zero_errors():
    from paddle_tpu import analysis
    fs = analysis.lint_paths([
        os.path.join(_REPO, "paddle_tpu", "tuning", "learned.py"),
        os.path.join(_REPO, "paddle_tpu", "analysis",
                     "perf_features.py")])
    assert [f for f in fs if f.severity == "error"] == []
