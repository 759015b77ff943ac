"""End-to-end request tracing (paddle_tpu.observability.tracing):
W3C traceparent in/out, span trees reconstructed from the JSONL log
alone, the flight recorder (SIGTERM/chaos dump + GET /debug/trace),
the SLO regression watchdog, and the PTL503 hygiene gate."""
import json
import os
import signal
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.flags import get_flags, set_flags
from paddle_tpu.observability import events, tracing, watchdog
from paddle_tpu.observability.__main__ import main as obs_main


@pytest.fixture
def flags_guard():
    keep = get_flags(["FLAGS_serving_engine", "FLAGS_observability_dir"])
    yield
    set_flags(keep)


@pytest.fixture
def obs_dir(tmp_path):
    d = str(tmp_path / "obs")
    set_flags({"FLAGS_observability_dir": d})
    yield d
    set_flags({"FLAGS_observability_dir": ""})


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


# ---------------------------------------------------------------------------
# W3C trace context
# ---------------------------------------------------------------------------

def test_traceparent_roundtrip():
    tid, sid = tracing.new_trace_id(), tracing.new_span_id()
    assert len(tid) == 32 and len(sid) == 16
    ctx = tracing.parse_traceparent(tracing.format_traceparent(tid, sid))
    assert ctx == tracing.TraceContext(tid, sid)


@pytest.mark.parametrize("header", [
    None, "", "garbage", "00-abc-def-01",
    "00-" + "0" * 32 + "-" + "cd" * 8 + "-01",      # all-zero trace
    "00-" + "ab" * 16 + "-" + "0" * 16 + "-01",     # all-zero span
    "ff-" + "ab" * 16 + "-" + "cd" * 8 + "-01",     # invalid version
    "zz-" + "ab" * 16 + "-" + "cd" * 8 + "-01",
])
def test_traceparent_rejects_malformed(header):
    assert tracing.parse_traceparent(header) is None


# ---------------------------------------------------------------------------
# spans + ambient stamping
# ---------------------------------------------------------------------------

def test_span_tree_and_ambient_stamping(obs_dir):
    """Nested spans share the trace; events emitted inside a
    trace_span block inherit its trace_id/span envelope fields."""
    with tracing.trace_span("outer", attrs={"k": 1}) as outer:
        events.emit("serving", action="start", url="u")
        inner = tracing.start_span("inner")
        inner.end(n=2)
    recs = events.read_events(obs_dir)
    spans = {r["name"]: r for r in recs if r["kind"] == "trace_span"}
    assert spans["inner"]["parent"] == spans["outer"]["span"]
    assert spans["inner"]["trace_id"] == spans["outer"]["trace_id"]
    assert spans["outer"]["trace_id"] == outer.trace_id
    assert "parent" not in spans["outer"]           # a trace root
    assert spans["outer"]["status"] == "ok"
    assert spans["outer"]["dur_s"] >= 0
    assert spans["inner"]["attrs"] == {"n": 2}
    ev = next(r for r in recs if r["kind"] == "serving")
    assert ev["trace_id"] == outer.trace_id
    assert ev["span"] == outer.span_id


def test_span_error_status_and_idempotent_end(obs_dir):
    with pytest.raises(ValueError):
        with tracing.trace_span("boom"):
            raise ValueError("x")
    sp = tracing.start_span("twice")
    sp.end()
    sp.end(status="error")                          # no second record
    recs = [r for r in events.read_events(obs_dir)
            if r["kind"] == "trace_span"]
    assert [r["status"] for r in recs
            if r["name"] == "boom"] == ["error"]
    assert len([r for r in recs if r["name"] == "twice"]) == 1


def test_disabled_tracing_is_noop():
    assert not events.enabled()
    sp = tracing.start_span("x")
    assert sp is tracing.NOOP_SPAN
    sp.end()                                        # must not raise
    with tracing.trace_span("y") as sp2:
        assert sp2 is tracing.NOOP_SPAN
        assert tracing.current() is None


def test_build_trace_attaches_links_and_events(obs_dir):
    with tracing.trace_span("serving_request") as root:
        events.emit("serving", action="start", url="u")
    with tracing.trace_span(
            "batch_step",
            links=[{"trace_id": root.trace_id, "span": root.span_id}]):
        pass
    recs = events.read_events(obs_dir)
    tree = tracing.build_trace(recs, root.trace_id)
    assert len(tree["roots"]) == 1
    node = tree["roots"][0]
    assert node["span"]["name"] == "serving_request"
    assert [e["kind"] for e in node["events"]] == ["serving"]
    assert [s["name"] for s in tree["linked"]] == ["batch_step"]
    text = tracing.render_trace(recs, root.trace_id)
    assert "serving_request" in text and "batch_step" in text


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_ring_bounded_and_dump(obs_dir):
    tracing.set_flight_capacity(8)
    try:
        for i in range(20):
            events.emit("serving", action="start", url=f"u{i}")
        snap = tracing.flight_snapshot()
        assert snap["count"] == 8 and snap["capacity"] == 8
        assert snap["events"][-1]["url"] == "u19"   # newest last
        path = tracing.dump_flight("test-reason")
        assert os.path.basename(path) == f"flight-{os.getpid()}.json"
        with open(path) as fh:
            dump = json.load(fh)
        assert dump["reason"] == "test-reason"
        assert dump["pid"] == os.getpid()
        assert len(dump["events"]) == 8
    finally:
        tracing.set_flight_capacity(512)


def test_flight_dump_disabled_returns_none():
    assert not events.enabled()
    assert tracing.dump_flight("x") is None


def test_preemption_dumps_flight_recorder(obs_dir, tmp_path):
    """The resilience hook: SIGTERM preemption writes flight-<pid>.json
    next to the event log before the clean exit."""
    from paddle_tpu import nn
    from paddle_tpu.resilience.driver import ResilientTrainLoop
    m = nn.Linear(3, 3)
    loop = ResilientTrainLoop(str(tmp_path / "ck"), m.state_dict(),
                              save_every=100, keep_last_k=None,
                              heartbeat=False)
    loop.end_step(0)
    os.kill(os.getpid(), signal.SIGTERM)
    with pytest.raises(SystemExit):
        loop.end_step(1)
    path = os.path.join(obs_dir, f"flight-{os.getpid()}.json")
    assert os.path.exists(path)
    with open(path) as fh:
        dump = json.load(fh)
    assert dump["reason"] == "preempt"
    assert any(r.get("kind") == "step" for r in dump["events"])


@pytest.mark.slow
def test_chaos_exit_fault_dumps_flight_recorder(tmp_path):
    """A scheduled exit fault dumps the ring BEFORE the process dies —
    the post-mortem survives the chaos run."""
    import subprocess
    import sys
    obs = str(tmp_path / "obs")
    code = (
        "from paddle_tpu.resilience.faults import maybe_fault\n"
        "from paddle_tpu.observability import events\n"
        "events.emit('serving', action='start', url='u')\n"
        "maybe_fault('step')\n"
        "maybe_fault('step')\n"                     # fires step@2=exit
        "print('UNREACHABLE')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               FLAGS_observability_dir=obs,
               FLAGS_fault_schedule="step@2=exit:7")
    env.pop("PADDLE_FAULT_STATE_FILE", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          cwd=repo, capture_output=True, text=True,
                          timeout=240)
    assert proc.returncode == 7
    assert "UNREACHABLE" not in proc.stdout
    dumps = [f for f in os.listdir(obs) if f.startswith("flight-")]
    assert len(dumps) == 1
    with open(os.path.join(obs, dumps[0])) as fh:
        dump = json.load(fh)
    assert dump["reason"] == "fault:exit"
    kinds = [r.get("kind") for r in dump["events"]]
    assert "fault" in kinds and "serving" in kinds


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------

def _write_log(path, rows):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        for row in rows:
            fh.write(json.dumps(row) + "\n")


def _span_rows(name, durs):
    return [{"v": 1, "ts": float(i), "pid": 1, "run": "r",
             "kind": "trace_span", "name": name, "status": "ok",
             "trace_id": "t" * 32, "span": f"{i:016x}",
             "start_ts": float(i), "dur_s": d}
            for i, d in enumerate(durs)]


def test_watchdog_flags_slowed_step_and_passes_clean(tmp_path):
    base = str(tmp_path / "base" / "events.jsonl")
    slow = str(tmp_path / "slow" / "events.jsonl")
    clean = str(tmp_path / "clean" / "events.jsonl")
    _write_log(base, _span_rows("batch_step", [0.01] * 10))
    _write_log(slow, _span_rows("batch_step", [0.05] * 10))
    _write_log(clean, _span_rows("batch_step", [0.0104] * 10))
    baselines = watchdog.compute_baselines(events.read_events(base))
    assert baselines["trace_span:batch_step"]["count"] == 10
    flagged = watchdog.check(events.read_events(slow), baselines)
    assert len(flagged) == 1
    f = flagged[0]
    assert f["key"] == "trace_span:batch_step" and f["ratio"] == 5.0
    assert watchdog.check(events.read_events(clean), baselines) == []


def test_watchdog_step_records_and_min_samples(tmp_path):
    rows = [{"v": 1, "ts": float(i), "pid": 1, "run": "r",
             "kind": "step", "step": i, "step_time_s": 0.02}
            for i in range(5)]
    log = str(tmp_path / "d" / "events.jsonl")
    _write_log(log, rows)
    base = watchdog.compute_baselines(events.read_events(log))
    assert base["step"]["p50"] == 0.02
    # two observed samples < min_samples=3: never flagged
    obs = [{"kind": "step", "step_time_s": 10.0}] * 2
    assert watchdog.check(obs, base) == []


def test_watchdog_self_check_catches_mid_run_degradation():
    recs = _span_rows("batch_step", [0.01] * 6 + [0.08] * 6)
    flagged = watchdog.self_check(recs)
    assert [f["key"] for f in flagged] == ["trace_span:batch_step"]
    assert watchdog.self_check(_span_rows("batch_step",
                                          [0.01] * 12)) == []


def test_watchdog_excludes_backpressure_keys_by_default():
    """Queue wait scales with offered load — it must not turn every
    load test into a 'regression' (override with exclude=())."""
    recs = _span_rows("queue", [0.01] * 6 + [0.5] * 6)
    assert watchdog.self_check(recs) == []
    assert [f["key"] for f in watchdog.self_check(recs, exclude=())] \
        == ["trace_span:queue"]


def test_watchdog_cli_exit_codes(tmp_path, capsys):
    base_d = str(tmp_path / "base")
    slow_d = str(tmp_path / "slow")
    _write_log(os.path.join(base_d, "events.jsonl"),
               _span_rows("batch_step", [0.01] * 10))
    _write_log(os.path.join(slow_d, "events.jsonl"),
               _span_rows("batch_step", [0.05] * 10))
    assert obs_main(["watchdog", "--dir", base_d,
                     "--baseline", base_d]) == 0
    assert obs_main(["watchdog", "--dir", slow_d,
                     "--baseline", base_d]) == 3
    assert obs_main(["watchdog", "--dir", slow_d, "--baseline", base_d,
                     "--warn-only"]) == 0
    out = capsys.readouterr().out
    assert "REGRESSION trace_span:batch_step" in out
    # --json is machine-readable
    assert obs_main(["watchdog", "--dir", slow_d, "--baseline", base_d,
                     "--warn-only", "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["regressions"][0]["key"] == "trace_span:batch_step"


def test_trace_cli_renders_and_errors(tmp_path, capsys):
    d = str(tmp_path / "d")
    tid = "ab" * 16
    rows = [{"v": 1, "ts": 1.0, "pid": 1, "run": "r",
             "kind": "trace_span", "name": "serving_request",
             "status": "ok", "trace_id": tid, "span": "cd" * 8,
             "start_ts": 1.0, "dur_s": 0.5}]
    _write_log(os.path.join(d, "events.jsonl"), rows)
    assert obs_main(["trace", tid, "--dir", d]) == 0
    assert "serving_request" in capsys.readouterr().out
    assert obs_main(["trace", "ee" * 16, "--dir", d]) == 1


# ---------------------------------------------------------------------------
# serving integration (engine-level, fast)
# ---------------------------------------------------------------------------

def test_engine_trace_covers_eviction_and_resume(gpt_model, obs_dir):
    """Eviction rides the trace: the evict event is stamped with the
    victim's trace, and re-admission opens a second queue span under
    the same root."""
    from paddle_tpu.serving import ServingEngine
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, 128, (12,)).tolist() for _ in range(3)]
    engine = ServingEngine(gpt_model, max_batch=3, page_size=8,
                           num_pages=8, max_pages_per_seq=4,
                           prefix_caching=False)
    with engine:
        reqs = [engine.submit(p, max_new_tokens=12) for p in prompts]
        for r in reqs:
            r.wait(timeout=120)
    assert engine.scheduler.evictions >= 1
    recs = events.read_events(obs_dir)
    evict = next(r for r in recs if r["kind"] == "evict")
    tid = evict["trace_id"]
    assert tid and evict["span"]
    mine = tracing.trace_records(recs, tid)
    queues = [r for r in mine if r.get("kind") == "trace_span"
              and r["name"] == "queue"]
    assert len(queues) >= 2                          # initial + resume
    root = next(r for r in mine if r.get("kind") == "trace_span"
                and r["name"] == "serving_request")
    assert root["attrs"]["evictions"] >= 1
    assert all(q["parent"] == root["span"] for q in queues)
    # the second admission is marked resumed both on the span attrs
    # and the serving_admit event
    admits = [r for r in mine if r.get("kind") == "serving_admit"]
    assert any(a.get("resumed") for a in admits)


def test_debug_trace_endpoint_serves_flight_ring(gpt_model, obs_dir,
                                                 flags_guard):
    from paddle_tpu.inference.serving import InferenceServer
    from paddle_tpu.serving import ServingEngine
    set_flags({"FLAGS_serving_engine": True})
    engine = ServingEngine(gpt_model, max_batch=2, page_size=8)
    engine.start()
    srv = InferenceServer(engine=engine).start()
    try:
        engine.submit([3, 9, 17], max_new_tokens=2).wait(timeout=60)
        with urllib.request.urlopen(srv.url + "/debug/trace",
                                    timeout=10) as r:
            snap = json.loads(r.read())
    finally:
        srv.stop()
        engine.stop()
    assert snap["pid"] == os.getpid()
    kinds = {e.get("kind") for e in snap["events"]}
    assert "batch_step" in kinds and "trace_span" in kinds


def test_decode_loop_and_compile_spans(obs_dir):
    """The mega-kernel generate path spans decode_loop with a
    decode_compile child on the program-cache miss."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.generation import decode_loop
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    paddle.seed(1)
    cfg = GPTConfig(num_layers=1, hidden_size=32, num_heads=4,
                    vocab_size=64, max_position_embeddings=64,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    m = GPTForPretraining(cfg)
    ids = np.array([[3, 9, 17]], np.int64)
    decode_loop(m, Tensor(ids), max_new_tokens=3)
    recs = events.read_events(obs_dir)
    spans = {r["name"]: r for r in recs if r["kind"] == "trace_span"}
    assert "decode_loop" in spans and "decode_compile" in spans
    assert spans["decode_compile"]["parent"] == spans["decode_loop"]["span"]
    ev = next(r for r in recs if r["kind"] == "decode_loop")
    assert ev["trace_id"] == spans["decode_loop"]["trace_id"]
    # warm call: no second compile span
    decode_loop(m, Tensor(ids), max_new_tokens=3)
    recs = events.read_events(obs_dir)
    assert len([r for r in recs if r.get("name") == "decode_compile"]) \
        == 1
    assert len([r for r in recs if r.get("name") == "decode_loop"]) == 2


# ---------------------------------------------------------------------------
# the slow end-to-end acceptance run: concurrent HTTP requests with
# client traceparents, span trees reconstructed from the log alone
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_http_concurrent_traces_reconstruct_from_log(gpt_model, obs_dir,
                                                     flags_guard,
                                                     capsys):
    from paddle_tpu.inference.serving import (InferenceServer,
                                              generate_http)
    from paddle_tpu.serving import ServingEngine
    set_flags({"FLAGS_serving_engine": True})
    engine = ServingEngine(gpt_model, max_batch=4, page_size=8)
    engine.start()
    srv = InferenceServer(engine=engine, max_in_flight=16).start()
    rs = np.random.RandomState(0)
    n_req, n_new = 4, 6
    client = [(tracing.new_trace_id(), tracing.new_span_id())
              for _ in range(n_req)]
    prompts = [rs.randint(0, 128, (5 + i,)).tolist()
               for i in range(n_req)]
    results = [None] * n_req

    def _one(i):
        tp = tracing.format_traceparent(*client[i])
        results[i] = list(generate_http(srv.url, prompts[i],
                                        max_new_tokens=n_new,
                                        traceparent=tp))

    threads = [threading.Thread(target=_one, args=(i,))
               for i in range(n_req)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    try:
        # the response echoes the traceparent with the SERVER root span
        body = json.dumps({"input_ids": prompts[0],
                           "max_new_tokens": 2,
                           "stream": False}).encode()
        echo_tid = tracing.new_trace_id()
        req = urllib.request.Request(
            srv.url + "/generate", data=body, method="POST",
            headers={"traceparent":
                     tracing.format_traceparent(echo_tid, "ee" * 8)})
        with urllib.request.urlopen(req, timeout=60) as r:
            echoed = r.headers.get("traceparent")
        assert echoed and echoed.split("-")[1] == echo_tid
        assert echoed.split("-")[2] != "ee" * 8      # server span id
    finally:
        srv.stop()
        engine.stop()
    assert all(len(r) == n_new for r in results)

    recs = events.read_events(obs_dir)
    for i, (tid, client_span) in enumerate(client):
        mine = tracing.trace_records(recs, tid)
        spans = [r for r in mine if r.get("kind") == "trace_span"]
        roots = [r for r in spans if r["name"] == "serving_request"]
        assert len(roots) == 1, f"request {i}"
        root = roots[0]
        # the client span parents the server root (W3C propagation)
        assert root["parent"] == client_span
        assert root["status"] == "ok"
        assert root["attrs"]["n_tokens"] == n_new
        assert root["attrs"]["prompt_len"] == len(prompts[i])
        # queue -> admit -> N batch-step links -> finish
        queues = [r for r in spans if r["name"] == "queue"]
        assert queues and all(q["parent"] == root["span"]
                              for q in queues)
        admits = [r for r in mine if r.get("kind") == "serving_admit"]
        assert len(admits) >= 1
        assert admits[0]["span"] == root["span"]
        tree = tracing.build_trace(recs, tid)
        # every generated token came out of a linked shared step span
        assert len(tree["linked"]) >= n_new
        assert all(s["name"] == "batch_step" for s in tree["linked"])
        # the CLI renders the same reconstruction
        assert obs_main(["trace", tid, "--dir", obs_dir]) == 0
        text = capsys.readouterr().out
        assert "serving_request" in text and "queue" in text
        assert "batch_step" in text
    # the shared step spans are genuinely shared: at least one links
    # more than one of the client traces
    tids = {t for t, _ in client}
    step_spans = [r for r in recs if r.get("kind") == "trace_span"
                  and r.get("name") == "batch_step"]
    assert any(len({link["trace_id"] for link in (s.get("links") or [])
                    if link["trace_id"] in tids}) > 1
               for s in step_spans)


# ---------------------------------------------------------------------------
# PTL503 gates
# ---------------------------------------------------------------------------

@pytest.mark.lint
def test_ptl503_fixtures():
    from paddle_tpu.analysis.obs_check import tracing_findings_source

    bad_discarded = (
        "from paddle_tpu.observability import tracing\n"
        "def f():\n"
        "    tracing.start_span('x')\n")
    bad_unused = (
        "from paddle_tpu.observability import tracing\n"
        "def f():\n"
        "    sp = tracing.start_span('x')\n"
        "    return 1\n")
    bad_partial_envelope = (
        "from paddle_tpu.observability import events\n"
        "def f(sid):\n"
        "    events.emit('evict', request='1', span=sid)\n")
    for src in (bad_discarded, bad_unused, bad_partial_envelope):
        found = tracing_findings_source(src, "fixture.py")
        assert [f.code for f in found] == ["PTL503"], src

    ok_ended = (
        "from paddle_tpu.observability import tracing\n"
        "def f():\n"
        "    sp = tracing.start_span('x')\n"
        "    sp.end()\n")
    ok_escapes = (
        "from paddle_tpu.observability import tracing\n"
        "def f(req):\n"
        "    sp = tracing.start_span('x')\n"
        "    req.span = sp\n")
    ok_attribute_target = (
        "from paddle_tpu.observability import tracing\n"
        "def f(req):\n"
        "    req._queue_span = tracing.start_span('x')\n")
    ok_full_envelope = (
        "from paddle_tpu.observability import events\n"
        "def f(tid, sid):\n"
        "    events.emit('evict', request='1', trace_id=tid, span=sid)\n")
    ok_noqa = (
        "from paddle_tpu.observability import tracing\n"
        "def f():\n"
        "    tracing.start_span('x')  # noqa: PTL503 — fixture\n")
    for src in (ok_ended, ok_escapes, ok_attribute_target,
                ok_full_envelope, ok_noqa):
        assert tracing_findings_source(src, "fixture.py") == [], src


@pytest.mark.lint
def test_ptl503_package_clean():
    from paddle_tpu.analysis.obs_check import check_tracing
    findings = check_tracing()
    assert findings == [], "\n".join(f.render() for f in findings)


@pytest.mark.lint
def test_trace_span_kind_in_schema_and_doc():
    from paddle_tpu.analysis.obs_check import check_event_schema
    assert "trace_span" in events.EVENT_SCHEMA
    assert "trace_id" in events.ENVELOPE_FIELDS
    findings = check_event_schema()
    assert findings == [], "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# phases of the serving loop (engine:* annotations + batch_step fields)
# ---------------------------------------------------------------------------

_PHASE_FIELDS = ("plan_s", "prepare_s", "dispatch_s", "read_s", "commit_s")


@pytest.fixture
def fused_guard():
    keep = get_flags(["FLAGS_serving_fused_steps"])
    yield
    set_flags(keep)


def waited_ts(steps):
    """ts of the one step that waited for work (after the pause)."""
    return next(s["ts"] for s in steps if s.get("wait_s", 0.0) >= 0.15)


@pytest.mark.parametrize("fused", [1, 4], ids=["single_step", "fused"])
def test_batch_step_carries_the_loop_phases(gpt_model, obs_dir,
                                            fused_guard, fused):
    """Every warm batch_step holds the five phase seconds of ITS OWN
    plan, dispatch, read and commit, though the loop dispatches a step
    before it reads the one before; host_gap_s runs from the last host
    read to this dispatch's end without the waits for work;
    admit_queue_s repeats serving_admit's queue_s."""
    from paddle_tpu.serving import ServingEngine
    set_flags({"FLAGS_serving_fused_steps": fused})
    rs = np.random.RandomState(9)
    prompts = [rs.randint(0, 128, (7,)).tolist() for _ in range(4)]
    engine = ServingEngine(gpt_model, max_batch=2, page_size=8,
                           prefix_caching=False)
    with engine:
        for r in [engine.submit(p, max_new_tokens=6) for p in prompts[:3]]:
            r.wait(timeout=120)
        time.sleep(0.2)            # the loop polls for work: idle_wait
        engine.submit(prompts[3], max_new_tokens=6).wait(timeout=120)
    recs = events.read_events(obs_dir)
    steps = [r for r in recs if r["kind"] == "batch_step"]
    warm = [s for s in steps if not s.get("cold_start")]
    assert len(warm) >= 6
    if fused > 1:
        assert any(s["fused_steps"] > 1 for s in warm)
    for s in warm:
        assert all(s[f] >= 0.0 for f in _PHASE_FIELDS), s
    # step_s is what the step cost the loop: from the later of its own
    # dispatch call and its predecessor's result to its own result
    for s in warm:
        if s.get("ahead"):
            # dispatched behind an unread step: its clock started when
            # that step's result came in, so it holds its own read and
            # no other step's device time
            assert s["step_s"] >= s["read_s"] - 1e-4, s
        else:
            # nothing unread before it: the dispatch and the host read
            # bracket it, as in a loop that never runs ahead
            assert s["step_s"] >= s["dispatch_s"] - 1e-4, s
    if fused == 1:
        assert any(s.get("ahead") for s in warm)
        # back-to-back steps tile the loop's time: between the first
        # and the last result of a request's run of steps, the step_s
        # of the steps in between add up to the wall time, none counted
        # twice (an ahead step is not the sum of two device steps)
        run = [s for s in steps[1:] if s["ts"] < waited_ts(steps)]
        wall = run[-1]["ts"] - run[0]["ts"]
        assert sum(s["step_s"] for s in run[1:]) <= wall + 0.05, run
    assert "host_gap_s" not in steps[0]
    for s in steps[1:]:
        if s["fused_steps"] == 1 and s["exit_reason"] == "single_step":
            # (a fused window's plan_s also counts the pre-staging it
            # does while the device runs, outside the gap)
            assert s["host_gap_s"] >= s["plan_s"] + s["prepare_s"] \
                + s["dispatch_s"] - 1e-5, s
        else:
            assert s["host_gap_s"] >= s["dispatch_s"] - 1e-5, s
    admits = [r["queue_s"] for r in recs if r["kind"] == "serving_admit"]
    assert len(admits) == 4
    assert [q for s in steps for q in s.get("admit_queue_s", ())] == admits
    # the one step after the pause: its wait is in wait_s, not in the gap
    waited = [s for s in steps if s.get("wait_s", 0.0) >= 0.15]
    assert len(waited) == 1 and waited[0]["admit_queue_s"] == admits[3:]
    assert waited[0]["host_gap_s"] < waited[0]["wait_s"]


def test_phase_clock_is_idle_with_the_log_off(gpt_model, monkeypatch):
    """With FLAGS_observability_dir unset a step enters and leaves its
    annotations and nothing else: no phase record, no OpEvent."""
    from paddle_tpu.core import dispatch
    from paddle_tpu.serving import Request, ServingEngine
    from paddle_tpu.serving import engine as engine_mod
    assert not events.enabled() and not dispatch._op_stream_hooks

    def no_op_event(*a, **kw):
        raise AssertionError("an OpEvent was built with nobody listening")
    monkeypatch.setattr(dispatch, "OpEvent", no_op_event)
    engine = ServingEngine(gpt_model, max_batch=2, page_size=8)
    req = Request([5, 6, 7, 8], max_new_tokens=2)
    engine.scheduler.submit(req)
    phases = engine_mod._LoopPhases()
    unread = None
    for _ in range(2):
        phases.switch(engine_mod._PLAN)
        plan, admitted, _ = engine.scheduler.plan_step(
            unread.plan if unread else None)
        for seq in admitted:
            phases.admitted(0.001)
        phases.switch(engine_mod._PREPARE)
        step = engine_mod._Flight(plan, ahead=unread is not None)
        assert engine._dispatch_step(step, unread, engine._epoch, phases)
        assert step.front is None
        if unread is not None:
            engine._land_step(unread, step, engine._epoch, phases)
        unread = step
        assert phases.seconds is None and phases.admit_queue_s is None
        assert phases.take(step.front) is engine_mod._NO_PHASES
    engine._land_step(unread, None, engine._epoch, phases)
    phases.stop()
    assert len(req.wait(timeout=5)) == 2
