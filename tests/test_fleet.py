"""Fleet serving tier (``paddle_tpu.serving.fleet``): router placement
(affinity / predicted cost / queue depth), mid-stream resubmission,
perf-model merging + the ``tuning merge`` CLI, Retry-After-honoring
client backoff, the supervisor over stub workers, aggregated metrics,
and the fleet lint scopes.

Everything here runs against lightweight in-process stub replicas
(plain ``ThreadingHTTPServer`` speaking the NDJSON contract) — no jax
engine, so the suite stays tier-1 fast.  The real-engine end-to-end
path (subprocess replicas, SIGKILL chaos) lives in
``test_fleet_chaos.py`` (slow).
"""
from __future__ import annotations

import json
import math
import os
import sys
import textwrap
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import events as obs_events
from paddle_tpu.serving.fleet import (FleetRouter, ReplicaSupervisor,
                                      merge_models, perf_merge)
from paddle_tpu.tuning.learned import (LearnedPerfModel, _Head,
                                       MODEL_SCHEMA)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# stub replica: the NDJSON /generate contract without an engine
# ---------------------------------------------------------------------------

def _stub_token(ids, i):
    """Deterministic token stream: a resumed leg (prompt + generated
    so far) continues exactly where the dead leg stopped, so the test
    can simulate the full expected sequence."""
    return (sum(ids) + 31 * (len(ids) + i)) % 251


class _StubReplica:
    """Threaded HTTP server speaking the replica contract: streaming
    ``POST /generate``, gauge-bearing ``GET /metrics``.  Failure
    injection: ``die_after`` tokens (connection torn, no done line)
    for the first ``die_times`` requests."""

    def __init__(self, queue_depth=0.0, occupancy=0.0,
                 die_after=None, die_times=0, token_delay=0.0,
                 health=None):
        self.queue_depth = queue_depth
        self.occupancy = occupancy
        self.die_after = die_after
        self.die_times = die_times
        self.token_delay = token_delay
        # engine health gauge value (0 ok .. 3 failed); None omits the
        # family entirely, like a pre-health replica build
        self.health = health
        self.requests = []            # (spec, headers) per /generate
        self._lock = threading.Lock()
        outer = self

        class _H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_GET(self):
                if self.path != "/metrics":
                    self.send_response(404)
                    self.end_headers()
                    return
                text = (
                    "# HELP paddle_serving_engine_queue_depth d\n"
                    "# TYPE paddle_serving_engine_queue_depth gauge\n"
                    'paddle_serving_engine_queue_depth{engine="s"} '
                    f"{outer.queue_depth}\n"
                    'paddle_serving_engine_batch_occupancy'
                    f'{{engine="s"}} {outer.occupancy}\n')
                if outer.health is not None:
                    text += ('paddle_serving_engine_health'
                             f'{{engine="s"}} {outer.health}\n')
                body = text.encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                n = int(self.headers.get("Content-Length", "0"))
                spec = json.loads(self.rfile.read(n))
                with outer._lock:
                    outer.requests.append(
                        (spec, {k.lower(): v
                                for k, v in self.headers.items()}))
                    die = None
                    if outer.die_times > 0:
                        die = outer.die_after
                        outer.die_times -= 1
                ids = spec["input_ids"]
                max_new = spec["max_new_tokens"]
                self.send_response(200)
                self.send_header("Content-Type",
                                 "application/x-ndjson")
                self.end_headers()
                toks = []
                for i in range(max_new):
                    if die is not None and i >= die:
                        # tear the stream: no done line, socket gone
                        self.wfile.flush()
                        self.connection.close()
                        return
                    tok = _stub_token(ids, i)
                    toks.append(tok)
                    self.wfile.write(json.dumps(
                        {"token": tok}).encode() + b"\n")
                    self.wfile.flush()
                    if outer.token_delay:
                        time.sleep(outer.token_delay)
                self.wfile.write(json.dumps(
                    {"done": True, "tokens": ids + toks,
                     "request_id": "stub"}).encode() + b"\n")

        self._httpd = ThreadingHTTPServer(("127.0.0.1", 0), _H)
        # torn-stream injection closes sockets mid-handler on purpose
        self._httpd.handle_error = lambda *a: None
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True)

    @property
    def url(self):
        h, p = self._httpd.server_address[:2]
        return f"http://{h}:{p}"

    def start(self):
        self._thread.start()
        return self

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()


def _expected_stream(prompt, max_new, die_after=None):
    """Simulate the fleet-level token stream: one leg, or a torn leg
    resumed by a survivor with the generated-so-far tokens kept."""
    ids = list(prompt)
    out = []
    i = 0
    for step in range(max_new):
        if die_after is not None and step == die_after:
            ids = ids + out     # resubmitted leg's prompt
            i = 0
        tok = _stub_token(ids, i)
        out.append(tok)
        i += 1
    return out


@pytest.fixture
def obs_dir(tmp_path):
    d = str(tmp_path / "obs")
    paddle.set_flags({"FLAGS_observability_dir": d})
    try:
        yield d
    finally:
        paddle.set_flags({"FLAGS_observability_dir": ""})


def _mk_router(stubs, **kw):
    kw.setdefault("poll_interval", 0.1)
    kw.setdefault("placement_wait_s", 2.0)
    return FleetRouter(replicas=[s.url for s in stubs], **kw)


def _generate(url, prompt, max_new=8, **kw):
    from paddle_tpu.inference.serving import generate_http
    return list(generate_http(url, prompt, max_new_tokens=max_new,
                              **kw))


# ---------------------------------------------------------------------------
# perf merge + CLI
# ---------------------------------------------------------------------------

def _head_from_samples(seed, n_samples, scale=1e-3):
    import random
    rng = random.Random(seed)
    samples = []
    for _ in range(16):
        f = {"batch": rng.randint(1, 8),
             "queue_depth": rng.randint(0, 5),
             "decode_seqs": rng.randint(0, 8),
             "tokens": rng.randint(1, 200)}
        s = scale * f["batch"] * (1 + 0.1 * f["decode_seqs"]) \
            * (1 + 0.02 * rng.random())
        samples.append((f, s))
    h = _Head.fit("batch_step", samples)
    h.stats["n_samples"] = n_samples
    return h


def test_merge_heads_is_weighted_geometric_mean():
    h1 = _head_from_samples(1, n_samples=10)
    h2 = _head_from_samples(2, n_samples=30, scale=2e-3)
    m1 = LearnedPerfModel({"batch_step": h1}, version=1)
    m2 = LearnedPerfModel({"batch_step": h2}, version=2)
    merged = merge_models([m1, m2])
    feats = {"batch": 4, "queue_depth": 2, "decode_seqs": 3,
             "tokens": 77}
    p1 = m1.predict("batch_step", feats)
    p2 = m2.predict("batch_step", feats)
    pm = merged.predict("batch_step", feats)
    expect = math.exp((10 * math.log(p1) + 30 * math.log(p2)) / 40.0)
    assert pm == pytest.approx(expect, rel=1e-9)
    # version beats every input; sample counts accumulate
    assert merged.version == 3
    head = merged.heads["batch_step"]
    assert head.stats["n_samples"] == 40
    assert head.stats["merged_from"] == 2
    # single-source merge is prediction-identical
    alone = merge_models([m1])
    assert alone.predict("batch_step", feats) == pytest.approx(
        p1, rel=1e-12)


def test_merge_disjoint_feature_sets_union():
    h1 = _Head("batch_step", ["a"], [0.0], [1.0], [2.0], -3.0,
               {"n_samples": 5})
    h2 = _Head("batch_step", ["b"], [0.0], [1.0], [4.0], -1.0,
               {"n_samples": 15})
    merged = perf_merge.merge_heads([h1, h2])
    assert merged.feature_names == ["a", "b"]
    feats = {"a": 1.0, "b": 2.0}
    expect = math.exp((5 * math.log(h1.predict(feats))
                       + 15 * math.log(h2.predict(feats))) / 20.0)
    assert merged.predict(feats) == pytest.approx(expect, rel=1e-9)


def test_tuning_merge_cli_roundtrip(tmp_path, capsys):
    from paddle_tpu.tuning.__main__ import main as tuning_main
    paths = []
    for seed, n, ver in ((1, 10, 3), (2, 30, 7)):
        m = LearnedPerfModel(
            {"batch_step": _head_from_samples(seed, n)}, version=ver)
        p = tmp_path / f"perf_model_{seed}.json"
        p.write_text(json.dumps(m.to_dict()))
        paths.append(str(p))
    out = tmp_path / "merged" / "perf_model.json"
    rc = tuning_main(["merge", *paths, "--out", str(out), "--json"])
    assert rc == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["version"] == 8        # max(3, 7) + 1
    assert summary["sources"] == 2
    loaded = LearnedPerfModel.from_dict(json.loads(out.read_text()))
    assert loaded.version == 8
    direct = merge_models([LearnedPerfModel.from_dict(
        json.loads(open(p).read())) for p in paths])
    feats = {"batch": 3, "queue_depth": 1, "decode_seqs": 2,
             "tokens": 50}
    assert loaded.predict("batch_step", feats) == pytest.approx(
        direct.predict("batch_step", feats), rel=1e-12)


def test_tuning_merge_cli_rejects_corrupt_input(tmp_path, capsys):
    from paddle_tpu.tuning.__main__ import main as tuning_main
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    rc = tuning_main(["merge", str(bad),
                      "--out", str(tmp_path / "out.json")])
    assert rc == 2
    assert not (tmp_path / "out.json").exists()


# ---------------------------------------------------------------------------
# retry client: Retry-After honored
# ---------------------------------------------------------------------------

class _FlakyServer:
    """Scripted 503-then-200 server: first ``n_503`` /generate posts
    answer 503 with a Retry-After header, later ones stream tokens."""

    def __init__(self, n_503=1, retry_after="0.07"):
        self.remaining_503 = n_503
        self.retry_after = retry_after
        self.hits = 0
        outer = self

        class _H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_POST(self):
                outer.hits += 1
                n = int(self.headers.get("Content-Length", "0"))
                spec = json.loads(self.rfile.read(n))
                if outer.remaining_503 > 0:
                    outer.remaining_503 -= 1
                    body = b'{"error": "overloaded"}'
                    self.send_response(503)
                    self.send_header("Retry-After", outer.retry_after)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                self.send_response(200)
                self.end_headers()
                toks = [_stub_token(spec["input_ids"], i)
                        for i in range(spec["max_new_tokens"])]
                for t in toks:
                    self.wfile.write(json.dumps(
                        {"token": t}).encode() + b"\n")
                self.wfile.write(json.dumps(
                    {"done": True, "tokens": toks}).encode() + b"\n")

        self._httpd = ThreadingHTTPServer(("127.0.0.1", 0), _H)
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True)
        self._thread.start()

    @property
    def url(self):
        h, p = self._httpd.server_address[:2]
        return f"http://{h}:{p}"

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()


def test_generate_http_honors_retry_after(monkeypatch):
    from paddle_tpu.inference import serving as serving_mod
    srv = _FlakyServer(n_503=1, retry_after="0.07")
    sleeps = []
    monkeypatch.setattr(serving_mod, "_retry_sleep", sleeps.append)
    try:
        toks = _generate(srv.url, [1, 2, 3], max_new=4,
                         retry_backoff=0.3)
    finally:
        srv.stop()
    assert len(toks) == 4
    assert srv.hits == 2
    # the server's 0.07 replaced the client's 0.3-based schedule
    assert sleeps == [pytest.approx(0.07)]


def test_generate_http_garbled_retry_after_uses_schedule(monkeypatch):
    from paddle_tpu.inference import serving as serving_mod
    srv = _FlakyServer(n_503=1, retry_after="soon")
    sleeps = []
    monkeypatch.setattr(serving_mod, "_retry_sleep", sleeps.append)
    try:
        toks = _generate(srv.url, [4, 5], max_new=3,
                         retry_backoff=0.011)
    finally:
        srv.stop()
    assert len(toks) == 3
    # fell back to the deterministic schedule (base 0.011 + jitter)
    assert len(sleeps) == 1 and 0.011 <= sleeps[0] < 0.022


def test_with_retries_delay_from_overrides_schedule():
    from paddle_tpu.resilience.retry import with_retries
    calls = {"n": 0}
    sleeps = []

    def fn():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("again")
        return "ok"

    out = with_retries(fn, attempts=4, retry_on=OSError,
                       base_delay=1.0, max_delay=2.0, jitter=0.0,
                       sleep=sleeps.append,
                       delay_from=lambda e: 0.25)
    assert out == "ok"
    assert sleeps == [0.25, 0.25]       # never the 1.0/2.0 schedule


# ---------------------------------------------------------------------------
# router: placement, resubmission, metrics, tracing
# ---------------------------------------------------------------------------

def test_router_streams_and_aggregates_metrics(obs_dir):
    stubs = [_StubReplica().start(), _StubReplica().start()]
    router = _mk_router(stubs).start()
    try:
        prompt = [1, 2, 3, 4]
        toks = _generate(router.url, prompt, max_new=6)
        assert toks == _expected_stream(prompt, 6)
        # aggregated exposition: replica-labelled engine families +
        # the router's own fleet families
        text = urllib.request.urlopen(
            router.url + "/metrics", timeout=10).read().decode()
        assert 'paddle_serving_engine_queue_depth{engine="s",' \
               'replica="0"}' in text
        assert 'replica="1"' in text
        assert "paddle_fleet_live_replicas" in text
        assert "paddle_fleet_routed_total" in text
        stats = router.fleet_stats()
        assert stats["live"] == 2
        assert stats["served"] >= 1
    finally:
        router.stop()
        for s in stubs:
            s.stop()
    # every placement emitted a router_route event with the trace
    routes = obs_events.read_events(obs_dir, kinds=["router_route"])
    assert routes and routes[-1]["candidates"] == 2
    assert routes[-1]["replica"] in ("0", "1")
    assert "trace_id" in routes[-1]


def test_router_affinity_beats_queue_depth(obs_dir):
    stubs = [_StubReplica().start(), _StubReplica().start()]
    router = _mk_router(stubs).start()
    try:
        prompt = list(range(32)) + [7, 8]     # two full 16-token pages
        _generate(router.url, prompt, max_new=2)
        first = [i for i, s in enumerate(stubs) if s.requests]
        assert len(first) == 1
        owner = first[0]
        other = 1 - owner
        # make the owner look heavily loaded: queue depth would send
        # the next request elsewhere — affinity must win anyway
        stubs[owner].queue_depth = 50.0
        stubs[other].queue_depth = 0.0
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if router.endpoints[owner].queue_depth == 50.0:
                break
            time.sleep(0.05)
        n_before = len(stubs[owner].requests)
        _generate(router.url, prompt + [9], max_new=2)
        assert len(stubs[owner].requests) == n_before + 1
        assert not stubs[other].requests
        assert int(router._c_affinity.value) >= 1
    finally:
        router.stop()
        for s in stubs:
            s.stop()
    routes = obs_events.read_events(obs_dir, kinds=["router_route"])
    assert routes[-1]["affinity_pages"] == 2
    assert routes[-1]["replica"] == str(owner)


def test_router_placement_consults_perf_model(obs_dir):
    # a head that prices decode_seqs (occupancy) steeply: the replica
    # with the deeper QUEUE but idle batch must win — pure
    # least-queue-depth would pick the other one
    head = _Head("batch_step", ["decode_seqs"], mu=[0.0], sd=[1.0],
                 w=[1.0], b=-5.0, stats={"n_samples": 10})
    model = LearnedPerfModel({"batch_step": head}, version=4)
    stubs = [_StubReplica(queue_depth=0.0, occupancy=6.0).start(),
             _StubReplica(queue_depth=3.0, occupancy=0.0).start()]
    router = _mk_router(stubs, perf_model=model).start()
    try:
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            eps = router.endpoints
            if eps[0].occupancy == 6.0 and eps[1].queue_depth == 3.0:
                break
            time.sleep(0.05)
        prompt = [5, 6, 7]                 # no full page: no affinity
        toks = _generate(router.url, prompt, max_new=3)
        assert toks == _expected_stream(prompt, 3)
        assert stubs[1].requests and not stubs[0].requests
    finally:
        router.stop()
        for s in stubs:
            s.stop()
    routes = obs_events.read_events(obs_dir, kinds=["router_route"])
    assert routes[-1]["replica"] == "1"
    assert routes[-1]["predicted_cost_s"] > 0
    assert routes[-1]["affinity_pages"] == 0


def test_router_resubmits_after_midstream_death(obs_dir):
    # replica 0 tears the stream after 3 tokens, once; replica 1 is
    # queue-deep so the first leg lands on 0
    stubs = [_StubReplica(die_after=3, die_times=1).start(),
             _StubReplica(queue_depth=9.0).start()]
    router = _mk_router(stubs).start()
    try:
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if router.endpoints[1].queue_depth == 9.0:
                break
            time.sleep(0.05)
        prompt = [2, 4, 6]
        toks = _generate(router.url, prompt, max_new=8)
        # untruncated: all 8 tokens, continuing exactly where the
        # dead leg stopped (prompt + generated-so-far resubmitted)
        assert toks == _expected_stream(prompt, 8, die_after=3)
        assert stubs[0].requests and stubs[1].requests
        resumed_spec = stubs[1].requests[-1][0]
        assert resumed_spec["input_ids"] == prompt + toks[:3]
        assert resumed_spec["max_new_tokens"] == 5
        assert int(router._c_resubmitted.value) == 1
    finally:
        router.stop()
        for s in stubs:
            s.stop()
    routes = obs_events.read_events(obs_dir, kinds=["router_route"])
    legs = [r for r in routes if r.get("resubmitted")]
    assert len(legs) == 1 and legs[0]["replica"] == "1"


def test_router_503_when_no_replica(obs_dir):
    router = _mk_router([], placement_wait_s=0.2).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _generate(router.url, [1, 2], max_new=2, retries=1)
        assert ei.value.code == 503
        assert ei.value.headers.get("Retry-After") == "1.0"
    finally:
        router.stop()


def test_router_propagates_traceparent(obs_dir):
    from paddle_tpu.observability import tracing as _tracing
    stub = _StubReplica().start()
    router = _mk_router([stub]).start()
    try:
        tp = _tracing.format_traceparent(_tracing.new_trace_id(),
                                         _tracing.new_span_id())
        _generate(router.url, [9, 9], max_new=2, traceparent=tp)
        hdrs = stub.requests[-1][1]
        hop = hdrs.get("traceparent")
        assert hop is not None
        ctx = _tracing.parse_traceparent(hop)
        # same trace as the client, re-parented on the router's span
        assert ctx.trace_id == tp.split("-")[1]
        assert hop != tp
    finally:
        router.stop()
        stub.stop()
    # the router span records the hop in the JSONL log
    spans = obs_events.read_events(obs_dir, kinds=["trace_span"])
    assert any(s.get("name") == "fleet_request" for s in spans)


# ---------------------------------------------------------------------------
# supervisor over stub workers (no jax subprocess cost)
# ---------------------------------------------------------------------------

_STUB_WORKER = textwrap.dedent("""
    import json, os, sys, threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class H(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass
        def do_GET(self):
            body = (b'paddle_serving_engine_queue_depth{engine="w"} 0'
                    b'\\n')
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
    h, p = httpd.server_address[:2]
    pf = sys.argv[1]
    with open(pf + ".tmp", "w") as fh:
        fh.write(f"http://{h}:{p}\\n")
    os.replace(pf + ".tmp", pf)
    httpd.serve_forever()
""")


@pytest.fixture
def stub_supervisor(tmp_path, obs_dir):
    script = tmp_path / "stub_worker.py"
    script.write_text(_STUB_WORKER)
    sup = ReplicaSupervisor(
        2,
        argv_builder=lambda rid, pf: [sys.executable, str(script), pf],
        max_restarts=3, restart_backoff_s=0.05, max_backoff_s=0.2,
        poll_interval=0.05, ready_timeout=30.0, preempt_grace_s=5.0)
    sup.start()
    try:
        yield sup
    finally:
        sup.stop()


def test_supervisor_gives_each_replica_its_own_chip(monkeypatch):
    """A chip belongs to one process: unless the fleet is held to the
    CPU, replica i is launched seeing only chip i (as a one-process
    TPU job); the caller's ``env`` overlay still wins."""
    sup = ReplicaSupervisor(3, env={"FLAGS_observability_dir":
                                    "/obs/{replica}"})
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    env = sup._child_env(sup.replicas[2])
    assert "TPU_VISIBLE_CHIPS" not in env
    assert env["FLAGS_observability_dir"] == "/obs/2"
    monkeypatch.delenv("JAX_PLATFORMS")
    envs = [sup._child_env(h) for h in sup.replicas]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2"]
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1"
               and e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
               for e in envs)
    pinned = ReplicaSupervisor(1, env={"TPU_VISIBLE_CHIPS": "3"})
    assert pinned._child_env(pinned.replicas[0])["TPU_VISIBLE_CHIPS"] \
        == "3"


def test_supervisor_restarts_killed_replica(stub_supervisor, obs_dir):
    sup = stub_supervisor
    assert all(h.url for h in sup.replicas)
    old_url = sup.replicas[0].url
    sup.kill("0")
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        h = sup.replicas[0]
        if h.alive and h.url and h.restarts == 1:
            break
        time.sleep(0.05)
    h = sup.replicas[0]
    assert h.alive and h.restarts == 1
    assert h.url != old_url or h.healthy
    events = obs_events.read_events(obs_dir,
                                    kinds=["replica_restart"])
    mine = [e for e in events if e["replica"] == "0"]
    assert mine and mine[-1]["reason"] == "crash"
    assert mine[-1]["restarts"] == 1


def test_supervisor_rolling_restart(stub_supervisor, obs_dir):
    sup = stub_supervisor
    sup.rolling_restart()
    assert all(h.alive and h.url and not h.draining
               for h in sup.replicas)
    events = obs_events.read_events(obs_dir,
                                    kinds=["replica_restart"])
    rolling = [e for e in events if e["reason"] == "rolling"]
    assert len(rolling) == 2


# ---------------------------------------------------------------------------
# lint scopes: fleet files are PTL401 + PTL701 territory
# ---------------------------------------------------------------------------

_FLEET_PTL401_BAD = '''
def poll_replica(url):
    try:
        return fetch(url)
    except Exception:
        return None
'''

_FLEET_PTL701_BAD = '''
import numpy as np

def route_step(batch):
    x = np.asarray(batch.tokens)
    if batch.mask.all():
        return x.item()
    return None
'''


def test_fleet_files_in_ptl401_scope():
    from paddle_tpu.analysis.lint import lint_source
    findings = lint_source(
        _FLEET_PTL401_BAD,
        filename="paddle_tpu/serving/fleet/router.py")
    assert any(f.code == "PTL401" for f in findings)
    # out of scope: the same code elsewhere is not flagged
    findings = lint_source(_FLEET_PTL401_BAD,
                           filename="paddle_tpu/vision/thing.py")
    assert not any(f.code == "PTL401" for f in findings)


def test_fleet_files_in_ptl701_scope():
    from paddle_tpu.analysis.lint import lint_source
    findings = lint_source(
        _FLEET_PTL701_BAD,
        filename="paddle_tpu/serving/fleet/replica.py")
    codes = [f.code for f in findings]
    assert codes.count("PTL701") >= 3     # asarray, .all(), .item()
    findings = lint_source(_FLEET_PTL701_BAD,
                           filename="paddle_tpu/vision/thing.py")
    assert not any(f.code == "PTL701" for f in findings)


def test_fleet_package_files_report_clean():
    """The shipped fleet modules themselves pass the scopes they were
    just added to (the package self-lint covers this too; this keeps
    the failure local when fleet code regresses)."""
    from paddle_tpu.analysis.lint import lint_file
    fleet_dir = os.path.join(_REPO, "paddle_tpu", "serving", "fleet")
    for name in os.listdir(fleet_dir):
        if not name.endswith(".py"):
            continue
        findings = [f for f in lint_file(os.path.join(fleet_dir, name))
                    if f.code in ("PTL401", "PTL501", "PTL701")]
        assert findings == [], "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# health-aware routing: drain degraded, restart failed, fast-fail
# ---------------------------------------------------------------------------

def _wait_until(cond, timeout=5.0, every=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(every)
    return cond()


def test_router_fast_503_when_all_draining_then_recovers(obs_dir):
    """Every replica draining: placement fails FAST with 503 +
    Retry-After instead of holding the client for the whole placement
    window — and un-draining resumes routing with no restart."""
    stubs = [_StubReplica().start(), _StubReplica().start()]
    router = _mk_router(stubs, placement_wait_s=10.0).start()
    try:
        assert _wait_until(
            lambda: all(h.healthy for h in router.endpoints))
        for h in router.endpoints:
            h.draining = True
        t0 = time.monotonic()
        with pytest.raises(urllib.error.HTTPError) as ei:
            _generate(router.url, [1, 2], max_new=2, retries=1)
        elapsed = time.monotonic() - t0
        assert ei.value.code == 503
        assert ei.value.headers.get("Retry-After") == "1.0"
        # well under placement_wait_s: the fast-fail path, not the
        # full bounded wait
        assert elapsed < 5.0
        for h in router.endpoints:
            h.draining = False
        prompt = [2, 4]
        assert _generate(router.url, prompt, max_new=4) == \
            _expected_stream(prompt, 4)
    finally:
        router.stop()
        for s in stubs:
            s.stop()


def test_router_routes_around_degraded_replica(obs_dir):
    """Health rank beats every other placement signal: while an ok
    replica exists, a degraded one receives NO new work (draining it
    is how it heals) — and fleet_stats surfaces the state."""
    stubs = [_StubReplica(health=1.0).start(),   # degraded
             _StubReplica().start()]             # no gauge -> ok
    router = _mk_router(stubs).start()
    try:
        assert _wait_until(
            lambda: router.endpoints[0].health_state == "degraded"
            and router.endpoints[1].healthy)
        for _ in range(3):
            _generate(router.url, [5, 6], max_new=2)
        assert not stubs[0].requests
        assert len(stubs[1].requests) == 3
        states = {r["id"]: r["health_state"]
                  for r in router.fleet_stats()["replicas"]}
        assert states == {"0": "degraded", "1": "ok"}
    finally:
        router.stop()
        for s in stubs:
            s.stop()


def test_router_hands_failed_replica_to_supervisor(obs_dir):
    """A replica reporting health=failed is unroutable AND handed to
    the supervisor for a restart — exactly once per failure episode
    (debounced), however many polls see it down."""
    from paddle_tpu.serving.fleet.replica import ReplicaHandle

    stubs = [_StubReplica(health=3.0).start(),   # failed
             _StubReplica().start()]

    class _FakeSup:
        def __init__(self):
            self.replicas = []
            self.calls = []

        def restart_replica(self, rid, reason="health"):
            self.calls.append((rid, reason))
            return True

    sup = _FakeSup()
    for i, s in enumerate(stubs):
        h = ReplicaHandle(str(i), port_file="")
        h.url = s.url
        sup.replicas.append(h)
    router = FleetRouter(supervisor=sup, poll_interval=0.05,
                         placement_wait_s=2.0).start()
    try:
        assert _wait_until(lambda: sup.calls)
        time.sleep(0.4)                  # many more poll cycles...
        assert sup.calls == [("0", "health")]     # ...one restart
        # traffic keeps flowing, all of it on the healthy replica
        prompt = [3, 1]
        assert _generate(router.url, prompt, max_new=3) == \
            _expected_stream(prompt, 3)
        assert not stubs[0].requests
        # recovery clears the debounce: the NEXT failure episode gets
        # its own restart
        stubs[0].health = 0.0
        assert _wait_until(
            lambda: router.endpoints[0].health_state == "ok")
        stubs[0].health = 3.0
        assert _wait_until(lambda: len(sup.calls) == 2)
    finally:
        router.stop()
        for s in stubs:
            s.stop()
