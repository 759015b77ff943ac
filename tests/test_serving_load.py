"""Serving engine load test: hundreds of concurrent HTTP token
streams against one InferenceServer + ServingEngine, with the p99
tail-latency SLO asserted from the exported ``GET /metrics``
histograms (the ISSUE 13 headline acceptance).

Marked ``slow`` (tier-1 stays inside the timeout budget) and runs on a
PRIVATE per-run XLA cache dir — warm-cache executable load from the
shared <checkout>/.jax_cache is a known ~60% segfault trigger on hybrid
runs (see test_llama's identical fixture)."""
import json
import threading
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.flags import get_flags, set_flags

pytestmark = pytest.mark.slow

N_STREAMS = 200
N_NEW = 8
PROMPT_LEN = 16
# generous on the virtual-CPU smoke config, but real: a serialized or
# wedged engine blows straight through it
P99_SLO_S = 30.0


@pytest.fixture(autouse=True, scope="module")
def _private_xla_cache(tmp_path_factory):
    """De-flake by construction: this module compiles its own
    executables against a fresh per-run XLA cache so nothing loads
    WARM from the shared <checkout>/.jax_cache (the jax-0.4.37 CPU
    deserialization fragility test_llama documents)."""
    import jax
    from jax.experimental.compilation_cache import (compilation_cache as
                                                    _cc)
    prev = jax.config.jax_compilation_cache_dir
    _cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("serving_xla_cache")))
    yield
    _cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir", prev)


def _histogram_p99(text: str, name: str, **labels):
    """p99 upper bound from Prometheus-text cumulative buckets."""
    want = {f'{k}="{v}"' for k, v in labels.items()}
    buckets = []
    count = None
    for line in text.splitlines():
        if line.startswith(name + "_bucket"):
            inner = line[line.index("{") + 1:line.index("}")]
            parts = set(inner.split(","))
            if not want <= parts:
                continue
            le = next(p.split('"')[1] for p in parts
                      if p.startswith('le="'))
            cum = float(line.rsplit(" ", 1)[1])
            buckets.append((float("inf") if le == "+Inf" else float(le),
                            cum))
        elif line.startswith(name + "_count"):
            inner = line[line.index("{") + 1:line.index("}")]
            if want <= set(inner.split(",")):
                count = float(line.rsplit(" ", 1)[1])
    assert count, f"histogram {name}{labels} not found"
    target = 0.99 * count
    for le, cum in sorted(buckets):
        if cum >= target:
            return le
    return float("inf")


def _run_http_load(fused_steps: int):
    from paddle_tpu.inference.serving import (InferenceServer,
                                              generate_http)
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddle_tpu.serving import ServingEngine

    paddle.seed(0)
    cfg = GPTConfig(num_layers=2, hidden_size=64, num_heads=4,
                    vocab_size=256, max_position_embeddings=64,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    model = GPTForPretraining(cfg)
    model.eval()
    keep = get_flags(["FLAGS_serving_engine",
                      "FLAGS_serving_fused_steps"])
    set_flags({"FLAGS_serving_engine": True,
               "FLAGS_serving_fused_steps": fused_steps})
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 256, (PROMPT_LEN,)).tolist()
               for _ in range(N_STREAMS)]
    engine = ServingEngine(model, max_batch=8, page_size=16,
                           prefix_caching=False)
    results: dict = {}
    errors: dict = {}
    try:
        with engine:
            srv = InferenceServer(engine=engine,
                                  max_in_flight=2 * N_STREAMS).start()
            # warm the prefill/decode program buckets OUTSIDE the
            # measured traffic (compile seconds are not serving tail)
            engine.submit(prompts[0], max_new_tokens=2).wait(timeout=300)

            def _stream(i):
                try:
                    results[i] = list(generate_http(
                        srv.url, prompts[i], max_new_tokens=N_NEW,
                        timeout=300))
                except Exception as e:  # noqa: BLE001 — collected and
                    # asserted below; a worker thread must not die mute
                    errors[i] = f"{type(e).__name__}: {e}"

            threads = [threading.Thread(target=_stream, args=(i,))
                       for i in range(N_STREAMS)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            with urllib.request.urlopen(srv.url + "/metrics",
                                        timeout=30) as r:
                metrics_text = r.read().decode()
            with urllib.request.urlopen(srv.url + "/health",
                                        timeout=30) as r:
                health = json.loads(r.read())
            srv.stop()
    finally:
        set_flags(keep)

    # every stream completed, untruncated, with real tokens
    assert not errors, f"{len(errors)} failed streams: " \
                       f"{list(errors.items())[:3]}"
    assert len(results) == N_STREAMS
    assert all(len(toks) == N_NEW for toks in results.values())
    # the server served every admitted stream (the warm request went
    # through the engine API, not HTTP)
    assert health["served"] == N_STREAMS
    assert health["errors"] == 0
    eid = engine.engine_id
    # headline SLO: p99 end-to-end request latency from the EXPORTED
    # histogram (queue + prefill + decode under 200-way concurrency)
    p99 = _histogram_p99(metrics_text,
                         "paddle_serving_engine_request_seconds",
                         engine=eid)
    assert p99 <= P99_SLO_S, f"p99 request latency {p99}s > SLO"
    ttft99 = _histogram_p99(metrics_text,
                            "paddle_serving_engine_ttft_seconds",
                            engine=eid)
    assert ttft99 <= P99_SLO_S, f"p99 TTFT {ttft99}s > SLO"
    # sanity on the engine counters the histograms ride with
    assert engine.scheduler.queue_depth() == 0
    assert engine.pool.available() == engine.pool.num_pages - 1
    return engine


def test_http_load_hundreds_of_streams_meets_p99_slo():
    _run_http_load(fused_steps=1)


def test_http_load_fused_windows_meets_p99_slo():
    """Same 200-stream load with the persistent-program serving step
    (FLAGS_serving_fused_steps=4): every stream completes untruncated
    and the p99 SLO holds — the fused window must not wedge admission
    under real queue pressure, and its early-exit-on-finish path is
    exactly what heavy churn exercises."""
    engine = _run_http_load(fused_steps=4)
    # the fused path actually ran: iterations outnumber dispatches
    steps = engine._c_steps.value
    dispatches = engine._c_dispatch.value
    assert dispatches and steps > dispatches, \
        f"fused windows never engaged ({steps} steps / " \
        f"{dispatches} dispatches)"
