"""Continuous-batching serving engine (paddle_tpu.serving): ragged
paged attention kernel parity, scheduler/page-pool lifecycle, prefix
cache sharing, engine-vs-generate() parity, HTTP /generate streaming,
and the PTL701 step-loop hygiene rule."""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.flags import get_flags, set_flags
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
from paddle_tpu.serving import PagePool, Request, Scheduler, ServingEngine
from paddle_tpu.serving.prefix_cache import PrefixCache


@pytest.fixture
def flags_guard():
    keep = get_flags(["FLAGS_serving_engine", "FLAGS_pallas_interpret",
                      "FLAGS_use_pallas_ragged_attention"])
    yield
    set_flags(keep)


@pytest.fixture(scope="module")
def gpt_model():
    paddle.seed(0)
    cfg = GPTConfig(num_layers=2, hidden_size=64, num_heads=4,
                    vocab_size=128, max_position_embeddings=128,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    m = GPTForPretraining(cfg)
    m.eval()
    return m


def _greedy_reference(model, prompts, n_new):
    out = []
    for p in prompts:
        ids = Tensor(np.asarray([p], "int64"))
        toks = model.generate(ids, max_new_tokens=n_new,
                              decode_strategy="greedy")
        out.append(np.asarray(toks._data)[0, len(p):].tolist())
    return out


def _prompt_with_midway_eos(model, n_new=8):
    """(prompt, full greedy continuation, eos): a prompt whose greedy
    run emits a second distinct token MIDWAY, so that stopping on it is
    observable.  The tiny random GPT mostly repeats one token, and
    which prompts do not depends on the installation's RNG — so scan a
    seeded batch instead of pinning one seed."""
    cand = np.random.RandomState(0).randint(0, 128, (64, 5))
    out = np.asarray(model.generate(
        Tensor(cand.astype("int64")), max_new_tokens=n_new,
        decode_strategy="greedy")._data)[:, 5:]
    for p, row in zip(cand.tolist(), out.tolist()):
        first_other = next((i for i, t in enumerate(row) if t != row[0]),
                           None)
        if first_other is None or not 3 <= first_other <= n_new - 2:
            continue
        [full] = _greedy_reference(model, [p], n_new)
        if full == row:
            return p, full, full[first_other]
    raise AssertionError("no candidate prompt changes token midway")


# ---------------------------------------------------------------------------
# ragged paged attention kernel
# ---------------------------------------------------------------------------

def _rand_case(rs, nh, nkv, b=4, qw=8, hd=16, ps=4, ppseq=6, p_total=32):
    import jax.numpy as jnp
    q = jnp.asarray(rs.randn(b, qw, nh, hd).astype("float32"))
    kp = jnp.asarray(rs.randn(nkv, p_total, ps, hd).astype("float32"))
    vp = jnp.asarray(rs.randn(nkv, p_total, ps, hd).astype("float32"))
    # mixed batch: full prefill, decode, empty padding slot, mid chunk
    kv_lens = jnp.asarray(np.array([13, 1, 0, 24], "int32"))
    q_lens = jnp.asarray(np.array([8, 1, 0, 3], "int32"))
    tables = jnp.asarray(rs.permutation(p_total)[:b * ppseq]
                         .reshape(b, ppseq).astype("int32"))
    return q, kp, vp, kv_lens, q_lens, tables


@pytest.mark.parametrize("nh,nkv", [(4, 4), (4, 2)],
                         ids=["mha", "gqa"])
def test_ragged_kernel_matches_reference_interpret(flags_guard, rng,
                                                   nh, nkv):
    """Interpret-mode Pallas kernel == jnp reference on a mixed
    prefill/decode batch with uneven per-sequence lengths (incl. GQA
    and an empty padding slot)."""
    from paddle_tpu.ops.pallas import ragged_paged_attention as rpa
    set_flags({"FLAGS_pallas_interpret": True})
    q, kp, vp, kv_lens, q_lens, tables = _rand_case(rng, nh, nkv)
    ref = rpa.ragged_paged_attention_ref(q, kp, vp, kv_lens, q_lens,
                                         tables)
    out = rpa.ragged_paged_attention(q, kp, vp, kv_lens, q_lens, tables)
    for b in range(q.shape[0]):
        n = int(q_lens[b])
        if n:
            np.testing.assert_allclose(np.asarray(out)[b, :n],
                                       np.asarray(ref)[b, :n],
                                       rtol=2e-5, atol=2e-5)
    # the zero-length padding row must be exactly zero, never NaN
    assert np.all(np.isfinite(np.asarray(out)))
    assert np.all(np.asarray(out)[2] == 0.0)


@pytest.mark.parametrize("qw", [1, 300], ids=["decode-only", "wide"])
def test_ragged_kernel_q1_and_tiled_chunk(flags_guard, rng, qw):
    """The two shapes the chip refused before the repair: Q=1 (every
    decode-only step; the wrapper pads it to the 8-sublane tile) and a
    chunk wider than one VMEM tile (cut into block_q-row grid tiles;
    300 rows = three 128-row tiles with a ragged tail), with a decode
    lane and an empty lane riding in the wide step."""
    from paddle_tpu.ops.pallas import ragged_paged_attention as rpa
    import jax.numpy as jnp
    set_flags({"FLAGS_pallas_interpret": True})
    nh, nkv, hd, ps, ppseq, b = 4, 2, 8, 16, 20, 4
    assert qw == 1 or qw > rpa._block_q(nh, hd, 4)
    q = jnp.asarray(rng.randn(b, qw, nh, hd).astype("float32"))
    kp = jnp.asarray(rng.randn(nkv, b * ppseq, ps, hd).astype("float32"))
    vp = jnp.asarray(rng.randn(nkv, b * ppseq, ps, hd).astype("float32"))
    tables = jnp.asarray(rng.permutation(b * ppseq).reshape(b, ppseq)
                         .astype("int32"))
    if qw == 1:
        kv_lens = np.array([1, 17, 0, 320], "int32")
        q_lens = np.array([1, 1, 0, 1], "int32")
    else:
        kv_lens = np.array([300, 77, 0, 310], "int32")
        q_lens = np.array([300, 1, 0, 130], "int32")
    ref = np.asarray(rpa.ragged_paged_attention_ref(
        q, kp, vp, kv_lens, q_lens, tables))
    out = np.asarray(rpa.ragged_paged_attention(
        q, kp, vp, kv_lens, q_lens, tables))
    assert out.shape == ref.shape
    for i in range(b):
        n = int(q_lens[i])
        np.testing.assert_allclose(out[i, :n], ref[i, :n],
                                   rtol=2e-5, atol=2e-5)
    assert np.all(np.isfinite(out)) and np.all(out[2] == 0.0)


def test_ragged_reference_matches_dense_attention(rng):
    """The jnp reference == a per-sequence dense causal attention
    oracle built independently in numpy."""
    from paddle_tpu.ops.pallas import ragged_paged_attention as rpa
    nh, nkv, hd, ps = 4, 2, 8, 4
    q, kp, vp, kv_lens, q_lens, tables = _rand_case(
        rng, nh, nkv, hd=hd, ps=ps)
    out = np.asarray(rpa.ragged_paged_attention_ref(
        q, kp, vp, kv_lens, q_lens, tables))
    qn, kpn, vpn = (np.asarray(a) for a in (q, kp, vp))
    tb = np.asarray(tables)
    rep = nh // nkv
    for b in range(qn.shape[0]):
        kv_len, q_len = int(kv_lens[b]), int(q_lens[b])
        if q_len == 0:
            continue
        # gather this sequence's context densely: [kv_len, nkv, hd]
        k = np.concatenate([kpn[:, p].transpose(1, 0, 2)
                            for p in tb[b]], axis=0)[:kv_len]
        v = np.concatenate([vpn[:, p].transpose(1, 0, 2)
                            for p in tb[b]], axis=0)[:kv_len]
        start = kv_len - q_len
        for i in range(q_len):
            for h in range(nh):
                g = h // rep
                scores = (k[:start + i + 1, g] @ qn[b, i, h]) \
                    / np.sqrt(hd)
                w = np.exp(scores - scores.max())
                w /= w.sum()
                want = w @ v[:start + i + 1, g]
                np.testing.assert_allclose(out[b, i, h], want,
                                           rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# the step's k/v write into the page pools
# ---------------------------------------------------------------------------

def _scatter_pages_oracle(pages, vals, page_ids, slots):
    """The write as it stood before the flat-row form: right, and on a
    TPU two layout copies of every pool a step."""
    import jax.numpy as jnp
    nkv, hd = vals.shape[2], vals.shape[3]
    flat = jnp.swapaxes(vals.reshape(-1, nkv, hd), 0, 1)
    return pages.at[:, page_ids.reshape(-1), slots.reshape(-1)].set(
        flat.astype(pages.dtype))


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in eqn.params.values():
            if hasattr(sub, "jaxpr"):
                yield from _eqns(sub.jaxpr)


@pytest.mark.parametrize("qw", [1, 40, 1024])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("hd", [96, 128])
def test_scatter_pages_matches_the_old_write(rng, hd, dtype, qw):
    """Lane 0 writes a full chunk that starts mid-page and so crosses
    page boundaries, lane 1 a third of one and pads the rest onto the
    sink, lane 2 aims past the pool and is dropped.  Every page but the
    sink (duplicate writes, never read back) equals the old expression's,
    with the rows sorted first (Q 1024) and as they come, and the
    scatter's index operand is int32 though the ids arrive as int64
    under ``jax_enable_x64``."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.generation import _SORT_ROWS_FROM, _scatter_pages
    assert jax.config.jax_enable_x64
    nkv, ps, b = 4, 16, 3
    ppseq = -(-(qw + 5) // ps)
    sink = b * ppseq
    tables = rng.permutation(sink).reshape(b, ppseq)
    page_ids = np.full((b, qw), sink, "int64")
    slots = np.zeros((b, qw), "int64")
    for lane, (start, count) in enumerate([(5, qw), (0, -(-qw // 3))]):
        pos = np.arange(start, start + count)
        page_ids[lane, :count] = tables[lane, pos // ps]
        slots[lane, :count] = pos % ps
    page_ids[2] = sink + 1 + np.arange(qw) % 3
    pages = jnp.asarray(rng.randn(nkv, sink + 1, ps, hd), dtype)
    vals = jnp.asarray(rng.randn(b, qw, nkv, hd).astype("float32"))
    got = _scatter_pages(pages, vals, page_ids, slots)
    want = _scatter_pages_oracle(pages, vals, page_ids, slots)
    assert got.shape == pages.shape and got.dtype == pages.dtype
    np.testing.assert_array_equal(
        np.asarray(got[:, :sink], np.float32),
        np.asarray(want[:, :sink], np.float32))
    assert not np.array_equal(np.asarray(got[:, :sink], np.float32),
                              np.asarray(pages[:, :sink], np.float32))
    eqns = list(_eqns(jax.make_jaxpr(_scatter_pages)(
        pages, vals, page_ids, slots).jaxpr))
    # a wide chunk's index rows are sorted first, a narrow one's are not
    assert any(e.primitive.name == "sort" for e in eqns) \
        == (b * qw * nkv >= _SORT_ROWS_FROM) == (qw == 1024)
    scatters = [e for e in eqns if e.primitive.name.startswith("scatter")]
    assert len(scatters) == 1
    assert scatters[0].invars[1].aval.dtype == np.int32
    assert scatters[0].invars[0].aval.shape == (nkv * (sink + 1) * ps, hd)


# ---------------------------------------------------------------------------
# page pool + scheduler
# ---------------------------------------------------------------------------

def test_page_pool_refcount_lifecycle():
    pool = PagePool(num_pages=4, page_size=8)
    assert pool.sink == 3 and pool.available() == 3
    a = pool.alloc()
    pool.ref(a)
    assert pool.refcount(a) == 2
    pool.unref(a)
    assert pool.available() == 2           # still held once
    pool.unref(a)
    assert pool.available() == 3           # back on the free list
    with pytest.raises(ValueError):
        pool.unref(a)                      # double free is loud
    for _ in range(3):
        pool.alloc()
    with pytest.raises(RuntimeError):
        pool.alloc()                       # the sink is never handed out


def test_scheduler_admission_completion_and_plan_layout():
    pool = PagePool(num_pages=16, page_size=4)
    sched = Scheduler(pool, max_batch=2, max_pages_per_seq=4)
    r1 = Request([1, 2, 3, 4, 5], max_new_tokens=3)
    r2 = Request([7, 8], max_new_tokens=3)
    r3 = Request([9], max_new_tokens=3)
    for r in (r1, r2, r3):
        sched.submit(r)
    plan, admitted, evicted = sched.plan_step()
    # iteration-level admission: only max_batch sequences run; r3 waits
    assert len(admitted) == 2 and not evicted
    # packed rows: r1's five tokens, r2's two behind them, in the rows
    # of the program of the widest chunk's bucket (8 wide: 8 + 2 lanes
    # - 1 rounded up to 16, which the floor of 128 passes), not in two
    # lanes of five
    assert plan.q_width == 8 and plan.rows == 128
    assert plan.tok.shape == plan.pos.shape == (128,)
    assert plan.tok[:7].tolist() == [1, 2, 3, 4, 5, 7, 8]
    assert plan.pos[:7].tolist() == [0, 1, 2, 3, 4, 0, 1]
    assert plan.q_lens.tolist()[:2] == [5, 2]
    assert plan.kv_lens.tolist()[:2] == [5, 2]
    # page/slot layout: token t of seq 0 -> page[t//4], slot t%4
    s0, s1 = plan.seqs
    assert plan.page_ids[:7].tolist() == [s0.pages[0]] * 4 \
        + [s0.pages[1]] + [s1.pages[0]] * 2
    assert plan.slots[:7].tolist() == [0, 1, 2, 3, 0, 0, 1]
    # the rows behind the tokens scatter into the sink page
    assert plan.page_ids[7:].tolist() == [pool.sink] * 121
    assert plan.prefill_waiting == 0
    assert (sched.rows_planned, sched.rows_empty) == (128, 121)
    sched.commit(plan)
    # finishing frees pages IMMEDIATELY and r3 admits next plan
    held = pool.available()
    sched.finish(plan.seqs[0])
    assert pool.available() == held + 2
    assert r1.done
    plan2, admitted2, _ = sched.plan_step()
    assert [s.req.id for s in admitted2] == [r3.id]


def test_scheduler_eviction_requeues_and_protects_planned():
    # 2 allocatable pages + sink: both prompts fit, growth does not
    pool = PagePool(num_pages=3, page_size=4)
    sched = Scheduler(pool, max_batch=2, max_pages_per_seq=2)
    r1 = Request([1, 2, 3, 4], max_new_tokens=4)
    r2 = Request([5, 6, 7], max_new_tokens=4)
    sched.submit(r1)
    sched.submit(r2)
    plan, admitted, evicted = sched.plan_step()
    assert len(admitted) == 2 and not evicted
    sched.commit(plan)
    # r1 decodes into a second page: zero free pages -> the YOUNGEST
    # running sequence (r2) is preempted and requeued at the front
    plan.seqs[0].tokens.append(10)
    plan.seqs[1].tokens.append(11)
    plan2, _, evicted2 = sched.plan_step()
    assert [s.req.id for s in evicted2] == [r2.id]
    assert r2.evictions == 1
    # the victim is NOT in the plan (its pages were reallocated) and
    # the protected grower is
    assert [s.req.id for s in plan2.seqs] == [r1.id]
    assert sched.queue_depth() == 1
    sched.commit(plan2)
    # finish r1 -> r2 re-admits and re-prefills its kept tokens
    sched.finish(plan2.seqs[0])
    plan3, admitted3, _ = sched.plan_step()
    assert [s.req.id for s in admitted3] == [r2.id]
    assert plan3.q_lens.tolist()[0] == 3


def test_request_too_long_fails_fast():
    pool = PagePool(num_pages=8, page_size=4)
    sched = Scheduler(pool, max_batch=2, max_pages_per_seq=2)
    r = Request(list(range(6)), max_new_tokens=4)   # 10 > 2*4
    sched.submit(r)
    assert r.done
    with pytest.raises(RuntimeError, match="at most 8"):
        r.wait(timeout=1)


def test_submit_respects_position_embedding_cap():
    pool = PagePool(num_pages=8, page_size=4)
    # page capacity is 2*4 == 8 but the model's position tables stop
    # at 6: admission must use the tighter bound (jnp.take would clip
    # out-of-range positions silently, not raise)
    sched = Scheduler(pool, max_batch=2, max_pages_per_seq=2,
                      max_seq_len=6)
    r = Request([1, 2, 3, 4], max_new_tokens=3)     # 7 > 6
    sched.submit(r)
    assert r.done
    with pytest.raises(RuntimeError, match="at most 6"):
        r.wait(timeout=1)
    ok = Request([1, 2, 3, 4], max_new_tokens=2)    # exactly 6 fits
    sched.submit(ok)
    assert not ok.done and sched.queue_depth() == 1


def test_admission_reclaims_cache_only_pages():
    # REGRESSION: a pool held ENTIRELY by cache-only prompt pages
    # (refcount 1, running batch drained) must not wedge admission —
    # _admit_one has to reclaim through the prefix cache instead of
    # bailing on the raw free-list count, else new requests hang until
    # client timeout.
    pool = PagePool(num_pages=5, page_size=4)       # 4 allocatable + sink
    cache = PrefixCache(pool)
    sched = Scheduler(pool, max_batch=2, max_pages_per_seq=2,
                      prefix_cache=cache)
    for base in (0, 100, 200, 300):                 # pin every free page
        page = pool.alloc()
        cache.insert([base, base + 1, base + 2, base + 3], [page])
        pool.unref(page)                # owner done; cache ref remains
    assert pool.available() == 0 and len(cache) == 4
    r = Request(list(range(400, 405)), max_new_tokens=2)  # 2 fresh pages
    sched.submit(r)
    plan, admitted, evicted = sched.plan_step()
    assert plan is not None
    assert [s.req.id for s in admitted] == [r.id] and not evicted
    assert cache.stats()["reclaimed"] == 2          # LRU pair freed
    sched.commit(plan)


def test_request_finish_is_idempotent():
    # stop() and an in-flight step can both finish a request; the
    # second call must not clobber state or push a second sentinel
    r = Request([1], max_new_tokens=1)
    r._emit(5)
    r._finish()
    r._finish(error="late step")
    assert r.wait(timeout=1) == [5] and r.error is None
    assert list(r.stream(timeout=0.1)) == [5]
    assert r._queue.empty()             # exactly one None sentinel


# ---------------------------------------------------------------------------
# prefix cache
# ---------------------------------------------------------------------------

def test_prefix_cache_share_release_reuse_lifecycle():
    pool = PagePool(num_pages=10, page_size=4)
    cache = PrefixCache(pool)
    prompt = list(range(11))               # 2 full pages + partial
    pages = [pool.alloc(), pool.alloc(), pool.alloc()]
    assert cache.insert(prompt, pages) == 2    # partial page not cached
    assert pool.refcount(pages[0]) == 2 and pool.refcount(pages[2]) == 1

    # full match on the shared prefix
    assert cache.match(prompt) == pages[:2]
    # partial overlap: first page shared, second diverges
    other = prompt[:4] + [99, 98, 97, 96, 1, 2]
    assert cache.match(other) == pages[:1]
    # owner releases: cache refs keep the full pages alive
    for p in pages:
        pool.unref(p)
    assert pool.refcount(pages[0]) == 1 and pool.refcount(pages[2]) == 0
    # reuse: a later identical prompt still matches
    assert cache.match(prompt) == pages[:2]
    # pressure reclaim frees cache-only pages LRU-first
    freed = cache.reclaim(2)
    assert freed == 2 and len(cache) == 0
    assert pool.refcount(pages[0]) == 0


def test_prefix_cache_hash_collision_never_shares():
    pool = PagePool(num_pages=10, page_size=4)
    cache = PrefixCache(pool, hash_fn=lambda prev, toks: "SAME")
    a = pool.alloc()
    cache.insert([1, 2, 3, 4], [a])
    # different content, same (degenerate) hash: must MISS, not share
    assert cache.match([5, 6, 7, 8]) == []
    assert cache.stats()["collisions"] == 1
    assert cache.match([1, 2, 3, 4]) == [a]


def test_prefix_cache_skips_prefill_flops(gpt_model):
    """A shared-prefix request must skip the prefill work: the
    dispatch stream's serving_prefill markers carry the REAL fed-token
    counts (core.dispatch.observe_op_stream)."""
    from paddle_tpu.core.dispatch import observe_op_stream
    rs = np.random.RandomState(7)
    prompt = rs.randint(0, 128, (24,)).tolist()
    events = []
    engine = ServingEngine(gpt_model, max_batch=2, page_size=8)
    with engine, observe_op_stream(events.append):
        cold = engine.submit(prompt, max_new_tokens=4).wait(timeout=60)
        n_cold = sum(ev.in_avals[0][0][0] for ev in events
                     if ev.op_name == "serving_prefill")
        events.clear()
        warm = engine.submit(prompt, max_new_tokens=4).wait(timeout=60)
        n_warm = sum(ev.in_avals[0][0][0] for ev in events
                     if ev.op_name == "serving_prefill")
    assert cold == warm                    # sharing never changes tokens
    assert n_cold == 24
    # only the boundary token re-feeds (its page rewrite is value-
    # identical); 24 -> 1 is the skipped-prefill-FLOPs proof
    assert n_warm == 1
    assert engine.prefix_cache.stats()["hits"] >= 3


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------

def test_engine_matches_generate_gpt(gpt_model):
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 128, (n,)).tolist() for n in (5, 9, 16, 3)]
    want = _greedy_reference(gpt_model, prompts, 8)
    engine = ServingEngine(gpt_model, max_batch=4, page_size=8)
    with engine:
        reqs = [engine.submit(p, max_new_tokens=8) for p in prompts]
        got = [r.wait(timeout=120) for r in reqs]
    assert got == want


def test_engine_matches_generate_llama_gqa():
    from paddle_tpu.models import LlamaForCausalLM, llama_config
    paddle.seed(0)
    cfg = llama_config("tiny")
    assert cfg.num_kv_heads < cfg.num_heads       # GQA is exercised
    m = LlamaForCausalLM(cfg)
    m.eval()
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, cfg.vocab_size, (n,)).tolist()
               for n in (7, 12)]
    want = _greedy_reference(m, prompts, 6)
    engine = ServingEngine(m, max_batch=2, page_size=8)
    with engine:
        got = [engine.submit(p, max_new_tokens=6).wait(timeout=120)
               for p in prompts]
    assert got == want


def test_engine_eos_stops_and_frees_pages(gpt_model):
    # an eos the greedy run first emits MIDWAY so the truncation is
    # observable
    prompt, full, eos = _prompt_with_midway_eos(gpt_model)
    # eager generate() with the same eos is the parity oracle
    want_t = gpt_model.generate(Tensor(np.asarray([prompt], "int64")),
                                max_new_tokens=8, eos_token_id=eos,
                                decode_strategy="greedy")
    want = np.asarray(want_t._data)[0, len(prompt):].tolist()
    engine = ServingEngine(gpt_model, max_batch=2, page_size=8)
    with engine:
        free0 = engine.pool.available()
        req = engine.submit(prompt, max_new_tokens=8, eos_token_id=eos)
        got = req.wait(timeout=60)
        deadline = time.monotonic() + 5
        while engine.pool.available() < free0 and \
                time.monotonic() < deadline:
            time.sleep(0.01)
        # stop-on-EOS: truncated at the first eos, pages back in the
        # pool immediately
        assert got == want
        assert got[-1] == eos and eos not in got[:-1]
        assert len(got) < 8
        assert engine.pool.available() == free0


def test_engine_streams_tokens_incrementally(gpt_model):
    rs = np.random.RandomState(2)
    engine = ServingEngine(gpt_model, max_batch=2, page_size=8)
    with engine:
        req = engine.submit(rs.randint(0, 128, (6,)).tolist(),
                            max_new_tokens=5)
        seen = list(req.stream(timeout=60))
    assert len(seen) == 5 and seen == req.tokens
    assert req.first_token_at is not None
    assert req.finished_at >= req.first_token_at


def test_engine_eviction_under_pressure_keeps_tokens(gpt_model):
    """Page exhaustion preempts a sequence and requeues it; outputs
    stay token-for-token identical to the unpressured run."""
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, 128, (12,)).tolist() for _ in range(3)]
    want = _greedy_reference(gpt_model, prompts, 12)
    engine = ServingEngine(gpt_model, max_batch=3, page_size=8,
                           num_pages=8, max_pages_per_seq=4,
                           prefix_caching=False)
    with engine:
        reqs = [engine.submit(p, max_new_tokens=12) for p in prompts]
        got = [r.wait(timeout=120) for r in reqs]
    assert engine.scheduler.evictions >= 1
    assert got == want
    assert engine.pool.available() == engine.pool.num_pages - 1


def test_engine_temperature_sampling_runs(gpt_model):
    rs = np.random.RandomState(4)
    engine = ServingEngine(gpt_model, max_batch=2, page_size=8)
    with engine:
        req = engine.submit(rs.randint(0, 128, (6,)).tolist(),
                            max_new_tokens=6, temperature=1.0)
        toks = req.wait(timeout=60)
    assert len(toks) == 6
    assert all(0 <= t < 128 for t in toks)


def test_engine_emits_observability_events(gpt_model, tmp_path):
    from paddle_tpu.observability import events as obs_events
    rs = np.random.RandomState(5)
    set_flags({"FLAGS_observability_dir": str(tmp_path)})
    try:
        engine = ServingEngine(gpt_model, max_batch=2, page_size=8)
        with engine:
            engine.submit(rs.randint(0, 128, (9,)).tolist(),
                          max_new_tokens=4).wait(timeout=60)
    finally:
        set_flags({"FLAGS_observability_dir": ""})
    kinds = [e["kind"] for e in obs_events.read_events(str(tmp_path))]
    assert "serving_admit" in kinds
    assert "batch_step" in kinds
    admits = [e for e in obs_events.read_events(str(tmp_path))
              if e["kind"] == "serving_admit"]
    assert admits[0]["prompt_len"] == 9


# ---------------------------------------------------------------------------
# HTTP /generate (engine mode)
# ---------------------------------------------------------------------------

@pytest.fixture
def http_engine(gpt_model, flags_guard):
    from paddle_tpu.inference.serving import InferenceServer
    set_flags({"FLAGS_serving_engine": True})
    engine = ServingEngine(gpt_model, max_batch=4, page_size=8)
    engine.start()
    srv = InferenceServer(engine=engine, max_in_flight=16).start()
    yield srv, engine
    try:
        srv.stop()
    finally:
        engine.stop()


def test_generate_http_stream_and_nonstream(http_engine, gpt_model):
    from paddle_tpu.inference.serving import generate_http
    srv, _ = http_engine
    rs = np.random.RandomState(0)
    prompt = rs.randint(0, 128, (9,)).tolist()
    [want] = _greedy_reference(gpt_model, [prompt], 6)
    # streaming NDJSON
    got = list(generate_http(srv.url, prompt, max_new_tokens=6))
    assert got == want
    # non-streaming JSON body
    body = json.dumps({"input_ids": prompt, "max_new_tokens": 6,
                       "stream": False}).encode()
    req = urllib.request.Request(srv.url + "/generate", data=body,
                                 method="POST")
    with urllib.request.urlopen(req, timeout=30) as r:
        payload = json.loads(r.read())
    assert payload["tokens"] == want
    # health surfaces the engine stats
    with urllib.request.urlopen(srv.url + "/health", timeout=10) as r:
        h = json.loads(r.read())
    assert h["engine"]["queue_depth"] == 0
    # /metrics exports the engine families
    with urllib.request.urlopen(srv.url + "/metrics", timeout=10) as r:
        text = r.read().decode()
    assert "paddle_serving_engine_request_seconds_bucket" in text
    assert "paddle_serving_engine_queue_depth" in text


def test_generate_http_bad_request_and_flag_gate(http_engine,
                                                 gpt_model):
    srv, _ = http_engine
    # malformed body -> 400
    req = urllib.request.Request(srv.url + "/generate",
                                 data=b"not json", method="POST")
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req, timeout=10)
    assert e.value.code == 400
    # over-long request -> 400 at admission, not a hang
    body = json.dumps({"input_ids": list(range(1000)),
                       "max_new_tokens": 5000}).encode()
    req = urllib.request.Request(srv.url + "/generate", data=body,
                                 method="POST")
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req, timeout=10)
    assert e.value.code == 400
    # flag off -> 404 (the engine route is opt-in)
    set_flags({"FLAGS_serving_engine": False})
    body = json.dumps({"input_ids": [1, 2, 3]}).encode()
    req = urllib.request.Request(srv.url + "/generate", data=body,
                                 method="POST")
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req, timeout=10)
    assert e.value.code == 404
    set_flags({"FLAGS_serving_engine": True})


def test_stop_drains_inflight_stream_and_sheds_late_arrivals(
        gpt_model, flags_guard):
    """The drain satellite: stop() must finish an in-flight STREAMING
    response before closing the socket, while a late arrival answers
    503 + Retry-After exactly like the non-streaming path."""
    from paddle_tpu.inference.serving import (InferenceServer,
                                              generate_http)
    set_flags({"FLAGS_serving_engine": True})
    engine = ServingEngine(gpt_model, max_batch=2, page_size=8)
    engine.start()
    # max_in_flight=1: the stream occupies the only slot, so the late
    # arrival hits the same 503 gate stop()'s _closing flag uses
    srv = InferenceServer(engine=engine, max_in_flight=1).start()
    rs = np.random.RandomState(0)
    result = {}

    def _long_stream():
        result["toks"] = list(generate_http(
            srv.url, rs.randint(0, 128, (8,)).tolist(),
            max_new_tokens=24, retries=1))

    t = threading.Thread(target=_long_stream)
    t.start()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:          # wait until admitted
        with srv._state:
            if srv._in_flight == 1:
                break
        time.sleep(0.005)
    body = json.dumps({"input_ids": [1, 2, 3]}).encode()
    req = urllib.request.Request(srv.url + "/generate", data=body,
                                 method="POST")
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req, timeout=10)
    assert e.value.code == 503
    assert e.value.headers.get("Retry-After") == "1"
    # stop() must DRAIN the stream: all 24 tokens arrive, no truncation
    stopper = threading.Thread(target=lambda: srv.stop(drain_timeout=30))
    stopper.start()
    t.join(timeout=60)
    stopper.join(timeout=60)
    engine.stop()
    assert len(result.get("toks", [])) == 24


# ---------------------------------------------------------------------------
# PTL701 — serving step-loop host-sync rule
# ---------------------------------------------------------------------------

_PTL701_BAD = '''
import numpy as np

def run_step(plan, tokens, finished):
    host = np.asarray(tokens)
    if bool(finished.all()):
        return host
    while finished.any():
        pass
    return tokens.item()
'''

_PTL701_OK = '''
import numpy as np

def run_step(plan, tokens):
    toks = np.asarray(tokens)  # noqa: PTL701 - admission boundary
    return toks

def build_tables(seqs):
    # host bookkeeping OUTSIDE step-loop functions is fine
    return np.asarray([s.pages for s in seqs])
'''


@pytest.mark.lint
def test_ptl701_flags_host_syncs_in_step_loops():
    from paddle_tpu.analysis.lint import lint_source
    findings = lint_source(_PTL701_BAD,
                           filename="paddle_tpu/serving/scheduler.py")
    codes = [f.code for f in findings]
    assert codes.count("PTL701") == 4      # asarray, all(), any(), item
    lines = sorted(f.line for f in findings if f.code == "PTL701")
    assert lines == [5, 6, 8, 10]


@pytest.mark.lint
def test_ptl701_noqa_and_non_step_functions_pass():
    from paddle_tpu.analysis.lint import lint_source
    findings = lint_source(_PTL701_OK,
                           filename="paddle_tpu/serving/engine.py")
    assert not [f for f in findings if f.code == "PTL701"]
    # outside SERVING_GLOBS the rule stays silent entirely
    findings = lint_source(_PTL701_BAD,
                           filename="paddle_tpu/tensor/math.py")
    assert not [f for f in findings if f.code == "PTL701"]


@pytest.mark.lint
def test_serving_package_is_ptl701_clean():
    import os

    import paddle_tpu
    from paddle_tpu.analysis.lint import lint_paths
    pkg = os.path.join(os.path.dirname(paddle_tpu.__file__), "serving")
    gen = os.path.join(os.path.dirname(paddle_tpu.__file__), "models",
                       "generation.py")
    findings = [f for f in lint_paths([pkg, gen])
                if f.code == "PTL701"]
    assert findings == []


_PTL701_FUSED_BAD = '''
import numpy as np

def build_fused_thing(plan):
    return np.asarray(plan)

def make_window(carry, finished):
    if finished.all():
        return carry.item()
'''


@pytest.mark.lint
def test_ptl701_covers_fused_window_builders():
    """The fused-loop builder names (*fused*/*window*) are PTL701-hot
    in BOTH the serving files and models/generation.py — a host sync
    inside the compiled window body can't creep in unseen."""
    from paddle_tpu.analysis.lint import lint_source
    for fname in ("paddle_tpu/serving/engine.py",
                  "paddle_tpu/models/generation.py"):
        findings = [f for f in lint_source(_PTL701_FUSED_BAD,
                                           filename=fname)
                    if f.code == "PTL701"]
        assert len(findings) == 3, (fname, findings)
        assert sorted(f.line for f in findings) == [5, 8, 9]


@pytest.mark.lint
def test_ptl701_generation_scope_spares_eager_paths():
    """In models/generation.py only *fused*/*window* names are hot —
    generate()'s eager loop legitimately syncs at its hoisted stop
    checks and step/loop helpers there stay out of scope."""
    from paddle_tpu.analysis.lint import lint_source
    src = ("import numpy as np\n"
           "def generate(logits, finished):\n"
           "    if bool(finished.all()):\n"
           "        return np.asarray(logits)\n"
           "def decode_step(x):\n"
           "    return np.asarray(x)\n")
    findings = [f for f in lint_source(
        src, filename="paddle_tpu/models/generation.py")
        if f.code == "PTL701"]
    assert findings == []
    # the SAME source inside serving scope flags the step function
    findings = [f for f in lint_source(
        src, filename="paddle_tpu/serving/engine.py")
        if f.code == "PTL701"]
    assert [f.line for f in findings] == [6]


# ---------------------------------------------------------------------------
# persistent-program serving step (FLAGS_serving_fused_steps)
# ---------------------------------------------------------------------------

@pytest.fixture
def fused_flags():
    keep = get_flags(["FLAGS_serving_fused_steps"])
    set_flags({"FLAGS_serving_fused_steps": 4})
    yield
    set_flags(keep)


def test_fused_engine_matches_generate_gpt(gpt_model, fused_flags):
    """Token-for-token parity with eager generate() when the decode
    loop runs as fused multi-iteration windows."""
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 128, (n,)).tolist() for n in (5, 9, 16, 3)]
    want = _greedy_reference(gpt_model, prompts, 8)
    engine = ServingEngine(gpt_model, max_batch=4, page_size=8)
    with engine:
        reqs = [engine.submit(p, max_new_tokens=8) for p in prompts]
        got = [r.wait(timeout=120) for r in reqs]
    assert got == want
    # the fused path actually engaged: iterations outnumber dispatches
    assert engine._c_steps.value > engine._c_dispatch.value


def test_fused_engine_matches_generate_llama_gqa(fused_flags):
    from paddle_tpu.models import LlamaForCausalLM, llama_config
    paddle.seed(0)
    cfg = llama_config("tiny")
    m = LlamaForCausalLM(cfg)
    m.eval()
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, cfg.vocab_size, (n,)).tolist()
               for n in (7, 12)]
    want = _greedy_reference(m, prompts, 6)
    engine = ServingEngine(m, max_batch=2, page_size=8)
    with engine:
        got = [engine.submit(p, max_new_tokens=6).wait(timeout=120)
               for p in prompts]
    assert got == want


def test_fused_engine_temperature_matches_single_step(gpt_model):
    """RNG-stream parity: the fused window splits the key once per
    iteration exactly like the single-step program, so SAMPLED outputs
    (not just greedy) match the single-step engine draw for draw."""
    rs = np.random.RandomState(4)
    prompts = [rs.randint(0, 128, (n,)).tolist() for n in (6, 11)]

    def run(fused):
        keep = get_flags(["FLAGS_serving_fused_steps"])
        set_flags({"FLAGS_serving_fused_steps": fused})
        try:
            engine = ServingEngine(gpt_model, max_batch=2, page_size=8,
                                   prefix_caching=False, seed=42)
            with engine:
                reqs = [engine.submit(p, max_new_tokens=7,
                                      temperature=0.8)
                        for p in prompts]
                return [r.wait(timeout=120) for r in reqs]
        finally:
            set_flags(keep)

    assert run(1) == run(4)


def test_fused_engine_eos_mid_window_early_exit(gpt_model, fused_flags,
                                                tmp_path):
    """EOS sampled mid-window: the compiled loop exits at that
    iteration (not at the window bound), output truncates exactly like
    the eager oracle, and the batch_step record says why it exited."""
    from paddle_tpu.observability import events as obs_events
    prompt, full, eos = _prompt_with_midway_eos(gpt_model)
    want_t = gpt_model.generate(Tensor(np.asarray([prompt], "int64")),
                                max_new_tokens=8, eos_token_id=eos,
                                decode_strategy="greedy")
    want = np.asarray(want_t._data)[0, len(prompt):].tolist()
    set_flags({"FLAGS_observability_dir": str(tmp_path)})
    try:
        engine = ServingEngine(gpt_model, max_batch=2, page_size=8)
        with engine:
            free0 = engine.pool.available()
            got = engine.submit(prompt, max_new_tokens=8,
                                eos_token_id=eos).wait(timeout=60)
            deadline = time.monotonic() + 5
            while engine.pool.available() < free0 and \
                    time.monotonic() < deadline:
                time.sleep(0.01)
            assert engine.pool.available() == free0
    finally:
        set_flags({"FLAGS_observability_dir": ""})
    assert got == want
    assert got[-1] == eos and len(got) < 8
    steps = [e for e in obs_events.read_events(str(tmp_path))
             if e["kind"] == "batch_step"]
    # the last window broke on the finish predicate, not the bound
    windowed = [e for e in steps if e["exit_reason"] != "single_step"]
    assert windowed and windowed[-1]["exit_reason"] == "finished"
    assert any(e["fused_steps"] > 1 for e in steps)
    assert all(e["exit_reason"] in ("single_step", "finished",
                                    "window_full", "page_limit")
               for e in steps)


def test_fused_engine_eviction_pressure_keeps_tokens(gpt_model,
                                                     fused_flags):
    """Under page pressure the window budget clamps to 1 and the
    byte-identical single-step path (with its eviction machinery)
    runs — outputs still match the unpressured oracle."""
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, 128, (12,)).tolist() for _ in range(3)]
    want = _greedy_reference(gpt_model, prompts, 12)
    engine = ServingEngine(gpt_model, max_batch=3, page_size=8,
                           num_pages=8, max_pages_per_seq=4,
                           prefix_caching=False)
    with engine:
        reqs = [engine.submit(p, max_new_tokens=12) for p in prompts]
        got = [r.wait(timeout=120) for r in reqs]
    assert engine.scheduler.evictions >= 1
    assert got == want
    assert engine.pool.available() == engine.pool.num_pages - 1


def test_fused_engine_prefix_cache_hit_parity(gpt_model, fused_flags):
    """Prefix-cache sharing composes with fused windows: the warm
    request still skips prefill FLOPs and outputs stay identical."""
    from paddle_tpu.core.dispatch import observe_op_stream
    rs = np.random.RandomState(7)
    prompt = rs.randint(0, 128, (24,)).tolist()
    events = []
    engine = ServingEngine(gpt_model, max_batch=2, page_size=8)
    with engine, observe_op_stream(events.append):
        cold = engine.submit(prompt, max_new_tokens=6).wait(timeout=60)
        events.clear()
        warm = engine.submit(prompt, max_new_tokens=6).wait(timeout=60)
        n_warm = sum(ev.in_avals[0][0][0] for ev in events
                     if ev.op_name == "serving_prefill")
    assert cold == warm
    assert n_warm == 1


def test_fused_window_exactly_one_host_sync_per_window(gpt_model,
                                                       fused_flags):
    """The headline contract: ONE device read per fused window, proven
    off the dispatch stream.  Each serving_host_sync marker's payload
    length is the iteration count that single read covered — for one
    request at max_new=8 with windows of 4 the schedule is exactly
    prefill(1) + window(4) + window(3, budget-finish)."""
    from paddle_tpu.core.dispatch import observe_op_stream
    rs = np.random.RandomState(6)
    prompt = rs.randint(0, 128, (10,)).tolist()
    syncs = []

    def hook(ev):
        if ev.op_name == "serving_host_sync":
            syncs.append(int(ev.in_avals[0][0][0]))

    engine = ServingEngine(gpt_model, max_batch=2, page_size=8,
                           prefix_caching=False)
    with engine, observe_op_stream(hook):
        got = engine.submit(prompt, max_new_tokens=8).wait(timeout=60)
    assert len(got) == 8
    assert syncs == [1, 4, 3]
    # and dispatch bookkeeping agrees: 3 launches, 8 iterations
    assert engine._c_dispatch.value == 3
    assert engine._c_steps.value == 8


def test_batch_step_events_carry_fused_fields(gpt_model, fused_flags,
                                              tmp_path):
    from paddle_tpu.analysis.perf_features import batch_step_features
    from paddle_tpu.observability import events as obs_events
    rs = np.random.RandomState(5)
    set_flags({"FLAGS_observability_dir": str(tmp_path)})
    try:
        engine = ServingEngine(gpt_model, max_batch=2, page_size=8)
        with engine:
            engine.submit(rs.randint(0, 128, (9,)).tolist(),
                          max_new_tokens=6).wait(timeout=60)
    finally:
        set_flags({"FLAGS_observability_dir": ""})
    steps = [e for e in obs_events.read_events(str(tmp_path))
             if e["kind"] == "batch_step"]
    assert steps
    # the prefill iteration is single-step; decode windows fuse
    assert steps[0]["fused_steps"] == 1
    assert steps[0]["exit_reason"] == "single_step"
    assert any(e["fused_steps"] > 1 for e in steps)
    # the featurizer learns the new column (and defaults it to 1.0 on
    # pre-fused logs so PR 9's model stays calibrated)
    feats = batch_step_features(steps[-1])
    assert feats["fused_steps"] == float(steps[-1]["fused_steps"])
    legacy = dict(steps[-1])
    legacy.pop("fused_steps")
    assert batch_step_features(legacy)["fused_steps"] == 1.0


@pytest.mark.parametrize("fused", [1, 4], ids=["single-step", "fused"])
def test_attn_blocks_of_a_plan_are_the_blocks_the_kernel_walks(
        gpt_model, flags_guard, tmp_path, fused):
    """``batch_step.attn_blocks`` is ``walk_blocks`` of the plan's
    lengths a layer (the kernel's own bounds), ``engine.stats()`` sums
    it, and a fused window counts every iteration."""
    import types
    from paddle_tpu.observability import events as obs_events
    from paddle_tpu.ops.pallas import ragged_paged_attention as rpa
    rs = np.random.RandomState(7)
    keep = get_flags(["FLAGS_serving_fused_steps"])
    set_flags({"FLAGS_observability_dir": str(tmp_path),
               "FLAGS_serving_fused_steps": fused})
    try:
        engine = ServingEngine(gpt_model, max_batch=3, page_size=8,
                               max_prefill_chunk=16)
        with engine:
            reqs = [engine.submit(rs.randint(0, 128, (n,)).tolist(),
                                  max_new_tokens=6) for n in (40, 9, 21)]
            for r in reqs:
                r.wait(timeout=120)
    finally:
        set_flags({"FLAGS_observability_dir": "", **keep})
    steps = [e for e in obs_events.read_events(str(tmp_path))
             if e["kind"] == "batch_step"]
    assert steps and all(e["attn_blocks"] >= e["batch"] * 2 for e in steps)
    assert any(e["fused_steps"] > 1 for e in steps) == (fused > 1)
    assert sum(e["attn_blocks"] for e in steps) \
        == engine.stats()["attn_blocks"]
    # the launches' live tiles and grid steps ride beside it
    for key in ("attn_tiles", "attn_tile_slots"):
        assert sum(e[key] for e in steps) == engine.stats()[key] > 0
    assert all(e["batch"] * 2 * e["fused_steps"] <= e["attn_tiles"]
               <= e["attn_tile_slots"] for e in steps)
    # a plan by hand: a chunk of 16 beside two decoding lanes and an idle
    # one, two layers of 4 heads of 16
    plan = types.SimpleNamespace(
        kv_lens=np.array([40, 9, 100, 0]), q_lens=np.array([16, 1, 1, 0]),
        q_width=16)
    a_layer = rpa.walk_blocks(plan.kv_lens, plan.q_lens, 16, 4, 16, 4, 8, 0)
    assert a_layer == 3      # every tile of 16 rows inside one block
    assert engine._attn_blocks(plan) == 2 * a_layer
    plan.q_lens, plan.q_width = np.array([1, 1, 1, 0]), 1
    steps_3 = sum(rpa.walk_blocks(plan.kv_lens + j, plan.q_lens, 1, 4, 16,
                                  4, 8, 0) for j in range(3))
    assert engine._attn_blocks(plan, 3) == 2 * steps_3


def test_attn_tiles_of_a_plan_are_the_launches_live_tiles_and_grid_steps(
        gpt_model):
    """``attn_tiles`` / ``attn_tile_slots`` are host arithmetic on the
    plan's ``q_lens`` by the kernel's own tiling, a layer: a chunk of 64
    beside seven decoding lanes in ``step_rows(64, 8)`` = 128 rows is
    1 + 7 live tiles of 64 rows in ``min(8 * 1, 128 / 64 + 8)`` = 8 grid
    steps at this model's 4 heads of 16, and 2 + 7 of ``min(8 * 2,
    4 + 8)`` = 12 at the longgen cell's tiles of 32 rows;
    ``attn_blocks`` is what it was."""
    import types
    from paddle_tpu.ops.pallas import ragged_paged_attention as rpa
    from paddle_tpu.serving.scheduler import step_rows
    engine = ServingEngine(gpt_model, max_batch=8, page_size=8,
                           max_prefill_chunk=64)
    q_lens = np.array([1, 1, 64, 1, 1, 1, 1, 1])
    plan = types.SimpleNamespace(
        kv_lens=np.array([9, 30, 64, 12, 70, 8, 100, 41]), q_lens=q_lens,
        q_width=64, rows=step_rows(64, 8))
    assert plan.rows == 128
    a_layer = rpa.launch_tiles(q_lens, 128, 64, 4, 16, 4, 8, 0)
    assert a_layer == (8, 8)
    assert engine._attn_tiles(plan) == (2 * 8, 2 * 8)
    assert engine._attn_tiles(plan, 3) == (6 * 8, 6 * 8)
    blocks = rpa.walk_blocks(plan.kv_lens, q_lens, 64, 4, 16, 4, 8, 0)
    assert engine._attn_blocks(plan) == 2 * blocks == 2 * 8
    # the same plan at the longgen cell's heads: tiles of 32 rows
    assert rpa.launch_tiles(q_lens, 128, 64, 64, 256, 4, 16, 73, 128) \
        == (2 + 7, min(8 * 2, 4 + 8))
    # and its own Q=1,024 step: 41 grid steps a layer where 256 stood
    wide = np.array([1, 1, 1024, 1, 1, 1, 1, 1])
    assert rpa.launch_tiles(wide, step_rows(1024, 8), 1024, 64, 256, 4, 16,
                            73, 128) == (32 + 7, 41)
    idle = np.array([1, 0, 1, 0, 0, 0, 0, 0])
    assert rpa.launch_tiles(idle, 8, 1, 64, 256, 4, 16, 73, 128) == (2, 8)


def test_scheduler_window_budget_clamps_pages_and_budget():
    """window_budget: the width obeys the tightest of the remaining
    token budget and the page pool, pre-allocates the window's pages
    and refreshes the plan's page tables."""
    def decode_plan(sched, req):
        sched.submit(req)
        plan, _, _ = sched.plan_step()        # prefill step
        sched.commit(plan)
        seq = plan.seqs[0]
        seq.tokens.append(7)
        req._emit(7)                           # one sampled token out
        plan, _, _ = sched.plan_step()         # steady-state decode
        assert plan.n_prefill == 0 and plan.q_width == 1 \
            and plan.tok.shape == (sched.max_batch,)
        return plan

    # page-limited: 3 usable pages, prompt holds 2 -> w clamps to 6
    pool = PagePool(4, 4)
    sched = Scheduler(pool, max_batch=2, max_pages_per_seq=8)
    plan = decode_plan(sched, Request(list(range(6)),
                                      max_new_tokens=20))
    w, reason = sched.window_budget(plan, 16)
    assert (w, reason) == (6, "page_limit")
    seq = plan.seqs[0]
    assert len(seq.pages) == 3                 # ceil((6+6)/4) grown
    assert list(plan.tables[0, :3]) == seq.pages
    # early exit leaves over-allocated pages -> commit_window trims
    sched.commit_window(plan, 2)
    assert seq.kv_len == 8 and len(seq.pages) == 2

    # budget-limited: only 3 tokens of budget left -> w = 3
    pool = PagePool(64, 4)
    sched = Scheduler(pool, max_batch=2, max_pages_per_seq=8)
    plan = decode_plan(sched, Request(list(range(6)),
                                      max_new_tokens=4))
    w, _ = sched.window_budget(plan, 16)
    assert w == 3

    # w == 1 means "run the single-step path": nothing allocated
    pool = PagePool(64, 4)
    sched = Scheduler(pool, max_batch=2, max_pages_per_seq=8)
    plan = decode_plan(sched, Request(list(range(6)),
                                      max_new_tokens=2))
    pages_before = len(plan.seqs[0].pages)
    w, _ = sched.window_budget(plan, 16)
    assert w == 1
    assert len(plan.seqs[0].pages) == pages_before


class _CountingPerfModel:
    def __init__(self):
        self.calls = 0

    def has(self, family):
        return family == "batch_step"

    def predict(self, family, feats):
        self.calls += 1
        return 0.001


def test_scheduler_prestage_commit_and_discard():
    """Double-buffered plan: the admission prediction computed while
    the device runs is consumed at the next boundary when the window
    exited as projected, and discarded when the state moved."""
    model = _CountingPerfModel()
    pool = PagePool(64, 4)
    sched = Scheduler(pool, max_batch=2, max_pages_per_seq=8,
                      perf_model=model, max_step_cost_s=1.0)
    sched.submit(Request([1, 2, 3], max_new_tokens=8))
    plan, _, _ = sched.plan_step()
    sched.commit(plan)
    seq = plan.seqs[0]
    seq.tokens.append(5)
    seq.req._emit(5)
    # decode plan BEFORE new work arrives, then a request queues while
    # the (notional) window runs — exactly the engine's sequence
    plan, _, _ = sched.plan_step()
    sched.commit(plan)
    seq.tokens.append(6)
    seq.req._emit(6)
    sched.submit(Request([4, 5, 6], max_new_tokens=8))

    # commit path: pre-stage, nothing changes, next plan admits the
    # head off the STAGED prediction (no fresh predict call)
    calls0 = model.calls
    sched.prestage_plan(plan, 4)
    assert model.calls == calls0 + 1
    plan2, admitted, _ = sched.plan_step()
    assert sched.prestage_commits == 1
    assert [s.req.id for s in admitted] and model.calls == calls0 + 1
    assert admitted[0].predicted_cost_s == 0.001

    # discard path: pre-stage, then the projected state breaks (a
    # finish frees pages + a slot) -> staged work is dropped
    sched.submit(Request([7, 8, 9], max_new_tokens=8))
    sched.commit(plan2)
    for s in plan2.seqs:
        if not s.req.done:
            s.tokens.append(9)
            s.req._emit(9)
    sched.prestage_plan(plan2, 4)
    sched.finish(seq)                    # projection invalidated
    before = sched.prestage_discards
    sched.plan_step()
    assert sched.prestage_discards == before + 1


def test_fused_engine_prestages_plans(gpt_model, fused_flags):
    """Queued work while windows run: the engine pre-stages plans on
    the host during device windows (visible in stats())."""
    rs = np.random.RandomState(8)
    prompts = [rs.randint(0, 128, (8,)).tolist() for _ in range(4)]
    engine = ServingEngine(gpt_model, max_batch=2, page_size=8,
                           prefix_caching=False)
    with engine:
        reqs = [engine.submit(p, max_new_tokens=10) for p in prompts]
        got = [r.wait(timeout=120) for r in reqs]
    assert all(len(g) == 10 for g in got)
    stats = engine.stats()
    assert stats["prestaged_plans"] >= 1
    assert stats["prestage_commits"] + stats["prestage_discards"] \
        <= stats["prestaged_plans"]


# ---------------------------------------------------------------------------
# fault containment: quarantine, watchdog, deadlines, health machine
# ---------------------------------------------------------------------------

@pytest.fixture
def chaos(tmp_path):
    """Observability capture plus guaranteed fault-schedule and
    timeout-flag cleanup — a leaked schedule would poison every test
    that follows.  Runs the whole scenario under FLAGS_lock_sanitizer:
    every engine built inside the test gets instrumented locks, so a
    lock-order inversion anywhere in the relaunch/quarantine machinery
    fails the test with a LockOrderError instead of hanging it."""
    from paddle_tpu.resilience import faults
    from paddle_tpu.observability.lockwatch import reset_lockwatch
    set_flags({"FLAGS_observability_dir": str(tmp_path),
               "FLAGS_lock_sanitizer": True})
    reset_lockwatch()
    try:
        yield str(tmp_path)
    finally:
        faults.install_schedule(None)
        set_flags({"FLAGS_observability_dir": "",
                   "FLAGS_serving_step_timeout_s": 0.0,
                   "FLAGS_lock_sanitizer": False})
        reset_lockwatch()


def _run_all(reqs, timeout=180):
    """wait() every request; returns (results, errored_indices) with
    None in the slot of each failed stream."""
    results, errs = [], []
    for i, r in enumerate(reqs):
        try:
            results.append(r.wait(timeout=timeout))
        except (RuntimeError, TimeoutError):
            results.append(None)
            errs.append(i)
    return results, errs


@pytest.mark.chaos
def test_quarantine_bisection_isolates_offender(gpt_model, chaos):
    """The headline chaos contract: poison ONE of 8 co-batched streams
    (serving_step@3=exc pins sticky poison to a single request) — the
    7 innocents finish token-identical to an unpoisoned run, the
    offender alone fails, and the quarantine event names it."""
    from paddle_tpu.observability import events as obs_events
    from paddle_tpu.resilience import faults
    rs = np.random.RandomState(11)
    prompts = [rs.randint(0, 128, (n,)).tolist()
               for n in (4, 6, 8, 5, 7, 9, 3, 10)]
    want = _greedy_reference(gpt_model, prompts, 8)
    faults.install_schedule("serving_step@3=exc")
    engine = ServingEngine(gpt_model, max_batch=8, page_size=8)
    try:
        engine.start()
        reqs = [engine.submit(p, max_new_tokens=8) for p in prompts]
        results, errs = _run_all(reqs)
    finally:
        engine.stop(drain=False)
    assert len(errs) == 1                    # the offender fails ALONE
    bad = errs[0]
    assert reqs[bad].error_kind == "quarantined"
    for i in range(8):                       # innocents: token-exact
        if i != bad:
            assert results[i] == want[i], f"stream {i} diverged"
    st = engine.stats()
    assert st["quarantined"] == 1
    assert st["quarantined_prompts"] == 1
    evs = obs_events.read_events(chaos, kinds=["quarantine"])
    mine = [e for e in evs if e["action"] == "quarantined"]
    assert len(mine) == 1 and mine[0]["request"] == reqs[bad].id
    # the health machine walked ok -> quarantining -> degraded
    states = [e["state"] for e in obs_events.read_events(
        chaos, kinds=["health_transition"])]
    assert "quarantining" in states and "degraded" in states


def test_cold_dispatch_failure_fails_engine_not_requests(gpt_model):
    """A dispatch that raises while its program is still tracing or
    compiling is a PROGRAM fault (on the chip: a Mosaic or VMEM
    refusal, which would repeat for every request that reaches the
    same Q bucket): the engine fails loudly — health ``failed``, every
    request errored with the compiler's message, later submits refused
    — instead of bisecting the batch into one quarantine per
    request."""
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, 128, (n,)).tolist() for n in (5, 9, 7)]
    engine = ServingEngine(gpt_model, max_batch=4, page_size=8)

    def refuse(*a, **kw):
        raise RuntimeError("RESOURCE_EXHAUSTED: Ran out of memory in "
                           "memory space vmem")

    refuse.packed = refuse            # the entry the engine calls
    engine._step_fn = refuse
    with engine, pytest.warns(UserWarning, match="loop died"):
        reqs = [engine.submit(p, max_new_tokens=4) for p in prompts]
        for r in reqs:
            with pytest.raises(RuntimeError, match="memory space vmem"):
                r.wait(timeout=60)
        assert engine.health == "failed"
        stats = engine.stats()
        assert stats["quarantined"] == 0
        assert stats["quarantined_prompts"] == 0
        late = engine.submit(prompts[0], max_new_tokens=4)
        assert late.done and late.error_kind == "unhealthy"


@pytest.mark.chaos
def test_quarantined_prompt_rejected_at_admission(gpt_model, chaos):
    """Repeat offender: the SAME prompt resubmitted after a quarantine
    is rejected at admission (by prompt hash) — and the engine keeps
    serving other work."""
    from paddle_tpu.observability import events as obs_events
    from paddle_tpu.resilience import faults
    rs = np.random.RandomState(12)
    poison_prompt = rs.randint(0, 128, (6,)).tolist()
    clean_prompt = rs.randint(0, 128, (5,)).tolist()
    [want_clean] = _greedy_reference(gpt_model, [clean_prompt], 6)
    faults.install_schedule("serving_step@1=exc")
    engine = ServingEngine(gpt_model, max_batch=2, page_size=8)
    try:
        engine.start()
        first = engine.submit(poison_prompt, max_new_tokens=6)
        with pytest.raises(RuntimeError, match="quarantined"):
            first.wait(timeout=120)
        again = engine.submit(poison_prompt, max_new_tokens=6)
        with pytest.raises(RuntimeError, match="quarantined"):
            again.wait(timeout=10)
        assert again.error_kind == "quarantined"
        clean = engine.submit(clean_prompt, max_new_tokens=6)
        assert clean.wait(timeout=120) == want_clean
    finally:
        engine.stop(drain=False)
    evs = obs_events.read_events(chaos, kinds=["quarantine"])
    assert [e for e in evs if e["action"] == "rejected"
            and e["request"] == again.id]
    assert engine.stats()["quarantined_prompts"] == 1


@pytest.mark.chaos
def test_nan_sentinel_quarantines_offending_lane(gpt_model, chaos):
    """On-device NaN-logits sentinel: a lane whose logits go NaN
    (injected via serving_step@2=nan) is quarantined alone — ragged
    attention never mixes lanes, so co-batched innocents are sound and
    token-exact, with no extra host read to detect it."""
    from paddle_tpu.observability import events as obs_events
    from paddle_tpu.resilience import faults
    rs = np.random.RandomState(13)
    prompts = [rs.randint(0, 128, (n,)).tolist() for n in (4, 6, 8, 5)]
    want = _greedy_reference(gpt_model, prompts, 8)
    faults.install_schedule("serving_step@2=nan")
    engine = ServingEngine(gpt_model, max_batch=4, page_size=8)
    try:
        engine.start()
        reqs = [engine.submit(p, max_new_tokens=8) for p in prompts]
        results, errs = _run_all(reqs)
    finally:
        engine.stop(drain=False)
    assert len(errs) == 1
    bad = errs[0]
    assert reqs[bad].error_kind == "quarantined"
    assert "nan_logits" in (reqs[bad].error or "")
    for i in range(4):
        if i != bad:
            assert results[i] == want[i]
    evs = obs_events.read_events(chaos, kinds=["quarantine"])
    assert [e for e in evs if e["reason"] == "nan_logits"]


@pytest.mark.chaos
def test_watchdog_relaunch_keeps_all_streams_exact(gpt_model, chaos):
    """Hung-step watchdog: a stalled dispatch trips the timeout, the
    iteration loop relaunches, every survivor requeues at the front —
    ALL streams still finish token-identical to the no-fault oracle
    (zero silent truncation)."""
    from paddle_tpu.observability import events as obs_events
    from paddle_tpu.resilience import faults
    rs = np.random.RandomState(14)
    prompts = [rs.randint(0, 128, (n,)).tolist() for n in (4, 6, 8, 5)]
    want = _greedy_reference(gpt_model, prompts, 8)
    faults.install_schedule("serving_step@4=stall:2")
    set_flags({"FLAGS_serving_step_timeout_s": 0.5})
    engine = ServingEngine(gpt_model, max_batch=4, page_size=8)
    try:
        engine.start()
        reqs = [engine.submit(p, max_new_tokens=8) for p in prompts]
        results, errs = _run_all(reqs)
    finally:
        engine.stop(drain=False)
    assert errs == []
    assert results == want                   # zero truncation, exact
    st = engine.stats()
    assert st["watchdog_relaunches"] == 1
    assert st["health"] == "degraded"
    evs = obs_events.read_events(chaos, kinds=["step_timeout"])
    assert len(evs) == 1 and evs[0]["relaunches"] == 1
    assert evs[0]["timeout_s"] == 0.5
    # the survivors were requeued (eviction-resume), not restarted
    assert all(r.evictions >= 1 for r in reqs)


@pytest.mark.chaos
def test_watchdog_relaunch_cap_fails_engine(gpt_model, chaos):
    """Past the relaunch cap the engine stops thrashing: health goes
    failed (terminal), every consumer fails loudly, and new submits
    are rejected — the fleet supervisor owns recovery from here."""
    from paddle_tpu.observability import events as obs_events
    from paddle_tpu.resilience import faults
    faults.install_schedule("serving_step@2=stall:2")
    set_flags({"FLAGS_serving_step_timeout_s": 0.3})
    engine = ServingEngine(gpt_model, max_batch=2, page_size=8,
                           max_watchdog_relaunches=0)
    try:
        engine.start()
        reqs = [engine.submit([1, 2, 3], max_new_tokens=8),
                engine.submit([4, 5, 6], max_new_tokens=8)]
        results, errs = _run_all(reqs, timeout=60)
    finally:
        engine.stop(drain=False)
    assert errs == [0, 1]                    # nobody hangs silently
    assert all(r.error_kind == "unhealthy" for r in reqs)
    assert engine.stats()["health"] == "failed"
    late = engine.submit([7, 8], max_new_tokens=2)
    with pytest.raises(RuntimeError, match="unhealthy"):
        late.wait(timeout=10)
    assert late.error_kind == "unhealthy"
    states = [e["state"] for e in obs_events.read_events(
        chaos, kinds=["health_transition"])]
    assert states[-1] == "failed"


def test_wait_timeout_cancels_and_raises():
    """satellite: a wait() timeout fails the request LOUDLY — the
    request is cancelled (not left running headless) and the consumer
    gets TimeoutError, never a silent partial stream."""
    req = Request([1, 2, 3], max_new_tokens=4)
    with pytest.raises(TimeoutError, match="cancelled"):
        req.wait(timeout=0.1)
    assert req.done
    assert req.error_kind == "cancelled"
    with pytest.raises(RuntimeError):
        req.wait(timeout=1)                  # already finished-in-error


def test_stream_timeout_cancels_and_raises():
    req = Request([1, 2, 3], max_new_tokens=4)
    it = req.stream(timeout=0.1)
    with pytest.raises(RuntimeError, match="timed out"):
        next(it)
    assert req.done and req.error_kind == "cancelled"


def test_deadline_cancels_mid_batch_and_frees_pages(gpt_model, chaos):
    """A request whose deadline expires mid-decode is cancelled from
    inside the loop: pages free immediately, the co-batched request is
    untouched, and the failure is a request_cancelled event + an
    error_kind="deadline" error on the consumer side."""
    from paddle_tpu.observability import events as obs_events
    rs = np.random.RandomState(15)
    p_ok = rs.randint(0, 128, (5,)).tolist()
    p_doomed = rs.randint(0, 128, (5,)).tolist()
    [want_ok] = _greedy_reference(gpt_model, [p_ok], 8)
    engine = ServingEngine(gpt_model, max_batch=2, page_size=8,
                           prefix_caching=False)
    try:
        engine.start()
        free0 = engine.pool.available()
        doomed = engine.submit(p_doomed, max_new_tokens=120,
                               deadline_s=0.3)
        ok = engine.submit(p_ok, max_new_tokens=8)
        assert ok.wait(timeout=120) == want_ok
        with pytest.raises(RuntimeError, match="deadline"):
            doomed.wait(timeout=60)
        assert doomed.error_kind == "deadline"
        deadline = time.monotonic() + 10
        while engine.pool.available() != free0 and \
                time.monotonic() < deadline:
            time.sleep(0.02)
        assert engine.pool.available() == free0   # pages all freed
    finally:
        engine.stop(drain=False)
    evs = obs_events.read_events(chaos, kinds=["request_cancelled"])
    mine = [e for e in evs if e["request"] == doomed.id]
    assert mine and "deadline" in mine[0]["reason"]
    assert mine[0]["deadline_s"] == 0.3


class _StubPerfModel:
    """Minimal learned-model stand-in: every batch step predicted to
    take ``step_s`` seconds."""

    def __init__(self, step_s):
        self.step_s = step_s

    def has(self, head):
        return True

    def predict(self, head, feats):
        return self.step_s


def test_deadline_doomed_rejected_up_front(gpt_model):
    """Predicted-cost admission: a request whose full decode cannot
    fit inside its deadline is rejected at submit, before burning a
    batch slot on a stream that must be cancelled mid-flight."""
    engine = ServingEngine(gpt_model, max_batch=2, page_size=8,
                           perf_model=_StubPerfModel(10.0))
    try:
        engine.start()
        req = engine.submit([1, 2, 3], max_new_tokens=8,
                            deadline_s=0.5)
        with pytest.raises(RuntimeError, match="deadline infeasible"):
            req.wait(timeout=10)
        assert req.error_kind == "deadline"
        # no deadline -> the same request is served normally
        free = engine.submit([1, 2, 3], max_new_tokens=4)
        assert len(free.wait(timeout=120)) == 4
    finally:
        engine.stop(drain=False)


def test_http_deadline_maps_to_503(gpt_model, flags_guard):
    """HTTP mapping: deadline_s rides the /generate spec and an
    infeasible deadline answers 503 + Retry-After (try again / try
    elsewhere), not 400 (the request itself is well-formed)."""
    from paddle_tpu.inference.serving import InferenceServer
    set_flags({"FLAGS_serving_engine": True})
    engine = ServingEngine(gpt_model, max_batch=2, page_size=8,
                           perf_model=_StubPerfModel(10.0))
    engine.start()
    srv = InferenceServer(engine=engine, max_in_flight=8).start()
    try:
        body = json.dumps({"input_ids": [1, 2, 3],
                           "max_new_tokens": 8,
                           "deadline_s": 0.25}).encode()
        req = urllib.request.Request(srv.url + "/generate", data=body,
                                     method="POST")
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=10)
        assert e.value.code == 503
        assert e.value.headers.get("Retry-After") is not None
    finally:
        srv.stop()
        engine.stop(drain=False)


@pytest.mark.chaos
def test_stop_detects_wedged_loop(gpt_model, chaos):
    """satellite: stop() on a wedged loop thread does not hang or lie
    — the failed join is detected, the flight recorder dumps, and the
    wedge is surfaced in stop()'s return and stats()."""
    from paddle_tpu.resilience import faults
    # the loop dispatches step 2 before it reads step 1: the prefill's
    # token reaches the client just before dispatch 3, the one to stall
    faults.install_schedule("serving_step@3=stall:3")
    engine = ServingEngine(gpt_model, max_batch=2, page_size=8)
    try:
        engine.start()
        req = engine.submit([1, 2, 3, 4], max_new_tokens=6)
        deadline = time.monotonic() + 60
        while not req.tokens and time.monotonic() < deadline:
            time.sleep(0.02)                 # wait for prefill commit
        assert req.tokens                    # dispatch 3 (the stall) is next
        time.sleep(0.3)                      # let the loop enter it
        st = engine.stop(drain=False, join_timeout=0.3)
    finally:
        faults.install_schedule(None)
    assert st["wedged"] is True
    assert st["health"] == "failed"
    assert engine.stats()["wedged_threads"] == 1


# -- lint scopes: the containment layer is PTL401/PTL701 territory ----------

_ENGINE_PTL401_BAD = '''
def recover_from_stall(url):
    try:
        return relaunch(url)
    except Exception:
        return None
'''

_ENGINE_PTL701_BAD = '''
import numpy as np

def watchdog_tick(batch):
    x = np.asarray(batch.tokens)
    if batch.mask.all():
        return x.item()
    return None
'''


def test_engine_files_in_ptl401_scope():
    """serving/engine.py + scheduler.py joined the PTL401 scope with
    the containment layer: a swallowed exception in a quarantine /
    relaunch path would BE the silent truncation this PR exists to
    prevent."""
    from paddle_tpu.analysis.lint import lint_source
    for fn in ("paddle_tpu/serving/engine.py",
               "paddle_tpu/serving/scheduler.py"):
        findings = lint_source(_ENGINE_PTL401_BAD, filename=fn)
        assert any(f.code == "PTL401" for f in findings), fn
    findings = lint_source(_ENGINE_PTL401_BAD,
                           filename="paddle_tpu/vision/thing.py")
    assert not any(f.code == "PTL401" for f in findings)


def test_watchdog_names_in_ptl701_hot_scope():
    """watchdog/quarantine/recover joined SERVING_HOT_NAMES: host
    syncs inside the containment machinery would serialize the very
    loop it guards."""
    from paddle_tpu.analysis.lint import lint_source
    findings = lint_source(_ENGINE_PTL701_BAD,
                           filename="paddle_tpu/serving/engine.py")
    codes = [f.code for f in findings]
    assert codes.count("PTL701") >= 3       # asarray, .all(), .item()
    # cold names in the same file stay out of scope
    cold = _ENGINE_PTL701_BAD.replace("watchdog_tick", "build_table")
    findings = lint_source(cold,
                           filename="paddle_tpu/serving/engine.py")
    assert not any(f.code == "PTL701" for f in findings)
