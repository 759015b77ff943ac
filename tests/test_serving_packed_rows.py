"""A serving step's token rows packed flat (``generation._StepRows``,
``step.packed``, ``Scheduler.plan_step``'s row budget): the packed entry
against the ``[B, Q]`` entry of the same body for each family, the
budget's rules on the scheduler alone, and through the engine — output
token for token the eager ``generate`` output, and every program a mixed
window uses compiled by one lone request a chunk width."""
import threading

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
from paddle_tpu.models.llama import LlamaForCausalLM, llama_config
from paddle_tpu.flags import get_flags, set_flags
from paddle_tpu.models.mimo_v2 import MiMoV2Config, MiMoV2ForCausalLM
from paddle_tpu.models.solar_open2 import (SolarOpen2Config,
                                           SolarOpen2ForCausalLM)
from paddle_tpu.serving import PagePool, Request, Scheduler, ServingEngine
from paddle_tpu.serving.scheduler import _bucket, step_rows

VOCAB = 96
FAMILIES = ("gpt", "llama", "described")


def _model(family: str, max_pos: int = 512):
    paddle.seed(3)
    if family == "gpt":
        m = GPTForPretraining(GPTConfig(
            vocab_size=VOCAB, hidden_size=32, num_layers=2, num_heads=4,
            max_position_embeddings=max_pos, hidden_dropout_prob=0.0,
            attention_dropout_prob=0.0))
    elif family == "llama":
        m = LlamaForCausalLM(llama_config(
            "tiny", vocab_size=VOCAB, max_position_embeddings=max_pos))
    elif family == "solar":
        # a gated GQA layer and a linear-attention layer (a state a slot)
        m = SolarOpen2ForCausalLM(SolarOpen2Config(
            vocab_size=VOCAB, hidden_size=64, num_hidden_layers=2,
            gqa_layers=[0], num_heads=4, num_kv_heads=2, head_dim=16,
            linear_num_heads=4, linear_head_dim=16, linear_low_rank=16,
            moe_intermediate_size=32, n_routed_experts=16,
            num_experts_per_tok=2, held_experts=(4, 4),
            max_position_embeddings=max_pos))
    else:
        # a full and a window layer (a ring a lane), a dense and two
        # expert layers of which this chip holds four experts
        m = MiMoV2ForCausalLM(MiMoV2Config(
            vocab_size=VOCAB, hidden_size=64, num_heads=4, num_kv_heads=1,
            swa_num_kv_heads=2, head_dim=24, v_head_dim=16,
            sliding_window=8, hybrid_layer_pattern=[0, 1, 1],
            moe_layer_freq=[0, 1, 1], intermediate_size=96,
            moe_intermediate_size=32, n_routed_experts=16,
            num_experts_per_tok=2, held_experts=(4, 4),
            max_position_embeddings=max_pos))
    m.eval()
    return m


@pytest.fixture(scope="module", params=FAMILIES)
def family(request):
    return request.param, _model(request.param)


def _feed(seqs, start, count, full, sink, ps, width):
    """The ``[B, width]`` arrays of one step: sequence ``i`` feeds
    ``count[i]`` tokens from position ``start[i]``."""
    b = len(seqs)
    tok = np.zeros((b, width), "int64")
    pos = np.zeros((b, width), "int32")
    pid = np.full((b, width), sink, "int32")
    slot = np.zeros((b, width), "int32")
    for i in range(b):
        p = np.arange(start[i], start[i] + count[i])
        tok[i, :count[i]], pos[i, :count[i]] = seqs[i][p], p
        pid[i, :count[i]], slot[i, :count[i]] = full[i, p // ps], p % ps
    kv = np.asarray([s + n for s, n in zip(start, count)], "int32")
    return tok, pos, pid, slot, kv, np.asarray(count, "int32")


def _pack(lanes, q_lens, n_rows, fill):
    """``lanes [B, Q]`` -> ``[n_rows]``: each sequence's valid slots one
    behind the other, then ``fill``."""
    out = np.full((n_rows,), fill, lanes.dtype)
    rows = np.concatenate([lanes[i, :n] for i, n in enumerate(q_lens)])
    out[:len(rows)] = rows
    return out


def test_packed_rows_and_lanes_give_the_same_logits_and_pools(family, rng):
    """A mixed step — a 300-token chunk, a decoding lane, an empty lane
    and a 130-token chunk that starts mid-sequence — through
    ``step(tok [B, Q], ...)`` at 4 x 512 rows and through
    ``step.packed`` at ``step_rows(512, 4)`` = 520 rows, from the same
    pools: the valid lanes' last-row logits and every pool page but the
    sink agree.  The described model's window layers write a ring."""
    name, model = family
    params, step = model.build_ragged_decode_step()
    cache = step.cache
    ps, b = 4, 4
    seqs = [rng.randint(0, VOCAB, (400,)) for _ in range(b)]
    before = [0, 57, 0, 41]            # context already in the pools
    q_lens = [300, 1, 0, 130]
    ppseq = -(-400 // ps)
    sink = b * ppseq
    ring = cache.ring_pages(ps, 512)
    full = np.arange(b * ppseq, dtype="int32").reshape(b, ppseq)
    tables = cache.tables(full, np.arange(b), ring)
    pools = cache.new_pools(sink + 1, ps, "float32", b, ring)
    lanes = jax.jit(step)
    # the contexts, through the [B, Q] entry
    tok, pos, pid, slot, kv, ql = _feed(seqs, [0] * b, before, full, sink,
                                        ps, 64)
    pools = lanes(params, tok, pos, pools, pid, slot, kv, ql, tables)[1]
    tok, pos, pid, slot, kv, ql = _feed(seqs, before, q_lens, full, sink,
                                        ps, 512)
    want = lanes(params, tok, pos, pools, pid, slot, kv, ql, tables)
    n_rows = step_rows(512, b)
    assert n_rows == 520 and sum(q_lens) <= n_rows
    packed = jax.jit(step.packed, static_argnames=("q_width",))
    got = packed(params, _pack(tok, q_lens, n_rows, 0),
                 _pack(pos, q_lens, n_rows, 0), pools,
                 _pack(pid, q_lens, n_rows, sink),
                 _pack(slot, q_lens, n_rows, 0), kv, ql, tables, q_width=512)
    assert len(got) == len(want) == (3 if name == "described" else 2)
    live = [i for i, n in enumerate(q_lens) if n]
    np.testing.assert_allclose(np.asarray(got[0])[live],
                               np.asarray(want[0])[live],
                               rtol=2e-5, atol=2e-5)
    assert np.all(np.isfinite(np.asarray(got[0])))
    for (gk, gv), (wk, wv) in zip(got[1], want[1]):
        # every page but the last, which the rows with no token write
        np.testing.assert_allclose(np.asarray(gk)[:, :-1],
                                   np.asarray(wk)[:, :-1],
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(gv)[:, :-1],
                                   np.asarray(wv)[:, :-1],
                                   rtol=2e-5, atol=2e-5)
    if name == "described":
        # rows routed to held experts: only rows that carry a token count
        np.testing.assert_array_equal(np.asarray(got[2]),
                                      np.asarray(want[2]))
        assert int(got[2][0]) > 0


@pytest.mark.parametrize("name", ["llama", "described", "solar"])
def test_the_kernel_s_packed_launch_gives_the_logits_of_both_entries(
        name, rng):
    """The step body with the Pallas kernel in it (interpret mode), on
    the small stubs of the three serve families with a softmax layer —
    LLaMA's GQA (Mistral's), MiMo's full and window layers with a sink,
    Solar's gated GQA layer beside a state layer: ``step.packed`` at
    ``step_rows(64, 4)`` = 128 rows, whose launches run over the live
    tiles of a 40-row chunk, a decoding lane, an empty lane and a 13-row
    chunk, gives the logits of the ``[B, Q]`` entry at 4 x 64 rows, and
    both those of the jnp reference's route."""
    model = _model(name)
    params, step = model.build_ragged_decode_step()
    cache = step.cache
    ps, b, qw = 4, 4, 64
    seqs = [rng.randint(0, VOCAB, (120,)) for _ in range(b)]
    before, q_lens = [0, 57, 0, 41], [40, 1, 0, 13]
    ppseq = -(-120 // ps)
    sink = b * ppseq
    ring = cache.ring_pages(ps, qw)
    full = np.arange(b * ppseq, dtype="int32").reshape(b, ppseq)
    tables = cache.tables(full, np.arange(b), ring)
    pools = cache.new_pools(sink + 1, ps, "float32", b, ring)
    tok, pos, pid, slot, kv, ql = _feed(seqs, [0] * b, before, full, sink,
                                        ps, qw)
    pools = jax.jit(step)(params, tok, pos, pools, pid, slot, kv, ql,
                          tables)[1]
    tok, pos, pid, slot, kv, ql = _feed(seqs, before, q_lens, full, sink,
                                        ps, qw)
    want = np.asarray(jax.jit(step)(params, tok, pos, pools, pid, slot, kv,
                                    ql, tables)[0])
    n_rows = step_rows(qw, b)
    assert n_rows == 128
    keep = get_flags(["FLAGS_pallas_interpret"])
    set_flags({"FLAGS_pallas_interpret": True})
    try:
        # a function jax has not traced: the route is chosen in the trace
        lanes = jax.jit(lambda *args: step(*args))
        assert "pallas_call" in str(jax.make_jaxpr(lanes)(
            params, tok, pos, pools, pid, slot, kv, ql, tables))
        lanes = np.asarray(lanes(params, tok, pos, pools, pid, slot, kv, ql,
                                 tables)[0])
        packed = np.asarray(jax.jit(
            step.packed, static_argnames=("q_width",))(
            params, _pack(tok, q_lens, n_rows, 0),
            _pack(pos, q_lens, n_rows, 0), pools,
            _pack(pid, q_lens, n_rows, sink),
            _pack(slot, q_lens, n_rows, 0), kv, ql, tables, q_width=qw)[0])
    finally:
        set_flags(keep)
    live = [i for i, n in enumerate(q_lens) if n]
    np.testing.assert_allclose(packed[live], lanes[live], rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(packed[live], want[live], rtol=2e-5,
                               atol=2e-5)
    assert np.all(np.isfinite(packed))


# ---------------------------------------------------------------------------
# the row budget, on the scheduler alone
# ---------------------------------------------------------------------------

def _sched(max_batch=8, chunk=0, pages=4096, ps=16):
    return Scheduler(PagePool(pages, ps), max_batch=max_batch,
                     max_pages_per_seq=128, max_prefill_chunk=chunk)


def _decoding(sched, n):
    """``n`` sequences past their prompts, one token pending each."""
    for _ in range(n):
        sched.submit(Request([1, 2, 3], max_new_tokens=64))
    plan, _, _ = sched.plan_step()
    sched.commit(plan)
    for seq in plan.seqs:
        seq.tokens.append(7)
        seq.req._emit(7)
    return plan.seqs


@pytest.mark.parametrize("q_width,max_batch,rows", [
    (1, 8, 8), (1, 3, 3), (2, 8, 128), (64, 8, 128), (128, 8, 136),
    (512, 8, 520), (1024, 8, 1032), (1024, 16, 1040), (4096, 4, 4104)])
def test_step_rows_is_a_function_of_the_programs_key(q_width, max_batch,
                                                    rows):
    assert step_rows(q_width, max_batch) == rows
    # one chunk as wide as the bucket and every other lane decoding fit
    assert q_width + max_batch - 1 <= max(rows, max_batch)


@pytest.mark.parametrize("second,joins", [(300, True), (506, True),
                                          (507, False), (520, False)])
def test_a_second_chunk_joins_only_inside_the_slack(second, joins):
    """520 tokens make a Q=1024 step of 1,032 rows: beside six decoding
    lanes a second chunk joins while 520 + it + 6 <= 1,032 and otherwise
    waits WHOLE, its lane held."""
    sched = _sched()
    _decoding(sched, 6)
    first = Request(list(range(520)), max_new_tokens=4)
    later = Request(list(range(second)), max_new_tokens=4)
    sched.submit(first)
    sched.submit(later)
    plan, admitted, _ = sched.plan_step()
    assert len(admitted) == 2          # admitted: it holds its lane
    assert plan.q_width == 1024 and plan.rows == 1032
    fed = plan.q_lens[:len(plan.seqs)].tolist()
    if joins:
        assert fed == [1] * 6 + [520, second]
        assert plan.prefill_waiting == 0
    else:
        assert fed == [1] * 6 + [520]
        assert plan.prefill_waiting == 1 and sched.prefill_waits == 1
    assert sum(fed) <= plan.rows
    assert plan.tok.shape == (1032,)
    assert sched.rows_empty == sched.rows_planned - 3 * 6 - 6 - sum(fed[6:])
    sched.commit(plan)
    for seq in plan.seqs[:6]:
        seq.tokens.append(7)
    nxt, _, _ = sched.plan_step()
    if not joins:
        # never cut to fit: it goes whole in the next step
        assert later.id in nxt.slots_map
        assert nxt.q_lens[nxt.slots_map[later.id]] == second
        assert nxt.q_width == _bucket(second)


def test_the_first_chunk_always_goes_and_chunks_are_never_cut():
    """Eight prompts at once, every one over half a bucket: a step takes
    the first wide chunk in running order and what fits beside it; no
    chunk is ever fed in part, and the steps together feed every token
    once."""
    lens = [600, 900, 130, 700, 1024, 513, 64, 300]
    sched = _sched()
    reqs = [Request(list(range(n)), max_new_tokens=2) for n in lens]
    for r in reqs:
        sched.submit(r)
    fed = {r.id: [] for r in reqs}
    for _ in range(32):
        plan, _, _ = sched.plan_step()
        if plan is None:
            break
        assert plan.rows == step_rows(plan.q_width, 8)
        assert int(plan.q_lens.sum()) <= plan.rows
        wide = [s for s in plan.seqs
                if plan.q_lens[plan.slots_map[s.req.id]] > 1]
        if any(s.kv_len < len(s.req.prompt) for s in sched.running):
            # the oldest sequence with a prompt left goes first
            oldest = next(s for s in sched.running
                          if s.kv_len < len(s.req.prompt))
            assert oldest in wide or len(oldest.req.prompt) == 1
        for s in plan.seqs:
            n = int(plan.q_lens[plan.slots_map[s.req.id]])
            if s.kv_len < len(s.req.prompt):
                fed[s.req.id].append(n)
        sched.commit(plan)
        for s in plan.seqs:
            if s.kv_len >= len(s.tokens):
                s.tokens.append(5)
                s.req._emit(5)
                if len(s.req.tokens) >= s.req.max_new_tokens:
                    sched.finish(s)
    assert [fed[r.id] for r in reqs] == [[n] for n in lens]
    assert sched.prefill_waits > 0 and not sched.has_work()


def test_no_sequence_waits_for_ever_under_a_closed_loop():
    """Eight lanes, sixteen clients that send their next request when the
    last completed, chunks of 256: every request finishes, and none
    waits more than a bounded number of steps for its first chunk."""
    rs = np.random.RandomState(5)
    sched = _sched(chunk=256)
    live, done, waited = {}, 0, []

    def send():
        r = Request(list(range(int(rs.randint(40, 900)))),
                    max_new_tokens=int(rs.randint(2, 12)))
        sched.submit(r)
        live[r.id] = [r, 0]

    for _ in range(16):
        send()
    for _ in range(3000):
        plan, _, _ = sched.plan_step()
        assert plan is not None
        for s in plan.seqs:
            assert plan.q_lens[plan.slots_map[s.req.id]] in (
                1, min(256, len(s.tokens) - s.kv_len))
        for s in sched.running:         # steps in a lane and not fed
            entry = live[s.req.id]
            entry[1] = 0 if s.req.id in plan.slots_map else entry[1] + 1
            waited.append(entry[1])
        sched.commit(plan)
        for s in list(plan.seqs):
            if s.kv_len >= len(s.tokens):
                s.tokens.append(5)
                s.req._emit(5)
                if len(s.req.tokens) >= s.req.max_new_tokens:
                    sched.finish(s)
                    del live[s.req.id]
                    done += 1
                    send()
        if done >= 120:
            break
    assert done >= 120
    # a running sequence is skipped at most while the chunks of the seven
    # ahead of it in running order (four of 256 a prompt) take their steps
    assert 0 < max(waited) <= 7 * 4


# ---------------------------------------------------------------------------
# through the engine
# ---------------------------------------------------------------------------

def _greedy(model, prompt, n_new):
    ids = Tensor(np.asarray([prompt], "int64"))
    out = model.generate(ids, max_new_tokens=n_new, decode_strategy="greedy")
    return np.asarray(out._data)[0, len(prompt):].tolist()


@pytest.mark.parametrize("name", ["gpt", "llama"])
def test_engine_output_is_generates_output_with_packed_rows(name, rng):
    """Prompts that share steps, wait for one another and decode beside a
    prefill: token for token the eager ``generate`` output."""
    model = _model(name, max_pos=256)
    prompts = [rng.randint(0, VOCAB, (n,)).tolist()
               for n in (150, 9, 70, 131, 1, 33)]
    want = [_greedy(model, p, 6) for p in prompts]
    engine = ServingEngine(model, max_batch=4, page_size=8,
                           prefix_caching=False)
    with engine:
        reqs = [engine.submit(p, max_new_tokens=6) for p in prompts]
        got = [r.wait(timeout=120) for r in reqs]
    assert got == want
    stats = engine.stats()
    assert stats["prefill_waits"] > 0          # 150 + 131 share no step
    assert 0 < stats["step_rows_empty"] < stats["step_rows"]


def test_a_lone_request_a_width_compiles_what_a_mixed_window_uses(rng):
    """What the benchmark's runners do and demand: one request a chunk
    width, alone, then many at once — the window compiles nothing."""
    model = _model("gpt", max_pos=256)
    engine = ServingEngine(model, max_batch=4, page_size=8,
                           max_prefill_chunk=64, prefix_caching=False)
    lens = [rng.randint(20, 200) for _ in range(12)]
    widths = set()
    for n in lens:                      # serve_described.chunk_buckets
        if n > 64:
            widths.add(64)
            n %= 64
        if n:
            widths.add(_bucket(n))
    with engine:
        for q in sorted(widths):
            engine.generate(rng.randint(0, VOCAB, (q,)).tolist(),
                            max_new_tokens=2)
        warmed = engine.stats()["programs"]
        assert warmed == len(widths) + 1            # and the decode step
        prompts = [rng.randint(0, VOCAB, (n,)).tolist() for n in lens]
        out = [None] * len(lens)

        def client(i):
            out[i] = engine.submit(prompts[i],
                                   max_new_tokens=5).wait(timeout=120)
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(lens))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(len(o) == 5 for o in out)
        assert engine.stats()["programs"] == warmed
