"""The serving loop keeps one step ahead: it dispatches step N+1 before
it reads step N's tokens (``ServingEngine._loop_body``,
``Scheduler.plan_step(unread)``, ``prev`` / ``take`` of the step's
program).  Every case here runs the engine as it is and again with its
own eligibility predicate (``_may_run_ahead``) held at False, which is
the loop that reads every step before it plans the next, and asks for
the same tokens, the same failures and the same programs."""
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.flags import set_flags
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
from paddle_tpu.models.llama import LlamaForCausalLM, llama_config
from paddle_tpu.models.mimo_v2 import MiMoV2Config, MiMoV2ForCausalLM
from paddle_tpu.models.solar_open2 import (SolarOpen2Config,
                                           SolarOpen2ForCausalLM)
from paddle_tpu.models.glm5 import Glm5Config, Glm5ForCausalLM
from paddle_tpu.serving import Request, ServingEngine
from paddle_tpu.serving import engine as engine_mod


@pytest.fixture(scope="module")
def gpt():
    paddle.seed(0)
    m = GPTForPretraining(GPTConfig(
        num_layers=2, hidden_size=64, num_heads=4, vocab_size=128,
        max_position_embeddings=128, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0))
    m.eval()
    return m


@pytest.fixture(scope="module")
def llama():
    paddle.seed(3)
    m = LlamaForCausalLM(llama_config("tiny"))
    m.eval()
    return m


@pytest.fixture(scope="module")
def mimo():
    """Window layers (a ring a lane) and routed experts (counts behind
    the sampled row), as ``tests/test_mimo_v2_serving.py`` builds it."""
    paddle.seed(11)
    m = MiMoV2ForCausalLM(MiMoV2Config(
        vocab_size=96, hidden_size=64, num_heads=4, num_kv_heads=1,
        swa_num_kv_heads=2, head_dim=24, v_head_dim=16, sliding_window=8,
        hybrid_layer_pattern=[0, 1, 1, 0], moe_layer_freq=[0, 1, 1, 1],
        intermediate_size=96, moe_intermediate_size=32,
        n_routed_experts=16, num_experts_per_tok=2, held_experts=(4, 4),
        max_position_embeddings=128))
    rs = np.random.RandomState(2)
    for blk in m.blocks:
        if blk.sink is not None:
            blk.sink.set_value(rs.uniform(2, 5, blk.sink.shape)
                               .astype("float32"))
        if hasattr(blk, "router_b"):
            blk.router_b.set_value(rs.uniform(-.3, .3, blk.router_b.shape)
                                   .astype("float32"))
    m.eval()
    return m


@pytest.fixture(scope="module")
def solar():
    """A gated GQA layer and a linear-attention layer (a state a lane,
    zeroed by the step when a sequence starts), routed experts beside a
    shared one, as ``tests/test_solar_open2_serving.py`` builds it."""
    paddle.seed(11)
    m = SolarOpen2ForCausalLM(SolarOpen2Config(
        vocab_size=96, hidden_size=64, num_hidden_layers=2, gqa_layers=[0],
        num_heads=4, num_kv_heads=2, head_dim=16, linear_num_heads=4,
        linear_head_dim=16, linear_low_rank=16, moe_intermediate_size=32,
        n_routed_experts=16, num_experts_per_tok=2, held_experts=(4, 4),
        max_position_embeddings=128))
    rs = np.random.RandomState(2)
    for blk in m.blocks:
        blk.router_b.set_value(rs.uniform(-.3, .3, blk.router_b.shape)
                               .astype("float32"))
    m.seed_decays(rs)
    m.eval()
    return m


@pytest.fixture(scope="module")
def glm():
    """Latent attention whose index keeps 8 keys a row (a 40-wide latent
    row and a 16-wide index key a token, no head in the cache) behind a
    dense layer, then routed experts beside a shared one, as
    ``tests/test_glm5_serving.py`` builds it."""
    paddle.seed(11)
    m = Glm5ForCausalLM(Glm5Config(
        vocab_size=96, hidden_size=64, num_hidden_layers=2, num_heads=4,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, index_n_heads=16,
        index_head_dim=16, index_topk=8, intermediate_size=128,
        first_k_dense_replace=1, moe_intermediate_size=32,
        n_routed_experts=16, num_experts_per_tok=2, held_experts=(4, 4),
        max_position_embeddings=128))
    rs = np.random.RandomState(2)
    m.blocks[1].router_b.set_value(
        rs.uniform(-.3, .3, m.blocks[1].router_b.shape).astype("float32"))
    m.seed_index(rs)
    m.eval()
    return m


@pytest.fixture
def family(request, gpt, llama, mimo, solar, glm):
    return {"gpt": gpt, "llama": llama, "mimo": mimo,
            "solar": solar, "glm": glm}[request.param]


@pytest.fixture
def chaos(tmp_path):
    """The event log on, and the fault schedule and the watchdog's flag
    cleared whatever the test did."""
    from paddle_tpu.resilience import faults
    set_flags({"FLAGS_observability_dir": str(tmp_path)})
    try:
        yield str(tmp_path)
    finally:
        faults.install_schedule(None)
        set_flags({"FLAGS_observability_dir": "",
                   "FLAGS_serving_step_timeout_s": 0.0})


def _engine(model, ahead, **kw):
    kw.setdefault("max_batch", 3)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_prefill_chunk", 8)
    kw.setdefault("prefix_caching", False)
    engine = ServingEngine(model, **kw)
    if not ahead:
        # the loop's own predicate: never a step behind an unread one
        engine._may_run_ahead = lambda: False
    return engine


def _serve(model, prompts, ahead, budgets=8, temperature=0.0, eos=None,
           **kw):
    """Every request queued before the loop starts (so that both loops
    admit the same requests in the same plan), then run to the end:
    ``(tokens or None where the request failed, stats, engine)``."""
    engine = _engine(model, ahead, **kw)
    if isinstance(budgets, int):
        budgets = [budgets] * len(prompts)
    engine._accepting = True        # what start() sets, a moment early
    reqs = [engine.submit(p, max_new_tokens=n, temperature=temperature,
                          eos_token_id=eos)
            for p, n in zip(prompts, budgets)]
    with engine:
        got = []
        for r in reqs:
            try:
                got.append(r.wait(timeout=300))
            except RuntimeError:
                got.append(None)
        stats = engine.stats()
    return got, stats, engine


def _vocab(model):
    return int(model.config.vocab_size)


def _prompts(model, lengths, seed=5):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, _vocab(model), (n,)).tolist() for n in lengths]


# ---------------------------------------------------------------------------
# the same tokens
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["gpt", "llama", "mimo", "solar", "glm"],
                         indirect=True)
@pytest.mark.parametrize("temperature", [0.0, 0.8],
                         ids=["greedy", "sampled"])
def test_tokens_equal_ahead_and_drained(family, temperature):
    """Prompts that take one, two and five chunks of 8, so that lanes
    decode beside lanes still eating their prompts, and budgets that end
    at different steps.  Sampled: the engine's key splits once a step
    and a lane's draw depends on its row, so equal tokens mean the two
    loops ran the same lanes in the same rows step for step."""
    prompts = _prompts(family, (5, 13, 37))
    budgets = [12, 9, 6]
    want, base, _ = _serve(family, prompts, False, budgets, temperature)
    got, stats, _ = _serve(family, prompts, True, budgets, temperature)
    assert got == want
    assert [len(t) for t in got] == budgets
    assert base["steps_ahead"] == 0 and stats["steps_ahead"] > 0
    # the same steps, one dispatch each, and the same programs
    assert stats["steps_ahead"] + stats["steps_drained"] \
        == base["steps_drained"]
    assert stats["programs"] == base["programs"]


@pytest.mark.parametrize("family", ["gpt", "mimo"], indirect=True)
def test_tokens_equal_with_requests_waiting_for_a_lane(family):
    """Seven requests over three lanes: a lane that ends is given to the
    next request one step later than a loop that commits first would
    give it (the scheduler holds it until the commit), and greedy tokens
    do not depend on who shares a step."""
    prompts = _prompts(family, (5, 13, 37, 9, 21, 6, 17), seed=8)
    budgets = [7, 4, 9, 3, 6, 8, 5]
    want, _, _ = _serve(family, prompts, False, budgets)
    got, stats, engine = _serve(family, prompts, True, budgets)
    assert got == want
    assert stats["steps_ahead"] > stats["steps_drained"]
    assert engine.pool.available() == engine.pool.num_pages - 1


def _midway_eos(model, n_new=10):
    """(prompt, greedy tokens, eos): a prompt whose greedy run emits a
    second distinct token midway, so that stopping on it shows."""
    cand = np.random.RandomState(0).randint(0, 128, (64, 5))
    out = np.asarray(model.generate(
        Tensor(cand.astype("int64")), max_new_tokens=n_new,
        decode_strategy="greedy")._data)[:, 5:]
    for p, row in zip(cand.tolist(), out.tolist()):
        k = next((i for i, t in enumerate(row) if t != row[0]), None)
        if k is not None and 3 <= k <= n_new - 3:
            return p, row, row[k]
    raise AssertionError("no candidate prompt changes token midway")


@pytest.mark.parametrize("ahead", [False, True], ids=["drained", "ahead"])
def test_a_lane_that_ends_on_eos_emits_nothing_after_it(gpt, ahead, chaos):
    """The lane is fed once more before the host knows its token was the
    end: that row's token is dropped, its k/v slot was in a page the
    sequence held, and every page comes back."""
    from paddle_tpu.observability import events as obs_events
    prompt, full, eos = _midway_eos(gpt)
    other = _prompts(gpt, (11,))[0]
    got, stats, engine = _serve(gpt, [prompt, other], ahead, 10, eos=eos)
    assert got[0] == full[:full.index(eos) + 1]
    assert engine.pool.available() == engine.pool.num_pages - 1
    if ahead:
        # the speculative row ran: one more token fed than emitted on
        # that lane's last step, and nothing of it reached the client
        steps = obs_events.read_events(chaos, kinds=["batch_step"])
        fed = sum(s["tokens"] for s in steps)
        emitted = sum(len(t) for t in got)
        assert fed == len(prompt) + len(other) + emitted - 2 + 1
        assert stats["steps_ahead"] > 0


@pytest.mark.parametrize("n_new", [1, 2, 5])
def test_a_budget_ends_at_exactly_max_new_tokens(gpt, n_new):
    """The host knows by count that the unread step holds a lane's last
    token and does not feed the lane again."""
    prompts = _prompts(gpt, (6, 19))
    want, base, _ = _serve(gpt, prompts, False, n_new)
    got, stats, engine = _serve(gpt, prompts, True, n_new)
    assert got == want and all(len(t) == n_new for t in got)
    # no step ran for a lane past its budget: the same count of steps
    assert stats["steps_ahead"] + stats["steps_drained"] \
        == base["steps_drained"]
    assert engine.pool.available() == engine.pool.num_pages - 1


@pytest.mark.parametrize("family", ["gpt", "mimo", "solar", "glm"],
                         indirect=True)
def test_page_pressure_drains_evicts_and_keeps_the_tokens(family):
    """A plan made ahead never evicts: where a sequence cannot grow the
    loop commits the unread step and plans again, and that plan evicts
    as it always did."""
    prompts = _prompts(family, (14, 14, 14), seed=1)
    want, _, _ = _serve(family, prompts, False, 12)
    got, stats, engine = _serve(family, prompts, True, 12, num_pages=17,
                                max_pages_per_seq=8)
    assert stats["evictions"] >= 1
    assert got == want
    assert stats["steps_ahead"] > 0 and stats["steps_drained"] > 1
    assert engine.pool.available() == engine.pool.num_pages - 1


def test_a_plan_made_ahead_returns_nothing_rather_than_evict(gpt):
    """The scheduler's half alone: with an unread plan and no page left
    for a lane's next token, ``plan_step`` gives no plan and evicts
    nobody; the same call without the unread plan evicts."""
    engine = _engine(gpt, True, max_batch=2, num_pages=5,
                     max_pages_per_seq=4)
    sched = engine.scheduler
    for p in _prompts(gpt, (8, 8)):
        sched.submit(Request(p, max_new_tokens=8))
    first, admitted, _ = sched.plan_step()
    assert len(admitted) == 2 and sched.pool.available() == 0
    # both prompts fill their two pages: the next token needs a third
    ahead, _, evicted = sched.plan_step(first)
    assert ahead is None and evicted == [] and len(sched.running) == 2
    sched.commit(first)
    for seq in first.seqs:
        seq.tokens.append(1)
    plan, _, evicted = sched.plan_step()
    assert plan is not None and len(evicted) == 1


def test_the_plan_ahead_sees_each_lane_as_the_unread_step_leaves_it(gpt):
    """kv grown by the unread step's rows, a finished prompt decoding
    from the token on the device (``take``), a chunked prompt at its
    next chunk, a lane at its budget fed nothing."""
    engine = _engine(gpt, True, max_batch=3, max_prefill_chunk=8)
    sched = engine.scheduler
    short, long, last = _prompts(gpt, (5, 20, 3))
    for p, n in ((short, 4), (long, 4), (last, 1)):
        sched.submit(Request(p, max_new_tokens=n))
    first, _, _ = sched.plan_step()
    assert list(first.q_lens) == [5, 8, 3]
    assert (first.take == -1).all()
    nxt, _, _ = sched.plan_step(first)
    # lane 0 sampled: its token is row 0 of the unread step; lane 1 eats
    # its next chunk; the third request's one token was its budget
    assert [s.req.prompt for s in nxt.seqs] == [short, long]
    assert list(nxt.q_lens[:2]) == [1, 8]
    assert list(nxt.kv_lens[:2]) == [6, 16]
    assert nxt.take[0] == 0 and (nxt.take[1:] == -1).all()
    assert nxt.pos[0] == 5 and list(nxt.pos[1:9]) == list(range(8, 16))
    assert list(nxt.tok[1:9]) == long[8:16]
    # nothing of the sequences moved: the commit is still to come
    assert [s.kv_len for s in first.seqs] == [0, 0, 0]


# ---------------------------------------------------------------------------
# failures with a step unread
# ---------------------------------------------------------------------------

@pytest.mark.chaos
@pytest.mark.parametrize("kind", ["exc", "nan"])
def test_a_fault_with_a_step_unread_quarantines_the_same_request(
        gpt, kind, chaos):
    """``serving_step@3`` fires at the third dispatch, which the loop
    makes while the second step is unread: that step is landed, then the
    failure is contained as the loop that never runs ahead contains it —
    the same request fails alone, every other one completes with the
    tokens of an unpoisoned run."""
    from paddle_tpu.observability import events as obs_events
    from paddle_tpu.resilience import faults
    prompts = _prompts(gpt, (6, 11, 4), seed=3)
    clean, _, _ = _serve(gpt, prompts, False, 8)
    outcome = {}
    for ahead in (False, True):
        faults.install_schedule(f"serving_step@3={kind}")
        got, stats, engine = _serve(gpt, prompts, ahead, 8)
        outcome[ahead] = got
        assert stats["quarantined"] == 1
        assert engine.pool.available() == engine.pool.num_pages - 1
    assert outcome[True] == outcome[False]
    failed = [i for i, t in enumerate(outcome[True]) if t is None]
    assert len(failed) == 1
    assert [t for t in outcome[True] if t is not None] \
        == [t for i, t in enumerate(clean) if i not in failed]
    quarantined = [e["request"] for e in obs_events.read_events(
        chaos, kinds=["quarantine"]) if e["action"] == "quarantined"]
    assert len(quarantined) == 2        # one a loop


@pytest.mark.chaos
def test_a_stall_with_a_step_unread_relaunches_and_completes(gpt, chaos):
    """The third dispatch hangs on the host with the second step unread:
    the watchdog's bracket is that older step's, it fires, the loop is
    relaunched with fresh pools and every stream ends token-exact."""
    from paddle_tpu.resilience import faults
    prompts = _prompts(gpt, (6, 11), seed=4)
    want, _, _ = _serve(gpt, prompts, False, 8)
    faults.install_schedule("serving_step@3=stall:2")
    set_flags({"FLAGS_serving_step_timeout_s": 0.3})
    got, stats, engine = _serve(gpt, prompts, True, 8)
    assert got == want
    assert stats["watchdog_relaunches"] == 1
    assert engine.pool.available() == engine.pool.num_pages - 1


def test_a_cancel_between_a_dispatch_and_its_read(gpt):
    """A client that cancels on its first token does so while a step
    that holds its lane is on the device, and with the loop a step ahead
    a second one behind it.  Its rows are dropped at both commits, its
    pages go to the requests that waited (the device runs its programs
    in dispatch order, so the dropped rows' k/v writes land before the
    new owner's), and everyone else's tokens are those of a run without
    the cancelled request's interference."""
    prompts = _prompts(gpt, (9, 12, 7, 15, 10), seed=6)
    want, _, _ = _serve(gpt, prompts[1:], False, 10)
    engine = _engine(gpt, True, max_batch=2, num_pages=15,
                     max_pages_per_seq=7)
    with engine:
        victim = engine.submit(prompts[0], max_new_tokens=10)
        rest = [engine.submit(p, max_new_tokens=10) for p in prompts[1:]]

        def cancel_on_first_token():
            for _ in victim.stream(timeout=60):
                victim.cancel("client went away")
                return
        t = threading.Thread(target=cancel_on_first_token)
        t.start()
        got = [r.wait(timeout=300) for r in rest]
        t.join()
        stats = engine.stats()
    assert victim.error_kind == "cancelled" and 1 <= len(victim.tokens) < 10
    assert got == want
    assert stats["cancelled"] == 1 and stats["steps_ahead"] > 0
    assert engine.pool.available() == engine.pool.num_pages - 1


def test_a_failed_read_takes_the_step_behind_it_and_restores_the_pools(
        gpt):
    """By hand: step B is dispatched behind unread step A, then A's read
    fails.  B consumed A's pools and tokens and goes with it; the
    engine's device state is what A was given, and nothing of either
    was committed, so both plans can be fed again."""
    engine = _engine(gpt, True, max_batch=2)
    engine.scheduler.submit(Request(_prompts(gpt, (6,))[0],
                                    max_new_tokens=4))
    phases = engine_mod._LoopPhases()
    pools0, key0 = engine._pools, engine._key
    plan_a, _, _ = engine.scheduler.plan_step()
    a = engine_mod._Flight(plan_a, ahead=False)
    assert engine._dispatch_step(a, None, engine._epoch, phases)
    plan_b, _, _ = engine.scheduler.plan_step(plan_a)
    b = engine_mod._Flight(plan_b, ahead=True)
    assert engine._dispatch_step(b, a, engine._epoch, phases)
    assert engine._pools is not pools0 and a.pools_in is pools0
    assert b.pools_in is not pools0 and a.key_in is key0
    assert engine._dispatch_plan is plan_a      # the oldest unread step's
    engine._land_step(a, b, engine._epoch, phases)
    assert engine._dispatch_plan is plan_b
    assert engine._dispatch_t0 == b.bracket_t0
    engine._land_step(b, None, engine._epoch, phases)
    assert engine._dispatch_plan is None and engine._dispatch_t0 is None
    [seq] = engine.scheduler.running
    assert len(seq.req.tokens) == 2 and seq.kv_len == 7
    phases.stop()


# ---------------------------------------------------------------------------
# what the records and the counters say
# ---------------------------------------------------------------------------

def test_one_host_read_a_step_whatever_the_order(gpt):
    """``serving_host_sync`` marks each host read: one a step, ahead or
    drained, and the dispatch bookkeeping agrees."""
    from paddle_tpu.core.dispatch import observe_op_stream
    syncs = []

    def hook(ev):
        if ev.op_name == "serving_host_sync":
            syncs.append(int(ev.in_avals[0][0][0]))

    engine = _engine(gpt, True)
    with engine, observe_op_stream(hook):
        reqs = [engine.submit(p, max_new_tokens=6)
                for p in _prompts(gpt, (5, 18))]
        for r in reqs:
            r.wait(timeout=120)
        stats = engine.stats()
    steps = stats["steps_ahead"] + stats["steps_drained"]
    assert syncs == [1] * steps
    assert engine._c_dispatch.value == engine._c_steps.value == steps
    assert stats["steps_ahead"] > 0


@pytest.mark.parametrize("family", ["gpt", "mimo"], indirect=True)
def test_one_program_a_bucket_whatever_mix_of_steps_ran(family):
    """A step behind an unread one and a step with nothing unread run
    the same program of their width: ``prev`` is the unread step's row
    or a resident zero row of the same shape, so nothing compiles for
    the second form."""
    import jax
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    prompts = _prompts(family, (3, 8, 30), seed=9)
    # buckets reached: a chunk of 8 (and 3, 6 of the remainders) and 1
    _, base, _ = _serve(family, prompts, False, 6)
    n = len(compiles)
    _, stats, engine = _serve(family, prompts, True, 6)
    widths = {key[0] for key in engine._programs}
    assert stats["programs"] == base["programs"] == len(widths)
    assert stats["steps_ahead"] > 0 and stats["steps_drained"] > 0
    # each program of the second engine compiled once, for both forms
    assert len(compiles) - n <= stats["programs"]


def test_batch_step_says_which_steps_ran_ahead(gpt, chaos):
    """``ahead`` is in the schema, the docs' table and the records, and
    the records' count of it is ``stats()``'s."""
    import os
    from paddle_tpu.observability import events as obs_events
    assert obs_events.EVENT_SCHEMA["batch_step"]["ahead"] == "bool"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "docs", "observability_events.md"),
              encoding="utf-8") as fh:
        assert "| `ahead` | bool" in fh.read()
    _, stats, _ = _serve(gpt, _prompts(gpt, (5, 18)), True, 6)
    steps = obs_events.read_events(chaos, kinds=["batch_step"])
    assert len(steps) == stats["steps_ahead"] + stats["steps_drained"]
    assert sum(1 for s in steps if s.get("ahead")) == stats["steps_ahead"]
    assert not steps[0].get("ahead")
    # every step's span closed, and its record names it
    spans = {e["span"] for e in obs_events.read_events(
        chaos, kinds=["trace_span"]) if e["name"] == "batch_step"}
    assert {s["span"] for s in steps} == spans


def test_a_steady_closed_loop_runs_nine_steps_in_ten_ahead(gpt):
    """Six clients over three lanes, each sending its next request when
    the last one ended, outputs of 4 to 12 tokens: a lane ends every few
    steps and a prompt arrives as often, and the loop drains only when
    nothing is left to feed."""
    engine = _engine(gpt, True, max_batch=3)
    rs = np.random.RandomState(12)
    jobs = [[(rs.randint(0, 128, (int(rs.randint(4, 30)),)).tolist(),
              int(rs.randint(4, 13))) for _ in range(6)]
            for _ in range(6)]
    done = []

    def client(mine):
        for prompt, n in mine:
            done.append(len(engine.submit(
                prompt, max_new_tokens=n).wait(timeout=300)) == n)

    with engine:
        threads = [threading.Thread(target=client, args=(j,)) for j in jobs]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = engine.stats()
    assert len(done) == 36 and all(done)
    steps = stats["steps_ahead"] + stats["steps_drained"]
    assert steps == engine._c_steps.value
    assert stats["steps_ahead"] >= 0.9 * steps, stats
    assert stats["evictions"] == 0 and stats["health"] == "ok"


def test_the_predicate_holds_the_loop_back_when_it_should(gpt):
    """``_may_run_ahead`` reads what the engine can observe: health, a
    bisection under way, a pinned poison, the fused window's flag."""
    from paddle_tpu.flags import get_flags
    engine = _engine(gpt, True)
    assert engine._may_run_ahead()
    engine.health = "degraded"
    assert not engine._may_run_ahead()
    engine.health = "ok"
    engine.scheduler.bisect_push_front([["r1"], ["r2"]])
    assert not engine._may_run_ahead()
    engine.scheduler.bisect_groups.clear()
    engine._poison["r1"] = ("exc", None)
    assert not engine._may_run_ahead()
    engine._poison.clear()
    keep = get_flags(["FLAGS_serving_fused_steps"])
    try:
        set_flags({"FLAGS_serving_fused_steps": 4})
        assert not engine._may_run_ahead()
    finally:
        set_flags(keep)
    assert engine._may_run_ahead()
