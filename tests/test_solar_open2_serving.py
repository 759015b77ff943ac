"""A model whose layers keep a fixed-size state a sequence (Solar Open 2:
three gated delta-rule linear-attention layers to one gated GQA layer,
routed experts beside a shared one) through the ragged step, the
scheduler's slots and the engine, against
``benchmark/reference/solar_open2.py`` on seeded weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.flags import get_flags, set_flags
from paddle_tpu.models.generation import (LaneState,
                                          build_fused_window_step)
from paddle_tpu.models.solar_open2 import (SolarOpen2Config,
                                           SolarOpen2ForCausalLM)
from paddle_tpu.ops import gated_delta as gd
from paddle_tpu.ops.routed_experts import (held_experts_swiglu,
                                           sigmoid_topk_route)
from paddle_tpu.serving import ServingEngine

from benchmark.reference import solar_open2 as ref

VOCAB = 256


def _config(**over):
    # one period of the layer pattern: [GQA, KDA, KDA, KDA]
    kw = dict(vocab_size=VOCAB, hidden_size=64, num_hidden_layers=4,
              gqa_layers=[0], num_heads=4, num_kv_heads=2, head_dim=16,
              linear_num_heads=4, linear_head_dim=16, linear_low_rank=16,
              moe_intermediate_size=32, n_routed_experts=16,
              num_experts_per_tok=2, held_experts=(4, 4),
              max_position_embeddings=256)
    kw.update(over)
    return SolarOpen2Config(**kw)


def _reseed(m, seed):
    """Fresh weights into the same model (one traced step serves every
    seed): the model's initialisers by hand, then the builder's draws
    away from zero."""
    rs = np.random.RandomState(seed)
    h = m.config.hidden_size
    for name, p in m.named_parameters():
        leaf = name.split(".")[-1]
        if leaf in ("ln1", "ln2", "norm", "out_norm"):
            continue
        std = {"router_w": h ** -0.5, "conv_q": 0.5, "conv_k": 0.5,
               "conv_v": 0.5}.get(leaf, 0.02)
        p.set_value(rs.normal(0.0, std, p.shape).astype("float32"))
    for blk in m.blocks:
        blk.router_b.set_value(rs.uniform(-.3, .3, blk.router_b.shape)
                               .astype("float32"))
    m.seed_decays(rs)


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    m = SolarOpen2ForCausalLM(_config())
    _reseed(m, 2)
    m.eval()
    return m


def _reference_args(c):
    return dict(gqa_layers=tuple(c.gqa_layers), heads=c.num_heads,
                kv=c.num_kv_heads, d=c.head_dim, eps=c.rms_eps,
                top_k=c.num_experts_per_tok, first_held=c.held_experts[0],
                routed_scale=c.routed_scaling_factor)


_REFERENCE = {}


def _reference(model, ids, dtype=jnp.float32, omit=()):
    """The plain reference's logits, one compiled forward a variant."""
    key = (id(model), len(ids), jnp.dtype(dtype).name, tuple(omit))
    if key not in _REFERENCE:
        args = _reference_args(model.config)
        _REFERENCE[key] = jax.jit(lambda w, x: ref.forward_logits(
            w, x, dtype=dtype, omit=omit, **args))
    return np.asarray(_REFERENCE[key](model.described_params(),
                                      jnp.asarray(ids)), np.float32)


class _Step:
    """The model's ragged step over fresh caches of its own description,
    fed by hand: ``feed(counts)`` gives every sequence its next
    ``counts[i]`` tokens in one step."""

    def __init__(self, model, seqs, ps=4, slots=None, jitted=None,
                 width=None):
        self.params, step = model.build_ragged_decode_step()
        self.cache = step.cache
        self.step = jitted or jax.jit(step)
        self.seqs, self.ps = seqs, ps
        b = len(seqs)
        self.ppseq = -(-max(len(s) for s in seqs) // ps)
        self.sink = b * self.ppseq
        self.pools = self.cache.new_pools(self.sink + 1, ps, "float32", b)
        self.full = np.arange(b * self.ppseq, dtype="int32") \
            .reshape(b, self.ppseq)
        self.tables = self.cache.tables(
            self.full, np.arange(b) if slots is None else slots, 0)
        self.done = [0] * b
        self.width = width        # of every step wider than a token
        self.logits = [dict() for _ in seqs]      # position -> last row

    def feed(self, counts):
        b, ps, width = len(self.seqs), self.ps, max(max(counts), 1)
        if self.width and width > 1:
            width = self.width
        tok = np.zeros((b, width), "int64")
        pos = np.zeros((b, width), "int32")
        pid = np.full((b, width), self.sink, "int32")
        slot = np.zeros((b, width), "int32")
        for i, n in enumerate(counts):
            p = np.arange(self.done[i], self.done[i] + n)
            tok[i, :n], pos[i, :n] = self.seqs[i][p], p
            pid[i, :n], slot[i, :n] = self.full[i, p // ps], p % ps
        kv = np.asarray([d + n for d, n in zip(self.done, counts)], "int32")
        logits, self.pools, counted = self.step(
            self.params, tok, pos, self.pools, pid, slot, kv,
            np.asarray(counts, "int32"), self.tables)
        for i, n in enumerate(counts):
            self.done[i] += n
            if n:
                self.logits[i][self.done[i] - 1] = np.asarray(logits[i])
        return np.asarray(counted)

    def run(self, prompt_lens, chunk):
        """Prefill in chunks of ``chunk``, then one token a step to each
        sequence's end."""
        while any(d < len(s) for d, s in zip(self.done, self.seqs)):
            self.feed([min(chunk, n - d) if d < n else int(d < len(s))
                       for d, n, s in zip(self.done, prompt_lens,
                                          self.seqs)])
        return self.logits


def _worst(got, want):
    return max(float(np.max(np.abs(row - want[p]))
                     / np.max(np.abs(want[p]))) for p, row in got.items())


def test_step_matches_reference_through_chunks_and_the_state(model, rng):
    """Prompts of 141 and 70 tokens in chunks of 100 — a chunk is cut
    into blocks of 64 and 36 rows, the second sequence's one chunk into
    64 and 6 — then decode to 150 and 90 through the state, the two
    sequences of unlike length in one step throughout and their slots
    not their lanes."""
    seqs = [rng.randint(0, VOCAB, (150,)), rng.randint(0, VOCAB, (90,))]
    step = _Step(model, seqs, slots=[1, 0])
    assert step.cache.n_state == 3 and step.cache.n_full == 1
    assert step.cache.layers[1] == LaneState(((4, 16, 16), (3, 192)))
    assert [tuple(a.shape for a in kept) for kept in step.pools[1:3]] \
        == [((2, 4, 16, 16), (2, 3, 192))] * 2
    got = step.run([141, 70], 100)
    for i, s in enumerate(seqs):
        assert len(got[i]) == (11, 21)[i]      # a chunk's last row too
        assert _worst(got[i], _reference(model, s)) < 1e-5
    # a sequence that starts anew in a used slot starts from zeros:
    # nothing is cleared by hand between the two runs
    again = _Step(model, seqs[::-1], slots=[1, 0], jitted=step.step)
    again.pools = step.pools
    got = again.run([70, 141], 100)
    assert _worst(got[0], _reference(model, seqs[1])) < 1e-5


def _recurrence(state, q, k, v, g, beta):
    """The delta rule, one token after the other, for one sequence in
    float64 numpy: ``(o [T, H, dv], state')``."""
    state = np.asarray(state, "float64").copy()
    out = []
    for t in range(q.shape[0]):
        state = np.exp(g[t])[:, :, None] * state
        seen = np.einsum("hkv,hk->hv", state, k[t])
        state = state + beta[t][:, None, None] * k[t][:, :, None] \
            * (v[t] - seen)[:, None, :]
        out.append(np.einsum("hkv,hk->hv", state, q[t]))
    return np.stack(out), state


def test_chunked_form_and_one_token_update_match_the_recurrence(rng):
    """One layer's state over packed rows: a chunk of 70 rows (blocks of
    64 and 6), a single row, an idle lane and a chunk of 5 rows that
    starts its sequence; slots are not lanes, and the slot of the idle
    lane and the slot nobody owns must not move."""
    nh, d, n_slots = 2, 8, 6
    q_lens = np.asarray([70, 1, 0, 5], "int32")
    offs = np.cumsum(q_lens) - q_lens
    slot = np.asarray([3, 0, 5, 2], "int32")
    reset = np.asarray([False, False, False, True])
    n = 80                                   # four rows carry no token
    lane = np.searchsorted(offs + q_lens, np.arange(n), side="right") \
        .clip(0, 3).astype("int32")
    at = (np.arange(n) - offs[lane]).astype("int32")
    f = lambda *shape: rng.standard_normal(shape).astype("float32")
    q, k, v = f(n, nh, d), f(n, nh, d), f(n, nh, d)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -rng.uniform(0.001, 0.7, (n, nh, d)).astype("float32")
    beta = rng.uniform(0.0, 2.0, (n, nh)).astype("float32")
    beta[76:] = 0.0                          # as the step masks them
    state = f(n_slots, nh, d, d)

    @jax.jit
    def both(state):
        args = [jnp.asarray(a) for a in (q, k, v, g, beta, offs, q_lens,
                                         slot)]
        o_slot, st = gd.gated_delta_step(state, *args, jnp.asarray(reset))
        o_rows, st = gd.gated_delta_chunks(st, *args, jnp.asarray(lane),
                                           jnp.asarray(at), 64)
        return o_slot, o_rows, st

    o_slot, o_rows, new = (np.asarray(a) for a in both(jnp.asarray(state)))
    for b, (lo, cnt, s) in enumerate(zip(offs, q_lens, slot)):
        if not cnt:
            continue
        start = np.zeros_like(state[s]) if reset[b] else state[s]
        rows = slice(lo, lo + cnt)
        want_o, want_s = _recurrence(start, q[rows], k[rows], v[rows],
                                     g[rows], beta[rows])
        got_o = o_slot[s][None] if cnt == 1 else o_rows[rows]
        np.testing.assert_allclose(got_o, want_o, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(new[s], want_s, rtol=2e-4, atol=2e-5)
    for s in (5, 1, 4):                      # the idle lane's, nobody's
        np.testing.assert_array_equal(new[s], state[s])
    assert gd.chunk_blocks(80, 4, 64) == 5 and gd.chunk_blocks(8, 8, 1) == 8


def test_short_convolution_carries_its_tail_from_chunk_to_chunk(rng):
    """A sequence of 11 rows fed as 2 + 1 + 8 (a chunk shorter than the
    tail, a single row, a chunk) beside a lane that idles reads what the
    whole convolution reads, and the idle slot's tail does not move."""
    k, dim = 4, 6
    x = rng.standard_normal((11, dim)).astype("float32")
    taps = rng.standard_normal((k, dim)).astype("float32")
    padded = np.concatenate([np.zeros((k - 1, dim), "float32"), x])
    want = sum(taps[i] * padded[i:i + 11] for i in range(k))
    tail = rng.standard_normal((3, k - 1, dim)).astype("float32")
    keep = tail.copy()
    got, fed = [], 0
    for count in (2, 1, 8):
        rows = np.zeros((8, dim), "float32")
        rows[:count] = x[fed:fed + count]
        q_lens = jnp.asarray([0, count], jnp.int32)
        y, tail = gd.short_conv_rows(
            jnp.asarray(rows), jnp.asarray(taps), jnp.asarray(tail),
            jnp.asarray([0, 0], jnp.int32), q_lens,
            jnp.asarray([2, 1], jnp.int32),
            jnp.asarray([False, fed == 0]),
            jnp.arange(8, dtype=jnp.int32))
        got.append(np.asarray(y)[:count])
        fed += count
    np.testing.assert_allclose(np.concatenate(got), want, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(tail)[1], x[-3:], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(tail)[[0, 2]], keep[[0, 2]])


def test_the_shares_of_an_expert_layer_add_up_to_the_whole(rng):
    """Section 4 of the model-configs guide: the routed parts that the
    four chips holding 4 of 16 experts each compute (the program's
    ``held_experts_swiglu``), with the shared expert — which every chip
    computes alike — counted once, add up to what the uncut reference
    gives for the whole layer."""
    paddle.seed(3)
    whole = SolarOpen2ForCausalLM(_config(num_hidden_layers=1,
                                          gqa_layers=[0],
                                          held_experts=(0, 16)))
    _reseed(whole, 4)
    lp = whole.described_params()["layers"][0]
    h = jnp.asarray(rng.standard_normal((24, 64)).astype("float32"))
    want, _ = ref.expert_layer(lp, h, top_k=2, first_held=0,
                               routed_scale=1.0)
    picks, weights = sigmoid_topk_route(h, lp["router_w"], lp["router_b"], 2)
    total, rows = jnp.zeros_like(h), 0
    for first in (0, 4, 8, 12):
        held = slice(first, first + 4)
        y, n = held_experts_swiglu(h, picks, weights, jnp.ones((24,), bool),
                                   lp["wg"][held], lp["wu"][held],
                                   lp["wd"][held], first)
        total, rows = total + y, rows + int(n.sum())
    assert rows == 24 * 2                    # every pick lives on one chip
    shared = (jax.nn.silu(h @ lp["shared_wg"]) * (h @ lp["shared_wu"])) \
        @ lp["shared_wd"]
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               rtol=1e-4, atol=1e-6)


def _greedy_by_hand(model, prompt, n_new):
    """Greedy decoding by feeding the step by hand: the prompt in
    chunks of 32, then a token a step."""
    seq = np.zeros((len(prompt) + n_new,), "int64")
    seq[:len(prompt)] = prompt
    step = _Step(model, [seq], ps=4, width=32,
                 jitted=_greedy_by_hand.step.setdefault(
                     id(model),
                     jax.jit(model.build_ragged_decode_step()[1])))
    for lo in range(0, len(prompt), 32):
        step.feed([min(32, len(prompt) - lo)])
    out = []
    for i in range(n_new):
        at = len(prompt) + i
        seq[at] = int(np.argmax(step.logits[0][at - 1]))
        out.append(int(seq[at]))
        if i + 1 < n_new:
            step.feed([1])
    return out


_greedy_by_hand.step = {}


def test_engine_serves_mixed_lengths_as_the_step_fed_by_hand(
        model, rng, tmp_path):
    """Five requests of unlike length over three lanes, chunks of 16,
    one step ahead: the engine's tokens are those of the step fed by
    hand with another chunking; the records carry the state's counters
    and ``engine.stats()`` sums them."""
    from paddle_tpu.observability import events as obs_events
    prompts = [rng.randint(0, VOCAB, (n,)).tolist()
               for n in (37, 5, 69, 21, 1)]
    set_flags({"FLAGS_observability_dir": str(tmp_path)})
    try:
        engine = ServingEngine(model, max_batch=3, page_size=4,
                               max_prefill_chunk=16, prefix_caching=False)
        with engine:
            reqs = [engine.submit(p, max_new_tokens=6) for p in prompts]
            got = [r.wait(timeout=300) for r in reqs]
        stats = engine.stats()
    finally:
        set_flags({"FLAGS_observability_dir": ""})
    assert got == [_greedy_by_hand(model, p, 6) for p in prompts]
    assert stats["steps_ahead"] > 0 and stats["evictions"] == 0
    steps = [e for e in obs_events.read_events(str(tmp_path))
             if e["kind"] == "batch_step"]
    for field in ("state_lanes", "state_resets", "scan_rows"):
        assert all(field in e for e in steps)
        assert sum(e[field] for e in steps) == stats[field]
    assert stats["state_resets"] == 5        # one a request
    assert all(e["state_lanes"] == e["batch"] for e in steps)
    # 37 = 16 + 16 + 5, 5, 69 = 4 x 16 + 5, 21 = 16 + 5: chunks of more
    # than one row; the prompt of one token takes the one-token update
    assert stats["scan_rows"] == 37 + 5 + 69 + 21
    assert all(e["scan_rows"] == 0 for e in steps if e["q_width"] == 1)
    assert sorted(engine.scheduler._free_slots) == [0, 1, 2]


@pytest.mark.parametrize("interpret", [True, False])
def test_the_records_count_the_expert_layers_that_took_the_kernel(
        rng, tmp_path, interpret):
    """``expert_kernel_layers``: every expert layer of a decode-only
    step (its 3 rows of 4 picks fit one tile, so a taken branch is the
    kernel that reads the expert's weights itself — interpreted here),
    none of a step with a chunk (128 rows of 4 picks do not), none at
    all where the kernel is not available; ``engine.stats()`` sums it,
    and the tokens are XLA's either way."""
    from paddle_tpu.observability import events as obs_events
    paddle.seed(5)
    model = SolarOpen2ForCausalLM(_config(num_experts_per_tok=4))
    _reseed(model, 3)
    model.eval()
    prompts = [rng.randint(0, VOCAB, (n,)).tolist() for n in (21, 6)]
    keep = get_flags(["FLAGS_pallas_interpret"])
    set_flags({"FLAGS_observability_dir": str(tmp_path),
               "FLAGS_pallas_interpret": interpret})
    try:
        engine = ServingEngine(model, max_batch=3, page_size=4,
                               max_prefill_chunk=16, prefix_caching=False)
        with engine:
            reqs = [engine.submit(p, max_new_tokens=4) for p in prompts]
            got = [r.wait(timeout=300) for r in reqs]
        stats = engine.stats()
    finally:
        set_flags({"FLAGS_observability_dir": "", **keep})
    assert got == [_greedy_by_hand(model, p, 4) for p in prompts]
    steps = [e for e in obs_events.read_events(str(tmp_path))
             if e["kind"] == "batch_step"]
    narrow = [e for e in steps if e["q_width"] == 1]
    assert narrow and len(narrow) < len(steps)
    assert all(e["expert_kernel_layers"] == (4 if interpret else 0)
               for e in narrow)
    assert all(e["expert_kernel_layers"] == 0
               for e in steps if e["q_width"] > 1)
    assert stats["expert_kernel_layers"] == \
        sum(e["expert_kernel_layers"] for e in steps)


def test_eviction_and_resume_reproduce_the_tokens(model, rng):
    """Too few pages for three sequences to end: one is evicted, its
    slot goes back, and its re-prefill from position 0 clears whatever
    state the slot it is given then holds."""
    prompts = [rng.randint(0, VOCAB, (14,)).tolist() for _ in range(3)]
    want = [_greedy_by_hand(model, p, 12) for p in prompts]
    engine = ServingEngine(model, max_batch=3, page_size=4, num_pages=17,
                           max_pages_per_seq=8, max_prefill_chunk=8,
                           prefix_caching=False)
    with engine:
        reqs = [engine.submit(p, max_new_tokens=12) for p in prompts]
        got = [r.wait(timeout=300) for r in reqs]
    assert engine.scheduler.evictions >= 1
    assert engine.stats()["state_resets"] >= 3 + engine.scheduler.evictions
    assert got == want
    assert engine.pool.available() == engine.pool.num_pages - 1
    assert sorted(engine.scheduler._free_slots) == [0, 1, 2]


def test_prefix_cache_and_fused_window_refuse_a_model_that_keeps_state(
        model):
    """One message for both kinds of a sequence's own cache: it names
    what the prefix cache cannot restore."""
    with pytest.raises(ValueError, match=r"0 window attention layers, 3 "
                                         r"state layers.*a ring's tail or "
                                         r"a lane's state.*"
                                         r"prefix_caching=False"):
        ServingEngine(model, prefix_caching=True)
    with pytest.raises(TypeError, match=r"SolarOpen2ForCausalLM.*reach no "
                                        r"lane's state.*step\.cache\."
                                        r"n_state = 3"):
        build_fused_window_step(model, 4)
    keep = get_flags(["FLAGS_serving_fused_steps"])
    set_flags({"FLAGS_serving_fused_steps": 4})
    try:
        with pytest.raises(ValueError, match="FLAGS_serving_fused_steps=4 "
                                             "with SolarOpen2ForCausalLM"):
            ServingEngine(model, prefix_caching=False)
    finally:
        set_flags(keep)


def test_state_bytes_do_not_depend_on_the_positions():
    def state_bytes(max_pos):
        paddle.seed(3)
        m = SolarOpen2ForCausalLM(_config(
            num_hidden_layers=2, gqa_layers=[0],
            max_position_embeddings=max_pos))
        e = ServingEngine(m, max_batch=2, page_size=16,
                          prefix_caching=False)
        size = lambda kept: sum(a.size * a.dtype.itemsize for a in kept)
        assert all(a.dtype == jnp.float32 for a in e._pools[1])
        return size(e._pools[1]), size(e._pools[0])
    state_8k, pages_8k = state_bytes(8192)
    state_64k, pages_64k = state_bytes(65536)
    assert state_8k == state_64k == 2 * (4 * 16 * 16 + 3 * 192) * 4
    assert pages_64k > 7 * pages_8k


def test_generate_waits_out_a_cold_compile(model):
    """``ServingEngine.generate`` is what a benchmark run warms its
    programs through: its wait has to outlast the compilation of a
    width's two programs (more than 60 s, cold, at the published
    widths on the chip), and a caller may still set its own."""
    import inspect
    wait = inspect.signature(ServingEngine.generate).parameters["timeout"]
    assert wait.default >= 600.0
    with ServingEngine(model, max_batch=2, page_size=4, num_pages=40,
                       max_prefill_chunk=16, prefix_caching=False) as e:
        assert len(e.generate([5, 6, 7], max_new_tokens=2, timeout=300)) == 2
