"""A model whose token mixer is latent attention over the keys an index
picks (GLM-5: a 576-wide latent row and a 128-wide index key a token,
top-k keys a query row; routed experts beside a shared one behind a
leading dense layer) through the ragged step, the page pools and the
engine, against ``benchmark/reference/glm5.py`` — which expands every
latent into a head's keys and values — on seeded weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.flags import get_flags, set_flags
from paddle_tpu.models.generation import (IndexKind, LatentAttentionKind,
                                          LatentPages, LayerDescription,
                                          FeedForwardKind,
                                          build_fused_window_step)
from paddle_tpu.models.glm5 import Glm5Config, Glm5ForCausalLM
from paddle_tpu.ops import latent_select as ls
from paddle_tpu.ops.routed_experts import (held_experts_swiglu,
                                           sigmoid_topk_route)
from paddle_tpu.serving import ServingEngine

from benchmark.builders import glm5 as builder
from benchmark.reference import glm5 as ref

VOCAB, TOP_K = 256, 16


def _config(**over):
    # a leading dense layer and two expert layers; sixteen index heads, so
    # that a row's scores are never zero for two keys at once
    kw = dict(vocab_size=VOCAB, hidden_size=64, num_hidden_layers=3,
              num_heads=4, q_lora_rank=32, kv_lora_rank=32,
              qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
              index_n_heads=16, index_head_dim=16, index_topk=TOP_K,
              intermediate_size=128, first_k_dense_replace=1,
              moe_intermediate_size=32, n_routed_experts=16,
              num_experts_per_tok=2, held_experts=(4, 4),
              max_position_embeddings=256)
    kw.update(over)
    return Glm5Config(**kw)


def _reseed(m, seed):
    """Fresh weights into the same model: the model's initialisers by
    hand at a width that makes every mechanism count at this size, then
    the builder's draws away from the initial state."""
    rs = np.random.RandomState(seed)
    h = m.config.hidden_size
    for name, p in m.named_parameters():
        leaf = name.split(".")[-1]
        if leaf in ("ln1", "ln2", "norm", "q_norm", "kv_norm",
                    "wi_k_norm_w", "wi_k_norm_b"):
            continue
        std = {"router_w": h ** -0.5}.get(leaf, 0.2)
        p.set_value(rs.normal(0.0, std, p.shape).astype("float32"))
    for blk in m.blocks:
        if hasattr(blk, "router_b"):
            blk.router_b.set_value(rs.uniform(-.3, .3, blk.router_b.shape)
                                   .astype("float32"))
    m.seed_index(rs)


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    m = Glm5ForCausalLM(_config())
    _reseed(m, 2)
    m.eval()
    return m


@pytest.fixture
def rng():
    return np.random.RandomState(5)


def reference_args(c):
    return dict(heads=c.num_heads, rank=c.kv_lora_rank,
                nope=c.qk_nope_head_dim, rope=c.qk_rope_head_dim,
                value=c.v_head_dim, theta=c.rope_theta,
                index_heads=c.index_n_heads, index_dim=c.index_head_dim,
                index_topk=c.index_topk, eps=c.rms_eps,
                top_k=c.num_experts_per_tok, first_held=c.held_experts[0],
                routed_scale=c.routed_scaling_factor)


_REFERENCE = {}


def reference(model, ids, dtype=jnp.float32, omit=()):
    """The plain reference's logits, one compiled forward a variant."""
    key = (id(model), len(ids), jnp.dtype(dtype).name, tuple(omit))
    if key not in _REFERENCE:
        args = reference_args(model.config)
        _REFERENCE[key] = jax.jit(lambda w, x: ref.forward_logits(
            w, x, dtype=dtype, omit=omit, **args))
    return np.asarray(_REFERENCE[key](builder.weights(model),
                                      jnp.asarray(ids)), np.float32)


class Step:
    """The model's ragged step over fresh pools of its own description,
    fed by hand: ``feed(counts)`` gives every sequence its next
    ``counts[i]`` tokens in one step.  A sequence's pages are scattered
    over the pool, not in a row."""

    def __init__(self, model, seqs, ps=4, jitted=None, width=None):
        self.params, step = model.build_ragged_decode_step()
        self.cache = step.cache
        self.step = jitted or jax.jit(step)
        self.seqs, self.ps = seqs, ps
        b = len(seqs)
        self.ppseq = -(-max(len(s) for s in seqs) // ps)
        self.sink = b * self.ppseq
        self.pools = self.cache.new_pools(self.sink + 1, ps, "float32", b)
        self.full = np.random.RandomState(0).permutation(self.sink) \
            .astype("int32").reshape(b, self.ppseq)
        self.tables = self.cache.tables(self.full, np.arange(b), 0)
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


def worst(got, want):
    return max(float(np.max(np.abs(row - want[p]))
                     / np.max(np.abs(want[p]))) for p, row in got.items())


def test_step_matches_reference_through_chunks_that_cross_top_k(model, rng):
    """Prompts of 51 and 10 tokens in chunks of 14 (pages of 4: a chunk
    boundary lies inside a page) with ``index_topk`` 16: the first
    prompt's second chunk starts choosing 16 of up to 51 keys, the
    second prompt sees every key and decodes beside the first's
    prefill; then both decode, the first choosing 16 of up to 56.  The
    absorbed step over its latent pools against the expanded
    reference."""
    seqs = [rng.randint(0, VOCAB, (56,)), rng.randint(0, VOCAB, (22,))]
    step = Step(model, seqs)
    assert step.cache.n_latent == 3 and step.cache.n_full == 0 \
        and step.cache.n_state == 0 and step.cache.window is None
    assert step.cache.layers[0] == LatentPages((40, 16))
    assert [tuple(a.shape for a in kept) for kept in step.pools] \
        == [((1, 29, 4, 40), (1, 29, 4, 16))] * 3
    got = step.run([51, 10], 14)
    for i, s in enumerate(seqs):
        assert len(got[i]) == (9, 13)[i]       # a chunk's last row too
        assert worst(got[i], reference(model, s)) < 1e-5
    # the index did something: with every key kept the logits differ
    everything = reference(model, seqs[0], omit=("index",))
    assert worst(got[0], everything) > 1e-2
    # ... and nothing while a row sees no more than top_k keys
    early = {p: row for p, row in got[1].items() if p < TOP_K}
    assert len(early) == 7
    assert worst(early, reference(model, seqs[1], omit=("index",))) < 1e-5


def _brute_force(q_nope, q_rot, w_uk, w_uv, q_i, w_i, latent, keys, p,
                 top_k, scale):
    """One query row at position ``p`` in float64: the kept keys by a
    stable sort of all ``p + 1`` scores, a head's keys and values READ
    OUT of the kept latents (``k[s, h] = [w_uk[h] c_s, k_r]``, ``v[s, h]
    = c_s w_uv[h]``: the expanded form), softmax over them alone."""
    f = lambda a: np.asarray(a, "float64")
    seen, rank = p + 1, w_uk.shape[-1]
    keep = np.arange(seen)
    if q_i is not None and seen > top_k:
        score = (f(w_i)[:, None]
                 * np.maximum(f(q_i) @ f(keys[:seen]).T, 0.0)).sum(0)
        keep = np.argsort(-score, kind="stable")[:top_k]
    c, k_r = f(latent[keep])[:, :rank], \
        f(latent[keep])[:, rank:rank + q_rot.shape[-1]]
    k_nope = np.einsum("sc,hdc->shd", c, f(w_uk))
    logits = (np.einsum("hd,shd->hs", f(q_nope), k_nope)
              + f(q_rot) @ k_r.T) * scale
    a = np.exp(logits - logits.max(-1, keepdims=True))
    a /= a.sum(-1, keepdims=True)
    return np.einsum("hs,shv->hv", a, np.einsum("sc,hcv->shv", c, f(w_uv)))


# (step width, rows a sequence, keys a sequence): below, at and above
# top_k = 8, a chunk that crosses it, an idle lane, a long cache
_SELECTION_CASES = {
    "decode_only": (1, [1, 1, 0], [30, 5, 0]),
    "at_top_k": (4, [1, 3, 1], [8, 11, 9]),
    "chunk_crosses_top_k": (16, [13, 1, 0], [17, 9, 0]),
    "two_chunks_and_a_lane": (16, [5, 1, 16], [8, 20, 33]),
    "long_cache": (16, [16, 0, 1], [47, 0, 48]),
    # the last chunk's block would run past the step's 32 rows
    "chunk_at_the_steps_end": (16, [16, 1, 15], [40, 9, 30]),
}


@pytest.mark.parametrize("indexed", [True, False],
                         ids=["indexed", "every_key"])
@pytest.mark.parametrize("case", sorted(_SELECTION_CASES))
def test_selection_matches_a_float64_brute_force(case, indexed, rng):
    q_width, q_lens, kv_lens = _SELECTION_CASES[case]
    b, ps, ppseq = 3, 4, 12
    pages, width, dim, nh, ih, top_k = b * ppseq + 1, 24, 8, 2, 16, 8
    f = lambda *shape: rng.standard_normal(shape).astype("float32")
    latent_pool, index_pool = f(1, pages, ps, width), f(1, pages, ps, dim)
    tables = rng.permutation(pages - 1)[:b * ppseq] \
        .reshape(b, ppseq).astype("int32")
    q_lens, kv_lens = (np.asarray(a, "int32") for a in (q_lens, kv_lens))
    n = b if q_width == 1 else 32
    offs = (np.arange(b) if q_width == 1
            else np.cumsum(q_lens) - q_lens).astype("int32")
    pos = np.zeros((n,), "int32")
    for i in range(b):
        pos[offs[i]:offs[i] + q_lens[i]] = \
            kv_lens[i] - q_lens[i] + np.arange(q_lens[i])
    lane = np.clip((np.arange(n)[:, None] >= offs[None, :]).sum(1) - 1,
                   0, b - 1).astype("int32")
    # a cache row of 24 values: a latent of 16, a shared key of 6 and
    # padding; heads of 5 + 6 that read values of 7 out of the latent
    rank, rope, nope, value = 16, 6, 5, 7
    q_nope, q_rot = f(n, nh, nope), f(n, nh, rope)
    w_uk, w_uv = f(nh, nope, rank), f(nh, rank, value)
    q_i, w_i = f(n, ih, dim), f(n, ih)

    @jax.jit
    def attend(latent_pool, index_pool):
        index = (jnp.asarray(q_i), jnp.asarray(w_i), index_pool, top_k) \
            if indexed else None
        return ls.attend_selected(
            jnp.asarray(q_nope), jnp.asarray(q_rot), jnp.asarray(w_uk),
            jnp.asarray(w_uv), latent_pool, index, jnp.asarray(tables),
            jnp.asarray(kv_lens), jnp.asarray(pos), jnp.asarray(offs),
            jnp.asarray(q_lens), jnp.asarray(lane), q_width, 0.3)

    got = np.asarray(attend(jnp.asarray(latent_pool),
                            jnp.asarray(index_pool)))
    assert got.shape == (n, nh, value) and np.all(np.isfinite(got))
    of = lambda pool, i: pool[0][tables[i]].reshape(ppseq * ps, -1)
    for i in range(b):
        for r in range(offs[i], offs[i] + q_lens[i]):
            want = _brute_force(
                q_nope[r], q_rot[r], w_uk, w_uv,
                q_i[r] if indexed else None, w_i[r], of(latent_pool, i),
                of(index_pool, i), pos[r], top_k, 0.3)
            np.testing.assert_allclose(got[r], want, rtol=5e-5, atol=5e-6)


@pytest.mark.parametrize("k", [1, 7, 64, 200])
def test_kth_largest_is_the_sorted_rows_kth(k, rng):
    x = rng.standard_normal((5, 200)).astype("float32")
    x[1, :150] = -np.inf                      # 50 real entries in row 1
    x[2] = np.abs(x[2])
    x[3, ::2] = -x[3, ::2] * 1e-30            # tiny, of both signs
    keys = np.asarray(ls.ordered_bits(jnp.asarray(x)))
    assert np.array_equal(np.argsort(keys, axis=1, kind="stable"),
                          np.argsort(x, axis=1, kind="stable"))
    got = np.asarray(ls.kth_largest(jnp.asarray(keys), k))
    assert np.array_equal(got, np.sort(keys, axis=1)[:, -k])
    hidden = np.where(np.isfinite(x), keys, 0).astype("uint32")
    got = np.asarray(ls.kth_largest(jnp.asarray(hidden), k))
    assert got[1] == (np.sort(hidden[1])[-k] if k <= 50 else 0)


def test_the_shares_of_an_expert_layer_add_up_to_the_whole(rng):
    """Section 4 of the model-configs guide: the routed parts that the
    four chips holding 4 of 16 experts each compute (the program's
    ``held_experts_swiglu``, times the routed scale), with the shared
    expert — which every chip computes alike — counted once, add up to
    what the uncut reference gives for the whole layer."""
    paddle.seed(3)
    whole = Glm5ForCausalLM(_config(num_hidden_layers=1,
                                    first_k_dense_replace=0,
                                    held_experts=(0, 16)))
    _reseed(whole, 4)
    lp = whole.described_params()["layers"][0]
    h = jnp.asarray(rng.standard_normal((24, 64)).astype("float32"))
    want, _ = ref.expert_layer(lp, h, top_k=2, first_held=0,
                               routed_scale=2.5)
    picks, weights = sigmoid_topk_route(h, lp["router_w"], lp["router_b"], 2)
    total, rows = jnp.zeros_like(h), 0
    for first in (0, 4, 8, 12):
        held = slice(first, first + 4)
        y, n = held_experts_swiglu(h, picks, weights, jnp.ones((24,), bool),
                                   lp["wg"][held], lp["wu"][held],
                                   lp["wd"][held], first)
        total, rows = total + 2.5 * y, rows + int(n.sum())
    assert rows == 24 * 2                    # every pick lives on one chip
    shared = (jax.nn.silu(h @ lp["shared_wg"]) * (h @ lp["shared_wu"])) \
        @ lp["shared_wd"]
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               rtol=1e-4, atol=1e-6)


def greedy_by_hand(model, prompt, n_new):
    """Greedy decoding by feeding the step by hand: the prompt in
    chunks of 32, then a token a step."""
    seq = np.zeros((len(prompt) + n_new,), "int64")
    seq[:len(prompt)] = prompt
    step = Step(model, [seq], ps=4, width=32,
                jitted=greedy_by_hand.step.setdefault(
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


greedy_by_hand.step = {}


def _select_counts(lengths, n_new, top_k):
    """What a request of each prompt length asks of one layer's index:
    every position ``p < length + n_new - 1`` is a row once."""
    seen = np.concatenate([np.arange(1, n + n_new) for n in lengths])
    return (int((seen > top_k).sum()), int(seen.sum()),
            int(np.minimum(seen, top_k).sum()))


def test_engine_serves_mixed_lengths_as_the_step_fed_by_hand(
        model, rng, tmp_path):
    """Five requests of unlike length over three lanes, chunks of 16,
    one step ahead: the engine's tokens are those of the step fed by
    hand with another chunking; the records carry the selection's three
    counters and ``engine.stats()`` sums them."""
    from paddle_tpu.observability import events as obs_events
    lengths = (37, 5, 69, 21, 1)
    prompts = [rng.randint(0, VOCAB, (n,)).tolist() for n in lengths]
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
    assert got == [greedy_by_hand(model, p, 6) for p in prompts]
    assert stats["steps_ahead"] > 0 and stats["evictions"] == 0
    steps = [e for e in obs_events.read_events(str(tmp_path))
             if e["kind"] == "batch_step"]
    for field in ("select_rows", "keys_visible", "keys_selected"):
        assert all(field in e for e in steps)
        assert sum(e[field] for e in steps) == stats[field]
    # a lane that ends is fed one step more while its last token is
    # unread (PR 30): those rows are counted too, so at least the
    # requests' own
    own = _select_counts(lengths, 6, TOP_K)
    assert stats["select_rows"] >= own[0] and stats["keys_visible"] >= own[1]
    assert own[2] <= stats["keys_selected"] < stats["keys_visible"]
    assert all(e["keys_selected"] <= e["keys_visible"] for e in steps)
    assert all(e["attn_blocks"] == e["state_lanes"] == 0 for e in steps)


def test_select_counts_are_the_rows_own_sums(model):
    """The host's closed form against a count row by row."""
    engine = ServingEngine(model, max_batch=3, page_size=4,
                           prefix_caching=False)

    class Plan:
        q_lens = np.asarray([16, 1, 0, 5, 3], "int32")
        kv_lens = np.asarray([20, 40, 0, 5, 17], "int32")

    seen = np.concatenate([np.arange(kv - q + 1, kv + 1) for q, kv in
                           zip(Plan.q_lens, Plan.kv_lens)])
    assert engine._select_counts(Plan) == (
        int((seen > TOP_K).sum()), int(seen.sum()),
        int(np.minimum(seen, TOP_K).sum())) == (6, 303, 268)


def test_eviction_and_resume_reproduce_the_tokens(model, rng):
    """Too few pages for three sequences to end: one is evicted and
    prefills again into other pages."""
    prompts = [rng.randint(0, VOCAB, (14,)).tolist() for _ in range(3)]
    want = [greedy_by_hand(model, p, 12) for p in prompts]
    engine = ServingEngine(model, max_batch=3, page_size=4, num_pages=17,
                           max_pages_per_seq=8, max_prefill_chunk=8,
                           prefix_caching=False)
    with engine:
        reqs = [engine.submit(p, max_new_tokens=12) for p in prompts]
        got = [r.wait(timeout=300) for r in reqs]
    assert engine.scheduler.evictions >= 1
    assert got == want
    assert engine.pool.available() == engine.pool.num_pages - 1


def test_a_prefix_hit_reads_the_latent_pages_of_the_first_request(
        model, rng):
    """Both pools of a latent layer are paged by token and shared
    through ``PagePool``, so a prefix hit is sound: the second request
    skips the shared pages' prefill and returns the same tokens."""
    shared = rng.randint(0, VOCAB, (40,)).tolist()
    tails = [rng.randint(0, VOCAB, (9,)).tolist() for _ in range(2)]
    want = [greedy_by_hand(model, shared + t, 5) for t in tails]
    engine = ServingEngine(model, max_batch=2, page_size=4,
                           max_prefill_chunk=16, prefix_caching=True)
    with engine:
        got = [engine.submit(shared + t, max_new_tokens=5).wait(timeout=300)
               for t in tails]
    assert got == want
    assert engine.stats()["prefix_cache"]["hits"] >= 1


def test_the_fused_window_refuses_a_latent_layer(model):
    with pytest.raises(TypeError, match=r"Glm5ForCausalLM.*take no latent "
                                        r"layer's pools.*step\.cache\."
                                        r"n_latent = 3"):
        build_fused_window_step(model, 4)
    keep = get_flags(["FLAGS_serving_fused_steps"])
    set_flags({"FLAGS_serving_fused_steps": 4})
    try:
        with pytest.raises(ValueError, match="FLAGS_serving_fused_steps=4 "
                                             "with Glm5ForCausalLM"):
            ServingEngine(model, prefix_caching=False)
    finally:
        set_flags(keep)


@pytest.mark.parametrize("heads", [4, 16])
def test_pool_bytes_a_token_do_not_depend_on_the_heads(heads):
    """A token leaves (640 + 128) x 4 B a layer at the published widths
    of the latent (512 + 64, padded to whole tiles) and the index key,
    however many heads read them."""
    paddle.seed(3)
    m = Glm5ForCausalLM(_config(
        num_hidden_layers=2, num_heads=heads, kv_lora_rank=512,
        qk_rope_head_dim=64, index_head_dim=128))
    e = ServingEngine(m, max_batch=2, page_size=16, num_pages=9,
                      prefix_caching=False)
    assert [tuple(a.shape for a in kept) for kept in e._pools] \
        == [((1, 9, 16, 640), (1, 9, 16, 128))] * 2
    per_token = sum(a.size * a.dtype.itemsize for a in e._pools[0]) \
        // (9 * 16)
    assert per_token == (640 + 128) * 4 == 3072


def test_a_layer_has_one_mixer_and_an_index_its_layers_rotation():
    ff = FeedForwardKind(width=8)
    latent = LatentAttentionKind(heads=2, q_rank=8, kv_rank=8, nope_dim=4,
                                 rope_dim=4, value_dim=4)
    assert LayerDescription(None, ff, latent_attention=latent) \
        .latent_attention is latent
    with pytest.raises(ValueError, match="one of the three"):
        LayerDescription(None, ff)
    with pytest.raises(ValueError, match="rotates by its layer's own"):
        LatentAttentionKind(heads=2, q_rank=8, kv_rank=8, nope_dim=4,
                            rope_dim=4, value_dim=4,
                            index=IndexKind(heads=2, dim=8, top_k=4,
                                            rotary_dim=8))
