"""A model that describes its layers (MiMo-V2: window and full attention
layers of unlike heads, routed experts of which a chip holds some)
through the ragged step, the scheduler's rings and the engine, against
``benchmark/reference/mimo_v2.py`` on seeded weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.flags import get_flags, set_flags
from paddle_tpu.models.generation import (CacheDescription,
                                          build_fused_window_step)
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
from paddle_tpu.models.llama import LlamaForCausalLM, llama_config
from paddle_tpu.models.mimo_v2 import MiMoV2Config, MiMoV2ForCausalLM
from paddle_tpu.serving import ServingEngine

from benchmark.reference import mimo_v2 as ref

VOCAB, WINDOW = 96, 8


def _config(**over):
    kw = dict(vocab_size=VOCAB, hidden_size=64, num_heads=4, num_kv_heads=1,
              swa_num_kv_heads=2, head_dim=24, v_head_dim=16,
              sliding_window=WINDOW,
              hybrid_layer_pattern=[0, 1, 1, 0], moe_layer_freq=[0, 1, 1, 1],
              intermediate_size=96, moe_intermediate_size=32,
              n_routed_experts=16, num_experts_per_tok=2,
              held_experts=(4, 4), max_position_embeddings=128)
    kw.update(over)
    return MiMoV2Config(**kw)


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    m = MiMoV2ForCausalLM(_config())
    rs = np.random.RandomState(2)
    for blk in m.blocks:                 # away from zero, as the builder's
        if blk.sink is not None:
            blk.sink.set_value(rs.uniform(2, 5, blk.sink.shape)
                               .astype("float32"))
        if hasattr(blk, "router_b"):
            blk.router_b.set_value(rs.uniform(-.3, .3, blk.router_b.shape)
                                   .astype("float32"))
    m.eval()
    return m


def _reference(model, ids, **change):
    c = model.config
    p = model.described_params()
    w = {k: p[k] for k in ("embed", "norm_w", "lm_w", "layers")}
    args = dict(
        layers_cfg=tuple((d.attention.window, d.attention.kv_heads,
                          d.attention.rope_theta)
                         for d in c.layer_descriptions()),
        heads=c.num_heads, dk=c.head_dim, dv=c.v_head_dim, rot=c.rotary_dim,
        window=c.sliding_window, v_scale=c.attention_value_scale,
        eps=c.rms_eps, top_k=c.num_experts_per_tok,
        first_held=c.held_experts[0])
    args.update(change)
    return np.asarray(ref.forward_logits(w, jnp.asarray(ids), **args))


def _through_the_step(model, seqs, prompt_lens, chunk, ps):
    """Prefill in chunks, then decode one token a step to each sequence's
    end; returns, per sequence, {position: last-row logits}."""
    params, step = model.build_ragged_decode_step()
    cache = step.cache
    jstep = jax.jit(step)
    b = len(seqs)
    ppseq = -(-max(len(s) for s in seqs) // ps)
    ring = cache.ring_pages(ps, chunk)
    sink = b * ppseq
    pools = cache.new_pools(sink + 1, ps, "float32", b, ring)
    full = np.arange(b * ppseq, dtype="int32").reshape(b, ppseq)
    tables = cache.tables(full, np.arange(b), ring)
    done, out, counts = [0] * b, [dict() for _ in seqs], []
    while any(d < len(s) for d, s in zip(done, seqs)):
        count = [min(chunk, n - d) if d < n else int(d < len(s))
                 for d, n, s in zip(done, prompt_lens, seqs)]
        width = max(count)
        tok = np.zeros((b, width), "int64")
        pos = np.zeros((b, width), "int32")
        pid = np.full((b, width), sink, "int32")
        slot = np.zeros((b, width), "int32")
        for i in range(b):
            p = np.arange(done[i], done[i] + count[i])
            tok[i, :count[i]], pos[i, :count[i]] = seqs[i][p], p
            pid[i, :count[i]], slot[i, :count[i]] = full[i, p // ps], p % ps
        kv = np.asarray([d + n for d, n in zip(done, count)], "int32")
        logits, pools, routed = jstep(params, tok, pos, pools, pid, slot,
                                      kv, np.asarray(count, "int32"), tables)
        counts.append(np.asarray(routed))
        for i in range(b):
            done[i] += count[i]
            if count[i]:
                out[i][done[i] - 1] = np.asarray(logits[i])
    return out, ring, np.stack(counts)


@pytest.mark.parametrize("interpret", [False, True])
def test_step_matches_reference_through_chunks_window_and_ring(
        model, rng, interpret):
    """Prompts of 41 and 23 tokens in chunks of 8, then decode to 70 and
    60: the window of 8 is crossed in the first chunk and a ring of 5
    pages of 4 (20 positions) wraps three times."""
    keep = get_flags(["FLAGS_pallas_interpret"])
    set_flags({"FLAGS_pallas_interpret": interpret})
    try:
        seqs = [rng.randint(0, VOCAB, (70,)), rng.randint(0, VOCAB, (60,))]
        got, ring, counts = _through_the_step(model, seqs, [41, 23], 8, 4)
    finally:
        set_flags(keep)
    assert ring == 5 and 70 > 3 * ring * 4
    for i, s in enumerate(seqs):
        want = _reference(model, s)
        for p, logits in got[i].items():
            err = np.max(np.abs(logits - want[p])) / np.max(np.abs(want[p]))
            assert err < 1e-5, (i, p, err)
    # the routing counts: rows to held experts, the fullest, experts hit
    assert counts.shape[1] == 3 and counts[:, 0].sum() > 0
    assert (counts[:, 1] <= counts[:, 0]).all()
    assert (counts[:, 2] <= 3 * 4).all()          # 3 expert layers x 4 held


@pytest.mark.parametrize("omission,change", [
    ("the sink", "no_sink"),
    ("the value scale", {"v_scale": 1.0}),
    ("the window", {"window": 1 << 20}),
    ("the selection bias", "no_bias")])
def test_leaving_out_a_mechanism_shows_in_the_logits(model, rng, omission,
                                                     change):
    """What the logits check has to catch: the same forward without the
    sink, the value scale, the window or the selection bias moves the
    worst of ten rows, and their median too, past ``LOGITS_TOL`` at this
    size (the program itself reads 4e-7 here)."""
    ids = rng.randint(0, VOCAB, (64,))
    want = _reference(model, ids)
    if isinstance(change, dict):
        got = _reference(model, ids, **change)
    else:
        field = {"no_sink": "sink", "no_bias": "router_b"}[change]
        blocks = [b for b in model.blocks
                  if getattr(b, field, None) is not None]
        kept = [np.asarray(getattr(b, field)._data) for b in blocks]
        try:
            for b in blocks:
                getattr(b, field).set_value(np.zeros_like(kept[0]) if
                                            field == "router_b" else
                                            np.full_like(kept[0], -1e9))
            got = _reference(model, ids)
        finally:
            for b, v in zip(blocks, kept):
                getattr(b, field).set_value(v)
    rows = np.max(np.abs(got[-10:] - want[-10:]), axis=-1) \
        / np.max(np.abs(want[-10:]))
    assert np.median(rows) > ref.LOGITS_TOL, (omission, rows)


def _greedy_by_reference(model, prompt, n_new, pad_to=64):
    """Greedy decoding by the plain reference's full forward.  The stack
    is causal, so a sequence padded to one length reads the same logits
    at its own positions and every step shares one compiled shape."""
    seq = list(prompt)
    for _ in range(n_new):
        padded = np.zeros((pad_to,), "int64")
        padded[:len(seq)] = seq
        seq.append(int(np.argmax(_reference(model, padded)[len(seq) - 1])))
    return seq[len(prompt):]


def test_engine_serves_it_token_exact_and_records_the_new_fields(
        model, rng, tmp_path):
    from paddle_tpu.observability import events as obs_events
    prompts = [rng.randint(0, VOCAB, (n,)).tolist() for n in (30, 17, 45, 9)]
    set_flags({"FLAGS_observability_dir": str(tmp_path)})
    try:
        engine = ServingEngine(model, max_batch=3, page_size=4,
                               max_prefill_chunk=8, prefix_caching=False)
        with engine:
            reqs = [engine.submit(p, max_new_tokens=10) for p in prompts]
            got = [r.wait(timeout=300) for r in reqs]
    finally:
        set_flags({"FLAGS_observability_dir": ""})
    assert got == [_greedy_by_reference(model, p, 10) for p in prompts]
    steps = [e for e in obs_events.read_events(str(tmp_path))
             if e["kind"] == "batch_step"]
    new = ("expert_rows", "expert_rows_max", "experts_hit",
           "window_pages_read", "full_pages_read")
    assert all(all(k in e for k in new) for e in steps)
    assert sum(e["expert_rows"] for e in steps) > 0
    # a decode-only step of three lanes at contexts past the window: each
    # window layer visits at most 3 pages of 4 a lane, each full layer all
    decode = [e for e in steps if e["prefill_seqs"] == 0 and e["batch"] == 3]
    assert decode and all(e["window_pages_read"] <= 3 * 3 * 2
                          for e in decode)
    assert any(e["full_pages_read"] > e["window_pages_read"]
               for e in decode)


def test_eviction_and_resume_stay_token_exact_with_a_window_model(model,
                                                                  rng):
    prompts = [rng.randint(0, VOCAB, (14,)).tolist() for _ in range(3)]
    want = [_greedy_by_reference(model, p, 12) for p in prompts]
    engine = ServingEngine(model, max_batch=3, page_size=4, num_pages=17,
                           max_pages_per_seq=8, max_prefill_chunk=8,
                           prefix_caching=False)
    with engine:
        reqs = [engine.submit(p, max_new_tokens=12) for p in prompts]
        got = [r.wait(timeout=300) for r in reqs]
    assert engine.scheduler.evictions >= 1
    assert got == want
    assert engine.pool.available() == engine.pool.num_pages - 1
    assert sorted(engine.scheduler._free_slots) == [0, 1, 2]


def test_prefix_caching_with_a_window_model_raises_with_the_reason(model):
    with pytest.raises(ValueError, match="window attention layers.*"
                                         "prefix_caching=False"):
        ServingEngine(model, prefix_caching=True)


@pytest.mark.parametrize("layers, reason", [
    (dict(hybrid_layer_pattern=[0, 1], moe_layer_freq=[0, 0]),
     r"step\.cache\.window = 8.*step\.routing_counts = False"),
    (dict(hybrid_layer_pattern=[0, 0], moe_layer_freq=[0, 1]),
     r"step\.cache\.window = None.*step\.routing_counts = True"),
])
def test_fused_window_refuses_by_what_it_cannot_carry(layers, reason):
    """A ring alone and routing counts alone each refuse; a model with
    neither is taken, whatever its class (the GPT and LLaMA ``fused_*``
    tests of test_serving.py)."""
    paddle.seed(11)
    m = MiMoV2ForCausalLM(_config(**layers))
    with pytest.raises(TypeError, match=reason):
        build_fused_window_step(m, 4)


def test_fused_window_refuses_a_described_model_by_name(model):
    with pytest.raises(TypeError, match="MiMoV2ForCausalLM.*a window layer"
                                        ".*routing counts"):
        build_fused_window_step(model, 4)
    # and the engine says so to its caller, not inside its loop
    keep = get_flags(["FLAGS_serving_fused_steps"])
    set_flags({"FLAGS_serving_fused_steps": 4})
    try:
        with pytest.raises(ValueError, match="FLAGS_serving_fused_steps=4 "
                                             "with MiMoV2ForCausalLM"):
            ServingEngine(model, prefix_caching=False)
    finally:
        set_flags(keep)


def _window_pool_bytes(max_pos):
    paddle.seed(3)
    m = MiMoV2ForCausalLM(_config(max_position_embeddings=max_pos,
                                  hybrid_layer_pattern=[0, 1],
                                  moe_layer_freq=[0, 1]))
    e = ServingEngine(m, max_batch=2, page_size=16, max_prefill_chunk=64,
                      prefix_caching=False)
    size = lambda pair: sum(a.size * a.dtype.itemsize for a in pair)
    return size(e._pools[1]), size(e._pools[0])


def test_window_layers_pool_bytes_do_not_depend_on_the_positions():
    window_8k, full_8k = _window_pool_bytes(8192)
    window_64k, full_64k = _window_pool_bytes(65536)
    assert window_8k == window_64k
    assert full_64k > 7 * full_8k
    # a ring of ceil((8 + 64) / 16) + 1 = 6 pages a lane, and the sink
    assert window_8k == 2 * (2 * 6 + 1) * 16 * (24 + 16) * 4


def test_a_key_wider_than_a_lane_tile_gets_a_pool_of_whole_tiles(rng):
    """Keys of 136 live in pools 256 wide (``generation._pool_width``),
    and the padding changes no logit."""
    paddle.seed(5)
    m = MiMoV2ForCausalLM(_config(head_dim=136, hybrid_layer_pattern=[0, 1],
                                  moe_layer_freq=[0, 1]))
    m.eval()
    _, step = m.build_ragged_decode_step()
    assert [layer[1:3] for layer in step.cache.layers] == [(256, 16)] * 2
    seq = rng.randint(0, VOCAB, (30,))
    got, _, _ = _through_the_step(m, [seq], [21], 8, 4)
    want = _reference(m, seq)
    for p, logits in got[0].items():
        assert np.max(np.abs(logits - want[p])) \
            / np.max(np.abs(want[p])) < 1e-5


def test_gpt_and_llama_pools_from_the_description_are_todays_shapes():
    paddle.seed(0)
    gpt = GPTForPretraining(GPTConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0))
    llama = LlamaForCausalLM(llama_config("tiny"))
    for m, nkv, hd, layers in ((gpt, 4, 8, 2), (llama, 2, 16, 2)):
        _, step = m.build_ragged_decode_step()
        cache = step.cache
        assert isinstance(cache, CacheDescription)
        assert cache.ring_pages(16, 0) == 0
        want = ((nkv, 33, 16, hd),) * 2
        assert cache.pool_shapes(33, 16, 8) == (want,) * layers
        e = ServingEngine(m, max_batch=8, page_size=16, num_pages=33)
        assert [tuple(a.shape for a in pair) for pair in e._pools] \
            == [want] * layers
        assert all(a.dtype == jnp.float32 for pair in e._pools
                   for a in pair)
        assert e.scheduler.ring_pages == 0
        # no ring: the tables are the full-layer tables themselves
        t = np.arange(6, dtype="int32").reshape(2, 3)
        assert cache.tables(t, [0, 1], 0) is not None
        np.testing.assert_array_equal(cache.tables(t, [0, 1], 0), t)
