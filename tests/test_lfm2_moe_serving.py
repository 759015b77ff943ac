"""A model whose conv layers keep only a convolution's tail a sequence
(LFM2-MoE: gated short convolutions, one GQA layer with per-head q/k
norms among them, a leading dense layer, then sigmoid-routed experts)
through the ragged step, the scheduler's slots and the engine, against
``benchmark/reference/lfm2_moe.py`` on seeded weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.generation import (AttentionKind, LaneState,
                                          LayerDescription, ShortConvKind,
                                          build_fused_window_step)
from paddle_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeForCausalLM
from paddle_tpu.ops.routed_experts import (held_experts_swiglu,
                                           sigmoid_topk_route)
from paddle_tpu.serving import ServingEngine

from benchmark.reference import lfm2_moe as ref
from test_solar_open2_serving import _Step, _greedy_by_hand, _worst

VOCAB = 256
TYPES = ["conv", "full_attention", "conv", "conv", "conv"]


def _config(**over):
    # the cell's layers at a small width: a leading dense conv layer,
    # then one period [attention, conv, conv, conv] of expert layers
    kw = dict(vocab_size=VOCAB, hidden_size=64, intermediate_size=96,
              num_hidden_layers=5, layer_types=TYPES, num_dense_layers=1,
              num_attention_heads=4, num_key_value_heads=2,
              moe_intermediate_size=32, num_experts=8,
              num_experts_per_tok=2, max_position_embeddings=256)
    kw.update(over)
    return Lfm2MoeConfig(**kw)


def _reseed(m, seed):
    """Fresh weights into the same model (one traced step serves every
    seed): the model's initialisers by hand, then the builder's draws
    away from their initial values."""
    rs = np.random.RandomState(seed)
    h = m.config.hidden_size
    for name, p in m.named_parameters():
        leaf = name.split(".")[-1]
        if leaf in ("ln1", "ln2", "norm"):
            continue
        if leaf in ("q_norm", "k_norm"):
            p.set_value(rs.uniform(0.5, 1.5, p.shape).astype("float32"))
            continue
        std = {"router_w": h ** -0.5, "conv_w": 3 ** -0.5,
               "router_b": 0.0}.get(leaf, 0.02)
        p.set_value(rs.normal(0.0, std, p.shape).astype("float32"))
    for blk in m.blocks:
        if hasattr(blk, "router_b"):
            blk.router_b.set_value(rs.uniform(-.2, .2, blk.router_b.shape)
                                   .astype("float32"))


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    m = Lfm2MoeForCausalLM(_config())
    _reseed(m, 2)
    m.eval()
    return m


def _reference_args(c):
    return dict(layer_types=tuple(c.layer_types),
                n_dense=c.num_dense_layers, heads=c.num_attention_heads,
                kv=c.num_key_value_heads, eps=c.norm_eps,
                rope_theta=c.rope_theta, top_k=c.num_experts_per_tok,
                first_held=c.held_experts[0],
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


def test_the_description_and_the_caches(model):
    """Four conv layers keep a tail of two rows a lane and nothing else,
    the attention layer's heads of 16 a pool pair, each a whole lane
    tile wide; every attention layer norms q and k, the leading layer is
    dense."""
    _, step = model.build_ragged_decode_step()
    cache = step.cache
    assert cache.n_state == 4 and cache.n_full == 1
    assert cache.layers[0] == LaneState(((2, 64),))
    assert cache.layers[1] == (2, 128, 128, None)
    assert [tuple(a.shape for a in p) for p in
            cache.new_pools(9, 4, "float32", 3)[2:]] == [((3, 2, 64),)] * 3
    md = model.config.description()
    assert [d.short_conv is not None for d in md.layers] == \
        [t == "conv" for t in TYPES]
    assert md.layers[1].attention.qk_norm
    assert md.layers[1].attention.rotary_dim == 16
    assert [d.feed_forward.held for d in md.layers] == [None] + [(0, 8)] * 4
    assert md.precision == "highest"
    with pytest.raises(ValueError, match="one of the four"):
        LayerDescription(None, md.layers[0].feed_forward)


def test_step_matches_reference_through_chunks_inside_the_conv_reach(
        model, rng):
    """One prompt of 60 tokens prefilled whole beside one of 45 fed in
    chunks of 1, 2, 1, 2, 3, 30 and 6 rows — boundaries that fall inside
    the convolution's reach of two rows, a chunk shorter than the tail
    among them — then 16 decode steps each; slots are not lanes."""
    seqs = [rng.randint(0, VOCAB, (76,)), rng.randint(0, VOCAB, (61,))]
    step = _Step(model, seqs, slots=[1, 0])
    plan = [[60, 1], [0, 2], [0, 1], [0, 2], [0, 3], [0, 30], [0, 6]]
    for counts in plan:
        step.feed(counts)
    for _ in range(16):
        step.feed([1, 1])
    for i, s in enumerate(seqs):
        got = step.logits[i]
        assert len(got) == (17, 23)[i]       # every chunk's last row too
        assert _worst(got, _reference(model, s)) < 1e-5
    # a lane that a second request reuses starts from a zero tail:
    # nothing is cleared by hand between the two runs
    again = _Step(model, seqs[::-1], slots=[1, 0], jitted=step.step)
    again.pools = step.pools
    got = again.run([45, 60], 16)
    assert _worst(got[0], _reference(model, seqs[1])) < 1e-5
    assert _worst(got[1], _reference(model, seqs[0])) < 1e-5


def test_a_conv_layer_reads_only_what_its_sequence_fed(model, rng):
    """The tail of a slot whose sequence has no row in a step does not
    move, and the tail a chunk leaves is its last two inputs ``z``."""
    seq = rng.randint(0, VOCAB, (12,))
    step = _Step(model, [seq, seq], slots=[0, 1])
    step.feed([5, 0])
    tails = [np.asarray(p[0]) for p in step.pools if len(p) == 1]
    assert all(np.any(t[0] != 0) and np.all(t[1] == 0) for t in tails)
    step.feed([0, 5])
    again = [np.asarray(p[0]) for p in step.pools if len(p) == 1]
    for before, after in zip(tails, again):
        np.testing.assert_array_equal(after[0], before[0])
        np.testing.assert_allclose(after[1], before[0], rtol=1e-6)


def test_the_shares_of_an_expert_layer_add_up_to_the_whole(rng):
    """Section 4 of the model-configs guide: the routed parts that two
    chips holding experts (0, 4) and (4, 4) compute (the program's
    ``held_experts_swiglu``) add up to what the reference gives for the
    whole layer, and each is what the reference gives for its half."""
    paddle.seed(3)
    whole = Lfm2MoeForCausalLM(_config(num_hidden_layers=2,
                                       layer_types=["conv", "conv"]))
    _reseed(whole, 4)
    lp = whole.described_params()["layers"][1]
    h = jnp.asarray(rng.standard_normal((24, 64)).astype("float32"))
    want, _ = ref.expert_layer(lp, h, top_k=2, first_held=0,
                               routed_scale=1.0)
    picks, weights = sigmoid_topk_route(h, lp["router_w"], lp["router_b"], 2)
    total, rows = jnp.zeros_like(h), 0
    for first in (0, 4):
        held = slice(first, first + 4)
        y, n = held_experts_swiglu(h, picks, weights, jnp.ones((24,), bool),
                                   lp["wg"][held], lp["wu"][held],
                                   lp["wd"][held], first)
        half = dict(lp, wg=lp["wg"][held], wu=lp["wu"][held],
                    wd=lp["wd"][held])
        mine, _ = ref.expert_layer(half, h, top_k=2, first_held=first,
                                   routed_scale=1.0)
        np.testing.assert_allclose(np.asarray(y), np.asarray(mine),
                                   rtol=1e-4, atol=1e-6)
        total, rows = total + y, rows + int(n.sum())
    assert rows == 24 * 2                    # every pick lives on one chip
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-6)


def test_the_q_k_norm_changes_nothing_for_a_family_that_does_not_set_it(
        rng):
    """Every other family's attention leaves ``qk_norm`` off, and a
    Solar Open 2 step handed norm weights it does not describe ignores
    them."""
    from benchmark import harness
    for cell in ("mistral-7b-8l.batch", "mimo-v2.5-7l-ep32.longgen",
                 "solar-open2-8l-ep32.longdoc", "gpt3-760m.chat"):
        cfg = harness.load_cell(cell, rehearse=True)["config"]
        md = harness.builder_for(cfg).build(
            cfg, 1, training=False).config.description()
        assert not any(d.attention.qk_norm for d in md.layers
                       if d.attention is not None), cell
    assert AttentionKind(window=None, kv_heads=1, key_dim=8,
                         value_dim=8).qk_norm is False
    from test_solar_open2_serving import _config as solar_config
    from paddle_tpu.models.solar_open2 import SolarOpen2ForCausalLM
    paddle.seed(5)
    solar = SolarOpen2ForCausalLM(solar_config(num_hidden_layers=1))
    seq = rng.randint(0, VOCAB, (12,))
    plain = _Step(solar, [seq])
    plain.feed([12])
    noisy = _Step(solar, [seq], jitted=plain.step)
    for lp in noisy.params["layers"]:
        lp["q_norm_w"] = lp["k_norm_w"] = jnp.full((16,), 3.0)
    noisy.feed([12])
    np.testing.assert_array_equal(noisy.logits[0][11], plain.logits[0][11])


def test_engine_serves_mixed_lengths_as_the_step_fed_by_hand(
        model, rng, tmp_path):
    """Five requests of unlike length over three lanes, chunks of 16,
    one step ahead: the engine's tokens are those of the step fed by
    hand a token at a time in decode; the records count the lanes whose
    tails the step read and wrote and one reset a request, and no row
    of a chunked form (a conv layer has none)."""
    from paddle_tpu.flags import set_flags
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
    steps = [e for e in obs_events.read_events(str(tmp_path))
             if e["kind"] == "batch_step"]
    for field in ("state_lanes", "state_resets", "scan_rows"):
        assert sum(e[field] for e in steps) == stats[field]
    assert stats["state_resets"] == 5        # one a request
    assert all(e["state_lanes"] == e["batch"] for e in steps)
    assert stats["state_lanes"] > 0 and stats["scan_rows"] == 0
    assert sorted(engine.scheduler._free_slots) == [0, 1, 2]


def test_engine_at_heads_of_64_serves_the_reference_greedy_tokens(rng):
    """The cell's attention geometry — 8 kv heads of 64 — at a small
    hidden width: the engine's pools are ``[8, P, ps, 128]`` (heads of 64
    padded to a whole lane tile, ``generation._pool_width``), and a
    prompt fed in chunks of 16 that cross a page and a chunk boundary
    decodes the plain reference's greedy tokens."""
    paddle.seed(13)
    m = Lfm2MoeForCausalLM(_config(hidden_size=512, num_attention_heads=8,
                                   num_key_value_heads=8))
    _reseed(m, 6)
    m.eval()
    engine = ServingEngine(m, max_batch=8, page_size=16, num_pages=33,
                           max_prefill_chunk=16, prefix_caching=False)
    assert [tuple(a.shape) for a in engine._pools[1]] == [(8, 33, 16, 128)] * 2
    prompt = rng.randint(0, VOCAB, (37,)).tolist()
    with engine:
        got = engine.submit(prompt, max_new_tokens=6).wait(timeout=300)
    # greedy from the reference: each new token is the argmax of the
    # reference's logits over the prompt and the tokens before it
    want = _reference(m, np.asarray(prompt + got[:-1]))
    assert got == [int(np.argmax(want[len(prompt) - 1 + i]))
                   for i in range(6)]


def test_prefix_cache_and_fused_window_refuse_a_model_that_keeps_tails(
        model):
    with pytest.raises(ValueError, match=r"0 window attention layers, 4 "
                                         r"state layers.*prefix_caching="
                                         r"False"):
        ServingEngine(model, prefix_caching=True)
    with pytest.raises(TypeError, match=r"Lfm2MoeForCausalLM.*step\.cache"
                                        r"\.n_state = 4"):
        build_fused_window_step(model, 4)


def test_the_config_refuses_what_the_step_does_not_compute():
    for over, key in ((dict(conv_bias=True), "conv_bias"),
                      (dict(norm_topk_prob=False), "norm_topk_prob"),
                      (dict(layer_types=["conv"] * 4), "layer_types"),
                      (dict(rope_parameters={"rope_theta": 1e6,
                                             "rope_type": "yarn"}),
                       "rope_parameters")):
        with pytest.raises(ValueError, match=key):
            _config(**over)
    with pytest.raises(ValueError, match="held_experts"):
        _config(held_experts=(6, 4))
    assert ShortConvKind(channels=64, kernel=3) == \
        _config().layer_descriptions()[0].short_conv
