"""The ragged step has ONE body (``generation.build_ragged_decode_step``)
that every served family reaches through its description.  Its logits
are held here to the model's own eager forward (``model(input_ids)``,
which shares no code with ``models/generation.py``) over the facts a
description carries and no token-exact test can see: at test sizes a
dropped bias, embedding scale or head flips no greedy token."""
import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
from paddle_tpu.models.llama import LlamaForCausalLM, llama_config

VOCAB, MAX_POS = 96, 128

CASES = {
    "gpt_tied_head": ("gpt", dict(tie_word_embeddings=True)),
    "gpt_untied_head": ("gpt", dict(tie_word_embeddings=False)),
    "llama_gqa": ("llama", dict(num_heads=4, num_kv_heads=2)),
    "llama_one_kv_head": ("llama", dict(num_heads=4, num_kv_heads=1)),
    "llama_attention_bias": ("llama", dict(attention_bias=True)),
    "llama_embed_scale": ("llama", dict(embed_scale=8.0)),
    "llama_gelu_tanh": ("llama", dict(hidden_act="gelu_tanh")),
    "llama_tied_head": ("llama", dict(tie_word_embeddings=True)),
}


def _model(family: str, overrides):
    paddle.seed(11)
    if family == "gpt":
        m = GPTForPretraining(GPTConfig(
            vocab_size=VOCAB, hidden_size=32, num_layers=2, num_heads=4,
            max_position_embeddings=MAX_POS, hidden_dropout_prob=0.0,
            attention_dropout_prob=0.0, **overrides))
    else:
        m = LlamaForCausalLM(llama_config(
            "tiny", vocab_size=VOCAB, max_position_embeddings=MAX_POS,
            **overrides))
    # biases start at zero and norm weights at one: move every vector,
    # so that one the step left out shows in the logits
    rs = np.random.RandomState(5)
    for p in m.parameters():
        if len(p.shape) == 1:
            p.set_value((np.asarray(p.numpy())
                         + 0.2 * rs.randn(*p.shape)).astype("float32"))
    m.eval()
    return m


def _feed(seqs, start, count, tables, sink, ps, width):
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
        pid[i, :count[i]], slot[i, :count[i]] = tables[i, p // ps], p % ps
    kv = np.asarray([s + n for s, n in zip(start, count)], "int32")
    return tok, pos, pid, slot, kv, np.asarray(count, "int32")


def _packed(lanes, q_lens, fill):
    """``lanes [B, Q]`` -> the rows ``step.packed`` takes: each
    sequence's valid slots one behind the other, then ``fill``."""
    rows = np.concatenate([lanes[i, :n] for i, n in enumerate(q_lens)])
    out = np.full((lanes.size,), fill, lanes.dtype)
    out[:len(rows)] = rows
    return out


@pytest.mark.parametrize("entry", ["lanes", "packed"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_ragged_step_logits_are_the_eager_forwards(case, entry):
    """A prefill in two chunks (the second attends to the first through
    the pools) and three decode steps, two sequences of unlike lengths:
    each step's last-row logits against the eager forward's rows at the
    same positions, within 1e-5 of the largest logit."""
    family, overrides = CASES[case]
    model = _model(family, overrides)
    params, step = model.build_ragged_decode_step()
    assert not step.routing_counts and step.cache.window is None
    ps, decodes = 4, 3
    lens = [37, 22]
    rs = np.random.RandomState(7)
    seqs = [rs.randint(0, VOCAB, (n + decodes,)) for n in lens]
    b = len(seqs)
    ppseq = -(-(max(lens) + decodes) // ps)
    sink = b * ppseq
    tables = np.arange(b * ppseq, dtype="int32").reshape(b, ppseq)
    pools = step.cache.new_pools(sink + 1, ps, "float32", b)
    lanes = jax.jit(step)
    packed = jax.jit(step.packed, static_argnames=("q_width",))

    def run(width, start, count):
        nonlocal pools
        tok, pos, pid, slot, kv, ql = _feed(seqs, start, count, tables,
                                            sink, ps, width)
        if entry == "lanes":
            logits, pools = lanes(params, tok, pos, pools, pid, slot, kv,
                                  ql, tables)
        else:
            logits, pools = packed(
                params, _packed(tok, count, 0), _packed(pos, count, 0),
                pools, _packed(pid, count, sink), _packed(slot, count, 0),
                kv, ql, tables, q_width=width)
        return np.asarray(logits, np.float32)

    first = [16, 16]
    run(32, [0, 0], first)
    got = [run(32, first, [n - f for n, f in zip(lens, first)])]
    for t in range(decodes):
        got.append(run(1, [n + t for n in lens], [1] * b))
    for i, n in enumerate(lens):
        want = np.asarray(
            model(Tensor(np.asarray(seqs[i])[None])).numpy(),
            np.float32)[0, n - 1:n + decodes]
        mine = np.stack([g[i] for g in got])
        err = float(np.max(np.abs(mine - want)) / np.max(np.abs(want)))
        assert err <= 1e-5, (case, entry, i, err)


@pytest.mark.parametrize("dim, width", [(16, 128), (64, 128), (96, 128),
                                        (128, 128), (136, 256), (192, 256),
                                        (576, 640)])
def test_a_pool_is_a_whole_number_of_lane_tiles_wide(dim, width):
    """Every head, narrower or wider than the 128-lane tile, gets a page
    pool a whole number of tiles wide: the layout the ragged kernel's
    Mosaic call takes, so the step neither re-lays nor pads a pool."""
    from paddle_tpu.models.generation import _pool_width
    assert _pool_width(dim) == width
