"""SEP (Ulysses) / CP (ring) wired through user knobs — loss parity.

The reference reaches segment parallelism via
``hybrid_configs={"sep_degree": n}`` (ref: fleet/meta_parallel/
segment_parallel.py + sep axis in fleet/base/topology.py); ring/context
parallelism via cp configs.  These tests assert the TPU-native wiring:
setting the knob routes GPT/LLaMA attention through
ulysses_attention / ring_attention_bhsd inside the jitted step and the
loss trajectory matches the non-sequence-parallel run (the reference's
loss-parity oracle, SURVEY.md §4).
"""
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.communication.group import _reset_groups
from paddle_tpu.distributed.fleet.base.topology import (
    _clear_hcg, get_hybrid_communicate_group)
from paddle_tpu.distributed.fleet.meta_parallel.segment_parallel import (
    active_seq_parallel_axis)
from paddle_tpu.distributed.mesh import reset_mesh
from paddle_tpu.jit import train_step
from paddle_tpu.models import GPTForPretraining, gpt_config
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM


def _fresh():
    reset_mesh()
    _reset_groups()
    _clear_hcg()


@pytest.fixture(autouse=True)
def _cleanup():
    # the sep/cp routes call the flash kernels directly: on the CPU
    # mesh they run in interpret mode, like every other kernel test
    keep = paddle.get_flags(["FLAGS_pallas_interpret"])
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    _fresh()
    yield
    _fresh()
    paddle.set_flags(keep)


def _init_fleet(**degrees):
    s = fleet.DistributedStrategy()
    s.hybrid_configs = degrees
    fleet.init(is_collective=True, strategy=s)
    return s


def _gpt_losses(n_steps=3, seed=7, heads=4, **hybrid):
    _fresh()
    _init_fleet(**hybrid)
    paddle.seed(seed)
    cfg = gpt_config("tiny", hidden_dropout_prob=0.0,
                     attention_dropout_prob=0.0, num_heads=heads)
    model = GPTForPretraining(cfg)
    optimizer = opt.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())
    step = train_step(model, model.loss_fn, optimizer)
    rs = np.random.RandomState(0)
    ids = rs.randint(0, cfg.vocab_size, (8, 32)).astype(np.int64)
    labels = rs.randint(0, cfg.vocab_size, (8, 32)).astype(np.int64)
    return [float(step(ids, labels)) for _ in range(n_steps)]


def test_topology_carries_sep_and_cp():
    _init_fleet(dp_degree=2, sep_degree=2, mp_degree=2)
    hcg = get_hybrid_communicate_group()
    assert hcg.get_sep_parallel_world_size() == 2
    assert hcg.get_context_parallel_world_size() == 1
    assert active_seq_parallel_axis() == ("sep", 2)
    _fresh()
    _init_fleet(dp_degree=2, cp_degree=4)
    hcg = get_hybrid_communicate_group()
    assert hcg.get_context_parallel_world_size() == 4
    assert hcg.get_context_parallel_group() is not None
    assert active_seq_parallel_axis() == ("cp", 4)


def test_gpt_sep_loss_parity():
    """hybrid_configs={"sep_degree": 4} trains the flagship GPT with the
    same loss as the dp-only run (VERDICT r3 next-step 2 'done' bar)."""
    base = _gpt_losses(dp=None, dp_degree=8)
    sep = _gpt_losses(dp_degree=2, sep_degree=4)
    np.testing.assert_allclose(base, sep, rtol=2e-4)
    assert all(np.isfinite(sep))


def test_gpt_sep_with_mp_loss_parity():
    base = _gpt_losses(dp_degree=8, heads=8)
    mix = _gpt_losses(dp_degree=2, sep_degree=2, mp_degree=2, heads=8)
    np.testing.assert_allclose(base, mix, rtol=2e-4)


def test_gpt_cp_loss_parity():
    base = _gpt_losses(dp_degree=8)
    cp = _gpt_losses(dp_degree=2, cp_degree=4)
    np.testing.assert_allclose(base, cp, rtol=2e-4)


def test_gpt_cp_with_mp_loss_parity():
    base = _gpt_losses(dp_degree=8, heads=8)
    mix = _gpt_losses(dp_degree=2, cp_degree=2, mp_degree=2, heads=8)
    np.testing.assert_allclose(base, mix, rtol=2e-4)


def _llama_losses(n_steps=3, seed=11, **hybrid):
    _fresh()
    _init_fleet(**hybrid)
    paddle.seed(seed)
    cfg = LlamaConfig(vocab_size=256, hidden_size=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, intermediate_size=128,
                      max_position_embeddings=64)
    model = LlamaForCausalLM(cfg)
    optimizer = opt.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())
    step = train_step(model, model.loss_fn, optimizer)
    rs = np.random.RandomState(1)
    ids = rs.randint(0, cfg.vocab_size, (8, 32)).astype(np.int64)
    labels = rs.randint(0, cfg.vocab_size, (8, 32)).astype(np.int64)
    return [float(step(ids, labels)) for _ in range(n_steps)]


def test_llama_gqa_sep_loss_parity():
    """GQA model under sep (kv heads broadcast before the route)."""
    base = _llama_losses(dp_degree=8)
    sep = _llama_losses(dp_degree=2, sep_degree=2, mp_degree=2)
    np.testing.assert_allclose(base, sep, rtol=3e-4)


def test_llama_gqa_cp_loss_parity():
    base = _llama_losses(dp_degree=8)
    cp = _llama_losses(dp_degree=2, cp_degree=2, mp_degree=2)
    np.testing.assert_allclose(base, cp, rtol=3e-4)


def test_unsupported_shape_warns_and_falls_back():
    """sep set but heads not divisible → one warning, correct numerics."""
    _init_fleet(dp_degree=2, sep_degree=4)
    paddle.seed(7)
    # heads=6 not divisible by sep=4 → plain-attention fallback
    cfg = gpt_config("tiny", hidden_dropout_prob=0.0,
                     attention_dropout_prob=0.0, hidden_size=48,
                     num_heads=6)
    model = GPTForPretraining(cfg)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        ids = np.zeros((8, 32), dtype=np.int64)
        model(paddle.to_tensor(ids))
    assert any("sep" in str(r.message) and "heads" in str(r.message)
               for r in rec)
