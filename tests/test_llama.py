"""LLaMA flagship — BASELINE config 4 shape: hybrid tp x pp x dp with
RMSNorm / rotary / SwiGLU / GQA."""
import jax
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.communication.group import _reset_groups
from paddle_tpu.distributed.fleet.base.topology import _clear_hcg
from paddle_tpu.distributed.mesh import reset_mesh
from paddle_tpu.jit import train_step
from paddle_tpu.models import (LlamaForCausalLM, llama_config,
                               llama_pipeline_step)


@pytest.fixture(autouse=True, scope="module")
def _private_xla_cache(tmp_path_factory):
    """De-flake: the hybrid tp x dp step SIGSEGVs/SIGABRTs ~60% of runs
    when its executable loads WARM from the shared persistent XLA cache
    (<checkout>/.jax_cache) — a pre-existing jax-0.4.37 CPU-executable
    deserialization fragility; cold-cache runs are stable.  Point this
    module at a fresh per-run cache dir so its compiles are always cold
    (a few extra seconds) and restore the shared cache afterwards."""
    import jax
    from jax.experimental.compilation_cache import (compilation_cache as
                                                    _cc)
    prev = jax.config.jax_compilation_cache_dir
    _cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("llama_xla_cache")))
    yield
    _cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir", prev)


@pytest.fixture(autouse=True)
def _cleanup():
    reset_mesh(); _reset_groups(); _clear_hcg()
    yield
    reset_mesh(); _reset_groups(); _clear_hcg()


def _data(cfg, b=8, s=16, seed=0):
    rs = np.random.RandomState(seed)
    ids = rs.randint(0, cfg.vocab_size, (b, s)).astype(np.int64)
    labels = rs.randint(0, cfg.vocab_size, (b, s)).astype(np.int64)
    return ids, labels


def test_llama_forward_shapes_and_gqa():
    cfg = llama_config("tiny")          # nh=4, n_kv=2 → GQA active
    assert cfg.num_kv_heads == 2
    m = LlamaForCausalLM(cfg)
    m.eval()
    ids, _ = _data(cfg, b=2)
    out = m(Tensor(ids))
    assert list(out.shape) == [2, 16, cfg.vocab_size]
    # kv projections are genuinely narrower than q (GQA, not MHA)
    assert m.llama.layers[0].self_attn.k_proj.weight.shape[1] == \
        2 * (cfg.hidden_size // cfg.num_heads)


def test_llama_rmsnorm_and_rope_match_reference_math():
    cfg = llama_config("tiny")
    m = LlamaForCausalLM(cfg)
    layer = m.llama.layers[0]
    rs = np.random.RandomState(0)
    x = rs.randn(2, 8, cfg.hidden_size).astype("float32")
    # RMSNorm: x / sqrt(mean(x^2) + eps) * w
    got = layer.input_layernorm(Tensor(x)).numpy()
    w = layer.input_layernorm.weight.numpy()
    want = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + cfg.rms_eps) * w
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    # rotary: the fused op with the layer's own cos/sin cache must match
    # the textbook complex rotation x_i' = x_i*cos - x_{i+1}*sin, ...
    from paddle_tpu.incubate.nn.functional import (
        fused_rotary_position_embedding)
    attn = layer.self_attn
    S = 8
    q = rs.randn(1, S, cfg.num_heads, attn.head_dim).astype("float32")
    cos = np.asarray(attn._cos[:S])
    sin = np.asarray(attn._sin[:S])
    got_q, _, _ = fused_rotary_position_embedding(
        Tensor(q), None, sin=Tensor(sin), cos=Tensor(cos),
        use_neox_rotary_style=False)
    q1, q2 = q[..., 0::2], q[..., 1::2]
    c, s = cos[None, :, None, 0::2], sin[None, :, None, 0::2]
    want_q = np.stack([q1 * c - q2 * s, q1 * s + q2 * c],
                      axis=-1).reshape(q.shape)
    np.testing.assert_allclose(got_q.numpy(), want_q, rtol=1e-5,
                               atol=1e-6)
    # rotation preserves pairwise norms
    np.testing.assert_allclose(
        (got_q.numpy() ** 2).sum(-1), (q ** 2).sum(-1), rtol=1e-4)


def test_llama_causality():
    """Changing a future token must not affect earlier logits."""
    cfg = llama_config("tiny")
    paddle.seed(0)
    m = LlamaForCausalLM(cfg)
    m.eval()
    ids, _ = _data(cfg, b=1)
    out1 = m(Tensor(ids)).numpy()
    ids2 = ids.copy()
    ids2[0, -1] = (ids2[0, -1] + 3) % cfg.vocab_size
    out2 = m(Tensor(ids2)).numpy()
    np.testing.assert_allclose(out1[0, :-1], out2[0, :-1], rtol=1e-4,
                               atol=1e-5)
    assert np.abs(out1[0, -1] - out2[0, -1]).max() > 1e-4


def test_llama_tp_parity():
    """mp=4 sharded forward matches single-device numerics."""
    cfg = llama_config("tiny")
    paddle.seed(7)
    ref = LlamaForCausalLM(cfg)
    ref.eval()
    ids, _ = _data(cfg, b=2)
    want = ref(Tensor(ids)).numpy()

    reset_mesh(); _reset_groups(); _clear_hcg()
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 4,
                               "pp_degree": 1, "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(7)
    tp = LlamaForCausalLM(cfg)
    tp.eval()
    tp = fleet.distributed_model(tp)
    got = tp(Tensor(ids)).numpy()
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)


def test_llama_hybrid_tp_dp_trains():
    """config-4 core: tp x dp hybrid training step through the engine."""
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 4,
                               "pp_degree": 1, "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(1)
    cfg = llama_config("tiny", sequence_parallel=True)
    model = LlamaForCausalLM(cfg)
    model = fleet.distributed_model(model)
    inner = model._layers if hasattr(model, "_layers") else model
    o = opt.AdamW(learning_rate=1e-3, parameters=inner.parameters(),
                  grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
    o = fleet.distributed_optimizer(o)
    step = train_step(inner, inner.loss_fn, o)
    ids, labels = _data(cfg)
    losses = [float(step(ids, labels)) for _ in range(3)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_llama_pipeline_step():
    """config-4 pp leg: llama pipeline ring trains and matches dp-only."""
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 8, "pp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(3)
    cfg = llama_config("tiny", num_layers=4)
    base_model = LlamaForCausalLM(cfg)
    o0 = opt.AdamW(learning_rate=1e-3,
                   parameters=base_model.parameters())
    base_step = train_step(base_model, base_model.loss_fn, o0)
    ids, labels = _data(cfg, b=8, s=16)
    base = [float(base_step(ids, labels)) for _ in range(3)]

    reset_mesh(); _reset_groups(); _clear_hcg()
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "pp_degree": 4}
    fleet.init(is_collective=True, strategy=strategy)
    hcg = fleet.get_hybrid_communicate_group()
    paddle.seed(3)
    model = LlamaForCausalLM(cfg)
    o = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    pstep = llama_pipeline_step(model, o, hcg.mesh, n_micro=4,
                                dp_axes=("dp",))
    pp = [float(pstep(ids, labels)) for _ in range(3)]
    np.testing.assert_allclose(base, pp, rtol=3e-4)
