"""Builds the program's Solar Open 2 stack
(``paddle_tpu.models.solar_open2.SolarOpen2ForCausalLM``) from a
configuration file's sizes, as the share one chip of an expert-parallel
deployment holds, and hands its weights to
``benchmark/reference/solar_open2.py``.

The file's keys are the published ``config.json``'s.  Two are read as
the chip's share of the published value and two more are this
builder's own:

* ``n_routed_experts``: the experts HELD here (the file lists it under
  ``reduced``); ``n_router_outputs`` is the router's published width,
  over which every row is routed, and ``first_held_expert`` the id of
  the first held one;
* ``vocab_size``: the slice of the vocabulary this chip embeds and
  scores.

Seeded weights: the model's own initialiser (normal, 0.02) from
``--seed``; then the routers' selection bias is drawn away from zero
(uniform in [-0.1, 0.1]), and each linear-attention layer's ``A_log``
from log U(1, 16) and ``dt_bias`` so that the decay a step spreads over
(0.5, 0.999) (``SolarOpen2ForCausalLM.seed_decays``): a program that
ignored the bias, or ran a recurrence whose decay is always 1 or
always 0, would otherwise pass the logits check.
"""
from __future__ import annotations

from typing import Any, Dict

# the published config.json's keys, at the top level of the file, and
# the two of this builder's own
MODEL_KEYS = {
    "model_type", "partial_rotary_factor", "linear_attn_config",
    "hidden_size", "num_hidden_layers", "num_attention_heads", "head_dim",
    "num_key_value_heads", "vocab_size", "intermediate_size",
    "moe_intermediate_size", "rms_norm_eps", "rope_theta",
    "tie_word_embeddings", "max_position_embeddings",
    "first_k_dense_replace", "use_rope", "gqa_interval", "gqa_layers",
    "use_gqa_gate", "kda_use_full_proj", "kda_allow_neg_eigval",
    "n_routed_experts", "n_shared_experts", "norm_topk_prob",
    "routed_scaling_factor", "num_experts_per_tok", "torch_dtype",
    "n_router_outputs", "first_held_expert"}

# what the program's stack computes; a file that says otherwise is
# refused rather than served as something else
_FIXED = {"model_type": "solar_open2", "use_rope": False,
          "kda_use_full_proj": False, "first_k_dense_replace": 0,
          "norm_topk_prob": True, "tie_word_embeddings": False}


def _model_config(cfg: Dict[str, Any]):
    from paddle_tpu.models.solar_open2 import SolarOpen2Config
    wrong = {k: cfg[k] for k, v in _FIXED.items() if cfg[k] != v}
    lin = cfg["linear_attn_config"]
    if lin["num_kv_heads"] not in (None, lin["num_heads"]):
        wrong["linear_attn_config.num_kv_heads"] = lin["num_kv_heads"]
    if any(not 0 <= i < cfg["num_hidden_layers"]
           for i in cfg["gqa_layers"]):
        wrong["gqa_layers"] = cfg["gqa_layers"]
    if wrong:
        raise ValueError(f"the program's Solar Open 2 stack does not "
                         f"compute {wrong}")
    return SolarOpen2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        gqa_layers=list(cfg["gqa_layers"]),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        use_gqa_gate=cfg["use_gqa_gate"],
        linear_num_heads=lin["num_heads"], linear_head_dim=lin["head_dim"],
        short_conv_kernel_size=lin["short_conv_kernel_size"],
        # the low-rank pairs are as wide as a head (the file's `assumed`)
        linear_low_rank=lin["head_dim"],
        kda_allow_neg_eigval=cfg["kda_allow_neg_eigval"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["n_router_outputs"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        held_experts=(cfg["first_held_expert"], cfg["n_routed_experts"]),
        rms_eps=cfg["rms_norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"])


def build(cfg: Dict[str, Any], seed: int, training: bool):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models.solar_open2 import SolarOpen2ForCausalLM
    from benchmark import generator
    if training:
        raise ValueError("the Solar Open 2 stack is built for serving only")
    paddle.seed(int(seed) % (1 << 31))
    model = SolarOpen2ForCausalLM(_model_config(cfg))
    rs = generator.rng_for(seed, 6)
    for blk in model.blocks:
        blk.router_b.set_value(rs.uniform(-0.1, 0.1, blk.router_b.shape)
                               .astype(np.float32))
    model.seed_decays(rs)
    model.eval()
    return model


def weights(model):
    return model.described_params()


def _reference_args(cfg: Dict[str, Any]) -> Dict[str, Any]:
    mc = _model_config(cfg)
    return dict(gqa_layers=tuple(mc.gqa_layers), heads=mc.num_heads,
                kv=mc.num_kv_heads, d=mc.head_dim, eps=mc.rms_eps,
                top_k=mc.num_experts_per_tok,
                first_held=mc.held_experts[0],
                routed_scale=mc.routed_scaling_factor)


def reference_logits(w, ids, cfg: Dict[str, Any], dtype=None, omit=()):
    """The plain reference's logits ``[S, V]``; ``dtype`` computes the
    stack in another precision and ``omit`` leaves mechanisms out (the
    tolerance's readings)."""
    import jax.numpy as jnp
    from benchmark.reference import solar_open2 as ref
    return ref.forward_logits(w, ids, dtype=dtype or jnp.float32,
                              omit=omit, **_reference_args(cfg))


def reference_logits_and_notes(w, ids, cfg: Dict[str, Any]):
    """The reference's logits and, from the same forward pass, each
    row's selection margin in each expert layer (``[layers, S]``), for
    :func:`reference_report`."""
    from benchmark.reference import solar_open2 as ref
    return ref.forward_logits(w, ids, with_margins=True,
                              **_reference_args(cfg))


def reference_report(margins, rows) -> str:
    """Said beside the logits' error: the selections among the checked
    ``rows`` that a rounding of the router's input could flip."""
    import numpy as np
    from benchmark.reference import solar_open2 as ref
    m = np.asarray(margins)[:, list(rows)]
    return (f"{int((m < ref.NEAR_TIE).sum())} of {m.size} checked "
            f"(expert layer, row) selections are near-ties (8th and 9th "
            f"score closer than {ref.NEAR_TIE:g}; smallest gap "
            f"{float(m.min()):.1e})")


def tolerances() -> Dict[str, float]:
    from benchmark.reference import solar_open2 as ref
    return {"logits": ref.LOGITS_TOL}
