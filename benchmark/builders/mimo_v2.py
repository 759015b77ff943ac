"""Builds the program's MiMo-V2 stack
(``paddle_tpu.models.mimo_v2.MiMoV2ForCausalLM``) from a configuration
file's sizes, as the share one chip of an expert-parallel deployment
holds, and hands its weights to ``benchmark/reference/mimo_v2.py``.

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
``--seed``; then the window layers' sinks and the routers' selection
bias, which a fresh model holds at zero, are drawn away from zero
(sinks uniform in [2, 5], bias uniform in [-0.1, 0.1]: enough to move
most rows' picks, too little to pile the rows on a few experts) so that a
program that ignored either would fail the logits check.
"""
from __future__ import annotations

from typing import Any, Dict

# the published config.json's keys, at the top level of the file, and
# the two of this builder's own
MODEL_KEYS = {
    "attention_bias", "attention_chunk_size", "attention_value_scale",
    "attention_projection_layout", "add_full_attention_sink_bias",
    "add_swa_attention_sink_bias", "swa_num_key_value_heads",
    "swa_num_attention_heads", "swa_head_dim", "swa_v_head_dim",
    "head_dim", "hidden_act", "hidden_size", "hybrid_block_size",
    "hybrid_layer_pattern", "intermediate_size", "layernorm_epsilon",
    "max_position_embeddings", "model_type", "moe_intermediate_size",
    "moe_layer_freq", "n_group", "n_routed_experts", "n_shared_experts",
    "norm_topk_prob", "num_attention_heads", "num_experts_per_tok",
    "num_hidden_layers", "num_key_value_heads", "partial_rotary_factor",
    "rope_scaling", "rope_theta", "routed_scaling_factor", "scoring_func",
    "sliding_window", "sliding_window_size", "swa_rope_theta",
    "tie_word_embeddings", "topk_group", "topk_method", "v_head_dim",
    "vocab_size", "torch_dtype",
    "n_router_outputs", "first_held_expert"}

# what the program's stack computes; a file that says otherwise is
# refused rather than served as something else
_FIXED = {"attention_bias": False, "hidden_act": "silu", "n_group": 1,
          "topk_group": 1, "scoring_func": "sigmoid",
          "topk_method": "noaux_tc", "norm_topk_prob": True,
          "routed_scaling_factor": None, "n_shared_experts": None,
          "attention_projection_layout": "fused_qkv",
          "tie_word_embeddings": False}


def _model_config(cfg: Dict[str, Any]):
    from paddle_tpu.models.mimo_v2 import MiMoV2Config
    wrong = {k: cfg[k] for k, v in _FIXED.items() if cfg[k] != v}
    same = [("swa_head_dim", "head_dim"), ("swa_v_head_dim", "v_head_dim"),
            ("swa_num_attention_heads", "num_attention_heads"),
            ("sliding_window_size", "sliding_window"),
            # a chunk of the window's own size adds nothing to the mask
            # (the configuration's `assumed`)
            ("attention_chunk_size", "sliding_window")]
    wrong.update({a: cfg[a] for a, b in same if cfg[a] != cfg[b]})
    if len(cfg["hybrid_layer_pattern"]) != cfg["num_hidden_layers"] \
            or len(cfg["moe_layer_freq"]) != cfg["num_hidden_layers"]:
        wrong["num_hidden_layers"] = cfg["num_hidden_layers"]
    if wrong:
        raise ValueError(f"the program's MiMo-V2 stack does not compute "
                         f"{wrong}")
    return MiMoV2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        swa_num_kv_heads=cfg["swa_num_key_value_heads"],
        head_dim=cfg["head_dim"], v_head_dim=cfg["v_head_dim"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        rope_theta=float(cfg["rope_theta"]),
        swa_rope_theta=float(cfg["swa_rope_theta"]),
        sliding_window=cfg["sliding_window"],
        hybrid_layer_pattern=list(cfg["hybrid_layer_pattern"]),
        moe_layer_freq=list(cfg["moe_layer_freq"]),
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["n_router_outputs"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        held_experts=(cfg["first_held_expert"], cfg["n_routed_experts"]),
        attention_value_scale=cfg["attention_value_scale"],
        add_swa_attention_sink_bias=cfg["add_swa_attention_sink_bias"],
        add_full_attention_sink_bias=cfg["add_full_attention_sink_bias"],
        rms_eps=cfg["layernorm_epsilon"],
        max_position_embeddings=cfg["max_position_embeddings"])


def build(cfg: Dict[str, Any], seed: int, training: bool):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models.mimo_v2 import MiMoV2ForCausalLM
    from benchmark import generator
    if training:
        raise ValueError("the MiMo-V2 stack is built for serving only")
    paddle.seed(int(seed) % (1 << 31))
    model = MiMoV2ForCausalLM(_model_config(cfg))
    rs = generator.rng_for(seed, 6)
    for blk in model.blocks:
        if blk.sink is not None:
            blk.sink.set_value(rs.uniform(2.0, 5.0, blk.sink.shape)
                               .astype(np.float32))
        if hasattr(blk, "router_b"):
            blk.router_b.set_value(
                rs.uniform(-0.1, 0.1, blk.router_b.shape)
                .astype(np.float32))
    model.eval()
    return model


def weights(model):
    p = model.described_params()
    return {"embed": p["embed"], "norm_w": p["norm_w"], "lm_w": p["lm_w"],
            "layers": p["layers"]}


def _reference_args(cfg: Dict[str, Any]) -> Dict[str, Any]:
    mc = _model_config(cfg)
    layers = tuple((d.attention.window, d.attention.kv_heads,
                    d.attention.rope_theta)
                   for d in mc.layer_descriptions())
    return dict(layers_cfg=layers, heads=mc.num_heads, dk=mc.head_dim,
                dv=mc.v_head_dim, rot=mc.rotary_dim,
                window=mc.sliding_window,
                v_scale=mc.attention_value_scale, eps=mc.rms_eps,
                top_k=mc.num_experts_per_tok,
                first_held=mc.held_experts[0])


def reference_logits(w, ids, cfg: Dict[str, Any], dtype=None):
    """The plain reference's logits ``[S, V]``; ``dtype`` computes the
    stack in another precision (the tolerance's second reading)."""
    import jax.numpy as jnp
    from benchmark.reference import mimo_v2 as ref
    return ref.forward_logits(w, ids, dtype=dtype or jnp.float32,
                              **_reference_args(cfg))


def reference_logits_and_notes(w, ids, cfg: Dict[str, Any]):
    """The reference's logits and, from the same forward pass, each
    row's selection margin in each expert layer (``[layers, S]``), for
    :func:`reference_report`."""
    from benchmark.reference import mimo_v2 as ref
    return ref.forward_logits(w, ids, with_margins=True,
                              **_reference_args(cfg))


def reference_report(margins, rows) -> str:
    """Said beside the logits' error: the selections among the checked
    ``rows`` that a rounding of the router's input could flip."""
    import numpy as np
    from benchmark.reference import mimo_v2 as ref
    m = np.asarray(margins)[:, list(rows)]
    return (f"{int((m < ref.NEAR_TIE).sum())} of {m.size} checked "
            f"(expert layer, row) selections are near-ties (8th and 9th "
            f"score closer than {ref.NEAR_TIE:g}; smallest gap "
            f"{float(m.min()):.1e})")


def tolerances() -> Dict[str, float]:
    from benchmark.reference import mimo_v2 as ref
    return {"logits": ref.LOGITS_TOL}
