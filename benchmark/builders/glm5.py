"""Builds the program's GLM-5 stack
(``paddle_tpu.models.glm5.Glm5ForCausalLM``) from a configuration
file's sizes, as the share one chip of an expert-parallel deployment
holds, and hands its weights to ``benchmark/reference/glm5.py``.

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
(uniform in [-0.1, 0.1]) and every layer's index off its initial state
(``Glm5ForCausalLM.seed_index``: head weights that differ, a key norm
with a weight and a bias): a program that ignored the bias, ran no
index, or picked the most recent ``index_topk`` keys, would otherwise
pass the logits check.

The reference takes a head's two read-outs of the latent as ONE matrix
``wkv_b [kv_lora_rank, heads (nope + value)]``, as a checkpoint holds
them (``kv_b_proj``), and expands every latent through it; the model
keeps them apart (``w_uk``, ``w_uv``) and never forms a head's keys.
"""
from __future__ import annotations

from typing import Any, Dict

# the published config.json's keys, at the top level of the file, and
# the two of this builder's own
MODEL_KEYS = {
    "model_type", "attention_bias", "ep_size", "first_k_dense_replace",
    "hidden_act", "head_dim", "hidden_size", "index_head_dim",
    "index_n_heads", "index_topk", "indexer_rope_interleave",
    "intermediate_size", "kv_lora_rank", "max_position_embeddings",
    "moe_intermediate_size", "moe_layer_freq", "n_group",
    "n_routed_experts", "n_shared_experts", "norm_topk_prob",
    "num_attention_heads", "num_experts_per_tok", "num_hidden_layers",
    "num_key_value_heads", "num_nextn_predict_layers", "q_lora_rank",
    "qk_head_dim", "qk_nope_head_dim", "qk_rope_head_dim", "rms_norm_eps",
    "rope_interleave", "rope_parameters", "routed_scaling_factor",
    "scoring_func", "tie_word_embeddings", "topk_group", "topk_method",
    "v_head_dim", "vocab_size", "torch_dtype", "n_router_outputs",
    "first_held_expert"}

# what the program's stack computes; a file that says otherwise is
# refused rather than served as something else
_FIXED = {"model_type": "glm_moe_dsa", "attention_bias": False,
          "hidden_act": "silu", "moe_layer_freq": 1, "n_group": 1,
          "topk_group": 1, "norm_topk_prob": True,
          "scoring_func": "sigmoid", "topk_method": "noaux_tc",
          "tie_word_embeddings": False, "num_nextn_predict_layers": 0}


def _model_config(cfg: Dict[str, Any]):
    from paddle_tpu.models.glm5 import Glm5Config
    wrong = {k: cfg[k] for k, v in _FIXED.items() if cfg[k] != v}
    if cfg["qk_head_dim"] != cfg["qk_nope_head_dim"] \
            + cfg["qk_rope_head_dim"]:
        wrong["qk_head_dim"] = cfg["qk_head_dim"]
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        wrong["num_key_value_heads"] = cfg["num_key_value_heads"]
    if cfg["rope_parameters"].get("rope_type") != "default":
        wrong["rope_parameters"] = cfg["rope_parameters"]
    if wrong:
        raise ValueError(f"the program's GLM-5 stack does not compute "
                         f"{wrong}")
    return Glm5Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        rope_interleave=cfg["rope_interleave"],
        index_n_heads=cfg["index_n_heads"],
        index_head_dim=cfg["index_head_dim"],
        index_topk=cfg["index_topk"],
        indexer_rope_interleave=cfg["indexer_rope_interleave"],
        intermediate_size=cfg["intermediate_size"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
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
    from paddle_tpu.models.glm5 import Glm5ForCausalLM
    from benchmark import generator
    if training:
        raise ValueError("the GLM-5 stack is built for serving only")
    paddle.seed(int(seed) % (1 << 31))
    model = Glm5ForCausalLM(_model_config(cfg))
    rs = generator.rng_for(seed, 6)
    for blk in model.blocks:
        if hasattr(blk, "router_b"):
            blk.router_b.set_value(
                rs.uniform(-0.1, 0.1, blk.router_b.shape)
                .astype(np.float32))
    model.seed_index(rs)
    model.eval()
    return model


def weights(model):
    """The reference's tree: the model's arrays, a head's two read-outs
    of the latent joined into the one ``wkv_b`` that expands it."""
    import jax.numpy as jnp
    p = model.described_params()
    layers = []
    for lp in p["layers"]:
        lp = dict(lp)
        w_uk, w_uv = lp.pop("w_uk"), lp.pop("w_uv")
        rank = w_uv.shape[1]
        lp["wkv_b"] = jnp.concatenate(
            [jnp.transpose(w_uk, (2, 0, 1)), jnp.transpose(w_uv, (1, 0, 2))],
            axis=-1).reshape(rank, -1)
        layers.append(lp)
    return {"embed": p["embed"], "norm_w": p["norm_w"], "lm_w": p["lm_w"],
            "layers": layers}


def _reference_args(cfg: Dict[str, Any]) -> Dict[str, Any]:
    mc = _model_config(cfg)
    return dict(heads=mc.num_heads, rank=mc.kv_lora_rank,
                nope=mc.qk_nope_head_dim, rope=mc.qk_rope_head_dim,
                value=mc.v_head_dim, theta=mc.rope_theta,
                index_heads=mc.index_n_heads, index_dim=mc.index_head_dim,
                index_topk=mc.index_topk, eps=mc.rms_eps,
                top_k=mc.num_experts_per_tok,
                first_held=mc.held_experts[0],
                routed_scale=mc.routed_scaling_factor)


def reference_logits(w, ids, cfg: Dict[str, Any], dtype=None, omit=()):
    """The plain reference's logits ``[S, V]``; ``dtype`` computes the
    stack in another precision and ``omit`` leaves mechanisms out (the
    tolerance's readings)."""
    import jax.numpy as jnp
    from benchmark.reference import glm5 as ref
    return ref.forward_logits(w, ids, dtype=dtype or jnp.float32,
                              omit=omit, **_reference_args(cfg))


def reference_logits_and_notes(w, ids, cfg: Dict[str, Any]):
    """The reference's logits and, from the same forward pass, each
    row's selection margins: in each expert layer's router and in each
    layer's index."""
    from benchmark.reference import glm5 as ref
    return ref.forward_logits(w, ids, with_margins=True,
                              **_reference_args(cfg))


def reference_report(margins, rows) -> str:
    """Said beside the logits' error: the selections among the checked
    ``rows`` that a rounding could flip."""
    import numpy as np
    from benchmark.reference import glm5 as ref
    e = np.asarray(margins["experts"])[:, list(rows)]
    k = np.asarray(margins["keys"])[:, list(rows)]
    chose = np.isfinite(k)
    return (f"{int((e < ref.NEAR_TIE).sum())} of {e.size} checked "
            f"(expert layer, row) selections are near-ties (8th and 9th "
            f"score closer than {ref.NEAR_TIE:g}; smallest gap "
            f"{float(e.min()):.1e}); {int(chose.sum())} of {k.size} "
            f"checked (layer, row) pairs chose keys, smallest gap at the "
            f"last kept key "
            f"{float(k[chose].min()) if chose.any() else float('nan'):.1e}")


def tolerances() -> Dict[str, float]:
    """``logits``: the limit of every checked row but at most
    ``flipped_rows`` of them, which have ``logits_flipped_row``;
    ``logits_median``: the limit of the median over the checked rows
    (``reference/glm5.py`` says why the rows are read as a set)."""
    from benchmark.reference import glm5 as ref
    return {"logits": ref.LOGITS_TOL, "flipped_rows": ref.FLIPPED_ROWS,
            "logits_flipped_row": ref.FLIP_TOL,
            "logits_median": ref.MEDIAN_TOL}
