"""Builds the program's LLaMA-style stack
(``paddle_tpu.models.llama.LlamaForCausalLM``) from a configuration
file's sizes — any model of that block: here Mistral-7B's widths — and
hands its weights to ``benchmark/reference/llama_stack.py``.
"""
from __future__ import annotations

from typing import Any, Dict

# Hugging Face's key names, at the top level of the configuration file
MODEL_KEYS = {"num_hidden_layers", "hidden_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "intermediate_size",
              "vocab_size", "max_position_embeddings", "rope_theta",
              "rms_norm_eps", "hidden_act", "sliding_window",
              "tie_word_embeddings", "attention_bias", "torch_dtype"}


def build(cfg: Dict[str, Any], seed: int, training: bool):
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    if cfg["head_dim"] * cfg["num_attention_heads"] != cfg["hidden_size"] \
            or cfg["sliding_window"] is not None:
        raise ValueError("the program's stack has heads of hidden/heads "
                         "and no sliding window")
    paddle.seed(int(seed) % (1 << 31))
    lcfg = LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        attention_bias=cfg["attention_bias"], hidden_act=cfg["hidden_act"],
        tie_word_embeddings=cfg["tie_word_embeddings"])
    model = LlamaForCausalLM(lcfg)
    if not training:
        model.eval()
    return model


def weights(model):
    params, _ = model.build_decode_step()
    return {"embed": params["embed"], "norm_w": params["norm_w"],
            "lm_w": params["lm_w"],
            "layers": [{k: lp[k] for k in ("ln1_w", "wq", "wk", "wv", "wo",
                                           "ln2_w", "wg", "wu", "wd")}
                       for lp in params["layers"]]}


def reference_logits(w, ids, cfg: Dict[str, Any]):
    from benchmark.reference import llama_stack as ref
    return ref.forward_logits(w, ids, cfg["num_attention_heads"],
                              cfg["num_key_value_heads"],
                              float(cfg["rope_theta"]),
                              float(cfg["rms_norm_eps"]))


def tolerances() -> Dict[str, float]:
    from benchmark.reference import llama_stack as ref
    return {"logits": ref.LOGITS_TOL}


def flops_shape(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return {"hidden": cfg["hidden_size"],
            "layers": cfg["num_hidden_layers"],
            "heads": cfg["num_attention_heads"],
            "head_dim": cfg["head_dim"],
            "kv_heads": cfg["num_key_value_heads"],
            "ffn": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
            "gated": True}
