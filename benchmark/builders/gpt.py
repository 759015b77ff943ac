"""Builds the program's GPT (``paddle_tpu.models.GPTForPretraining``)
from a configuration file's sizes, and hands its weights to the plain
reference (``benchmark/reference/gpt.py``).  The program is imported
inside the functions: loading a configuration needs only ``MODEL_KEYS``.
"""
from __future__ import annotations

from typing import Any, Dict

# the size keys a configuration file of this family holds at top level
MODEL_KEYS = {"num_hidden_layers", "hidden_size", "num_attention_heads",
              "head_dim", "intermediate_size", "vocab_size",
              "max_position_embeddings", "tie_word_embeddings",
              "layer_norm_epsilon", "activation_function"}


def build(cfg: Dict[str, Any], seed: int, training: bool):
    """The model with weights random from ``seed``, float32, on the
    default device.  ``training`` turns activation recompute on (the
    configuration's train settings say so) and leaves dropout at 0."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForPretraining
    from paddle_tpu.models.gpt import GPTConfig
    if cfg["head_dim"] * cfg["num_attention_heads"] != cfg["hidden_size"] \
            or cfg["layer_norm_epsilon"] != 1e-5 \
            or cfg["activation_function"] != "gelu_tanh":
        raise ValueError("the program's GPT has heads of hidden/heads, "
                         "layer-norm epsilon 1e-5 and tanh GELU")
    paddle.seed(int(seed) % (1 << 31))
    recompute = bool(training and cfg.get("train", {}).get("recompute"))
    gcfg = GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        intermediate_size=cfg["intermediate_size"],
        hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
        use_recompute=recompute,
        tie_word_embeddings=cfg["tie_word_embeddings"])
    model = GPTForPretraining(gcfg)
    if not training:
        model.eval()
    return model


def weights(model):
    """The tree of named arrays the reference reads (the model's own
    decode-step parameters: the same buffers, no copy)."""
    params, _ = model.build_decode_step()
    return params


def reference_logits(w, ids, cfg: Dict[str, Any]):
    from benchmark.reference import gpt as ref
    return ref.forward_logits(w, ids, cfg["num_attention_heads"])


def reference_loss(w, ids, labels, cfg: Dict[str, Any]):
    from benchmark.reference import gpt as ref
    return ref.loss(w, ids, labels, cfg["num_attention_heads"])


def tolerances() -> Dict[str, float]:
    from benchmark.reference import gpt as ref
    return {"logits": ref.LOGITS_TOL, "loss": ref.LOSS_TOL}


def flops_shape(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return {"hidden": cfg["hidden_size"],
            "layers": cfg["num_hidden_layers"],
            "heads": cfg["num_attention_heads"],
            "head_dim": cfg["head_dim"],
            "kv_heads": cfg["num_attention_heads"],
            "ffn": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
            "gated": False}
