"""Operations a training step needs, from shapes alone.

Model FLOP/s utilization counts what the forward and backward passes
*require* per token: every matrix multiplication with a weight (2
operations per parameter forward, 4 backward: 6 per parameter) and
causal attention's two products.  Work the program chooses to do again
(activation recompute) does not count, and neither do embedding
look-ups, norms, biases or the softmax.
"""
from __future__ import annotations

from typing import Any, Dict


def matmul_params(shape: Dict[str, Any]) -> int:
    """Weights that take part in a matrix multiplication, per token:
    ``layers`` blocks and the output head (tied or not, the head is a
    ``[vocab, hidden]`` product).

    ``shape``: ``hidden``, ``layers``, ``heads``, ``head_dim``,
    ``kv_heads``, ``ffn``, ``vocab`` and ``gated`` (a gated
    feed-forward has three matrices, a plain one two)."""
    h, hd = int(shape["hidden"]), int(shape["head_dim"])
    q = h * int(shape["heads"]) * hd
    kv = 2 * h * int(shape["kv_heads"]) * hd
    out = int(shape["heads"]) * hd * h
    ffn = (3 if shape["gated"] else 2) * h * int(shape["ffn"])
    return int(shape["layers"]) * (q + kv + out + ffn) \
        + int(shape["vocab"]) * h


def attention_flops_per_token(shape: Dict[str, Any], seq: int) -> float:
    """Causal attention, forward and backward, per token of a sequence
    of ``seq``: QK^T and PV are each ``2 * context * heads * head_dim``
    forward, the mean context of a causal sequence is ``seq / 2``, and
    the backward pass costs twice the forward."""
    width = int(shape["heads"]) * int(shape["head_dim"])
    forward = 2 * 2 * (seq / 2.0) * width
    return 3.0 * forward * int(shape["layers"])


def train_flops_per_token(shape: Dict[str, Any], seq: int) -> float:
    return 6.0 * matmul_params(shape) + attention_flops_per_token(shape,
                                                                  seq)
