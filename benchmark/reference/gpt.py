"""Plain reference of the GPT-3 decoder (Brown et al. 2020; GPT-2's
block): learned positions, pre-norm blocks of causal multi-head
attention and a GELU feed-forward, final layer norm, output head tied to
the word embedding unless ``lm_w`` is given.

Straightforward ``jax.numpy`` in float32 at matmul precision "highest"
(on a TPU a float32 product is otherwise one bf16 MXU pass): no kernel,
no cache, no batching, one sequence at a time.  Departures from the
paper, all the program's own and kept so that the two compute the same
function: the tanh approximation of GELU (GPT-2's), layer-norm epsilon
1e-5, dense attention in every layer (the paper alternates dense and
locally banded sparse layers; no public implementation of GPT-3's widths
does), vocabulary padded from 50257 to 50304 rows.

``w`` is the tree of named arrays that ``benchmark/builders/gpt.py``
takes out of the model: ``wte [V, H]``, ``wpe [P, H]``, ``blocks`` (each
``ln1_w ln1_b wq wk wv bq bk bv wo bo ln2_w ln2_b w1 b1 w2 b2``, weights
``[in, out]``), ``lnf_w``, ``lnf_b``, ``lm_w`` (``[V, H]`` or None).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# --- tolerances, each with its reason --------------------------------------
# Serving, logits: the engine serves float32 weights, but the program's
# float32 products run at jax's default precision, which on the MXU is
# one bf16 pass (2^-8 relative per product); PR 21 measured 4-5e-3 of the
# largest value for the ragged kernel alone, and the same step at
# Mistral-7B's widths, 8 layers, measured 2.7e-2 to 3.3e-2 of the largest
# logit end to end (my chip run, PR 24; see reference/llama_stack.py).  A
# wrong position or cache row or a dropped layer decorrelates the logits
# (error of the order of 1).  While the program multiplies at default
# precision the check cannot tell bfloat16 serving from float32 serving.
LOGITS_TOL = 1e-1          # max |got - ref| / max |ref|
# Training, first loss: AMP O2 computes in bfloat16 and returns the loss
# in bfloat16, whose spacing between 8 and 16 is 2^-4 = 0.0625; the
# reference is float32 on the same batch and the same initial weights.
# One spacing of rounding plus the bfloat16 forward's own error.
LOSS_TOL = 0.125           # |got - ref|, in nats


def _layer_norm(x, w, b, eps=1e-5):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), axis=-1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def forward_logits(w, ids, heads: int):
    """``ids [S]`` -> logits ``[S, V]`` of one sequence."""
    with jax.default_matmul_precision("highest"):
        w = _f32(w)
        s = ids.shape[0]
        x = w["wte"][ids] + w["wpe"][jnp.arange(s)]
        hidden = x.shape[-1]
        hd = hidden // heads
        causal = jnp.tril(jnp.ones((s, s), bool))
        for b in w["blocks"]:
            h = _layer_norm(x, b["ln1_w"], b["ln1_b"])
            q = (h @ b["wq"] + b["bq"]).reshape(s, heads, hd)
            k = (h @ b["wk"] + b["bk"]).reshape(s, heads, hd)
            v = (h @ b["wv"] + b["bv"]).reshape(s, heads, hd)
            att = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
            att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
            ctx = jnp.einsum("hqk,khd->qhd", att, v).reshape(s, hidden)
            x = x + ctx @ b["wo"] + b["bo"]
            h = _layer_norm(x, b["ln2_w"], b["ln2_b"])
            x = x + _gelu_tanh(h @ b["w1"] + b["b1"]) @ b["w2"] + b["b2"]
        x = _layer_norm(x, w["lnf_w"], w["lnf_b"])
        head = w["wte"] if w.get("lm_w") is None else w["lm_w"]
        return x @ head.T


def loss(w, ids, labels, heads: int):
    """Mean cross-entropy of ``labels [B, S]`` under the logits of
    ``ids [B, S]``, one sequence at a time."""
    def one(pair):
        logits = forward_logits(w, pair[0], heads)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, pair[1][:, None],
                                             axis=-1))
    return jnp.mean(jax.lax.map(one, (ids, labels)))
