"""Plain reference of the LLaMA-style decoder stack that Mistral-7B
uses (Jiang et al. 2023, arXiv:2310.06825; v0.3 has no sliding window):
RMS norm, rotary positions, grouped-query causal attention without
biases, SwiGLU feed-forward, final RMS norm, untied output head.

Straightforward ``jax.numpy`` in float32 at matmul precision "highest":
no kernel, no cache, no batching, one sequence at a time.  One
departure, the program's own: the rotation pairs adjacent dimensions
``(2i, 2i+1)`` (the original LLaMA layout) where Hugging Face's
``rotate_half`` pairs ``(i, i + d/2)``.  The two are the same function
under a fixed permutation of each head's rows of ``wq`` and ``wk``, which
is how published checkpoints are converted, and with random weights
neither is preferred.

``w`` is the tree ``benchmark/builders/llama_stack.py`` takes out of the
model: ``embed [V, H]``, ``layers`` (each ``ln1_w wq wk wv wo ln2_w wg wu
wd``, weights ``[in, out]``), ``norm_w``, ``lm_w [V, H]``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# Serving, logits: the engine serves float32 weights, but the program's
# float32 products run at jax's default precision, which on the MXU is
# one bf16 pass (2^-8 relative per product).  Measured on the chip at
# Mistral-7B's widths, 8 layers, seven seeds: 2.7e-2 to 3.3e-2 of the
# largest logit (my chip run, PR 24); 2.4e-7 on the CPU, where float32 is
# float32.  A wrong rotation, a wrong key-value head for a query head, a
# wrong position or cache row, or a dropped layer decorrelates the logits
# (error of the order of 1).  The tolerance is three times what was
# measured; while the program multiplies at default precision it cannot
# tell bfloat16 serving from float32 serving (PERF.md, Open questions).
LOGITS_TOL = 1e-1          # max |got - ref| / max |ref|


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def _rope(x, theta: float):
    """``x [S, heads, hd]`` rotated by its row's position; pair
    ``(2i, 2i+1)`` turns by ``pos / theta^(2i/hd)``."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def forward_logits(w, ids, heads: int, kv_heads: int, theta: float,
                   eps: float):
    """``ids [S]`` -> logits ``[S, V]`` of one sequence."""
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), w)
        s = ids.shape[0]
        x = w["embed"][ids]
        hidden = x.shape[-1]
        hd = w["layers"][0]["wq"].shape[1] // heads
        group = heads // kv_heads
        causal = jnp.tril(jnp.ones((s, s), bool))
        for lp in w["layers"]:
            h = _rms_norm(x, lp["ln1_w"], eps)
            q = _rope((h @ lp["wq"]).reshape(s, heads, hd), theta)
            k = _rope((h @ lp["wk"]).reshape(s, kv_heads, hd), theta)
            v = (h @ lp["wv"]).reshape(s, kv_heads, hd)
            k = jnp.repeat(k, group, axis=1)      # query head j reads
            v = jnp.repeat(v, group, axis=1)      # kv head j // group
            att = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
            att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
            ctx = jnp.einsum("hqk,khd->qhd", att, v).reshape(s, heads * hd)
            x = x + ctx @ lp["wo"]
            h = _rms_norm(x, lp["ln2_w"], eps)
            x = x + (jax.nn.silu(h @ lp["wg"]) * (h @ lp["wu"])) @ lp["wd"]
        x = _rms_norm(x, w["norm_w"], eps)
        return x @ w["lm_w"].T
