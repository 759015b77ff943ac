"""Plain reference of the MiMo-V2 language model's decoder stack
(https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json,
``model_type: mimo_v2``), as one chip's share of it: the full forward
over one sequence, given the expert ids the chip holds.

Straightforward ``jax.numpy`` in float32 at matmul precision "highest":
no kernel, no cache, no paging, no batching, no grouping of rows — every
held expert multiplies every row and a weight of zero drops the rows
that did not pick it.

The layer, as ISSUE 27 writes it down (``x [S, H]``):

* RMS norm (``eps``) before attention and before the feed-forward,
  residual adds after each, a final RMS norm and an untied head;
* one fused projection to ``heads`` queries of ``dk``, ``kv`` keys of
  ``dk`` and ``kv`` values of ``dv`` (``kv`` differs between full and
  window layers); output projection ``[heads * dv, H]``;
* rotary on the first ``rot`` dimensions of each query and key head,
  pair ``(i, i + rot/2)`` turning by ``pos / theta^(2i/rot)``, the rest
  passing through; ``theta`` is the layer kind's own;
* scores ``q.k / sqrt(dk)``, causal; in a window layer key ``j`` is
  visible to query ``i`` iff ``i - W < j <= i``, and a learned scalar
  ``s_h`` per query head joins the softmax's denominator:
  ``p_j = exp(a_j) / (sum_visible exp(a_k) + exp(s_h))``;
* values scaled by ``v_scale`` before the weighted sum;
* feed-forward: dense SwiGLU, or ``g = sigmoid(h W_r)`` over all routed
  experts, the ``top_k`` with the largest ``g + b`` selected (``b``
  steers the selection only; a tie goes to the lower id), weights
  ``g_e / sum_selected g``, ``y = sum over the HELD selected experts of
  w_e SwiGLU_e(h)`` — experts held elsewhere add nothing here, as on
  the chip.

``w`` is the tree ``benchmark/builders/mimo_v2.py`` takes out of the
model: ``embed [V, H]``, ``norm_w``, ``lm_w [V, H]`` and ``layers``, each
``ln1_w wqkv wo sink ln2_w wg wu wd`` (weights ``[in, out]``; ``sink
[heads]`` or None; an expert layer's ``wg wu wd`` are sequences, one
matrix a held expert, and it has ``router_w [H, E]`` and ``router_b
[E]``).
``layers_cfg`` gives each layer's ``(window or None, kv, theta)``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# Serving, logits: max |got - ref| / max |ref| over the checked rows.
# The engine serves float32 weights and this model's step multiplies
# them as float32: its XLA products at precision "high" (three bf16
# passes), the attention kernel's and the router's at "highest".  What
# still separates the program from this reference is rounding at 1e-5
# and SELECTION FLIPS — a row whose 8th and 9th largest g + b lie closer
# than the rounding its router input carries picks another expert; where
# one of the two is held here a whole w_e SwiGLU_e(h) term appears or
# vanishes for that row.  The logits check counts the near-ties among
# the checked rows and prints them, the rows' errors and their median
# beside the worst; the tolerance is not widened for them.
# The limit lies between two readings on the chip at the published
# widths (PERF.md section 6, PR 27).  The program against this reference
# over twenty seeds: 5.7e-5 to 7.1e-5 in eighteen, 2.4e-4 in one and
# 1.8e-3 in one (five of its ten rows at 1.4e-3 to 1.8e-3: a flip).  This reference computed in
# bfloat16 (weights and activations) against itself in float32, which
# has to fail: worst row 2.7e-2, 3.0e-2, 3.9e-2 over three seeds (median
# row 2.3e-2 to 2.6e-2).  With the program's products at jax's default
# (one bf16 pass) it read 1.3e-2 to 4.0e-2 over nine seeds and no limit
# told it from bfloat16, which is why the step does not run there.
# What the limit has to catch, measured with this reference at the
# published widths (prompt of 300, seed 3000000119): no value scale
# 2.1e-1, no window 2.4e-1, no sink 8.4e-2, no selection bias 4.3e-2; at
# a small size on the CPU, where the program reads 4e-7,
# tests/test_mimo_v2_serving.py shows each of the four above the limit.
LOGITS_TOL = 1e-2
# a selection whose 8th and 9th scores lie closer than this is a
# near-tie: about the rounding a bf16 pass leaves on g (2^-8 of ~0.5)
NEAR_TIE = 2e-3


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def _rope_head(x, rot: int, theta: float):
    """``x [S, heads, d]``: the first ``rot`` dimensions of every head
    rotated by the row's position, pairs ``(i, i + rot/2)``."""
    s = x.shape[0]
    half = rot // 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def route(h, router_w, router_b, top_k: int):
    """``(weights [S, E], margin [S])``: each row's weight on every
    routed expert (zero where it was not selected) and the gap between
    its ``top_k``-th and next score."""
    g = jax.nn.sigmoid(h.astype(jnp.float32) @ router_w.astype(jnp.float32))
    score = g + router_b.astype(jnp.float32)
    order = jnp.argsort(-score, axis=-1, stable=True)
    ranked = jnp.take_along_axis(score, order, axis=-1)
    chosen = jnp.zeros_like(g).at[
        jnp.arange(g.shape[0])[:, None], order[:, :top_k]].set(1.0)
    picked = g * chosen
    return picked / jnp.sum(picked, axis=-1, keepdims=True), \
        ranked[:, top_k - 1] - ranked[:, top_k]


def forward_logits(w, ids, layers_cfg, *, heads: int, dk: int, dv: int,
                   rot: int, window: int, v_scale: float, eps: float,
                   top_k: int, first_held: int, dtype=jnp.float32,
                   with_margins: bool = False):
    """``ids [S]`` -> logits ``[S, V]`` of one sequence.  ``dtype``
    other than float32 computes the stack in that precision (weights
    and activations; the PR's second reading for the tolerance).  With
    ``with_margins`` also returns ``[expert layers, S]``: each row's
    selection margin in each expert layer."""
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: jnp.asarray(a, dtype), w)
        s = ids.shape[0]
        x = w["embed"][ids]
        row = jnp.arange(s)
        causal = row[None, :] <= row[:, None]
        in_window = causal & (row[None, :] > row[:, None] - window)
        margins = []
        for lp, (win, kv, theta) in zip(w["layers"], layers_cfg):
            h = _rms_norm(x, lp["ln1_w"], eps)
            qkv = h @ lp["wqkv"]
            q = qkv[:, :heads * dk].reshape(s, heads, dk)
            k = qkv[:, heads * dk:(heads + kv) * dk].reshape(s, kv, dk)
            v = qkv[:, (heads + kv) * dk:].reshape(s, kv, dv) * v_scale
            q, k = _rope_head(q, rot, theta), _rope_head(k, rot, theta)
            k = jnp.repeat(k, heads // kv, axis=1)   # query head j reads
            v = jnp.repeat(v, heads // kv, axis=1)   # kv head j // group
            att = jnp.einsum("qhd,khd->hqk", q, k).astype(jnp.float32) \
                / math.sqrt(dk)
            att = jnp.where(in_window if win else causal, att, -jnp.inf)
            if lp["sink"] is not None:
                col = jnp.broadcast_to(
                    lp["sink"].astype(jnp.float32)[:, None, None],
                    (heads, s, 1))
                att = jax.nn.softmax(jnp.concatenate([att, col], axis=-1),
                                     axis=-1)[..., :-1]
            else:
                att = jax.nn.softmax(att, axis=-1)
            ctx = jnp.einsum("hqk,khd->qhd", att.astype(dtype), v)
            x = x + ctx.reshape(s, heads * dv) @ lp["wo"]
            h = _rms_norm(x, lp["ln2_w"], eps)
            if "router_w" not in lp:
                x = x + (jax.nn.silu(h @ lp["wg"]) * (h @ lp["wu"])) \
                    @ lp["wd"]
                continue
            weights, margin = route(h, lp["router_w"], lp["router_b"],
                                    top_k)
            margins.append(margin)
            for e in range(len(lp["wg"])):
                y = (jax.nn.silu(h @ lp["wg"][e]) * (h @ lp["wu"][e])) \
                    @ lp["wd"][e]
                x = x + weights[:, first_held + e, None].astype(dtype) * y
        x = _rms_norm(x, w["norm_w"], eps)
        logits = (x @ w["lm_w"].T).astype(jnp.float32)
        if with_margins:
            return logits, jnp.stack(margins)
        return logits
