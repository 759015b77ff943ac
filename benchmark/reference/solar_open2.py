"""Plain reference of the Solar Open 2 language model's decoder stack
(https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json,
``model_type: solar_open2``), as one chip's share of it: the full
forward over one sequence, given the expert ids the chip holds.

Straightforward ``jax.numpy`` in float32 at matmul precision "highest":
no kernel, no cache, no paging, no batching, no chunking — a
linear-attention layer is its recurrence, one token after the other,
and every held expert multiplies every row (a weight of zero drops the
rows that did not pick it).  Softmax attention may be taken a block of
query rows at a time (``q_block``), which changes no number and keeps a
6,000-token sequence's scores out of memory.

The layers, as ISSUE 31 writes them down (``x [S, H]``, pre-norm:
``h = x + Mix(norm(x))``, ``y = h + MoE(norm(h))``, a final RMS norm
and an untied head):

* a **GQA layer**: ``q = u Wq`` as ``heads`` of ``d``, ``k, v`` as
  ``kv`` heads of ``d``, no rotation and no position term, causal
  softmax of ``q.k / sqrt(d)``, the weighted sum multiplied by
  ``sigmoid(u Wgate)`` element by element, then ``Wo``;
* a **linear-attention layer** (Kimi Delta Attention): ``q, k, v =
  silu(conv(u Wq)), silu(conv(u Wk)), silu(conv(u Wv))`` with ``conv``
  the causal depthwise convolution ``y_t = sum_i c[i] x_{t-(K-1)+i}``
  (zeros before the sequence, no bias); ``q, k`` divided by their
  length a head (``sqrt(sum x^2 + 1e-6)``), ``q`` also by ``sqrt(d)``;
  ``g_t = -exp(a_log[h]) softplus(u Wf_down Wf_up + dt_bias)`` a key
  channel; ``beta_t = 2 sigmoid(u Wbeta)`` a head; per head, ``S``
  ``[d, d]`` float32 from zeros::

      S' = diag(exp(g_t)) S;  S = S' + beta_t k_t (v_t - S'^T k_t)^T
      o_t = S^T q_t

  then an RMS norm over each head's ``d`` (one weight vector), times
  ``sigmoid(u Wgate_down Wgate_up)``, then ``Wo``;
* the **expert layer**: ``g = sigmoid(h W_r)`` over all routed experts,
  the ``top_k`` with the largest ``g + b`` selected (``b`` steers the
  selection only; a tie goes to the lower id), weights ``g_e /
  sum_selected g``, ``y = SwiGLU_shared(h) + scale * sum over the HELD
  selected experts of w_e SwiGLU_e(h)`` — experts held elsewhere add
  nothing here, as on the chip.

``w`` is the tree ``benchmark/builders/solar_open2.py`` takes out of
the model: ``embed [V, H]``, ``norm_w``, ``lm_w [V, H]`` and ``layers``
(weights ``[in, out]``, convolution taps ``[K, channels]``, an expert
layer's ``wg wu wd`` sequences of one matrix a held expert).
``gqa_layers`` lists the GQA layers' indices.

``omit`` names mechanisms to leave out, for the tolerance's table
(``tests/test_solar_open2_tolerance.py``): each must move the logits
past ``LOGITS_TOL``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# Serving, logits: max |got - ref| / max |ref| over the checked rows.
# The engine serves float32 weights and this model's step multiplies
# them as float32: its XLA products at precision "high" (three bf16
# passes), the router's, the recurrence's and the attention kernel's at
# "highest".  What separates the program from this reference is rounding
# (2.1e-4 to 4.6e-4 at eight layers: a linear-attention layer leaves
# 7e-5, a GQA layer 2e-6) and SELECTION FLIPS: a row whose 8th and 9th
# largest g + b lie closer than that rounding picks another expert;
# where one of the two is held here a whole w_e SwiGLU_e(h) term appears
# or vanishes for that row, and the linear-attention layers' state
# carries the trace to every later row of the sequence.  Among 320
# crowded scores that happens in about one check in three.  The logits
# check counts the near-ties among the checked rows and prints them.
#
# The limit lies between two readings on the chip at the published
# widths (PERF.md section 6, PR 31; tests/test_solar_open2_tolerance.py
# has the same table at a small size).  The program against this
# reference over 46 checks of 19 seeds: 2.1e-4 to 5.5e-4 in 31 of them,
# 8.6e-4 to 3.0e-3 in ten, 1.07e-2 in five checks of ONE seed — the same
# to three digits with blocks of 16, 32 or 64 rows and with every
# product at "highest" (which is why the description stays at "high"),
# and 2.7e-4 for that seed once this file's exp sat elsewhere in its
# loop: a flip follows the last bit of whichever side computed it.
# This reference computed in bfloat16 (weights and activations) against
# itself in float32, which has to fail: worst of ten rows 7.7e-2 to
# 1.24e-1.  Each mechanism in OMISSIONS left out, which has to fail too:
# no selection bias 2.0e-1 to 2.8e-1, write strength not doubled 5.2e-1,
# no output gate 7.2e-1, no decay 8.4e-1, no GQA gate 8.4e-1, no shared
# expert 9.8e-1, no convolution 9.9e-1 at the least, no q/k
# normalisation not finite.  3e-2 leaves a factor of 2.8 above the
# largest honest reading and of 2.6 below the smallest bfloat16 one
# (ISSUE 31 set 1e-2 "unless the table says otherwise": one seed in 19
# read over it).  A flip ON a checked row would read about what
# bfloat16 reads and fail any limit between the two; by the count above
# that is a run in a few hundred.
LOGITS_TOL = 3e-2
NEAR_TIE = 2e-3

OMISSIONS = ("decay", "beta_doubling", "convolution", "qk_norm",
             "output_gate", "gqa_gate", "shared_expert", "selection_bias")


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


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


def _causal_conv(x, taps):
    """``y_t = sum_i taps[i] x_{t-(K-1)+i}``, zeros before row 0."""
    k = taps.shape[0]
    padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(taps[i] * padded[i:i + x.shape[0]] for i in range(k))


def _gqa(lp, u, heads: int, kv: int, d: int, q_block: int, omit):
    s = u.shape[0]
    q = (u @ lp["wq"]).reshape(s, heads, d)
    k = jnp.repeat((u @ lp["wk"]).reshape(s, kv, d), heads // kv, axis=1)
    v = jnp.repeat((u @ lp["wv"]).reshape(s, kv, d), heads // kv, axis=1)
    out = []
    for lo in range(0, s, q_block):
        rows = jnp.arange(lo, min(lo + q_block, s))
        att = jnp.einsum("qhd,khd->hqk", q[rows], k).astype(jnp.float32) \
            / math.sqrt(d)
        att = jnp.where(jnp.arange(s)[None, :] <= rows[:, None], att,
                        -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd",
                              jax.nn.softmax(att, axis=-1).astype(u.dtype),
                              v))
    ctx = jnp.concatenate(out, axis=0).reshape(s, heads * d)
    if "gqa_gate" not in omit:
        ctx = ctx * jax.nn.sigmoid(u @ lp["wgate"])
    return ctx @ lp["wo"]


def _kda(lp, u, heads: int, d: int, eps: float, omit):
    s = u.shape[0]
    f32 = jnp.float32
    conv = (lambda x, taps: x) if "convolution" in omit else _causal_conv
    q, k, v = (jax.nn.silu(conv(u @ lp["w" + n], lp["conv_" + n]))
               .reshape(s, heads, d).astype(f32) for n in "qkv")
    if "qk_norm" not in omit:
        q, k = (a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
                for a in (q, k))
    q = q * d ** -0.5
    g = -jnp.exp(lp["a_log"].astype(f32))[None, :, None] * jax.nn.softplus(
        ((u @ lp["wf_down"]) @ lp["wf_up"] + lp["dt_bias"])
        .astype(f32)).reshape(s, heads, d)
    if "decay" in omit:
        g = jnp.zeros_like(g)
    beta = (1.0 if "beta_doubling" in omit else 2.0) \
        * jax.nn.sigmoid((u @ lp["wbeta"]).astype(f32))          # [S, h]

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        decayed = jnp.exp(g_t)[:, :, None] * state               # S'
        seen = jnp.einsum("hkv,hk->hv", decayed, k_t)            # S'^T k
        state = decayed + b_t[:, None, None] * k_t[:, :, None] \
            * (v_t - seen)[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((heads, d, d), f32),
                        (q, k, v, g, beta))
    o = _rms_norm(o, lp["out_norm_w"].astype(f32), eps).astype(u.dtype)
    o = o.reshape(s, heads * d)
    if "output_gate" not in omit:
        o = o * jax.nn.sigmoid((u @ lp["wgate_down"]) @ lp["wgate_up"])
    return o @ lp["wo"]


def expert_layer(lp, h, top_k: int, first_held: int, routed_scale: float,
                 omit=()):
    """``(y [S, H], margin [S])``: the shared expert and this chip's
    routed part of one expert layer for normed rows ``h``."""
    bias = jnp.zeros_like(lp["router_b"]) if "selection_bias" in omit \
        else lp["router_b"]
    weights, margin = route(h, lp["router_w"], bias, top_k)
    y = jnp.zeros_like(h)
    for e in range(len(lp["wg"])):
        y = y + weights[:, first_held + e, None].astype(h.dtype) \
            * _swiglu(h, lp["wg"][e], lp["wu"][e], lp["wd"][e])
    y = y * routed_scale
    if "shared_wg" in lp and "shared_expert" not in omit:
        y = y + _swiglu(h, lp["shared_wg"], lp["shared_wu"],
                        lp["shared_wd"])
    return y, margin


def forward_logits(w, ids, gqa_layers, *, heads: int, kv: int, d: int,
                   eps: float, top_k: int, first_held: int,
                   routed_scale: float = 1.0, dtype=jnp.float32,
                   q_block: int = 512, omit=(),
                   with_margins: bool = False):
    """``ids [S]`` -> logits ``[S, V]`` of one sequence.  ``dtype``
    other than float32 computes the stack in that precision (weights and
    activations; the recurrence's state stays float32): the tolerance's
    second reading.  With ``with_margins`` also returns ``[layers, S]``:
    each row's selection margin in each expert layer."""
    unknown = set(omit) - set(OMISSIONS)
    if unknown:
        raise ValueError(f"unknown omissions {sorted(unknown)}")
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: jnp.asarray(a, dtype), w)
        x = w["embed"][ids]
        margins = []
        for i, lp in enumerate(w["layers"]):
            u = _rms_norm(x, lp["ln1_w"], eps)
            if i in gqa_layers:
                x = x + _gqa(lp, u, heads, kv, d, q_block, omit)
            else:
                x = x + _kda(lp, u, heads, d, eps, omit)
            y, margin = expert_layer(lp, _rms_norm(x, lp["ln2_w"], eps),
                                     top_k, first_held, routed_scale, omit)
            margins.append(margin)
            x = x + y
        x = _rms_norm(x, w["norm_w"], eps)
        logits = (x @ w["lm_w"].T).astype(jnp.float32)
        if with_margins:
            return logits, jnp.stack(margins)
        return logits
