"""Autoregressive generation (ref: PaddleNLP GenerationMixin.generate —
the reference ecosystem's decode API).

TPU-native decode: the prefill runs once over the prompt, then each
step feeds ONE new token with the layer KV caches carried forward —
attention runs at sq=1 against the cached sk, the decode shape the
Pallas flash kernel's bottom-right causal alignment (q_offset) was
built for.  Sampling draws from the framework RNG (``paddle.seed``
deterministic).

Two decode engines share this module:

* the **eager loop** — one host-dispatched model call per token,
  writing into a preallocated token buffer (``lax.dynamic_update_slice``
  — no O(n²) concat growth) with the ``finished.all()`` host sync
  hoisted to every ``FLAGS_eager_finished_sync_every`` tokens (the
  exact eager stop column is reconstructed from the buffer, so outputs
  are unchanged);
* the **compiled mega-kernel loop** (``decode_loop``, behind
  ``FLAGS_megakernel_decode`` — MPK, PAPERS.md arXiv 2512.22219): the
  whole token loop runs inside ONE jitted ``lax.while_loop`` whose body
  is the model's cache-aware single-token step built from the fused
  Pallas decode kernels (``ops/pallas/fused_decode``), with on-device
  sampling and EOS tracking — zero host transfers per token, KV caches
  donated to the loop carry.  Beam search / paged caches / models
  without a ``build_decode_step`` fall back to the eager loop; every
  call emits a ``decode_loop`` observability event saying which engine
  ran.

Models without cache plumbing fall back to full-prefix recompute per
step (``use_cache=False``) — identical tokens, O(n^2) instead of O(n).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..flags import get_flag
from ..random_state import default_generator

__all__ = ["generate", "decode_loop", "build_ragged_decode_step",
           "build_fused_window_step", "AttentionKind", "FeedForwardKind",
           "LinearAttentionKind", "LatentAttentionKind", "IndexKind",
           "LayerDescription", "ModelDescription", "CacheDescription",
           "LaneState", "LatentPages"]

_GREEDY = ("greedy_search", "greedy")


def _sample_logits(logits_row, key, decode_strategy, temperature, top_k,
                   top_p):
    """One next-token choice from [B, V] logits — pure jnp, the key
    passed explicitly so the SAME function is the eager sampler and the
    compiled loop body's sampler (token-for-token parity by
    construction)."""
    if decode_strategy in _GREEDY:
        return jnp.argmax(logits_row, axis=-1)
    logits = logits_row.astype(jnp.float32)
    if temperature and temperature != 1.0:
        logits = logits / temperature
    if top_k and top_k > 0:
        kth = jnp.sort(logits, axis=-1)[..., -int(top_k)][..., None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p and top_p < 1.0:
        sorted_l = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_l, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest set of tokens whose mass reaches top_p
        cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_l, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1)


def _sample(logits_row, decode_strategy, temperature, top_k, top_p):
    """Eager-path sampler: draws its key from the framework RNG."""
    key = None if decode_strategy in _GREEDY \
        else default_generator.next_key()
    return _sample_logits(logits_row, key, decode_strategy, temperature,
                          top_k, top_p)


def _reorder_past(past, beam_idx):
    """Reorder a dense per-layer (k, v) cache along the batch axis (the
    beam permutation after each step — ref: GenerationMixin
    _reorder_cache)."""
    out = []
    for k, v in past:
        out.append((Tensor(jnp.asarray(k._data)[beam_idx]),
                    Tensor(jnp.asarray(v._data)[beam_idx])))
    return out


def _beam_search(model, arr, max_new_tokens, num_beams, length_penalty,
                 eos_token_id, supports_cache, last_only,
                 pad_token_id=None, forced_eos_token_id=None):
    """HF-semantics beam search (ref: PaddleNLP GenerationMixin
    beam_search + transformers BeamSearchScorer): per-batch
    BeamHypotheses with score = sum_logprobs / len**length_penalty,
    2*num_beams candidate expansion so eos candidates never starve the
    live set, cache rows permuted by the chosen beam indices."""
    B = int(arr.shape[0])
    nb = int(num_beams)
    # expand each row to nb beams; first beam active, rest -inf so the
    # first step picks nb DISTINCT continuations of the prompt
    arr = jnp.repeat(arr, nb, axis=0)
    beam_scores = jnp.full((B, nb), -1e9, jnp.float32).at[:, 0].set(0.0)
    hyps = [[] for _ in range(B)]      # (score, token_array)
    done = [False] * B                 # pool frozen (HF is_done)

    past = None
    if supports_cache:
        kw = {"last_logits_only": True} if last_only else {}
        logits, past = model(Tensor(arr), use_cache=True, **kw)
    else:
        logits = model(Tensor(arr))

    for it in range(int(max_new_tokens)):
        logp = jax.nn.log_softmax(
            jnp.asarray(logits._data)[:, -1, :].astype(jnp.float32), -1)
        V = logp.shape[-1]
        if forced_eos_token_id is not None and \
                it == int(max_new_tokens) - 1:
            # HF ForcedEOSTokenLogitsProcessor (BART's config default):
            # the last generated slot can only be eos, at logp 0
            logp = jnp.full_like(logp, -1e9).at[
                :, int(forced_eos_token_id)].set(0.0)
        scores = beam_scores.reshape(B * nb, 1) + logp
        scores = scores.reshape(B, nb * V)
        top_s, top_i = jax.lax.top_k(scores, 2 * nb)
        top_s = np.asarray(top_s)
        top_i = np.asarray(top_i)
        arr_np = np.asarray(arr)
        beam_idx = np.zeros((B, nb), np.int64)
        beam_tok = np.zeros((B, nb), np.int64)
        new_scores = np.zeros((B, nb), np.float32)
        for b in range(B):
            if done[b]:
                beam_idx[b, :] = b * nb
                new_scores[b, :] = -1e9
                continue
            live = 0
            for rank, (s, i) in enumerate(zip(top_s[b], top_i[b])):
                src, tok = divmod(int(i), V)
                if eos_token_id is not None and tok == eos_token_id:
                    if rank >= nb:
                        # HF BeamSearchScorer: an eos candidate outside
                        # the top num_beams never forms a hypothesis
                        continue
                    seq = arr_np[b * nb + src]
                    # HF normalizes by the STORED sequence length —
                    # prompt/start included, the appended eos excluded
                    cur_len = seq.shape[0]
                    hyps[b].append(
                        (float(s) / (cur_len ** length_penalty),
                         np.concatenate([seq, [eos_token_id]])))
                    if len(hyps[b]) > nb:
                        # HF BeamHypotheses: keep only the best nb
                        hyps[b].remove(min(hyps[b],
                                           key=lambda t: t[0]))
                    continue
                if live < nb:
                    beam_idx[b, live] = b * nb + src
                    beam_tok[b, live] = tok
                    new_scores[b, live] = s
                    live += 1
            if live < nb:          # pathological: pad with beam 0
                beam_idx[b, live:] = b * nb
                new_scores[b, live:] = -1e9
            # is_done (early_stopping=False semantics): once nb
            # hypotheses exist and the best live continuation cannot
            # beat the worst of them, the pool freezes
            if len(hyps[b]) >= nb:
                cur_len = arr_np.shape[1] + 1
                # HF is_done: best over ALL 2*nb candidates (incl. the
                # eos ones) vs the worst KEPT hypothesis
                best_possible = float(top_s[b][0]) / (
                    cur_len ** length_penalty)
                worst_kept = min(h[0] for h in hyps[b])
                if worst_kept >= best_possible:
                    done[b] = True
        if all(done):
            break
        flat_idx = jnp.asarray(beam_idx.reshape(-1))
        arr = jnp.concatenate(
            [jnp.asarray(arr)[flat_idx],
             jnp.asarray(beam_tok.reshape(-1, 1), arr.dtype)], axis=1)
        beam_scores = jnp.asarray(new_scores)
        if it == int(max_new_tokens) - 1:
            # the loop is over: this iteration's forward (and the cache
            # reorder feeding it) would be discarded — finalize reads
            # only arr/beam_scores
            continue
        if supports_cache:
            past = _reorder_past(past, flat_idx)
            logits, past = model(Tensor(arr[:, -1:]), past=past,
                                 use_cache=True)
        else:
            logits = model(Tensor(arr))

    # finalize: UNDONE batches' live beams join the hypothesis pools
    arr_np = np.asarray(arr)
    bs = np.asarray(beam_scores)
    full_len = arr_np.shape[1]
    for b in range(B):
        if done[b]:
            continue
        for j in range(nb):
            hyps[b].append(
                (float(bs[b, j]) / (max(full_len, 1) ** length_penalty),
                 arr_np[b * nb + j]))
    best = [max(h, key=lambda t: t[0])[1] for h in hyps]
    width = max(len(s) for s in best)
    pad = pad_token_id if pad_token_id is not None else (
        eos_token_id if eos_token_id is not None else 0)
    out = np.full((B, width), pad, arr_np.dtype)
    for b, s in enumerate(best):
        out[b, :len(s)] = s
    return Tensor(jnp.asarray(out))


def seq2seq_generate(decode_step, start_token_id, batch, max_new_tokens,
                     eos_token_id, pad_token_id, num_beams=1,
                     length_penalty=1.0, forced_eos_token_id=None,
                     max_positions=None):
    """Shared seq2seq decode used by the encoder-decoder families
    (T5/BART): ``decode_step(dec_ids_tensor) -> logits`` closes over
    the (beam-expanded, if needed) encoder memory.  Greedy rows hold
    at pad after eos; ``num_beams > 1`` runs the HF-semantics beam
    scorer; ``forced_eos_token_id`` forces the final slot (BART's
    config default)."""
    if max_positions is not None and \
            1 + int(max_new_tokens) > int(max_positions):
        raise ValueError(
            f"decoder length 1+{max_new_tokens} exceeds "
            f"max_position_embeddings {max_positions}")
    if num_beams > 1:
        start = jnp.asarray(np.full((batch, 1), start_token_id,
                                    "int64"))
        return _beam_search(decode_step, start, max_new_tokens,
                            int(num_beams), length_penalty,
                            eos_token_id, supports_cache=False,
                            last_only=False, pad_token_id=pad_token_id,
                            forced_eos_token_id=forced_eos_token_id)
    dec = np.full((batch, 1), start_token_id, "int64")
    finished = np.zeros((batch,), bool)
    for it in range(int(max_new_tokens)):
        logits = decode_step(Tensor(dec))
        if forced_eos_token_id is not None and \
                it == int(max_new_tokens) - 1:
            nxt = np.full((batch,), forced_eos_token_id, "int64")
        else:
            nxt = np.asarray(
                jnp.asarray(logits._data)[:, -1, :].argmax(-1))
        nxt = np.where(finished, pad_token_id, nxt)
        dec = np.concatenate([dec, nxt[:, None].astype("int64")], 1)
        if eos_token_id is not None:
            finished |= nxt == eos_token_id
            if finished.all():
                break
    return Tensor(jnp.asarray(dec))


def _to_paged(past, batch, max_total):
    """Convert a dense prefill cache (per-layer (k, v) of
    [B, S, nkv, hd]) into per-layer page pools + views (ref role: the
    serving block cache behind block_multihead_attention)."""
    from ..ops.paged_attention import build_paged_caches
    k0 = past[0][0]._data
    nkv, hd = k0.shape[2], k0.shape[3]
    views = build_paged_caches(len(past), batch, max_total, nkv, hd,
                               dtype=str(k0.dtype))
    for view, (k, v) in zip(views, past):
        ka, va = k._data, v._data
        for b in range(batch):
            view.cache.prefill(b, Tensor(ka[b]), Tensor(va[b]))
    return views


# ---------------------------------------------------------------------------
# the compiled mega-kernel decode engine
# ---------------------------------------------------------------------------

def _megakernel_fallback_reason(model, decode_strategy, num_beams,
                                use_paged_cache, supports_cache,
                                max_new_tokens) -> Optional[str]:
    """None when the compiled loop can run this request; else the
    (stable, event-logged) reason the eager loop runs instead."""
    if num_beams > 1:
        return "beam_search"
    if decode_strategy not in _GREEDY + ("sampling",):
        return f"strategy:{decode_strategy}"
    if use_paged_cache:
        return "paged_cache"
    if not supports_cache:
        return "no_kv_cache"
    if not hasattr(model, "build_decode_step"):
        return "no_decode_step_builder"
    if int(max_new_tokens) <= 0:
        return "nothing_to_generate"
    return None


def _build_decode_program(step_fn, *, s_prompt, max_new, strategy,
                          temperature, top_k, top_p, eos_token_id):
    """One jitted program running the ENTIRE token loop in a
    lax.while_loop — sample on device, track EOS on device, step the
    model through the fused decode kernels.  The preallocated token
    buffer and KV caches are DONATED loop carries (they are also
    outputs, so XLA reuses their buffers in place across the loop —
    the donation_hints follow-on from the pass pipeline)."""
    sampling = strategy not in _GREEDY

    def program(params, tokens, caches, last_logits, key):
        b = tokens.shape[0]

        def cond(carry):
            i, _, finished, _, _, _ = carry
            live = i < max_new
            if eos_token_id is not None:
                live = jnp.logical_and(
                    live, jnp.logical_not(jnp.all(finished)))
            return live

        def body(carry):
            i, tokens, finished, key, logits, caches = carry
            sub = None
            if sampling:
                key, sub = jax.random.split(key)
            nxt = _sample_logits(logits, sub, strategy, temperature,
                                 top_k, top_p)
            if eos_token_id is not None:
                nxt = jnp.where(finished, eos_token_id, nxt)
                finished = finished | (nxt == eos_token_id)
            tokens = jax.lax.dynamic_update_slice(
                tokens, nxt[:, None].astype(tokens.dtype),
                (jnp.int32(0), jnp.int32(s_prompt) + i))
            pos = jnp.int32(s_prompt) + i
            logits, caches = step_fn(params, nxt, caches, pos)
            return (i + jnp.int32(1), tokens, finished, key, logits,
                    caches)

        init = (jnp.int32(0), tokens,
                jnp.zeros((b,), bool), key, last_logits, caches)
        i, tokens, _, key, _, caches = jax.lax.while_loop(cond, body,
                                                          init)
        return tokens, i, key, caches

    # CPU has no donation support (jax warns and ignores) — donate only
    # where it buys the in-place carry reuse
    donate = (1, 2) if jax.default_backend() != "cpu" else ()
    return jax.jit(program, donate_argnums=donate)


def _compiled_decode(model, arr, max_new_tokens, decode_strategy,
                     temperature, top_k, top_p, eos_token_id,
                     last_only):
    """Prefill eagerly once, then hand the whole token loop to the
    cached jitted program.  Exactly ONE host sync (the generated-token
    count, to slice the buffer) per call."""
    kw = {"last_logits_only": True} if last_only else {}
    logits, past = model(Tensor(arr), use_cache=True, **kw)
    params, step_fn = model.build_decode_step()
    last_logits = jnp.asarray(logits._data)[:, -1, :]
    sampling = decode_strategy not in _GREEDY
    key = default_generator.get_state() if sampling \
        else jax.random.PRNGKey(0)

    # preallocate the full [B, S_prompt+max_new] token buffer and the
    # fixed-shape KV caches — donated to the program, so the loop
    # updates them in place on accelerator backends
    b, s_prompt = int(arr.shape[0]), int(arr.shape[1])
    s_total = s_prompt + int(max_new_tokens)
    tokens = jnp.zeros((b, s_total), arr.dtype)
    tokens = jax.lax.dynamic_update_slice(tokens, arr, (0, 0))
    caches = []
    for k, v in past:
        ka, va = jnp.asarray(k._data), jnp.asarray(v._data)
        kc = jnp.zeros((b, s_total) + ka.shape[2:], ka.dtype)
        vc = jnp.zeros((b, s_total) + va.shape[2:], va.dtype)
        caches.append(
            (jax.lax.dynamic_update_slice(kc, ka, (0, 0, 0, 0)),
             jax.lax.dynamic_update_slice(vc, va, (0, 0, 0, 0))))
    caches = tuple(caches)

    programs = model.__dict__.setdefault("_megakernel_programs", {})
    ckey = (tuple(arr.shape), str(arr.dtype), int(max_new_tokens),
            str(decode_strategy), float(temperature or 1.0),
            int(top_k or 0), float(top_p or 1.0),
            None if eos_token_id is None else int(eos_token_id),
            tuple((tuple(k.shape), str(k.dtype)) for k, _ in caches),
            # kernel routing is decided at trace time — a flag flip
            # must build a fresh program, not replay the stale route
            bool(get_flag("use_pallas_fused_decode")),
            bool(get_flag("pallas_interpret")))
    prog = programs.get(ckey)
    if prog is None:
        from ..observability import tracing
        prog = _build_decode_program(
            step_fn, s_prompt=s_prompt,
            max_new=int(max_new_tokens), strategy=decode_strategy,
            temperature=temperature, top_k=top_k, top_p=top_p,
            eos_token_id=eos_token_id)
        programs[ckey] = prog
        # the first call with this signature pays trace + XLA compile —
        # span it (the steady-state path below skips the block)
        with tracing.trace_span(
                "decode_compile",
                attrs={"batch": b, "prompt_len": s_prompt,
                       "max_new_tokens": int(max_new_tokens)}):
            tokens, n_steps, key_out, _ = prog(params, tokens, caches,
                                               last_logits, key)
    else:
        tokens, n_steps, key_out, _ = prog(params, tokens, caches,
                                           last_logits, key)
    n = int(n_steps)                       # the one host sync
    if sampling:
        default_generator.set_state(key_out)
    return tokens[:, :s_prompt + n], n


# ---------------------------------------------------------------------------
# what a model says about its layers, and the page pools that follow
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class AttentionKind:
    """One layer's attention.  ``window`` None: full causal attention,
    whose cache holds every token; else the last ``window`` keys are
    visible and the cache holds a ring of them.  The first
    ``rotary_dim`` dimensions of a head are rotated (0: none) from base
    ``rope_theta``, as pairs ``(i, i + rotary_dim / 2)`` or, with
    ``rope_interleaved``, ``(2i, 2i + 1)``.  ``value_scale`` multiplies
    the value rows before the weighted sum.  ``gate``: the weighted sum
    is multiplied, element by element and before the output projection,
    by ``sigmoid(u wgate)`` of the layer's normed input ``u``."""
    window: Optional[int]
    kv_heads: int
    key_dim: int
    value_dim: int
    rotary_dim: int = 0
    rope_theta: float = 10000.0
    rope_interleaved: bool = False
    sink: bool = False
    value_scale: float = 1.0
    gate: bool = False


@dataclass(frozen=True)
class LinearAttentionKind:
    """One layer's token mixer where it is no softmax attention: gated
    delta-rule linear attention (``ops/gated_delta.py``) of ``heads``
    heads with keys of ``key_dim`` and values of ``value_dim``, behind a
    causal depthwise convolution over the last ``conv_kernel`` positions
    of the q, k and v projections.  What a sequence carries through such
    a layer is a state of ``heads x key_dim x value_dim`` and the
    convolution's last ``conv_kernel - 1`` inputs, whatever its length.
    ``beta_scale`` multiplies the write strength ``sigmoid(.)`` (2: the
    state's transition may have negative eigenvalues)."""
    heads: int
    key_dim: int
    value_dim: int
    conv_kernel: int = 4
    beta_scale: float = 1.0


@dataclass(frozen=True)
class IndexKind:
    """The index of a latent-attention layer: which keys a query row
    attends.  ``heads`` index queries of ``dim`` a row, from the row's
    normed query latent, and ONE index key of ``dim`` a token, a layer
    norm (weight and bias, at ``norm_eps``) of a projection of the
    layer's normed input; the first ``rotary_dim`` dimensions of both
    are rotated.  A row's score for a visible key is ``sum_h w_h
    relu(q_h . k)`` with ``w`` a projection of the row's input times
    ``heads ** -0.5 * dim ** -0.5``, and the row attends the ``top_k``
    keys of the largest scores (every visible key while there are no
    more than that).  The index keys are cached beside the latent."""
    heads: int
    dim: int
    top_k: int
    rotary_dim: int
    rope_interleaved: bool = False
    norm_eps: float = 1e-6


@dataclass(frozen=True)
class LatentAttentionKind:
    """One layer's token mixer where keys and values are a LATENT
    (multi-head latent attention, in its absorbed form): a token's cache
    row is its normed latent of ``kv_rank`` and ONE rotated key of
    ``rope_dim`` shared by all heads, nothing a head.  Queries come
    through a normed latent of ``q_rank``: ``heads`` of ``nope_dim +
    rope_dim``, the last ``rope_dim`` rotated from base ``rope_theta``
    (pairs ``(2i, 2i + 1)`` with ``rope_interleaved``).  A head's
    ``nope_dim`` query is carried into the latent by its ``w_uk``
    (``[nope_dim, kv_rank]``), logits are ``(q~ . c + q_r . k_r) /
    sqrt(nope_dim + rope_dim)``, and the weighted sum of latents leaves
    through the head's ``w_uv`` (``[kv_rank, value_dim]``).  With an
    ``index`` a row attends only the keys its index picks
    (:class:`IndexKind`, ``ops/latent_select.py``)."""
    heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    value_dim: int
    rope_theta: float = 10000.0
    rope_interleaved: bool = False
    index: Optional[IndexKind] = None

    def __post_init__(self):
        ix = self.index
        if ix is not None and (
                ix.rotary_dim != self.rope_dim
                or ix.rope_interleaved != self.rope_interleaved):
            raise ValueError(
                "an index rotates by its layer's own tables: rotary_dim "
                f"{ix.rotary_dim} and rope_interleaved "
                f"{ix.rope_interleaved} differ from the layer's "
                f"{self.rope_dim} and {self.rope_interleaved}")


@dataclass(frozen=True)
class FeedForwardKind:
    """One layer's feed-forward of ``width``: ``gated`` (``act(gate) *
    up``, then down) or a plain two-matrix MLP with biases, ``act``
    being "silu" or "gelu_tanh".  ``held`` None: dense; else gated
    experts behind a router over ``router_width`` of which each row
    takes ``top_k``, and of which this chip holds ``held = (first,
    count)``; their weighted sum is multiplied by ``routed_scale``, and
    a shared gated expert of ``shared_width`` (0: none), which every
    row takes, is added to it unweighted."""
    width: int
    router_width: int = 0
    top_k: int = 0
    held: Optional[Tuple[int, int]] = None
    act: str = "silu"
    gated: bool = True
    shared_width: int = 0
    routed_scale: float = 1.0


@dataclass(frozen=True)
class LayerDescription:
    """A layer mixes tokens by ``attention``, by ``linear_attention`` or
    by ``latent_attention`` (the other two are None), then runs
    ``feed_forward``."""
    attention: Optional[AttentionKind]
    feed_forward: FeedForwardKind
    linear_attention: Optional[LinearAttentionKind] = None
    latent_attention: Optional[LatentAttentionKind] = None

    def __post_init__(self):
        mixers = (self.attention, self.linear_attention,
                  self.latent_attention)
        if sum(m is not None for m in mixers) != 1:
            raise ValueError("a layer has attention, linear attention or "
                             "latent attention, one of the three")


@dataclass(frozen=True)
class ModelDescription:
    """What ``build_ragged_decode_step`` writes a model's step from,
    beside its arrays: every field a fact of the architecture, read
    from the model's config (``config.description()``).

    ``norm`` is "rms_norm" or "layer_norm" (weights and biases), at
    ``norm_eps``, for both norms of a layer and the final one.
    ``learned_positions``: a position table is added to the embedding
    (the rotation, if any, is the layer's: ``AttentionKind``).
    ``tied_head``: the head multiplies by the embedding.  ``precision``
    is the matmul precision a served step runs at: None is jax's
    default, at which a float32 product is ONE bf16 pass on the MXU; a
    name ("high": three passes) is what the step's XLA products run at,
    with the attention kernel's two dots at "highest" (Mosaic takes no
    "high")."""
    layers: Tuple[LayerDescription, ...]
    heads: int
    norm: str = "rms_norm"
    norm_eps: float = 1e-5
    learned_positions: bool = False
    embed_scale: float = 1.0
    tied_head: bool = False
    precision: Optional[str] = None


class LaneState(tuple):
    """A layer whose cache is a fixed-size state a running sequence: the
    shapes of its arrays for ONE sequence (a linear-attention layer: the
    matrix state ``(heads, key_dim, value_dim)`` and the convolution's
    tail ``(conv_kernel - 1, channels)``).  :class:`CacheDescription`
    takes it in a layer's place."""


class LatentPages(tuple):
    """A latent-attention layer's cache: the widths of its paged rows, a
    row a token — ``(latent, index)`` with ``latent`` the pool width of
    the normed latent and the shared rotated key side by side
    (:func:`_pool_width`) and ``index`` that of the index key, or
    ``(latent,)`` for a layer with no index.  No width depends on the
    number of heads.  :class:`CacheDescription` takes it in a layer's
    place."""


class CacheDescription:
    """What a ragged step keeps from step to step, layer by layer — the
    one place the serving engine (and anything else that feeds a step)
    learns its geometry from.  ``build_ragged_decode_step`` hangs it
    on the step it returns (``step.cache``).  A layer's cache is of one
    of four kinds, and ``pools`` holds a tuple of arrays a layer:

    * a **full** layer's pools are ``[kv_heads, num_pages, page_size,
      dim]`` (keys ``key_dim`` wide, values ``value_dim``), shared
      through the scheduler's ``PagePool`` and ``tables``; the last page
      is the sink that padding rows write to;
    * a **window** layer's pools do not grow with the sequence: each
      running sequence owns a **ring** of ``ring_pages()`` pages in
      ``[kv_heads, max_batch * ring + 1, page_size, dim]`` (the last
      page again the sink), position ``p`` lives in ring entry ``(p //
      page_size) % ring``;
    * a **state** layer (:class:`LaneState`) keeps float32 arrays
      ``[max_batch, *shape]``: a fixed-size state a running sequence,
      which does not grow with the sequence, is not paged and is never
      shared.  The step itself zeroes a sequence's state when the
      sequence's first row of the step is at position 0;
    * a **latent** layer (:class:`LatentPages`) keeps one pool a width,
      ``[1, num_pages, page_size, width]``: a token's latent row and,
      beside it, its index key.  They are paged by token exactly as a
      full layer's pools are, through the same ``PagePool``, page ids
      and ``tables``, and hold no axis of heads.

    A running sequence owns one of ``max_batch`` **slots** (the
    scheduler's): its ring is ring ``slot`` and its state row is row
    ``slot``.  A step learns the slots from ``tables`` (:meth:`tables`):
    behind a row's full-layer page ids ride its ring's page ids and,
    where a layer keeps state, its slot."""

    def __init__(self, layers):
        # per layer: (kv_heads, key_dim, value_dim, window or None), a
        # LaneState or a LatentPages
        self.layers = tuple(
            layer if isinstance(layer, (LaneState, LatentPages)) else
            (int(layer[0]), int(layer[1]), int(layer[2]),
             None if layer[3] is None else int(layer[3]))
            for layer in layers)
        paged = [layer for layer in self.layers
                 if not isinstance(layer, (LaneState, LatentPages))]
        windows = {w for _, _, _, w in paged if w is not None}
        if len(windows) > 1:
            raise ValueError(f"window layers of unlike windows {windows} "
                             "would need rings of unlike sizes")
        self.window = windows.pop() if windows else None
        self.n_window = sum(1 for layer in paged if layer[3] is not None)
        self.n_full = len(paged) - self.n_window
        self.n_state = sum(isinstance(layer, LaneState)
                           for layer in self.layers)
        self.n_latent = len(self.layers) - len(paged) - self.n_state

    def ring_pages(self, page_size: int, max_chunk: int) -> int:
        """Pages of one lane's ring: the window, the widest chunk a step
        writes before it attends, and one page of slack for a chunk that
        starts inside a page.  0 for a model with no window layer."""
        if self.window is None:
            return 0
        return -(-(self.window + int(max_chunk)) // int(page_size)) + 1

    def pool_shapes(self, num_pages: int, page_size: int, max_batch: int,
                    ring_pages: int = 0):
        out = []
        for layer in self.layers:
            if isinstance(layer, LaneState):
                out.append(tuple((int(max_batch), *shape)
                                 for shape in layer))
                continue
            if isinstance(layer, LatentPages):
                out.append(tuple((1, int(num_pages), int(page_size), width)
                                 for width in layer))
                continue
            nkv, dk, dv, window = layer
            pages = int(num_pages) if window is None \
                else int(max_batch) * int(ring_pages) + 1
            out.append(((nkv, pages, int(page_size), dk),
                        (nkv, pages, int(page_size), dv)))
        return tuple(out)

    def new_pools(self, num_pages: int, page_size: int, dtype,
                  max_batch: int, ring_pages: int = 0):
        """Fresh zeroed arrays, a tuple a layer: ``(k_pages, v_pages)``
        or a latent layer's pools in ``dtype``, or a state layer's
        arrays in float32."""
        return tuple(
            tuple(jnp.zeros(shape, jnp.float32
                            if isinstance(layer, LaneState) else dtype)
                  for shape in shapes)
            for layer, shapes in zip(
                self.layers, self.pool_shapes(num_pages, page_size,
                                              max_batch, ring_pages)))

    def tables(self, full_tables, slots, ring_pages: int):
        """``tables`` as a step takes them: each row's full-layer page
        ids, then the page ids of ring ``slots[row]`` (ring ``r`` owns
        window-pool pages ``r * ring_pages ..``), then, where a layer
        keeps state, ``slots[row]`` itself.  With neither the
        full-layer tables themselves."""
        out = [np.asarray(full_tables, "int32")]
        slots = np.asarray(slots, "int32")[:, None]
        if ring_pages:
            out.append(slots * np.int32(ring_pages)
                       + np.arange(ring_pages, dtype="int32")[None, :])
        if self.n_state:
            out.append(slots)
        return out[0] if len(out) == 1 else np.concatenate(out, axis=1)

    def split_slots(self, tables):
        """:meth:`tables` less its last column inside a step:
        ``(tables, slots)``; ``slots`` None where no layer keeps
        state."""
        if not self.n_state:
            return tables, None
        return tables[:, :-1], tables[:, -1].astype(jnp.int32)

    @staticmethod
    def split_tables(tables, window_pool_pages: int):
        """The ring page ids undone inside a step: ``(full_tables,
        ring)`` from ``tables [B, ppseq + ring_pages]`` (the slots
        already off, :meth:`split_slots`) and the pages of a window
        layer's pool (``B * ring_pages + 1``, :meth:`pool_shapes`)."""
        ring_pages = (int(window_pool_pages) - 1) // tables.shape[0]
        cut = tables.shape[1] - ring_pages
        return tables[:, :cut], tables[:, cut:]


# ---------------------------------------------------------------------------
# the ragged batched decode step (continuous-batching serving engine)
# ---------------------------------------------------------------------------

# The parts of a serve step, as a device trace names them: every
# operation of a ``serve_step_q<Q>`` program runs under exactly one of
# these ``jax.named_scope`` names (the step body below opens all but
# ``sample``, which is the engine's: everything its program does around
# the step).  A part's name is a component of an operation's ``tf_op``
# path; ``benchmark/layer_metrics/step_parts.py`` turns the paths into
# each part's share of the device's busy seconds and imports this tuple.
# A new mixer opens a part of its own and adds its name here
STEP_PARTS = ("embed", "attention", "linear_attention", "latent_attention",
              "feed_forward", "experts", "lm_head", "sample")
# The train step's parts (``models/gpt.py``, ``jit/train_step.py``,
# ``optimizer/optimizer.py``).  ``backward`` is no part of the model: the
# tape's operations run under it, those of a part as
# ``backward/<part>/transpose(jvp())/...`` (``core/dispatch.py`` re-opens
# a node's scopes around its vjp) and a recomputed forward's as
# ``backward/<part>/jvp()/...``, and what the tape adds itself under
# ``backward`` alone
TRAIN_STEP_PARTS = ("embed", "attention", "mlp", "lm_head", "backward",
                    "optimizer", "cast_params")


def _mixer_part(d: "LayerDescription") -> str:
    """The part of ``STEP_PARTS`` a layer's token mixer runs under."""
    if d.latent_attention is not None:
        return "latent_attention"
    return "linear_attention" if d.attention is None else "attention"


# XLA's TPU scatter takes index rows as they come at ~73 ns a row and,
# once they are sorted, at ~0.4 ms flat plus ~9 ns a row; by itself it
# sorts only from 65,536 rows.  The step's write sorts from this many,
# where sorting starts to pay (v5e, f32 rows of 128: PERF.md section 6,
# PR 26).  An index row is a (token row, kv head): a packed Q=1024 step
# of eight lanes has 1,032 x 8 = 8,256 at eight kv heads and sorts; at
# four, or at Q=512, it has ~4,100 and pays ~0.3 ms a pool unsorted
# where the sort would cost 0.4
_SORT_ROWS_FROM = 8192


@jax.named_scope("kv_write")
def _scatter_pages(pages, vals, page_ids, slots):
    """Write one step's new k/v rows into the page pools.  ``pages
    [nkv, P, ps, hd]``; ``vals [rows, nkv, hd]``; ``page_ids/slots
    [rows]``, one a token row of the step (any leading shape of that
    many elements; padding rows target the engine's sink page, never
    read back; several may name one row, and any of them wins).  A row
    whose page or slot lies outside the pool is dropped.

    The pool is written as ``[nkv * P * ps, hd]``: one scattered row
    per (token, kv head), ``hd`` the only window axis.  That view is a
    bitcast of the row-major layout the ragged kernel's Mosaic call
    takes, so with the pools donated the scatter runs in place and the
    compiled step holds no copy and no temporary of a pool's size.
    (Scattered over axes 1 and 2 with ``nkv`` as a window, as
    ``pages.at[:, page_ids, slots].set``, XLA's layout assignment puts
    the scattered axes first and the step re-lays every pool twice.)
    ``tests/test_smoke_chip.py`` guards that on a described v5e and
    ``chip_smoke.py`` on the chip; ``tests/test_serving.py`` holds the
    values to the old expression's.  A wide chunk's rows are sorted
    first (``_SORT_ROWS_FROM``; the sort is the same for every pool of
    a step, so XLA keeps one).  Index constants are pinned int32
    (``jax_enable_x64`` is on)."""
    nkv, n_pages, ps, hd = pages.shape
    p = page_ids.reshape(-1).astype(jnp.int32)
    s = slots.reshape(-1).astype(jnp.int32)
    n_rows = nkv * n_pages * ps
    ok = (p >= 0) & (p < n_pages) & (s >= 0) & (s < ps)
    row = jnp.where(ok, p * jnp.int32(ps) + s, jnp.int32(n_rows))
    head0 = jnp.arange(nkv, dtype=jnp.int32) * jnp.int32(n_pages * ps)
    idx = (row[:, None] + head0[None, :]).reshape(-1)       # [BQ * nkv]
    upd = vals.reshape(-1, hd).astype(pages.dtype)
    in_order = idx.shape[0] >= _SORT_ROWS_FROM
    if in_order:
        idx, perm = jax.lax.sort(
            (idx, jnp.arange(idx.shape[0], dtype=jnp.int32)), num_keys=1)
        upd = upd.at[perm].get(mode="promise_in_bounds",
                               unique_indices=True)
    flat = pages.reshape(n_rows, hd).at[idx].set(
        upd, mode="drop", indices_are_sorted=in_order)
    return flat.reshape(pages.shape)


class _StepRows:
    """Where a ragged step's token rows sit.  Every per-token operation
    of a step (embedding, norms, projections, rotary, the k/v write,
    the feed-forward) runs over ``n_rows`` flat rows, of which sequence
    ``b`` owns rows ``offs[b] .. offs[b] + q_lens[b] - 1``; the rest
    carry no token (token 0 at position 0, written to the sink page).
    The attention kernel takes the rows as they are, with ``offs``,
    ``q_lens`` and ``q_width`` (``ragged_paged_attention_rows``).

    ``offs`` is nondecreasing, and two layouts use it: the packed one,
    ``offs = cumsum(q_lens) - q_lens`` in as many rows as the step's
    program has, and the ``[B, Q]`` one, ``offs[b] = b * Q`` in ``B * Q``
    rows."""

    def __init__(self, n_rows: int, offs, q_lens, q_width: int):
        from ..ops.pallas.ragged_paged_attention import row_lanes
        self.n, self.q_width = int(n_rows), int(q_width)
        self.offs = offs.astype(jnp.int32)
        self.q_lens = q_lens.astype(jnp.int32)
        # a row's sequence and its index in that sequence's chunk, as
        # the attention launch reads them (an empty sequence owns no
        # row: the test of ``valid`` leaves it out)
        self.lane, self.at = row_lanes(self.offs, self.n)
        self.valid = self.at < self.q_lens[self.lane]

    def of_lanes(self, per_lane):
        """``per_lane [B, ...]`` -> ``[rows, ...]``: each row its
        sequence's entry."""
        return per_lane[self.lane]

    def last_rows(self, h):
        """Each sequence's LAST valid row of ``h [rows, H]`` (an empty
        sequence clamps to a row inside ``h``) — the lm-head matmul
        then runs on [B, H] instead of every token."""
        idx = jnp.clip(self.offs + self.q_lens - jnp.int32(1),
                       jnp.int32(0), jnp.int32(self.n - 1))
        return h[idx]


def _two_ways_in(rows_body):
    """The step object of a ``rows_body(p, tok [rows],
    pos [rows], pools, page_ids [rows], slots [rows], kv_lens, q_lens,
    tables, rows: _StepRows)``: the documented ``[B, Q]`` call, which
    runs the body at ``B * Q`` rows with sequence ``b`` starting at row
    ``b * Q``, and ``step.packed(..., q_width)``, the serving engine's,
    whose ``tok/pos/page_ids/slots`` are already flat rows with sequence
    ``b`` behind sequence ``b - 1`` (``offs = cumsum(q_lens) -
    q_lens``), as ``Scheduler.plan_step`` lays them out, and whose
    attention width ``q_width`` is static."""

    def step(p, tok, pos, pools, page_ids, slots, kv_lens, q_lens, tables):
        b, qw = tok.shape
        with jax.named_scope("embed"):
            offs = jnp.arange(b, dtype=jnp.int32) * jnp.int32(qw)
            rows = _StepRows(b * qw, offs, q_lens, qw)
        return rows_body(p, tok.reshape(-1), pos.reshape(-1), pools,
                         page_ids.reshape(-1), slots.reshape(-1), kv_lens,
                         q_lens, tables, rows)

    def packed(p, tok, pos, pools, page_ids, slots, kv_lens, q_lens,
               tables, q_width: int):
        with jax.named_scope("embed"):
            ql = q_lens.astype(jnp.int32)
            offs = jnp.cumsum(ql, dtype=jnp.int32) - ql
            rows = _StepRows(tok.shape[0], offs, q_lens, q_width)
        return rows_body(p, tok, pos, pools, page_ids, slots, kv_lens,
                         q_lens, tables, rows)

    step.packed = packed
    return step


def build_ragged_decode_step(model):
    """Cache-aware BATCHED decode step over paged KV pools — the
    continuous-batching serving engine's per-iteration body (ragged
    carries: per-sequence lengths and page tables instead of the
    compiled loop's one dense ``pos``).

    Returns ``(params, step)`` with::

        step(params, tok [B, Q], pos [B, Q], pools, page_ids [B, Q],
             slots [B, Q], kv_lens [B], q_lens [B], tables [B, ppseq])
          -> (last_logits [B, V], pools')

    where ``pools`` is a per-layer tuple of ``(k_pages, v_pages)``
    ``[nkv, P, ps, hd]`` pools shared by every sequence (or of a layer's
    per-sequence state), as ``step.cache`` (a
    :class:`CacheDescription`) describes them.  Each
    sequence contributes ``q_lens[b]`` new tokens this step (a prefill
    chunk or one decode token, padded to the batch-wide ``Q``); their
    k/v land at ``(page_ids, slots)`` BEFORE the one-launch ragged
    paged attention, so the new tokens attend to themselves causally —
    the same order as ``attend_cache_append``.

    Inside, a step's tokens are flat rows (:class:`_StepRows`): the
    matmuls, norms, rotary and k/v write run over the rows, and the
    attention kernel's grid is their live tiles.  The call above runs the
    body at ``B * Q`` rows; the serving engine calls the same body through
    ``step.packed(params, tok [rows], pos [rows], pools, page_ids
    [rows], slots [rows], kv_lens, q_lens, tables, q_width)`` with the
    sequences' tokens packed one behind the other, so that a step with
    one wide chunk beside decoding lanes multiplies about as many rows
    as it feeds tokens (:func:`_two_ways_in`).  Numerics mirror the
    model's own forward (same norm references, fp32 attention
    statistics), so engine output is token-for-token the eager
    ``generate`` output.

    **What a model provides** (docs/serving_a_new_architecture.md):

    * ``model.config.description()``, a :class:`ModelDescription`: the
      norm, where positions come from, the head, the step's precision,
      and layer by layer the token mixer — attention (full or windowed,
      key-value heads and key and value widths of the layer's own, a
      rotation over the first ``rotary_dim`` dimensions from the layer's
      own base, a sink or none, an output gate or none) or gated
      delta-rule linear attention (:class:`LinearAttentionKind`) — and
      the feed-forward (an MLP, gated, or routed experts of which this
      chip holds some, with a shared expert beside them or none);
    * ``model.described_params()``, the tree of arrays the one body
      below reads — ``embed``, ``positions`` (a learned table),
      ``rope`` (``{_rope_key(theta): (cos, sin)}``), ``norm_w`` /
      ``norm_b``, ``lm_w`` (an untied head) and ``layers``, each with
      ``ln1_w`` / ``ln1_b``, the projection as :func:`_qkv_rows` takes
      it, ``wgate`` (an attention layer's output gate), ``wo`` / ``bo``,
      ``sink``, ``ln2_w`` / ``ln2_b`` and ``w1 b1 w2 b2`` (MLP), ``wg wu
      wd`` (gated; a tuple an expert behind ``router_w`` / ``router_b``,
      with ``shared_wg shared_wu shared_wd`` a shared expert); a
      linear-attention layer has ``wq wk wv``, the convolutions' taps
      ``conv_q conv_k conv_v [kernel, channels]``, the decay's ``wf_down
      wf_up dt_bias a_log``, ``wbeta``, the gate's ``wgate_down
      wgate_up``, ``out_norm_w`` and ``wo``
      (:func:`_linear_attention_rows`); a latent-attention layer has
      ``wq_a q_norm_w wq_b wkv_a kv_norm_w w_uk w_uv wo`` and, with an
      index, ``wi_q wi_k wi_k_norm_w wi_k_norm_b wi_w``
      (:func:`_latent_attention_rows`).  Whether a projection has a bias
      is read from the tree: an entry that is absent or None adds
      nothing.

    Window layers write and read a ring (:class:`CacheDescription`):
    their page ids and slots are derived here from ``pos`` and the ring
    page ids behind the full-layer pages in ``tables``;
    ``page_ids``/``slots`` serve the full layers alone.  A
    linear-attention layer's entry of ``pools`` is its state and its
    convolution's tail, a row a slot, and the last column of ``tables``
    says which slot a sequence owns.  A latent-attention layer's entry is
    its latent pool and its index-key pool (:class:`LatentPages`),
    written at ``(page_ids, slots)`` and read through the full layers'
    page ids in ``tables``.

    With an expert layer (``step.routing_counts``) ``step`` returns
    ``(logits, pools', counts)``: ``counts i32[3]`` are the rows routed
    to held experts summed over layers, the fullest held expert's rows
    (max over layers) and the held experts with at least one row summed
    over layers."""
    from ..ops.pallas import fused_decode as _fd
    from ..ops.pallas.ragged_paged_attention import \
        ragged_paged_attention_rows
    from ..ops.routed_experts import held_experts_swiglu, \
        sigmoid_topk_route

    params = model.described_params()
    md = model.config.description()
    descs = md.layers
    nh, eps = md.heads, md.norm_eps
    gated_norm = {"rms_norm": True, "layer_norm": False}[md.norm]
    if any(d.feed_forward.gated != gated_norm for d in descs):
        raise NotImplementedError(
            "fused_decode.norm_mlp pairs an RMS norm with a gated "
            "feed-forward and a layer norm with an MLP, and no other way")
    cache = CacheDescription(
        [(d.attention.kv_heads, _pool_width(d.attention.key_dim),
          _pool_width(d.attention.value_dim), d.attention.window)
         if d.attention is not None
         else _latent_pages(d.latent_attention)
         if d.latent_attention is not None
         else _lane_state(d.linear_attention)
         for d in descs])
    window_layer = next((i for i, d in enumerate(descs)
                         if d.attention is not None
                         and d.attention.window is not None), None)
    has_experts = any(d.feed_forward.held is not None for d in descs)
    kernel_precision = None if md.precision is None \
        else jax.lax.Precision.HIGHEST
    i32 = jnp.int32

    def norm(x, w, b):
        if md.norm == "layer_norm":
            return _fd.reference_layer_norm(x, w, b, eps)
        return _fd.reference_rms_norm(x, w, eps)

    def rotated(x, att, cos, sin):
        rot = att.rotary_dim
        turn = lambda a: _fd.reference_rope_rows(
            a, cos, sin, neox=not att.rope_interleaved)
        if rot == x.shape[-1]:
            return turn(x)
        return jnp.concatenate([turn(x[..., :rot]), x[..., rot:]], axis=-1)

    def body(p, tok, pos, pools, page_ids, slots, kv_lens, q_lens,
             tables, rows):
        # every operation runs under one of STEP_PARTS, for the device
        # trace: the parts are siblings, none opens inside another
        with jax.named_scope("embed"):
            x = jnp.take(p["embed"], tok, axis=0)         # [rows, H]
            if md.embed_scale != 1.0:
                x = x * md.embed_scale
            valid = rows.valid
            pos = pos.astype(i32)
            if md.learned_positions:
                x = x + jnp.take(p["positions"], pos, axis=0)
            tables, state_slots = cache.split_slots(tables)
            full_tables = tables
            if window_layer is not None:
                wpool = pools[window_layer][0]
                ps = wpool.shape[2]
                full_tables, ring = cache.split_tables(tables,
                                                       wpool.shape[1])
                entry = (pos // i32(ps)) % i32(ring.shape[1])
                ring_ids = jnp.where(
                    valid, jnp.take_along_axis(
                        rows.of_lanes(ring.astype(i32)), entry[:, None],
                        axis=1)[:, 0],
                    i32(wpool.shape[1] - 1))              # padding: sink
                ring_slots = jnp.where(valid, pos % i32(ps), i32(0))
            rope = {theta: (jnp.take(cos, pos, axis=0)[:, None, :],
                            jnp.take(sin, pos, axis=0)[:, None, :])
                    for theta, (cos, sin) in p.get("rope", {}).items()}
        counts = [i32(0), i32(0), i32(0)]
        new_pools = []
        for i, (d, lp) in enumerate(zip(descs, p["layers"])):
            att, ff = d.attention, d.feed_forward
            # the mixer's part holds the layer's first norm and the
            # residual's add too
            with jax.named_scope(_mixer_part(d)):
                u = norm(x, lp["ln1_w"], lp.get("ln1_b"))
                if d.latent_attention is not None:
                    lat = d.latent_attention
                    out, kept = _latent_attention_rows(
                        lp, u, lat, pools[i], rows, page_ids, slots,
                        kv_lens, full_tables, pos,
                        rope[_rope_key(lat.rope_theta)], eps)
                elif att is None:
                    out, kept = _linear_attention_rows(
                        lp, u, d.linear_attention, pools[i], rows,
                        state_slots, pos, eps)
                else:
                    dk, dv = att.key_dim, att.value_dim
                    with jax.named_scope("qkv_proj"):
                        qp, kp, vp = _qkv_rows(lp, u, nh, att)
                        if att.value_scale != 1.0:
                            vp = vp * att.value_scale
                        if att.rotary_dim:
                            cos, sin = rope[_rope_key(att.rope_theta)]
                            qp = rotated(qp, att, cos, sin)
                            kp = rotated(kp, att, cos, sin)
                    windowed = att.window is not None
                    ids, sl, tb = (ring_ids, ring_slots, ring) if windowed \
                        else (page_ids, slots, full_tables)
                    # rows as wide as the pools (_pool_width): the zeros
                    # add nothing to q.k, and the scale stays the head's
                    # own
                    qp, kp, vp = (_pad_last(a, pool.shape[-1])
                                  for a, pool in ((qp, pools[i][0]),
                                                  (kp, pools[i][0]),
                                                  (vp, pools[i][1])))
                    kpg = _scatter_pages(pools[i][0], kp, ids, sl)
                    vpg = _scatter_pages(pools[i][1], vp, ids, sl)
                    kept = (kpg, vpg)
                    ctx = ragged_paged_attention_rows(
                        qp, kpg, vpg, kv_lens, q_lens, rows.offs, tb,
                        rows.q_width, scale=1.0 / math.sqrt(dk),
                        window=att.window,
                        sinks=lp["sink"] if att.sink else None,
                        precision=kernel_precision)
                    ctx = ctx[..., :dv].reshape(-1, nh * dv)
                    if att.gate:
                        with jax.named_scope("attention_gate"):
                            ctx = ctx * jax.nn.sigmoid(
                                jnp.matmul(u, lp["wgate"]))
                    with jax.named_scope("attn_out"):
                        out = jnp.matmul(ctx, lp["wo"])
                        if lp.get("bo") is not None:
                            out = out + lp["bo"]
                new_pools.append(kept)
                x = x + out
            with jax.named_scope("feed_forward" if ff.held is None
                                 else "experts"):
                if ff.held is not None:
                    h2 = norm(x, lp["ln2_w"], lp.get("ln2_b"))
                    with jax.named_scope("router"):
                        picks, weights = sigmoid_topk_route(
                            h2, lp["router_w"], lp["router_b"], ff.top_k)
                    y, n_rows = held_experts_swiglu(
                        h2, picks, weights, valid, lp["wg"], lp["wu"],
                        lp["wd"], ff.held[0])
                    if ff.routed_scale != 1.0:
                        y = y * ff.routed_scale
                    if ff.shared_width:
                        with jax.named_scope("shared_expert"):
                            y = y + jnp.matmul(
                                jax.nn.silu(
                                    jnp.matmul(h2, lp["shared_wg"]))
                                * jnp.matmul(h2, lp["shared_wu"]),
                                lp["shared_wd"])
                    counts = [counts[0] + jnp.sum(n_rows, dtype=i32),
                              jnp.maximum(counts[1], jnp.max(n_rows)),
                              counts[2] + jnp.sum(n_rows > 0, dtype=i32)]
                elif ff.gated:
                    y = _fd.norm_mlp(x, kind=md.norm, norm_w=lp["ln2_w"],
                                     w_gate=lp["wg"], w1=lp["wu"],
                                     w2=lp["wd"], eps=eps, act=ff.act)
                else:
                    y = _fd.norm_mlp(x, kind=md.norm, norm_w=lp["ln2_w"],
                                     norm_b=lp["ln2_b"], w1=lp["w1"],
                                     b1=lp["b1"], w2=lp["w2"], b2=lp["b2"],
                                     eps=eps, act=ff.act)
                x = x + y
        with jax.named_scope("lm_head"):
            h = norm(x, p["norm_w"], p.get("norm_b"))
            w = p["embed"] if md.tied_head else p["lm_w"]
            logits = jnp.matmul(rows.last_rows(h), jnp.swapaxes(w, -1, -2))
        if has_experts:
            with jax.named_scope("experts"):
                counts = jnp.stack(counts)
            return logits, tuple(new_pools), counts
        return logits, tuple(new_pools)

    def rows_body(*args):
        if md.precision is None:
            return body(*args)
        with jax.default_matmul_precision(md.precision):
            return body(*args)

    step = _two_ways_in(rows_body)
    step.cache = cache
    step.routing_counts = has_experts
    return params, step


def _qkv_rows(lp, h, nh: int, att: AttentionKind):
    """One layer's query, key and value rows ``[rows, heads, dim]`` from
    its normed input ``h``, in whichever form the model's tree holds the
    projection: one ``wqkv`` (queries, then keys, then values, side by
    side) or ``wq`` / ``wk`` / ``wv``, each with a bias (``bq`` ...) or
    none — so that each family's program multiplies the matrices it
    holds, and none is joined or split on the device to fit."""
    nkv, dk, dv = att.kv_heads, att.key_dim, att.value_dim
    if "wqkv" in lp:
        qkv = jnp.matmul(h, lp["wqkv"])
        q, k, v = (qkv[:, :nh * dk], qkv[:, nh * dk:(nh + nkv) * dk],
                   qkv[:, (nh + nkv) * dk:])
    else:
        q, k, v = (jnp.matmul(h, lp[w]) if lp.get(b) is None
                   else jnp.matmul(h, lp[w]) + lp[b]
                   for w, b in (("wq", "bq"), ("wk", "bk"), ("wv", "bv")))
    return (q.reshape(-1, nh, dk), k.reshape(-1, nkv, dk),
            v.reshape(-1, nkv, dv))


def _lane_state(kind: LinearAttentionKind) -> LaneState:
    """What a sequence carries through a linear-attention layer: the
    matrix state and the last ``conv_kernel - 1`` rows of the q, k and v
    projections side by side."""
    return LaneState((
        (kind.heads, kind.key_dim, kind.value_dim),
        (kind.conv_kernel - 1,
         kind.heads * (2 * kind.key_dim + kind.value_dim))))


def _latent_pages(kind: LatentAttentionKind) -> LatentPages:
    """What a token leaves in a latent-attention layer's cache: its
    latent and the shared rotated key in one row (:func:`_pool_width`:
    512 + 64 = 576 is padded to 640, a ninth more pool memory and latent
    bytes read, for a pool the compiler lays out row-major as it does
    the key-value pools), and its index key where the layer has an
    index."""
    row = _pool_width(kind.kv_rank + kind.rope_dim)
    if kind.index is None:
        return LatentPages((row,))
    return LatentPages((row, _pool_width(kind.index.dim)))


def _latent_attention_rows(lp, u, kind: LatentAttentionKind, kept, rows,
                           page_ids, slots, kv_lens, tables, pos, rope,
                           eps: float):
    """One latent-attention layer's mixer over a step's packed rows:
    ``(out [rows, H], pools')`` from the layer's normed input ``u`` and
    the layer's pools ``kept`` (:class:`LatentPages`).

    The step's rows are written first — a token's normed latent and its
    rotated shared key as ONE row of the latent pool, its index key as
    one row of the index pool — so that a row attends itself; then
    every row attends, in the latent (the absorbed form: a head's query
    is carried through ``w_uk`` before the logits and the weighted sum
    of latents through ``w_uv`` after, both inside
    ``ops/latent_select.py``), the keys of its own sequence that its
    index picks.  ``rope = (cos,
    sin)`` are the step's rows of the layer's rotary tables.  The caller
    holds the ``latent_attention`` scope open."""
    from ..ops import latent_select as ls
    from ..ops.pallas import fused_decode as _fd
    n, nh = rows.n, kind.heads
    rank, nope, rd = kind.kv_rank, kind.nope_dim, kind.rope_dim
    cos, sin = rope                                       # [rows, 1, rd]
    turn = lambda a, interleaved: _fd.reference_rope_rows(
        a, cos, sin, neox=not interleaved)
    highest = jax.lax.Precision.HIGHEST
    cq = _fd.reference_rms_norm(jnp.matmul(u, lp["wq_a"]),
                                lp["q_norm_w"], eps)
    q = jnp.matmul(cq, lp["wq_b"]).reshape(n, nh, nope + rd)
    q_r = turn(q[..., nope:], kind.rope_interleaved)
    kv = jnp.matmul(u, lp["wkv_a"])
    latent = _fd.reference_rms_norm(kv[:, :rank], lp["kv_norm_w"], eps)
    k_r = turn(kv[:, None, rank:], kind.rope_interleaved)[:, 0]
    width = kept[0].shape[-1]
    pools = [_scatter_pages(
        kept[0], _pad_last(jnp.concatenate([latent, k_r], axis=-1),
                           width)[:, None, :], page_ids, slots)]
    index = None
    if kind.index is not None:
        ix = kind.index
        with jax.named_scope("index_select"):
            # a selection is discontinuous: what it is taken from
            # runs at "highest", as the router's scores do
            rot = lambda a: jnp.concatenate(
                [turn(a[..., :ix.rotary_dim], ix.rope_interleaved),
                 a[..., ix.rotary_dim:]], axis=-1)
            q_i = rot(jnp.matmul(cq, lp["wi_q"], precision=highest)
                      .reshape(n, ix.heads, ix.dim))
            k_i = rot(_fd.reference_layer_norm(
                jnp.matmul(u, lp["wi_k"], precision=highest),
                lp["wi_k_norm_w"], lp["wi_k_norm_b"],
                ix.norm_eps)[:, None, :])
            w_i = jnp.matmul(u, lp["wi_w"], precision=highest) \
                * (ix.heads ** -0.5 * ix.dim ** -0.5)
        pools.append(_scatter_pages(
            kept[1], _pad_last(k_i, kept[1].shape[-1]), page_ids,
            slots))
        index = (_pad_last(q_i, kept[1].shape[-1]), w_i, pools[1],
                 ix.top_k)
    ctx = ls.attend_selected(
        q[..., :nope], q_r, lp["w_uk"], lp["w_uv"], pools[0], index,
        tables, kv_lens, pos, rows.offs, rows.q_lens, rows.lane,
        rows.q_width, scale=1.0 / math.sqrt(nope + rd)
    ).reshape(n, nh * kind.value_dim)
    out = jnp.matmul(ctx, lp["wo"])
    return out, tuple(pools)


# rows a block of the chunked gated delta rule (ops/gated_delta.py)
_SCAN_BLOCK = 64


def _linear_attention_rows(lp, u, kind: LinearAttentionKind, kept, rows,
                           slots, pos, eps: float):
    """One linear-attention layer's mixer over a step's packed rows:
    ``(out [rows, H], (state', tail'))`` from the layer's normed input
    ``u`` and what the layer kept (``kept = (state [slots, heads, dk,
    dv], tail [slots, K - 1, heads (2 dk + dv)])``, both float32).

    A sequence that feeds one row takes the recurrence itself
    (``linear_attn_step``), one that feeds a chunk the chunked form
    (``linear_attn_scan``; a decode-only program has no such sequence
    and holds no scan).  A sequence whose first row of the step is at
    position 0 starts from zeros; one with no row, and every row that
    carries no token, moves nothing.  The caller holds the
    ``linear_attention`` scope open."""
    from ..ops import gated_delta as gd
    from ..ops.pallas.fused_decode import reference_rms_norm
    nh, dk, dv = kind.heads, kind.key_dim, kind.value_dim
    state, tail = kept
    f32 = jnp.float32
    offs, q_lens, n = rows.offs, rows.q_lens, rows.n
    reset = (q_lens > 0) & (pos[jnp.minimum(offs, jnp.int32(n - 1))] == 0)
    # q, k and v: projection, short convolution, silu
    mixed, new_tail, col = [], [], 0
    for name, width in (("q", nh * dk), ("k", nh * dk), ("v", nh * dv)):
        a, t = gd.short_conv_rows(
            jnp.matmul(u, lp["w" + name]).astype(f32),
            lp["conv_" + name].astype(f32),
            tail[:, :, col:col + width], offs, q_lens, slots, reset,
            rows.at)
        mixed.append(jax.nn.silu(a).reshape(n, nh, -1))
        new_tail.append(t)
        col += width
    q, k, v = mixed
    unit = lambda a: a * jax.lax.rsqrt(
        jnp.sum(a * a, axis=-1, keepdims=True) + f32(1e-6))
    q, k = unit(q) * f32(dk ** -0.5), unit(k)
    g = -jnp.exp(lp["a_log"].astype(f32))[None, :, None] \
        * jax.nn.softplus(
            jnp.matmul(jnp.matmul(u, lp["wf_down"]), lp["wf_up"])
            .astype(f32) + lp["dt_bias"]).reshape(n, nh, dk)
    beta = f32(kind.beta_scale) * jax.nn.sigmoid(
        jnp.matmul(u.astype(f32), lp["wbeta"].astype(f32),
                   precision=jax.lax.Precision.HIGHEST))
    beta = jnp.where(rows.valid[:, None], beta, 0.0)
    with jax.named_scope("linear_attn_step"):
        o_slot, state = gd.gated_delta_step(
            state, q, k, v, g, beta, offs, q_lens, slots, reset)
    o = o_slot[slots[rows.lane]]
    if rows.q_width > 1:
        with jax.named_scope("linear_attn_scan"):
            o_chunk, state = gd.gated_delta_chunks(
                state, q, k, v, g, beta, offs, q_lens, slots,
                rows.lane, rows.at, min(_SCAN_BLOCK, rows.q_width))
        o = jnp.where((q_lens[rows.lane] > 1)[:, None, None],
                      o_chunk, o)
    # a norm over each head's values, then the output gate
    o = reference_rms_norm(o, lp["out_norm_w"].astype(f32), eps)
    gate = jnp.matmul(jnp.matmul(u, lp["wgate_down"]),
                      lp["wgate_up"])
    o = o.reshape(n, nh * dv).astype(u.dtype) * jax.nn.sigmoid(gate)
    out = jnp.matmul(o, lp["wo"])
    return out, (state, jnp.concatenate(new_tail, axis=-1))


def _pool_width(dim: int) -> int:
    """The last axis of a page pool for rows of ``dim``.  A head wider
    than the 128-lane tile and not a whole number of tiles (keys of
    192) is padded up to one: the compiler's own
    layout for ``f32[nkv, P, ps, 192]`` is not the row-major one the
    Mosaic call takes, and every step would re-lay each such pool twice
    (two whole-pool copies a pool a step at Q=1 and Q=1024, sandbox AOT
    for a v5e, PR 27; none at 256).  The price is pool memory and key
    bytes read: a third more at 192.  Narrower heads stay as they are
    (heads of 96 copy too; ROADMAP S15)."""
    return dim if dim <= 128 else -(-dim // 128) * 128


def _pad_last(a, width: int):
    if a.shape[-1] == width:
        return a
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, width - a.shape[-1])])


def _rope_key(theta: float) -> str:
    """The name of a rotary base's tables in ``params["rope"]``."""
    return f"{float(theta):g}"


def build_fused_window_step(model, max_window: int):
    """Persistent-program serving step: fuse up to ``max_window``
    ragged batch iterations into ONE compiled ``lax.while_loop``
    dispatch (the serving-engine analogue of ``decode_loop``).

    Returns ``(params, window)`` with::

        window(params, tok [B], pools, kv_lens [B], live [B] bool,
               tables [B, ppseq], temps [B], eos_ids [B], budgets [B],
               key, n_steps)
          -> (packed [B, max_window + 2] int32, pools', key')

    ``kv_lens`` are the PRE-append lengths (tokens already in KV);
    ``tok`` is each live lane's pending last-sampled token.  Every
    iteration re-derives the page-append cursors on device
    (``append_positions``), runs the ragged step at
    Q=1, and samples EXACTLY like the engine's single-step program
    (one ``jax.random.split`` per iteration, argmax/categorical
    blend on temperature) so the RNG stream and the sampled tokens
    match the one-dispatch-per-step path token for token.

    The loop carries EOS/budget state on device and exits as soon as
    ANY lane finishes (EOS sampled, or its remaining ``budgets`` hit) —
    lane layout therefore never shifts mid-window and the host-side
    scheduler sees exactly the states the single-step engine would
    have seen at a boundary.  ``n_steps`` is a TRACED scalar (≤ the
    static ``max_window``), so one compiled program serves every
    window length the scheduler budgets.

    The single host read per window is the ``packed`` array: columns
    ``[:max_window]`` hold the per-lane sampled tokens (column ``j``
    = iteration ``j``; only the first ``steps`` columns are live),
    column ``[max_window]`` the finished mask, and column
    ``[max_window + 1]`` the number of iterations actually run,
    broadcast to every lane."""
    from ..ops.pallas.ragged_paged_attention import append_positions

    params, step = build_ragged_decode_step(model)
    if step.cache.window is not None or step.cache.n_state \
            or step.cache.n_latent or step.routing_counts:
        raise TypeError(
            f"build_fused_window_step does not take "
            f"{type(model).__name__}: the fused window derives one "
            f"append cursor a lane from tables and reads its pools as "
            f"key-value pairs, so it can fill no ring of a window layer, "
            f"reach no lane's state and take no latent layer's pools "
            f"(step.cache.window = {step.cache.window}, "
            f"step.cache.n_state = {step.cache.n_state}, "
            f"step.cache.n_latent = {step.cache.n_latent}), and carries "
            f"no routing counts (step.routing_counts = "
            f"{step.routing_counts}); serve such a model with "
            f"FLAGS_serving_fused_steps=1")

    def fused_window(params, tok, pools, kv_lens, live, tables, temps,
                     eos_ids, budgets, key, n_steps):
        b = tok.shape[0]
        page_size = pools[0][0].shape[2]
        sink = pools[0][0].shape[1] - 1
        buf0 = jnp.zeros((b, max_window), jnp.int32)
        q_lens = live.astype(jnp.int32)                    # lane layout
        t32 = temps.astype(jnp.float32)                    # is static
        n_steps = jnp.asarray(n_steps, jnp.int32)          # per window

        def cond(carry):
            i, _, _, _, finished, _, _, _ = carry
            return jnp.logical_and(i < n_steps,
                                   jnp.logical_not(jnp.any(finished)))

        def body(carry):
            i, tok, pools, kv, finished, key, buf, ngen = carry
            page_ids, slots = append_positions(kv, tables, live,
                                               page_size, sink)
            kv_next = kv + q_lens
            logits, pools = step(params, tok[:, None], kv[:, None],
                                 pools, page_ids[:, None],
                                 slots[:, None], kv_next, q_lens,
                                 tables)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            key, sub = jax.random.split(key)
            scaled = logits.astype(jnp.float32) \
                / jnp.maximum(t32, jnp.float32(1e-6))[:, None]
            sampled = jax.random.categorical(sub, scaled, axis=-1) \
                .astype(jnp.int32)
            nxt = jnp.where(t32 > jnp.float32(0.0), sampled, greedy)
            buf = jax.lax.dynamic_update_slice(
                buf, nxt[:, None], (jnp.int32(0), i))
            ngen = ngen + q_lens
            finished = finished | (live & ((nxt == eos_ids)
                                           | (ngen >= budgets)))
            tok = jnp.where(live, nxt, jnp.int32(0))
            return (i + jnp.int32(1), tok, pools, kv_next, finished,
                    key, buf, ngen)

        init = (jnp.int32(0), tok.astype(jnp.int32), pools,
                kv_lens.astype(jnp.int32), jnp.zeros((b,), bool), key,
                buf0, jnp.zeros((b,), jnp.int32))
        i, _, pools, _, finished, key, buf, _ = jax.lax.while_loop(
            cond, body, init)
        packed = jnp.concatenate(
            [buf, finished.astype(jnp.int32)[:, None],
             jnp.broadcast_to(i, (b,))[:, None]], axis=1)
        return packed, pools, key

    return params, fused_window


def decode_loop(model, input_ids, **kwargs):
    """The compiled mega-kernel decode entry: ``generate`` with the
    whole token loop inside one jitted ``lax.while_loop`` (fused
    rope+QKV / attention+cache-append / norm+MLP kernels, on-device
    sampling + EOS, donated KV carries — zero host transfers per
    token).  Unsupported requests (beam search, paged cache, models
    without ``build_decode_step``) fall back to the eager loop; the
    ``decode_loop`` observability event records which engine ran."""
    return generate(model, input_ids, _megakernel=True, **kwargs)


def generate(model, input_ids, max_new_tokens: int = 20,
             max_length: Optional[int] = None,
             decode_strategy: str = "greedy_search",
             temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
             eos_token_id: Optional[int] = None,
             num_beams: int = 1, length_penalty: float = 1.0,
             pad_token_id: Optional[int] = None,
             use_cache: bool = True, use_paged_cache: bool = False,
             _megakernel: Optional[bool] = None,
             **unused):
    """Returns a Tensor [B, S_prompt + n_generated] of token ids."""
    import inspect

    from ..observability import events
    ids = input_ids if isinstance(input_ids, Tensor) else Tensor(
        np.asarray(input_ids))
    if max_length is not None:
        max_new_tokens = max(int(max_length) - ids.shape[1], 0)
    # bound by the model's position table: rope/position embeddings have
    # nothing past max_position_embeddings
    max_pos = getattr(getattr(model, "config", None),
                      "max_position_embeddings", None)
    if max_pos is not None:
        room = int(max_pos) - ids.shape[1]
        if room <= 0:
            raise ValueError(
                f"prompt length {ids.shape[1]} already reaches "
                f"max_position_embeddings {max_pos}")
        max_new_tokens = min(int(max_new_tokens), room)
    # cache support is a SIGNATURE property — probing with try/except
    # TypeError would swallow genuine bugs inside the cache path
    fwd = model.forward if hasattr(model, "forward") else model
    params = inspect.signature(fwd).parameters
    supports_cache = use_cache and "use_cache" in params
    last_only = supports_cache and "last_logits_only" in params
    mk_requested = bool(get_flag("megakernel_decode")) \
        if _megakernel is None else bool(_megakernel)
    mk_reason = _megakernel_fallback_reason(
        model, decode_strategy, num_beams, use_paged_cache,
        supports_cache, max_new_tokens) if mk_requested else None
    was_training = getattr(model, "training", False)
    if hasattr(model, "eval"):
        model.eval()
    try:
        arr = jnp.asarray(ids._data)
        if mk_requested and mk_reason is None:
            from ..observability import tracing
            # the whole compiled generation (prefill + token loop) is
            # one step span; the decode_compile child + the decode_loop
            # event land inside it
            with tracing.trace_span(
                    "decode_loop",
                    attrs={"model": type(model).__name__,
                           "strategy": decode_strategy}):
                out, n_gen = _compiled_decode(
                    model, arr, max_new_tokens, decode_strategy,
                    temperature, top_k, top_p, eos_token_id, last_only)
                events.emit("decode_loop", model=type(model).__name__,
                            batch=int(arr.shape[0]),
                            prompt_len=int(arr.shape[1]),
                            max_new_tokens=int(max_new_tokens),
                            generated=n_gen, strategy=decode_strategy,
                            compiled=True)
            return Tensor(out)
        if mk_requested:
            events.emit("decode_loop", model=type(model).__name__,
                        batch=int(arr.shape[0]),
                        prompt_len=int(arr.shape[1]),
                        max_new_tokens=int(max_new_tokens),
                        strategy=decode_strategy, compiled=False,
                        fallback=mk_reason)
        # num_beams == 1 beam_search degenerates to greedy (the HF /
        # PaddleNLP convention)
        if num_beams > 1:
            if decode_strategy not in ("beam_search", "greedy_search",
                                       "greedy"):
                raise NotImplementedError(
                    f"num_beams={num_beams} with decode_strategy="
                    f"{decode_strategy!r}: beam-sampling is not "
                    "implemented — temperature/top_k/top_p would be "
                    "silently ignored")
            if use_paged_cache:
                raise ValueError(
                    "beam search reorders cache rows every step; the "
                    "page pool does not support row permutation — use "
                    "the dense cache (use_paged_cache=False)")
            return _beam_search(model, arr, max_new_tokens,
                                num_beams, length_penalty,
                                eos_token_id, supports_cache, last_only,
                                pad_token_id=pad_token_id)
        finished = jnp.zeros((arr.shape[0],), bool)
        past = None
        if supports_cache:
            kw = {"last_logits_only": True} if last_only else {}
            logits, past = model(Tensor(arr), use_cache=True, **kw)
            if use_paged_cache:
                if not getattr(model, "supports_paged_cache", False):
                    raise ValueError(
                        f"{type(model).__name__} does not support "
                        "use_paged_cache=True (its attention has no "
                        "PagedLayerView dispatch)")
                past = _to_paged(past, arr.shape[0],
                                 arr.shape[1] + int(max_new_tokens))
        else:
            logits = model(Tensor(arr))
        # eager loop over a PREALLOCATED buffer: one dynamic_update_slice
        # per token instead of an O(n²) concat chain, and the
        # finished.all() host sync hoisted to every K tokens
        s_prompt = int(arr.shape[1])
        max_new = int(max_new_tokens)
        buf = jnp.zeros((arr.shape[0], s_prompt + max_new), arr.dtype)
        buf = jax.lax.dynamic_update_slice(buf, arr, (0, 0))
        cur = s_prompt
        sync_every = max(int(get_flag("eager_finished_sync_every")
                             or 1), 1)
        stopped = False
        for it in range(max_new):
            nxt = _sample(jnp.asarray(logits._data)[:, -1, :],
                          decode_strategy, temperature, top_k, top_p)
            if eos_token_id is not None:
                nxt = jnp.where(finished, eos_token_id, nxt)
                finished = finished | (nxt == eos_token_id)
            buf = jax.lax.dynamic_update_slice(
                buf, nxt[:, None].astype(buf.dtype), (0, cur))
            cur += 1
            if eos_token_id is not None and \
                    (it == max_new - 1
                     or it % sync_every == sync_every - 1) and \
                    bool(finished.all()):
                stopped = True
                break
            if it < max_new - 1:
                if supports_cache:
                    logits, past = model(Tensor(buf[:, cur - 1:cur]),
                                         past=past, use_cache=True)
                else:
                    logits = model(Tensor(buf[:, :cur]))
        if stopped:
            # reconstruct the exact per-token stop column: every row
            # finished at its FIRST generated eos, and the original
            # loop broke right after the last row finished — columns
            # past that point are all-eos padding the hoisted sync let
            # through
            gen = np.asarray(buf[:, s_prompt:cur])
            first_eos = (gen == eos_token_id).argmax(axis=1)
            cur = s_prompt + int(first_eos.max()) + 1
        arr = buf[:, :cur]
    finally:
        if was_training and hasattr(model, "train"):
            model.train()
    return Tensor(arr)
