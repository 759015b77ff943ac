"""SEP (Ulysses) and CP (ring attention) — the user-reachable wiring.

ref: python/paddle/distributed/fleet/meta_parallel/segment_parallel.py
(the sep-axis segment-parallel model wrapper) and the RingFlashAttention
paths in auto_parallel/incubate (SURVEY.md §2.3 SEP/CP rows).

TPU-native design: the hybrid mesh carries dedicated ``sep`` and ``cp``
axes (fleet ``hybrid_configs={"sep_degree": n}`` / ``{"cp_degree": n}``).
Attention entering ``paddle.nn.functional.scaled_dot_product_attention``
is routed here when either degree > 1: a *partial-manual*
``jax.shard_map`` (manual over just the sep/cp axis, every other mesh
axis left to GSPMD) shards the sequence dim and runs

- **sep** → :func:`paddle_tpu.ops.ulysses.ulysses_attention` — all-to-all
  trades sharded sequence for sharded heads, full-sequence flash locally,
  inverse all-to-all back (DeepSpeed-Ulysses; rides the ICI all-to-all);
- **cp**  → :func:`paddle_tpu.ops.ring_attention.ring_attention_bhsd` —
  KV chunks rotate around the ICI ring via ``ppermute`` with
  online-softmax merges (differentiable: the ring backward reuses the
  Pallas flash backward with the global lse).

Both are exact (a parallelisation, not an approximation), so when shapes
or settings fall outside kernel constraints we warn once and fall back to
the plain (GSPMD-sharded) attention — numerics stay identical, only the
sequence-parallel layout is lost.
"""
from __future__ import annotations

import math
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ....core.dispatch import call_op
from ....flags import get_flag
from ....ops.ring_attention import ring_attention_bhsd
from ....ops.ulysses import ulysses_attention
from ....ops.flash_attention import DEFAULT_BLOCK_Q
from ..base.topology import get_hybrid_communicate_group

__all__ = ["active_seq_parallel_axis", "segment_parallel_attention",
           "sep_attention", "cp_ring_attention"]

_warned: set = set()


def _warn_once(key: str, msg: str):
    if key not in _warned:
        _warned.add(key)
        warnings.warn(msg, RuntimeWarning, stacklevel=3)


def active_seq_parallel_axis() -> Optional[Tuple[str, int]]:
    """The live long-context axis from the fleet topology:
    ``("sep", n)`` or ``("cp", n)``, or None when neither degree > 1."""
    hcg = get_hybrid_communicate_group()
    if hcg is None:
        return None
    sep = hcg.get_sep_parallel_world_size()
    if sep > 1:
        return ("sep", sep)
    cp = getattr(hcg, "get_context_parallel_world_size", lambda: 1)()
    if cp > 1:
        return ("cp", cp)
    return None


def _interpret() -> bool:
    return bool(get_flag("pallas_interpret"))


def sep_attention(query, key, value, is_causal: bool = True, scale=None):
    """Ulysses attention over the ``sep`` mesh axis.

    query/key/value: Tensors [B, S, H, D] (global view; S becomes
    sep-sharded inside).  Heads stay mp-shardable — the shard_map is
    manual over sep only.
    """
    hcg = get_hybrid_communicate_group()
    mesh = hcg.mesh
    interpret = _interpret()

    def f(q, k, v):
        d = q.shape[-1]
        sc = scale if scale is not None else 1.0 / math.sqrt(d)

        def body(ql, kl, vl):
            return ulysses_attention(ql, kl, vl, "sep", sc, is_causal,
                                     interpret)

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=P(None, "sep", None, None),
            out_specs=P(None, "sep", None, None),
            axis_names={"sep"}, check_vma=False)(q, k, v)

    return call_op(f, (query, key, value), {}, op_name="sep_attention")


def cp_ring_attention(query, key, value, is_causal: bool = True,
                      scale=None):
    """Ring (context-parallel) attention over the ``cp`` mesh axis.

    query/key/value: Tensors [B, S, H, D].  Inside the manual region the
    [B, S_local, H, D] block is flattened to the ring kernel's
    [H*B, S_local, D] layout — heads-major, so an mp sharding on H stays
    contiguous on the merged dim.
    """
    hcg = get_hybrid_communicate_group()
    mesh = hcg.mesh
    interpret = _interpret()

    def f(q, k, v):
        b, s, h, d = q.shape
        sc = scale if scale is not None else 1.0 / math.sqrt(d)

        def body(ql, kl, vl):
            s_loc = ql.shape[1]
            qt = jnp.transpose(ql, (2, 0, 1, 3)).reshape(h * b, s_loc, d)
            kt = jnp.transpose(kl, (2, 0, 1, 3)).reshape(h * b, s_loc, d)
            vt = jnp.transpose(vl, (2, 0, 1, 3)).reshape(h * b, s_loc, d)
            out = ring_attention_bhsd(qt, kt, vt, "cp", sc, is_causal,
                                      interpret)
            return jnp.transpose(out.reshape(h, b, s_loc, d), (1, 2, 0, 3))

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=P(None, "cp", None, None),
            out_specs=P(None, "cp", None, None),
            axis_names={"cp"}, check_vma=False)(q, k, v)

    return call_op(f, (query, key, value), {}, op_name="cp_ring_attention")


def segment_parallel_attention(query, key, value, attn_mask, dropout_p,
                               is_causal, training):
    """Route one sdpa call through the live sep/cp axis, or return None
    (caller falls back to plain attention) with a one-time warning when
    the call can't be parallelised this way."""
    axis = active_seq_parallel_axis()
    if axis is None:
        return None
    name, n = axis
    if attn_mask is not None:
        _warn_once(f"{name}-mask",
                   f"{name}_degree={n} is set but this attention call "
                   "passes an attn_mask; falling back to plain attention "
                   "(sequence stays unsharded) for masked calls")
        return None
    if dropout_p > 0.0 and training:
        _warn_once(f"{name}-dropout",
                   f"{name}_degree={n} is set but attention dropout > 0; "
                   "the flash-based sequence-parallel kernels don't carry "
                   "dropout — falling back to plain attention. Set "
                   "attention dropout to 0 to enable sep/cp")
        return None
    B, S, H, D = query.shape
    Sk = key.shape[1]
    if S != Sk:
        _warn_once(f"{name}-crossattn",
                   f"{name}_degree={n}: q/k sequence lengths differ "
                   f"({S} vs {Sk}); sequence parallelism applies to "
                   "self-attention — falling back")
        return None
    if S % n:
        _warn_once(f"{name}-seqdiv",
                   f"{name}_degree={n} does not divide sequence length "
                   f"{S}; falling back to plain attention")
        return None
    if D % 8:
        _warn_once(f"{name}-headdim",
                   f"{name}_degree={n}: head_dim {D} not a multiple of 8 "
                   "(flash kernel lane constraint); falling back")
        return None
    s_loc = S // n
    if name == "sep":
        if H % n:
            _warn_once("sep-heads",
                       f"sep_degree={n} does not divide num_heads {H}; "
                       "Ulysses needs heads % sep == 0 — falling back")
            return None
        bq = min(DEFAULT_BLOCK_Q, S)
        if S % bq:
            _warn_once("sep-block",
                       f"sep: global sequence {S} not aligned to the "
                       f"flash block ({bq}); falling back")
            return None
        return sep_attention(query, key, value, is_causal)
    # cp: per-rank chunks must align with the flash block gate
    bq = min(DEFAULT_BLOCK_Q, s_loc)
    if s_loc % bq:
        _warn_once("cp-block",
                   f"cp: per-rank sequence {s_loc} not aligned to the "
                   f"flash block ({bq}); falling back")
        return None
    return cp_ring_attention(query, key, value, is_causal)
