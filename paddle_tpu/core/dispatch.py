"""Op dispatch + tape autograd engine.

TPU-native re-design of the reference's generated dygraph forward functions
and eager backward engine (ref: paddle/fluid/eager/backward.cc —
egr::Backward topo-sort over GradNodes; generated dygraph_functions.cc).

Every framework op is a *pure jnp function*.  ``call_op`` executes it
eagerly; when autograd is needed it captures the op's VJP with ``jax.vjp``
and records a GradNode.  Because jnp works identically on tracers, the same
tape runs under ``jax.jit`` tracing — which is how the jitted/`to_static`
path reuses the whole eager stack unchanged.

``run_backward`` is the engine: Kahn topo-sort from the root node,
cotangent accumulation per (node, out_index), leaf ``.grad`` accumulation,
tensor hooks — mirroring egr::Backward's ready-queue design.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
# jax.named_scope pushes onto this stack; jax has no public getter for it
from jax._src import source_info_util as _name_stack

from .. import dtype as dtypes
from ..flags import get_flag
from .autograd_state import grad_enabled, _state
from .tensor import Tensor


def _is_float_dtype(d) -> bool:
    return (jnp.issubdtype(d, jnp.floating)
            or jnp.issubdtype(d, jnp.complexfloating))


# installed by paddle_tpu.amp: (op_name, arrays) -> arrays with AMP casts
_amp_hook = None

# installed by paddle_tpu.static: records every executed op into the
# program being captured (fn, kwargs, in_tensors, out_tensors, multi, name)
_op_observer = None

# installed by paddle_tpu.profiler while recording: (op_name, t0, t1)
_prof_op_hook = None

# op-stream introspection (paddle_tpu.analysis.graphcheck): hooks called
# with an OpEvent for every dispatched op.  A list (not a single slot)
# so nested observers compose; kept empty on the hot path — the only
# steady-state cost is one falsy check per call_op.
_op_stream_hooks: List[Callable] = []


class OpEvent:
    """Lightweight per-op record for stream analysis: name + input/
    output (shape, dtype) pairs.  Values are never retained."""

    __slots__ = ("op_name", "in_avals", "out_avals")

    def __init__(self, op_name, in_avals, out_avals):
        self.op_name = op_name
        self.in_avals = in_avals      # [(shape, dtype_str), ...]
        self.out_avals = out_avals

    def __repr__(self):
        return (f"OpEvent({self.op_name!r}, in={self.in_avals}, "
                f"out={self.out_avals})")


def _aval(v):
    try:
        return (tuple(v.shape), str(v.dtype))
    except Exception:
        return ((), type(v).__name__)


def _emit_op_event(op_name, arrays, outs, multi):
    if not _op_stream_hooks:
        return
    vals = list(outs) if multi and isinstance(outs, (tuple, list)) \
        else [outs]
    ev = OpEvent(op_name or "op", [_aval(a) for a in arrays],
                 [_aval(o) for o in vals])
    for h in list(_op_stream_hooks):
        h(ev)


import contextlib


@contextlib.contextmanager
def observe_op_stream(hook: Callable):
    """Register ``hook(OpEvent)`` for every op dispatched inside the
    block (the graphcheck analyzer's entry point; composes with the
    static-capture observer and nests)."""
    _op_stream_hooks.append(hook)
    try:
        yield hook
    finally:
        try:
            _op_stream_hooks.remove(hook)
        except ValueError:
            pass


class GradNode:
    """One recorded op on the tape."""

    __slots__ = ("vjp_fn", "inputs", "out_avals", "multi_out", "op_name",
                 "scopes", "__weakref__")

    def __init__(self, vjp_fn, inputs: Sequence[Tensor],
                 out_avals: List[Tuple[tuple, Any]], multi_out: bool,
                 op_name: str = ""):
        self.vjp_fn = vjp_fn
        self.inputs = list(inputs)
        self.out_avals = out_avals  # [(shape, dtype), ...]
        self.multi_out = multi_out
        self.op_name = op_name
        # the ``jax.named_scope`` names the op was recorded under:
        # ``run_backward`` re-opens them around the op's vjp, so that a
        # device trace names a backward operation by its forward's part
        # (``backward/attention/transpose(jvp())/dot_general``) — a
        # ``jax.vjp`` taken op by op keeps no scope of its caller's
        self.scopes = _name_stack.current_name_stack()

    def release(self):
        self.vjp_fn = None
        self.inputs = []


def _wrap_outputs(outs, multi, node: Optional[GradNode], stop_gradient: bool):
    if not multi:
        t = Tensor(outs, stop_gradient=stop_gradient)
        if node is not None:
            t._bind_node(node, 0)
        return t
    tensors = []
    for i, o in enumerate(outs):
        t = Tensor(o, stop_gradient=stop_gradient)
        if node is not None:
            t._bind_node(node, i)
        tensors.append(t)
    return tuple(tensors)


def _check_numerics(op_name, outs):
    level = get_flag("check_nan_inf_level")
    vals = outs if isinstance(outs, (tuple, list)) else [outs]
    for v in vals:
        if isinstance(v, jax.core.Tracer) or not _is_float_dtype(v.dtype):
            continue
        bad = bool(jnp.any(~jnp.isfinite(v)))
        if bad:
            msg = f"nan/inf detected in output of op '{op_name}'"
            if level == 0:
                raise FloatingPointError(msg)
            print(f"[check_nan_inf] {msg}")


def call_op(fn: Callable, tensor_args: Sequence[Tensor],
            kwargs: Optional[dict] = None, multi_out: bool = False,
            op_name: str = "", nondiff_out: Optional[Sequence[int]] = None):
    """Execute op ``fn(*arrays, **kwargs)`` over the values of
    ``tensor_args``, recording autograd if enabled.

    - ``multi_out``: fn returns a tuple of arrays.
    - ``nondiff_out``: indices of outputs that are not differentiable
      (e.g. argmax index outputs of a (values, indices) op).
    """
    kwargs = kwargs or {}
    arrays = [t._data for t in tensor_args]
    rec_fn = fn
    if _amp_hook is not None:
        cast = _amp_hook(op_name or getattr(fn, "__name__", ""), arrays)
        if cast is not arrays:   # hook returns the SAME list when off
            pre = [a.dtype for a in arrays]
            arrays = cast
            dts = tuple(a.dtype for a in arrays)
            if list(dts) != pre:
                # the amp decision must survive into recorded programs:
                # a static replay calls the RECORDED fn on raw (uncast)
                # inputs, so bake this call's cast into it
                def rec_fn(*xs, __fn=fn, __dts=dts, **kw):
                    xs = [x.astype(d) if hasattr(x, "astype") else x
                          for x, d in zip(xs, __dts)]
                    return __fn(*xs, **kw)

    needs_grad = (grad_enabled()
                  and any(not t.stop_gradient for t in tensor_args)
                  and any(_is_float_dtype(a.dtype) for a in arrays))

    if _prof_op_hook is not None:
        import time as _time
        _t0 = _time.perf_counter()
        try:
            return _call_op_inner(fn, arrays, kwargs, tensor_args, multi_out,
                                  op_name, needs_grad, rec_fn)
        finally:
            _prof_op_hook(op_name or getattr(fn, "__name__", "op"), _t0,
                          _time.perf_counter())
    return _call_op_inner(fn, arrays, kwargs, tensor_args, multi_out,
                          op_name, needs_grad, rec_fn)


def _call_op_inner(fn, arrays, kwargs, tensor_args, multi_out, op_name,
                   needs_grad, rec_fn=None):
    rec_fn = rec_fn or fn
    if not needs_grad:
        outs = fn(*arrays, **kwargs)
        if get_flag("check_nan_inf"):
            _check_numerics(op_name or getattr(fn, "__name__", "op"), outs)
        if get_flag("benchmark"):
            _sync(outs)
        wrapped = _wrap_outputs(outs, multi_out, None, True)
        if _op_observer is not None:
            _op_observer(rec_fn, kwargs, tensor_args,
                         list(wrapped) if multi_out else [wrapped],
                         multi_out, op_name)
        if _op_stream_hooks:
            _emit_op_event(op_name or getattr(fn, "__name__", "op"),
                           arrays, outs, multi_out)
        return wrapped

    f = lambda *xs: fn(*xs, **kwargs)
    outs, vjp_fn = jax.vjp(f, *arrays)
    out_list = list(outs) if multi_out else [outs]
    out_avals = [(tuple(o.shape), o.dtype) for o in out_list]
    node = GradNode(vjp_fn, tensor_args, out_avals, multi_out,
                    op_name or getattr(fn, "__name__", "op"))
    if get_flag("check_nan_inf"):
        _check_numerics(node.op_name, outs)
    if get_flag("benchmark"):
        _sync(outs)
    wrapped = _wrap_outputs(outs, multi_out, node, False)
    if _op_observer is not None:
        _op_observer(rec_fn, kwargs, tensor_args,
                     list(wrapped) if multi_out else [wrapped],
                     multi_out, op_name)
    if _op_stream_hooks:
        _emit_op_event(node.op_name, arrays, outs, multi_out)
    return wrapped


def _sync(outs):
    vals = outs if isinstance(outs, (tuple, list)) else [outs]
    for v in vals:
        if not isinstance(v, jax.core.Tracer):
            try:
                v.block_until_ready()
            except AttributeError:
                pass


def call_op_custom_vjp(fwd_fn: Callable, bwd_fn: Callable,
                       tensor_args: Sequence[Tensor], kwargs=None,
                       multi_out: bool = False, op_name: str = ""):
    """Record an op with a hand-written backward rule.

    ``fwd_fn(*arrays, **kwargs) -> (outs, residuals)``;
    ``bwd_fn(residuals, out_cotangents) -> tuple of input cotangents``
    (one per tensor arg, None allowed).  Used by PyLayer and fused kernels
    whose backward should not be jax.vjp of the forward (e.g. recompute,
    pallas flash attention).
    """
    kwargs = kwargs or {}
    arrays = [t._data for t in tensor_args]
    needs_grad = grad_enabled() and any(not t.stop_gradient for t in tensor_args)
    if _prof_op_hook is not None:
        import time as _time
        _t0 = _time.perf_counter()
        outs, residuals = fwd_fn(*arrays, **kwargs)
        _prof_op_hook(op_name or getattr(fwd_fn, "__name__", "op"), _t0,
                      _time.perf_counter())
    else:
        outs, residuals = fwd_fn(*arrays, **kwargs)
    if multi_out is None:  # infer: a tuple of arrays means multiple outputs
        multi_out = isinstance(outs, tuple)
    if not needs_grad:
        wrapped = _wrap_outputs(outs, multi_out, None, True)
        _observe_custom_vjp(fwd_fn, bwd_fn, kwargs, tensor_args, wrapped,
                            multi_out, op_name)
        if _op_stream_hooks:
            _emit_op_event(op_name or getattr(fwd_fn, "__name__", "op"),
                           arrays, outs, multi_out)
        return wrapped

    n_in = len(arrays)

    def vjp_fn(cots):
        got = bwd_fn(residuals, cots)
        if not isinstance(got, (tuple, list)):
            got = (got,)
        got = list(got) + [None] * (n_in - len(got))
        return tuple(
            jnp.zeros_like(arrays[i]) if g is None else g
            for i, g in enumerate(got))

    out_list = list(outs) if multi_out else [outs]
    out_avals = [(tuple(o.shape), o.dtype) for o in out_list]
    node = GradNode(vjp_fn, tensor_args, out_avals, multi_out, op_name)
    wrapped = _wrap_outputs(outs, multi_out, node, False)
    _observe_custom_vjp(fwd_fn, bwd_fn, kwargs, tensor_args, wrapped,
                        multi_out, op_name)
    if _op_stream_hooks:
        _emit_op_event(op_name or getattr(fwd_fn, "__name__", "op"),
                       arrays, outs, multi_out)
    return wrapped


def _observe_custom_vjp(fwd_fn, bwd_fn, kwargs, tensor_args, wrapped,
                        multi_out, op_name):
    """Make custom-vjp ops visible to program capture (static Program /
    SOT-lite): record a pure replay fn that carries the SAME hand-written
    backward via jax.custom_vjp, so replayed programs differentiate the
    op exactly like the eager tape does."""
    if _op_observer is None:
        return
    kw = dict(kwargs)
    n_in = len(tensor_args)

    @jax.custom_vjp
    def replay(*xs):
        return fwd_fn(*xs, **kw)[0]

    def replay_fwd(*xs):
        o, r = fwd_fn(*xs, **kw)
        return o, (r, xs)

    def replay_bwd(res, cots):
        r, xs = res
        got = bwd_fn(r, cots)
        if not isinstance(got, (tuple, list)):
            got = (got,)
        got = list(got) + [None] * (n_in - len(got))
        return tuple(jnp.zeros_like(x) if g is None else g
                     for g, x in zip(got, xs))

    replay.defvjp(replay_fwd, replay_bwd)
    _op_observer(replay, {}, tensor_args,
                 list(wrapped) if multi_out else [wrapped], multi_out,
                 op_name)


# ---------------------------------------------------------------------------
# Backward engine
# ---------------------------------------------------------------------------

def _edge_eligible(t: Tensor) -> bool:
    """An input edge carries gradient iff the tensor wants grad and is
    float/complex.  Counting and propagation must use the SAME predicate or
    dependency counts drift and gradients get silently dropped."""
    return (not t.stop_gradient) and _is_float_dtype(t._data.dtype)


def run_backward(root: Tensor, grad_tensor=None, retain_graph: bool = False,
                 leaf_filter=None):
    if root.stop_gradient:
        raise RuntimeError("backward() on a tensor with stop_gradient=True")
    if grad_tensor is None:
        cot = jnp.ones_like(root._data)
    else:
        cot = grad_tensor._data if isinstance(grad_tensor, Tensor) else jnp.asarray(grad_tensor)

    node = root._grad_node
    if node is None:
        if leaf_filter is None or id(root) in leaf_filter:
            _accumulate_leaf(root, cot)
        return

    # pass root's own hooks/retained grad
    cot = _apply_hooks(root, cot)
    if root._retain_grads:
        _accumulate_leaf(root, cot, force=True)

    # 1. dependency counting (number of consumer edges reachable from root)
    deps: Dict[GradNode, int] = {}
    visited = set()
    stack = [node]
    while stack:
        n = stack.pop()
        if id(n) in visited:
            continue
        visited.add(id(n))
        for t in n.inputs:
            pn = t._grad_node
            if pn is not None and _edge_eligible(t):
                deps[id(pn)] = deps.get(id(pn), 0) + 1
                stack.append(pn)

    # 2. ready-queue propagation
    pending: Dict[int, List[Optional[Any]]] = {id(node): [None] * len(node.out_avals)}
    pending[id(node)][root._out_index] = cot
    node_by_id = {id(node): node}
    ready = [node]
    released = []
    open_now = _name_stack.current_name_stack()
    while ready:
        n = ready.pop()
        cots = pending.pop(id(n))
        full = []
        for i, (shape, dt) in enumerate(n.out_avals):
            c = cots[i]
            if c is None:
                c = jnp.zeros(shape, dt)
            elif c.dtype != dt and _is_float_dtype(dt):
                # mixed-precision tape (amp auto_cast): cotangent follows
                # the consumer's compute dtype; cast back to this node's
                # output dtype for the vjp call
                c = c.astype(dt)
            full.append(c)
        if n.vjp_fn is None:
            raise RuntimeError(
                "Trying to backward through the graph a second time "
                "(set retain_graph=True if needed)")
        # the node's own scopes, less those it shares with this call's
        # (a recompute replays, and differentiates, under ``backward``)
        own = n.scopes[len(open_now):] \
            if n.scopes[:len(open_now)] == open_now else n.scopes
        with _name_stack.set_name_stack(open_now + own):
            in_cots = n.vjp_fn(tuple(full) if n.multi_out else full[0])
        if not retain_graph:
            released.append(n)
        for t, c in zip(n.inputs, in_cots):
            if not _edge_eligible(t):
                continue
            has_cot = not (c is None or (hasattr(c, "dtype")
                                         and c.dtype == jax.dtypes.float0))
            pn = t._grad_node
            if has_cot:
                c = _apply_hooks(t, c)
            if pn is None:
                if has_cot and (leaf_filter is None or id(t) in leaf_filter):
                    _accumulate_leaf(t, c)
            else:
                if has_cot and t._retain_grads:
                    _accumulate_leaf(t, c, force=True)
                key = id(pn)
                node_by_id[key] = pn
                if has_cot:
                    slot = pending.setdefault(key, [None] * len(pn.out_avals))
                    idx = t._out_index
                    slot[idx] = c if slot[idx] is None else slot[idx] + c
                else:
                    pending.setdefault(key, [None] * len(pn.out_avals))
                # the edge is consumed either way — counts must stay in sync
                deps[key] -= 1
                if deps[key] == 0:
                    ready.append(pn)
    for n in released:
        n.release()


def _apply_hooks(t: Tensor, cot):
    for h in t._hooks:
        out = h(Tensor(cot))
        if out is not None:
            cot = out._data if isinstance(out, Tensor) else out
    return cot


def _accumulate_leaf(t: Tensor, cot, force: bool = False):
    if t.stop_gradient and not force:
        return
    cot = jnp.asarray(cot)
    if cot.dtype != t._data.dtype and _is_float_dtype(t._data.dtype):
        cot = cot.astype(t._data.dtype)
    if t._grad is None:
        t._grad = Tensor(cot)
    else:
        t._grad = Tensor(t._grad._data + cot)


# ---------------------------------------------------------------------------
# functional grad (used by paddle.grad and the jit functionalizer)
# ---------------------------------------------------------------------------

def grad(outputs, inputs, grad_outputs=None, retain_graph=None,
         create_graph=False, only_inputs=True, allow_unused=False,
         no_grad_vars=None):
    """paddle.grad — compute grads of outputs w.r.t. inputs without
    touching ``.grad`` slots.  Implemented by running the tape backward
    into a side accumulation dict."""
    outs = outputs if isinstance(outputs, (list, tuple)) else [outputs]
    ins = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    gouts = grad_outputs if isinstance(grad_outputs, (list, tuple)) else (
        [grad_outputs] * len(outs))

    # save/restore .grad on the input tensors, run backward with a leaf
    # filter so only the requested inputs accumulate (paddle.grad must not
    # side-effect other leaves' .grad slots)
    saved = [(t, t._grad, t._retain_grads, t.stop_gradient) for t in ins]
    allowed = {id(t) for t in ins}
    for t in ins:
        t._grad = None
        t._retain_grads = True
    try:
        for o, g in zip(outs, gouts):
            run_backward(o, g,
                         retain_graph=True if retain_graph is None else retain_graph,
                         leaf_filter=allowed)
        results = []
        for t in ins:
            if t._grad is None:
                if not allow_unused:
                    raise RuntimeError(
                        "One of the differentiated tensors appears unused; "
                        "pass allow_unused=True to return None for it")
                results.append(None)
            else:
                results.append(Tensor(t._grad._data))
    finally:
        for t, g, r, sg in saved:
            t._grad, t._retain_grads, t.stop_gradient = g, r, sg
    return results
