"""The SPMD train-step engine ("functionalizer").

This is the TPU-native replacement for BOTH reference executors: the
StandaloneExecutor/InterpreterCore static runtime (ref: paddle/fluid/
framework/new_executor/ — instruction scheduling, stream assignment, GC)
and the fleet hybrid-parallel step orchestration (ref: fleet/meta_parallel/
+ meta_optimizers/).  One mechanism: run the *whole eager machinery* —
Layer.forward, the tape backward, optimizer mutation, RNG draws — under
``jax.jit`` tracing, with model/optimizer state lifted to function inputs
and outputs.  XLA then owns scheduling, memory, fusion and collective
placement, which is the executor's entire job (SURVEY.md §3.2 TPU note).

Parallelism comes from sharding annotations: parameters carry per-dim
specs (set by fleet mp/sharding layers or auto_parallel), the batch is
sharded over the data axes, and GSPMD completes the program — the
reference's completion/partitioner passes, done by the compiler.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core.tensor import Tensor
from ..core.autograd_state import no_grad
from ..distributed.mesh import get_mesh
from ..distributed.shard_utils import param_spec, largest_dim_spec as _largest_dim_spec
from ..nn.layer.layers import Layer
from ..optimizer.lr import LRScheduler
from ..random_state import default_generator


def _dedupe(params: Sequence[Tensor]) -> List[Tensor]:
    seen, out = set(), []
    for p in params:
        if id(p) not in seen:
            seen.add(id(p))
            out.append(p)
    return out


class TrainStep:
    """Compile (model, loss_fn, optimizer) into one jitted SPMD step.

    ``step(*batch)`` returns the loss; parameters/optimizer state/buffers
    are updated in place (arrays swapped, no host transfer).  The batch is
    sharded over the data axes of the active mesh; everything else follows
    parameter annotations + GSPMD propagation.
    """

    def __init__(self, model: Layer, loss_fn: Optional[Callable] = None,
                 optimizer=None, scaler=None, mesh: Optional[Mesh] = None,
                 batch_spec: Optional[Sequence] = None,
                 step_fn: Optional[Callable] = None, donate: bool = True):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = getattr(optimizer, "_inner_opt", optimizer)
        self.scaler = scaler
        self.step_fn = step_fn
        self.mesh = mesh if mesh is not None else get_mesh()
        self._batch_spec = batch_spec
        self._donate = donate

        self.params = _dedupe([p for p in model.parameters()])
        self.buffers = _dedupe([b for b in model.buffers()])
        self._jitted = None
        self._jit_cache: Dict[Any, Any] = {}

    # -- optimizer state plumbing ---------------------------------------
    def _opt_state(self):
        o = self.optimizer
        if o is None:
            return {"acc": {}, "master": {}}
        return {"acc": {n: dict(s) for n, s in o._accumulators.items()},
                "master": dict(o._master_weights)}

    def _install_opt_state(self, st):
        o = self.optimizer
        if o is None:
            return
        o._accumulators = defaultdict(dict,
                                      {n: dict(v) for n, v in st["acc"].items()})
        o._master_weights = dict(st["master"])

    # -- sharding ---------------------------------------------------------
    def _named_sharding(self, spec) -> Any:
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def _param_sharding(self, p: Tensor):
        spec = param_spec(p)
        if spec is not None:
            return self._named_sharding(spec)
        return self._named_sharding(())

    def _data_axes(self) -> Tuple[str, ...]:
        axes = []
        for a in ("dp", "sharding"):
            if self.mesh is not None and self.mesh.shape.get(a, 1) > 1:
                axes.append(a)
        return tuple(axes)

    def _state_shardings(self, opt_state):
        if self.mesh is None:
            return None
        p_sh = [self._param_sharding(p) for p in self.params]
        b_sh = [self._named_sharding(()) for _ in self.buffers]
        # optimizer accumulators follow their parameter's layout; with a
        # sharding axis configured (ZeRO stage 1/2) un-annotated states get
        # largest-dim sharded over it (the DygraphShardingOptimizer split)
        from ..distributed.shard_utils import resolve_shard_state_axis
        shard_axis, degree = resolve_shard_state_axis(self.optimizer,
                                                      self.mesh)
        key_of = {}
        for i, p in enumerate(self.params):
            key_of[p.name if p.name else f"param_{i}"] = p

        def acc_sharding(pkey, arr):
            p = key_of.get(pkey)
            if p is not None and param_spec(p) is not None and \
                    tuple(arr.shape) == tuple(p._data.shape):
                return self._param_sharding(p)
            if degree > 1 and hasattr(arr, "shape") and arr.shape:
                s = _largest_dim_spec(arr.shape, shard_axis, degree)
                if s is not None:
                    return self._named_sharding(s)
            return self._named_sharding(())

        acc_sh = {n: {k: acc_sharding(k, v) for k, v in store.items()}
                  for n, store in opt_state["acc"].items()}
        master_sh = {k: acc_sharding(k, v)
                     for k, v in opt_state["master"].items()}
        out = {"p": p_sh, "b": b_sh,
               "o": {"acc": acc_sh, "master": master_sh},
               "rng": self._named_sharding(())}
        if self.scaler is not None:
            r = self._named_sharding(())
            out["s"] = {"scale": r, "incr": r, "decr": r}
        return out

    def _batch_shardings(self, batch_arrays):
        if self.mesh is None:
            return None
        axes = self._data_axes()
        out = []
        for a in batch_arrays:
            if self._batch_spec is not None:
                out.append(self._named_sharding(self._batch_spec))
            elif axes and hasattr(a, "ndim") and a.ndim >= 1:
                out.append(self._named_sharding(
                    (axes,) + (None,) * (a.ndim - 1)))
            else:
                out.append(self._named_sharding(()))
        return out

    # -- the traced step --------------------------------------------------
    def _make_step(self):
        model, opt, loss_fn, scaler = (self.model, self.optimizer,
                                       self.loss_fn, self.scaler)
        params, buffers = self.params, self.buffers

        def train_step(state, lr, batch):
            # 1. install traced state into the eager objects
            for p, v in zip(params, state["p"]):
                p._data = v
                p._grad = None
                p._grad_node = None
            for b, v in zip(buffers, state["b"]):
                b._data = v
            self._install_opt_state(state["o"])
            if opt is not None:
                opt._lr_override = lr
            if scaler is not None:
                scaler._set_state_arrays(state["s"])
            saved_key = default_generator.get_state()
            default_generator.set_state(state["rng"])
            try:
                # 2. run the eager train step under trace
                ts = [Tensor(a) for a in batch]
                if self.step_fn is not None:
                    loss = self.step_fn(model, *ts)
                else:
                    out = model(ts[0])
                    loss = loss_fn(out, *ts[1:])
                # the trace's parts (models.generation.TRAIN_STEP_PARTS):
                # the tape's operations under "backward", the update's
                # under "optimizer"
                if scaler is not None:
                    with jax.named_scope("backward"):
                        scaler.scale(loss).backward()
                    with jax.named_scope("optimizer"):
                        scaler.step(opt)
                        scaler.update()
                elif opt is not None:
                    with jax.named_scope("backward"):
                        loss.backward()
                    with jax.named_scope("optimizer"):
                        opt.step()
                if opt is not None:
                    opt.clear_grad()
                # 3. collect new state
                new_state = {
                    "p": [p._data for p in params],
                    "b": [b._data for b in buffers],
                    "o": self._opt_state(),
                    "rng": default_generator.get_state(),
                }
                if scaler is not None:
                    new_state["s"] = scaler._get_state_arrays()
                return new_state, loss._data
            finally:
                if opt is not None:
                    opt._lr_override = None
                default_generator.set_state(saved_key)

        return train_step

    def _current_lr(self) -> float:
        if self.optimizer is None:
            return 0.0
        lr = self.optimizer._learning_rate
        return float(lr()) if isinstance(lr, LRScheduler) else float(lr)

    # -- public -----------------------------------------------------------
    def __call__(self, *batch):
        batch_arrays = tuple(b._data if isinstance(b, Tensor)
                             else jnp.asarray(b) for b in batch)
        state = {
            "p": [p._data for p in self.params],
            "b": [b._data for b in self.buffers],
            "o": self._opt_state(),
            "rng": default_generator.get_state(),
        }
        if self.scaler is not None:
            state["s"] = self.scaler._get_state_arrays()
        # cache key: optimizer-state tree structure changes once after the
        # first step (accumulator creation) → exactly two traces
        key = (tuple(sorted(state["o"]["acc"])),
               len(state["o"]["master"]),
               tuple(tuple(a.shape) for a in batch_arrays))
        fn = self._jit_cache.get(key)
        jit_miss = fn is None
        if fn is None:
            # resilience fault point: a jit-cache miss is where a
            # scheduled compile-time crash/stall/exception fires (the
            # wedged-Mosaic-compile case the stall heartbeat must catch)
            from ..resilience.faults import maybe_fault
            maybe_fault("compile")
            step = self._make_step()
            kw = {}
            if self.mesh is not None:
                st_sh = self._state_shardings(state["o"])
                kw["in_shardings"] = (st_sh, self._named_sharding(()),
                                      tuple(self._batch_shardings(batch_arrays)))
                # bootstrap step: optimizer state is created inside the
                # trace, so the output tree is bigger than the input tree —
                # let GSPMD infer; steady state pins the layouts
                if state["o"]["acc"] or self.optimizer is None:
                    kw["out_shardings"] = (st_sh, self._named_sharding(()))
                # jit refuses committed args with mismatched shardings
                # (e.g. state arrays born on a previous mesh) — place them
                # explicitly on the first call with this structure
                state = jax.device_put(state, st_sh)
            if self._donate:
                kw["donate_argnums"] = (0,)
            fn = jax.jit(step, **kw)
            self._jit_cache[key] = fn
        lr = jnp.asarray(self._current_lr(), dtype=jnp.float32)
        # abstract call signature for cost analysis (Engine.cost lowers
        # the step once more on ShapeDtypeStructs — no arrays retained);
        # built once per trace key, never on the steady-state hot path
        if self._jitted is not fn:
            self._jitted = fn
            _sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
            self._cost_args = (jax.tree.map(_sds, state), _sds(lr),
                               jax.tree.map(_sds, batch_arrays))
        if jit_miss:
            # observability: a jit miss pays trace+XLA-compile inside
            # this first call — record it as a `compile` event so the
            # log explains the step-time spike (jax.monitoring adds the
            # backend_compile breakdown when available).  Steady-state
            # calls skip this block entirely.
            from ..observability import events as _obs_events
            if _obs_events.enabled():
                from ..observability import tracing as _obs_tracing
                import time as _time
                # the span makes the compile a first-class trace node
                # (watchdog key trace_span:train_step_compile); the
                # compile event inside it inherits the span's trace ids
                with _obs_tracing.trace_span("train_step_compile"):
                    _t0 = _time.perf_counter()
                    new_state, loss = fn(state, lr, batch_arrays)
                    _obs_events.emit(
                        "compile", source="train_step",
                        dur_s=round(_time.perf_counter() - _t0, 6),
                        key=f"acc={sorted(state['o']['acc'])} "
                            f"batch="
                            f"{[tuple(a.shape) for a in batch_arrays]}")
            else:
                new_state, loss = fn(state, lr, batch_arrays)
        else:
            new_state, loss = fn(state, lr, batch_arrays)
        # swap updated arrays back into the live objects
        for p, v in zip(self.params, new_state["p"]):
            p._data = v
        for b, v in zip(self.buffers, new_state["b"]):
            b._data = v
        self._install_opt_state(new_state["o"])
        if self.scaler is not None:
            self.scaler._set_state_arrays(new_state["s"])
        # decommit the key from this step's mesh — otherwise every later
        # random init (jax.random.split chains shardings) is pinned to it.
        # device_put avoids the host round-trip sync np.asarray would force.
        # Without a mesh nothing is committed, and committing the key
        # here would commit every output of the next step and cost the
        # step after it a third XLA compile.
        rng = new_state["rng"]
        default_generator.set_state(
            rng if self.mesh is None
            else jax.device_put(rng, jax.devices()[0]))
        return Tensor(loss)


def train_step(model: Layer, loss_fn=None, optimizer=None, scaler=None,
               mesh=None, **kwargs) -> TrainStep:
    """Build a compiled SPMD train step (the fleet/engine entry point)."""
    return TrainStep(model, loss_fn, optimizer, scaler, mesh=mesh, **kwargs)
