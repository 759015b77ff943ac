"""Optimizer base (ref: python/paddle/optimizer/optimizer.py ~2.5k LoC).

TPU-native design: the update rule of each optimizer is a pure jnp function
``_update(param, grad, state, lr) -> (new_param, new_state)``.  Eagerly it
runs per-parameter; under the jit functionalizer the whole step (all params)
traces into one XLA program, which is where fused multi-tensor updates come
from on TPU — no hand-written multi_tensor CUDA kernel needed.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..core.autograd_state import no_grad
from ..regularizer import L1Decay, L2Decay
from ..nn.clip import ClipGradBase
from .lr import LRScheduler


def _param_key(p: Tensor, idx: int) -> str:
    return p.name if p.name else f"param_{idx}"


class _AccShim:
    """Rebinds an optimizer's accumulator get/set to a local dict for
    ONE ``_update_param`` call — the static minimize path uses it to
    turn state reads/writes into explicit op inputs/outputs (discovery
    pass on zeros, then per-replay binding), keeping the update rule
    itself untouched and pure."""

    def __init__(self, p: Tensor, preset=None):
        self.p = p
        self.names: list = []
        self.inits: dict = {}
        self.values: dict = dict(preset or {})

    def bound(self, opt: "Optimizer"):
        import contextlib

        @contextlib.contextmanager
        def cm():
            orig_get, orig_set = opt._get_accumulator, opt._set_accumulator

            def get(name, p, idx, fill=0.0, dtype=None, shape=None):
                if name not in self.values:
                    dt = dtype or p._data.dtype
                    shp = tuple(shape) if shape is not None \
                        else p._data.shape
                    init = jnp.full(shp, fill, dtype=dt)
                    self.names.append(name)
                    self.inits[name] = init
                    self.values[name] = init
                return self.values[name]

            def set_(name, p, idx, value):
                self.values[name] = value

            opt._get_accumulator, opt._set_accumulator = get, set_
            try:
                yield self
            finally:
                opt._get_accumulator, opt._set_accumulator = \
                    orig_get, orig_set

        return cm()


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        self._learning_rate = learning_rate
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        self._name = name

        if weight_decay is None:
            self._regularization = None
        elif isinstance(weight_decay, (L1Decay, L2Decay)):
            self._regularization = weight_decay
        else:
            self._regularization = L2Decay(float(weight_decay))

        # parameter groups (list of dicts) or flat list
        self._param_groups: List[dict] = []
        self._parameter_list: List[Tensor] = []
        if parameters is not None:
            parameters = list(parameters)
            if parameters and isinstance(parameters[0], dict):
                for g in parameters:
                    self._add_param_group(dict(g))
            else:
                self._parameter_list = parameters
                self._param_groups = [{"params": parameters}]
        # accumulators: name -> {param_key: jnp array}
        self._accumulators: Dict[str, Dict[str, jnp.ndarray]] = \
            defaultdict(dict)
        self._master_weights: Dict[str, jnp.ndarray] = {}
        self._global_step = 0
        # traced-lr override: the jit engine threads the scheduler's lr in
        # as a scalar array so lr changes don't retrace the step
        self._lr_override = None
        # sharding hints set by fleet sharding wrappers, read by the engine
        self._shard_state_axis: Optional[str] = None
        self._shard_grads = False

    # ------------------------------------------------------------------
    def _add_param_group(self, group: dict):
        params = list(group["params"])
        group["params"] = params
        self._parameter_list.extend(params)
        self._param_groups.append(group)

    def _append_params(self, parameters):
        """Used by fleet wrappers to rebind parameter lists."""
        self._parameter_list = list(parameters)
        self._param_groups = [{"params": self._parameter_list}]

    # ------------------------------------------------------------------
    # lr plumbing
    # ------------------------------------------------------------------
    def get_lr(self) -> float:
        if self._lr_override is not None:
            return self._lr_override  # scalar array under trace
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate())
        return float(self._learning_rate)

    def set_lr(self, value: float):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError(
                "cannot set_lr when the lr is an LRScheduler; call "
                "scheduler.step() instead")
        self._learning_rate = float(value)

    def set_lr_scheduler(self, scheduler: LRScheduler):
        self._learning_rate = scheduler

    def _group_lr(self, group: dict) -> float:
        base = self.get_lr()
        return base * float(group.get("learning_rate", 1.0))

    # ------------------------------------------------------------------
    # accumulators
    # ------------------------------------------------------------------
    def _get_accumulator(self, name: str, p: Tensor, idx: int,
                         fill: float = 0.0, dtype=None, shape=None):
        key = _param_key(p, idx)
        store = self._accumulators[name]
        if key not in store:
            dt = dtype or (jnp.float32 if self._use_master(p) else p._data.dtype)
            shp = tuple(shape) if shape is not None else p._data.shape
            store[key] = jnp.full(shp, fill, dtype=dt)
        return store[key]

    def _set_accumulator(self, name: str, p: Tensor, idx: int, value):
        self._accumulators[name][_param_key(p, idx)] = value

    def _use_master(self, p: Tensor) -> bool:
        return self._multi_precision and p._data.dtype in (
            jnp.float16, jnp.bfloat16)

    def _get_master(self, p: Tensor, idx: int):
        key = _param_key(p, idx)
        if key not in self._master_weights:
            self._master_weights[key] = p._data.astype(jnp.float32)
        return self._master_weights[key]

    # ------------------------------------------------------------------
    # step
    # ------------------------------------------------------------------
    def _collect_params_grads(self):
        out = []
        idx = 0
        for group in self._param_groups:
            for p in group["params"]:
                g = p._grad
                out.append((p, g, group, idx))
                idx += 1
        return out

    def _apply_regularization(self, p: Tensor, g, group: dict, pv=None):
        # per-param regularizer attr wins (ParamAttr.regularizer) — and
        # must be honored even when no GLOBAL regularization is set.
        # ``pv`` overrides the param value (the static step passes the
        # traced array; p._data there would bake a stale constant).
        attrs = getattr(p, "_paddle_attrs", None)
        if attrs is not None and attrs.regularizer is not None:
            reg = attrs.regularizer
        else:
            reg = group.get("weight_decay", self._regularization)
        if reg is None:
            return g
        if not isinstance(reg, (L1Decay, L2Decay)):
            reg = L2Decay(float(reg))
        val = p._data if pv is None else pv
        if isinstance(reg, L2Decay) and reg.coeff:
            return g + reg.coeff * val.astype(g.dtype)
        if isinstance(reg, L1Decay) and reg.coeff:
            return g + reg.coeff * jnp.sign(val).astype(g.dtype)
        return g

    # subclasses with decoupled decay (AdamW/Lamb) skip grad-coupled reg
    _decoupled_decay = False

    @no_grad()
    def step(self):
        self._global_step += 1
        entries = self._collect_params_grads()
        # grad clip over the whole set (matches reference semantics)
        if self._grad_clip is not None:
            pg = [(p, g) for p, g, _, _ in entries]
            clipped = self._grad_clip(pg)
            entries = [(p, cg, grp, i) for (p, g, grp, i), (_, cg)
                       in zip(entries, clipped)]
        for p, g, group, idx in entries:
            if g is None or p.stop_gradient:
                continue
            gv = g._data if isinstance(g, Tensor) else g
            use_master = self._use_master(p)
            pv = self._get_master(p, idx) if use_master else p._data
            gv = gv.astype(pv.dtype)
            if not self._decoupled_decay:
                gv = self._apply_regularization(p, gv, group)
            lr = self._group_lr(group)
            new_p = self._update_param(p, pv, gv, lr, group, idx)
            if use_master:
                self._master_weights[_param_key(p, idx)] = new_p
                with jax.named_scope("cast_params"):
                    p._data = new_p.astype(p._data.dtype)
            else:
                p._data = new_p

    def _update_param(self, p, pv, gv, lr, group, idx):
        raise NotImplementedError

    minimize_return = None

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        from ..static.capture import in_static_capture
        if in_static_capture():
            return self._static_minimize(loss, parameters, no_grad_set)
        loss.backward()
        self.step()
        return None, [(p, p.grad) for p in self._parameter_list]

    def _static_minimize(self, loss, parameters=None, no_grad_set=None):
        """Static-graph training (ref: Optimizer.minimize appending
        backward + optimizer ops to the Program; base/backward.py +
        the per-optimizer _append_optimize_op).

        TPU-native: append_backward records the grad op, then ONE
        update op applies this optimizer's ``_update_param`` rule to
        every (param, grad) — accumulator reads/writes are rebound to
        op inputs/outputs through a shim, so the op stays pure and the
        Executor write-backs commit new params/state after each run.
        The lr is baked at build time (re-build the program to change
        it); master weights don't apply (static params are fp32).
        """
        from ..static import append_backward
        from ..static.capture import current_program

        prog = current_program()
        # default to THIS optimizer's parameters (multi-optimizer setups
        # must not cross-train each other's subsets); fall back to every
        # program param only when the optimizer was built without any
        params_arg = parameters if parameters is not None else \
            (self._parameter_list or None)
        pg = append_backward(loss, parameter_list=params_arg,
                             no_grad_set=no_grad_set)
        if not pg:
            return [], []
        params = [p for p, _ in pg]
        grad_ts = [g for _, g in pg]
        lr = float(self.get_lr())

        # discover each param's state (names, inits) with a shimmed dry
        # run on zeros — nothing touches the real accumulators.  The
        # dry run (and the replay) patches _global_step: optimizers with
        # step-dependent bias correction (RAdam/NAdam) read it, and the
        # eager value here is 0 (division by (1 - beta^0) explodes)
        metas = []
        state_tensors = []
        saved_step = self._global_step
        try:
            self._global_step = 1
            for j, p in enumerate(params):
                shim = _AccShim(p)
                with shim.bound(self):
                    self._update_param(p, jnp.zeros_like(p._data),
                                       jnp.zeros_like(p._data), lr, {}, j)
                metas.append(shim.names)
                for name in shim.names:
                    t = Tensor(shim.inits[name])
                    t.name = f"{p.name or 'p%d' % j}_{name}"
                    state_tensors.append(t)
        finally:
            self._global_step = saved_step
        # the step counter itself is traced state (a baked python int
        # would freeze bias correction at the build-time value)
        step_t = Tensor(jnp.zeros((), jnp.int32))
        step_t.name = "global_step"
        state_tensors.append(step_t)

        n = len(params)
        opt = self

        def step_fn(*arrays):
            pvs = list(arrays[:n])
            gvs = list(arrays[n:2 * n])
            svs = list(arrays[2 * n:])
            gs_new = svs[-1] + 1          # traced step counter
            svs = svs[:-1]
            if opt._grad_clip is not None:
                # clip classes are pure jnp over g._data — trace-safe
                pg_t = [(p, Tensor(g)) for p, g in zip(params, gvs)]
                gvs = [t._data for _, t in opt._grad_clip(pg_t)]
            new_ps, new_ss = [], []
            si = 0
            saved = opt._global_step
            try:
                opt._global_step = gs_new
                for j, (p, names) in enumerate(zip(params, metas)):
                    gv = gvs[j].astype(pvs[j].dtype)
                    if not opt._decoupled_decay:
                        gv = opt._apply_regularization(p, gv, {},
                                                       pv=pvs[j])
                    shim = _AccShim(p, preset=dict(
                        zip(names, svs[si:si + len(names)])))
                    with shim.bound(opt):
                        new_p = opt._update_param(p, pvs[j], gv, lr, {}, j)
                    new_ps.append(new_p.astype(arrays[j].dtype))
                    new_ss.extend(shim.values[nm] for nm in names)
                    si += len(names)
            finally:
                opt._global_step = saved
            return tuple(new_ps) + tuple(new_ss) + (gs_new,)

        out_ps = [Tensor(jnp.zeros_like(p._data),
                         name=f"{p.name or 'p%d' % i}@NEW")
                  for i, p in enumerate(params)]
        out_ss = [Tensor(jnp.zeros_like(t._data), name=f"{t.name}@NEW")
                  for t in state_tensors]
        prog._record(step_fn, {},
                     list(params) + grad_ts + state_tensors,
                     out_ps + out_ss, multi_out=True,
                     name=f"{type(self).__name__.lower()}_step")
        prog.writebacks.extend(zip(params, out_ps))
        prog.writebacks.extend(zip(state_tensors, out_ss))
        return [], pg

    @no_grad()
    def clear_grad(self, set_to_zero: bool = True):
        for p in self._parameter_list:
            p.clear_grad(set_to_zero=False)

    clear_gradients = clear_grad

    # ------------------------------------------------------------------
    # state dict
    # ------------------------------------------------------------------
    def state_dict(self) -> dict:
        sd = {}
        for name, store in self._accumulators.items():
            for key, v in store.items():
                sd[f"{key}_{name}"] = Tensor(v)
        if self._master_weights:
            sd["master_weights"] = {k: Tensor(v) for k, v
                                    in self._master_weights.items()}
        if isinstance(self._learning_rate, LRScheduler):
            sd["LR_Scheduler"] = self._learning_rate.state_dict()
        sd["global_step"] = self._global_step
        return sd

    def set_state_dict(self, state_dict: dict):
        state_dict = dict(state_dict)
        if "LR_Scheduler" in state_dict and isinstance(self._learning_rate,
                                                       LRScheduler):
            self._learning_rate.set_state_dict(state_dict.pop("LR_Scheduler"))
        self._global_step = int(state_dict.pop("global_step", 0))
        mw = state_dict.pop("master_weights", None)
        if mw:
            self._master_weights = {
                k: (v._data if isinstance(v, Tensor) else jnp.asarray(np.asarray(v)))
                for k, v in mw.items()}
        candidates = list(dict.fromkeys(
            list(self._accumulators.keys()) + self._accumulator_names()))
        # longest suffix first so "moment1" wins over "moment"
        candidates.sort(key=len, reverse=True)
        for full_key, v in state_dict.items():
            vv = v._data if isinstance(v, Tensor) else jnp.asarray(np.asarray(v))
            # split "<param_key>_<acc_name>" on last known acc name
            for name in candidates:
                suffix = "_" + name
                if full_key.endswith(suffix):
                    self._accumulators[name][full_key[:-len(suffix)]] = vv
                    break

    def _accumulator_names(self):
        return ["moment", "moment1", "moment2", "beta1_pow", "beta2_pow",
                "velocity", "inf_norm", "mean_square", "mean_grad",
                "avg_squared_grad", "avg_squared_update"]

    def get_opti_var_name_list(self):
        return [f"{k}_{n}" for n, store in self._accumulators.items()
                for k in store]

    def __repr__(self):
        return f"{type(self).__name__}(lr={self.get_lr()})"
