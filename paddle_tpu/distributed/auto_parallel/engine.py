"""auto_parallel static Engine (ref: python/paddle/distributed/
auto_parallel/static/engine.py — Engine.fit/evaluate/predict/prepare).

The reference traces a serial program, completes dist attrs, partitions
per rank and inserts reshards; here the Engine wraps the jit TrainStep:
parameter placements come from shard_tensor annotations, batch sharding
from the mesh's data dims, and GSPMD does completion/partition/reshard.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import numpy as np

from ...core.tensor import Tensor
from ...nn.layer.layers import Layer
from .api import ProcessMesh, get_mesh
from .strategy import Strategy

from ...device import chip_peak_flops as _chip_peak_flops


class Engine:
    def __init__(self, model: Layer, loss=None, optimizer=None,
                 metrics=None, strategy: Optional[Strategy] = None):
        self._model = model
        self._loss = loss
        self._optimizer = getattr(optimizer, "_inner_opt", optimizer)
        self._metrics = metrics if isinstance(metrics, (list, tuple)) else \
            ([metrics] if metrics is not None else [])
        self._strategy = strategy or Strategy()
        self._train_step = None
        self.history = None

    def _ensure_step(self):
        if self._train_step is None:
            from ...jit.train_step import TrainStep
            pm = get_mesh()
            mesh = pm.jax_mesh if pm is not None else None
            self._train_step = TrainStep(self._model, None, self._optimizer,
                                         mesh=mesh,
                                         step_fn=self._step_fn())
        return self._train_step

    # -- reference API ----------------------------------------------------
    def prepare(self, inputs_spec=None, labels_spec=None, mode="train"):
        self._ensure_step()

    def _param_bytes(self) -> int:
        return max(1, sum(int(np.prod(p.shape)) * 4
                          for p in self._model.parameters()))

    def _rank_candidates(self, candidates, batch_tokens):
        """Analytic roofline pre-rank (ref: auto_parallel/static/tuner/
        rule-based stage), delegated to the shared cost model
        (``paddle_tpu.tuning.cost_model.rank_plans``): per-device
        compute against the chip's ICI compute intensity, dp/sharding's
        ring grad all-reduce, mp's activation collectives.  Model- and
        batch-size aware, for ORDERING only — measurement decides the
        winner."""
        from ...tuning.cost_model import rank_plans
        return rank_plans(candidates, batch_tokens, self._param_bytes())

    def _tune_from_perf_model(self, tcache, plan_key, candidates,
                              sample_inputs):
        """Zero-trial plan selection from the telemetry-trained perf
        model (``tuning.learned``): on a plan-cache miss, a trained
        ``plan`` head predicts every candidate's step seconds and the
        winner installs directly — no trial steps, no compiles beyond
        the lazy one the chosen mesh pays anyway.  Returns the tune()
        result dict, or None to fall through to measurement (flag off,
        no model file, no plan head)."""
        from ...flags import get_flag as _get_flag
        if not _get_flag("learned_perf_model"):
            return None
        from ...tuning import learned as _learned
        model = _learned.load_model(tcache.directory)
        if model is None or not model.has("plan"):
            return None
        batch_tokens = int(np.asarray(sample_inputs).size)
        p_bytes = self._param_bytes()
        scored = []
        for c in candidates:
            pred = model.plan_seconds(c, batch_tokens, p_bytes)
            if pred is None:
                return None
            scored.append((pred, tuple(int(x) for x in c)))
        scored.sort()
        from ..mesh import build_mesh, set_mesh
        dp, sh, mp = scored[0][1]
        mesh = build_mesh({"dp": dp, "pp": 1, "sharding": sh,
                           "sep": 1, "cp": 1, "ep": 1, "mp": mp})
        set_mesh(mesh)
        from . import api as _api
        _api._auto_mesh = None
        self._train_step = None
        report = [{"dp": d_, "sharding": s_, "mp": m_,
                   "predicted_s": round(p, 6), "source": "learned"}
                  for p, (d_, s_, m_) in scored]
        from ...tuning.cost_model import plan_layout
        tcache.store("engine_plan", plan_key, {
            "best": {"dp": dp, "sharding": sh, "mp": mp},
            "layout": plan_layout(dp, sh, mp), "report": report,
            "source": "learned", "model_version": model.version,
            "batch_tokens": batch_tokens, "param_bytes": p_bytes})
        self.tuning_report = report
        return {"dp": dp, "sharding": sh, "mp": mp, "report": report,
                "predicted": True}

    def _plan_signature(self, candidates, batch, n_devices, backend):
        """Persistent-cache key for a tune() search: model parameter
        shape/dtype signature + batch shapes + candidate set + device
        count + backend.  Anything that changes the timed OUTCOME is
        here; knobs that only bound how many candidates get timed
        (top_k, budget_s, profile) are deliberately absent — a winner
        tuned under any of them remains the plan for this workload."""
        import hashlib
        import json as _json
        params = [[list(p.shape), str(p.dtype)]
                  for p in self._model.parameters()]
        model_sig = hashlib.sha256(_json.dumps(
            [type(self._model).__name__, params],
            sort_keys=True).encode()).hexdigest()[:16]
        return {"model": model_sig,
                "batch": [[list(a.shape), str(a.dtype)] for a in batch],
                "candidates": sorted(list(map(int, c))
                                     for c in candidates),
                "n_devices": int(n_devices), "backend": str(backend)}

    def tune(self, sample_inputs, sample_labels=None, candidates=None,
             profile: Optional[bool] = None, top_k: Optional[int] = None,
             budget_s: Optional[float] = None):
        """Search mesh factorizations for the fastest step (ref:
        auto_parallel/static/tuner/ — the rule-based + profile search).

        Candidates are (dp, sharding, mp) factorizations of the device
        count; the model's GSPMD placement annotations name AXES, so the
        same annotated model lowers under each candidate mesh without
        re-annotation.  Every measured candidate is scored by REAL step
        wall time (``profile=True`` takes a 3-rep median).  ``top_k``
        measures only the best k candidates of the analytic roofline
        pre-rank, and ``budget_s`` stops starting new candidates once
        the wall budget is spent (in-flight work is never
        interrupted).  Parameters and optimizer state are snapshotted around
        each candidate's trial step and restored, the winning mesh is
        installed, and a report lands in ``self.tuning_report``."""
        import time as _time
        import jax
        from ..mesh import build_mesh, set_mesh, get_mesh as _get_raw
        from ...jit.train_step import TrainStep

        if profile is None:
            profile = bool(getattr(self._strategy.tuning, "profile",
                                   False))
        n = len(jax.devices())
        if candidates is None:
            candidates = self._strategy.tuning.candidates
        if candidates is None:
            candidates = []
            for mp in (d for d in range(1, n + 1) if n % d == 0):
                rest = n // mp
                for sh in (d for d in range(1, rest + 1) if rest % d == 0):
                    candidates.append((rest // sh, sh, mp))
        batch = [np.asarray(sample_inputs)]
        if sample_labels is not None:
            if isinstance(sample_labels, (list, tuple)):
                batch.extend(np.asarray(l) for l in sample_labels)
            else:
                batch.append(np.asarray(sample_labels))

        # persistent plan cache (FLAGS_tuning_cache_dir): an identical
        # (model, batch, candidates, devices) search resolves from disk
        # with ZERO trial steps — the winner installs directly and the
        # step compiles lazily (XLA's persistent compile cache absorbs
        # that compile too)
        from ...tuning.cache import get_cache as _get_tuning_cache
        tcache = _get_tuning_cache()
        plan_key = None
        if tcache is not None:
            plan_key = self._plan_signature(
                candidates, batch, n, jax.devices()[0].platform)
            cached = tcache.lookup("engine_plan", plan_key)
            if cached is not None:
                dp, sh, mp = (int(cached["best"][k])
                              for k in ("dp", "sharding", "mp"))
                mesh = build_mesh({"dp": dp, "pp": 1, "sharding": sh,
                                   "sep": 1, "cp": 1, "ep": 1, "mp": mp})
                set_mesh(mesh)
                from . import api as _api
                _api._auto_mesh = None
                self._train_step = None
                report = list(cached.get("report", []))
                report.append({"dp": dp, "sharding": sh, "mp": mp,
                               "cache": "hit"})
                self.tuning_report = report
                return {"dp": dp, "sharding": sh, "mp": mp,
                        "report": report, "cached": True}
            predicted = self._tune_from_perf_model(
                tcache, plan_key, candidates, sample_inputs)
            if predicted is not None:
                return predicted

        ranked = self._rank_candidates(
            candidates, int(np.asarray(sample_inputs).size))
        skipped_rank = []
        if top_k is not None and top_k < len(ranked):
            skipped_rank = ranked[top_k:]
            ranked = ranked[:top_k]
        candidates = ranked
        t_tune0 = _time.monotonic()

        from ...random_state import default_generator

        def snapshot():
            params = [p._data for p in self._model.parameters()]
            bufs = [b._data for b in self._model.buffers()]
            rng = default_generator.get_state()
            opt = None
            if self._optimizer is not None:
                opt = ({k: dict(v) for k, v in
                        self._optimizer._accumulators.items()},
                       dict(self._optimizer._master_weights))
            return params, bufs, rng, opt

        def restore(snap):
            params, bufs, rng, opt = snap
            for p, v in zip(self._model.parameters(), params):
                p._data = v
            # trial steps advance buffers (BN running stats) and the
            # global RNG — both must roll back or tuning skews training
            for b, v in zip(self._model.buffers(), bufs):
                b._data = v
            default_generator.set_state(rng)
            if opt is not None and self._optimizer is not None:
                from collections import defaultdict
                self._optimizer._accumulators = defaultdict(
                    dict, {k: dict(v) for k, v in opt[0].items()})
                self._optimizer._master_weights = dict(opt[1])

        prev_mesh = _get_raw()
        snap = snapshot()
        report = []
        best = None
        attempted = 0
        for dp, sh, mp in candidates:
            entry = {"dp": dp, "sharding": sh, "mp": mp}
            # the budget must fire even when every attempt FAILS —
            # only the first candidate is always attempted
            if budget_s is not None and attempted > 0 and \
                    _time.monotonic() - t_tune0 > budget_s:
                entry["skipped"] = "tuning budget exhausted"
                report.append(entry)
                continue
            attempted += 1
            try:
                mesh = build_mesh({"dp": dp, "pp": 1, "sharding": sh,
                                   "sep": 1, "cp": 1, "ep": 1, "mp": mp})
                set_mesh(mesh)
                step = TrainStep(self._model, None, self._optimizer,
                                 mesh=mesh, step_fn=self._step_fn(),
                                 donate=False)
                t0 = _time.perf_counter()
                loss = step(*batch)
                float(loss)                       # force execution
                entry["compile_plus_step_s"] = round(
                    _time.perf_counter() - t0, 3)
                # ONE scoring basis for every candidate: wall time of
                # post-compile steps (the executable is cached, so this
                # is cheap and comparable; the cost model can report
                # flops=0 on some backends, which would make every
                # candidate tie at 0)
                reps = 3 if profile else 1
                times = []
                for _ in range(reps):
                    t0 = _time.perf_counter()
                    float(step(*batch))
                    times.append(_time.perf_counter() - t0)
                entry["step_s"] = sorted(times)[reps // 2]
                score = entry["step_s"]
                entry["score"] = score
                if best is None or score < best[0]:
                    best = (score, (dp, sh, mp), mesh, step)
            except Exception as e:  # noqa: BLE001 — a candidate that
                entry["error"] = str(e)[-200:]    # can't lower is skipped
            finally:
                restore(snap)
                self._train_step = None
            report.append(entry)
        for dp, sh, mp in skipped_rank:
            report.append({"dp": dp, "sharding": sh, "mp": mp,
                           "skipped": "below top_k in roofline pre-rank"})
        self.tuning_report = report
        if best is None:
            set_mesh(prev_mesh)
            raise RuntimeError(
                f"Engine.tune: no candidate compiled; report: {report}")
        _, (dp, sh, mp), mesh, win_step = best
        set_mesh(mesh)
        # a previously installed ProcessMesh would override the winner in
        # _ensure_step (api.get_mesh is consulted first) — clear it so
        # the tuned raw mesh governs
        from . import api as _api
        _api._auto_mesh = None
        # reuse the winner's already-compiled step — rebuilding would pay
        # a third compile of the same program
        self._train_step = win_step
        if tcache is not None and plan_key is not None:
            from ...tuning.cost_model import plan_layout
            # the canonical-PartitionSpec layout table makes the entry
            # consumable without re-deriving GSPMD placements; the
            # workload scale (batch_tokens/param_bytes) makes every
            # measured report row a training sample for the learned
            # perf model's plan head (tuning.learned)
            tcache.store("engine_plan", plan_key, {
                "best": {"dp": dp, "sharding": sh, "mp": mp},
                "layout": plan_layout(dp, sh, mp),
                "report": report,
                "batch_tokens": int(np.asarray(sample_inputs).size),
                "param_bytes": self._param_bytes()})
        return {"dp": dp, "sharding": sh, "mp": mp, "report": report}

    def _step_fn(self):
        def step_fn(model, *batch):
            inputs, labels = batch[0], batch[1:]
            out = model(inputs)
            if callable(self._loss):
                return self._loss(out, *labels)
            raise ValueError("Engine needs a callable loss")
        return step_fn

    def fit(self, train_data, train_sample_split=None, batch_size=1,
            epochs=1, steps_per_epoch=None, log_freq=10, valid_data=None,
            **kwargs):
        from ...io import DataLoader
        if getattr(self._strategy.tuning, "enable", False) and \
                self._train_step is None:
            if not hasattr(train_data, "__getitem__"):
                import warnings
                warnings.warn(
                    "strategy.tuning.enable is set but fit() received an "
                    "iterable dataset (no __getitem__) — skipping the "
                    "mesh search; call engine.tune(sample) explicitly",
                    RuntimeWarning)
            else:
                # strategy.tuning.enable: search the mesh before training
                # (ref: Engine._tune on the first fit).  Samples are
                # UNBATCHED dataset items — always stack batch_size of
                # them (no shape heuristics: a 1-d feature equal in
                # length to batch_size is still a single sample)
                sample = train_data[0]
                sample = sample if isinstance(sample, (list, tuple)) \
                    else [sample]
                xs = [np.asarray(getattr(s, "numpy", lambda: s)())
                      for s in sample]
                batched = [np.stack([x] * max(int(batch_size), 1))
                           for x in xs]
                try:
                    self.tune(batched[0], batched[1:] or None)
                except Exception as e:  # noqa: BLE001
                    import warnings
                    warnings.warn(
                        f"mesh tuning failed ({e}); training continues "
                        "under the current mesh", RuntimeWarning)
        step = self._ensure_step()
        loader = train_data if hasattr(train_data, "__iter__") and \
            not hasattr(train_data, "__getitem__") else DataLoader(
                train_data, batch_size=batch_size, shuffle=False)
        history = {"loss": []}
        for epoch in range(epochs):
            for i, batch in enumerate(loader):
                if steps_per_epoch is not None and i >= steps_per_epoch:
                    break
                batch = batch if isinstance(batch, (list, tuple)) else [batch]
                loss = step(*batch)
                history["loss"].append(float(loss))
            if self._optimizer is not None and hasattr(
                    self._optimizer, "_learning_rate") and hasattr(
                    self._optimizer._learning_rate, "step"):
                self._optimizer._learning_rate.step()
        self.history = history
        return history

    def evaluate(self, valid_data, batch_size=1, steps=None, **kwargs):
        from ...io import DataLoader
        self._model.eval()
        loader = valid_data if hasattr(valid_data, "__iter__") and \
            not hasattr(valid_data, "__getitem__") else DataLoader(
                valid_data, batch_size=batch_size)
        losses = []
        for i, batch in enumerate(loader):
            if steps is not None and i >= steps:
                break
            batch = batch if isinstance(batch, (list, tuple)) else [batch]
            out = self._model(batch[0] if isinstance(batch[0], Tensor)
                              else Tensor(np.asarray(batch[0])))
            if self._loss is not None and len(batch) > 1:
                losses.append(float(self._loss(out, batch[1])))
        self._model.train()
        return {"eval_loss": float(np.mean(losses)) if losses else None}

    def predict(self, test_data, batch_size=1, steps=None, **kwargs):
        from ...io import DataLoader
        self._model.eval()
        loader = test_data if hasattr(test_data, "__iter__") and \
            not hasattr(test_data, "__getitem__") else DataLoader(
                test_data, batch_size=batch_size)
        outs = []
        for i, batch in enumerate(loader):
            if steps is not None and i >= steps:
                break
            batch = batch if isinstance(batch, (list, tuple)) else [batch]
            outs.append(self._model(batch[0]))
        self._model.train()
        return outs

    def save(self, path: str, training: bool = True):
        from ... import save as psave
        psave(self._model.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            psave(self._optimizer.state_dict(), path + ".pdopt")

    def load(self, path: str, strict: bool = True, load_optimizer: bool = True):
        from ... import load as pload
        self._model.set_state_dict(pload(path + ".pdparams"))
        if load_optimizer and self._optimizer is not None:
            import os
            if os.path.exists(path + ".pdopt"):
                self._optimizer.set_state_dict(pload(path + ".pdopt"))

    def cost(self, mode="train"):
        """ref: Engine.cost — estimated (time, memory) of one step.

        The reference runs its own analytic cost model over the
        partitioned program; here XLA itself is the cost model: the
        jitted step's memory analysis gives the executable's peak
        footprint (args + outputs + temps) and its cost analysis gives
        FLOPs.  Returns the REFERENCE's tuple shape and units:
        ``(time_cost_ms, max_memory_bytes)`` (time from FLOPs at a
        nominal 50% MFU of the attached chip's peak), so code ported
        from the reference unpacking ``time, memory = engine.cost()``
        reads correctly.  ``None`` before the step compiles."""
        step = self._train_step
        if step is None or getattr(step, "_jitted", None) is None:
            return None
        # lower+compile bypasses jax.jit's executable cache — cache the
        # result per trace signature so repeated cost() calls (logging
        # loops) don't pay a redundant full XLA compile each time
        cache = getattr(step, "_cost_compiled", None)
        if cache is not None and cache[0] is step._cost_args:
            compiled = cache[1]
        else:
            try:
                compiled = step._jitted.lower(*step._cost_args).compile()
            except Exception:
                return None
            step._cost_compiled = (step._cost_args, compiled)
        try:
            cost = compiled.cost_analysis()
        except Exception:
            return None
        if isinstance(cost, list):
            cost = cost[0] if cost else {}
        flops = float(cost.get("flops", 0.0))
        mem_bytes = 0
        try:
            ma = compiled.memory_analysis()
            mem_bytes = int(
                getattr(ma, "argument_size_in_bytes", 0)
                + getattr(ma, "output_size_in_bytes", 0)
                + getattr(ma, "temp_size_in_bytes", 0))
        except Exception:
            pass
        if not mem_bytes:
            mem_bytes = int(float(cost.get("bytes accessed", 0.0)))
        import jax
        dev = jax.devices()[0]
        # the CPU test mesh has no peak on record: a nominal 1 TFLOP/s
        # keeps the reference's tuple shape there
        peak = _chip_peak_flops(dev) if dev.platform == "tpu" else 1e12
        time_cost = flops / (0.5 * peak) if flops else 0.0
        return (time_cost * 1e3, mem_bytes)
