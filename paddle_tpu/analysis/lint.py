"""Tracing-safety AST linter (PTL0xx).

Walks Python source — the package itself, ``examples/``, or user model
code — and flags TPU/JAX tracing hazards with ``PTL`` codes.  Stdlib
only: linting must not import jax (or the package under analysis).

Two notions of "traced region" drive context sensitivity:

* **decorated**: any function decorated ``@to_static`` /
  ``@paddle.jit.to_static`` / ``@train_step`` (and every function nested
  inside one) is traced — host syncs there are definitive hazards.  A
  trailing ``# ptl: traced`` comment on the ``def`` line opts a function
  in explicitly (for callables passed to ``train_step``/``jax.jit`` by
  reference).
* **surface modules**: files matching ``SURFACE_GLOBS`` (the package's
  op-surface — ``nn/functional``, ``tensor/*``, ``ops/``) hold functions
  that execute *inside* user traces, so every function they define is
  treated as traced.  This is what lets the linter find stray host syncs
  on the package's own hot paths.

Suppression: ``# noqa`` or ``# noqa: PTL001[,PTL006]`` on the flagged
line.  The package self-lint (tests/test_analysis.py) holds the surface
at zero error-severity findings.
"""
from __future__ import annotations

import ast
import fnmatch
import io
import os
import re
import tokenize
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .concheck import concheck_findings_source, is_concurrency_path
from .rules import ERROR, WARNING, Finding, make_finding
from .shardcheck import (is_shard_path, is_strategy_path,
                         shard_findings_source, strategy_findings_source)

# files whose functions run under user traces (relative-path globs,
# matched with '/' separators against the path tail)
SURFACE_GLOBS = (
    "*/nn/functional/*.py",
    "*/incubate/nn/functional/*.py",
    "*/ops/*.py",
    "*/ops/pallas/*.py",
    "*/tensor/math.py",
    "*/tensor/manipulation.py",
    "*/tensor/creation.py",
    "*/tensor/linalg.py",
    "*/tensor/logic.py",
    "*/tensor/search.py",
    "*/tensor/stat.py",
    "*/tensor/random.py",
    "*/tensor/einsum.py",
    "*/tensor/_helpers.py",
)
# surface files exempt from surface mode (their host-side code is the
# point: test oracles, case generators, kernel benchmarking)
SURFACE_EXEMPT = ("*/tensor/op_registry.py", "*/ops/pallas/autotune.py")

# resilience-critical files (PTL401 exception-hygiene scope): a
# swallow-and-continue handler here turns a torn checkpoint / dead
# worker / failed predict into silent wrong behavior — and in the
# fleet tier, a router/health-poll handler that silently eats a
# replica failure routes traffic into a corpse
RESILIENCE_GLOBS = (
    "*/resilience/*.py",
    "*/distributed/checkpoint/*.py",
    "*/inference/*.py",
    "*/serving/fleet/*.py",
    # the engine's fault-containment layer (quarantine bisection,
    # watchdog relaunch, deadline cancellation): a swallowed failure
    # here silently truncates client streams
    "*/serving/engine.py",
    "*/serving/scheduler.py",
)

# instrumented subsystems (PTL501 raw-timing scope): timings reported
# from here must flow through observability.metrics, not ad-hoc
# time.time()/perf_counter() deltas (time.monotonic deadlines are fine)
TIMING_GLOBS = (
    "*/tuning/*.py",
    "*/resilience/*.py",
    "*/inference/*.py",
    "*/serving/*.py",
)

# continuous-batching serving files (PTL701 scope): step-loop code
# paths (functions named *step*/*loop*/*fused*/*window*) must not read
# device values back to the host — every sync serializes the whole
# batch pipeline per token.  The ONE sanctioned read is the per-window
# boundary (a reasoned noqa)
SERVING_GLOBS = (
    "*/serving/scheduler.py",
    "*/serving/engine.py",
    "*/serving/fleet/*.py",
)
SERVING_HOT_NAMES = ("step", "loop", "fused", "window",
                     # fault-containment paths run INSIDE the
                     # iteration loop's cadence — a host sync there
                     # stalls recovery exactly when latency matters
                     "watchdog", "quarantine", "recover")

# the fused-window builders live next to generate() in
# models/generation.py — only the compiled-window code paths
# (*fused*/*window* names) are PTL701-hot there; generate()'s eager
# loop legitimately syncs at its hoisted stop checks
GENERATION_GLOBS = (
    "*/models/generation.py",
)
GENERATION_HOT_NAMES = ("fused", "window")

# program-pass files (PTL602 scope): graph passes must build new
# _OpRecords, never mutate the shared ones in place
PASS_GLOBS = (
    "*/static/passes/*.py",
)

# Pallas kernel files (PTL603 scope): array constructors inside kernel
# bodies (functions taking *_ref refs) must pin 32-bit dtypes — the
# package runs with jax_enable_x64 on, so an unpinned literal under an
# outer jit silently promotes to f64/i64
KERNEL_GLOBS = (
    "*/ops/pallas/*.py",
    "*/ops/flash_attention.py",
)

_HOST_SYNC_METHODS = {"numpy", "item", "tolist"}
_HOST_CASTS = {"float", "int", "bool"}
_TRACED_DECORATORS = {"to_static", "train_step", "TrainStep"}
# producers whose result is a Tensor (or traced array) wherever they
# appear — the roots of the tensorish lattice
_TENSOR_PRODUCERS = {"ensure_tensor", "to_tensor", "unwrap", "call_op",
                     "call_op_custom_vjp"}
# module roots whose function results are tensor-valued
_TENSOR_ROOTS = {"paddle", "paddle_tpu", "F", "jnp"}
# functions under those roots that return HOST values (dtype predicates,
# static metadata) — their results are trace-safe to branch on
_HOST_RESULT_FNS = {
    "issubdtype", "iinfo", "finfo", "result_type", "can_cast", "isdtype",
    "promote_types", "broadcast_shapes", "ndim", "shape", "size",
    "is_complex", "is_floating_point", "is_integer", "is_tensor",
    "in_dynamic_mode", "get_default_dtype",
}
# metadata attributes that yield host values (ints/strings), not
# Tensors — tensorish propagation stops here (x.shape[-1] is static)
_META_ATTRS = {"shape", "ndim", "dtype", "size", "name", "ndimension",
               "stop_gradient", "place", "is_leaf", "itemsize"}
# Tensor methods that return Tensors (chains like x.sum().mean())
_TENSOR_METHODS = {
    "sum", "mean", "max", "min", "prod", "abs", "norm", "std", "var",
    "all", "any", "count_nonzero", "matmul", "mm", "dot", "reshape",
    "transpose", "astype", "cast", "squeeze", "unsqueeze", "flatten",
    "clip", "detach", "clone", "exp", "log", "sqrt", "tanh", "sigmoid",
    "softmax", "argmax", "argmin", "cumsum", "t", "pow", "add",
    "subtract", "multiply", "divide", "logsumexp",
}
_IMPURE_HOST_CALLS = {
    ("time", "time"), ("time", "perf_counter"), ("time", "monotonic"),
    ("random", "random"), ("random", "randint"), ("random", "uniform"),
    ("random", "randrange"), ("random", "choice"), ("random", "shuffle"),
    ("random", "gauss"),
}

_NOQA_RE = re.compile(r"#\s*noqa\b(?P<colon>\s*:\s*(?P<raw>[^#]*))?",
                      re.IGNORECASE)
# one rule code: 1-4 letters + 1-4 digits (PTL801, E402, BLE001, ...)
_NOQA_CODE_RE = re.compile(r"[A-Za-z]{1,4}\d{1,4}$")
_TRACED_MARK_RE = re.compile(r"#\s*ptl:\s*traced", re.IGNORECASE)


def _dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _decorator_marks_traced(dec: ast.AST) -> bool:
    if isinstance(dec, ast.Call):
        dec = dec.func
    dotted = _dotted(dec)
    if dotted is None:
        return False
    return dotted.split(".")[-1] in _TRACED_DECORATORS


def _is_layer_class(cls: ast.ClassDef) -> bool:
    for base in cls.bases:
        dotted = _dotted(base) or ""
        if dotted.split(".")[-1] == "Layer":
            return True
    return False


class _Scope:
    __slots__ = ("traced", "tensor_names", "in_layer", "func_name")

    def __init__(self, traced: bool, in_layer: bool = False,
                 func_name: str = ""):
        self.traced = traced
        self.tensor_names: Set[str] = set()
        self.in_layer = in_layer
        self.func_name = func_name


class _Linter(ast.NodeVisitor):
    def __init__(self, filename: str, source_lines: Sequence[str],
                 surface: bool):
        self.filename = filename
        self.lines = source_lines
        self.surface = surface
        self.findings: List[Finding] = []
        self._scopes: List[_Scope] = []
        self._class_stack: List[ast.ClassDef] = []

    # -- helpers ---------------------------------------------------------
    @property
    def scope(self) -> Optional[_Scope]:
        return self._scopes[-1] if self._scopes else None

    @property
    def traced(self) -> bool:
        return bool(self._scopes and self._scopes[-1].traced)

    def emit(self, code: str, message: str, node: ast.AST,
             severity: Optional[str] = None):
        self.findings.append(make_finding(
            code, message, file=self.filename,
            line=getattr(node, "lineno", 0),
            col=getattr(node, "col_offset", 0), severity=severity))

    def _tensorish(self, node: ast.AST, depth: int = 0) -> bool:
        """Lexical may-be-Tensor lattice (best effort, no type info)."""
        if depth > 8 or node is None:
            return False
        if isinstance(node, ast.Name):
            sc = self.scope
            return bool(sc and node.id in sc.tensor_names)
        if isinstance(node, ast.Call):
            f = node.func
            dotted = _dotted(f)
            if dotted is not None:
                leaf = dotted.split(".")[-1]
                root = dotted.split(".")[0]
                if leaf in _HOST_RESULT_FNS:
                    return False
                if leaf in _TENSOR_PRODUCERS:
                    return True
                if root in _TENSOR_ROOTS and "." in dotted:
                    return True
            if isinstance(f, ast.Attribute) and f.attr in _TENSOR_METHODS:
                return self._tensorish(f.value, depth + 1)
            return False
        if isinstance(node, ast.Attribute):
            if node.attr in _META_ATTRS:
                return False
            # x.T / x.real style — propagate from the base
            return self._tensorish(node.value, depth + 1)
        if isinstance(node, ast.BinOp):
            return (self._tensorish(node.left, depth + 1)
                    or self._tensorish(node.right, depth + 1))
        if isinstance(node, ast.UnaryOp):
            return self._tensorish(node.operand, depth + 1)
        if isinstance(node, ast.Compare):
            # identity tests (x is None) are host-safe on any object
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
                return False
            return (self._tensorish(node.left, depth + 1)
                    or any(self._tensorish(c, depth + 1)
                           for c in node.comparators))
        if isinstance(node, ast.BoolOp):
            return any(self._tensorish(v, depth + 1) for v in node.values)
        if isinstance(node, ast.Subscript):
            return self._tensorish(node.value, depth + 1)
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self._tensorish(e, depth + 1) for e in node.elts)
        if isinstance(node, ast.IfExp):
            return (self._tensorish(node.body, depth + 1)
                    or self._tensorish(node.orelse, depth + 1))
        return False

    def _track_assign(self, targets: Iterable[ast.AST], value: ast.AST):
        sc = self.scope
        if sc is None:
            return
        is_t = self._tensorish(value)
        for tgt in targets:
            if isinstance(tgt, ast.Name):
                if is_t:
                    sc.tensor_names.add(tgt.id)
                else:
                    sc.tensor_names.discard(tgt.id)
            elif isinstance(tgt, (ast.Tuple, ast.List)) and is_t:
                for e in tgt.elts:
                    if isinstance(e, ast.Name):
                        sc.tensor_names.add(e.id)

    # -- function defs ---------------------------------------------------
    def _check_mutable_defaults(self, node):
        bad = (ast.List, ast.Dict, ast.Set)
        defaults = list(node.args.defaults) + [
            d for d in node.args.kw_defaults if d is not None]
        for d in defaults:
            hit = isinstance(d, bad)
            if isinstance(d, ast.Call):
                dotted = _dotted(d.func) or ""
                hit = dotted in ("list", "dict", "set")
            if not hit:
                continue
            in_layer = bool(self._class_stack
                            and _is_layer_class(self._class_stack[-1]))
            layer_hot = in_layer and node.name in ("__init__", "forward")
            self.emit(
                "PTL006",
                f"mutable default argument on '{node.name}'"
                + (" (Layer.%s: shared across instances and recompile "
                   "caches)" % node.name if layer_hot else ""),
                d, severity=ERROR)

    def _visit_func(self, node):
        self._check_mutable_defaults(node)
        dec_traced = any(_decorator_marks_traced(d)
                         for d in node.decorator_list)
        line = self.lines[node.lineno - 1] if node.lineno - 1 < len(
            self.lines) else ""
        mark_traced = bool(_TRACED_MARK_RE.search(line))
        traced = (dec_traced or mark_traced or self.traced
                  or self.surface)
        in_layer = bool(self._class_stack
                        and _is_layer_class(self._class_stack[-1]))
        sc = _Scope(traced, in_layer, node.name)
        # parameters of traced functions are assumed tensor-carrying
        # UNLESS this is surface mode, where most params are config
        # scalars: there, only ensure_tensor/assignment marks them
        if dec_traced or mark_traced:
            for a in (node.args.posonlyargs + node.args.args
                      + node.args.kwonlyargs):
                if a.arg not in ("self", "cls"):
                    sc.tensor_names.add(a.arg)
        self._scopes.append(sc)
        for child in node.body:
            self.visit(child)
        self._scopes.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def visit_ClassDef(self, node):
        self._class_stack.append(node)
        self.generic_visit(node)
        self._class_stack.pop()

    def visit_Lambda(self, node):
        # lambdas inherit the enclosing traced-ness; no new scope
        self.visit(node.body)

    # -- statements ------------------------------------------------------
    def visit_Assign(self, node):
        self.visit(node.value)
        self._track_assign(node.targets, node.value)

    def visit_AnnAssign(self, node):
        if node.value is not None:
            self.visit(node.value)
            self._track_assign([node.target], node.value)

    def visit_AugAssign(self, node):
        self.visit(node.value)

    def visit_If(self, node):
        if self.traced and self._tensorish(node.test):
            self.emit("PTL003",
                      "Python 'if' on a Tensor-valued condition under "
                      "trace (host read; one SOT specialization per "
                      "branch path)", node)
        self.generic_visit(node)

    def visit_While(self, node):
        if self.traced and self._tensorish(node.test):
            self.emit("PTL003",
                      "Python 'while' on a Tensor-valued condition under "
                      "trace (host read per iteration; unrolled capture)",
                      node)
        self.generic_visit(node)

    def visit_IfExp(self, node):
        if self.traced and self._tensorish(node.test):
            self.emit("PTL003",
                      "conditional expression on a Tensor-valued "
                      "condition under trace (host read)", node)
        self.generic_visit(node)

    def visit_For(self, node):
        if self.traced and self._tensorish(node.iter):
            self.emit("PTL008",
                      "iteration over a Tensor under trace (per-element "
                      "host reads; capture unrolls with data size)", node)
        self.generic_visit(node)

    def visit_Assert(self, node):
        if self.traced and self._tensorish(node.test):
            self.emit("PTL002",
                      "assert on a Tensor-valued expression under trace "
                      "(bool() host read)", node)
        self.generic_visit(node)

    # -- calls -----------------------------------------------------------
    def visit_Call(self, node):
        dotted = _dotted(node.func)

        if self.traced:
            # PTL001 host-sync methods
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _HOST_SYNC_METHODS:
                self.emit("PTL001",
                          f".{node.func.attr}() host sync under trace "
                          "(graph break + value guard on the SOT path; "
                          "RuntimeError under whole-graph trace)",
                          node)
            # PTL002 host casts on tensorish args
            if isinstance(node.func, ast.Name) and \
                    node.func.id in _HOST_CASTS and node.args:
                if self._tensorish(node.args[0]):
                    self.emit("PTL002",
                              f"{node.func.id}() on a Tensor-valued "
                              "expression under trace (host "
                              "concretization)", node)
            # PTL004 np.* on tensorish args
            if dotted is not None and \
                    dotted.split(".")[0] in ("np", "numpy") and \
                    len(dotted.split(".")) > 1:
                if any(self._tensorish(a) for a in node.args):
                    self.emit("PTL004",
                              f"{dotted}() applied to a Tensor under "
                              "trace (eager host materialization; "
                              "falls off the captured graph)", node)
            # PTL005 in-place *_ ops
            if isinstance(node.func, ast.Attribute):
                attr = node.func.attr
                if attr.endswith("_") and not attr.endswith("__") and \
                        not attr.startswith("_"):
                    self.emit("PTL005",
                              f".{attr}() in-place op inside a captured "
                              "region (identity rebind mid-capture)",
                              node)
            # PTL007 impure host effects
            if dotted is not None:
                parts = tuple(dotted.split("."))
                if parts in _IMPURE_HOST_CALLS or (
                        len(parts) >= 3 and parts[-3] == "np"
                        and parts[-2] == "random") or (
                        parts[0] in ("np", "numpy")
                        and len(parts) == 3 and parts[1] == "random"):
                    self.emit("PTL007",
                              f"{dotted}() under trace: the value is "
                              "baked at record time and replayed "
                              "verbatim", node)
            # PTL009 print of a tensor
            if isinstance(node.func, ast.Name) and \
                    node.func.id == "print" and \
                    any(self._tensorish(a) for a in node.args):
                self.emit("PTL009",
                          "print() of a Tensor under trace (host sync "
                          "per step; prints a tracer under whole-graph "
                          "capture)", node)
            # PTL010 float64 literals flowing into ops
            for kw in node.keywords:
                if kw.arg in ("dtype",) and \
                        isinstance(kw.value, ast.Constant) and \
                        kw.value.value == "float64":
                    self.emit("PTL010",
                              "dtype='float64' under trace (no fast TPU "
                              "f64 path; promotion spreads through the "
                              "segment)", kw.value)
            for a in list(node.args):
                if isinstance(a, ast.Constant) and a.value == "float64":
                    self.emit("PTL010",
                              "'float64' literal under trace (no fast "
                              "TPU f64 path)", a)
            if dotted in ("np.float64", "numpy.float64", "jnp.float64"):
                self.emit("PTL010",
                          f"{dotted} under trace (no fast TPU f64 path)",
                          node)

        self.generic_visit(node)


_BROAD_EXC_NAMES = {"Exception", "BaseException"}
# calls that count as "the handler reported the failure"
_LOGGING_LEAVES = {"warn", "warning", "error", "exception", "critical",
                   "log", "debug", "info"}


def _is_broad_handler(handler: ast.ExceptHandler) -> bool:
    """Bare ``except:`` or one whose type (or any tuple member) is
    Exception/BaseException."""
    t = handler.type
    if t is None:
        return True
    types = t.elts if isinstance(t, ast.Tuple) else [t]
    for node in types:
        dotted = _dotted(node) or ""
        if dotted.split(".")[-1] in _BROAD_EXC_NAMES:
            return True
    return False


def _handler_reports(handler: ast.ExceptHandler) -> bool:
    """Does the handler body re-raise, or call a warn/log function?"""
    for node in ast.walk(ast.Module(body=handler.body, type_ignores=[])):
        if isinstance(node, ast.Raise):
            return True
        if isinstance(node, ast.Call):
            dotted = _dotted(node.func)
            if dotted and dotted.split(".")[-1] in _LOGGING_LEAVES:
                return True
    return False


class _ExceptionHygiene(ast.NodeVisitor):
    """PTL401: broad exception handlers that neither re-raise nor log,
    scoped to RESILIENCE_GLOBS files (resilience/, distributed/
    checkpoint/, inference/)."""

    def __init__(self, filename: str):
        self.filename = filename
        self.findings: List[Finding] = []

    def visit_Try(self, node):
        for handler in node.handlers:
            if _is_broad_handler(handler) and not _handler_reports(handler):
                what = "bare 'except:'" if handler.type is None else \
                    "broad 'except Exception'"
                self.findings.append(make_finding(
                    "PTL401",
                    f"{what} swallows the failure (no re-raise, no "
                    "warn/log) in resilience-critical code",
                    file=self.filename, line=handler.lineno,
                    col=handler.col_offset))
        self.generic_visit(node)


def is_resilience_path(path: str) -> bool:
    p = path.replace(os.sep, "/")
    return any(fnmatch.fnmatch(p, g) for g in RESILIENCE_GLOBS)


_RAW_TIMING_CALLS = {"time.time", "time.perf_counter",
                     "_time.time", "_time.perf_counter"}


class _TimingHygiene(ast.NodeVisitor):
    """PTL501: raw wall-clock reads in instrumented subsystems, scoped
    to TIMING_GLOBS files (tuning/, resilience/, inference/)."""

    def __init__(self, filename: str):
        self.filename = filename
        self.findings: List[Finding] = []

    def visit_Call(self, node):
        dotted = _dotted(node.func)
        if dotted in _RAW_TIMING_CALLS:
            self.findings.append(make_finding(
                "PTL501",
                f"{dotted}() in an instrumented subsystem bypasses "
                "observability.metrics (use a registry histogram's "
                ".time()/.observe() or events.span())",
                file=self.filename, line=node.lineno,
                col=node.col_offset))
        self.generic_visit(node)


def is_timing_path(path: str) -> bool:
    p = path.replace(os.sep, "/")
    return any(fnmatch.fnmatch(p, g) for g in TIMING_GLOBS)


# _OpRecord slots (static/capture.py) — assigning to these on anything
# but ``self``, or calling a mutator on the list/dict-valued ones,
# rewrites a shared record in place
_OPRECORD_ATTRS = {"fn", "kwargs", "inputs", "outputs", "multi_out",
                   "name"}
_OPRECORD_CONTAINER_ATTRS = {"kwargs", "inputs", "outputs"}
_MUTATOR_METHODS = {"append", "extend", "insert", "pop", "remove",
                    "clear", "sort", "reverse", "update", "setdefault",
                    "popitem"}


class _PassHygiene(ast.NodeVisitor):
    """PTL602: in-place _OpRecord mutation inside program-pass files
    (scoped to PASS_GLOBS).  Flags ``op.fn = ...`` / ``op.inputs[0] =
    ...`` / ``op.inputs.append(...)`` shapes on any receiver except
    ``self`` — passes rebind Program.ops with NEW records instead."""

    def __init__(self, filename: str):
        self.filename = filename
        self.findings: List[Finding] = []

    def _flag(self, node: ast.AST, what: str):
        self.findings.append(make_finding(
            "PTL602",
            f"{what} mutates a shared _OpRecord in place — build a new "
            "record and rebind Program.ops instead",
            file=self.filename, line=node.lineno, col=node.col_offset))

    def _check_target(self, tgt: ast.AST):
        if isinstance(tgt, ast.Attribute) and \
                tgt.attr in _OPRECORD_ATTRS and \
                not (isinstance(tgt.value, ast.Name)
                     and tgt.value.id in ("self", "cls")):
            self._flag(tgt, f"assignment to .{tgt.attr}")
        elif isinstance(tgt, ast.Subscript) and \
                isinstance(tgt.value, ast.Attribute) and \
                tgt.value.attr in _OPRECORD_CONTAINER_ATTRS:
            self._flag(tgt, f"item assignment into .{tgt.value.attr}")
        elif isinstance(tgt, (ast.Tuple, ast.List)):
            for e in tgt.elts:
                self._check_target(e)

    def visit_Assign(self, node):
        for tgt in node.targets:
            self._check_target(tgt)
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        self._check_target(node.target)
        self.generic_visit(node)

    def visit_AnnAssign(self, node):
        self._check_target(node.target)
        self.generic_visit(node)

    def visit_Call(self, node):
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in _MUTATOR_METHODS \
                and isinstance(f.value, ast.Attribute) \
                and f.value.attr in _OPRECORD_CONTAINER_ATTRS \
                and not (isinstance(f.value.value, ast.Name)
                         and f.value.value.id in ("self", "cls")):
            self._flag(node, f".{f.value.attr}.{f.attr}()")
        self.generic_visit(node)


def is_pass_path(path: str) -> bool:
    p = path.replace(os.sep, "/")
    return any(fnmatch.fnmatch(p, g) for g in PASS_GLOBS)


# PTL701: device-sync shapes that stall the serving batch pipeline
_SYNC_METHODS = {"item", "numpy", "tolist", "block_until_ready"}
_SYNC_CALLS = {"np.asarray", "np.array", "numpy.asarray",
               "numpy.array", "jax.device_get"}
_BOOL_CASTS = {"bool", "int", "float"}


class _ServingStepHygiene(ast.NodeVisitor):
    """PTL701: host syncs inside serving step-loop code paths, scoped
    to SERVING_GLOBS (hot names ``step``/``loop``/``fused``/``window``)
    and to the fused-window builders in models/generation.py (hot
    names ``fused``/``window``): flags
    ``.item()``/``.numpy()``/``.tolist()``/``.block_until_ready()``,
    ``np.asarray``/``np.array``/``jax.device_get`` calls, and
    ``finished.all()``-style reads steering an ``if``/``while`` or a
    bool/int/float cast.  The single per-window boundary read carries
    a reasoned noqa."""

    def __init__(self, filename: str,
                 hot_names: Tuple[str, ...] = SERVING_HOT_NAMES):
        self.filename = filename
        self.hot_names = tuple(hot_names)
        self.findings: List[Finding] = []
        self._depth = 0
        self._seen: Set[Tuple[int, int]] = set()

    def _flag(self, node: ast.AST, what: str):
        if (node.lineno, node.col_offset) in self._seen:
            return                         # bool(x.all()) inside an if
        self._seen.add((node.lineno, node.col_offset))
        self.findings.append(make_finding(
            "PTL701",
            f"{what} inside a serving step-loop code path is a host "
            "sync — it serializes the batch pipeline per token; keep "
            "values on device (the one admission-boundary read takes "
            "a reasoned noqa)",
            file=self.filename, line=node.lineno, col=node.col_offset))

    def _visit_func(self, node):
        name = node.name.lower()
        hot = any(k in name for k in self.hot_names)
        self._depth += 1 if hot else 0
        for child in node.body:
            self.visit(child)
        self._depth -= 1 if hot else 0

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    @staticmethod
    def _is_reduction_read(node: ast.AST) -> bool:
        return (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("all", "any")
                and not node.args and not node.keywords)

    def _check_test(self, test: ast.AST):
        for sub in ast.walk(test):
            if self._is_reduction_read(sub):
                self._flag(sub, f".{sub.func.attr}() in a branch "
                                "condition")

    def visit_If(self, node):
        if self._depth:
            self._check_test(node.test)
        self.generic_visit(node)

    def visit_While(self, node):
        if self._depth:
            self._check_test(node.test)
        self.generic_visit(node)

    def visit_Call(self, node):
        if self._depth:
            dotted = _dotted(node.func)
            if dotted in _SYNC_CALLS:
                self._flag(node, f"{dotted}()")
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _SYNC_METHODS \
                    and not node.args and not node.keywords:
                self._flag(node, f".{node.func.attr}()")
            elif isinstance(node.func, ast.Name) \
                    and node.func.id in _BOOL_CASTS and node.args \
                    and self._is_reduction_read(node.args[0]):
                # key the finding on the INNER read so an if-wrapped
                # bool(x.all()) is reported once
                self._flag(node.args[0], f"{node.func.id}(... "
                           f".{node.args[0].func.attr}())")
        self.generic_visit(node)


def is_serving_path(path: str) -> bool:
    p = path.replace(os.sep, "/")
    return any(fnmatch.fnmatch(p, g) for g in SERVING_GLOBS)


def is_generation_path(path: str) -> bool:
    p = path.replace(os.sep, "/")
    return any(fnmatch.fnmatch(p, g) for g in GENERATION_GLOBS)


# jnp/np array constructors whose default dtype follows the x64 flag
_UNPINNED_CONSTRUCTORS = {"zeros", "ones", "full", "empty", "arange",
                          "asarray", "array", "linspace", "eye"}
_CONSTRUCTOR_ROOTS = {"jnp", "np", "numpy"}
_DTYPE_LEAVES = {
    "bool", "bool_", "int8", "int16", "int32", "int64", "uint8",
    "uint16", "uint32", "uint64", "float16", "float32", "float64",
    "bfloat16", "complex64", "complex128", "dtype",
}
# bare builtins as a dtype argument resolve to f64/i64 under x64 — the
# hazard spelled differently, never a valid pin
_AMBIGUOUS_DTYPE_NAMES = {"float", "int"}


def _looks_like_dtype(node: ast.AST) -> Optional[bool]:
    """True: a pinned dtype argument; False: an ambiguous (float/int)
    one; None: not a dtype-shaped argument at all."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return True                      # explicit 'float32'/'int64'
    if isinstance(node, ast.Name):
        if node.id in _AMBIGUOUS_DTYPE_NAMES:
            return False
        return True if node.id in _DTYPE_LEAVES else None
    if isinstance(node, ast.Attribute):
        leaf = node.attr
        if leaf in _DTYPE_LEAVES:
            return True                  # jnp.float32, x.dtype, ...
        return None
    return None


class _KernelLiteralHygiene(ast.NodeVisitor):
    """PTL603: unpinned array-constructor literals inside Pallas kernel
    bodies (functions taking ``*_ref`` refs), scoped to KERNEL_GLOBS.
    With jax_enable_x64 globally on, ``jnp.zeros(shape)`` /
    ``jnp.arange(n)`` traced under an outer jit materialize f64/i64."""

    def __init__(self, filename: str):
        self.filename = filename
        self.findings: List[Finding] = []
        self._kernel_depth = 0

    def _visit_func(self, node):
        is_kernel = any(a.arg.endswith("_ref")
                        for a in (node.args.posonlyargs + node.args.args
                                  + node.args.kwonlyargs))
        self._kernel_depth += 1 if is_kernel else 0
        for child in node.body:
            self.visit(child)
        self._kernel_depth -= 1 if is_kernel else 0

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def visit_Call(self, node):
        if self._kernel_depth:
            dotted = _dotted(node.func)
            parts = (dotted or "").split(".")
            if len(parts) == 2 and parts[0] in _CONSTRUCTOR_ROOTS \
                    and parts[1] in _UNPINNED_CONSTRUCTORS:
                verdicts = [_looks_like_dtype(a) for a in node.args]
                for kw in node.keywords:
                    if kw.arg == "dtype":
                        verdicts.append(_looks_like_dtype(kw.value))
                if any(v is False for v in verdicts):
                    self.findings.append(make_finding(
                        "PTL603",
                        f"{dotted}() in a Pallas kernel body pins its "
                        "dtype with bare float/int — that resolves to "
                        "f64/i64 under the global x64 default; use the "
                        "explicit 32-bit jnp dtype",
                        file=self.filename, line=node.lineno,
                        col=node.col_offset))
                elif not any(v is True for v in verdicts):
                    self.findings.append(make_finding(
                        "PTL603",
                        f"{dotted}() in a Pallas kernel body has no "
                        "pinned dtype — under an outer jit with the "
                        "global x64 default this materializes "
                        "f64/i64; pass jnp.float32/jnp.int32 "
                        "explicitly",
                        file=self.filename, line=node.lineno,
                        col=node.col_offset))
        self.generic_visit(node)


def is_kernel_path(path: str) -> bool:
    p = path.replace(os.sep, "/")
    return any(fnmatch.fnmatch(p, g) for g in KERNEL_GLOBS)


def _collect_noqa(source: str) -> Dict[int, Optional[Set[str]]]:
    """line -> None (bare noqa: suppress all) | set of codes.

    A suppression like ``noqa: PTL801,PTL803 reason text`` (after the
    hash) takes any number of comma/space-separated codes; token
    collection stops at the first non-code token so trailing prose
    never dilutes the set.  A colon followed by no valid code
    suppresses nothing (typo-safe), while a bare noqa suppresses
    everything on the line.  Only real COMMENT tokens count — the same
    text inside a docstring (e.g. this one) is documentation, not a
    suppression.
    """
    comments = []
    try:
        for tok in tokenize.generate_tokens(
                io.StringIO(source).readline):
            if tok.type == tokenize.COMMENT:
                comments.append((tok.start[0], tok.string))
    except (tokenize.TokenError, IndentationError, SyntaxError,
            ValueError):
        # unparseable blob: fall back to the raw line scan so the
        # suppression surface degrades rather than vanishing
        comments = [(i, line)
                    for i, line in enumerate(source.splitlines(), 1)
                    if "#" in line]
    out: Dict[int, Optional[Set[str]]] = {}
    for i, line in comments:
        m = _NOQA_RE.search(line)
        if not m:
            continue
        if m.group("colon") is None:
            out[i] = None                  # bare noqa
            continue
        raw = m.group("raw").strip()
        if not raw:
            out[i] = None                  # '# noqa:' == bare noqa
            continue
        codes: Set[str] = set()
        for tok in re.split(r"[,\s]+", raw):
            if _NOQA_CODE_RE.fullmatch(tok):
                codes.add(tok.upper())
            else:
                break                      # reason text starts here
        out[i] = codes
    return out


def is_surface_path(path: str) -> bool:
    p = path.replace(os.sep, "/")
    if any(fnmatch.fnmatch(p, g) for g in SURFACE_EXEMPT):
        return False
    return any(fnmatch.fnmatch(p, g) for g in SURFACE_GLOBS)


def lint_source(source: str, filename: str = "<string>",
                surface: Optional[bool] = None,
                select: Optional[Set[str]] = None,
                ignore: Optional[Set[str]] = None,
                respect_noqa: bool = True) -> List[Finding]:
    """Lint one source blob.  ``surface=None`` infers from the path;
    ``select`` keeps only the named codes, ``ignore`` drops them
    (ignore wins when a code appears in both).  ``respect_noqa=False``
    reports suppressed findings too — the stale-noqa sweep diffs the
    two views."""
    if surface is None:
        surface = is_surface_path(filename)
    try:
        tree = ast.parse(source, filename=filename)
    except SyntaxError as e:
        return [make_finding("PTL000",
                             f"could not parse: {e.msg}",
                             file=filename, line=e.lineno or 0,
                             severity=WARNING)]
    linter = _Linter(filename, source.splitlines(), surface)
    linter.visit(tree)
    findings = list(linter.findings)
    if is_resilience_path(filename):
        hygiene = _ExceptionHygiene(filename)
        hygiene.visit(tree)
        findings.extend(hygiene.findings)
    if is_timing_path(filename):
        timing = _TimingHygiene(filename)
        timing.visit(tree)
        findings.extend(timing.findings)
    if is_pass_path(filename):
        passes = _PassHygiene(filename)
        passes.visit(tree)
        findings.extend(passes.findings)
    if is_kernel_path(filename):
        kernels = _KernelLiteralHygiene(filename)
        kernels.visit(tree)
        findings.extend(kernels.findings)
    if is_serving_path(filename):
        serving = _ServingStepHygiene(filename)
        serving.visit(tree)
        findings.extend(serving.findings)
    if is_generation_path(filename):
        gen = _ServingStepHygiene(filename,
                                  hot_names=GENERATION_HOT_NAMES)
        gen.visit(tree)
        findings.extend(gen.findings)
    if is_shard_path(filename):
        findings.extend(shard_findings_source(source, filename, tree=tree))
    if is_strategy_path(filename):
        findings.extend(
            strategy_findings_source(source, filename, tree=tree))
    if is_concurrency_path(filename):
        findings.extend(
            concheck_findings_source(source, filename, tree=tree))
    noqa = _collect_noqa(source) if respect_noqa else {}
    out = []
    for f in findings:
        supp = noqa.get(f.line, "missing")
        if supp is None:               # bare noqa
            continue
        if isinstance(supp, set) and f.code.upper() in supp:
            continue
        if select is not None and f.code not in select:
            continue
        if ignore is not None and f.code in ignore:
            continue
        out.append(f)
    out.sort(key=lambda f: (f.file, f.line, f.col, f.code))
    return out


# every code lint_source can emit with a trustworthy line number — the
# stale-noqa sweep only judges these; whole-repo passes (registry,
# cost-model, PTL502/601) have no per-line re-fire to compare against
LINT_SOURCE_CODES: Set[str] = frozenset({
    "PTL000", "PTL001", "PTL002", "PTL003", "PTL004", "PTL005",
    "PTL006", "PTL007", "PTL008", "PTL009", "PTL010",
    "PTL401", "PTL501", "PTL602", "PTL603", "PTL701",
    "PTL801", "PTL802", "PTL803", "PTL804",
    "PTL901", "PTL902", "PTL903", "PTL904",
})


def stale_noqa_paths(paths: Sequence[str]) -> List[Finding]:
    """PTL905: every ``# noqa: PTLxxx`` whose rule no longer fires on
    that line (``python -m paddle_tpu.analysis --stale-noqa``).

    Bare ``# noqa`` comments and codes outside
    :data:`LINT_SOURCE_CODES` (whole-repo passes, foreign linters like
    BLE001) are not judged — the sweep only reports suppressions it
    can re-check exactly, so a PTL905 is always actionable.
    """
    findings: List[Finding] = []
    for path in iter_python_files(paths):
        try:
            with open(path, "r", encoding="utf-8") as fh:
                source = fh.read()
        except OSError:
            continue
        noqa = _collect_noqa(source)
        if not any(codes for codes in noqa.values()
                   if codes is not None):
            continue
        fired: Dict[int, Set[str]] = {}
        for f in lint_source(source, filename=path, respect_noqa=False):
            fired.setdefault(f.line, set()).add(f.code)
        if is_concurrency_path(path):
            # PTL902 normally reports ONE site per attribute; the
            # suppressions live per-line, so liveness needs the
            # all-candidate-sites view or every noqa after the first
            # would read as stale
            for f in concheck_findings_source(source, path,
                                              all_sites=True):
                fired.setdefault(f.line, set()).add(f.code)
        for line, codes in sorted(noqa.items()):
            if codes is None:
                continue
            for code in sorted(codes):
                if code not in LINT_SOURCE_CODES:
                    continue
                if code not in fired.get(line, ()):
                    findings.append(make_finding(
                        "PTL905",
                        "stale suppression: %s no longer fires on this "
                        "line — delete the noqa (it would silence a "
                        "future real finding)" % code,
                        file=path, line=line))
    return findings


def lint_file(path: str, select: Optional[Set[str]] = None,
              surface: Optional[bool] = None,
              ignore: Optional[Set[str]] = None) -> List[Finding]:
    with open(path, "r", encoding="utf-8") as fh:
        src = fh.read()
    return lint_source(src, filename=path, surface=surface, select=select,
                       ignore=ignore)


def iter_python_files(paths: Sequence[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = [d for d in dirs
                           if d not in ("__pycache__", ".git",
                                        ".jax_cache")]
                for f in sorted(files):
                    if f.endswith(".py"):
                        out.append(os.path.join(root, f))
        elif p.endswith(".py"):
            out.append(p)
    return out


def lint_paths(paths: Sequence[str], select: Optional[Set[str]] = None,
               surface: Optional[bool] = None,
               ignore: Optional[Set[str]] = None) -> List[Finding]:
    findings: List[Finding] = []
    for f in iter_python_files(paths):
        findings.extend(lint_file(f, select=select, surface=surface,
                                  ignore=ignore))
    return findings
