"""paddle_tpu — a TPU-native deep learning framework with the PaddlePaddle
API surface, re-founded on JAX/XLA/Pallas.

Architecture (see SURVEY.md §7): eager UX on a tape over jnp ops;
`to_static`≅jax.jit; PIR≅StableHLO; CINN≅XLA+Pallas; ProcessGroupNCCL≅
ICI/DCN collectives; auto_parallel≅GSPMD.
"""
from __future__ import annotations

import os as _os

# x64 must be on before any jax computation: paddle's default int dtype is
# int64 and float64 tensors exist.  Creation ops pass explicit dtypes so the
# framework default float stays float32.
import jax as _jax
_jax.config.update("jax_enable_x64", True)
# The one place that decides where compiled programs persist.  When
# JAX_COMPILATION_CACHE_DIR is set the directory is JAX's own business
# and nothing in this package names another; otherwise it is a fixed
# path beside the checkout (the path is part of the cache key, so it
# must not move between runs or processes).
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), ".jax_cache"))

__version__ = "0.3.0"  # kept in sync with paddle.version.full_version

from . import flags as _flags_mod
from .flags import set_flags, get_flags

from . import dtype as _dtype_mod
from .dtype import (DType, bool_, uint8, int8, int16, int32, int64, float16,
                    bfloat16, float32, float64, complex64, complex128,
                    set_default_dtype, get_default_dtype)
bool = bool_  # paddle.bool

from . import device
from .device import (CPUPlace, CUDAPlace, TPUPlace, XPUPlace, CustomPlace,
                     CUDAPinnedPlace, set_device, get_device,
                     is_compiled_with_cuda, is_compiled_with_rocm,
                     is_compiled_with_xpu, is_compiled_with_cinn,
                     is_compiled_with_distribute)

from .core.tensor import Tensor, to_tensor, is_tensor
from .core.autograd_state import no_grad, enable_grad, is_grad_enabled, set_grad_enabled
from .core import dispatch as _dispatch
from .core.dispatch import grad

from . import errors
from .random_state import seed, get_rng_state, set_rng_state, Generator
from .random_state import get_rng_state_tracker as _get_rng_state_tracker

from .framework.param_attr import ParamAttr
from .framework.io import save, load
from .regularizer import L1Decay, L2Decay

# op surface
from .tensor import *  # noqa: F401,F403
from .tensor import einsum
from .tensor.creation import create_parameter
from .tensor.search import topk, where, nonzero, argmax, argmin, argsort, sort

# static mode toggles (ref: paddle.enable_static/disable_static)
def enable_static():
    from . import static as _static
    _static.enable_static()


def disable_static():
    from . import static as _static
    _static.disable_static()


# static check helpers
def in_dynamic_mode() -> bool:
    from .static import in_static_mode as _ism
    return not _ism()


def in_static_mode() -> bool:
    return not in_dynamic_mode()


in_dygraph_mode = in_dynamic_mode
in_dynamic_or_pir_mode = in_dynamic_mode


def iinfo(dtype):
    """ref: paddle.iinfo — integer dtype limits."""
    import numpy as _np
    from .dtype import convert_dtype
    return _np.iinfo(convert_dtype(dtype).numpy_dtype)


def finfo(dtype):
    """ref: paddle.finfo — float dtype limits (bf16-aware via ml_dtypes)."""
    import numpy as _np
    from .dtype import convert_dtype
    d = convert_dtype(dtype)
    if d.name == "bfloat16":
        import ml_dtypes
        return ml_dtypes.finfo(ml_dtypes.bfloat16)
    return _np.finfo(d.numpy_dtype)


def get_cudnn_version():
    return None


# subpackage re-exports grow here as each build stage lands (SURVEY.md §7).
_SUBPACKAGES = ["nn", "optimizer", "autograd", "amp", "io", "metric",
                "linalg", "fft", "signal", "framework", "jit", "static",
                "distributed", "distribution", "vision", "hapi", "incubate",
                "utils", "profiler", "sparse", "text", "audio",
                "quantization", "onnx", "version", "inference",
                "hub", "sysconfig", "multiprocessing", "callbacks",
                "geometric", "tuning", "observability"]

# an env-ingested FLAGS_observability_dir configured the event log
# while the core modules were still importing; now that they exist,
# install the dispatch/host-read hooks (no-op when the flag is unset)
from .observability import events as _obs_events
_obs_events._ensure_hooks()


def __getattr__(name):
    # paddle.Model / paddle.summary live in hapi (ref: paddle/__init__.py)
    if name in ("Model", "summary"):
        from .hapi import Model, summary
        globals().update(Model=Model, summary=summary)
        return globals()[name]
    # lazy subpackage import keeps partially-built stages from breaking the core
    if name in _SUBPACKAGES:
        import importlib
        if name == "callbacks":   # paddle.callbacks = hapi.callbacks (ref)
            mod = importlib.import_module(".hapi.callbacks", __name__)
        else:
            mod = importlib.import_module(f".{name}", __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")
