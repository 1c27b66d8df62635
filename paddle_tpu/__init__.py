"""paddle_tpu — a TPU-native deep learning framework.

A from-scratch framework with the capabilities of the reference
(PaddlePaddle, /root/reference) re-designed for TPU: JAX/XLA is the compute
and compilation substrate, Pallas provides fused kernels, and distribution
is expressed as sharding over a ``jax.sharding.Mesh`` rather than explicit
communication ops.

Two execution modes mirror the reference's dygraph/static split:
- **eager**: ``Tensor`` wrappers with a tape-based autograd (imperative UX);
- **traced**: the same model code jit-compiled over a parameter pytree
  (``paddle_tpu.jit`` / hapi ``Model`` / fleet use this path for speed).
"""

from . import core  # isort: skip  (must init flags first)
from . import tensor as tensor_api
from .core import (Parameter, Tensor, get_default_dtype, get_device, get_flags,  # noqa: F401
                   no_grad, seed, set_default_dtype, set_device, set_flags, to_tensor)
from .core.autograd import enable_grad, is_grad_enabled, set_grad_enabled  # noqa: F401
from .core.device import (device_count, is_compiled_with_cuda,  # noqa: F401
                          is_compiled_with_tpu, synchronize)
from .core.dtype import (bfloat16, bool_, complex64, complex128, float16, float32,  # noqa: F401
                         float64, int8, int16, int32, int64, uint8)
from .core.rng import get_rng_state, set_rng_state  # noqa: F401
from .tensor import *  # noqa: F401,F403
from .tensor import cast, is_tensor, rank, shape  # noqa: F401

__version__ = "0.1.0"

bool = bool_  # noqa: A001
reverse = flip  # noqa: F405 — fluid-era alias (reference fluid/layers reverse)


def grad(outputs, inputs, grad_outputs=None, retain_graph=None, create_graph=False,
         only_inputs=True, allow_unused=False, no_grad_vars=None):
    """``paddle.grad`` parity (reference: imperative/partial_grad_engine.cc).

    Computes grads of ``outputs`` w.r.t. ``inputs`` without touching ``.grad``.
    """
    from .core import autograd as _autograd
    outputs = outputs if isinstance(outputs, (list, tuple)) else [outputs]
    inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    saved = [(t._grad, t._node) for t in inputs]
    for t in inputs:
        t._grad = None
    capture = {id(t): t for t in inputs}
    try:
        for i, o in enumerate(outputs):
            g = None if grad_outputs is None else grad_outputs[i]
            _autograd.backward(o, g, retain_graph=True, capture=capture,
                               accumulate_leaves=False)
        results = []
        for t, (old, _) in zip(inputs, saved):
            if t._grad is None:
                if not allow_unused:
                    raise RuntimeError("one of the inputs received no gradient; "
                                       "pass allow_unused=True to permit this")
                results.append(None)
            else:
                results.append(Tensor(t._grad))
    finally:
        for t, (old, node) in zip(inputs, saved):
            t._grad = old
    return results


# Submodules imported lazily to keep import time low and avoid cycles.
_LAZY = ("nn", "optimizer", "amp", "metric", "io", "vision", "distributed", "jit",
         "static", "hapi", "ops", "models", "distribution", "profiler", "text",
         "incubate", "utils", "autograd", "regularizer", "callbacks", "linalg", "fft",
         "signal", "sparse", "onnx", "device", "framework", "inference",
         "quantization", "compat", "sysconfig", "hub", "reader", "dataset",
         "serving", "telemetry", "gateway", "faults", "simulation",
         "autoscaler")


def __getattr__(name):
    if name in _LAZY:
        import importlib
        mod = importlib.import_module(f".{name}", __name__)
        globals()[name] = mod
        return mod
    alias = _TOP_ALIASES.get(name)
    if alias is not None:
        import importlib
        obj = getattr(importlib.import_module(alias[0], __name__), alias[1])
        globals()[name] = obj
        return obj
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")


def save(obj, path, protocol=4, **configs):
    from .framework import io as _io
    return _io.save(obj, path, protocol=protocol, **configs)


def load(path, **configs):
    from .framework import io as _io
    return _io.load(path, **configs)


def summary(net, input_size=None, dtypes=None, input=None):
    from .hapi.model_summary import summary as _summary
    return _summary(net, input_size, dtypes, input)


def flops(net, input_size, custom_ops=None, print_detail=False):
    from .hapi.dynamic_flops import flops as _flops
    return _flops(net, input_size, custom_ops, print_detail)


def enable_static():
    from . import static as _static
    _static._enable()


def disable_static():
    from . import static as _static
    _static._disable()


def in_dynamic_mode():
    from . import static as _static
    return not _static._enabled()


def in_dygraph_mode():
    """Legacy alias (reference fluid.framework.in_dygraph_mode)."""
    return in_dynamic_mode()


enable_dygraph = disable_static
disable_dygraph = enable_static


# ------------------------------------------------------------------ places
def CPUPlace():
    from .core.device import Place
    return Place("cpu")


def TPUPlace(dev_id: int = 0):
    from .core.device import Place
    return Place(f"tpu:{dev_id}")


def CUDAPlace(dev_id: int = 0):
    raise RuntimeError(
        "paddle_tpu has no CUDA devices; use paddle.TPUPlace()/CPUPlace() or "
        "paddle.set_device('tpu')")


def CUDAPinnedPlace():
    raise RuntimeError("paddle_tpu has no CUDA pinned memory; host numpy "
                       "arrays transfer via device_put")


def NPUPlace(dev_id: int = 0):
    raise RuntimeError("paddle_tpu is not compiled with NPU support")


def XPUPlace(dev_id: int = 0):
    raise RuntimeError("paddle_tpu is not compiled with XPU support")


# ------------------------------------------------- legacy/top-level aliases
def get_cudnn_version():
    return None


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_npu() -> bool:
    return False


def get_cuda_rng_state():
    """No CUDA generators in this build; returns [] (shape-compatible with
    the reference's per-device state list)."""
    return []


def set_cuda_rng_state(state_list):
    if state_list:
        raise ValueError("no CUDA generators exist in a TPU build")


def disable_signal_handler():
    """The reference unhooks its C++ signal handlers; none are installed
    here, so this is a documented no-op."""


def monkey_patch_math_varbase():
    """Tensor operator methods are installed at class definition in this
    framework; retained as a no-op for API parity."""


def monkey_patch_variable():
    """See monkey_patch_math_varbase."""


def tolist(x):
    return x.tolist() if hasattr(x, "tolist") else list(x)


def crop_tensor(x, shape=None, offsets=None, name=None):
    from .tensor import crop
    return crop(x, shape=shape, offsets=offsets)


def create_parameter(shape, dtype, name=None, attr=None, is_bias=False,
                     default_initializer=None):
    from .static import create_parameter as _cp
    return _cp(shape, dtype, name=name, attr=attr, is_bias=is_bias,
               default_initializer=default_initializer)


# binds the NAME to the function after the submodule import, so
# ``paddle.batch`` is the callable (the submodule stays importable as
# ``paddle_tpu.batch`` via sys.modules)
from .batch import batch  # noqa: E402,F401

# name → (module, attr) resolved on first access through __getattr__
import numpy as _np  # noqa: E402

dtype = _np.dtype  # paddle.dtype: dtype objects are numpy/jnp dtypes here

_TOP_ALIASES = {
    "Model": (".hapi", "Model"),
    "DataParallel": (".distributed", "DataParallel"),
    "ParamAttr": (".framework.param_attr", "ParamAttr"),
    "VarBase": (".core.tensor", "Tensor"),   # legacy dygraph tensor name
}
