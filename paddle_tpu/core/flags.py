"""Global flag registry.

TPU-native analog of the reference's gflags tier
(paddle/fluid/platform/flags.cc:48+, pybind/global_value_getter_setter.cc) and
``paddle.set_flags``/``get_flags``.  One flat dict, seeded from ``FLAGS_*``
environment variables at import, mutable at runtime.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Iterable, Mapping, Optional

_REGISTRY: Dict[str, "Flag"] = {}


class Flag:
    __slots__ = ("name", "value", "default", "help")

    def __init__(self, name: str, default: Any, help: str = ""):
        self.name = name
        self.default = default
        self.value = default
        self.help = help


def define_flag(name: str, default: Any, help: str = "") -> None:
    if name in _REGISTRY:
        return
    flag = Flag(name, default, help)
    env = os.environ.get(name)
    if env is not None:
        flag.value = _coerce(env, default)
    _REGISTRY[name] = flag


def _coerce(text: str, like: Any) -> Any:
    if isinstance(like, bool):
        return text.lower() in ("1", "true", "yes", "on")
    if isinstance(like, int):
        return int(text)
    if isinstance(like, float):
        return float(text)
    return text


def set_flags(flags: Mapping[str, Any]) -> None:
    """Set one or more registered flags (``paddle.set_flags`` parity)."""
    for name, value in flags.items():
        if name not in _REGISTRY:
            define_flag(name, value)
        else:
            _REGISTRY[name].value = value


def get_flags(names: Optional[Iterable[str]] = None) -> Dict[str, Any]:
    """Read flags (``paddle.get_flags`` parity)."""
    if names is None:
        return {k: f.value for k, f in _REGISTRY.items()}
    if isinstance(names, str):
        names = [names]
    return {n: _REGISTRY[n].value for n in names}


def flag(name: str) -> Any:
    return _REGISTRY[name].value


# Core flags (subset of the reference's 51 exported flags that are meaningful
# on TPU; the CUDA/cuDNN knobs have no analog).
define_flag("FLAGS_check_nan_inf", False, "Check every op output for NaN/Inf (eager mode).")
define_flag("FLAGS_use_pallas_kernels", True, "Use Pallas fused kernels where available.")
define_flag("FLAGS_allocator_strategy", "xla", "Kept for API parity; XLA owns allocation on TPU.")
define_flag("FLAGS_default_dtype", "float32", "Default floating point dtype.")
define_flag("FLAGS_seed", 0, "Global random seed.")
define_flag("FLAGS_eager_log_ops", False, "Log every eagerly dispatched op (debug tracing).")
define_flag("FLAGS_benchmark", False, "Block on every eager op result (perf debugging).")
define_flag("FLAGS_eager_nudge_after", 20000,
            "Warn once after this many consecutive grad-recording eager "
            "dispatches with no jit step (0 disables).")
define_flag("FLAGS_paged_attn_interpret", False,
            "Run the paged-attention decode kernel in Pallas interpret "
            "mode (CPU CI of the in-kernel table walk).")
