"""Shared helpers: framework sniffing, pytree/device-array utilities, dataclass synthesis.

Reference parity: ``unionml/utils.py:63-76`` (framework sniffers, ``module_is_installed``).
The stage-wrapping half of the reference's utils module lives in
:mod:`unionml_tpu.stage`. TPU-native additions: device-array conversion used by the
default Dataset pipeline and JSON-able dataclass synthesis replacing ``dataclasses_json``.
"""

import importlib
import os
from dataclasses import _MISSING_TYPE, MISSING, asdict, field, fields, is_dataclass, make_dataclass
from inspect import Parameter, signature
from pathlib import Path
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple, Type

import jax
import numpy as np

_EMPTY = Parameter.empty


def is_pytorch_model(model_type: Optional[type]) -> bool:
    """True when ``model_type`` is a torch ``nn.Module`` subclass (``utils.py:63-64``)."""
    if model_type is None or not isinstance(model_type, type):
        return False
    return any(base.__module__.startswith("torch") for base in model_type.__mro__)


def is_keras_model(model_type: Optional[type]) -> bool:
    """True when ``model_type`` is a keras model subclass (``utils.py:67-68``)."""
    if model_type is None or not isinstance(model_type, type):
        return False
    return any(base.__module__.startswith(("keras", "tensorflow.python.keras")) for base in model_type.__mro__)


def is_flax_module(model_type: Optional[type]) -> bool:
    """True when ``model_type`` is a flax ``nn.Module`` subclass — a jax-native model family."""
    if model_type is None or not isinstance(model_type, type):
        return False
    return any(base.__module__.startswith("flax") for base in model_type.__mro__)


def is_sklearn_model(obj_or_type: Any) -> bool:
    try:
        import sklearn.base
    except ImportError:  # pragma: no cover
        return False
    if isinstance(obj_or_type, type):
        return issubclass(obj_or_type, sklearn.base.BaseEstimator)
    return isinstance(obj_or_type, sklearn.base.BaseEstimator)


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at one stable directory.

    Every entry point that compiles (``unionml-tpu serve``, ``run_app``,
    ``fit``, the backend worker, the bench scripts, ``chip_smoke.py``, the
    test suite) calls this before its first compile. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this sets no
    directory of its own; where it is not, the cache goes to
    ``<checkout>/.jax_cache`` — a fixed, git-ignored path, never a temp name,
    because the directory is part of what a later process must find again.
    Returns the directory in use.

    The compile-time floor for caching drops to 0: a server start replays
    dozens of sub-second programs (the engine's point updates and scatters)
    that JAX's 1 s default would recompile on every start.
    """
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if cache_dir:
        return cache_dir
    cache_dir = str(Path(__file__).resolve().parents[2] / ".jax_cache")
    if jax.config.jax_compilation_cache_dir != cache_dir:
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # a process that compiled before this call decided "no cache" once and
        # for all; make it look again
        compilation_cache.reset_cache()
    return cache_dir


def module_is_installed(module: str) -> bool:
    """``utils.py:71-76`` parity."""
    try:
        importlib.import_module(module)
        return True
    except ImportError:
        return False


def pick_free_port() -> int:
    """Reserve an ephemeral localhost port (bind-probe; small TOCTOU window applies)."""
    import socket

    with socket.socket() as probe:
        probe.bind(("127.0.0.1", 0))
        return probe.getsockname()[1]


def to_device_arrays(*arrays: Any, dtype: Any = None) -> Tuple[jax.Array, ...]:
    """Convert host data (pandas / numpy / lists) to device arrays.

    This is the host->device boundary of the default data pipeline: pandas objects go
    through ``.to_numpy()`` then ``jax.device_put``. On TPU, float64 numpy data is cast
    to float32 unless ``dtype`` says otherwise (x64 is disabled by default in jax).
    """
    import jax.numpy as jnp

    out = []
    for array in arrays:
        if isinstance(array, dict):
            # multi-input features (tokenized models): convert each value, keep the dict
            out.append({k: to_device_arrays(v, dtype=dtype)[0] for k, v in array.items()})
            continue
        if hasattr(array, "to_numpy"):
            array = array.to_numpy()
        array = np.asarray(array)
        if dtype is not None:
            array = array.astype(dtype)
        elif array.dtype == np.float64:
            array = array.astype(np.float32)
        out.append(jnp.asarray(array))
    return tuple(out)


def make_json_dataclass(name: str, field_specs: Sequence[Tuple], bases: Tuple[type, ...] = ()) -> Type:
    """``make_dataclass`` with ``to_dict``/``from_dict``/``to_json``/``from_json`` methods.

    Stands in for the reference's ``dataclasses_json`` decoration of synthesized kwargs
    dataclasses (``unionml/dataset.py:251``, ``model.py:201-203``) without the external
    dependency.
    """
    import json

    cls = make_dataclass(name, field_specs, bases=bases)

    def to_dict(self) -> Dict[str, Any]:
        return asdict(self)

    @classmethod
    def from_dict(cls_, data: Mapping[str, Any]):
        names = {f.name for f in fields(cls_)}
        return cls_(**{k: v for k, v in data.items() if k in names})

    def to_json(self) -> str:
        return json.dumps(asdict(self))

    @classmethod
    def from_json(cls_, raw: str):
        return cls_.from_dict(json.loads(raw))

    cls.to_dict = to_dict
    cls.from_dict = from_dict
    cls.to_json = to_json
    cls.from_json = from_json
    return cls


def kwargs_field_specs(
    fn: Callable,
    default_overrides: Optional[Mapping[str, Any]] = None,
    skip_first: int = 1,
) -> List[Tuple]:
    """Field specs for a kwargs dataclass synthesized from ``fn``'s trailing parameters.

    Mirrors the synthesis at ``unionml/dataset.py:240-280``: the first ``skip_first``
    parameters (the data argument) are dropped; defaults come from ``default_overrides``
    first, then the signature.
    """
    default_overrides = default_overrides or {}
    specs: List[Tuple] = []
    for index, param in enumerate(signature(fn).parameters.values()):
        if index < skip_first:
            continue
        default = default_overrides.get(param.name, param.default)
        annotation = param.annotation if param.annotation is not _EMPTY else Any
        if default is _EMPTY:
            specs.append((param.name, annotation))
        elif isinstance(default, (list, dict, set)):
            specs.append((param.name, annotation, field(default_factory=lambda d=default: d)))
        else:
            specs.append((param.name, annotation, field(default=default)))
    return specs
