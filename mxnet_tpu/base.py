"""Foundations: error types, dtype handling, naming utilities.

TPU-native re-design of the roles played in the reference by
``3rdparty/dmlc-core`` (logging / CHECK macros / parameter descriptors) and
``include/mxnet/base.h``.  There is no C ABI here (reference
``src/c_api/c_api.cc:?``): the framework is Python-first over jax, so errors
are ordinary Python exceptions rather than per-thread error strings fetched
via ``MXGetLastError``.
"""
from __future__ import annotations

import os
from typing import Any, Sequence

import numpy as np


class MXNetError(RuntimeError):
    """Framework error type (reference: ``dmlc::Error`` surfaced as
    ``mxnet.base.MXNetError`` via the C ABI, python/mxnet/base.py:?)."""


#: Persistent XLA compile cache used when ``JAX_COMPILATION_CACHE_DIR`` does
#: not place it elsewhere: one fixed, git-ignored directory beside the
#: package.  The path is part of jax's cache key, so it must never move
#: between runs (no tempfile, pid or clock in it).
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def configure_compile_cache() -> None:
    """Point jax's persistent compilation cache at a directory that
    survives the process, before anything compiles.  A chip session
    starts with no compiled code and a full-width step costs minutes to
    compile, so every executable is admitted (jax's default skips
    compiles under one second — the hundreds of small eager-op programs
    a cold start pays for one by one — and has no size floor).  ``JAX_COMPILATION_CACHE_DIR``
    wins when set: jax reads it itself and no directory is set here."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def check(cond: bool, msg: str = "") -> None:
    """CHECK-style assertion (reference ``dmlc/logging.h`` ``CHECK(x)``)."""
    if not cond:
        raise MXNetError(msg or "Check failed")


# --- dtype handling ---------------------------------------------------------
# The reference's mshadow type codes (mshadow/base.h:?): a stable int code per
# dtype crossing the C ABI.  We keep numpy dtypes as the canonical currency and
# accept strings / numpy types / jax dtypes everywhere.

_DTYPE_ALIASES = {
    "float32": np.float32,
    "float64": np.float64,
    "float16": np.float16,
    "bfloat16": None,  # filled lazily from ml_dtypes via jnp
    "uint8": np.uint8,
    "int8": np.int8,
    "int32": np.int32,
    "int64": np.int64,
    "bool": np.bool_,
}


def resolve_dtype(dtype: Any):
    """Normalise a user-supplied dtype to a numpy dtype object."""
    if dtype is None:
        return None
    if isinstance(dtype, str):
        if dtype == "bfloat16":
            import ml_dtypes  # ships with jax

            return np.dtype(ml_dtypes.bfloat16)
        if dtype not in _DTYPE_ALIASES:
            raise MXNetError(f"unknown dtype {dtype!r}")
        return np.dtype(_DTYPE_ALIASES[dtype])
    return np.dtype(dtype)


def dtype_name(dtype: Any) -> str:
    """Stable string name for a dtype (used in param serialization)."""
    return np.dtype(dtype).name


# --- shape utilities --------------------------------------------------------

def normalize_shape(shape) -> tuple:
    if shape is None:
        return None
    if isinstance(shape, (int, np.integer)):
        return (int(shape),)
    return tuple(int(s) for s in shape)


def getenv_int(name: str, default: int) -> int:
    """dmlc::GetEnv equivalent; the reference exposes ~100 MXNET_* env vars
    (docs/.../env_var.md:?).  We honour the same names where they map."""
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def getenv_bool(name: str, default: bool) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.lower() in ("1", "true", "yes", "on")


_UUID_COUNTER = [0]


def gen_name(prefix: str) -> str:
    """Sequential unique names (reference: NameManager in python/mxnet/name.py:?)."""
    _UUID_COUNTER[0] += 1
    return f"{prefix}{_UUID_COUNTER[0]}"
