"""Imperative op dispatch: the analog of ``Imperative::Invoke``.

Reference call stack (SURVEY §3.1): python wrapper → ``MXImperativeInvokeEx``
→ ``Imperative::Invoke`` (infer shape/type → alloc outputs → push FCompute to
the dependency engine; returns to python immediately, engine worker threads
execute async) — ``src/c_api/c_api_ndarray.cc:?``,
``src/imperative/imperative.cc:?``, ``src/engine/threaded_engine.cc:?``.

TPU-native redesign: jax dispatch IS the dependency engine — every jnp call
is enqueued asynchronously on the device stream and jax tracks buffer
dependencies, so the reference's read/write-var scheduling falls out for
free.  ``apply_op`` therefore just:

  1. unwraps NDArray operands to raw ``jax.Array``s,
  2. runs the pure function (under ``jax.vjp`` if the autograd tape is
     recording and any operand is attached to the graph),
  3. wraps outputs back into NDArrays and wires tape nodes.

Blocking happens only at ``wait_to_read``/``asnumpy`` — same contract as the
reference engine's ``WaitForVar`` (``include/mxnet/engine.h:?``).
"""
from __future__ import annotations

from typing import Any, Callable, Dict

from .. import autograd as ag
from .. import sanitizer as _san
from ..telemetry import memwatch as _mw

# Global op registry: name -> python callable operating on NDArrays.
# (Reference: nnvm's dmlc::Registry of Op objects; here ops are plain
# functions and the registry exists for introspection, custom-op loading and
# the symbol/json export path.)
_OPS: Dict[str, Callable] = {}

# Per-op metadata keyed by EVERY registered name (canonical + aliases):
#   no_grad   -- op is intentionally non-differentiable; apply_op skips the
#                jax.vjp trace and wires a zero-cotangent tape node instead
#                (the analog of the reference marking FGradient absent)
#   canonical -- canonical op name (aliases point at the same dict)
#   aliases   -- alias tuple of the canonical registration
_OP_META: Dict[str, dict] = {}

# Registrations that overwrote an existing name.  Nothing in-tree should
# ever land here; the runtime half of mxlint's T3 rule asserts it empty
# (the static half cannot see table-driven registration loops).
_DUPLICATE_REGISTRATIONS = []


def _register(name: str, fn: Callable, aliases=(), no_grad: bool = False):
    meta = {"no_grad": bool(no_grad), "canonical": name,
            "aliases": tuple(aliases)}
    for n in (name,) + tuple(aliases):
        if n in _OPS and _OPS[n] is not fn:
            _DUPLICATE_REGISTRATIONS.append(
                (n, _OP_META.get(n, {}).get("canonical", n), name))
        _OPS[n] = fn
        _OP_META[n] = meta
    return fn


def defop(name: str = None, aliases=(), no_grad: bool = False):
    """Decorator: register an NDArray-level op under ``name`` (+aliases).
    Like make_exporter, registration adds unknown-attribute validation.
    ``no_grad=True`` marks an intentionally non-differentiable op (integer
    outputs, comparisons): apply_op then skips the vjp trace for it."""

    def deco(fn):
        opname = name or fn.__name__
        fn = _attr_validated(fn, opname)
        _register(opname, fn, aliases, no_grad)
        return fn

    return deco


def get_op(name: str):
    return _OPS.get(name)


def list_ops():
    return sorted(_OPS)


def op_meta(name: str):
    """Registration metadata for ``name`` (canonical or alias); {} if the
    op predates metadata or does not exist."""
    return _OP_META.get(name, {})


def duplicate_registrations():
    """(name, previous_canonical, new_canonical) for every registration
    that overwrote an existing op name.  Should always be empty."""
    return list(_DUPLICATE_REGISTRATIONS)


def _in_graph(x) -> bool:
    return getattr(x, "_req_grad", False) or getattr(x, "_node", None) is not None


# --- dispatch-platform hint --------------------------------------------------
# In a mixed-platform process (the on-chip parity lane runs its cpu
# oracle and tpu leg in ONE process) ``jax.devices()[0]`` is the TPU
# even when the op's operands are committed to host memory.  Ops whose
# lowering is platform-conditional (the pallas flash kernel) must route
# by where the computation will actually run, so every dispatch that
# holds CONCRETE operands publishes their platform here for the
# duration of its trace; platform-conditional ops consult it before
# falling back to the process-default backend.  Thread-local: the
# parity harness and data loaders run concurrent dispatches.

import threading as _threading


class _DispatchPlatform(_threading.local):
    def __init__(self):
        self.stack = []


_DISPATCH_PLATFORM = _DispatchPlatform()


def platform_of_raw(raw):
    """Platform of a CONCRETE jax array (None for tracers/unknown)."""
    import jax

    if isinstance(raw, jax.core.Tracer) or not isinstance(raw, jax.Array):
        return None  # tracers and host (numpy/scalar) operands
    dev = raw.device  # Device for single-device arrays, else Sharding
    if isinstance(dev, jax.Device):
        return dev.platform
    return next(iter(dev.device_set)).platform


def platform_of_raws(raws):
    """First non-None operand platform (the shared scan used by every
    dispatch site: apply_op, CachedOp, FusedTrainStep)."""
    for raw in raws:
        plat = platform_of_raw(raw)
        if plat is not None:
            return plat
    return None


def current_dispatch_platform():
    stack = _DISPATCH_PLATFORM.stack
    return stack[-1] if stack else None


class dispatch_platform:
    """Publish ``platform`` while tracing a dispatch.  A None platform
    (tracer operands) pushes nothing, preserving any outer hint."""

    def __init__(self, platform):
        self.platform = platform

    def __enter__(self):
        if self.platform is not None:
            _DISPATCH_PLATFORM.stack.append(self.platform)
        return self

    def __exit__(self, *exc):
        if self.platform is not None:
            _DISPATCH_PLATFORM.stack.pop()
        return False


def _profiler_mod():
    """The profiler module iff it is loaded AND running (dispatch stays
    hook-free otherwise — same contract as the reference engine checking
    ``profiler_->IsProfiling()`` per opr)."""
    import sys

    prof = sys.modules.get("mxnet_tpu.profiler")
    return prof if prof is not None and prof.is_running() else None


_NO_META = {"no_grad": False}

# hot-path module refs, bound once on first dispatch (apply_op runs per
# op — per-call relative imports cost ~1 µs each on the deferred path)
_ENG = None
_NDA = None


def _bind_dispatch_refs():
    global _ENG, _NDA
    from .. import engine
    from ..ndarray import NDArray

    _NDA = NDArray
    _ENG = engine
    return engine


# per-op NaN-bisection hook, installed by ``telemetry.numerics.bisect()``
# for eager divergence replays ONLY — called (name, input raws, output
# raws) after every dispatch.  One ``is not None`` test on the hot path.
_bisect_hook = None


def _zero_vjp(n_inputs: int):
    """Tape vjp for no_grad ops: all-None cotangents (autograd skips
    accumulation for None, exactly as it does for float0)."""

    def vjp(cots):
        return (None,) * n_inputs

    return vjp


def apply_op(fun: Callable, *nd_args, name: str = ""):
    """Apply pure raw-array function ``fun`` to NDArray operands.

    ``fun`` must be traceable jax code closed over any non-array attributes
    (the analog of the reference's dmlc ``Parameter`` struct being bound at
    op-construction time).  Returns NDArray or tuple of NDArrays.

    With op bulking on (``MXT_ENGINE_BULK=1`` / ``engine.bulk(n)``) the
    dispatch is *deferred*: it joins the thread's pending segment and the
    returned NDArrays hold pending placeholders until the segment flushes
    as one jit-compiled unit (mxnet_tpu/engine.py).  The disabled path is
    the single ``_bulk_on`` boolean test below.
    """
    _engine = _ENG
    if _engine is None:
        _engine = _bind_dispatch_refs()
    NDArray = _NDA

    if _engine._bulk_on:
        deferred = _engine.maybe_defer(fun, nd_args, name)
        if deferred is not None:
            # outputs are pending placeholders here; the ledger picks the
            # real buffers up when ``NDArray._data`` materializes the flush
            single, vals = deferred
            new = NDArray.__new__
            if single:
                o = new(NDArray)
                o._raw = vals[0]
                o._node, o._oidx = None, 0
                o._req_grad, o._grad, o._grad_req = False, None, "null"
                return o
            nd_outs = []
            for v in vals:
                o = new(NDArray)
                o._raw = v
                o._node, o._oidx = None, 0
                o._req_grad, o._grad, o._grad_req = False, None, "null"
                nd_outs.append(o)
            return tuple(nd_outs)
    import jax

    raws = [a._data for a in nd_args]
    if _san._enabled:
        # donation sanitizer: a stale operand (buffer donated by a fused
        # trainer/step-fusion/optimizer dispatch) fails HERE with the
        # donation site instead of XLA's generic deleted-array error.
        # Tracers (re-trace under jit/vjp) never hit the registry.
        for r in raws:
            _san.check(r, f"operand of {name or 'op'!r}")
    from .. import amp as _amp

    if _amp.is_active():
        raws = _amp.maybe_cast_args(name, raws)
    recording = ag.is_recording() and any(_in_graph(a) for a in nd_args)
    no_grad_op = recording and _OP_META.get(name, _NO_META)["no_grad"]
    prof = _profiler_mod()
    if prof is not None:
        import time

        t0 = time.perf_counter()
    with dispatch_platform(platform_of_raws(raws)):
        if recording and not no_grad_op:
            cached = (_engine.cached_vjp(fun, raws, name)
                      if _engine._bulk_on and _engine._async_on else None)
            if cached is not None:
                outs, vjp = cached
            else:
                outs, vjp = jax.vjp(fun, *raws)
        else:
            outs = fun(*raws)
            vjp = None
    if _bisect_hook is not None:
        _bisect_hook(name, raws,
                     outs if isinstance(outs, (tuple, list)) else (outs,))
    if _engine.is_naive():
        # NaiveEngine: synchronous dispatch — device errors surface HERE,
        # at the op that caused them, with this op's name in the stack.
        # (Tracers pass through: export/vjp tracing has no async result.)
        flat = outs if isinstance(outs, (tuple, list)) else [outs]
        if not any(isinstance(o, jax.core.Tracer) for o in flat):
            from ..base import MXNetError

            try:
                jax.block_until_ready(outs)
            except Exception as e:
                if _mw._enabled:
                    _mw.annotate_oom(
                        e, context=f"NaiveEngine op {name or 'op'!r}")
                raise MXNetError(
                    f"operator {name or 'op'!r} failed under NaiveEngine "
                    f"(synchronous) dispatch: {e}") from e
    if prof is not None:
        prof.record_op_event(prof.current_scope_prefix() + (name or "op"),
                             time.perf_counter() - t0)
    single = not isinstance(outs, (tuple, list))
    outs_t = (outs,) if single else tuple(outs)
    nd_outs = [NDArray(o) for o in outs_t]
    if recording:
        if vjp is None:
            # no_grad op: outputs stay ON the tape (heads remain attached,
            # downstream backward() still works) but the vjp trace is
            # skipped entirely — backward sees None cotangents and skips
            # accumulation, which is observably identical to the zero
            # gradients these ops produced before.
            vjp = _zero_vjp(len(nd_args))
        node = ag.Node(vjp, list(nd_args),
                       [(o.shape, o.dtype) for o in outs_t], name=name,
                       single=single, fun=fun)
        for i, o in enumerate(nd_outs):
            o._node = node
            o._oidx = i
    return nd_outs[0] if single else tuple(nd_outs)


def wrap_raw(x):
    """Wrap a raw array without tape wiring (for op-free paths)."""
    from ..ndarray import NDArray

    return NDArray(x)


def commit_out(out, result):
    """Honour an ``out=`` kwarg: rebind the handle AND carry the tape node so
    the result stays attached to the autograd graph."""
    if out is None:
        return result
    # copy the handle slot directly: a pending placeholder moves to ``out``
    # without forcing a flush
    out._raw = result._raw
    out._node = result._node
    out._oidx = result._oidx
    return out


def accum_dtype(dt):
    """fp32 accumulation dtype for reduced-precision matmul/reduce inputs
    (the TPU analog of cuDNN's pseudo-fp16 math mode); None if the dtype
    already accumulates natively."""
    import numpy as np

    return np.float32 if np.dtype(dt).name in ("bfloat16", "float16") else None


# Attributes every op tolerates: graph/bookkeeping junk the reference's
# dmlc Parameter layer also strips before validation (node naming, symbol
# attrs, arity hints the json graph carries) plus the reference's harmless
# backend performance hints, which legacy MXNet-exported json checkpoints
# carry on conv/pool/BN nodes and which have no TPU meaning.
_COMMON_ATTRS = frozenset(["name", "attr", "num_args", "num_outputs",
                           "__layout__", "layout",
                           "workspace", "cudnn_tune", "cudnn_off"])


def _attr_validated(fn, opname):
    """The dmlc ``Parameter`` role (SURVEY §5 config row): a typo'd or
    unknown op attribute RAISES instead of vanishing into ``**kwargs``.
    Known attributes = the op function's named parameters + _COMMON_ATTRS;
    ops without a ``**kwargs`` catch-all already validate natively."""
    import functools
    import inspect

    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return fn
    params = sig.parameters.values()
    if not any(p.kind is inspect.Parameter.VAR_KEYWORD for p in params):
        return fn  # no silent catch-all to guard
    named = frozenset(
        p.name for p in params
        if p.kind in (inspect.Parameter.POSITIONAL_OR_KEYWORD,
                      inspect.Parameter.KEYWORD_ONLY))

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        unknown = [k for k in kwargs
                   if k not in named and k not in _COMMON_ATTRS]
        if unknown:
            from ..base import MXNetError

            raise MXNetError(
                f"operator {opname!r} got unknown attribute(s) "
                f"{sorted(unknown)}; accepted: {sorted(named)}")
        if "layout" in kwargs and "layout" not in named:
            # tolerated only as the channel-first default the op already
            # implements; a channels-last request must NOT be swallowed
            # (it would silently pool/conv over the wrong axes)
            v = kwargs["layout"]
            if v is not None and str(v) not in ("NCHW", "NCW", "NCDHW"):
                from ..base import MXNetError

                raise MXNetError(
                    f"operator {opname!r} does not implement "
                    f"layout={v!r} (channel-first only)")
        return fn(*args, **kwargs)

    return wrapper


def make_exporter(module):
    """Create the per-opmodule ``_export`` helper: registers the op under its
    name + aliases and exposes it as a module attribute (the analog of the
    reference generating python wrappers from the C++ registry at import,
    python/mxnet/ndarray/register.py:?)."""
    module.__all__ = getattr(module, "__all__", [])

    def _export(fn, name=None, aliases=(), no_grad=False):
        name = name or fn.__name__
        fn.__name__ = name
        fn = _attr_validated(fn, name)
        _register(name, fn, aliases, no_grad)
        setattr(module, name, fn)
        module.__all__.append(name)
        for a in aliases:
            setattr(module, a, fn)
            module.__all__.append(a)
        return fn

    return _export
