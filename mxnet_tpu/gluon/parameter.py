"""Gluon Parameter / ParameterDict.

Reference: ``python/mxnet/gluon/parameter.py:?`` — ``Parameter`` holds one
NDArray copy per context plus a gradient buffer per context, supports
deferred initialization (shape resolved at first forward), ``lr_mult``/
``wd_mult``, ``grad_req``, sparse storage types; ``ParameterDict`` is a
prefix-namespaced registry shared down the Block tree.

TPU-native redesign: the reference replicates a parameter once per GPU and
all-reduces gradients across replicas.  Here a Parameter owns ONE logical
NDArray which may be *sharded or replicated over a device mesh* by XLA GSPMD
— multi-device placement is a sharding annotation, not N python-side copies,
so ``initialize(ctx=[...])`` records the context list but keeps a single
array (replicated layout on the mesh's data axis).  ``list_data()`` /
``list_grad()`` return per-ctx views for API compatibility; the Trainer and
KVStore operate on the single logical array and XLA inserts the collectives
(SURVEY §2.3 D1: psum replaces ``src/kvstore/comm.h``).
"""
from __future__ import annotations

import re
from collections import OrderedDict

import numpy as np

from ..base import MXNetError, resolve_dtype
from ..context import Context, current_context
from ..ndarray import NDArray
from ..telemetry import memwatch as _mw
from .. import initializer as init_mod


class DeferredInitializationError(MXNetError):
    """Raised when a deferred-init parameter's data is read before shape
    inference (reference: gluon/parameter.py:? same name)."""


def _shape_known(shape):
    return shape is not None and all(s > 0 for s in shape)


class Parameter:
    """A trainable parameter (reference: ``gluon.Parameter``)."""

    def __init__(self, name, grad_req="write", shape=None, dtype=np.float32,
                 lr_mult=1.0, wd_mult=1.0, init=None,
                 allow_deferred_init=False, differentiable=True,
                 stype="default", grad_stype="default"):
        self.name = name
        self._grad_req = grad_req
        if isinstance(shape, int):
            shape = (shape,)
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = resolve_dtype(dtype) if dtype is not None else None
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._differentiable = differentiable
        if not differentiable:
            self._grad_req = "null"
        if stype not in ("default", "row_sparse", "csr"):
            raise MXNetError(f"invalid stype {stype!r}")
        self._stype = stype
        self._grad_stype = grad_stype
        self._data = None          # the single logical NDArray
        self._ctx_list = None
        self._deferred_init = None  # (init, ctx_list) pending shape
        # attributes consulted by Trainer/optimizer
        self.attributes = {}

    # -- properties ----------------------------------------------------------
    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        if req not in ("write", "add", "null"):
            raise MXNetError(f"invalid grad_req {req!r}")
        if not self._differentiable:
            req = "null"
        if self._grad_req != req:
            self._grad_req = req
            if self._data is not None:
                self._data.attach_grad(req)

    @property
    def stype(self):
        return self._stype

    @property
    def dtype_np(self):
        return self.dtype

    # -- initialization ------------------------------------------------------
    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        """Allocate and initialize (reference: gluon/parameter.py:?
        ``Parameter.initialize``).  Deferred when shape is unknown."""
        if self._data is not None and not force_reinit:
            return
        if default_init is None:
            default_init = init_mod.Uniform()
        if ctx is None:
            ctx = [current_context()]
        elif isinstance(ctx, Context):
            ctx = [ctx]
        self._ctx_list = list(ctx)
        chosen = init if init is not None else (self.init or default_init)
        chosen = init_mod.create(chosen) if isinstance(chosen, str) else chosen
        if not _shape_known(self.shape):
            if self.allow_deferred_init:
                self._deferred_init = (chosen, list(ctx))
                return
            raise MXNetError(
                f"cannot initialize parameter {self.name!r}: shape "
                f"{self.shape} unknown and allow_deferred_init is False")
        self._init_impl(chosen, ctx)

    def _init_impl(self, initializer, ctx_list):
        import jax.numpy as jnp

        arr = NDArray(jnp.zeros(self.shape, self.dtype), ctx=ctx_list[0])
        desc = init_mod.InitDesc(self.name)
        if initializer is None:
            initializer = init_mod.Uniform()
        initializer(desc, arr)
        # initializers assign fresh arrays born UNCOMMITTED on jax's
        # DEFAULT device; commit the parameter to the requested context
        # (a re-placement when they differ, e.g. a cpu ctx on a TPU host;
        # an alias of the same buffer when they agree).  A parameter must
        # be committed from birth: jit keys its executables on which
        # operands are committed, every jit output is committed as soon
        # as one input is (batches from ``nd.array`` are), so a step
        # whose parameters start uncommitted compiles once for the first
        # call and once more for every later one.  Only without an
        # active mesh: under a mesh `.device` is a Sharding and
        # replicate() below owns placement (a device_put here would
        # collapse the mesh layout, and would crash on non-addressable
        # multi-process arrays).
        from .. import parallel

        mesh = parallel.current_mesh()
        if mesh is None:
            import jax

            arr._data = jax.device_put(arr._data, ctx_list[0].device)
        else:
            # under an active device mesh, parameters are born
            # replicated so GSPMD derives the gradient all-reduce
            parallel.replicate(arr)
        self._data = arr
        self._deferred_init = None
        if self._grad_req != "null":
            self._data.attach_grad(self._grad_req)
        if _mw._enabled:
            # label the holders so the OOM post-mortem names buffers by
            # parameter path even after optimizer updates rebind them
            _mw.adopt(arr, self.name)
            if arr._grad is not None:
                _mw.adopt(arr._grad, self.name + ".grad")

    def _finish_deferred_init(self, shape):
        """Complete a deferred init once the shape is known (reference:
        ``Parameter._finish_deferred_init``)."""
        if self._deferred_init is None:
            return
        shape = tuple(int(s) for s in shape)
        if self.shape is not None and len(self.shape) == len(shape):
            # merge: keep known dims, fill unknown (0) dims
            merged = []
            for have, got in zip(self.shape, shape):
                if have > 0 and got > 0 and have != got:
                    raise MXNetError(
                        f"inferred shape {shape} incompatible with declared "
                        f"{self.shape} for parameter {self.name!r}")
                merged.append(have if have > 0 else got)
            shape = tuple(merged)
        self.shape = shape
        initializer, ctx = self._deferred_init
        self._init_impl(initializer, ctx)

    def set_data(self, data):
        if not isinstance(data, NDArray):
            data = NDArray(data)
        if self._data is None:
            if self._deferred_init is not None:
                self.shape = data.shape
                initializer, ctx = self._deferred_init
                self._init_impl(initializer, ctx)
            else:
                raise MXNetError(
                    f"parameter {self.name!r} has not been initialized")
        if _shape_known(self.shape) and data.shape != self.shape:
            raise MXNetError(
                f"set_data shape mismatch for {self.name!r}: "
                f"{data.shape} vs {self.shape}")
        new_raw = data.astype(self.dtype, copy=False)._data
        old_raw = self._data._data
        # a mesh-placed parameter keeps its NamedSharding across loads
        # (checkpoint restore paths route through here with host arrays;
        # rebinding bare would collapse a TP layout back to one device)
        sharding = getattr(old_raw, "sharding", None)
        if sharding is not None and \
                getattr(new_raw, "shape", None) == old_raw.shape:
            try:
                import jax
                from jax.sharding import NamedSharding

                if isinstance(sharding, NamedSharding):
                    new_raw = jax.device_put(new_raw, sharding)
            except Exception:
                pass  # best-effort: an unplaceable load stays unsharded
        self._data._data = new_raw
        self.shape = data.shape

    # -- access --------------------------------------------------------------
    def _check_initialized(self):
        if self._data is not None:
            return
        if self._deferred_init is not None:
            raise DeferredInitializationError(
                f"parameter {self.name!r} has deferred initialization "
                "pending shape inference; run a forward pass first")
        raise MXNetError(
            f"parameter {self.name!r} has not been initialized; call "
            ".initialize() (e.g. net.initialize())")

    def data(self, ctx=None):
        """The parameter value (single logical array — see module doc)."""
        self._check_initialized()
        return self._data

    def list_data(self):
        self._check_initialized()
        return [self._data for _ in (self._ctx_list or [None])]

    def grad(self, ctx=None):
        self._check_initialized()
        if self._grad_req == "null" or self._data.grad is None:
            raise MXNetError(
                f"cannot get gradient of {self.name!r}: grad_req is 'null'")
        return self._data.grad

    def list_grad(self):
        g = self.grad()
        return [g for _ in (self._ctx_list or [None])]

    def list_ctx(self):
        if self._data is None and self._deferred_init is not None:
            return list(self._deferred_init[1])
        self._check_initialized()
        return list(self._ctx_list or [current_context()])

    def zero_grad(self):
        if self._data is not None and self._data.grad is not None:
            self._data.zero_grad()

    def reset_ctx(self, ctx):
        if isinstance(ctx, Context):
            ctx = [ctx]
        self._ctx_list = list(ctx)
        if self._data is not None:
            self._data._data = self._data.as_in_context(ctx[0])._data

    def cast(self, dtype):
        self.dtype = resolve_dtype(dtype)
        if self._data is not None:
            self._data._data = self._data._data.astype(self.dtype)
            if self._data.grad is not None:
                self._data.attach_grad(self._grad_req)

    def var(self):  # pragma: no cover - legacy symbolic compat
        raise NotImplementedError(
            "Parameter.var() belongs to the legacy symbol API; hybridize "
            "captures graphs through tracing instead")

    def __repr__(self):
        return (f"Parameter {self.name} (shape={self.shape}, "
                f"dtype={np.dtype(self.dtype).name if self.dtype else None})")


class Constant(Parameter):
    """Non-trainable constant parameter (reference: ``gluon.Constant``)."""

    def __init__(self, name, value):
        if not isinstance(value, NDArray):
            value = NDArray(np.asarray(value, dtype=np.float32))
        self.value = value

        class _CInit(init_mod.Initializer):
            def _init_weight(self, _name, arr):
                arr._data = value._data.astype(arr.dtype)

        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=value.dtype, init=_CInit(),
                         differentiable=False)


class ParameterDict:
    """Prefix-namespaced parameter registry (reference:
    ``gluon.ParameterDict``): Blocks share one down the tree; ``get`` creates
    or fetches, ``update`` merges, bulk initialize/save/load."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = OrderedDict()
        self._shared = shared

    @property
    def prefix(self):
        return self._prefix

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def __iter__(self):
        return iter(self._params)

    def __len__(self):
        return len(self._params)

    def __getitem__(self, key):
        return self._params[key]

    def __contains__(self, key):
        return key in self._params

    def __repr__(self):
        lines = "\n".join(f"  {v}" for v in self._params.values())
        return f"ParameterDict '{self._prefix}' (\n{lines}\n)"

    def get(self, name, **kwargs):
        """Create-or-fetch ``prefix+name`` (reference semantics: attribute
        conflicts raise; shared dict consulted first)."""
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            param = Parameter(name, **kwargs)
            self._params[name] = param
        else:
            for k, v in kwargs.items():
                if getattr(param, k, None) is not None and v is not None:
                    existing = getattr(param, k)
                    if k == "shape" and v is not None:
                        v = (v,) if isinstance(v, int) else tuple(v)
                        if existing is not None and len(existing) == len(v):
                            # merge unknown dims
                            merged = tuple(
                                a if a > 0 else b for a, b in zip(existing, v))
                            param.shape = merged
                            continue
                    if k == "dtype":
                        continue
                else:
                    setattr(param, k, v)
        return param

    def _get_impl(self, name):
        if name in self._params:
            return self._params[name]
        if self._shared is not None and name in self._shared:
            self._params[name] = self._shared[name]
            return self._params[name]
        return None

    def get_constant(self, name, value=None):
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            if value is None:
                raise MXNetError(
                    f"no constant named {name!r}; provide a value")
            param = Constant(name, value)
            self._params[name] = param
        return param

    def update(self, other):
        for k, v in other.items():
            if k in self._params and self._params[k] is not v:
                raise MXNetError(f"duplicate parameter name {k!r}")
            self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        if init is None:
            init = init_mod.Uniform()
        for p in self._params.values():
            p.initialize(None, ctx, default_init=init,
                         force_reinit=force_reinit)

    def zero_grad(self):
        for p in self._params.values():
            p.zero_grad()

    def reset_ctx(self, ctx):
        for p in self._params.values():
            p.reset_ctx(ctx)

    def setattr(self, name, value):
        for p in self._params.values():
            setattr(p, name, value)

    def save(self, filename, strip_prefix=""):
        """Save to the MXNet .params container (see mxnet_tpu/serialization
        — `NDArray.save` format, reference src/ndarray/ndarray.cc:?)."""
        from .. import ndarray as nd

        arg_dict = {}
        for name, p in self._params.items():
            weight = p.data()
            if strip_prefix and name.startswith(strip_prefix):
                name = name[len(strip_prefix):]
            arg_dict[name] = weight
        nd.save(filename, arg_dict)

    def load(self, filename, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix=""):
        from .. import ndarray as nd

        loaded = nd.load(filename)
        if isinstance(loaded, list):
            raise MXNetError("parameter file must contain a dict of arrays")
        loaded = {restore_prefix + k.removeprefix("arg:").removeprefix(
            "aux:"): v for k, v in loaded.items()}
        if not allow_missing:
            for name in self._params:
                if name not in loaded:
                    raise MXNetError(
                        f"parameter {name!r} missing from file {filename!r}")
        for name, value in loaded.items():
            if name not in self._params:
                if ignore_extra:
                    continue
                raise MXNetError(
                    f"file {filename!r} has parameter {name!r} not present "
                    "in this ParameterDict (set ignore_extra=True to skip)")
            p = self._params[name]
            if p._data is None and p._deferred_init is None:
                p.shape = value.shape
                p.initialize(ctx=ctx or [current_context()],
                             default_init=init_mod.Zero())
            p.set_data(value)
