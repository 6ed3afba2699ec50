"""``mx.np`` — the NumPy-compatible front end.

Reference: ``python/mxnet/numpy/`` (≥1.6, SURVEY §2.4) — a numpy-semantics
``ndarray`` type + function namespace over the same kernels as ``mx.nd``,
gated by ``mx.util.set_np()``.  Ops are ``_np_*``-registered in the
reference (``src/operator/numpy/``, SURVEY §2.2 NumPy-ops row).

TPU-native redesign: jnp IS numpy semantics, so this layer is thin — a
generic wrapper binds jnp functions into the autograd tape via the same
``apply_op`` dispatch every other op uses (zero-dim and zero-size shapes
work natively; the reference needed a shape-semantics flag through the C++
core for that).  The ``ndarray`` type shares the NDArray machinery, so
``mx.np`` arrays flow through gluon/optimizers/kvstore unchanged.
"""
from __future__ import annotations

import numpy as _onp

from ..base import MXNetError, resolve_dtype as _resolve_dtype
from ..context import current_context
from ..ndarray import NDArray
from ..ops.registry import apply_op as _apply_op

__all__ = ["ndarray"]

# numpy dtype aliases (reference mxnet/numpy exposes these)
float32 = _onp.float32
float64 = _onp.float64
float16 = _onp.float16
int8 = _onp.int8
int32 = _onp.int32
int64 = _onp.int64
uint8 = _onp.uint8
bool_ = _onp.bool_
pi = _onp.pi
e = _onp.e
inf = _onp.inf
nan = _onp.nan
newaxis = None


class ndarray(NDArray):
    """NumPy-semantics array (reference ``mxnet.numpy.ndarray``): same
    engine/autograd machinery as NDArray, numpy repr, operators stay in
    the np type."""

    __slots__ = ()

    def __repr__(self):
        arr = self.asnumpy()
        return f"array({_onp.array2string(arr, separator=', ')})" \
            if arr.ndim else f"array({arr.item()})"

    def _binary(self, other, jf, name, reflected=False):
        return _np(super()._binary(other, jf, name, reflected=reflected))

    def __neg__(self):
        return _np(super().__neg__())

    def __abs__(self):
        return _np(super().__abs__())

    def __getitem__(self, key):
        return _np(super().__getitem__(key))

    def as_nd_ndarray(self):
        """Convert to the classic ``mx.nd`` type (reference
        ``ndarray.as_nd_ndarray``); shares storage + tape node."""
        out = NDArray.__new__(NDArray)
        _share(self, out)
        return out

    def as_np_ndarray(self):
        return self

    # numpy-style aliases over NDArray methods
    def item(self):
        return self.asnumpy().item()

    def tolist(self):
        return self.asnumpy().tolist()

    @property
    def device(self):
        return self.context


def _share(src, dst):
    dst._data = src._data
    dst._node = src._node
    dst._oidx = src._oidx
    dst._req_grad = src._req_grad
    dst._grad = src._grad
    dst._grad_req = src._grad_req


def _np(x):
    """Re-type an NDArray result as np ndarray (shares all state)."""
    if isinstance(x, ndarray):
        return x
    if isinstance(x, NDArray):
        out = ndarray.__new__(ndarray)
        _share(x, out)
        return out
    if isinstance(x, (tuple, list)):
        return type(x)(_np(v) for v in x)
    return x


def _wrap(jfn, name=None):
    """Bind a jnp function into the op-dispatch/autograd machinery.

    NDArray positionals become tracked operands; everything else (python
    scalars, lists, shape tuples, kwargs) closes over the pure function —
    the same split the reference makes between op inputs and dmlc
    ``Parameter`` attributes.
    """
    opname = name or jfn.__name__

    def fn(*args, **kwargs):
        # track NDArray positionals, including one level inside sequences
        # (concatenate/stack/einsum take lists of arrays)
        paths, tracked = [], []
        for i, a in enumerate(args):
            if isinstance(a, NDArray):
                paths.append((i, None))
                tracked.append(a)
            elif isinstance(a, (list, tuple)):
                for j, e in enumerate(a):
                    if isinstance(e, NDArray):
                        paths.append((i, j))
                        tracked.append(e)
        kw_arr = {k: v for k, v in kwargs.items() if isinstance(v, NDArray)}
        kwargs = {k: (v._data if isinstance(v, NDArray) else v)
                  for k, v in kwargs.items()}

        def pure(*raws):
            full = [list(a) if isinstance(a, (list, tuple)) else a
                    for a in args]
            for (i, j), r in zip(paths, raws[:len(paths)]):
                if j is None:
                    full[i] = r
                else:
                    full[i][j] = r
            kw = dict(kwargs)
            for k, r in zip(kw_arr, raws[len(paths):]):
                kw[k] = r
            return jfn(*full, **kw)

        return _np(_apply_op(pure, *tracked, *kw_arr.values(),
                             name=f"np_{opname}"))

    fn.__name__ = opname
    fn.__qualname__ = opname
    fn.__doc__ = f"mx.np.{opname} — numpy-compatible; see jnp.{opname}."
    return fn


# --- creation ----------------------------------------------------------------

def array(object, dtype=None, ctx=None, device=None):
    """Reference ``mx.np.array``: floats default to float32 (classic MXNet
    default dtype) unless ``mx.util.set_np_default_dtype`` is active."""
    import jax.numpy as jnp

    from .. import util as _util

    if isinstance(object, NDArray):
        out = _np(NDArray(object._data, dtype=dtype))
        return out
    arr = _onp.asarray(object)
    if dtype is None and arr.dtype == _onp.float64 \
            and not _util.is_np_default_dtype():
        dtype = _onp.float32
    return _np(NDArray(jnp.asarray(arr, dtype=_resolve_dtype(dtype)),
                       ctx=ctx or device or current_context()))


def _creation(jfn, name):
    def fn(*args, dtype=None, ctx=None, device=None, **kwargs):
        import jax.numpy as jnp

        from .. import util as _util

        if dtype is None and name in ("zeros", "ones", "empty", "full") \
                and not _util.is_np_default_dtype():
            dtype = _onp.float32
        raw = jfn(*args, dtype=_resolve_dtype(dtype), **kwargs) \
            if dtype is not None else jfn(*args, **kwargs)
        return _np(NDArray(raw, ctx=ctx or device or current_context()))

    fn.__name__ = name
    return fn


def empty(shape, dtype=None, ctx=None, device=None):
    import jax.numpy as jnp

    return _creation(jnp.zeros, "empty")(shape, dtype=dtype, ctx=ctx,
                                         device=device)


# metadata/introspection: plain python results, NOT op-dispatched
def shape(a):
    return tuple(a.shape) if isinstance(a, NDArray) else _onp.shape(a)


def ndim(a):
    return a.ndim if isinstance(a, NDArray) else _onp.ndim(a)


def size(a, axis=None):
    if isinstance(a, NDArray):
        return a.size if axis is None else a.shape[axis]
    return _onp.size(a, axis)


def result_type(*args):
    return _onp.result_type(*[a.dtype if isinstance(a, NDArray) else a
                              for a in args])


def can_cast(from_, to, casting="safe"):
    f = from_.dtype if isinstance(from_, NDArray) else from_
    return _onp.can_cast(f, to, casting)


def promote_types(t1, t2):
    return _onp.promote_types(t1, t2)


def may_share_memory(a, b, max_work=None):
    if isinstance(a, NDArray) and isinstance(b, NDArray):
        return a._data is b._data
    return False


shares_memory = may_share_memory


# --- namespace assembly ------------------------------------------------------

def _install():
    import jax.numpy as jnp

    g = globals()

    unary = """sin cos tan arcsin arccos arctan sinh cosh tanh arcsinh
        arccosh arctanh exp expm1 log log2 log10 log1p sqrt cbrt square
        absolute abs sign floor ceil trunc rint negative reciprocal
        logical_not isnan isinf isfinite isneginf isposinf conj real
        imag angle degrees radians ravel sort unique nonzero
        copy diag diagonal atleast_1d atleast_2d atleast_3d
        flatnonzero""".split()
    binary = """add subtract multiply divide true_divide floor_divide mod
        remainder power float_power maximum minimum fmax fmin arctan2
        hypot logaddexp logaddexp2 copysign nextafter logical_and
        logical_or logical_xor equal not_equal greater greater_equal less
        less_equal bitwise_and bitwise_or bitwise_xor left_shift
        right_shift gcd lcm heaviside ldexp dot vdot inner outer matmul
        kron cross convolve correlate searchsorted""".split()
    other = """sum mean max min amax amin prod nanprod nansum std var
        median average percentile quantile ptp argmax argmin nanargmax
        nanargmin all any cumsum cumprod nancumsum count_nonzero
        reshape transpose swapaxes moveaxis rollaxis expand_dims squeeze
        concatenate stack vstack hstack dstack column_stack split
        array_split hsplit vsplit dsplit tile repeat roll flip fliplr
        flipud rot90 broadcast_to broadcast_arrays append where clip
        round around argsort take take_along_axis partition argpartition
        trace tensordot einsum pad bincount digitize interp histogram
        allclose isclose array_equal array_equiv triu tril
        meshgrid unravel_index ravel_multi_index diff ediff1d gradient
        dot insert delete resize invert""".split()
    creation = """zeros ones full arange linspace logspace geomspace eye
        identity tri zeros_like ones_like full_like empty_like
        frombuffer""".split()

    for nm in unary + binary + other:
        if nm in g:
            continue
        g[nm] = _wrap(getattr(jnp, nm), nm)
        __all__.append(nm)
    for nm in creation:
        if nm in g:
            continue
        g[nm] = _creation(getattr(jnp, nm), nm)
        __all__.append(nm)
    __all__.extend(["array", "empty"])


_install()
del _install

from . import random  # noqa: E402,F401
from . import linalg  # noqa: E402,F401
__all__.extend(["random", "linalg"])
