"""The gated delta rule (Gated DeltaNet; the linear-attention layer of
``qwen3_next``), in the two forms a server runs, and the only code that
knows its state's layout.

Reference: NONE (the reference predates linear attention).  A head keeps
a float32 matrix ``S`` (``head_k_dim``, ``head_v_dim``), keys down the
rows.  A token, with ``q``, ``k`` (``head_k_dim``) and ``v``
(``head_v_dim``) of that head, a write strength ``beta`` in (0, 1) and a
log decay ``g <= 0``::

    S' = exp(g) S                     the memory fades
    d  = beta (v - S'^T k)            what the memory lacks of v at k
    S  = S' + k d^T                   written at k
    o  = S^T q                        read at q

* :func:`recurrence` is those lines, a ``lax.scan`` over positions: the
  module's plain form, which the other two are held to.
* :func:`step` advances every slot of a pool ``(slots, heads,
  head_k_dim, head_v_dim)`` by one token.  Where :func:`step_applicable`
  says so (a TPU, no mesh, heads of 128 x 128) it is the Pallas kernel
  ``gated_delta_step``: a slot's state is read once, decayed, corrected,
  read out and written once, IN PLACE (``input_output_aliases``), so a
  step program that donates the pool holds no copy of it.  Elsewhere the
  same lines as XLA ops under ``jax.named_scope("gated_delta_step")``.
* :func:`chunk_scan` is the prefill: the same function of a whole
  sequence, 64 rows a chunk.  Inside a chunk the corrections solve a
  unit lower-triangular system, ``(I + tril(diag(beta) (K K^T * decay),
  -1)) D = diag(beta) (V - decay K S_0)``, for every chunk at once; a
  ``lax.scan`` then carries the state from chunk to chunk.  A row with
  ``beta`` = 0 and ``g`` = 0 leaves the state as it was: that is how the
  padded end of a prompt bucket enters, so what comes back last is the
  state at each row's TRUE length.

Everything is float32 and the products run at ``Precision.HIGHEST``:
the state is summed into over thousands of tokens.
"""
from __future__ import annotations

import functools

import numpy as np

__all__ = ["recurrence", "step", "step_applicable", "step_form", "chunk_scan",
           "state_shape", "CHUNK", "HEADS_PER_BLOCK"]

#: rows of a chunk of :func:`chunk_scan`
CHUNK = 64
#: heads of a slot's state that one grid step of the kernel holds: 16
#: heads of (128, 128) float32 are 1 MiB, in and out double-buffered
#: 4 MiB of VMEM
HEADS_PER_BLOCK = 16


def state_shape(heads, head_k_dim, head_v_dim):
    """A slot's recurrent state as stored (float32)."""
    return (int(heads), int(head_k_dim), int(head_v_dim))


def _one_token(s, q, k, v, beta, g):
    """The four lines over any leading axes: ``s`` (.., dk, dv), ``q``,
    ``k`` (.., dk), ``v`` (.., dv), ``beta``, ``g`` (..,) -> (o, s)."""
    import jax.numpy as jnp

    s = s * jnp.exp(g)[..., None, None]
    d = beta[..., None] * (v - (s * k[..., :, None]).sum(axis=-2))
    s = s + k[..., :, None] * d[..., None, :]
    return (s * q[..., :, None]).sum(axis=-2), s


def recurrence(q, k, v, beta, g, s0=None):
    """The plain form, token by token: ``q``, ``k`` (B, T, H, dk), ``v``
    (B, T, H, dv), ``beta``, ``g`` (B, T, H), all float32; ``s0`` (B, H,
    dk, dv) or None for zeros -> (o (B, T, H, dv), the state after the
    last row)."""
    import jax
    import jax.numpy as jnp

    b, _t, h, dk = q.shape
    if s0 is None:
        s0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)

    def one(s, row):
        o, s = _one_token(s, *row)
        return s, o

    s, o = jax.lax.scan(one, s0, tuple(jnp.moveaxis(a, 1, 0)
                                       for a in (q, k, v, beta, g)))
    return jnp.moveaxis(o, 0, 1), s


# -- the step -------------------------------------------------------------------

def step_applicable(platform, mesh, heads, head_k_dim, head_v_dim):
    """Whether :func:`step` is the kernel, from what the caller
    observes: the platform the pool lives on, the engine's mesh (a
    sharded pool would need a ``shard_map`` wrapper) and the shapes
    Mosaic tiles without padding."""
    return (platform == "tpu" and mesh is None and head_k_dim % 128 == 0
            and head_v_dim % 128 == 0 and heads % 8 == 0)


def step_form(shape):
    """``"step_kernel"`` or ``"step_xla"``: which form :func:`step`
    takes over slots of ``shape`` (H, dk, dv) here and now, from the
    platform programs are compiled for and the active mesh; a served
    model's layer and its engine's ``linear_attention`` counter ask
    the same function."""
    import jax

    from .. import parallel

    ok = step_applicable(jax.default_backend(), parallel.current_mesh(),
                         *shape)
    return "step_kernel" if ok else "step_xla"


def _step_kernel(cols_ref, v_ref, beta_ref, decay_ref, s_ref, o_ref, so_ref,
                 *, heads):
    """One slot's ``heads`` heads.  ``cols_ref`` (1, 1, dk, 2 * heads):
    the heads' ``k`` and then their ``q`` as COLUMNS (the state's rows
    are keys, so both multiply down the sublanes); ``v_ref``,
    ``beta_ref``, ``decay_ref`` (1, heads, dv) rows, the two scalars a
    head broadcast along the lanes; ``s_ref`` / ``so_ref`` the same
    (1, heads, dk, dv) block of the pool."""
    for j in range(heads):
        kc = cols_ref[0, 0, :, j:j + 1]                      # (dk, 1)
        qc = cols_ref[0, 0, :, heads + j:heads + j + 1]
        s = s_ref[0, j] * decay_ref[0, j:j + 1, :]           # (dk, dv)
        d = beta_ref[0, j:j + 1, :] \
            * (v_ref[0, j:j + 1, :] - (s * kc).sum(axis=0, keepdims=True))
        s = s + kc * d
        so_ref[0, j] = s
        o_ref[0, j:j + 1, :] = (s * qc).sum(axis=0, keepdims=True)


def _step_pallas(pool, q, k, v, beta, g, heads_per_block, interpret=False):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, h, dk, dv = pool.shape
    hb = min(heads_per_block, h)
    while h % hb:
        hb //= 2
    nb = h // hb
    # a block's k and q as columns: (slots, blocks, dk, [k of hb | q of hb])
    cols = jnp.concatenate(
        [a.reshape(n, nb, hb, dk).transpose(0, 1, 3, 2) for a in (k, q)],
        axis=-1)
    lanes = lambda a: jnp.broadcast_to(a[..., None], (n, h, dv))  # noqa: E731
    row = pl.BlockSpec((1, hb, dv), lambda i, j: (i, j, 0))
    blk = pl.BlockSpec((1, hb, dk, dv), lambda i, j: (i, j, 0, 0))
    o, pool = pl.pallas_call(
        functools.partial(_step_kernel, heads=hb),
        grid=(n, nb),
        in_specs=[pl.BlockSpec((1, 1, dk, 2 * hb), lambda i, j: (i, j, 0, 0)),
                  row, row, row, blk],
        out_specs=[row, blk],
        out_shape=[jax.ShapeDtypeStruct((n, h, dv), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the state is written where it was read: no second pool
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="gated_delta_step",
        interpret=interpret,
    )(cols, v, lanes(beta), lanes(jnp.exp(g)), pool)
    return o, pool


def step(pool, q, k, v, beta, g, live=None, kernel=False, interpret=False):
    """One token a slot: ``pool`` (slots, H, dk, dv) float32; ``q``,
    ``k`` (slots, H, dk), ``v`` (slots, H, dv), ``beta``, ``g`` (slots,
    H), float32 -> (o (slots, H, dv), the pool after the token).
    ``live`` (slots,) bool: the slots this step owns; any other enters
    with ``beta`` = 0 and ``g`` = 0, so its state stays as it is (a step
    is not idempotent: a slot stepped twice at one position would hold
    the token twice).  ``kernel`` (static; the caller decides it from
    :func:`step_applicable`) picks the Pallas kernel over the XLA form.
    Every slot computes either way."""
    import jax
    import jax.numpy as jnp

    if live is not None:
        beta = jnp.where(live[:, None], beta, 0.0)
        g = jnp.where(live[:, None], g, 0.0)
    if kernel:
        return _step_pallas(pool, q, k, v, beta, g, HEADS_PER_BLOCK,
                            interpret)
    with jax.named_scope("gated_delta_step"):
        return _one_token(pool, q, k, v, beta, g)


# -- the chunked scan -----------------------------------------------------------

def chunk_scan(q, k, v, beta, g, live=None, s0=None, chunk=CHUNK):
    """The prefill form: arguments as :func:`recurrence` takes them,
    ``live`` (B, T) bool the rows a request owns (None: all).  A row
    that is not live enters with ``beta`` = 0 and ``g`` = 0 and leaves
    the state as it was (its own output is nobody's).  -> (o (B, T, H,
    dv), the state after each sequence's last LIVE row, (B, H, dk,
    dv))."""
    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import solve_triangular

    b, t, h, dk = q.shape
    dv = v.shape[-1]
    if live is not None:
        beta = jnp.where(live[..., None], beta, 0.0)
        g = jnp.where(live[..., None], g, 0.0)
    c = int(chunk)
    tp = -(-t // c) * c
    if tp != t:
        pad = lambda a: jnp.pad(                            # noqa: E731
            a, ((0, 0), (0, tp - t)) + ((0, 0),) * (a.ndim - 2))
        q, k, v, beta, g = (pad(a) for a in (q, k, v, beta, g))
    n = tp // c
    if s0 is None:
        s0 = jnp.zeros((b, h, dk, dv), jnp.float32)
    mm = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)

    with jax.named_scope("gated_delta_chunk_scan"):
        # (B, H, N, C, ..): a head's chunks side by side
        chunks = lambda a: jnp.moveaxis(                    # noqa: E731
            a.reshape((b, n, c) + a.shape[2:]), 3, 1)
        q, k, v, beta, g = (chunks(a) for a in (q, k, v, beta, g))
        gc = jnp.cumsum(g, axis=-1)                     # decay to each row
        rows = jnp.arange(c)
        # decay from row s to row t of a chunk, where s <= t; 0 elsewhere
        # (masked before the exponential: above the diagonal it would grow)
        diff = gc[..., :, None] - gc[..., None, :]
        below = rows[:, None] >= rows[None, :]
        decay = jnp.exp(jnp.where(below, diff, -jnp.inf))
        kb = k * beta[..., None]
        a = jnp.where(rows[:, None] > rows[None, :],
                      mm("bhnck,bhnsk->bhncs", kb, k) * decay, 0.0)
        # D = U - W S_0 with (I + A) U = beta V, (I + A) W = beta exp(G) K
        rhs = jnp.concatenate(
            [v * beta[..., None], kb * jnp.exp(gc)[..., None]], axis=-1)
        uw = solve_triangular(a + jnp.eye(c, dtype=a.dtype), rhs,
                              lower=True, unit_diagonal=True)
        u, w = uw[..., :dv], uw[..., dv:]
        qk = mm("bhnck,bhnsk->bhncs", q, k) * decay     # row t reads s <= t
        q_in = q * jnp.exp(gc)[..., None]               # reads of S_0
        last = gc[..., -1]                              # (B, H, N)
        k_out = k * jnp.exp(last[..., None] - gc)[..., None]

        def one(s, xs):
            u, w, qk, q_in, k_out, last = xs
            d = u - mm("bhck,bhkv->bhcv", w, s)
            o = mm("bhck,bhkv->bhcv", q_in, s) + mm("bhcs,bhsv->bhcv", qk, d)
            s = s * jnp.exp(last)[..., None, None] \
                + mm("bhck,bhcv->bhkv", k_out, d)
            return s, o

        s, o = jax.lax.scan(one, s0, tuple(
            jnp.moveaxis(x, 2, 0) for x in (u, w, qk, q_in, k_out, last)))
        # (N, B, H, C, dv) -> (B, T, H, dv)
        o = o.transpose(1, 0, 3, 2, 4).reshape(b, tp, h, dv)[:, :t]
    return o, s
