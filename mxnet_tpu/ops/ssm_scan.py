"""The selective state-space recurrence of Mamba-2 (the ``M`` layers of
``nemotron_h``), in the forms a server runs, and the only code that knows
its state's layout.

Reference: NONE (the reference predates state-space layers).  A head
keeps a float32 matrix ``S`` (``head_dim``, ``state_size``).  A token,
with the head's ``x``
(``head_dim``), a step ``dt`` > 0, the head's rate ``A`` < 0 and a skip
``D``, and with ``B`` and ``C`` (``state_size``) shared by the ``heads /
groups`` consecutive heads of a group::

    S = exp(A dt) S + (dt x) B^T      the memory fades, then is written
    y = S C + D x                     read at C

A diagonal, input-dependent decay and no correction term: beside
:mod:`mxnet_tpu.ops.gated_delta` (a delta rule over keys) the third form
of per-slot recurrent state in the repo.

* :func:`recurrence` is those lines, a ``lax.scan`` over positions: the
  module's plain form, which the other two are held to.
* :func:`chunk_scan` is the prefill, the state-space-dual form, ``CHUNK``
  rows a chunk: inside a chunk row ``t`` reads row ``s <= t`` through
  ``(C_t . B_s) exp(L_t - L_s) dt_s`` (``L`` the cumulative log decay;
  ``C B^T`` once a GROUP, not a head), every chunk at once; a
  ``lax.scan`` then carries the state from chunk to chunk.  A row with
  ``dt`` = 0 leaves the state as it was: that is how the padded end of a
  prompt bucket enters, so what comes back is the state at each row's
  TRUE length.
* :func:`step` advances every slot of a pool by one token.  A POOL KEEPS
  A STATE TRANSPOSED AND PACKED (:func:`state_shape`, :func:`to_stored`):
  ``(heads / pack, state_size, pack x head_dim)``, the state's ``N`` down
  the rows and ``pack`` consecutive heads of one group side by side along
  a row's 128 lanes (2 at heads of 64, as the paged pool packs KV heads
  of 64).  So ``dt x``, the decay and the read-out are lane ROWS of the
  pack, ``B`` and ``C`` are columns shared by it, and reading a head out
  at ``C`` sums DOWN the rows (vector adds), not along lanes: with ``N``
  along lanes, ``(head_dim, state_size)`` a head, the read-out was eight
  cross-lane reductions a head and bound the kernel (PERF.md, PR 47:
  1.79 ms a call of 128 slots against XLA's 1.45; the packed layout
  reads its own line there).  Where :func:`step_applicable` says so (a
  TPU, no mesh, whole 128-lane rows) the step is the Pallas kernel
  ``ssm_state_step``: a grid step is one GROUP of one slot (16 heads, 512
  KiB at the published sizes), ``B`` and ``C`` loaded once for its heads;
  the state is read once, decayed, written and read out, and stored once
  IN PLACE (``input_output_aliases``), so a step program that donates the
  pool holds no copy of it.  Elsewhere the same lines as XLA ops under
  ``jax.named_scope("ssm_state_step")``.

:func:`recurrence` and :func:`chunk_scan` take and return states in the
plain order ``(B, H, head_dim, state_size)``; what crosses into a pool
goes through :func:`to_stored`.  Everything is float32 and the products
run at ``Precision.HIGHEST``: the state is summed into over thousands of
tokens.
"""
from __future__ import annotations

import functools

__all__ = ["recurrence", "step", "step_applicable", "step_form", "chunk_scan",
           "state_shape", "lane_pack", "to_stored", "from_stored", "CHUNK"]

#: rows of a chunk of :func:`chunk_scan` (the published kernel's
#: ``chunk_size``; any chunk gives the same state)
CHUNK = 128


def lane_pack(heads, head_dim, groups=1):
    """Heads of one group that share a lane row of a stored state: the
    most (a power of two) that fit 128 lanes and divide a group's
    heads; 1 at heads of 128 and wider."""
    pack = 1
    while 2 * pack * head_dim <= 128 and (heads // groups) % (2 * pack) == 0:
        pack *= 2
    return pack


def state_shape(heads, head_dim, state_size, groups=1):
    """A slot's recurrent state as stored (float32): ``(heads / pack,
    state_size, pack x head_dim)`` (the module text)."""
    pack = lane_pack(heads, head_dim, groups)
    return (int(heads) // pack, int(state_size), pack * int(head_dim))


def to_stored(s, groups=1):
    """States in the plain order ``(.., H, P, N)`` as a pool keeps
    them, ``(.., H / pack, N, pack x P)``: head ``j x pack + q`` in
    lanes ``q x P .. (q + 1) x P`` of row-block ``j``."""
    import jax.numpy as jnp

    h, p, n = s.shape[-3:]
    pack = lane_pack(h, p, groups)
    s = s.reshape(s.shape[:-3] + (h // pack, pack, p, n))
    return jnp.moveaxis(s, -1, -3).reshape(s.shape[:-4]
                                           + (h // pack, n, pack * p))


def from_stored(s, head_dim):
    """:func:`to_stored`'s inverse: ``(.., H / pack, N, pack x P)`` ->
    ``(.., H, P, N)``."""
    import jax.numpy as jnp

    rows, n, lanes = s.shape[-3:]
    pack = lanes // head_dim
    s = s.reshape(s.shape[:-3] + (rows, n, pack, head_dim))
    return jnp.moveaxis(s, -3, -1).reshape(s.shape[:-4]
                                           + (rows * pack, head_dim, n))


def _by_group(a, heads):
    """``B`` or ``C`` (.., G, N) -> (.., H, N): each group's row for the
    consecutive heads (or packs of heads) it serves."""
    import jax.numpy as jnp

    return jnp.repeat(a, heads // a.shape[-2], axis=-2)


def _one_token(s, x, dt, A, B, C, D):
    """The two lines over any leading axes: ``s`` (.., H, P, N), ``x``
    (.., H, P), ``dt`` (.., H), ``A``, ``D`` (H,), ``B``, ``C`` (.., G,
    N) -> (y (.., H, P), s)."""
    import jax.numpy as jnp

    h = x.shape[-2]
    B, C = _by_group(B, h), _by_group(C, h)
    s = s * jnp.exp(A * dt)[..., None, None] \
        + (dt[..., None] * x)[..., :, None] * B[..., None, :]
    return (s * C[..., None, :]).sum(axis=-1) + D[:, None] * x, s


def recurrence(x, dt, A, B, C, D, s0=None):
    """The plain form, token by token: ``x`` (B, T, H, P), ``dt`` (B, T,
    H) (after its softplus), ``A`` (H,) negative, ``B``, ``C`` (B, T, G,
    N), ``D`` (H,), all float32; ``s0`` (B, H, P, N) or None for zeros
    -> (y (B, T, H, P), the state after the last row)."""
    import jax
    import jax.numpy as jnp

    b, _t, h, p = x.shape
    if s0 is None:
        s0 = jnp.zeros((b, h, p, B.shape[-1]), jnp.float32)

    def one(s, row):
        xr, dtr, br, cr = row
        y, s = _one_token(s, xr, dtr, A, br, cr, D)
        return s, y

    s, y = jax.lax.scan(one, s0, tuple(jnp.moveaxis(a, 1, 0)
                                       for a in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1), s


# -- the step -------------------------------------------------------------------

def step_applicable(platform, mesh, heads, head_dim, state_size, groups=1):
    """Whether :func:`step` is the kernel, from what the caller
    observes: the platform the pool lives on, the engine's mesh (a
    sharded pool would need a ``shard_map`` wrapper) and the shapes
    Mosaic tiles without padding: a stored row whole lanes of 128, the
    state's rows whole sublane tiles, a group's packed heads too (or all
    of the heads one group)."""
    pack = lane_pack(heads, head_dim, groups)
    rows = heads // groups // pack
    return (platform == "tpu" and mesh is None
            and (pack * head_dim) % 128 == 0 and state_size % 8 == 0
            and heads % groups == 0 and (rows % 8 == 0 or groups == 1))


def step_form(heads, head_dim, state_size, groups=1):
    """``"step_kernel"`` or ``"step_xla"``: which form :func:`step`
    takes over slots of ``heads`` heads ``(head_dim, state_size)`` in
    ``groups`` groups here and now, from the platform programs are
    compiled for and the active mesh; a served model's layer and its
    engine's ``linear_attention`` counter ask the same function."""
    import jax

    from .. import parallel

    ok = step_applicable(jax.default_backend(), parallel.current_mesh(),
                         heads, head_dim, state_size, groups)
    return "step_kernel" if ok else "step_xla"


def _one_token_stored(s, xdt, decay, B, C):
    """The recurrence's two lines over stored states: ``s`` (.., R, N,
    L) (``R`` row-blocks of ``L`` lanes: a pack of heads), ``xdt``,
    ``decay`` (.., R, L) lane rows, ``B``, ``C`` (.., R, N) each
    row-block's group -> (``S C`` (.., R, L), s)."""
    s = s * decay[..., None, :] + B[..., :, None] * xdt[..., None, :]
    return (s * C[..., :, None]).sum(axis=-2), s


def _step_kernel(x_ref, decay_ref, b_ref, c_ref, s_ref, o_ref, so_ref, *,
                 rows):
    """One slot's one group of ``rows`` row-blocks (a pack of heads
    each).  ``x_ref``, ``decay_ref`` (1, rows, L): each pack's ``dt x``
    and ``exp(A dt)`` as lane rows; ``b_ref``, ``c_ref`` (1, 1, 1, N)
    the group's rows, loaded once and turned, once a block, into what
    the state's rows (its ``N``) multiply by: row ``n`` all ``B[n]``
    (a column array in HBM would pad every value to a lane row);
    ``s_ref`` / ``so_ref`` the same (1, rows, N, L) block of the pool;
    ``o_ref`` (1, rows, L) the packs' ``S C``, summed down the rows."""
    import jax.numpy as jnp

    ns, lanes = s_ref.shape[2:]

    def down_rows(ref):                                      # -> (N, L)
        square = jnp.broadcast_to(ref[0, 0], (ns, ns)).T
        return square if lanes == ns else jnp.broadcast_to(
            square[:, :1], (ns, lanes))

    b, c = down_rows(b_ref), down_rows(c_ref)
    for j in range(rows):
        s = s_ref[0, j] * decay_ref[0, j:j + 1, :] \
            + b * x_ref[0, j:j + 1, :]                       # (N, L)
        so_ref[0, j] = s
        o_ref[0, j:j + 1, :] = (s * c).sum(axis=0, keepdims=True)


def _step_pallas(pool, xdt, decay, B, C, interpret=False):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, r, ns, lanes = pool.shape
    g = B.shape[1]
    rb = r // g
    row = pl.BlockSpec((1, rb, lanes), lambda i, j: (i, j, 0))
    col = pl.BlockSpec((1, 1, 1, ns), lambda i, j: (i, j, 0, 0))
    blk = pl.BlockSpec((1, rb, ns, lanes), lambda i, j: (i, j, 0, 0))
    return pl.pallas_call(
        functools.partial(_step_kernel, rows=rb),
        grid=(n, g),
        in_specs=[row, row, col, col, blk],
        out_specs=[row, blk],
        out_shape=[jax.ShapeDtypeStruct((n, r, lanes), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the state is written where it was read: no second pool
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="ssm_state_step",
        interpret=interpret,
    )(xdt, decay, B[:, :, None], C[:, :, None], pool)


def step(pool, x, dt, A, B, C, D, live=None, kernel=False, interpret=False):
    """One token a slot: ``pool`` (slots, H / pack, N, pack x P) float32,
    as stored (:func:`state_shape`); ``x`` (slots, H, P), ``dt`` (slots,
    H), ``A``, ``D`` (H,), ``B``, ``C`` (slots, G, N), float32 -> (y
    (slots, H, P), the pool after the token).  ``live`` (slots,) bool:
    the slots this step owns; any other enters with ``dt`` = 0, so its
    state stays as it is (a step is not idempotent: a slot stepped
    twice at one position would hold the token twice).  ``kernel``
    (static; the caller decides it from :func:`step_applicable`) picks
    the Pallas kernel over the XLA form.  Every slot computes either
    way."""
    import jax
    import jax.numpy as jnp

    n, h, p = x.shape
    r, lanes = pool.shape[1], pool.shape[3]
    if live is not None:
        dt = jnp.where(live[:, None], dt, 0.0)
    # a pack's heads side by side along a lane row
    xdt = (dt[..., None] * x).reshape(n, r, lanes)
    decay = jnp.repeat(jnp.exp(A * dt), p, axis=-1).reshape(n, r, lanes)
    if kernel:
        y, pool = _step_pallas(pool, xdt, decay, B, C, interpret)
    else:
        with jax.named_scope("ssm_state_step"):
            # each row-block's group: its B and C
            y, pool = _one_token_stored(pool, xdt, decay, _by_group(B, r),
                                        _by_group(C, r))
    return y.reshape(n, h, p) + D[:, None] * x, pool


# -- the chunked scan -----------------------------------------------------------

def chunk_scan(x, dt, A, B, C, D, live=None, s0=None, chunk=CHUNK):
    """The prefill form: arguments as :func:`recurrence` takes them,
    ``live`` (B, T) bool the rows a request owns (None: all).  A row
    that is not live enters with ``dt`` = 0 and leaves the state as it
    was (its own output is nobody's).  -> (y (B, T, H, P), the state
    after each sequence's last LIVE row, (B, H, P, N))."""
    import jax
    import jax.numpy as jnp

    b, t, h, p = x.shape
    g, ns = B.shape[-2:]
    r = h // g
    if live is not None:
        dt = jnp.where(live[..., None], dt, 0.0)
    c = int(chunk)
    tp = -(-t // c) * c
    if tp != t:
        pad = lambda a: jnp.pad(                            # noqa: E731
            a, ((0, 0), (0, tp - t)) + ((0, 0),) * (a.ndim - 2))
        x, dt, B, C = (pad(a) for a in (x, dt, B, C))
    n = tp // c
    if s0 is None:
        s0 = jnp.zeros((b, h, p, ns), jnp.float32)
    mm = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)

    with jax.named_scope("ssm_chunk_scan"):
        # (B, G, R, N, C, ..): a group's heads side by side, their chunks too
        heads = lambda a: jnp.moveaxis(                     # noqa: E731
            a.reshape((b, n, c, g, r) + a.shape[3:]), (1, 2), (3, 4))
        xc = heads(x * dt[..., None])                       # (B, G, R, N, C, P)
        lc = jnp.cumsum(heads(A * dt), axis=-1)             # L to each row
        groups = lambda a: jnp.moveaxis(                    # noqa: E731
            a.reshape(b, n, c, g, ns), 3, 1)
        bc, cc = groups(B), groups(C)                       # (B, G, N, C, K)
        rows = jnp.arange(c)
        # decay from row s to row t of a chunk, where s <= t; 0 elsewhere
        # (masked before the exponential: above the diagonal it would grow)
        diff = lc[..., :, None] - lc[..., None, :]
        decay = jnp.exp(jnp.where(rows[:, None] >= rows[None, :], diff,
                                  -jnp.inf))
        # row t reads row s <= t: C_t . B_s once a GROUP, the decay a head
        cb = mm("bgntk,bgnsk->bgnts", cc, bc)
        y = mm("bgrnts,bgrnsp->bgrntp", cb[:, :, None] * decay, xc)
        last = lc[..., -1]                                  # (B, G, R, N)
        x_out = xc * jnp.exp(last[..., None] - lc)[..., None]

        def one(s, xs):
            cc, into, x_out, bc, last = xs
            y0 = mm("bgtk,bgrpk->bgrtp", cc, s) * into[..., None]
            s = s * jnp.exp(last)[..., None, None] \
                + mm("bgrtp,bgtk->bgrpk", x_out, bc)
            return s, y0

        s, y0 = jax.lax.scan(one, s0.reshape(b, g, r, p, ns), (
            jnp.moveaxis(cc, 2, 0), jnp.moveaxis(jnp.exp(lc), 3, 0),
            jnp.moveaxis(x_out, 3, 0), jnp.moveaxis(bc, 2, 0),
            jnp.moveaxis(last, 3, 0)))
        # (B, G, R, N, C, P) -> (B, T, H, P)
        y = (y + jnp.moveaxis(y0, 0, 3)).transpose(0, 3, 4, 1, 2, 5) \
            .reshape(b, tp, h, p)[:, :t] + D[:, None] * x[:, :t]
    return y, s.reshape(b, h, p, ns)
