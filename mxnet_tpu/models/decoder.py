"""What every served decoder shares: the cache spec, the cache views
its one attention runs over, and the four paged programs, written once.

Reference: NONE (the reference predates LLM serving).

A served architecture is a :class:`PagedDecoder` subclass, built by the
net's ``serving_decoder(max_len)`` hook, that supplies ``cache_spec()``
(which layers keep what, and how the model decodes: the next token a
step, or :class:`BlockDecoding`, the positions of a block in any order),
``_weights()`` (``layers``: a dict a layer; ``emb``), ``layer(p, x, rope,
view) -> (x, kept, expert rows or None)``, ``_logits(w, x)`` and, for a
state layer, ``_sequence_state(kept, t0)``: what prefill keeps of a
whole sequence, as ``CacheSpec.state_entry`` lays a layer's arrays out.
A model that runs its stack several times a token says so in its spec
(``CacheSpec.passes``) and supplies ``end_pass(w, x)``, what follows the
stack after every pass.

A cache view holds where K and V live and answers one call, ``attend(q,
k, v) -> (context, kept)``: this call's queries, keys and values after
RoPE, heads-major (``(B, H, T, hd)``; a step's T is 1); the view stores
k/v as its kind stores them and gives back the context with the heads
beside their channels (``(B, T, H, hd)``; a step may leave its unit T
where it is) and what a cache keeps of the call.  A new cache kind is
one more view and, if it has a storage format, that format's functions
under ``ops/``.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import numpy as np

from ..base import MXNetError
from ..ops import latent_cache, paged_attention, sparse_select
from ..ops.attention import masked_attention
from ..ops.flash_attention import prefill_flash_attention
from .moe import expert_product

__all__ = ["CacheSpec", "BlockDecoding", "PagedDecoder", "Causal",
           "SelectingCausal", "BehindPrefix", "DenseCache", "StepView", "rms_norm",
           "layer_norm",
           "split_heads", "rope_tables", "apply_rope",
           "headnorm_qkv", "headnorm_attention", "block_commit",
           "causal_conv", "ring_conv",
           "ring_at_length"]


class BlockDecoding(NamedTuple):
    """How a block-diffusion decoder generates (``CacheSpec.decoding``):
    a block of ``block_len`` positions at a time, each position holding
    ``mask_id`` until a pass commits it; ``steps`` denoising passes a
    block at most, ``block_len / steps`` commits a pass at least (the
    remainder on the first passes), every masked position whose
    confidence passes ``threshold`` where those are more; then one pass
    more over the finished block, whose keys and values stay."""

    block_len: int
    mask_id: int
    steps: int
    threshold: float

    def schedule(self):
        """Commits a pass at least, pass by pass: ``(steps,)`` ints."""
        base, rem = divmod(self.block_len, self.steps)
        return tuple(base + (i < rem) for i in range(self.steps))


class CacheSpec:
    """What a served model's decoder keeps between steps, layer by
    layer — the answer to the engine's question (``decoder.cache_spec()``).

    ``layers[l]`` is ``"kv"`` (the layer owns a K and a V block pool
    ``(num_blocks, num_kv_heads, block_size, head_dim)``, addressed
    through the slots' block tables), ``"state"`` (it owns the arrays
    of ``state_arrays``, ``((shape, dtype), ...)``: of each a
    ``(num_slots,) + shape`` array of ITS dtype, None the weights': a
    fixed-size state a slot, written whole at admission and in place by
    every step; ``state_shape`` is the one-array case in the weights'
    dtype.  A layer's cache entry is that array where it owns one, a
    tuple of them where several: :meth:`state_entry`) or ``"latent"`` (it
    owns, in the same block tables, a pool of latent rows, ``latent_dim``
    values a token, and a pool of index keys, ``index_dim`` values a
    token, stored as ``ops.latent_cache`` stores them; a query reads
    the ``select_topk`` latent rows its indexer selects).  A spec of
    ``"kv"`` layers and no latent one that states ``index_dim`` and
    ``select_topk`` is a SELECTING K/V cache (:attr:`kv_selecting`; K/V
    layers beside latent ones read all they see): each such layer owns
    a third pool beside K and V, of index keys
    (``ops.sparse_select.index_pool_shape``), its K and V pools keep
    every KV head of a token in one stored row
    (``ops.paged_attention.selected_rows``), and a query
    reads the ``select_topk`` K/V rows its indexer selects.
    ``expert_layers`` x ``num_experts`` is the shape of the per-expert
    row counts that
    the step and prefill programs of a model with routed experts
    return beside their tokens (0: none).  ``decoding``: None for a
    decoder that yields the next token a step, left to right, or the
    :class:`BlockDecoding` of one that commits the positions of a block
    in any order.  ``passes``: how many times the stack of ``layers``
    runs a token, every pass with the same weights and its OWN rows: a
    token then keeps ``passes`` x ``layers`` K/V rows, and a layer's K
    and V pool hold ``passes x num_blocks`` blocks, pass ``t``'s at the
    slots' block ids + ``t x num_blocks``
    (``ops.paged_attention.pass_blocks``).  1 for a stack run once;
    above 1 every layer keeps K/V, none routes, and the model decodes
    the next token a step."""

    __slots__ = ("layers", "num_kv_heads", "head_dim", "state_arrays",
                 "expert_layers", "num_experts", "decoding", "latent_dim",
                 "index_dim", "select_topk", "passes")

    def __init__(self, layers, num_kv_heads, head_dim, state_shape=None,
                 expert_layers=0, num_experts=0, decoding=None,
                 latent_dim=0, index_dim=0, select_topk=0,
                 state_arrays=None, passes=1):
        self.layers = tuple(layers)
        if any(kind not in ("kv", "state", "latent")
               for kind in self.layers):
            raise MXNetError(f"unknown cache kind in {self.layers}")
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        if state_arrays is None and state_shape is not None:
            state_arrays = ((state_shape, None),)
        self.state_arrays = tuple(
            (tuple(int(d) for d in shape),
             None if dtype is None else np.dtype(dtype))
            for shape, dtype in state_arrays or ())
        self.expert_layers = int(expert_layers)
        self.num_experts = int(num_experts)
        self.decoding = decoding
        self.latent_dim = int(latent_dim)
        self.index_dim = int(index_dim)
        self.select_topk = int(select_topk)
        if self.state_layers and not self.state_arrays:
            raise MXNetError("state layers need a state_shape, or the "
                             "state_arrays a layer owns")
        if self.latent_layers and not (self.latent_dim and self.index_dim
                                       and self.select_topk):
            raise MXNetError("latent layers need latent_dim, index_dim "
                             "and select_topk")
        if bool(self.index_dim) != bool(self.select_topk) or (
                self.select_topk
                and not (self.latent_layers or self.kv_layers)):
            raise MXNetError(
                "index_dim and select_topk go together and say that "
                "layers in the block tables select what a query reads: "
                "the latent layers where there are any, else the K/V "
                "layers")
        self.passes = int(passes)
        if self.passes < 1:
            raise MXNetError("a stack runs at least once: passes >= 1")
        if self.passes > 1 and (self.kv_layers != len(self.layers)
                                or self.expert_layers or self.select_topk
                                or decoding is not None):
            raise MXNetError(
                "a stack run several times a token (passes > 1) keeps K/V "
                "rows a pass: a per-slot state, latent rows, a selection, "
                "routed experts' row counts and block decoding have no "
                "per-pass form yet")
        if self.select_topk and decoding is not None:
            raise MXNetError(
                "a block decoder's pass has several columns a slot; a "
                "selecting layer's step selects for one new token a slot")

    @property
    def kv_layers(self):
        return self.layers.count("kv")

    @property
    def state_layers(self):
        return self.layers.count("state")

    @property
    def latent_layers(self):
        return self.layers.count("latent")

    @property
    def kv_selecting(self):
        """Whether the K/V layers select what a query reads: each then
        owns an index-key pool beside K and V."""
        return bool(self.kv_layers and self.select_topk
                    and not self.latent_layers)

    def kv_bytes_per_block(self, block_size, itemsize):
        """Bytes one block holds over every layer that keeps rows in
        the block tables: K and V of the K/V layers, once a pass, and
        their index keys where they select; the latent rows and index
        keys of the latent layers; all as stored (padding counted)."""
        return 2 * self.passes * self.kv_layers * self.num_kv_heads \
            * int(block_size) * self.head_dim * int(itemsize) \
            + self.kv_layers * self.kv_selecting \
            * sparse_select.index_bytes_per_block(
                block_size, self.index_dim, itemsize) \
            + self.latent_layers * latent_cache.bytes_per_block(
                block_size, self.latent_dim, self.index_dim, itemsize)

    @staticmethod
    def state_entry(arrays):
        """A state layer's cache entry from its arrays in the spec's
        order: the array itself where the layer owns one, the tuple
        where several."""
        arrays = tuple(arrays)
        return arrays[0] if len(arrays) == 1 else arrays

    @staticmethod
    def entry_arrays(entry):
        """:meth:`state_entry`'s inverse: always a tuple."""
        return entry if isinstance(entry, tuple) else (entry,)

    def state_array_bytes(self, itemsize):
        """Bytes of each of a state layer's arrays a slot, in their
        order; an array without a dtype of its own holds values of
        ``itemsize`` bytes (the weights')."""
        return tuple(
            math.prod(shape) * (int(itemsize) if dtype is None
                                else dtype.itemsize)
            for shape, dtype in self.state_arrays)

    def state_bytes_per_slot(self, itemsize):
        """Bytes of one slot's state over every state layer, each array
        by its own dtype."""
        return self.state_layers * sum(self.state_array_bytes(itemsize))


# -- what the models' layers share ---------------------------------------------

def rms_norm(x, w, eps):
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    var = (xf * xf).mean(axis=-1, keepdims=True)
    return (xf / jnp.sqrt(var + eps) * w.astype(jnp.float32)) \
        .astype(x.dtype)


def layer_norm(x, w, b, eps):
    """LayerNorm with a weight and a bias over the last axis, in float32
    (an indexer's key norm)."""
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    xf = xf - xf.mean(axis=-1, keepdims=True)
    var = (xf * xf).mean(axis=-1, keepdims=True)
    return (xf / jnp.sqrt(var + eps) * w.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x.dtype)


def rope_tables(t, head_dim, theta):
    """cos/sin tables (T, head_dim/2) — compile-time constants."""
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2,
                                     dtype=np.float64) / head_dim))
    pos = np.arange(t, dtype=np.float64)
    ang = np.outer(pos, inv)
    return (np.cos(ang).astype(np.float32),
            np.sin(ang).astype(np.float32))


def apply_rope(x, cos, sin):
    """x (B, H, T, D) with D even; rotate pairs (x[..., ::2], x[..., 1::2])."""
    import jax.numpy as jnp

    x1 = x[..., ::2]
    x2 = x[..., 1::2]
    xr1 = x1 * cos - x2 * sin
    xr2 = x1 * sin + x2 * cos
    out = jnp.stack([xr1, xr2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def split_heads(a, n):
    """A projection's output, heads-major as the views take it:
    ``(B, T, n * hd) -> (B, n, T, hd)``; a step's ``(S, n * hd)`` has
    T = 1."""
    if a.ndim == 2:
        return a.reshape(a.shape[0], n, 1, -1)
    b, t, _ = a.shape
    return a.reshape(b, t, n, -1).transpose(0, 2, 1, 3)


def headnorm_qkv(p, u, num_heads, num_kv_heads, eps):
    """The Qwen3 family's projections: ``u`` (B, T, H), or a step's (S,
    H), through ``p["q"]`` / ``["k"]`` / ``["v"]`` (out, in), heads-major
    (:func:`split_heads`), q and k through an RMSNorm over each head
    (``q_norm`` / ``k_norm``: one learned weight of ``head_dim``, shared
    by the heads), BEFORE any rotation -> (q, k, v)."""
    q = rms_norm(split_heads(u @ p["q"].T, num_heads), p["q_norm"], eps)
    k = rms_norm(split_heads(u @ p["k"].T, num_kv_heads), p["k_norm"], eps)
    return q, k, split_heads(u @ p["v"].T, num_kv_heads)


def headnorm_attention(p, u, rope, view, num_heads, num_kv_heads, eps):
    """GQA over :func:`headnorm_qkv`'s heads, rotated in pairs (2i, 2i +
    1), over a cache view: ``rope`` the (cos, sin) rows of the call's
    positions over heads-major q and k; ``p`` also holds ``o`` -> (y,
    what the view kept)."""
    q, k, v = headnorm_qkv(p, u, num_heads, num_kv_heads, eps)
    q, k = apply_rope(q, *rope), apply_rope(k, *rope)
    ctx, kept = view.attend(q, k, v)
    return ctx.reshape(*u.shape[:-1], -1) @ p["o"].T, kept


def causal_conv(w, mixed, bias=None):
    """A causal depthwise convolution over whole sequences and its
    SiLU: ``w`` (taps, C), tap ``j`` multiplying the input at ``t -
    (taps - 1) + j``, zeros before the start; ``mixed`` (B, T, C);
    ``bias`` (C,) or None."""
    import jax
    import jax.numpy as jnp

    taps, t = w.shape[0], mixed.shape[1]
    xp = jnp.pad(mixed, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(w[j] * xp[:, j:j + t] for j in range(taps))
    if bias is not None:
        conv = conv + bias
    return conv * jax.nn.sigmoid(conv)


def ring_conv(w, mixed, ring, pos, live, bias=None):
    """:func:`causal_conv`'s one token a slot against the ring of its
    last ``taps - 1`` inputs: ``mixed`` (S, C) the input at ``pos``
    (S,), ``ring`` (S, taps - 1, C) with row ``t % (taps - 1)`` holding
    position ``t``'s -> (the convolution and its SiLU, the ring with
    ``mixed`` in the oldest row's place).  The oldest row
    makes way, which a second step at this position would miss: only a
    slot the step owns (``live``) writes."""
    import jax
    import jax.numpy as jnp

    taps = w.shape[0]
    rows = jnp.arange(ring.shape[0])
    # tap j multiplies the input at pos - (taps - 1) + j
    conv = w[taps - 1] * mixed + sum(
        w[j] * ring[rows, (pos - (taps - 1) + j) % (taps - 1)]
        for j in range(taps - 1))
    if bias is not None:
        conv = conv + bias
    conv = conv * jax.nn.sigmoid(conv)
    at = pos % (taps - 1)
    ring = ring.at[rows, at].set(
        jnp.where(live[:, None], mixed, ring[rows, at]))
    return conv, ring


def ring_at_length(mixed, t0, kk):
    """The ring :func:`ring_conv` reads next, from a whole sequence's
    convolution input ``mixed`` (B, T, C) and the TRUE lengths ``t0``:
    the input at ``t0 - kk .. t0 - 1`` laid out as the ring keeps it
    (row ``t % kk``), zeros where the prompt is shorter, never the
    padded end's."""
    import jax.numpy as jnp

    t0 = jnp.broadcast_to(t0, (mixed.shape[0],))
    # ring row r holds the one position p in [t0-kk, t0) with p % kk == r
    src = t0[:, None] - 1 - (t0[:, None] - 1 - jnp.arange(kk)[None]) % kk
    take = jnp.clip(src, 0, mixed.shape[1] - 1)[:, :, None]
    return jnp.where((src >= 0)[:, :, None],
                     jnp.take_along_axis(mixed, take, axis=1), 0)


def block_commit(logits, ids, masked, step, decoding):
    """A block decoder's commit rule, in float32 on the device:
    ``logits`` (S, B, V) of one pass over each slot's block, ``ids``
    (S, B) what the block holds (the mask id where ``masked`` (S, B)),
    ``step`` (S,) the block's denoising pass, ``decoding`` the
    :class:`BlockDecoding`.  A row's candidate is its argmax and its
    confidence the candidate's softmax probability; among the masked
    rows those above the threshold are committed where they are at
    least the pass's share of the schedule, else that many of the most
    confident (a tie goes to the earlier position).  A block without
    masks commits nothing: its pass is the one that leaves its keys and
    values.  -> (ids with the committed rows decided, commit (S, B))."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("block_commit"):
        lf = logits.astype(jnp.float32)
        x0 = jnp.argmax(lf, axis=-1).astype(jnp.int32)
        conf = 1.0 / jnp.exp(lf - lf.max(axis=-1, keepdims=True)).sum(-1)
        conf = jnp.where(masked, conf, -jnp.inf)
        need = jnp.asarray(decoding.schedule(), jnp.int32)[
            jnp.clip(step, 0, decoding.steps - 1)]            # (S,)
        high = conf > decoding.threshold
        j = jnp.arange(ids.shape[1])
        a, b = conf[:, :, None], conf[:, None, :]
        ahead = (b > a) | ((b == a) & (j[None, None, :] < j[None, :, None]))
        most = masked & (ahead.sum(-1) < need[:, None])
        commit = jnp.where((high.sum(-1) >= need)[:, None], high, most)
        return jnp.where(commit, x0, ids), commit


def block_advance(xp, state, ids, commit, stepped, decoding):
    """A block decoder's bookkeeping of one pass, the same lines on the
    device (``xp`` ``jax.numpy``: inside the pass's own program, so the
    next pass can be queued before this one is fetched) and on the host
    (``numpy``: the engine's mirrors, a pass late).  ``state``: ``(ids
    (S, B), masked (S, B), step (S,), pos0 (S,))`` as the pass read
    them; ``ids`` / ``commit`` what :func:`block_commit` made of it;
    ``stepped`` (S,) the rows the pass was made for.  A stepped block
    that held no mask has had the pass that leaves its keys and values:
    its cursor moves a block on, a block of masks opens, the count
    starts again.  Any other stepped block takes the pass's ids, loses
    the masks it committed and counts a pass.  A row not stepped is
    left as it was.  -> the state the next pass reads."""
    held, masked, step, pos0 = state
    stored = stepped & ~masked.any(axis=1)
    going = stepped & ~stored
    return (xp.where(stored[:, None], decoding.mask_id,
                     xp.where(going[:, None], ids, held)),
            xp.where(stored[:, None], True,
                     masked & ~(commit & going[:, None])),
            xp.where(stored, 0, step + going),
            xp.where(stored, pos0 + decoding.block_len, pos0))


# -- cache views ------------------------------------------------------------------

class Causal:
    """Whole sequences, each attending itself causally (prefill).
    Nothing is stored: what a cache would keep is the raw (k, v) rows
    ``(B, Hkv, T, hd)``.  ``live`` (B, T): the positions a request owns
    (not a padded end's), for a layer that counts rows.  ``lengths``
    (B,): with them the attention is the flash forward kernel
    (``ops.flash_attention.prefill_flash_attention``: no score tensor
    and no repeated K/V in HBM, tiles past a row's length skipped);
    without, ``masked_attention`` under ``tril``.  ``block``: a block
    decoder's block length; position ``p`` then sees ``t < (p // block
    + 1) * block``, whole earlier blocks and its own in both
    directions."""

    pos = None

    def __init__(self, t, live=None, lengths=None, block=None):
        import jax.numpy as jnp

        self.live, self.lengths, self.block = live, lengths, block
        if lengths is None and block is None:
            self.mask = jnp.tril(jnp.ones((t, t), bool))    # (Q, T)
        elif lengths is None:
            p = jnp.arange(t)
            self.mask = p[None, :] < (p[:, None] // block + 1) * block

    def attend(self, q, k, v):
        if self.lengths is None:
            ctx = masked_attention(q, k, v, self.mask)
        elif self.block is None:
            ctx = prefill_flash_attention(q, k, v, self.lengths)
        else:
            ctx = prefill_flash_attention(q, k, v, self.lengths,
                                          span=self.block)
        return ctx.transpose(0, 2, 1, 3), (k, v)


class SelectingCausal:
    """Whole sequences of a layer that selects (prefill): row ``t``
    attends the ``topk`` rows ``s <= t`` its indexer selects, all of
    them while they are fewer.  Nothing is stored: what a cache would
    keep is the sequence's logical rows, ``(latent rows, index keys)``
    of a latent layer (:meth:`attend_latent`), ``(k, v, index keys)`` of
    a K/V layer (:meth:`attend_selecting`).  ``live`` (B, T) the
    positions a request owns.  With ``lengths`` (B,) the selection and
    the attention run in tiles of query rows and skip those past every
    row's end (``ops.sparse_select.causal_tiles``); without, every row
    at once in the kind's plain form."""

    pos = None

    def __init__(self, topk, live=None, lengths=None):
        self.topk, self.live, self.lengths = topk, live, lengths

    def attend_latent(self, make_query, latent, index_keys, per_row,
                      w_uk, w_uv, scale, finish):
        """``make_query(*rows of per_row) -> (q_nope, q_rope, q_idx,
        w_idx)``; ``finish(heads (.., H, dv)) -> y`` -> (y, kept)."""
        if self.lengths is None:
            y = latent_cache.plain_causal_attention(
                make_query, latent, index_keys, per_row, self.topk, w_uk,
                w_uv, scale, finish)
        else:
            y = latent_cache.causal_attention(
                make_query, latent, index_keys, per_row, self.lengths,
                self.topk, w_uk, w_uv, scale, finish)
        return y, (latent, index_keys)

    def attend_selecting(self, q, k, v, q_idx, w_idx, index_keys):
        """A K/V layer's selection and attention: ``q`` (B, H, T, hd),
        ``k`` / ``v`` (B, Hkv, T, hd) after their rotation, ``q_idx``
        (B, T, J, D), ``w_idx`` (B, T, J) and ``index_keys`` (B, T, D)
        of the indexer -> (the context (B, T, H, hd), kept)."""
        if self.lengths is None:
            ctx = sparse_select.kv_plain_causal_attention(
                q, k, v, q_idx, w_idx, index_keys, self.topk)
        else:
            ctx = sparse_select.kv_causal_attention(
                q, k, v, q_idx, w_idx, index_keys, self.lengths, self.topk)
        return ctx, (k, v, index_keys)


class BehindPrefix:
    """A suffix behind a reused prefix: ``entry = (K, V)`` each ``(B,
    Hkv, Lpre, hd)``, dense copies gathered from shared pool blocks,
    sentinel-padded past ``s0[b]``.  Suffix row j attends every real
    prefix column (``t < s0[b]``) plus the suffix causally —
    bit-identical attention to a full prefill; a row with no cache hit
    has ``s0[b] = 0``: every prefix column masked.  Keeps the suffix's
    own rows, for the request's PRIVATE blocks."""

    pos = live = None

    def __init__(self, entry, mask):
        self.entry, self.mask = entry, mask

    @staticmethod
    def mask_of(s0, lpre, ls):
        """(B, 1, Ls, Lpre + Ls), shared by the layers."""
        import jax.numpy as jnp

        b = s0.shape[0]
        mask_pre = (jnp.arange(lpre)[None, None, None, :]
                    < s0[:, None, None, None])      # (B,1,1,Lpre)
        mask_pre = jnp.broadcast_to(mask_pre, (b, 1, ls, lpre))
        mask_suf = jnp.broadcast_to(
            jnp.tril(jnp.ones((ls, ls), bool))[None, None],
            (b, 1, ls, ls))
        return jnp.concatenate([mask_pre, mask_suf], axis=-1)

    def attend(self, q, k, v):
        import jax.numpy as jnp

        kc = jnp.concatenate([self.entry[0], k], axis=2)
        vc = jnp.concatenate([self.entry[1], v], axis=2)
        return masked_attention(q, kc, vc, self.mask) \
            .transpose(0, 2, 1, 3), (k, v)


class DenseCache:
    """A dense ``(B, Hkv, max_len, hd)`` K and V cache, one new token a
    row, written at ``pos`` and masked ``t <= pos``: ``pos`` () for a
    batch decoded in lockstep (offline ``generate``)."""

    live = None

    def __init__(self, entry, pos, mask):
        self.entry, self.pos, self.mask = entry, pos, mask

    @staticmethod
    def mask_of(pos, max_len):
        """(1, T), shared by the rows and the layers."""
        import jax.numpy as jnp

        return (jnp.arange(max_len) <= pos)[None, :]

    def _write(self, cache, new):
        import jax.numpy as jnp
        from jax import lax

        z = jnp.zeros((), jnp.int32)
        return lax.dynamic_update_slice(cache, new, (z, z, self.pos, z))

    def attend(self, q, k, v):
        kc, vc = self._write(self.entry[0], k), self._write(self.entry[1], v)
        return masked_attention(q, kc, vc, self.mask), (kc, vc)


class StepView:
    """What a decode call's layer sees of the paged cache: its own
    ``entry`` (a ``(K pool, V pool)`` pair, or a state layer's
    ``CacheSpec.state_entry``: a ``(slots, L, hidden)`` state, or a
    tuple of such; a selecting layer's ``(latent pool, index-key pool)``
    or ``(K pool, V pool, index-key pool)``), each slot's position
    ``pos`` and, for a layer in the block tables, the
    call's ``ops.paged_attention.Window``.  The pool's layout is that
    module's; this view only says when to write and when to attend."""

    __slots__ = ("entry", "pos", "win", "topk", "selected")

    def __init__(self, entry, pos, win=None, topk=0):
        self.entry, self.pos, self.win, self.topk = entry, pos, win, topk
        #: a selecting layer's step leaves here what it selected: (S, k)
        #: positions, -1 where a slot sees fewer than k
        self.selected = None

    @property
    def live(self):
        return self.win.live

    def attend(self, q, k, v):
        kp = paged_attention.write_rows(self.entry[0], self.win, k)
        vp = paged_attention.write_rows(self.entry[1], self.win, v)
        return paged_attention.window_attention(q, kp, vp, self.win), \
            (kp, vp)

    def _select(self, index_pool, index_keys, q_idx, w_idx):
        """A selecting layer's step, either kind: the new token's index
        key written in place, the slot's cached index keys scored up to
        its position, the exact ``topk`` taken and left in
        ``selected`` -> (the index-key pool, ``idx``, ``valid``)."""
        import jax.numpy as jnp

        ip = sparse_select.write_rows(index_pool, self.win, index_keys)
        idx, valid = sparse_select.window_select(q_idx, w_idx, ip,
                                                 self.win, self.topk)
        self.selected = jnp.where(valid, idx, -1)
        return ip, idx, valid

    def attend_latent(self, make_query, latent, index_keys, per_row,
                      w_uk, w_uv, scale, finish):
        """A latent layer's step: the new token's latent row and index
        key (``(S, width)`` each) written in place, the slot's cached
        index keys scored up to its position, the exact ``topk`` taken
        and those latent rows alone attended, through the block table
        (``ops.latent_cache``)."""
        import jax.numpy as jnp

        lp = latent_cache.write_rows(self.entry[0], self.win, latent)
        q_nope, q_rope, q_idx, w_idx = make_query(*per_row)
        ip, idx, valid = self._select(self.entry[1], index_keys, q_idx,
                                      w_idx)
        heads = latent_cache.window_attention(
            q_nope, q_rope, lp, self.win, idx, valid, w_uk, w_uv, scale)
        return finish(heads), (lp, ip)

    def attend_selecting(self, q, k, v, q_idx, w_idx, index_keys):
        """A selecting K/V layer's step: the new token's K, V and index
        key (``q`` (S, H, 1, hd), ``k`` / ``v`` (S, Hkv, 1, hd),
        ``index_keys`` (S, D)) written in place, the slot's cached index
        keys scored up to its position with ``q_idx`` (S, J, D) /
        ``w_idx`` (S, J), the exact ``topk`` taken and those rows of K
        and V alone attended, through the block table -> (the context
        (S, H, hd), the three pools)."""
        import jax

        kp = paged_attention.write_rows(self.entry[0], self.win, k)
        vp = paged_attention.write_rows(self.entry[1], self.win, v)
        ip, idx, valid = self._select(self.entry[2], index_keys, q_idx,
                                      w_idx)
        with jax.named_scope("gqa_selected_attention"):
            ctx = sparse_select.gqa_selected_attention(
                q[:, :, 0], paged_attention.selected_rows(kp, self.win, idx),
                paged_attention.selected_rows(vp, self.win, idx), valid)
        return ctx, (kp, vp, ip)


# -- the programs -----------------------------------------------------------------

class PagedDecoder:
    """The four paged programs every served model shares.  Each embeds,
    builds what its layers' views share once (RoPE rows, the window or
    the mask), runs ``self.layer`` over every layer with that layer's
    view, takes the last position and ends in ``self._logits``; a model
    with routed experts gets its row counts ``(expert layers, E)`` back
    third."""

    #: a block decoder's block length (None: the next token a step): the
    #: one thing that says its prefill mask and the windows of its
    #: ``_verify_blocks_impl`` are a block's, not causal
    block_len = None

    def __init__(self, net, max_len):
        import jax.numpy as jnp

        self.cfg = cfg = net.config
        self.max_len = int(max_len)
        self._net = net
        cos, sin = rope_tables(self.max_len, cfg.head_dim, cfg.rope_theta)
        self._cos, self._sin = jnp.asarray(cos), jnp.asarray(sin)

    def expert_product(self, rows, dtype):
        """Which form the routed expert layers of a program of ``rows``
        rows take over a bank of ``dtype`` (``moe.expert_product``:
        ``"grouped_kernel"`` or ``"every_expert"``); None for a model
        without routed experts."""
        cfg = self.cfg
        if not self.cache_spec().expert_layers:
            return None
        return expert_product(rows, cfg.num_experts_per_tok,
                              cfg.num_experts, cfg.hidden_size,
                              cfg.moe_intermediate_size, dtype)

    def linear_attention(self):
        """Which form a step's linear-attention layers take
        (``ops.gated_delta.step_form``); None for a model without
        them."""
        return None

    def _layers(self, w, x, rope, views):
        """-> (x, what each layer's view kept, the expert rows as the
        programs return them: () or a 1-tuple)."""
        import jax.numpy as jnp

        views = list(views)
        if len(views) != len(w["layers"]):
            raise MXNetError(
                f"{len(views)} cache views for {len(w['layers'])} layers: "
                "the cache does not hold what the decoder's cache_spec() "
                "states")
        kept_all, counts = [], []
        for p, view in zip(w["layers"], views):
            x, kept, c = self.layer(p, x, rope, view)
            kept_all.append(kept)
            if c is not None:
                counts.append(c)
        return x, kept_all, ((jnp.stack(counts),) if counts else ())

    def end_pass(self, w, x):
        """What follows the stack after EVERY pass of a model that runs
        it several times (``CacheSpec.passes``): the model's to say."""
        raise NotImplementedError

    def _passes(self, one, carry):
        """The stack ``CacheSpec.passes`` times as ONE loop on the
        device: ``one(carry, t) -> (carry, what pass t kept)`` is traced
        once, whatever the passes, so a program holds the layers once
        -> (carry, what the passes kept, stacked on a new first axis)."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        with jax.named_scope("loop_pass"):
            return lax.scan(one, carry, jnp.arange(self.cache_spec().passes,
                                                   dtype=jnp.int32))

    def _decode_passes(self, w, cache, tables, x, pos, rope, paged_kernel):
        """:meth:`_decode` of a stack run several times: pass ``t``
        writes and reads its own rows of every layer's pool, through
        the slots' block tables moved to that pass's blocks; the pools
        ride the loop and are written in place."""
        passes = self.cache_spec().passes
        num_blocks = cache[0][0].shape[0] // passes

        def one(carry, t):
            x, cache = carry
            win = paged_attention.window(
                cache[0][0],
                paged_attention.pass_blocks(tables, t, num_blocks, passes),
                pos, self.max_len, paged_kernel)
            x, cache, _ = self._layers(
                w, x, rope, [StepView(e, pos, win) for e in cache])
            return (self.end_pass(w, x), cache), None

        (x, cache), _ = self._passes(one, (x, list(cache)))
        return self._logits(w, x), cache

    def _decode(self, w, cache, tables, x, pos, rope, paged_kernel):
        import jax.numpy as jnp

        spec = self.cache_spec()
        if len(cache) != len(spec.layers):
            raise MXNetError(
                f"a cache of {len(cache)} entries for the "
                f"{len(spec.layers)} layers of the decoder's cache_spec()")
        if spec.passes > 1:
            return self._decode_passes(w, cache, tables, x, pos, rope,
                                       paged_kernel)
        # the window is sized by a pool of rows in the block tables: any
        # layer's but a state layer's
        pool = next(e for kind, e in zip(spec.layers, cache)
                    if kind != "state")[0]
        win = paged_attention.window(pool, tables, pos, self.max_len,
                                     paged_kernel,
                                     block=self.block_len is not None)
        topk = spec.select_topk
        views = [StepView(e, pos, win, topk) for e in cache]
        x, cache, counts = self._layers(w, x, rope, views)
        # a selecting model's step says, last, what each layer read:
        # (layers, S, k) positions
        picked = [v.selected for v in views if v.selected is not None]
        return (self._logits(w, x), cache) + counts \
            + ((jnp.stack(picked),) if picked else ())

    def _step_blocks_impl(self, w, cache, tables, ids_t, pos,
                          paged_kernel=False):
        """Per-slot decode step against the PAGED cache, the core of
        continuous batching: requests admitted at different times
        decode in one program, each slot at its own position.
        ``cache[l]`` is a ``(K pool, V pool)`` pair shared by every slot
        or a state layer's ``(S,) + shape`` array (a tuple of them
        where it owns several), by the cache spec; ``tables``
        (S, MB) int32 holds each slot's block ids in logical order,
        vacant entries = ``num_blocks``; ``ids_t``, ``pos`` (S,) int32.
        -> (logits (S, V), cache[, expert rows]).  MB is static, so the
        compute cost matches a slot ledger's while HBM capacity is the
        POOL size — bounded by tokens in flight, not max_len × slots.

        Vacant slots run at pos 0 with token 0: their K/V write drops at
        the sentinel block, their state write lands in their own row,
        which admission overwrites whole, and the experts they are
        routed to do not count them (a slot is vacant while its table
        starts with the sentinel).  ``paged_kernel`` (static; the engine
        decides it from ``ops.paged_attention.applicable``) picks the
        Pallas kernel over the gathered view."""
        import jax.numpy as jnp

        pos = jnp.asarray(pos, jnp.int32)
        rope = (self._cos[pos][:, None, None, :],   # (S,1,1,hd/2)
                self._sin[pos][:, None, None, :])
        x = w["emb"][ids_t]                         # (S, H)
        return self._decode(w, cache, tables, x, pos, rope, paged_kernel)

    def _verify_blocks_impl(self, w, cache, tables, toks, pos0,
                            paged_kernel=False):
        """Speculative VERIFY forward: a widened :meth:`_step_blocks_impl`
        that advances every slot K = k+1 candidate positions in ONE
        dispatch.  ``toks`` (S, K) int32 is ``[last_committed, draft_1 ..
        draft_k]`` per slot; ``pos0`` (S,) each slot's committed write
        cursor, so window column j carries absolute position ``pos0[s]
        + j`` and sees ``t <= pos0[s] + j``: draft_j attends the
        in-window K/V of draft_1..j-1 it was conditioned on.  Returns the
        (S, K, V) logits — column j is the target model's next-token
        choice AFTER consuming ``toks[s, :j+1]``, exactly what the
        acceptance rule compares drafts against.  Rejected columns need
        no cleanup (``ops.paged_attention.write_rows``).  A decoder with
        per-slot state has no roll-back: its engine refuses
        speculation.

        A block decoder (``block_len``): the K columns are one block,
        ``toks`` its current ids (the mask id where a position
        is undecided) and ``pos0`` the block's first position: every
        column sees the whole block and all before it, and column j's
        logits are the distribution of token ``pos0 + j`` ITSELF (not
        shifted).  The block's K/V is written in place every pass; the
        pass over the finished block leaves what later blocks read (the
        stale-row invariant covers the passes before it)."""
        import jax.numpy as jnp

        kk = toks.shape[1]
        pos0 = jnp.asarray(pos0, jnp.int32)
        pw = pos0[:, None] + jnp.arange(kk, dtype=jnp.int32)[None, :]
        rope = (self._cos[pw][:, None], self._sin[pw][:, None])
        x = w["emb"][toks]                          # (S, K, H)
        return self._decode(w, cache, tables, x, pw, rope, paged_kernel)

    def _last(self, w, x, t0):
        import jax.numpy as jnp

        if t0.ndim == 0:
            return self._logits(w, jnp.take(x, t0 - 1, axis=1))
        # per-row true lengths (B,): serving admits prompts of different
        # lengths in one padded prefill, each row gathers its own last
        # real position
        return self._logits(w, jnp.take_along_axis(
            x, (t0 - 1)[:, None, None], axis=1)[:, 0])

    def _prefill_view(self, lp, real, lengths, t0):
        """The one view a prefill's layers share: whole sequences under
        the causal (or a block decoder's) mask; a model whose layers
        select what they read answers with its own."""
        return Causal(lp, real, lengths, self.block_len)

    def _prefill_rows_impl(self, w, ids, t0, flash=False):
        """Batched full-sequence prompt pass over PADDED ids (B, Lp) with
        true lengths ``t0`` (scalar, or (B,) a row each) -> (rows, logits
        at each row's last real position[, expert rows]).  ``rows[l]`` is
        the layer's raw post-RoPE (k, v) ``(B, Hkv, Lp, hd)`` — no
        max_len cache allocation, so the CALLER picks the storage
        layout: the offline path pads rows into per-batch max_len
        caches, the paged serving engine scatters them into pool blocks
        (the prefill→decode KV handoff) — or a state layer's state of
        the TRUE length (``_sequence_state``).  ``flash`` (static; the
        caller decides it from
        ``ops.flash_attention.prefill_applicable``) picks the flash
        forward kernel over ``masked_attention``.  A stack run several
        times (``CacheSpec.passes``) hands every pass's rows over:
        ``rows[l]`` is then (k, v) each ``(passes, B, Hkv, Lp, hd)``."""
        import jax.numpy as jnp

        b, lp = ids.shape
        rope = (self._cos[:lp][None, None], self._sin[:lp][None, None])
        x = w["emb"][ids]                                   # (B, Lp, H)
        t0 = jnp.asarray(t0, jnp.int32)
        # not the padded end
        real = jnp.arange(lp)[None] < (t0[:, None] if t0.ndim else t0)
        lengths = jnp.broadcast_to(t0, (b,)) if flash else None
        causal = self._prefill_view(lp, real, lengths, t0)
        if self.cache_spec().passes > 1:
            # rows[l]: the layer's (k, v), each (passes, B, Hkv, Lp, hd)
            def one(x, _t):
                x, rows, _ = self._layers(w, x, rope,
                                          (causal for _ in w["layers"]))
                return self.end_pass(w, x), rows

            x, rows = self._passes(one, x)
            return rows, self._last(w, x, t0)
        x, rows, counts = self._layers(w, x, rope,
                                       (causal for _ in w["layers"]))
        rows = [self._sequence_state(r, t0) if kind == "state" else r
                for kind, r in zip(self.cache_spec().layers, rows)]
        return (rows, self._last(w, x, t0)) + counts

    def _prefill_suffix_impl(self, w, prefix_kv, ids, t0, s0):
        """Prompt-SUFFIX prefill attending a reused prefix: the radix
        prefix cache supplies each row's leading ``s0[b]`` tokens of K/V
        (``prefix_kv[l]``, see :class:`BehindPrefix`), and only the
        novel suffix ``ids`` (B, Ls) runs through the transformer, row j
        at absolute position ``s0[b] + j`` (RoPE + mask) — at
        suffix-sized projection/MLP cost.  Returns the suffix rows'
        post-RoPE K/V and logits at each row's true last suffix
        position ``t0[b] - 1``."""
        import jax.numpy as jnp

        if self.cache_spec().passes > 1:
            raise MXNetError(
                "the suffix prefill has no loop over passes: a stack run "
                "several times a token is served without the radix "
                "prefix cache")
        ls = ids.shape[1]
        lpre = prefix_kv[0][0].shape[2]
        s0 = jnp.asarray(s0, jnp.int32)
        pw = s0[:, None] + jnp.arange(ls, dtype=jnp.int32)[None, :]
        pw = jnp.minimum(pw, jnp.int32(self.max_len - 1))
        rope = (self._cos[pw][:, None], self._sin[pw][:, None])
        x = w["emb"][ids]                           # (B, Ls, H)
        mask = BehindPrefix.mask_of(s0, lpre, ls)
        x, rows, counts = self._layers(
            w, x, rope, (BehindPrefix(e, mask) for e in prefix_kv))
        return (rows, self._last(w, x, jnp.asarray(t0, jnp.int32))) + counts
