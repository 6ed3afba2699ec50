"""Continuous-batching decode engine for the generative path.

The engine owns the device half of serving: weights (optionally int8),
the cache storage, and a fixed family of compiled programs that stay
shape-stable under arbitrary request traffic.  It asks the model for
two things (``net.serving_decoder(max_len)``): the decoder's paged
programs (``_step_blocks_impl``, ``_prefill_rows_impl``, written once in
``models.decoder.PagedDecoder``; the commit is the engine's scatter over
what they return) and its **cache spec**
(:class:`~mxnet_tpu.models.decoder.CacheSpec`): which layers own a K/V block pool and
which a fixed per-slot state, of what arrays.  A layer's state lives in
one array ``(num_slots,) + shape`` for each ``(shape, dtype)`` its spec
states (``CacheSpec.state_arrays``: a convolution's ring in the weights'
dtype, a linear-attention layer's float32 matrices beside it), each
beside the pools, donated through the step like them, and written whole
at admission.

K/V lives in a shared block pool per layer whose stored format is
``ops.paged_attention``'s alone (``pool_shape``; ``kv_pack`` KV heads to
a stored row); each slot carries a block-table row (vacant entries =
``num_blocks``, the out-of-bounds sentinel XLA's scatter rule DROPS).
Capacity is bounded by tokens in flight, not ``max_len × num_slots``.
Programs: **step** (``_step_blocks_impl`` — one signature, ever),
**prefill** (``_prefill_rows_impl`` at one (admit_bucket, prompt_bucket)
shape per bucket pair, returning RAW K/V rows — no max_len allocation),
and **scatter** (``ops.paged_attention.scatter_rows`` at the admitted
physical block ids — the prefill→decode KV handoff).

With ``mesh=`` the engine is mesh-native: every weight (and the KV
pool) is committed to the mesh via the serving partition-rule table
(``parallel.partition.SERVING_RULES`` unless ``partition_rules=``
overrides) — q/k/v/gate/up column-parallel, o/down row-parallel, KV
head axis sharded over ``tp`` — so the step/prefill/scatter compiles
are keyed by the mesh their inputs live on: one decode compile per
engine lifetime per mesh.  A dp axis is NOT this engine's business:
the server splits a dp×tp mesh into per-replica tp submeshes and runs
one engine per replica (serving/lanes.py).

How a model decodes is its decoder's to say (``cache_spec().decoding``).
None: the next token a slot a step, left to right.  A
:class:`~mxnet_tpu.models.decoder.BlockDecoding`: the engine's decode
program is one PASS over every slot's block of positions (still
``_step_fn``: the decoder's ``_verify_blocks_impl``, then the commit rule
on the device), :meth:`LlamaServingEngine.step` answers a
:class:`BlockTick`, the prefill stores the prompt's whole blocks and
yields the opening block instead of a token, and the host keeps each
slot's block (ids, which are undecided, its pass) between ticks.

Thread discipline: the prefill lane and the decode lane share one
engine.  ``dev_lock`` serializes every dispatch that MUTATES the KV
storage (decode step, handoff scatter, slot clears); the prefill
forward itself runs outside the lock, so a long prompt never stalls
decode — only its cheap block scatter briefly takes the lock.

Between any two step calls the scheduler may admit new requests
(prefill + scatter) or evict finished ones — the continuous-batching
join point.  A step is two halves, :meth:`LlamaServingEngine.dispatch_step`
(queue it; the cursors move on) and :meth:`LlamaServingEngine.fetch_step`
(wait for its tokens; book them): a slot's input token is the one the
step before produced, read on the device, so the decode lane queues
step K+1 before it fetches step K (``serving/lanes.py``
``DecodeLane._tick``); ``step()`` is the two back to back.  Weights are
frozen at engine build; ``int8=True`` stores them as per-output-channel
symmetric int8 (scale = max|row|/127) and
dequantizes in-kernel — the weight-only quantization the int8 MXU
pricing in ``INT8_TOPOLOGY_r05.json`` motivates.  The engine is driven
by the disaggregated lanes in :mod:`.lanes`.
"""
from __future__ import annotations

import threading
import time
from typing import NamedTuple

import numpy as np
from jax.profiler import TraceAnnotation

from .. import telemetry
from ..telemetry import numerics as _numerics
from ..telemetry import retrace as _retrace
from ..telemetry import tracing
from ..base import MXNetError
from ..ops import flash_attention
from .scheduler import _materialize

__all__ = ["LlamaServingEngine", "REFUSALS", "refuse"]

#: reviewed signature budget (mxlint T15): one decode-step program per
#: (batch bucket, cache length bucket) plus one prefill program per
#: prompt bucket — the prefill lane pads every batch to a bucket of its
#: replica's ``BucketPolicy`` (``serving/lanes.py``), fixed at construction
__compile_signatures__ = {
    "serving_step": "1 per (batch bucket, cache bucket); prefill adds "
                    "1 per prompt bucket",
    "serving_verify": "1 per engine — the k-token speculative verify "
                      "window (num_slots, spec_k+1) is shape-static",
    "serving_gather": "1 per (batch bucket, prefix bucket) — dense "
                      "prefix copy for suffix prefill",
    "serving_prefill_sfx": "1 per (batch bucket, prefix bucket, suffix "
                           "bucket) — radix-hit suffix prefill",
}

#: matmul weights that the int8 option quantizes (per-output-channel);
#: embeddings and the RMSNorm scales stay in the load dtype
_QUANT_KEYS = ("q", "k", "v", "o", "gate", "up", "down")
_LAYER_KEYS = ("ln_in", "q", "k", "v", "o", "ln_post", "gate", "up",
               "down")


def _quantize_mat(m):
    """Per-output-channel symmetric int8: rows of the (out, in) weight
    each get scale = max|row| / 127."""
    import jax.numpy as jnp

    m32 = m.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(m32), axis=1, keepdims=True)
                        / 127.0, 1e-8)
    q8 = jnp.clip(jnp.round(m32 / scale), -127, 127).astype(jnp.int8)
    return {"q8": q8, "scale": scale.astype(jnp.float32)}


def _quantize_tree(w):
    layers = []
    for L in w["layers"]:
        layers.append({k: _quantize_mat(L[k]) if k in _QUANT_KEYS
                       else L[k] for k in _LAYER_KEYS})
    return dict(layers=layers, emb=w["emb"], norm=w["norm"],
                head=_quantize_mat(w["head"]))


def _dequantize_tree(w):
    """Inverse of ``_quantize_tree`` inside the jit: int8 → f32 rows ×
    scales at trace time, so XLA sees ordinary dense matmuls (and on
    int8-capable MXUs can fuse the dequant into the gemm)."""
    def dq(leaf):
        if isinstance(leaf, dict):
            return leaf["q8"].astype(leaf["scale"].dtype) * leaf["scale"]
        return leaf

    layers = []
    for L in w["layers"]:
        layers.append({k: dq(L[k]) for k in _LAYER_KEYS})
    return dict(layers=layers, emb=w["emb"], norm=w["norm"],
                head=dq(w["head"]))


def _named_weight_items(w):
    """(rule-matchable name, getter/setter path) for every leaf of the
    decoder weight tree — the serving-side analog of Gluon's dotted
    parameter paths, so ``SERVING_RULES``/``LLAMA_RULES`` patterns match
    unchanged (``layers.0.q_weight`` hits the column-parallel rule the
    same way ``...self_attn.q_proj.weight`` does at training time)."""
    items = []
    for i, L in enumerate(w["layers"]):
        for key in L:
            items.append((f"layers.{i}.{key}_weight", ("layers", i, key)))
    items.append(("embed_weight", ("emb",)))
    items.append(("norm_weight", ("norm",)))
    items.append(("lm_head_weight", ("head",)))
    return items


_NO_RULE = ("a mesh-placed engine (mesh=) has no partition rule for a "
            "per-slot state or an expert bank")
_DENSE_ONLY = ("int8=True quantizes the dense decoder's matrices only; a "
               "model with per-slot state or routed experts is served in "
               "its load dtype")

#: what an option needs of a cache that a kind of cache does not give:
#: (the spec's trait, the option) -> why it is refused, by name, so
#: nothing falls back.  The traits are :func:`refuse`'s to read off a
#: spec: ``"loop"`` (a stack run several times a token, ``passes`` above
#: 1), ``"latent"`` (layers that keep latent rows and select what a
#: query reads), ``"kv_select"`` (K/V layers that keep an index key
#: beside and select what a query reads: ``CacheSpec.kv_selecting``),
#: ``"block"`` (a block decoder, ``decoding``) and
#: ``"state"`` (per-slot state, or routed experts).  A new cache kind
#: costs rows here and nothing in the callers.
REFUSALS = {
    ("state", "spec"):
        "speculative decoding (draft_net / spec_k) over per-slot state "
        "needs the state rolled back when a draft is rejected, which "
        "this engine does not do",
    ("state", "mesh"): _NO_RULE,
    ("state", "int8"): _DENSE_ONLY,
    ("state", "radix"):
        "radix_cache=True shares a prompt prefix's K/V blocks; a model "
        "with per-slot state also needs a snapshot of the state at the "
        "prefix boundary, which nothing keeps",
    ("block", "spec"):
        "speculative decoding (draft_net / spec_k) verifies a "
        "left-to-right draft; a block decoder commits the positions of a "
        "block in any order",
    ("block", "mesh"): _NO_RULE,
    ("block", "int8"): _DENSE_ONLY,
    ("block", "radix"):
        "radix_cache=True shares a prompt prefix's K/V blocks behind a "
        "causal suffix; a block decoder's prompt ends inside a block "
        "that the decode lane opens, and its prefill has no suffix path",
    # not an option: what a block decoder cannot have beside it
    ("block", "state"):
        "a block decoder's pass is made again for a slot that was left "
        "out of the booking (parked, or carried a pass past its "
        "request's end: the tick runs a pass ahead), which a per-slot "
        "state layer's step, not idempotent, does not allow",
    ("latent", "spec"):
        "speculative decoding (draft_net / spec_k) verifies a window of "
        "columns a slot; the latent step selects and attends one new "
        "token a slot",
    ("latent", "mesh"):
        "a mesh-placed engine (mesh=) has no partition rule for a latent "
        "pool, an index-key pool or an expert bank",
    ("latent", "int8"): _DENSE_ONLY,
    ("latent", "radix"):
        "radix_cache=True reuses K/V blocks behind a causal suffix; the "
        "suffix prefill has no view over latent blocks and their index "
        "keys",
    ("kv_select", "spec"):
        "speculative decoding (draft_net / spec_k) verifies a window of "
        "columns a slot; a selecting K/V layer's step selects and "
        "attends for one new token a slot",
    ("kv_select", "mesh"):
        "a mesh-placed engine (mesh=) has no partition rule for an "
        "index-key pool beside K and V, for pools that keep a token's "
        "KV heads in one row, or for an expert bank",
    ("kv_select", "int8"): _DENSE_ONLY,
    ("kv_select", "radix"):
        "radix_cache=True reuses K/V blocks behind a causal suffix; the "
        "suffix prefill has no view that selects over the prefix's index "
        "keys, and a suffix row would read every shared row",
    ("loop", "spec"):
        "speculative decoding (draft_net / spec_k) has not been carried "
        "through the loop over passes: a stack run several times a token "
        "decodes one token a slot a step",
    ("loop", "mesh"):
        "a mesh-placed engine (mesh=) has no partition rule for a pool "
        "that holds a layer's blocks once a pass",
    ("loop", "int8"):
        "int8=True quantizes the dense decoder's weight tree; a stack run "
        "several times a token is served in its load dtype",
    ("loop", "radix"):
        "radix_cache=True prefills a suffix behind shared prefix blocks; "
        "the suffix prefill has no loop over the passes of a stack run "
        "several times a token",
}


def refuse(spec, *, spec_k=0, mesh=None, int8=False, radix=False):
    """Raise :data:`REFUSALS`' sentence for the first option asked for
    that ``spec``'s cache does not give (the engine asks for what it is
    given, the replica for ``radix``); a spec of K/V layers alone,
    decoded the next token a step, is refused nothing."""
    trait = "loop" if spec.passes > 1 else \
        "latent" if spec.latent_layers else \
        "kv_select" if spec.kv_selecting else \
        "block" if spec.decoding is not None else \
        "state" if spec.state_layers or spec.expert_layers else None
    if trait is None:
        return
    for option, asked in (("spec", spec_k), ("mesh", mesh is not None),
                          ("int8", int8), ("radix", radix)):
        if asked:
            raise MXNetError(REFUSALS[trait, option])
    if trait == "block" and spec.state_layers:
        raise MXNetError(REFUSALS["block", "state"])


class BlockTick(NamedTuple):
    """What one pass of a block-decoding engine did, slot by slot
    (``LlamaServingEngine.step`` of such an engine), every slot but
    ``live`` reading as nothing done."""

    #: (S,) the slots the pass is booked for: those it was made for,
    #: but for a slot the host has written since the pass was queued
    #: (its request ended with the pass before, which had not been
    #: fetched then: the row was computed and is booked for nobody)
    live: np.ndarray
    pos0: np.ndarray     #: (S,) each block's first position
    ids: np.ndarray      #: (S, B) what the blocks hold after the pass
    commit: np.ndarray   #: (S, B) the positions this pass decided
    step: np.ndarray     #: (S,) which denoising pass of its block it was
    #: (S,) the slots whose block held no mask: their pass left the
    #: block's keys and values, their cursor moved on, and the block
    #: took ``step + 1`` passes in all
    stored: np.ndarray


class StepHandle:
    """One step on the device's queue, from
    :meth:`LlamaServingEngine.dispatch_step` to
    :meth:`LlamaServingEngine.fetch_step`: its tokens on the device and
    everything the host knew as it was dispatched, so that whoever books
    it reads the step's own and not the engine's newest."""

    __slots__ = ("seq", "active", "toks", "selected", "behind", "ahead",
                 "t_lock", "t_disp0", "t_disp1", "t_tok", "c_disp1",
                 "c_tok", "pos",
                 "kv_tokens", "selection", "experts", "writes")

    def __init__(self, seq, active, toks, t_lock, t_disp0, t_disp1,
                 behind=(), ahead=False, selected=None, pos=None,
                 kv_tokens=0, selection=None, writes=None, c_disp1=None):
        self.seq = seq            #: ``engine.steps`` as it was dispatched
        self.active = active      #: the slots it advances
        self.toks = toks          #: what the host fetches, on the device
        #: a selecting model: what each layer read, (layers, S, k), on
        #: the device (:meth:`LlamaServingEngine.selection_of`)
        self.selected = selected
        #: the prefill batches that were queued before it and not yet
        #: fetched as its dispatch returned
        self.behind = behind
        #: the step before it had not been fetched as it was dispatched
        self.ahead = ahead
        #: before dev_lock, lock held, the jitted call returned and the
        #: lock released; ``t_tok``: its tokens on the host (fetch_step)
        self.t_lock, self.t_disp0, self.t_disp1 = t_lock, t_disp0, t_disp1
        self.t_tok = None
        #: the two ends of the wait for its tokens on the CPU clock of the
        #: thread that passed them (``tracing.clocks``): ``c_tok`` from
        #: fetch_step; ``c_disp1`` from the lane, whose turn goes on
        #: after the dispatch returns (``verify`` takes its own)
        self.c_disp1, self.c_tok = c_disp1, None
        #: (S,) the cursors after it (a block decoder's move as it is
        #: booked: None), the K/V rows it attended (``pos + 1`` over the
        #: active slots) and a selecting model's ``kv_visible`` /
        #: ``kv_selected``
        self.pos = pos
        self.kv_tokens = kv_tokens
        self.selection = selection or {}
        #: ``experts_touched`` / ``expert_rows_max`` / ``expert_rows_mean``
        #: of a model that routes, fetched behind the tokens
        self.experts = {}
        #: a block decoder's pass: the engine's count of the host's
        #: writes to each slot's row as it was queued (``_book_block``)
        self.writes = writes


class LlamaServingEngine:
    """Device-side half of continuous batching for any model that
    answers ``serving_decoder(max_len)`` with a decoder holding the
    paged programs (step, prefill rows) and a ``cache_spec()`` —
    ``LlamaForCausalLM`` (every layer a K/V pool),
    ``Lfm2MoeForCausalLM`` (K/V pools beside per-slot states, routed
    experts) and ``SdarMoeForCausalLM`` (a block decoder: its
    ``cache_spec().decoding`` says so) today."""

    def __init__(self, net, max_len=None, num_slots=4, int8=False,
                 block_size=16, num_blocks=None, mesh=None,
                 partition_rules=None, replica_id=0, spec_k=0):
        import jax
        import jax.numpy as jnp

        self.spec_k = int(spec_k)
        self.max_len = int(max_len or net.config.max_seq_len)
        self.num_slots = int(num_slots)
        self.int8 = bool(int8)
        self.mesh = mesh
        self.partition_rules = partition_rules
        self.replica_id = int(replica_id)
        self.dev_lock = threading.RLock()
        dec = net.serving_decoder(self.max_len)
        self._dec = dec
        #: the model's answer: which layers keep K/V, which a state
        spec = self.cache_spec = dec.cache_spec()
        #: how the model decodes: None (the next token a slot a step),
        #: or the decoder's ``BlockDecoding``
        block = self.block = spec.decoding
        self.decoding = "next_token" if block is None else "block_diffusion"
        refuse(spec, spec_k=self.spec_k, mesh=mesh, int8=self.int8)
        w = dec._weights()
        self._w = _quantize_tree(w) if self.int8 else w
        deq = _dequantize_tree if self.int8 else (lambda t: t)
        cfg = net.config
        dt = w["emb"].dtype
        #: bytes of one cached value (K, V and a state array that names no
        #: dtype of its own share the weights' load dtype): what the
        #: manager prices blocks and states with
        self.cache_itemsize = int(np.dtype(dt).itemsize)
        #: where the weights (and so the cache) live: with the mesh and
        #: the shapes, what the attention kernels are chosen from
        platform = next(iter(w["emb"].devices())).platform
        self.block_size = int(block_size)
        if self.block_size < 1:
            raise MXNetError("block_size must be >= 1")
        #: static block-table width — the step gathers this many
        #: blocks per slot regardless of actual ownership
        self.max_blocks = -(-self.max_len // self.block_size)
        self.num_blocks = int(num_blocks or
                              self.num_slots * self.max_blocks)
        from ..ops import latent_cache, paged_attention, sparse_select

        # decided once, before the pool is made, from where the
        # weights (and so the pool) live, the mesh and the shapes:
        # the kernel reads whole 128-lane rows, so under it heads of
        # 64 are stored ``kv_pack`` = 2 to a row, (blocks, Hkv // 2,
        # bs, 128); the gather path keeps one head a row.  A selecting
        # K/V layer's step reads the rows its indexer names and never
        # walks a slot's blocks: no paged kernel, and every KV head of
        # a token in ONE stored row, so that a selected position is one
        # read a pool (``paged_attention.selected_rows``)
        pack = spec.num_kv_heads if spec.kv_selecting else \
            paged_attention.applicable(
                platform, mesh, spec.head_dim, spec.num_kv_heads,
                self.block_size, dt) if spec.kv_layers else 0
        paged_kernel = pack > 0 and not spec.kv_selecting
        self.kv_pack = pack = max(1, pack)
        # a stack run several times keeps a pass's blocks behind the
        # pass before's, in one pool a layer
        pshape = paged_attention.pool_shape(
            spec.passes * self.num_blocks, spec.num_kv_heads,
            spec.head_dim, self.block_size, pack)
        lshapes = latent_cache.pool_shapes(
            self.num_blocks, self.block_size, spec.latent_dim,
            spec.index_dim)
        # one entry a layer, by the spec: a (K, V) pool pair (with the
        # layer's index-key pool third where the K/V layers select), a
        # (latent rows, index keys) pool pair, or the arrays of the
        # layer's per-slot state, each of its own dtype
        ishape = sparse_select.index_pool_shape(
            self.num_blocks, self.block_size, spec.index_dim)
        self._pool = [
            (jnp.zeros(pshape, dt), jnp.zeros(pshape, dt))
            + ((jnp.zeros(ishape, dt),) if spec.kv_selecting else ())
            if kind == "kv" else
            tuple(jnp.zeros(shape, dt) for shape in lshapes)
            if kind == "latent" else
            spec.state_entry(
                jnp.zeros((self.num_slots,) + shape, sdt or dt)
                for shape, sdt in spec.state_arrays)
            for kind in spec.layers]
        if len(self._pool) != len(w["layers"]):
            raise MXNetError(
                f"the decoder's cache_spec() states {len(self._pool)} "
                f"layers and its weights hold {len(w['layers'])}")
        self._tables = np.full((self.num_slots, self.max_blocks),
                               self.num_blocks, np.int32)
        with self.dev_lock:
            # uncontended at construction; taken so the placement
            # writes to _w/_pool share the KV mutators' guard
            self._place_on_mesh_locked()
        # host mirrors: last emitted token + next write position per slot
        self._last = np.zeros(self.num_slots, np.int32)
        self._pos = np.zeros(self.num_slots, np.int32)
        if block is not None:
            # a block decoder's mirrors: ``_pos`` is the block's first
            # position; what the block holds, which of its positions are
            # undecided and which denoising pass comes next
            bl = block.block_len
            self._blk_ids = np.zeros((self.num_slots, bl), np.int32)
            self._blk_masked = np.zeros((self.num_slots, bl), bool)
            self._blk_step = np.zeros(self.num_slots, np.int32)
            #: how often the host has written each slot's row (an
            #: admission's commit, ``clear_slot``): a pass whose slot
            #: was written after it was queued is booked for nobody
            self._writes = np.zeros(self.num_slots, np.int64)
            #: passes that stored blocks took, blocks stored, tokens
            #: committed: over every tick (``server.stats()``)
            self.block_totals = {"block_passes": 0, "blocks_committed": 0,
                                 "committed_tokens": 0}
        #: which slots' ``_last`` (a block decoder: row of the block
        #: mirrors) the host wrote (a prefill's commit, ``set_mirror``,
        #: ``clear_slot``) since a step last advanced them, and which
        #: the last step left out (its vacant row's output stands where
        #: their token did): a slot's next input is what the step before
        #: produced, still on the device, and the host's where this says
        #: so (every slot to start with)
        self._fresh = np.ones(self.num_slots, bool)
        self.steps = 0
        #: the :class:`StepHandle` of the last step()/verify() whose
        #: tokens reached the host: its stamps, ``behind``, ``kv_tokens``
        #: and ``experts`` are the lane log's ``decode.tick`` record.
        #: Written and read by the decode thread alone.
        self.booked = None
        #: bytes of per-slot state a slot's layers read and write a
        #: step, by the spec (the record's ``state_bytes`` is this times
        #: the active slots)
        self.state_bytes_per_step = 2 * spec.state_bytes_per_slot(
            self.cache_itemsize)
        #: bytes a token keeps in the block tables over every layer and
        #: pass, by the spec (a record's ``kv_bytes`` is this times its
        #: tokens)
        self.kv_bytes_per_token = spec.kv_bytes_per_block(
            self.block_size, self.cache_itemsize) // self.block_size
        #: What each lane has on the device's queue, for the other to
        #: see: the ``seq`` of the newest step()/verify() whose dispatch
        #: has returned and whose tokens are not yet on the host (the
        #: decode lane keeps a step queued behind the one it books, so
        #: there may be an older one too), and the ``seq``s of the
        #: prefill batches in the same state (the
        #: prefill lane writes it between its forward's return and its
        #: fetch's).  Each lane says so as its dispatch returns and
        #: then reads the other's: programs run in the order they were
        #: queued, so what it reads was queued first.  Plain attributes,
        #: written by one thread and read by the other, no lock.
        self.step_in_flight = None
        self.prefill_in_flight = ()
        #: the ``mxt.*`` names of step()'s dispatch and fetch spans; a
        #: replica names its draft engine's apart (``mxt.draft.*``)
        self.span_names = ("mxt.decode.dispatch", "mxt.decode.fetch")
        self._signatures = set()

        # decode-step logit stats behind the same gate as the training
        # tiers — baked at engine construction, so the jitted step keeps
        # one signature per numerics mode (rebuild the engine to toggle)
        self._numerics = _numerics.trace_enabled()
        numerics_on = self._numerics
        #: which attention the step and verify programs were built
        #: with: "paged_kernel" (ops/paged_attention.py reads the pool
        #: in place), "gather" (a dense per-slot view through the
        #: table), or, of a model whose layers select what a query
        #: reads, "latent_sparse" / "kv_sparse" (the selected latent
        #: rows, or K/V rows, alone); ``kv_pack`` beside it says how
        #: many KV heads a stored row holds (above 1 under the kernel,
        #: and every KV head of a token where K/V layers select)
        self.decode_attention = "latent_sparse" if spec.latent_layers \
            else "kv_sparse" if spec.kv_selecting \
            else "paged_kernel" if paged_kernel else "gather"
        #: which form the step's linear-attention layers take
        #: (``ops.gated_delta.step_form``): "step_kernel" (the state
        #: read and written once, in place) or "step_xla"; None for a
        #: model without such layers
        self.linear_attention = dec.linear_attention()
        self._platform = platform
        #: which attention the prefill programs run, decided a bucket
        #: (``prefill_attention_at``): "flash" where the engine's
        #: longest 128-aligned prompt goes through
        #: ``ops.flash_attention.prefill_flash_attention``, "dense"
        #: where every bucket keeps ``masked_attention``
        self.prefill_attention = self.prefill_attention_at(
            self.max_len // 128 * 128)
        #: which form the routed expert layers of the step program take
        #: (``models.moe.routed_ffn`` chooses from the platform, the
        #: mesh and the rows a call): "grouped_kernel"
        #: (ops/grouped_ffn.py) or "every_expert"; None for a model
        #: without experts.  A prefill bucket decides for itself
        #: (``expert_product_at``)
        self.expert_product = self.expert_product_at(
            self.num_slots * (1 if block is None else block.block_len))
        #: per-expert row counts that ride behind the tokens of every
        #: step and prefill fetch (0: the model routes nothing)
        self._n_counts = spec.expert_layers * spec.num_experts
        #: (first, count): the part of each routed layer's bank that
        #: the model holds (its config's to say); None: all of it
        self.experts_held = getattr(cfg, "experts_held", None)
        #: ``experts_touched`` / ``expert_rows_max`` summed over every
        #: step and prefill (``server.stats()``)
        self.expert_totals = {"programs": 0, "rows": 0,
                              "experts_touched": 0, "expert_rows_max": 0}

        def _carried(ids, prev):
            # a slot's input is the token the step before produced for
            # it, read where that step left it on the device (``prev``,
            # that step's whole output: its tokens first); the host's id
            # where it wrote the slot's mirror since, -1 elsewhere
            return jnp.where(ids >= 0, ids, prev[:ids.shape[0]])

        def _behind(first, out):
            # a model with routed experts returns their row counts
            # third: they go out in the same array as what the host
            # fetches, behind it
            first = first.reshape(-1)
            if self._n_counts:
                first = jnp.concatenate(
                    [first, out[2].reshape(-1).astype(jnp.int32)])
            return first

        def _tokens(logits, out):
            return _behind(jnp.argmax(logits, axis=-1).astype(jnp.int32),
                           out)

        def _step_fn(wq, pools, tables, ids, prev, pos):
            out = dec._step_blocks_impl(
                deq(wq), pools, tables, _carried(ids, prev), pos,
                paged_kernel=paged_kernel)
            logits, pools = out[:2]
            tok = _tokens(logits, out)
            # a selecting model's step says, last, what it read
            picked = out[-1:] if spec.select_topk else ()
            if numerics_on:
                return (tok, pools, _numerics.stats_of(logits)) + picked
            return (tok, pools) + picked

        def _prefill_fn(wq, ids, t0):
            out = dec._prefill_rows_impl(
                deq(wq), ids, t0, flash=self._prefill_flash(ids.shape[1]))
            rows, logits = out[:2]
            return _tokens(logits, out), rows

        if block is not None:
            from ..models.decoder import block_advance, block_commit

            def _step_fn(wq, pools, tables, carried, host):
                # one pass over every slot's block: (S, B) ids in,
                # the block's K/V written in place, the commit rule
                # on the device; out go the ids after the pass and
                # what it committed.  A block without masks commits
                # nothing: its pass is the one whose K/V stays.
                # What a block holds, its masks, its pass count and
                # its cursor are the pass before's own (``carried``,
                # its last output); the host's row (``host``: the
                # same four, then ``fresh``, then ``stepped``) where
                # it wrote the slot since.  The pass books itself:
                # out goes, last, what the next one reads
                bl = block.block_len
                rows = (host[:, :bl], host[:, bl:2 * bl],
                        host[:, 2 * bl], host[:, 2 * bl + 1])
                fresh, stepped = (host[:, 2 * bl + 2] != 0,
                                  host[:, 2 * bl + 3] != 0)
                state = tuple(
                    jnp.where(fresh[:, None] if c.ndim > 1 else fresh,
                              h.astype(c.dtype), c)
                    for h, c in zip(rows, carried))
                ids, masked, nstep, pos0 = state
                out = dec._verify_blocks_impl(
                    deq(wq), pools, tables, ids, pos0,
                    paged_kernel=paged_kernel)
                logits, pools = out[:2]
                ids, commit = block_commit(logits, ids, masked, nstep,
                                           block)
                tok = _behind(jnp.concatenate(
                    [ids, commit.astype(jnp.int32)], axis=1), out)
                nxt = block_advance(jnp, state, ids, commit, stepped,
                                    block)
                if numerics_on:
                    return tok, pools, _numerics.stats_of(logits), nxt
                return tok, pools, nxt

            def _prefill_fn(wq, ids, t0):
                # the prompt's whole blocks only, and no token: what
                # comes back first is the opening block, the rest
                # of the prompt and then mask ids (the last row's
                # logits are returned to nobody: dead to the compiler)
                bl = block.block_len
                whole = t0 // bl * bl
                out = dec._prefill_rows_impl(
                    deq(wq), ids, whole,
                    flash=self._prefill_flash(ids.shape[1]))
                at = whole[:, None] \
                    + jnp.arange(bl, dtype=jnp.int32)[None]
                opening = jnp.where(
                    at < t0[:, None],
                    jnp.take_along_axis(
                        ids, jnp.minimum(at, ids.shape[1] - 1), axis=1),
                    jnp.int32(block.mask_id))
                return _behind(opening, out), out[0]

        def _verify_fn(wq, pools, tables, toks, pos0):
            logits, pools = dec._verify_blocks_impl(
                deq(wq), pools, tables, toks, pos0,
                paged_kernel=paged_kernel)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if numerics_on:
                return tok, pools, _numerics.stats_of(logits)
            return tok, pools

        def _gather_fn(pools, rows_idx):
            # rows_idx (KB, NBP) int32 physical block ids in logical
            # order, sentinel-padded — dense per-row prefix K/V
            # copies (KB, Hkv, NBP*bs, hd) for the suffix prefill
            return [tuple(paged_attention.gather_rows(p, rows_idx, pack)
                          for p in pair) for pair in pools]

        def _prefill_sfx_fn(wq, pre_kv, ids, t0, s0):
            rows, logits = dec._prefill_suffix_impl(
                deq(wq), pre_kv, ids, t0, s0)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), \
                rows

        def _scatter_fn(pools, rows, flat_idx, slots=None):
            # rows[l]: (KB, Hkv, Lp, hd) raw prefill K/V, written
            # block by block at flat_idx (the prefill→decode KV
            # handoff, ``paged_attention.scatter_rows``).  A state
            # layer's rows, (KB,) + shape for each of its arrays,
            # replace the WHOLE state of ``slots`` (vacant rows:
            # slot id num_slots, dropped), so a reused slot never
            # sees its predecessor's; a latent layer's rows (KB, Lp,
            # width) go block by block as its format stores them
            # (of a stack run several times: (passes, KB, Hkv, Lp,
            # hd), pass t's into that pass's blocks of the pool)
            # (a selecting K/V layer's third pool and third rows: its
            # index keys (KB, Lp, width), by the index-key format)
            kv = paged_attention.scatter_pass_rows if spec.passes > 1 \
                else paged_attention.scatter_rows
            by_block = {"kv": (kv, kv, sparse_select.scatter_rows),
                        "latent": (latent_cache.scatter_rows,) * 2}
            return [
                tuple(put(p, r, flat_idx)
                      for put, p, r in zip(by_block[kind], entry, row))
                if kind in by_block
                else jax.tree_util.tree_map(
                    lambda e, r: e.at[slots].set(r, mode="drop"),
                    entry, row)
                for kind, entry, row in zip(spec.layers, pools, rows)]

        self._step = jax.jit(_step_fn, donate_argnums=(1,))
        #: the last token-at-a-time step's output, on the device: the
        #: next one's ``prev`` (zeros before the first, which takes every
        #: id from the host; committed to the weights' device as a step's
        #: output is, so the second step is the first's compiled program)
        self._toks = None

        def born(zeros):
            return self._dev(zeros, zeros.dtype) if mesh is not None else \
                jax.device_put(zeros, next(iter(w["emb"].devices())))

        if block is None:
            self._toks = born(np.zeros(self.num_slots + self._n_counts,
                                       np.int32))
        else:
            #: the last pass's own booking, on the device: what the
            #: blocks hold, their masks, pass counts and cursors as the
            #: next pass reads them (``block_advance``; the mirrors'
            #: shapes, and like them zeros before the first pass, which
            #: takes every row from the host)
            self._blk_dev = tuple(born(np.zeros_like(m)) for m in (
                self._blk_ids, self._blk_masked, self._blk_step, self._pos))
        self._prefill = jax.jit(_prefill_fn)
        self._scatter = jax.jit(_scatter_fn, donate_argnums=(0,))
        self._verify = jax.jit(_verify_fn, donate_argnums=(1,))
        self._gather = jax.jit(_gather_fn)
        self._prefill_sfx = jax.jit(_prefill_sfx_fn)

    # -- mesh placement -------------------------------------------------------
    def _place_on_mesh_locked(self):
        """Commit weights + KV storage to ``self.mesh`` per the serving
        rule table: every leaf gets an explicit NamedSharding (sharded
        or replicated), so jit infers the device assignment from its
        inputs and the compiles are mesh-keyed.  int8 leaves shard the
        q8 rows like the original weight; the per-row scales follow the
        output dim.  Caller holds ``dev_lock``."""
        if self.mesh is None:
            return
        import jax

        from ..parallel import _named_sharding, _pspec
        from ..parallel.partition import as_rules

        rules = as_rules(self.partition_rules
                         if self.partition_rules is not None
                         else "llama_serving")
        mesh = self.mesh
        self._replicated = _named_sharding(mesh, _pspec())

        def put(leaf, spec):
            return jax.device_put(leaf, _named_sharding(mesh,
                                                        _pspec(*spec)))

        def leaf_shape(leaf):
            return leaf["q8"].shape if isinstance(leaf, dict) \
                else leaf.shape

        items = _named_weight_items(self._w)
        shapes = {}
        tree = {"layers": [dict(L) for L in self._w["layers"]],
                "emb": self._w["emb"], "norm": self._w["norm"],
                "head": self._w["head"]}
        for name, path in items:
            leaf = tree["layers"][path[1]][path[2]] if len(path) == 3 \
                else tree[path[0]]
            shapes[name] = leaf_shape(leaf)
        for i, (kb, _) in enumerate(self._pool):
            shapes[f"layers.{i}.kv_pool"] = kb.shape
        specs = rules.specs(shapes, mesh)
        for name, path in items:
            spec = specs.get(name, ())
            if len(path) == 3:
                leaf = tree["layers"][path[1]][path[2]]
            else:
                leaf = tree[path[0]]
            if isinstance(leaf, dict):
                placed = {"q8": put(leaf["q8"], spec),
                          "scale": put(leaf["scale"],
                                       ((spec[0] if spec else None),
                                        None))}
            else:
                placed = put(leaf, spec)
            if len(path) == 3:
                tree["layers"][path[1]][path[2]] = placed
            else:
                tree[path[0]] = placed
        self._w = tree
        placed_kv = []
        for i, (kb, vb) in enumerate(self._pool):
            spec = specs.get(f"layers.{i}.kv_pool", ())
            placed_kv.append((put(kb, spec), put(vb, spec)))
        self._pool = placed_kv

    def _dev(self, a, dtype=np.int32):
        """Host array → device, committed to the engine's mesh when
        sharded (replicas may live entirely off the default device)."""
        import jax
        import jax.numpy as jnp

        if self.mesh is None:
            return jnp.asarray(a, dtype)
        return jax.device_put(np.asarray(a, dtype), self._replicated)

    # -- observability --------------------------------------------------------
    def _note(self, key):
        if key not in self._signatures:
            self._signatures.add(key)
            telemetry.count("serving.engine_compile")
            if _retrace._enabled:
                # registered compile site, one per program (prefill keys
                # per bucket; a post-warmup unwarmed bucket is a retrace)
                if len(key) == 4:
                    comps = {"batch": key[1], "prefix_len": key[2],
                             "suffix_len": key[3]}
                elif len(key) == 3:
                    comps = {"batch": key[1], "prompt_len": key[2]}
                else:
                    comps = {"program": key[0]}
                _retrace.observe(
                    "serving_" + str(key[0]), id(self), comps,
                    site="mxnet_tpu.serving.generative:"
                         "LlamaServingEngine (%s)" % (key[0],))

    def _prefill_flash(self, lp):
        """The rule at a bucket of ``lp`` positions, from what the
        engine observes: the static its prefill program is traced with
        (a latent layer takes no notice: its view is its decoder's)."""
        return flash_attention.prefill_applicable(
            self._platform, self.mesh, self.cache_spec.head_dim, lp)

    def prefill_attention_at(self, lp):
        """``"flash"`` or ``"dense"``: which attention the prefill
        program of a bucket ``lp`` positions long runs;
        ``"latent_sparse"`` / ``"kv_sparse"`` where the layers select
        what they read."""
        if self.cache_spec.select_topk:
            return self.decode_attention
        return "flash" if self._prefill_flash(lp) else "dense"

    def selection_counts(self, seen, whole=False):
        """``kv_visible`` / ``kv_selected`` of a selecting model, a
        layer: over rows that each see ``seen`` positions (a step's
        active slots) or, ``whole``, over every row of prompts ``seen``
        tokens long, the positions visible and the positions read (at
        most ``select_topk`` a row); and ``index_key_bytes`` /
        ``selected_kv_bytes``, the bytes those are over every selecting
        layer; {} for a model that reads all it sees."""
        k = self.cache_spec.select_topk
        if not k:
            return {}
        n = np.asarray(seen, np.int64)
        if whole:
            visible = n * (n + 1) // 2
            m = np.minimum(n, k)
            read = m * (m + 1) // 2 + (n - m) * k
        else:
            visible, read = n, np.minimum(n, k)
        # what the scoring and the attention had to read over the
        # selecting layers: a logical index key a visible position, and
        # what a selected position keeps (its K and V rows, or its
        # latent row), without the stored rows' padding
        spec, size = self.cache_spec, self.cache_itemsize
        layers = spec.latent_layers or spec.kv_layers
        row = spec.latent_dim if spec.latent_layers \
            else 2 * spec.num_kv_heads * spec.head_dim
        return {"kv_visible": int(visible.sum()),
                "kv_selected": int(read.sum()),
                "index_key_bytes":
                    int(visible.sum()) * layers * spec.index_dim * size,
                "selected_kv_bytes": int(read.sum()) * layers * row * size}

    def selection_of(self, slot, step):
        """What each layer of ``step`` (its :class:`StepHandle`) read for
        ``slot``: (layers, k) positions, -1 where the slot saw fewer;
        None for a model that selects nothing.  One small fetch, for a
        request's end."""
        if step.selected is None:
            return None
        with self.dev_lock:
            return np.asarray(step.selected[:, slot])

    def expert_product_at(self, rows):
        """``"grouped_kernel"``, ``"every_expert"`` or None: the form
        of the routed expert layers in a program of ``rows`` rows."""
        return self._dec.expert_product(rows, self._w["emb"].dtype)

    def compiled_signatures(self):
        """Every (program, *bucket) shape this engine has compiled."""
        return sorted(self._signatures)

    def kv_pool_bytes(self, by_kind=False):
        """PER-DEVICE bytes of the cache storage: K and V over the layers that own them, plus the per-slot states
        of the layers that keep one — the figure the memory planner's
        ``plan_kv_pool`` predicts pre-build.  ``by_kind`` splits it:
        ``{"kv_blocks": ..., "slot_state": ...}`` and, where a layer
        keeps them, ``"latent_blocks"`` and ``"index_key_blocks"`` (as
        stored, padding counted; a selecting K/V layer's index keys
        beside its ``"kv_blocks"``) and, where a state layer owns several
        arrays, ``"slot_state_arrays"``: the bytes of each over the
        layers, in the spec's order.  On a tp mesh each
        device holds one shard of the pool's head axis, so this is the
        single-shard footprint, not the global array size."""
        def shard_bytes(a):
            shards = getattr(a, "addressable_shards", None)
            if shards:
                return shards[0].data.nbytes
            return a.nbytes

        with self.dev_lock:
            kv, kinds = self._pool, self.cache_spec.layers
            blocks = sum(shard_bytes(e[0]) + shard_bytes(e[1])
                         for k, e in zip(kinds, kv) if k == "kv")
            arrays = [sum(shard_bytes(self.cache_spec.entry_arrays(e)[i])
                          for k, e in zip(kinds, kv) if k == "state")
                      for i in range(len(self.cache_spec.state_arrays))]
            state = sum(arrays)
            latent, keys = (sum(shard_bytes(e[i]) for k, e in zip(kinds, kv)
                                if k == "latent") for i in (0, 1))
            if self.cache_spec.kv_selecting:
                keys = sum(shard_bytes(e[2]) for k, e in zip(kinds, kv)
                           if k == "kv")
        if by_kind:
            out = {"kv_blocks": int(blocks), "slot_state": int(state)}
            if len(arrays) > 1:
                out["slot_state_arrays"] = tuple(int(a) for a in arrays)
            if self.cache_spec.latent_layers:
                out["latent_blocks"] = int(latent)
            if self.cache_spec.select_topk:
                out["index_key_blocks"] = int(keys)
            return out
        return int(blocks + state + latent + keys)

    def split_fetch(self, fetched, n):
        """A step's or prefill's fetched vector -> (the tokens of its
        ``n`` rows, the lane-log fields of the expert row counts behind
        them; {} for a model that routes nothing).  Counts enter the
        totals.  A block decoder's rows are ``(n, ...)``: a prefill's
        opening blocks, a pass's ids beside what it committed."""
        head = len(fetched) - self._n_counts
        toks = fetched[:head]
        if self.block is not None:
            toks = toks.reshape(n, -1)
        if not self._n_counts:
            return toks, {}
        spec = self.cache_spec
        counts = np.asarray(fetched[head:]).reshape(spec.expert_layers,
                                                    spec.num_experts)
        touched = counts > 0
        fields = {
            "experts_touched": int(touched.sum()),
            "expert_rows_max": int(counts.max()),
            "expert_rows_mean": float(counts.sum() / max(1, touched.sum())),
        }
        first, held = self.experts_held or (0, spec.num_experts)
        if held < spec.num_experts:
            # a bank that holds a part of the router's experts: the
            # touched ones among those it holds, whose weights are here
            fields["experts_touched_held"] = int(
                touched[:, first:first + held].sum())
        tot = self.expert_totals
        tot["programs"] += 1
        tot["rows"] += int(counts.sum())
        tot["experts_touched"] += fields["experts_touched"]
        tot["expert_rows_max"] = max(tot["expert_rows_max"],
                                     fields["expert_rows_max"])
        return toks, fields

    # -- transitions (the prefill lane's) -------------------------------------
    def prefill_rows(self, prompts_pad, t0s):
        """Prefill lane, phase 1: the heavy prompt forward.  Runs
        WITHOUT the device lock — decode steps interleave freely while
        a long prompt prefills.  Returns (first-token device array,
        per-layer raw K/V rows) for :meth:`commit_rows`."""
        kb, lp = prompts_pad.shape
        self._note(("prefill", kb, lp))
        return self._prefill(self._w, self._dev(prompts_pad),
                             self._dev(t0s))

    def commit_rows(self, rows, slots, block_lists, t0s, first,
                    skip_blocks=None):
        """Prefill lane, phase 2: the KV handoff.  Under the device
        lock (briefly — one scatter dispatch), write the prefilled rows
        into each admitted request's blocks and install the block
        tables + decode mirrors, after which the decode lane's next
        step adopts the slots.  ``first`` is the already-materialized
        first-token vector (kb,); vacant rows carry slot id
        ``num_slots`` and sentinel blocks.

        ``skip_blocks`` (r19 radix path): per-row count of leading
        SHARED prefix blocks already holding K/V — ``rows`` then only
        carry the novel suffix, the scatter targets the block list past
        the shared prefix, and ``t0s`` stays the FULL prompt length
        (the decode cursor).  Shared blocks are never written.

        Returns ``(t_lock, t_commit1)``: ``perf_counter`` before asking
        for the device lock and after releasing it (the lane log's
        ``prefill.batch`` record)."""
        import jax.numpy as jnp

        kb = len(slots)
        lp = next(r for kind, r in zip(self.cache_spec.layers, rows)
                  if kind != "state")[0].shape[-2]
        nbp = -(-lp // self.block_size)
        flat = np.full(kb * nbp, self.num_blocks, np.int32)
        for r, blocks in enumerate(block_lists):
            if blocks is None:
                continue
            skip = 0 if skip_blocks is None else int(skip_blocks[r])
            tail = blocks[skip:]
            take = min(nbp, len(tail))
            flat[r * nbp: r * nbp + take] = tail[:take]
        t_lock = time.perf_counter()
        with self.dev_lock:
            # state layers are written by slot, K/V layers by block
            by_slot = (self._dev(slots),) \
                if self.cache_spec.state_layers else ()
            self._pool = self._scatter(self._pool, rows, self._dev(flat),
                                       *by_slot)
            for i, s in enumerate(slots):
                if s < self.num_slots:
                    row = np.full(self.max_blocks, self.num_blocks,
                                  np.int32)
                    blocks = block_lists[i]
                    row[:len(blocks)] = blocks
                    self._tables[s] = row
                    self._fresh[s] = True
                    if self.block is None:
                        self._last[s] = first[i]
                        self._pos[s] = t0s[i]
                    else:
                        # the cursor stands at the prompt's last whole
                        # block; ``first[i]`` is the block it opens
                        bl = self.block.block_len
                        self._pos[s] = t0s[i] // bl * bl
                        self._blk_ids[s] = first[i]
                        self._blk_masked[s] = np.arange(bl) >= t0s[i] % bl
                        self._blk_step[s] = 0
                        self._writes[s] += 1
        return t_lock, time.perf_counter()

    def gather_prefix(self, rows_idx):
        """Radix-hit prefill, phase 0: dense per-request copies of the
        shared prefix blocks' K/V, ``rows_idx`` (kb, nbp) physical ids
        sentinel-padded.  Dispatch runs UNDER the device lock — the
        decode step donates the pool buffer, so an unlocked read could
        alias a donated buffer mid-step; the returned copies are fresh
        arrays, safe to consume outside the lock."""
        kb, nbp = rows_idx.shape
        self._note(("gather", kb, nbp * self.block_size))
        with self.dev_lock:
            return self._gather(self._pool, self._dev(rows_idx))

    def prefill_suffix(self, prefix_kv, prompts_pad, t0s, s0s):
        """Radix-hit prefill, phase 1: the novel-suffix forward against
        the gathered prefix K/V.  Like :meth:`prefill_rows` this runs
        WITHOUT the device lock (``prefix_kv`` is a private copy).
        ``prompts_pad`` (kb, ls) carries only suffix tokens, ``t0s``
        their true suffix lengths, ``s0s`` each row's reused prefix
        length (block-aligned; 0 = no hit).  Returns (first-token
        device array, suffix K/V rows) for
        :meth:`commit_rows(..., skip_blocks=)`."""
        kb, ls = prompts_pad.shape
        lpre = prefix_kv[0][0].shape[2]
        self._note(("prefill_sfx", kb, lpre, ls))
        return self._prefill_sfx(self._w, prefix_kv,
                                 self._dev(prompts_pad),
                                 self._dev(t0s), self._dev(s0s))

    # -- transitions (the decode lane's) --------------------------------------
    def _step_queued(self, seq):
        """Step ``seq`` is on the device's queue: say so to the prefill
        lane (until its tokens are fetched) -> (whether the step before
        it is still unfetched, the prefill batches queued before it and
        not yet fetched, which it runs behind)."""
        ahead = self.step_in_flight is not None
        self.step_in_flight = seq
        return ahead, self.prefill_in_flight

    def _step_fetched(self, seq):
        if self.step_in_flight == seq:    # no newer step is queued
            self.step_in_flight = None

    def drop_steps(self):
        """The decode lane gives up the steps it has queued and not
        fetched (a turn of it raised, and it releases every slot they
        advanced): none is in flight for the prefill lane to see, and
        no slot's next input is what they left on the device."""
        self.step_in_flight = None
        with self.dev_lock:
            self._fresh[:] = True

    def step(self, active):
        """One decode step over ALL slots; returns the (num_slots,)
        next-token vector on host and advances the ``active`` slots'
        mirrors (a block decoder: one pass over every slot's block,
        and the :class:`BlockTick` of :meth:`_book_block`): its two
        halves back to back.  The device lock covers dispatch and mirror
        updates, NOT the host materialization wait — handoff scatters
        interleave with the wait."""
        return self.fetch_step(self.dispatch_step(active))

    def dispatch_step(self, active):
        """The half of :meth:`step` that does not wait: upload the host's
        mirrors, queue the step program and move the ``active`` slots'
        cursors on -> the :class:`StepHandle` that :meth:`fetch_step`
        takes.  Every other slot runs as a vacant one, its table row the
        sentinel (its K/V write drops, a state layer leaves its
        state as it is, the experts it is routed to do not count it) and
        its output read by nobody: so a slot committed and not yet adopted, or finished
        by a step whose tokens are not yet booked, is never stepped.

        A slot's input token is the one the step before produced for
        it, read on the device; the host's ``_last`` where it wrote it
        since that slot was last stepped, or where the step before left
        the slot out (``_fresh``: a slot parked for want of a block
        comes back with the token the host booked for it, whose step
        has been fetched by then: the lane is at most one step ahead).
        So the next step
        can be queued before this one's tokens have reached the host.
        A block decoder's pass is carried the same way: what each block
        holds, its masks, its pass count and its cursor are the pass
        before's own booking, on the device, and the host's row of the
        mirrors under the same ``_fresh``.  Its table rows go up whole
        (a slot held and left out is computed and booked for nobody, its
        writes idempotent), and so a pass may carry a slot whose request
        the pass before it ended, which nobody knew as it was queued:
        :meth:`_book_block` books that row for nobody.
        The device runs programs in the order they were queued, and both
        lanes queue what touches the pool under ``dev_lock`` on the
        array the last such program returned: a commit's scatter into
        blocks that a finished slot gave back runs behind every step
        that was queued while the slot still held them."""
        self._note(("step",))
        lstats = selected = pos = writes = None
        kv_tokens, selection = 0, {}
        act = np.asarray(active, np.intp)
        t_lock = time.perf_counter()
        with self.dev_lock:
            t_disp0 = time.perf_counter()
            with TraceAnnotation(self.span_names[0], seq=self.steps + 1,
                                 replica=self.replica_id):
                # (tokens, storage[, logit stats under numerics])
                if self.block is not None:
                    out = self._step(self._w, self._pool,
                                     *self._block_args(act))
                    self._pool, self._blk_dev = out[1], out[-1]
                    self._fresh[:] = True
                    self._fresh[act] = False
                    writes = self._writes.copy()
                else:
                    ids = self._dev(np.where(self._fresh, self._last,
                                             np.int32(-1)))
                    # a copy goes up: the mirror moves on below, and an
                    # upload may read the host's array after this returns
                    at = self._dev(self._pos.copy())
                    mine = np.zeros(self.num_slots, bool)
                    mine[act] = True
                    tables = np.where(mine[:, None], self._tables,
                                      np.int32(self.num_blocks))
                    out = self._step(
                        self._w, self._pool, self._dev(tables), ids,
                        self._toks, at)
                    self._pool = out[1]
                    if self.cache_spec.select_topk:
                        selected = out[-1]
                    self._toks = out[0]
                    self._fresh[:] = True
                    self._fresh[act] = False
                    self._pos[act] += 1
                    # the step attends pos + 1 rows: the cursors as they
                    # are now
                    pos = self._pos.copy()
                    kv_tokens = int(pos[act].sum())
                    selection = self.selection_counts(pos[act])
                if self._numerics:
                    lstats = out[2]
            self.steps += 1
            seq = self.steps
        ahead, behind = self._step_queued(seq)
        t_disp1 = time.perf_counter()
        if lstats is not None:
            # queue the decode-step logit stats (device scalars) for
            # the stride harvest, outside the device lock
            _numerics.record_compiled(("serving.logits",), (lstats,))
        return StepHandle(seq, act, out[0], t_lock, t_disp0, t_disp1,
                          behind=behind, ahead=ahead, selected=selected,
                          pos=pos, kv_tokens=kv_tokens, selection=selection,
                          writes=writes)

    def _block_args(self, act):
        """What a block pass over the slots ``act`` takes behind the
        weights and the pool: the tables (a copy: a grant may write the
        mirror while the upload reads), the pass before's own booking,
        and one ``(S, 2B + 4)`` array of the host's: each slot's row of
        the four mirrors, whether the pass takes it (``_fresh``) and
        whether the pass is made for the slot."""
        bl = self.block.block_len
        host = np.zeros((self.num_slots, 2 * bl + 4), np.int32)
        host[act, 2 * bl + 3] = 1
        with self.dev_lock:       # re-entrant: the dispatch holds it
            host[:, :bl] = self._blk_ids
            host[:, bl:2 * bl] = self._blk_masked
            host[:, 2 * bl] = self._blk_step
            host[:, 2 * bl + 1] = self._pos
            host[:, 2 * bl + 2] = self._fresh
            return (self._dev(self._tables.copy()), self._blk_dev,
                    self._dev(host))

    def fetch_step(self, step):
        """The half of :meth:`step` that waits: ``step``'s tokens to the
        host, its ``t_tok`` and ``experts`` filled in, the ``_last``
        mirror of its slots booked -> the (num_slots,) vector (a block
        decoder: the :class:`BlockTick`)."""
        try:
            with TraceAnnotation(self.span_names[1], seq=step.seq,
                                 replica=self.replica_id):
                out = _materialize([step.toks])[0]
        finally:
            self._step_fetched(step.seq)
        step.t_tok, step.c_tok = tracing.clocks()
        out, step.experts = self.split_fetch(out, self.num_slots)
        self.booked = step
        if self.block is not None:
            return self._book_block(out, step)
        with self.dev_lock:
            self._last[step.active] = out[step.active]
        return out

    def _book_block(self, out, step):
        """A block pass's fetched ``(S, 2B)`` (ids, then what was
        committed) into the mirrors of ``step``'s slots -> the
        :class:`BlockTick`: the lines the pass ran on the device for
        the pass behind it (``block_advance``), a pass late, for the
        lane's bookkeeping.  A slot the host has written since the pass
        was queued (released, or admitted into again) is left out: the
        mirrors there are another request's, or nobody's."""
        from ..models.decoder import block_advance

        bl = self.block.block_len
        with self.dev_lock:
            live = np.zeros(self.num_slots, bool)
            live[step.active] = True
            live &= step.writes == self._writes
            tick = BlockTick(live, self._pos.copy(), out[:, :bl],
                             out[:, bl:].astype(bool) & live[:, None],
                             self._blk_step.copy(),
                             live & ~self._blk_masked.any(axis=1))
            (self._blk_ids, self._blk_masked, self._blk_step,
             self._pos) = block_advance(
                np, (self._blk_ids, self._blk_masked, tick.step, tick.pos0),
                tick.ids, tick.commit, live, self.block)
            # every column of a block attends to the block's end
            step.kv_tokens = int(tick.pos0[live].sum()) + bl * int(live.sum())
            tot = self.block_totals
            tot["block_passes"] += int((tick.step[tick.stored] + 1).sum())
            tot["blocks_committed"] += int(tick.stored.sum())
            tot["committed_tokens"] += int(tick.commit.sum())
        return tick

    def verify(self, drafts):
        """Speculative decode: ONE multi-position target forward over
        the window ``[last_committed, draft_1..draft_k]`` per slot.
        ``drafts`` is (num_slots, k) int32 (vacant rows are ignored —
        their writes drop at the sentinel).  Returns the (num_slots,
        k+1) greedy verdict matrix on host: column j is the target's
        next token after consuming the window's first j+1 tokens.

        Unlike :meth:`step` the mirrors are NOT advanced here — the
        decode lane computes each slot's accepted length, rolls the
        manager back via ``truncate``, and commits the mirrors with
        :meth:`set_mirror`.  The window's K/V lands in the pool
        optimistically; rejected columns stay beyond the rolled-back
        cursor (masked) until the next window overwrites them."""
        self._note(("verify",))
        lstats = None
        t_lock = time.perf_counter()
        with self.dev_lock:
            t_disp0 = time.perf_counter()
            with TraceAnnotation("mxt.decode.dispatch",
                                 seq=self.steps + 1,
                                 replica=self.replica_id):
                toks_mat = np.concatenate(
                    [self._last[:, None], np.asarray(drafts, np.int32)],
                    axis=1)
                res = self._verify(
                    self._w, self._pool, self._dev(self._tables),
                    self._dev(toks_mat), self._dev(self._pos))
                out, self._pool = res[:2]
                if self._numerics:
                    lstats = res[2]
            self.steps += 1
            seq = self.steps
        ahead, behind = self._step_queued(seq)
        t_disp1, c_disp1 = tracing.clocks()
        step = StepHandle(seq, None, out, t_lock, t_disp0, t_disp1,
                          behind=behind, ahead=ahead, c_disp1=c_disp1)
        try:
            if lstats is not None:
                _numerics.record_compiled(("serving.logits",), (lstats,))
            with TraceAnnotation("mxt.decode.fetch", seq=seq,
                                 replica=self.replica_id):
                out = _materialize([out])[0]
        finally:
            self._step_fetched(seq)
        step.t_tok, step.c_tok = tracing.clocks()
        self.booked = step
        return out

    def last_tokens(self):
        """Snapshot of the per-slot last-committed-token mirror."""
        with self.dev_lock:
            return self._last.copy()

    def positions(self):
        """Snapshot of the per-slot committed write cursors."""
        with self.dev_lock:
            return self._pos.copy()

    def set_mirror(self, slot, last, pos):
        """Commit a slot's decode mirror (speculative acceptance, or
        aligning a draft engine's cursor with the target's)."""
        with self.dev_lock:
            self._last[slot] = int(last)
            self._fresh[slot] = True
            self._pos[slot] = int(pos)

    def set_blocks(self, slot, at, blocks=()):
        """``slot``'s row of the block tables from index ``at`` on:
        ``blocks``, then the sentinel.  The manager's grant behind a
        cursor appends (``PagedKVCacheManager.grant_step``); a rollback
        that gave blocks back passes none."""
        with self.dev_lock:
            row = self._tables[slot]
            row[at:] = self.num_blocks
            row[at:at + len(blocks)] = blocks

    def clear_slot(self, slot):
        with self.dev_lock:
            self._last[slot] = 0
            self._fresh[slot] = True
            self._pos[slot] = 0
            self._tables[slot] = self.num_blocks
            if self.block is not None:
                self._blk_ids[slot] = 0
                self._blk_masked[slot] = False
                self._blk_step[slot] = 0
                self._writes[slot] += 1
