"""Llama model family (stretch config 5 in BASELINE.md).

Reference: NONE — the reference predates Llama (SURVEY §5 long-context:
ABSENT).  This is new capability, built the way the reference's GluonNLP
zoo would have shipped it: config-driven Gluon HybridBlocks, so a stock
``gluon.Trainer`` trains it and ``hybridize()`` compiles one XLA program.

TPU-first design:
- attention runs the Pallas flash kernel (ops/flash_attention.py) when on
  TPU — O(T·D) HBM traffic; ring/Ulysses sequence parallelism plugs in via
  ``attn_mode`` for long context (parallel/ring.py over the ICI mesh);
- GQA: KV heads repeated at compute time (bf16-friendly, keeps the KV
  projection narrow the way Llama-3 does);
- RoPE is precomputed per (T, D) and baked into the trace as constants;
- weights are all ``use_bias=False`` Dense layers → pure MXU matmuls, and
  ``shard_llama`` annotates tp/dp shardings for pjit (megatron-style
  column/row split pairs).
"""
from __future__ import annotations

import math

import numpy as np

from .. import autograd
from ..base import MXNetError
from ..gluon.block import HybridBlock
from ..gluon import nn
from ..ops.flash_attention import prefill_applicable
from ..telemetry import numerics as _numerics
from .decoder import (CacheSpec, DenseCache, PagedDecoder, rms_norm,
                      split_heads)
from .decoder import apply_rope as _apply_rope
from .decoder import rope_tables as _rope_tables

__all__ = ["LlamaConfig", "RMSNorm", "LlamaAttention", "LlamaMLP",
           "LlamaDecoderLayer", "LlamaModel", "LlamaForCausalLM",
           "LlamaDecoder", "llama3_8b", "llama_tiny", "mixtral_8x7b",
           "mixtral_tiny", "shard_llama", "llama_param_pspecs",
           "llama_pipeline_forward", "llama_pipeline_train_step",
           "packed_lm_loss", "LLAMA_CONFIGS"]


class LlamaConfig:
    def __init__(self, hidden_size=4096, intermediate_size=14336,
                 num_layers=32, num_heads=32, num_kv_heads=8,
                 vocab_size=128256, max_seq_len=8192, rope_theta=500000.0,
                 rms_eps=1e-5, tie_embeddings=False, attn_mode="flash",
                 num_experts=0, num_experts_per_tok=2,
                 capacity_factor=1.25, moe_router="topk",
                 scan_layers=False):
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.vocab_size = vocab_size
        self.max_seq_len = max_seq_len
        self.rope_theta = rope_theta
        self.rms_eps = rms_eps
        self.tie_embeddings = tie_embeddings
        self.attn_mode = attn_mode  # flash | sdpa | ring | ulysses
        # MoE (Mixtral-style): 0 experts = dense SwiGLU MLP
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.capacity_factor = capacity_factor
        # topk | expert_choice — see models/moe.py: expert_choice leaks
        # future-token info in causal decoders; topk for production LM
        self.moe_router = moe_router
        # scan_layers: trace/compile ONE decoder layer and lax.scan it
        # over a stacked parameter tree (the production TPU idiom —
        # layer-count-independent compile time, per-layer buffers
        # allocated once, per-iteration remat).  Cost: one recorded
        # restack of the layer parameters per step (an extra HBM pass
        # over the weights); leave False when squeezing the last GiB on
        # a single chip.  r4 scale-proof finding, tools/scale_proof.py.
        self.scan_layers = scan_layers
        if hidden_size % num_heads:
            raise MXNetError("num_heads must evenly divide hidden_size")
        if num_heads % num_kv_heads:
            raise MXNetError("num_kv_heads must evenly divide num_heads")
        self.head_dim = hidden_size // num_heads

#: reviewed signature budget (mxlint T15): the scanned-layer machinery
#: compiles one stacked-layer program per (model config, batch avals,
#: remat policy) — layer homogeneity is the point of the scan, so the
#: per-layer axis contributes no signatures
__compile_signatures__ = {
    "llama_scan": "1 per (model config, batch avals, remat policy)",
}

LLAMA_CONFIGS = {
    "llama3_8b": dict(hidden_size=4096, intermediate_size=14336,
                      num_layers=32, num_heads=32, num_kv_heads=8,
                      vocab_size=128256, rope_theta=500000.0),
    "llama_tiny": dict(hidden_size=64, intermediate_size=176,
                       num_layers=2, num_heads=4, num_kv_heads=2,
                       vocab_size=256, max_seq_len=128),
    # Mixtral-8x7B architecture (sparse MoE decoder, top-2 of 8 experts)
    "mixtral_8x7b": dict(hidden_size=4096, intermediate_size=14336,
                         num_layers=32, num_heads=32, num_kv_heads=8,
                         vocab_size=32000, rope_theta=1e6,
                         num_experts=8, num_experts_per_tok=2),
    "mixtral_tiny": dict(hidden_size=64, intermediate_size=176,
                         num_layers=2, num_heads=4, num_kv_heads=2,
                         vocab_size=256, max_seq_len=128,
                         num_experts=4, num_experts_per_tok=2),
}


class RMSNorm(HybridBlock):
    """Root-mean-square LayerNorm (no mean subtraction, no bias); stats in
    fp32 even under bf16 params."""

    def __init__(self, units, eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        self._eps = eps
        with self.name_scope():
            self.weight = self.params.get("weight", shape=(units,),
                                          init="ones")

    def hybrid_forward(self, F, x, weight):
        from ..ops.registry import apply_op
        import jax.numpy as jnp

        def _f(xr, wr):
            xf = xr.astype(jnp.float32)
            var = (xf * xf).mean(axis=-1, keepdims=True)
            out = xf / jnp.sqrt(var + self._eps)
            return (out * wr.astype(jnp.float32)).astype(xr.dtype)

        return apply_op(_f, x, weight, name="rms_norm")


class LlamaAttention(HybridBlock):
    """GQA self-attention with RoPE + flash kernel."""

    def __init__(self, cfg: LlamaConfig, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        hd = cfg.head_dim
        with self.name_scope():
            self.q_proj = nn.Dense(cfg.num_heads * hd, use_bias=False,
                                   flatten=False, in_units=cfg.hidden_size,
                                   prefix="q_")
            self.k_proj = nn.Dense(cfg.num_kv_heads * hd, use_bias=False,
                                   flatten=False, in_units=cfg.hidden_size,
                                   prefix="k_")
            self.v_proj = nn.Dense(cfg.num_kv_heads * hd, use_bias=False,
                                   flatten=False, in_units=cfg.hidden_size,
                                   prefix="v_")
            self.o_proj = nn.Dense(cfg.hidden_size, use_bias=False,
                                   flatten=False,
                                   in_units=cfg.num_heads * hd, prefix="o_")
        self._rope_cache = {}

    def _rope(self, t):
        # cache the NUMPY tables, never device arrays: jnp.asarray
        # under an active trace stages a constant owned by THAT trace,
        # and caching it leaks a stale tracer into the next retrace
        # (e.g. when the scan machinery rebuilds for a new remat tier)
        if t not in self._rope_cache:
            self._rope_cache[t] = _rope_tables(t, self._cfg.head_dim,
                                               self._cfg.rope_theta)
        import jax.numpy as jnp

        cos, sin = self._rope_cache[t]
        return jnp.asarray(cos), jnp.asarray(sin)

    def hybrid_forward(self, F, x, segment_ids=None, **params):
        from ..ops.registry import apply_op

        cfg = self._cfg
        b, t = x.shape[0], x.shape[1]
        q = self.q_proj(x)
        k = self.k_proj(x)
        v = self.v_proj(x)
        cos, sin = self._rope(t)

        def _heads(qr, kr, vr):
            import jax.numpy as jnp

            hd = cfg.head_dim
            qh = qr.reshape(b, t, cfg.num_heads, hd).transpose(0, 2, 1, 3)
            kh = kr.reshape(b, t, cfg.num_kv_heads, hd) \
                .transpose(0, 2, 1, 3)
            vh = vr.reshape(b, t, cfg.num_kv_heads, hd) \
                .transpose(0, 2, 1, 3)
            qh = _apply_rope(qh, cos[None, None], sin[None, None])
            kh = _apply_rope(kh, cos[None, None], sin[None, None])
            rep = cfg.num_heads // cfg.num_kv_heads
            if rep > 1:
                kh = jnp.repeat(kh, rep, axis=1)
                vh = jnp.repeat(vh, rep, axis=1)
            return qh, kh, vh

        def _attend(qr, kr, vr):
            qh, kh, vh = _heads(qr, kr, vr)
            hd = cfg.head_dim
            if cfg.attn_mode in ("ring", "ulysses"):
                from ..parallel import ring as _ring

                fn = (_ring.ring_attention_raw
                      if cfg.attn_mode == "ring"
                      else _ring.ulysses_attention_raw)
                out = fn(qh, kh, vh, causal=True,
                         scale=1.0 / math.sqrt(hd))
            elif cfg.attn_mode == "flash":
                from ..ops.flash_attention import flash_attention_raw

                out = flash_attention_raw(qh, kh, vh, True,
                                          1.0 / math.sqrt(hd))
            else:
                from ..ops.flash_attention import _sdpa_ref

                out = _sdpa_ref(qh, kh, vh, True, 1.0 / math.sqrt(hd))
            return out.transpose(0, 2, 1, 3).reshape(b, t, -1)

        def _attend_packed(qr, kr, vr, segr):
            # packed-batch path: causal AND same-segment, the serving
            # slots' mask shape (ops.attention.masked_attention) applied to
            # training.  Flash/ring modes have no segment support, so
            # packing always takes the dense masked sdpa.
            qh, kh, vh = _heads(qr, kr, vr)
            out = _sdpa_segmented(qh, kh, vh, segr,
                                  1.0 / math.sqrt(cfg.head_dim))
            return out.transpose(0, 2, 1, 3).reshape(b, t, -1)

        if segment_ids is not None:
            ctx = apply_op(_attend_packed, q, k, v, segment_ids,
                           name="llama_attention_packed")
        else:
            ctx = apply_op(_attend, q, k, v, name="llama_attention")
        return self.o_proj(ctx)


def _segment_causal_mask(seg):
    """(B, T) int segment ids → (B, 1, T, T) bool attention mask:
    causal AND same-segment, the packed-batch analogue of the per-slot
    mask the serving step hands ``ops.attention.masked_attention``.  The
    diagonal is always legal (``seg[q] == seg[q]``), so no query row is
    fully masked and the dense softmax stays NaN-free even on padding
    rows (segment id 0); padding positions only see other padding and
    their loss is masked out anyway (``data.PackedBatch.loss_mask``)."""
    import jax.numpy as jnp

    seg = seg.astype(jnp.int32)
    t = seg.shape[1]
    same = seg[:, :, None] == seg[:, None, :]
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    return (same & causal[None])[:, None]


def _sdpa_segmented(q, k, v, seg, scale):
    """Dense sdpa with the segment-causal mask — f32 score accumulation
    like ``_sdpa_ref``/the serving ``masked_attention``.  q/k/v (B, H, T, D)
    post-GQA-repeat, seg (B, T) int."""
    import jax
    import jax.numpy as jnp

    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(_segment_causal_mask(seg), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


class LlamaMLP(HybridBlock):
    """SwiGLU feed-forward: down(silu(gate(x)) * up(x))."""

    def __init__(self, cfg: LlamaConfig, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.gate_proj = nn.Dense(cfg.intermediate_size, use_bias=False,
                                      flatten=False,
                                      in_units=cfg.hidden_size,
                                      prefix="gate_")
            self.up_proj = nn.Dense(cfg.intermediate_size, use_bias=False,
                                    flatten=False, in_units=cfg.hidden_size,
                                    prefix="up_")
            self.down_proj = nn.Dense(cfg.hidden_size, use_bias=False,
                                      flatten=False,
                                      in_units=cfg.intermediate_size,
                                      prefix="down_")

    def hybrid_forward(self, F, x):
        g = self.gate_proj(x)
        return self.down_proj(g * F.sigmoid(g) * self.up_proj(x))


class LlamaDecoderLayer(HybridBlock):
    def __init__(self, cfg: LlamaConfig, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.input_layernorm = RMSNorm(cfg.hidden_size, cfg.rms_eps,
                                           prefix="ln_in_")
            self.self_attn = LlamaAttention(cfg, prefix="attn_")
            self.post_attention_layernorm = RMSNorm(
                cfg.hidden_size, cfg.rms_eps, prefix="ln_post_")
            if cfg.num_experts > 0:
                from .moe import MoEMLP

                self.mlp = MoEMLP(cfg.hidden_size, cfg.intermediate_size,
                                  cfg.num_experts, cfg.num_experts_per_tok,
                                  cfg.capacity_factor, cfg.moe_router,
                                  prefix="moe_")
            else:
                self.mlp = LlamaMLP(cfg, prefix="mlp_")

    def hybrid_forward(self, F, x, segment_ids=None):
        if segment_ids is None:
            x = x + self.self_attn(self.input_layernorm(x))
        else:
            x = x + self.self_attn(self.input_layernorm(x), segment_ids)
        return x + self.mlp(self.post_attention_layernorm(x))


class LlamaModel(HybridBlock):
    def __init__(self, cfg: LlamaConfig, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        with self.name_scope():
            self.embed_tokens = nn.Embedding(cfg.vocab_size,
                                             cfg.hidden_size,
                                             prefix="embed_")
            self.layers = nn.HybridSequential(prefix="layers_")
            for _ in range(cfg.num_layers):
                self.layers.add(LlamaDecoderLayer(cfg))
            self.norm = RMSNorm(cfg.hidden_size, cfg.rms_eps,
                                prefix="norm_")

    def hybrid_forward(self, F, input_ids, segment_ids=None):
        h = self.embed_tokens(input_ids)
        _numerics.tap("embed", h)
        if self._cfg.scan_layers and len(self.layers) > 1:
            # per-layer stats exit the scan as stacked ys — taps here
            # would see scan-body tracers; see _scan_machinery
            h = _apply_layers_scanned(self, h, segment_ids)
        else:
            for i, layer in enumerate(self.layers):
                h = layer(h) if segment_ids is None \
                    else layer(h, segment_ids)
                _numerics.tap(f"decoder.{i}", h)
        h = self.norm(h)
        _numerics.tap("norm", h)
        return h


class LlamaForCausalLM(HybridBlock):
    """Decoder + LM head; training forward returns logits (B, T, V)."""

    def __init__(self, cfg: LlamaConfig, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        with self.name_scope():
            self.model = LlamaModel(cfg, prefix="model_")
            self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False,
                                    flatten=False,
                                    in_units=cfg.hidden_size,
                                    prefix="lm_head_")

    @property
    def config(self):
        return self._cfg

    def hybrid_forward(self, F, input_ids, segment_ids=None):
        """``segment_ids`` (B, T) int — packed-pretraining mode
        (``data.SequencePacker``): attention is masked to causal ∧
        same-segment so packed documents never see each other.  One
        compile signature either way: the packed batch shape is fixed
        by the packer, and segment ids ride as a second traced input,
        not as shape variation."""
        if segment_ids is None:
            h = self.model(input_ids)
        else:
            h = self.model(input_ids, segment_ids)
        out = _lm_head(self, h)
        _numerics.tap("logits", out)
        return out

    def serving_decoder(self, max_len):
        """What ``GenerativeServer``'s engine asks a model for: the
        decoder with the paged programs and the cache spec."""
        return LlamaDecoder(self, max_len)

    def set_remat(self, tier):
        """Set the decoder-stack remat tier ("none" / "dots" / "layer"
        / "auto"; see ``mxnet_tpu.memory.policy``).  "auto" asks the
        planner for the cheapest tier that fits the device budget at
        first forward.  Default is "layer" — the historical blanket
        per-decoder-layer ``jax.checkpoint``.  Rebuilds the scan
        machinery, so the next step retraces."""
        from ..memory import policy as _mem_policy

        self.model._remat = _mem_policy.normalize(tier)
        self.model._scan_mach = None
        return self

    def generate(self, input_ids, max_new_tokens=16, use_cache=True,
                 do_sample=False, temperature=1.0, top_k=0, top_p=1.0,
                 seed=None):
        """Decoding.  ``use_cache=True`` (default) runs the jitted
        incremental decode step with a static-shape KV cache
        (O(T) per token); ``use_cache=False`` re-forwards the full
        sequence per token (O(T²), kept as the greedy reference oracle).
        ``do_sample=True`` draws from the (temperature / top-k / top-p
        filtered) distribution — cached path only."""
        from .. import ndarray as nd
        from .. import autograd as ag

        # guard BOTH paths (cached and oracle/MoE): positions past
        # max_seq_len mean RoPE extrapolation outside the trained window
        need = input_ids.shape[1] + max_new_tokens
        max_ctx = getattr(self._cfg, "max_seq_len", None)
        if max_ctx is not None and need > max_ctx:
            raise MXNetError(
                f"generate: prompt ({input_ids.shape[1]}) + max_new_tokens "
                f"({max_new_tokens}) = {need} exceeds the model's "
                f"max_seq_len ({max_ctx}); RoPE tables and KV caches are "
                f"only valid inside the trained context window")
        if use_cache and self._cfg.num_experts == 0:
            return self._generate_cached(
                input_ids, max_new_tokens, do_sample=do_sample,
                temperature=temperature, top_k=top_k, top_p=top_p,
                seed=seed)
        if do_sample:
            raise MXNetError("do_sample requires the KV-cache path "
                             "(use_cache=True, dense MLP config)")
        cur = input_ids
        with ag.pause():
            for _ in range(max_new_tokens):
                logits = self(cur)
                nxt = nd.argmax(logits, axis=-1)[:, -1:]
                cur = nd.concat(cur, nxt.astype(cur.dtype), dim=1)
        return cur

    def _generate_cached(self, input_ids, max_new_tokens, **sample_kw):
        from .. import ndarray as nd

        if max_new_tokens < 1:  # n=0: prompt unchanged (oracle parity)
            return input_ids
        b, t0 = input_ids.shape
        # bucket max_len to a power of two (min 64) so repeated calls with
        # nearby lengths reuse ONE compiled decoder instead of recompiling
        need = t0 + max_new_tokens  # generate() validated need<=max_seq_len
        max_ctx = getattr(self._cfg, "max_seq_len", None)
        bucket = 64
        while bucket < need:
            bucket *= 2
        if max_ctx is not None:
            bucket = min(bucket, max_ctx)
        cache = self.__dict__.setdefault("_kv_decoders", {})
        dec = cache.get(bucket)
        if dec is None:
            dec = cache[bucket] = LlamaDecoder(self, max_len=bucket)
        ids = dec.generate(input_ids._data, max_new_tokens, **sample_kw)
        return nd.NDArray(ids).astype(input_ids.dtype)


class LlamaDecoder(PagedDecoder):
    """Jitted incremental decoder with a static-shape KV cache.

    Reference: NONE (the reference predates LLM serving).  TPU-first
    design: ``generate`` is ONE compiled XLA program — a batched
    full-sequence prefill writes the prompt's K/V into the
    (B, Hkv, max_len, D) cache, then a ``lax.scan`` greedy-decode loop
    runs entirely on device (no per-token host round trips).  Weights
    enter as jit ARGUMENTS (pulled fresh from the net's Parameters on
    every call), so generation always sees current weights and XLA does
    not bake multi-GB constants into the executable.

    One :meth:`attention` and one :meth:`layer` over a cache view
    (``models.decoder``): the paged programs the serving engine runs are
    the shared base's, and the dense-cache programs here (offline
    ``generate``) run the same layer over a
    :class:`~.decoder.DenseCache`.  The math mirrors
    ``LlamaAttention``/``LlamaMLP``; attention scores accumulate in
    float32 (``preferred_element_type``) exactly like the training
    ``_sdpa_ref`` path, and tests/test_llama.py pins cached == uncached
    logits so the paths cannot drift.  Dense MLP only: a routed expert
    layer is served by the dropless ``models.moe.routed_ffn`` (as
    ``models.lfm2`` does), not by the fixed-capacity ``MoEMLP`` this
    family trains with.
    """

    def __init__(self, net: "LlamaForCausalLM", max_len: int):
        import jax

        if net.config.num_experts:
            raise MXNetError(
                "LlamaDecoder serves dense MLP configs: num_experts > 0 "
                "builds the fixed-capacity MoEMLP, which drops tokens; "
                "a served expert layer is models.moe.routed_ffn "
                "(dropless), as models.lfm2 wires it")
        super().__init__(net, max_len)
        self._step = jax.jit(self._step_impl, donate_argnums=(1,))
        self._gen = jax.jit(self._generate_impl,
                            static_argnums=(6, 7, 8, 9, 10))

    def _weights(self):
        """Fresh raw-weight pytree from the net's Parameters (cheap: just
        handle plumbing; jit hashes it by shape/dtype, not value)."""
        net = self._net
        raw = lambda p: p.data()._data  # noqa: E731
        layers = [
            dict(ln_in=raw(lr.input_layernorm.weight),
                 q=raw(lr.self_attn.q_proj.weight),
                 k=raw(lr.self_attn.k_proj.weight),
                 v=raw(lr.self_attn.v_proj.weight),
                 o=raw(lr.self_attn.o_proj.weight),
                 ln_post=raw(lr.post_attention_layernorm.weight),
                 gate=raw(lr.mlp.gate_proj.weight),
                 up=raw(lr.mlp.up_proj.weight),
                 down=raw(lr.mlp.down_proj.weight))
            for lr in net.model.layers]
        emb = raw(net.model.embed_tokens.weight)
        head = emb if self.cfg.tie_embeddings else raw(net.lm_head.weight)
        return dict(layers=layers, emb=emb,
                    norm=raw(net.model.norm.weight), head=head)

    def cache_spec(self):
        """The serving engine's question: every layer owns a K/V pool."""
        cfg = self.cfg
        return CacheSpec(("kv",) * cfg.num_layers, cfg.num_kv_heads,
                         cfg.head_dim)

    def init_cache(self, batch):
        import jax.numpy as jnp

        cfg = self.cfg
        shape = (batch, cfg.num_kv_heads, self.max_len, cfg.head_dim)
        dt = self._net.model.embed_tokens.weight.data().dtype
        dt = np.dtype(dt)
        return [(jnp.zeros(shape, dt), jnp.zeros(shape, dt))
                for _ in range(cfg.num_layers)]

    def attention(self, p, h, rope, view):
        """GQA over a cache view: ``h`` (B, T, hidden), or a step's
        (S, hidden); ``rope`` the (cos, sin) rows of the call's
        positions, broadcastable over heads-major q and k -> (y, what
        the view kept)."""
        cfg = self.cfg
        q = split_heads(h @ p["q"].T, cfg.num_heads)
        k = split_heads(h @ p["k"].T, cfg.num_kv_heads)
        v = split_heads(h @ p["v"].T, cfg.num_kv_heads)
        q, k = _apply_rope(q, *rope), _apply_rope(k, *rope)
        ctx, kept = view.attend(q, k, v)
        return ctx.reshape(*h.shape[:-1], -1) @ p["o"].T, kept

    def layer(self, p, x, rope, view):
        """x + attn(ln(x)) then + mlp(ln(x)) -> (x, what the view kept,
        None: no expert rows)."""
        import jax

        eps = self.cfg.rms_eps
        y, kept = self.attention(p, rms_norm(x, p["ln_in"], eps), rope,
                                 view)
        x = x + y
        h2 = rms_norm(x, p["ln_post"], eps)
        g = h2 @ p["gate"].T
        return x + (g * jax.nn.sigmoid(g) * (h2 @ p["up"].T)) \
            @ p["down"].T, kept, None

    def _logits(self, w, x):
        return rms_norm(x, w["norm"], self.cfg.rms_eps) @ w["head"].T

    def _step_impl(self, w, caches, ids_t, pos):
        """One token a row against dense caches: ids_t (B,) int32, pos
        () int32 → (logits (B, V), caches)."""
        import jax.numpy as jnp

        pos = jnp.asarray(pos, jnp.int32)
        rope = (self._cos[pos][..., None, None, :],
                self._sin[pos][..., None, None, :])
        x = w["emb"][ids_t]                                     # (B, H)
        mask = DenseCache.mask_of(pos, self.max_len)
        x, caches, _ = self._layers(
            w, x, rope, (DenseCache(e, pos, mask) for e in caches))
        return self._logits(w, x), caches

    def _prefill_impl(self, w, ids, t0, flash=False):
        """Prompt pass + full-length caches: K/V rows land at [0:Lp] of
        fresh (B, Hkv, max_len, hd) caches (pad rows are overwritten by
        decode steps starting at ``t0``, and the causal mask keeps them
        invisible to real rows).  One MXU-friendly forward instead of
        T0 serialized vector steps, compiled once per padded shape."""
        import jax.numpy as jnp
        from jax import lax

        cfg = self.cfg
        b = ids.shape[0]
        rows, logits = self._prefill_rows_impl(w, ids, t0, flash)
        z = jnp.zeros((), jnp.int32)
        shape = (b, cfg.num_kv_heads, self.max_len, cfg.head_dim)
        caches = [
            (lax.dynamic_update_slice(jnp.zeros(shape, k.dtype), k,
                                      (z, z, z, z)),
             lax.dynamic_update_slice(jnp.zeros(shape, v.dtype), v,
                                      (z, z, z, z)))
            for k, v in rows]
        return caches, logits

    def logits_at(self, ids):
        """Teacher-forced per-step decode over ``ids`` (B, T) returning
        logits at every position (B, T, V) — the parity-test surface for
        the single-token step path."""
        import jax.numpy as jnp
        import numpy as np

        ids = jnp.asarray(ids, jnp.int32)
        b, t = ids.shape
        w = self._weights()
        caches = self.init_cache(b)
        outs = []
        for p in range(t):
            logits, caches = self._step(w, caches, ids[:, p], jnp.int32(p))
            outs.append(np.asarray(logits))
        return np.stack(outs, axis=1)

    def _pick(self, logits, key, temperature, top_p, top_k, do_sample,
              use_top_p):
        """Greedy or filtered sampling from last-position logits (B, V).
        ``top_k``/``do_sample``/``use_top_p`` are trace-static;
        temperature/top_p ride as traced scalars so tuning them doesn't
        recompile.  The nucleus filter (two full-vocab sorts per token)
        only compiles in when actually requested."""
        import jax
        import jax.numpy as jnp

        if not do_sample:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        lg = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
        if top_k and top_k < lg.shape[-1]:
            kth = jnp.sort(lg, axis=-1)[:, -top_k][:, None]
            lg = jnp.where(lg < kth, -jnp.inf, lg)
        if use_top_p:
            # nucleus: drop tokens whose EXCLUSIVE cumulative prob ≥
            # top_p (the top token always survives)
            srt = jnp.sort(lg, axis=-1)[:, ::-1]
            probs = jax.nn.softmax(srt, axis=-1)
            cum = jnp.cumsum(probs, axis=-1) - probs
            count = jnp.maximum((cum < top_p).sum(-1), 1)
            thresh = jnp.take_along_axis(srt, (count - 1)[:, None], axis=1)
            lg = jnp.where(lg < thresh, -jnp.inf, lg)
        return jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)

    def _generate_impl(self, w, ids, t0, key, temperature, top_p,
                       n_steps, top_k, do_sample, use_top_p, flash=False):
        """Padded ids (B, Lp) + traced true length ``t0`` → (B, n_steps)
        continuation in one XLA program: batched prefill (``flash``: see
        ``_prefill_rows_impl``), then a decode scan (first new token
        comes from the prefill logits; decode steps overwrite the pad
        K/V rows starting at ``t0``)."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        caches, logits = self._prefill_impl(w, ids, t0, flash)
        key, sub = jax.random.split(key)
        cur = self._pick(logits, sub, temperature, top_p, top_k,
                         do_sample, use_top_p)

        def decode_body(carry, _):
            caches, cur, pos, key = carry
            logits, caches = self._step_impl(w, caches, cur, pos)
            key, sub = jax.random.split(key)
            nxt = self._pick(logits, sub, temperature, top_p, top_k,
                             do_sample, use_top_p)
            return (caches, nxt, pos + 1, key), nxt

        (_, _, _, _), toks = lax.scan(
            decode_body,
            (caches, cur, jnp.asarray(t0, jnp.int32), key), None,
            length=n_steps - 1)
        return jnp.concatenate([cur[:, None], toks.T], axis=1)

    @staticmethod
    def _bucket(n, quantum=16):
        b = quantum
        while b < n:
            b *= 2
        return b

    def generate(self, ids, max_new_tokens, do_sample=False,
                 temperature=1.0, top_k=0, top_p=1.0, seed=None):
        """Decode (greedy, or sampled with ``do_sample=True``).  Prompt
        length and step count are padded to power-of-two buckets (true
        length rides in as a traced scalar), so nearby calls reuse ONE
        compiled XLA program instead of retracing per exact
        (prompt_len, max_new_tokens)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        ids = np.asarray(ids, np.int32)
        b, t0 = ids.shape
        n = int(max_new_tokens)
        if n < 1:
            raise MXNetError("max_new_tokens must be >= 1")
        if t0 + n > self.max_len:
            raise MXNetError("max_len exceeded; build a larger decoder")
        lp = min(self._bucket(t0), self.max_len)
        nb = min(self._bucket(n), self.max_len - lp)
        if nb < n:  # bucketed padding doesn't fit: run exact shapes
            lp, nb = t0, n
        ids_pad = np.zeros((b, lp), np.int32)
        ids_pad[:, :t0] = ids
        if not do_sample:
            # greedy must not touch the global RNG stream (reproducible
            # training runs interleave greedy eval generates)
            key = jax.random.PRNGKey(0)
        elif seed is None:
            from .. import random as mx_random

            key = mx_random.next_key()
        else:
            key = jax.random.PRNGKey(int(seed))
        w = self._weights()
        # the prefill's attention, from where the weights live (sharded
        # weights stand for a mesh: the dense path)
        devs = w["emb"].devices()
        flash = prefill_applicable(
            next(iter(devs)).platform, None if len(devs) == 1 else devs,
            self.cfg.head_dim, lp)
        toks = self._gen(w, jnp.asarray(ids_pad),
                         jnp.int32(t0), key,
                         jnp.float32(temperature), jnp.float32(top_p),
                         int(nb), int(top_k), bool(do_sample),
                         bool(do_sample and top_p < 1.0), flash)
        return np.concatenate([ids, np.asarray(toks)[:, :n]], axis=1)


def llama3_8b(**overrides):
    """Llama-3-8B architecture (BASELINE config 5)."""
    return LlamaForCausalLM(LlamaConfig(**{**LLAMA_CONFIGS["llama3_8b"],
                                           **overrides}))


def llama_tiny(**overrides):
    """Tiny config for tests/dryruns."""
    return LlamaForCausalLM(LlamaConfig(**{**LLAMA_CONFIGS["llama_tiny"],
                                           **overrides}))


def mixtral_8x7b(**overrides):
    """Mixtral-8x7B sparse-MoE architecture (beyond-reference model
    family: MoE + expert parallelism, SURVEY §2.3 D9)."""
    return LlamaForCausalLM(LlamaConfig(**{**LLAMA_CONFIGS["mixtral_8x7b"],
                                           **overrides}))


def mixtral_tiny(**overrides):
    """Tiny MoE config for tests/dryruns."""
    return LlamaForCausalLM(LlamaConfig(**{**LLAMA_CONFIGS["mixtral_tiny"],
                                           **overrides}))


def _lm_head(net, h):
    """Project hidden states to vocab logits for ``net`` — THE single
    definition of the head routing: tied configs reuse the embedding
    matrix ((V, H), recorded ``tied_lm_head`` op so the head gradient
    accumulates into the tied embedding), untied use the dedicated
    Dense.  Every forward path (plain, GPipe) must call this so the
    routing can't diverge (ADVICE r3: the pipelined forward once used
    the dead lm_head for tied configs); the fused 1F1B loss keeps an
    inline jnp equivalent pinned by the tied/untied grad-equality
    tests."""
    if net._cfg.tie_embeddings:
        from ..ops.registry import apply_op

        w = net.model.embed_tokens.weight.data()
        return apply_op(lambda hr, wr: hr @ wr.T, h, w,
                        name="tied_lm_head")
    return net.lm_head(h)


def packed_lm_loss(logits, labels, loss_mask):
    """Mean next-token cross-entropy over a packed batch, masked to the
    real targets (``data.PackedBatch``: padding and each document's
    last position carry ``loss_mask`` 0 — no cross-document
    prediction).  f32 log-softmax accumulation like
    ``softmax_cross_entropy``; that op sums over the whole batch, which
    can't express a per-token mask — hence this dedicated raw op.
    logits (B, T, V), labels (B, T) int, loss_mask (B, T) float."""
    from ..ops.registry import apply_op

    def f(lg, lb, m):
        import jax
        import jax.numpy as jnp

        lp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(
            lp, lb[..., None].astype(jnp.int32), axis=-1)[..., 0]
        mf = m.astype(jnp.float32)
        return -(ll * mf).sum() / jnp.maximum(mf.sum(), 1.0)

    return apply_op(f, logits, labels, loss_mask, name="packed_lm_loss")


def llama_pipeline_forward(net, input_ids, n_microbatches, mesh=None,
                           axis_name="pp"):
    """Forward the SAME ``LlamaForCausalLM`` Block over a GPipe pipeline
    (``parallel.pipeline_apply``, SURVEY §2.3 D7 — new capability).

    The decoder stack is cut into ``mesh[axis_name]`` equal stages; each
    stage applies its layers with the ORIGINAL Block code (layer 0 is the
    template whose parameter handles are swapped per layer inside the
    staged function), activations hop stage→stage over the ICI ring, and
    embedding/final-norm/LM-head run outside the pipeline, replicated.
    The per-layer parameter stacking is recorded nd ops, so
    ``backward()`` routes pipeline gradients into every layer's own
    ``Parameter.grad()`` and ``gluon.Trainer`` works unchanged —
    equivalence with the unpipelined forward (loss AND per-param grads)
    is asserted in tests/test_ring.py.

    ``input_ids``: (B, T) with ``B % n_microbatches == 0``; returns
    logits (B, T, vocab).
    """
    from .. import parallel
    from ..ndarray import NDArray
    from ..ops import tensor as tops

    mesh = mesh or parallel.current_mesh()
    if mesh is None:
        raise MXNetError("no active mesh; call parallel.set_mesh first")
    n_stages = mesh.shape[axis_name]
    batch = input_ids.shape[0]
    if batch % n_microbatches:
        raise MXNetError(
            f"batch {batch} not divisible by {n_microbatches} "
            "microbatches")

    h = net.model.embed_tokens(input_ids)  # (B, T, H)
    t_len, hidden = h.shape[1], h.shape[2]
    mbs = h.reshape((n_microbatches, batch // n_microbatches, t_len,
                     hidden))

    mach = _pipeline_machinery(net, n_stages)
    names, shells, lps = mach["names"], mach["shells"], mach["lps"]
    stacked = _stacked_layer_params(net, names, n_stages, lps)
    saved = [sh._data for sh in shells]

    try:
        out = parallel.pipeline_apply(mach["stage_fn"], stacked, mbs,
                                      mesh=mesh, axis_name=axis_name)
    finally:
        for sh, s in zip(shells, saved):
            sh._data = s
    h_out = out.reshape((batch, t_len, hidden))
    h_out = net.model.norm(h_out)
    return _lm_head(net, h_out)


def _apply_layers_scanned(model, h, segment_ids=None):
    """cfg.scan_layers: apply the decoder stack as
    ``lax.scan(checkpoint_wrap(layer, tier))`` over a stacked parameter
    tree, the tier resolved by the memory policy (default "layer").

    The layer-0 Block is the compile template (handle-swap per
    iteration, the pipeline machinery's trick), so the stack traces and
    compiles ONE layer regardless of depth, XLA allocates one layer's
    buffers instead of L copies, and each iteration rematerializes in
    the backward (r4 finding: a python layer loop cost ~1 GiB x L of
    XLA temp that scan removes by construction —
    tools/scale_proof.py).  The per-layer parameters are restacked with
    RECORDED ops every call, so gradients reach each layer's own
    Parameter and ``gluon.Trainer`` works unchanged."""
    from ..ops import tensor as tops
    from ..ops.registry import apply_op

    mach = _scan_machinery(model, _resolve_model_remat(model, h),
                           with_seg=segment_ids is not None)
    names, shells = mach["names"], mach["shells"]
    per_layer = [ly._collect_params_with_prefix()
                 for ly in model.layers]
    stacked = [tops.stack(*[lp[n].data() for lp in per_layer], axis=0)
               for n in names]
    saved = [sh._data for sh in shells]
    try:
        if segment_ids is not None:
            res = apply_op(mach["fn"], h, segment_ids, *stacked,
                           name="scan_layers_packed")
        else:
            res = apply_op(mach["fn"], h, *stacked, name="scan_layers")
        # static build-time bool out of the machinery cache (keyed on
        # the numerics mode), not a tracer
        if not mach["numerics"]:  # mxlint: allow=T2
            return res
        # unpack the stacked per-layer stat ys (unused downstream, so
        # autograd feeds them zero cotangents) and queue them for the
        # stride harvest under decoder.<i> paths
        out, l2, maxabs, mean, nan, inf = res
        _numerics.tap_stacked("decoder", {
            "l2": l2._data, "maxabs": maxabs._data, "mean": mean._data,
            "nan": nan._data, "inf": inf._data})
        return out
    finally:
        for sh, s in zip(shells, saved):
            sh._data = s


def _layer_template(layers):
    """(template layer-0 Block, sorted param names, shell handles) — the
    ONE extraction of the handle-swap machinery's raw ingredients,
    shared by the scan forward and the pipeline machinery (the 1F1B
    commit unified the GPipe/1F1B copies; this keeps scan on the same
    helper instead of growing a third)."""
    template = layers[0]
    tparams = template._collect_params_with_prefix()
    names = sorted(tparams)
    shells = [tparams[n]._data for n in names]
    return template, names, shells


def _resolve_model_remat(model, h):
    """The decoder stack's remat tier: ``set_remat()``'s choice, the
    planner's pick for "auto" (cheapest tier that fits, sized at the
    live activation shape), or the historical "layer" default."""
    from ..memory import policy as _mem_policy

    tier = _mem_policy.normalize(getattr(model, "_remat", "layer"))
    if tier != "auto":
        if tier != "none":
            _mem_policy.record_policy(tier, "forced")
        return tier
    import numpy as np

    from .. import parallel

    batch_b = int(np.prod(h.shape)) * np.dtype(h.dtype).itemsize
    tier, _plan = _mem_policy.auto_tier(
        model, mesh=parallel.current_mesh(), batch_bytes=batch_b)
    return tier


def _scan_machinery(model, remat="layer", with_seg=False):
    """Cached per-(model, remat-tier, packed?) scan plumbing
    (identity-stable like :func:`_pipeline_machinery`, so jit caches
    hit across steps; a tier change — or switching between packed and
    plain batches — rebuilds)."""
    cache = getattr(model, "_scan_mach", None)
    numerics_on = _numerics.trace_enabled()
    # remat is a host-side tier string, never a tracer
    if (cache is not None and cache["remat"] == remat  # mxlint: allow=T2
            and cache["with_seg"] == with_seg
            and cache["numerics"] == numerics_on):
        return cache
    from ..gluon.block import _trace_guard
    from ..memory.policy import checkpoint_wrap
    from ..ndarray import NDArray

    template, names, shells = _layer_template(list(model.layers))

    if with_seg:
        # packed path: segment ids are a scan-invariant second input to
        # every layer (same (B, T) array each iteration — lax.scan
        # closes over it, only the stacked params are scanned)
        def apply_one(sl, carry, segr):
            for sh, s in zip(shells, sl):
                sh._data = s
            with _trace_guard():  # inline the template (no nested jit)
                return template(NDArray(carry), NDArray(segr))._data
    else:
        def apply_one(sl, carry):
            for sh, s in zip(shells, sl):
                sh._data = s
            with _trace_guard():  # inline the template (no nested jit)
                return template(NDArray(carry))._data

    import jax

    wrapped = checkpoint_wrap(apply_one, remat)

    # numerics: per-layer output stats ride the scan as stacked ys —
    # computed inside the same compile, stacked (L,) per stat by
    # lax.scan itself, and returned flat (apply_op dispatches tuples of
    # arrays).  Taps inside the body would hand scan tracers to the
    # collector; the ys are the only legal exit.
    def _body_ys(new):
        if not numerics_on:
            return ()
        st = _numerics.stats_of(new)
        return (st["l2"], st["maxabs"], st["mean"], st["nan"], st["inf"])

    if with_seg:
        def _scan_raw(hr, segr, *stk):
            from jax import lax

            def body(carry, sl):
                new = wrapped(sl, carry, segr)
                return new, _body_ys(new)

            out, ys = lax.scan(body, hr, tuple(stk))
            return (out,) + ys if numerics_on else out
    else:
        def _scan_raw(hr, *stk):
            from jax import lax

            def body(carry, sl):
                new = wrapped(sl, carry)
                return new, _body_ys(new)

            out, ys = lax.scan(body, hr, tuple(stk))
            return (out,) + ys if numerics_on else out

    # jit the scan program: (a) eager steps run ONE compiled program
    # instead of a traced-eager loop, and (b) shard_map-based layers
    # (ring/Ulysses attention) require a jit around them — eager scan
    # evaluation of a shard_map body is NotImplemented in jax
    fn = jax.jit(_scan_raw)

    cache = {"names": names, "shells": shells, "fn": fn,
             "apply_one": apply_one, "remat": remat,
             "with_seg": with_seg, "numerics": numerics_on}
    model._scan_mach = cache
    return cache


def _pipeline_machinery(net, n_stages):
    """Cached per-(net, n_stages) pipeline plumbing: template layer,
    its parameter shells (handle-swap targets), and the stage function.
    Caching keeps ``stage_fn`` IDENTITY stable across training steps so
    :func:`parallel.pipeline_train_1f1b`'s program cache hits instead of
    re-tracing the whole schedule every call.  Shared by the GPipe
    forward and the fused 1F1B train step."""
    from ..ndarray import NDArray

    cache = getattr(net, "_pp_machinery", None)
    if cache is not None and cache["n_stages"] == n_stages:
        return cache
    layers = list(net.model.layers)
    n_layers = len(layers)
    if n_layers % n_stages:
        raise MXNetError(
            f"{n_layers} decoder layers not divisible into "
            f"{n_stages} pipeline stages")
    lps = n_layers // n_stages
    template, names, shells = _layer_template(layers)

    def stage_fn(ptree, x_raw):
        out = x_raw
        for i in range(lps):
            for sh, name in zip(shells, names):
                sh._data = ptree[name][i]
            out = template(NDArray(out))._data
        return out

    cache = {"n_stages": n_stages, "names": names, "shells": shells,
             "template": template, "lps": lps, "stage_fn": stage_fn,
             "loss_fn": None}
    net._pp_machinery = cache
    return cache


def _stacked_layer_params(net, names, n_stages, lps):
    """{name: (S, L/S, *shape)} stacks of the per-layer parameters via
    RECORDED nd ops, so gradients through the stack reach each layer's
    own Parameter.  Rebuilt every call (the values change each step);
    the trace-stable machinery lives in :func:`_pipeline_machinery`."""
    from ..ops import tensor as tops

    per_layer_params = [ly._collect_params_with_prefix()
                        for ly in net.model.layers]
    stacked = {}
    for name in names:
        flat = tops.stack(*[lp[name].data() for lp in per_layer_params],
                          axis=0)
        stacked[name] = flat.reshape(
            (n_stages, lps) + tuple(flat.shape[1:]))
    return stacked


class _FusedGradStep(autograd.Function):
    """Wire a fused train step (loss + precomputed grads, e.g. the 1F1B
    schedule) into the tape: forward runs the runner, backward returns
    the stashed gradients scaled by the incoming cotangent."""

    def __init__(self, runner):
        super().__init__()
        self._runner = runner

    def forward(self, *inputs):
        loss, grads = self._runner(*inputs)
        self._grads = grads
        return loss

    def backward(self, dloss):
        from ..ndarray import NDArray

        scale = dloss._data
        return tuple(
            None if g is None else NDArray(g._data * scale)
            for g in self._grads)


def llama_pipeline_train_step(net, input_ids, labels, n_microbatches,
                              mesh=None, axis_name="pp"):
    """Fused 1F1B pipeline train step for a ``LlamaForCausalLM``: one
    compiled program interleaves each microbatch's backward right behind
    its forward (``parallel.pipeline_train_1f1b`` — peak activation
    memory O(S) instead of GPipe's O(M)), with the final RMSNorm + LM
    head + token cross-entropy computed on the last stage and the
    embedding stack outside the schedule.  Returns the MEAN token loss
    as a recorded NDArray: ``loss.backward()`` deposits gradients into
    every parameter (decoder layers via the stacked-params path,
    embedding via the schedule's input cotangent, norm/head via tail
    grads), so ``gluon.Trainer`` works unchanged."""
    import jax
    import jax.numpy as jnp

    from .. import parallel
    from ..ndarray import NDArray

    mesh = mesh or parallel.current_mesh()
    if mesh is None:
        raise MXNetError("no active mesh; call parallel.set_mesh first")
    n_stages = mesh.shape[axis_name]
    batch = input_ids.shape[0]
    if batch % n_microbatches:
        raise MXNetError(
            f"batch {batch} not divisible by {n_microbatches} "
            "microbatches")
    cfg = net._cfg
    eps = float(cfg.rms_eps)

    h = net.model.embed_tokens(input_ids)  # recorded
    t_len, hidden = h.shape[1], h.shape[2]
    mbs = h.reshape((n_microbatches, batch // n_microbatches, t_len,
                     hidden))
    lab_mbs = labels.reshape((n_microbatches,
                              batch // n_microbatches, t_len))
    mach = _pipeline_machinery(net, n_stages)
    names, shells, lps = mach["names"], mach["shells"], mach["lps"]
    stacked = _stacked_layer_params(net, names, n_stages, lps)
    saved = [sh._data for sh in shells]
    norm_w = net.model.norm.weight.data()
    # tied models reuse the embedding matrix as the LM head (same (V, H)
    # layout as lm_head.weight) — the tape then accumulates BOTH the
    # input-cotangent and the head contributions into the embedding
    head_w = (net.model.embed_tokens.weight.data()
              if cfg.tie_embeddings else net.lm_head.weight.data())

    if mach["loss_fn"] is None:
        def loss_fn(out, lab, tail):
            nw, hw = tail
            xf = out.astype(jnp.float32)
            var = (xf * xf).mean(axis=-1, keepdims=True)
            hn = (xf * jax.lax.rsqrt(var + eps)
                  * nw.astype(jnp.float32)).astype(out.dtype)
            logits = hn @ hw.T
            ls = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(
                ls, lab.astype(jnp.int32)[..., None], axis=-1)
            return jnp.sum(nll)

        mach["loss_fn"] = loss_fn
    stack_leaves = [stacked[name] for name in names]

    def runner(mbs_nd, lab_nd, *leaf_nds):
        k = len(names)
        stack_tree = {name: leaf_nds[i]
                      for i, name in enumerate(names)}
        tail = tuple(leaf_nds[k:])
        try:
            loss, grads, tgrads, dxs = parallel.pipeline_train_1f1b(
                mach["stage_fn"], mach["loss_fn"], stack_tree, mbs_nd,
                lab_nd, tail_params=tail, mesh=mesh,
                axis_name=axis_name)
        finally:
            for sh, s_ in zip(shells, saved):
                sh._data = s_
        return loss, (dxs, None,
                      *[grads[name] for name in names],
                      *list(jax.tree_util.tree_leaves(tgrads)))

    loss_sum = _FusedGradStep(runner)(mbs, lab_mbs, *stack_leaves,
                                      norm_w, head_w)
    return loss_sum / float(batch * t_len)


def llama_param_pspecs(net, mesh, tp_axis="tp", ep_axis="ep"):
    """{param_name (structural): partition-spec tuple} for the megatron
    TP/EP layout over ``mesh`` — used by :func:`shard_llama` (placement
    of real arrays) AND by the abstract 8B lowering proof
    (ShapeDtypeStruct shardings with no memory).  Params not listed are
    replicated (spec ``()``).

    The rules themselves live in the partition engine
    (``parallel.partition.MIXTRAL_RULES`` — the llama table plus the
    MoE expert-bank rows, which match nothing on a dense net); this
    function just resolves them against the net's parameter paths and
    ``mesh``, renaming the canonical 'tp'/'ep' axes when asked."""
    from ..parallel import partition as _pt

    rename = {"tp": tp_axis, "ep": ep_axis}
    rules = _pt.PartitionRules(
        [(pat, tuple(rename.get(a, a) if isinstance(a, str) else a
                     for a in spec))
         for pat, spec in _pt.MIXTRAL_RULES])
    shapes = {name: p.shape
              for name, p in net._collect_params_with_prefix().items()}
    specs = rules.specs(shapes, mesh)
    if net._cfg.tie_embeddings:
        # the tied head reads the embedding matrix; its own (dead)
        # weight stays replicated exactly as the hand-rolled table did
        specs.pop("lm_head.weight", None)
    return specs


def shard_llama(net, mesh=None, tp_axis="tp", dp_axis="dp", ep_axis="ep"):
    """Annotate megatron-style TP shardings over ``mesh`` (pjit/GSPMD
    derives the collectives — SURVEY §2.3 D6, new capability):

    - q/k/v/gate/up: column-parallel (output dim split over tp)
    - o/down:       row-parallel (input dim split over tp)
    - embed/lm_head: vocab-parallel
    - MoE layers: expert bank sharded over ``ep`` (+tp within experts)
    Replicates everything else.  Weights are stored (out, in), so the
    output dim is axis 0.  The rules live in
    :func:`llama_param_pspecs`; this function applies them to the
    initialized arrays.
    """
    from .. import parallel

    mesh = mesh or parallel.current_mesh()
    has_tp = mesh is not None and tp_axis in mesh.shape
    has_ep = mesh is not None and ep_axis in mesh.shape
    if mesh is None or not (has_tp or has_ep):
        parallel.replicate_block_params(net)
        return net
    parallel.replicate_block_params(net)  # baseline: replicate all
    params = net._collect_params_with_prefix()
    for name, spec in llama_param_pspecs(net, mesh, tp_axis=tp_axis,
                                         ep_axis=ep_axis).items():
        parallel.shard_param(params[name], spec, mesh)
    return net
