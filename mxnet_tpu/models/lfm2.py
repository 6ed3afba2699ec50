"""LFM2-MoE family (``model_type`` ``lfm2_moe``): gated short convolutions
beside grouped-query attention, dense then sigmoid-routed expert layers.

Reference: NONE (the reference predates it).  Layer equations, with
``norm`` an RMSNorm with a learned weight and no biases anywhere:

* layer: ``h = h + operator(norm_op(h))``, ``h = h + ffn(norm_ffn(h))``;
  the operator is ``conv`` or ``full_attention`` by ``layer_types[l]``,
  the feed-forward a dense SwiGLU for ``l < num_dense_layers``, else the
  routed expert block;
* short convolution: ``[B, C, x] = split3(W_in u)``, ``z = B * x``,
  ``c_t = sum_j w[:, j] * z_{t-2+j}`` (depthwise, causal, zeros before
  the start), ``y = W_out (C * c)``;
* attention: GQA; q and k pass an RMSNorm over a head's channels (one
  learned weight shared by the heads) BEFORE RoPE; float32 softmax;
* expert block: :func:`mxnet_tpu.models.moe.routed_ffn` with sigmoid
  scores, a per-expert bias added for the choice only, weights
  renormalised over the chosen experts, no shared expert, no capacity.

One definition of the mathematics: :meth:`Lfm2Math.layer`
``(params, x, rope rows, cache view) -> (x, what the view kept, expert
rows)`` is what the Gluon blocks' ``hybrid_forward`` runs over a whole
sequence (a :class:`~.decoder.Causal` view) and what the paged programs
that :class:`Lfm2Decoder` inherits (``models.decoder.PagedDecoder``)
run against the paged cache.  A conv layer's cache is not keys and
values: it is the last ``conv_L_cache`` columns of ``z`` a slot, kept as
a ring by position (row ``t % L`` holds ``z_t``), so that a decode step
writes one row at its own position exactly as it writes one K/V row —
running a step again at the same position rewrites the same values.
"""
from __future__ import annotations

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from .decoder import (CacheSpec, Causal, PagedDecoder, StepView,
                      headnorm_attention, rms_norm, rope_tables)
from .llama import RMSNorm
from .moe import routed_ffn

__all__ = ["Lfm2MoeConfig", "Lfm2MoeLayer", "Lfm2MoeForCausalLM",
           "Lfm2Math", "Lfm2Decoder", "lfm2_moe_tiny", "LFM2_CONFIGS"]


class Lfm2MoeConfig:
    def __init__(self, hidden_size=2048, intermediate_size=11776,
                 moe_intermediate_size=1536, num_layers=40,
                 num_dense_layers=2, layer_types=None, num_heads=32,
                 num_kv_heads=8, vocab_size=65536, max_seq_len=8192,
                 rope_theta=1e6, norm_eps=1e-5, conv_L_cache=3,
                 num_experts=64, num_experts_per_tok=4,
                 norm_topk_prob=True, use_expert_bias=True,
                 routed_scaling_factor=1.0):
        if layer_types is None:
            # the published pattern: an attention layer after every
            # two, then every three, convolutions
            layer_types = ["full_attention" if l % 4 == 2 else "conv"
                           for l in range(num_layers)]
        if len(layer_types) != num_layers:
            raise MXNetError("layer_types must name every layer")
        for kind in layer_types:
            if kind not in ("conv", "full_attention"):
                raise MXNetError(f"unknown layer type {kind!r}")
        if hidden_size % num_heads or num_heads % num_kv_heads:
            raise MXNetError("heads must divide hidden_size, and "
                             "num_kv_heads the heads")
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_layers = num_layers
        self.num_dense_layers = num_dense_layers
        self.layer_types = list(layer_types)
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = hidden_size // num_heads
        self.vocab_size = vocab_size
        self.max_seq_len = max_seq_len
        self.rope_theta = rope_theta
        self.norm_eps = norm_eps
        self.conv_L_cache = conv_L_cache
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = norm_topk_prob
        self.use_expert_bias = use_expert_bias
        self.routed_scaling_factor = routed_scaling_factor
        #: the head is the embedding (the family's convention)
        self.tie_embeddings = True

    @property
    def num_expert_layers(self):
        return self.num_layers - self.num_dense_layers

    def is_conv(self, l):
        return self.layer_types[l] == "conv"

    def is_dense(self, l):
        return l < self.num_dense_layers


LFM2_CONFIGS = {
    # hidden 64, one dense layer then two periods, 8 experts top 2
    "lfm2_moe_tiny": dict(
        hidden_size=64, intermediate_size=160, moe_intermediate_size=48,
        num_layers=9, num_dense_layers=1,
        layer_types=["conv", "full_attention", "conv", "conv", "conv",
                     "full_attention", "conv", "conv", "conv"],
        num_heads=4, num_kv_heads=2, vocab_size=256, max_seq_len=128,
        num_experts=8, num_experts_per_tok=2),
}


def _layer_param_shapes(cfg, l):
    """Leaf name -> shape of layer ``l``'s parameters; matrices are
    (out, in) but the expert bank, which is stacked (experts, in, out)."""
    h, hd = cfg.hidden_size, cfg.head_dim
    out = {"op_norm": (h,), "ffn_norm": (h,)}
    if cfg.is_conv(l):
        out.update(in_proj=(3 * h, h), conv=(cfg.conv_L_cache, h),
                   out_proj=(h, h))
    else:
        out.update(q=(cfg.num_heads * hd, h), k=(cfg.num_kv_heads * hd, h),
                   v=(cfg.num_kv_heads * hd, h), o=(h, cfg.num_heads * hd),
                   q_norm=(hd,), k_norm=(hd,))
    if cfg.is_dense(l):
        f = cfg.intermediate_size
        out.update(gate=(f, h), up=(f, h), down=(h, f))
    else:
        e, i = cfg.num_experts, cfg.moe_intermediate_size
        out.update(router=(e, h), expert_bias=(e,), w_gate=(e, h, i),
                   w_up=(e, h, i), w_down=(e, i, h))
    return out


class Lfm2Math:
    """The layer mathematics, once."""

    def __init__(self, cfg):
        self.cfg = cfg

    # -- operators ------------------------------------------------------------
    def short_conv(self, p, u, view):
        """Gated short convolution.  Whole sequences (any ``view`` but a
        :class:`~.decoder.StepView`): ``u`` (B, T, H) -> (y, z) with z
        (B, T, H) the conv's input, from which prefill takes the state.
        A step: ``u`` (S, H) against the state ring -> (y, new state)."""
        import jax
        import jax.numpy as jnp

        h = self.cfg.hidden_size
        kk = self.cfg.conv_L_cache
        w = p["conv"].astype(u.dtype)                   # (L, H)
        with jax.named_scope("short_conv"):
            bcx = u @ p["in_proj"].T
            b, c, x = bcx[..., :h], bcx[..., h:2 * h], bcx[..., 2 * h:]
            z = b * x
            if not isinstance(view, StepView):
                t = z.shape[1]
                zp = jnp.pad(z, ((0, 0), (kk - 1, 0), (0, 0)))
                conv = sum(w[j] * zp[:, j:j + t] for j in range(kk))
                return (c * conv) @ p["out_proj"].T, z
            state, pos = view.entry, view.pos           # (S, L, H), (S,)
            rows = jnp.arange(state.shape[0])
            state = state.at[rows, pos % kk].set(z)
            # tap j multiplies z at position pos - (L-1) + j
            conv = sum(w[j] * state[rows, (pos - (kk - 1) + j) % kk]
                       for j in range(kk))
            return (c * conv) @ p["out_proj"].T, state

    def attention(self, p, u, rope, view):
        """GQA with per-head q/k RMSNorm before RoPE, over a cache view:
        ``u`` (B, T, H), or a step's (S, H); ``rope`` the (cos, sin) rows
        of the call's positions over heads-major q and k -> (y, what the
        view kept)."""
        cfg = self.cfg
        return headnorm_attention(p, u, rope, view, cfg.num_heads,
                                  cfg.num_kv_heads, cfg.norm_eps)

    def ffn(self, p, u, live=None):
        """Dense SwiGLU, or the routed expert block -> (y, rows each
        expert received or None).  ``live`` (shape of ``u`` without its
        last axis, bool): the rows a request owns, the only ones
        counted."""
        import jax

        cfg = self.cfg
        if "router" not in p:
            g = u @ p["gate"].T
            return (g * jax.nn.sigmoid(g) * (u @ p["up"].T)) @ p["down"].T, \
                None
        lead = u.shape[:-1]
        with jax.named_scope("moe_ffn"):
            y, counts = routed_ffn(
                u.reshape(-1, u.shape[-1]), p["router"], p["w_gate"],
                p["w_up"], p["w_down"], cfg.num_experts_per_tok,
                score="sigmoid",
                choice_bias=p["expert_bias"] if cfg.use_expert_bias
                else None,
                renormalize=cfg.norm_topk_prob,
                scale=cfg.routed_scaling_factor,
                live=None if live is None else live.reshape(-1))
        return y.reshape(*lead, -1), counts

    # -- the layer ------------------------------------------------------------
    def layer(self, p, x, rope, view):
        """``(params, x, rope rows, cache view) -> (x, what the view
        kept, expert rows)``.  A :class:`~.decoder.Causal` view: whole
        sequences, ``x`` (B, T, H), and what comes back is what a cache
        would keep of them (the conv's input ``z``, or the (k, v)
        rows).  A :class:`~.decoder.StepView`: one token a slot, ``x``
        (S, H), and the layer's updated cache entry comes back.
        ``view.live``: see :meth:`ffn`."""
        eps = self.cfg.norm_eps
        h = rms_norm(x, p["op_norm"], eps)
        if "in_proj" in p:
            y, kept = self.short_conv(p, h, view)
        else:
            y, kept = self.attention(p, h, rope, view)
        x = x + y
        y, counts = self.ffn(p, rms_norm(x, p["ffn_norm"], eps), view.live)
        return x + y, kept, counts


class Lfm2MoeLayer(HybridBlock):
    """One layer; its Parameters carry the leaf names of
    :func:`_layer_param_shapes` and its forward is
    :meth:`Lfm2Math.layer` over whole sequences."""

    def __init__(self, cfg, index, **kwargs):
        super().__init__(**kwargs)
        self._cfg, self._index = cfg, index
        self._names = sorted(_layer_param_shapes(cfg, index))
        with self.name_scope():
            for name, shape in _layer_param_shapes(cfg, index).items():
                init = "ones" if name.endswith("norm") else None
                setattr(self, name,
                        self.params.get(name, shape=shape, init=init))

    def hybrid_forward(self, F, x, **params):
        from ..ops.registry import apply_op

        names, cfg = self._names, self._cfg
        t = x.shape[1]

        def _f(xr, *raw):
            cos, sin = rope_tables(t, cfg.head_dim, cfg.rope_theta)
            return Lfm2Math(cfg).layer(
                dict(zip(names, raw)), xr,
                (cos[None, None], sin[None, None]), Causal(t))[0]

        return apply_op(_f, x, *(params[n] for n in names),
                        name="lfm2_moe_layer")


class Lfm2MoeForCausalLM(HybridBlock):
    """Embedding, the layers, a final RMSNorm, the tied head; the
    forward returns logits (B, T, V)."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        with self.name_scope():
            self.embed_tokens = nn.Embedding(cfg.vocab_size,
                                             cfg.hidden_size,
                                             prefix="embed_")
            self.layers = nn.HybridSequential(prefix="layers_")
            for l in range(cfg.num_layers):
                self.layers.add(Lfm2MoeLayer(cfg, l))
            self.norm = RMSNorm(cfg.hidden_size, cfg.norm_eps,
                                prefix="norm_")

    @property
    def config(self):
        return self._cfg

    def hybrid_forward(self, F, input_ids):
        from ..ops.registry import apply_op

        h = self.embed_tokens(input_ids)
        for layer in self.layers:
            h = layer(h)
        h = self.norm(h)
        return apply_op(lambda hr, wr: hr @ wr.T, h,
                        self.embed_tokens.weight.data(),
                        name="tied_lm_head")

    def serving_decoder(self, max_len):
        """What ``GenerativeServer``'s engine asks a model for."""
        return Lfm2Decoder(self, max_len)


class Lfm2Decoder(PagedDecoder, Lfm2Math):
    """What the shared paged programs (step, verify, prefill rows,
    prefill suffix) need of this family: the cache spec, the weights,
    :meth:`Lfm2Math.layer`, the logits and what prefill keeps of a conv
    layer's sequence."""

    def cache_spec(self):
        cfg = self.cfg
        return CacheSpec(
            layers=tuple("state" if cfg.is_conv(l) else "kv"
                         for l in range(cfg.num_layers)),
            num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            state_shape=(cfg.conv_L_cache, cfg.hidden_size),
            expert_layers=cfg.num_expert_layers,
            num_experts=cfg.num_experts)

    def _weights(self):
        net = self._net
        raw = lambda p: p.data()._data  # noqa: E731
        layers = [{n: raw(getattr(lr, n)) for n in lr._names}
                  for lr in net.layers]
        return dict(layers=layers, emb=raw(net.embed_tokens.weight),
                    norm=raw(net.norm.weight))

    def _logits(self, w, x):
        return rms_norm(x, w["norm"], self.cfg.norm_eps) @ w["emb"].T

    def _sequence_state(self, z, t0):
        """A conv layer's state of the TRUE length, from the whole
        sequence's ``z`` (B, Lp, H): ``z`` at ``t0-L .. t0-1`` laid out
        as the ring keeps it (row ``t % L``), zeros where the prompt is
        shorter, never the padded end's."""
        import jax.numpy as jnp

        kk = self.cfg.conv_L_cache
        # ring row r holds the one position p in [t0-L, t0) with p % L == r
        src = t0[:, None] - 1 - (t0[:, None] - 1 - jnp.arange(kk)[None]) % kk
        take = jnp.clip(src, 0, z.shape[1] - 1)[:, :, None]     # (B, L, 1)
        return jnp.where((src >= 0)[:, :, None],
                         jnp.take_along_axis(z, take, axis=1), 0)


def lfm2_moe_tiny(**overrides):
    kw = dict(LFM2_CONFIGS["lfm2_moe_tiny"])
    kw.update(overrides)
    return Lfm2MoeForCausalLM(Lfm2MoeConfig(**kw))
