"""SDAR-MoE family (``model_type`` ``sdar_moe``): the Qwen3-MoE layer,
generating by diffusion over blocks of positions.

Reference: NONE (the reference predates it).  Layer equations, with
``norm`` an RMSNorm with a learned weight and no biases anywhere:

* layer: ``h = h + attention(norm_attn(h))``, ``h = h + experts(norm_ffn(h))``;
  every layer is alike (no dense layer, no shared expert);
* attention: GQA; q and k pass an RMSNorm over a head's channels (one
  learned weight shared by the heads) BEFORE RoPE over the whole head;
  float32 softmax; **position p sees key t iff t < (p // B + 1) * B**:
  whole earlier blocks and its own block in both directions;
* experts: :func:`mxnet_tpu.models.moe.routed_ffn` with float32 softmax
  scores over all experts, the ``k`` highest renormalised to sum 1, no
  choice bias, no capacity;
* model: embedding, the layers, a final RMSNorm, an untied head.  Row
  ``p``'s logits are the distribution of token ``p`` ITSELF (not of the
  next one); a position not yet decided holds the mask id.

Generation (greedy; :class:`~.decoder.BlockDecoding`,
:func:`~.decoder.block_commit`): the prompt's whole blocks are prefilled
and yield no token; the rest of the prompt opens the first decoded
block beside masks.  A pass over a block commits its most confident
masked positions; a block without masks is passed once more, and that
pass's keys and values are what later blocks read.

One definition of the mathematics: :meth:`SdarMath.layer` ``(params, x,
rope rows, cache view) -> (x, what the view kept, expert rows)`` is what
the Gluon blocks' ``hybrid_forward`` runs over a whole sequence (a
:class:`~.decoder.Causal` view with the block length) and what the paged
programs that :class:`SdarDecoder` inherits run against the paged cache
(a :class:`~.decoder.StepView` whose window is one block).
"""
from __future__ import annotations

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from .decoder import (BlockDecoding, CacheSpec, Causal, PagedDecoder,
                      headnorm_attention, rms_norm, rope_tables)
from .llama import RMSNorm
from .moe import routed_ffn

__all__ = ["SdarMoeConfig", "SdarMoeLayer", "SdarMoeForCausalLM",
           "SdarMath", "SdarDecoder", "sdar_moe_tiny", "SDAR_CONFIGS"]


class SdarMoeConfig:
    def __init__(self, hidden_size=2048, moe_intermediate_size=768,
                 num_layers=48, num_heads=32, num_kv_heads=4, head_dim=128,
                 vocab_size=151936, max_seq_len=32768, rope_theta=1e6,
                 norm_eps=1e-6, num_experts=128, num_experts_per_tok=8,
                 norm_topk_prob=True, block_length=4, denoising_steps=4,
                 confidence_threshold=0.9, mask_token_id=151669):
        if num_heads % num_kv_heads:
            raise MXNetError("num_kv_heads must divide the heads")
        if not 1 <= denoising_steps <= block_length:
            raise MXNetError("denoising_steps must lie in [1, block_length]")
        if not 0 <= mask_token_id < vocab_size:
            raise MXNetError("mask_token_id must be a row of the vocabulary")
        self.hidden_size = hidden_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.vocab_size = vocab_size
        self.max_seq_len = max_seq_len
        self.rope_theta = rope_theta
        self.norm_eps = norm_eps
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = norm_topk_prob
        self.block_length = block_length
        self.denoising_steps = denoising_steps
        self.confidence_threshold = confidence_threshold
        self.mask_token_id = mask_token_id
        self.tie_embeddings = False

    @property
    def decoding(self):
        return BlockDecoding(self.block_length, self.mask_token_id,
                             self.denoising_steps,
                             self.confidence_threshold)


SDAR_CONFIGS = {
    # hidden 64, 3 layers, 4 query / 2 KV heads of 16, 16 experts top 8
    "sdar_moe_tiny": dict(
        hidden_size=64, moe_intermediate_size=32, num_layers=3, num_heads=4,
        num_kv_heads=2, head_dim=16, vocab_size=256, max_seq_len=128,
        num_experts=16, num_experts_per_tok=8, mask_token_id=255),
}


def _layer_param_shapes(cfg):
    """Leaf name -> shape of a layer's parameters; matrices are (out,
    in) but the expert bank, which is stacked (experts, in, out)."""
    h, hd = cfg.hidden_size, cfg.head_dim
    e, i = cfg.num_experts, cfg.moe_intermediate_size
    return {"attn_norm": (h,), "ffn_norm": (h,),
            "q": (cfg.num_heads * hd, h), "k": (cfg.num_kv_heads * hd, h),
            "v": (cfg.num_kv_heads * hd, h), "o": (h, cfg.num_heads * hd),
            "q_norm": (hd,), "k_norm": (hd,), "router": (e, h),
            "w_gate": (e, h, i), "w_up": (e, h, i), "w_down": (e, i, h)}


class SdarMath:
    """The layer mathematics, once."""

    def __init__(self, cfg):
        self.cfg = cfg

    def experts(self, p, u, live=None, experts_held=None):
        """The routed expert block -> (y, rows each expert received).
        ``live`` (shape of ``u`` without its last axis, bool): the rows
        a request owns, the only ones counted.  ``experts_held``
        ``(first, count)``: the part of the bank that ``p`` holds."""
        import jax

        cfg = self.cfg
        with jax.named_scope("moe_ffn"):
            y, counts = routed_ffn(
                u.reshape(-1, u.shape[-1]), p["router"], p["w_gate"],
                p["w_up"], p["w_down"], cfg.num_experts_per_tok,
                score="softmax", renormalize=cfg.norm_topk_prob,
                experts_held=experts_held,
                live=None if live is None else live.reshape(-1))
        return y.reshape(u.shape), counts

    def layer(self, p, x, rope, view):
        """``(params, x, rope rows, cache view) -> (x, what the view
        kept, expert rows)``.  A :class:`~.decoder.Causal` view: whole
        sequences, ``x`` (B, T, H), and the raw (k, v) rows come back.
        A :class:`~.decoder.StepView` over a block's window: ``x`` (S,
        B, H), and the layer's updated pools come back."""
        cfg = self.cfg
        y, kept = headnorm_attention(
            p, rms_norm(x, p["attn_norm"], cfg.norm_eps), rope, view,
            cfg.num_heads, cfg.num_kv_heads, cfg.norm_eps)
        x = x + y
        y, counts = self.experts(p, rms_norm(x, p["ffn_norm"], cfg.norm_eps),
                                 view.live)
        return x + y, kept, counts


class SdarMoeLayer(HybridBlock):
    """One layer; its Parameters carry the leaf names of
    :func:`_layer_param_shapes` and its forward is
    :meth:`SdarMath.layer` over whole sequences under the block mask."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        self._names = sorted(_layer_param_shapes(cfg))
        with self.name_scope():
            for name, shape in _layer_param_shapes(cfg).items():
                init = "ones" if name.endswith("norm") else None
                setattr(self, name,
                        self.params.get(name, shape=shape, init=init))

    def hybrid_forward(self, F, x, **params):
        from ..ops.registry import apply_op

        names, cfg = self._names, self._cfg
        t = x.shape[1]

        def _f(xr, *raw):
            cos, sin = rope_tables(t, cfg.head_dim, cfg.rope_theta)
            return SdarMath(cfg).layer(
                dict(zip(names, raw)), xr,
                (cos[None, None], sin[None, None]),
                Causal(t, block=cfg.block_length))[0]

        return apply_op(_f, x, *(params[n] for n in names),
                        name="sdar_moe_layer")


class SdarMoeForCausalLM(HybridBlock):
    """Embedding, the layers, a final RMSNorm, the untied head; the
    forward returns logits (B, T, V), row ``p`` for token ``p``."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        with self.name_scope():
            self.embed_tokens = nn.Embedding(cfg.vocab_size,
                                             cfg.hidden_size,
                                             prefix="embed_")
            self.layers = nn.HybridSequential(prefix="layers_")
            for _ in range(cfg.num_layers):
                self.layers.add(SdarMoeLayer(cfg))
            self.norm = RMSNorm(cfg.hidden_size, cfg.norm_eps,
                                prefix="norm_")
            self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False,
                                    flatten=False,
                                    in_units=cfg.hidden_size,
                                    prefix="lm_head_")

    @property
    def config(self):
        return self._cfg

    def hybrid_forward(self, F, input_ids):
        h = self.embed_tokens(input_ids)
        for layer in self.layers:
            h = layer(h)
        return self.lm_head(self.norm(h))

    def serving_decoder(self, max_len):
        """What ``GenerativeServer``'s engine asks a model for."""
        return SdarDecoder(self, max_len)


class SdarDecoder(PagedDecoder, SdarMath):
    """What the shared paged programs need of this family: the cache
    spec, which also says how it decodes, the weights,
    :meth:`SdarMath.layer` and the logits."""

    def __init__(self, net, max_len):
        super().__init__(net, max_len)
        self.block_len = self.cfg.block_length
        if self.max_len % self.block_len:
            raise MXNetError(
                f"max_len {self.max_len} must be whole blocks of "
                f"{self.block_len} positions: a block at its end is "
                "written and read whole")

    def cache_spec(self):
        cfg = self.cfg
        return CacheSpec(
            layers=("kv",) * cfg.num_layers,
            num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            expert_layers=cfg.num_layers, num_experts=cfg.num_experts,
            decoding=cfg.decoding)

    def _weights(self):
        net = self._net
        raw = lambda p: p.data()._data  # noqa: E731
        layers = [{n: raw(getattr(lr, n)) for n in lr._names}
                  for lr in net.layers]
        return dict(layers=layers, emb=raw(net.embed_tokens.weight),
                    norm=raw(net.norm.weight), head=raw(net.lm_head.weight))

    def _logits(self, w, x):
        return rms_norm(x, w["norm"], self.cfg.norm_eps) @ w["head"].T


def sdar_moe_tiny(**overrides):
    kw = dict(SDAR_CONFIGS["sdar_moe_tiny"])
    kw.update(overrides)
    return SdarMoeForCausalLM(SdarMoeConfig(**kw))
