"""GLM-MoE-DSA family (``model_type`` ``glm_moe_dsa``): latent attention
(MLA) whose queries read the keys a learned indexer selects (DSA),
dense then sigmoid-routed expert layers beside a shared expert.

Reference: NONE (the reference predates it).  Layer equations, with
``norm`` an RMSNorm with a learned weight, no biases but the indexer
key's norm, rotary pairs (2i, 2i+1), ``h = norm_attn(x)``:

* latent attention: ``c_q = norm(h W_qa)``; ``q = c_q W_qb``, a head
  ``nope + rope`` wide, RoPE on the ``rope`` part; ``[c_kv | k_r] = h
  W_kva``, ``c_kv = norm(c_kv)``, ``k_r = RoPE(k_r)``, one for all
  heads; ``[k_nope_h | v_h] = c_kv W_kvb``; ``score_h[t, s] =
  (q_nope_h[t] . k_nope_h[s] + q_rope_h[t] . k_r[s]) / sqrt(nope +
  rope)`` over ``s`` in ``S_t``, float32 softmax, ``o = concat_h(sum_s
  p_h v_h) W_o``.  A cache keeps ``[c_kv | k_r]`` a token;
* the indexer: ``q_I = c_q W_Iq`` (``index_n_heads`` x
  ``index_head_dim``), ``k_I = LayerNorm(h W_Ik)`` (one for all heads),
  RoPE on the first ``rope`` values of both, ``w = h W_Iw *
  index_n_heads^-1/2 * index_head_dim^-1/2``; ``I[t, s] = sum_j w[t, j]
  relu(q_I[t, j] . k_I[s])``; ``S_t`` = the ``index_topk`` positions
  ``s <= t`` of largest ``I[t, s]`` (a tie to the earlier), all of them
  while fewer are visible.  A cache keeps ``k_I`` a token;
* feed-forward: a dense SwiGLU for ``l < first_k_dense``; else
  :func:`mxnet_tpu.models.moe.routed_ffn` with sigmoid scores over ALL
  ``num_experts``, a per-expert bias added for the choice only, the
  chosen weights renormalised and scaled by ``routed_scaling_factor``,
  over the bank's held part ``experts_held`` (the shares of a layer
  divided over chips add up to the uncut layer), plus the shared
  expert, a SwiGLU every row takes, counted once;
* model: embedding, the layers, a final RMSNorm, an untied head.  The
  multi-token-prediction layer of the published checkpoint is not part
  of the served forward.

One definition of the mathematics: :meth:`GlmMath.layer` ``(params, x,
rope rows, cache view) -> (x, what the view kept, expert rows)`` is
what the Gluon blocks' ``hybrid_forward`` runs over a whole sequence (a
:class:`~.decoder.SelectingCausal` view without lengths: the plain
expanded form) and what the paged programs that :class:`GlmDecoder`
inherits run: the prefill in query tiles, the step against the paged
latent cache (a :class:`~.decoder.StepView`), both in the absorbed form
over the selected rows alone.  The two forms and the stored row are
``ops.latent_cache``'s.
"""
from __future__ import annotations

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from .decoder import (CacheSpec, PagedDecoder, SelectingCausal, apply_rope,
                      layer_norm, rms_norm, rope_tables)
from . import mla
from .llama import RMSNorm
from .moe import expert_layer_ffn, expert_product
from .moe import swiglu as _swiglu  # noqa: F401  (the tests' name for it)

__all__ = ["GlmMoeDsaConfig", "GlmMoeDsaLayer", "GlmMoeDsaForCausalLM",
           "GlmMath", "GlmDecoder", "glm_moe_dsa_tiny", "GLM_CONFIGS"]

#: eps of the indexer key's LayerNorm (the DeepSeek-V3.2 inference
#: reference this family follows)
INDEX_NORM_EPS = 1e-6


class GlmMoeDsaConfig:
    def __init__(self, hidden_size=6144, intermediate_size=12288,
                 moe_intermediate_size=2048, num_layers=78,
                 first_k_dense=3, num_heads=64, q_lora_rank=2048,
                 kv_lora_rank=512, qk_nope_head_dim=192,
                 qk_rope_head_dim=64, v_head_dim=256, index_n_heads=32,
                 index_head_dim=128, index_topk=2048, num_experts=256,
                 num_experts_per_tok=8, n_shared_experts=1,
                 norm_topk_prob=True, routed_scaling_factor=2.5,
                 experts_held=None, vocab_size=154880, max_seq_len=202752,
                 rope_theta=1e6, norm_eps=1e-5):
        first, held = experts_held or (0, num_experts)
        if not (0 <= first and held >= 1 and first + held <= num_experts):
            raise MXNetError(f"experts_held {experts_held} is not a range "
                             f"of the {num_experts} experts")
        if not 0 <= first_k_dense <= num_layers:
            raise MXNetError("first_k_dense must lie in [0, num_layers]")
        if index_head_dim < qk_rope_head_dim or qk_rope_head_dim % 2:
            raise MXNetError("the indexer's head carries the rotary part")
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_layers = num_layers
        self.first_k_dense = first_k_dense
        self.num_heads = num_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.index_n_heads = index_n_heads
        self.index_head_dim = index_head_dim
        self.index_topk = index_topk
        #: the router's width: every expert of the layer, held or not
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.n_shared_experts = n_shared_experts
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        #: (first, count): the contiguous part of each layer's bank that
        #: this replica holds; the rest lie on other chips
        self.experts_held = (int(first), int(held))
        self.vocab_size = vocab_size
        self.max_seq_len = max_seq_len
        self.rope_theta = rope_theta
        self.norm_eps = norm_eps
        self.tie_embeddings = False

    @property
    def head_dim(self):
        """What rotates: the rotary tables' width."""
        return self.qk_rope_head_dim

    @property
    def latent_dim(self):
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def num_expert_layers(self):
        return self.num_layers - self.first_k_dense

    def is_dense(self, l):
        return l < self.first_k_dense


GLM_CONFIGS = {
    # hidden 64, one dense layer then two expert layers; 4 heads of
    # 16 + 8 / 16 over a latent of 32 + 8, an indexer of 2 x 16 that
    # selects 8; 16 experts, 4 a token, one shared
    "glm_moe_dsa_tiny": dict(
        hidden_size=64, intermediate_size=160, moe_intermediate_size=32,
        num_layers=3, first_k_dense=1, num_heads=4, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, index_n_heads=2, index_head_dim=16, index_topk=8,
        num_experts=16, num_experts_per_tok=4, vocab_size=256,
        max_seq_len=128),
}


def _layer_param_shapes(cfg, l):
    """Leaf name -> shape of layer ``l``'s parameters; matrices are
    (out, in) but the expert bank, which is stacked (held, in, out)."""
    h, nh = cfg.hidden_size, cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ql, kl = cfg.q_lora_rank, cfg.kv_lora_rank
    ih, idim = cfg.index_n_heads, cfg.index_head_dim
    out = {"attn_norm": (h,), "ffn_norm": (h,),
           "q_a": (ql, h), "q_a_norm": (ql,), "q_b": (nh * (dn + dr), ql),
           "kv_a": (kl + dr, h), "kv_a_norm": (kl,),
           "kv_b": (nh * (dn + dv), kl), "o": (h, nh * dv),
           "idx_q": (ih * idim, ql), "idx_k": (idim, h),
           "idx_k_norm": (idim,), "idx_k_bias": (idim,), "idx_w": (ih, h)}
    if cfg.is_dense(l):
        f = cfg.intermediate_size
        out.update(gate=(f, h), up=(f, h), down=(h, f))
    else:
        e, i = cfg.num_experts, cfg.moe_intermediate_size
        held, s = cfg.experts_held[1], cfg.n_shared_experts * i
        out.update(router=(e, h), expert_bias=(e,), w_gate=(held, h, i),
                   w_up=(held, h, i), w_down=(held, i, h),
                   shared_gate=(s, h), shared_up=(s, h), shared_down=(h, s))
    return out


class GlmMath:
    """The layer mathematics, once."""

    def __init__(self, cfg):
        self.cfg = cfg

    def attention(self, p, u, rope, view):
        """Latent attention over the keys the indexer selects, over a
        cache view: ``u`` (B, T, H), or a step's (S, H); ``rope`` the
        (cos, sin) rows of the call's positions as the paged programs
        lay them out (heads-major) -> (y, what the view kept)."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        nh, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        kl, eps = cfg.kv_lora_rank, cfg.norm_eps
        ih, idim = cfg.index_n_heads, cfg.index_head_dim
        lead = u.shape[:-1]
        # the rows' rotations, over (.., heads, dr): a step's (S, 1, ..)
        # or whole sequences' (1, T, 1, ..)
        cos, sin = (r[:, 0] if u.ndim == 2 else jnp.swapaxes(r, 1, 2)
                    for r in rope)
        w_uk, w_uv = mla.kv_b_halves(p["kv_b"], nh, dn, cfg.v_head_dim, kl)

        with jax.named_scope("mla_project"):
            one = (cos[..., 0, :], sin[..., 0, :])      # no head axis
            c_q, latent = mla.latent_rows(p, u, one, kl, eps)
            k_idx = layer_norm(u @ p["idx_k"].T, p["idx_k_norm"],
                               p["idx_k_bias"], INDEX_NORM_EPS)
            k_idx = jnp.concatenate(
                [apply_rope(k_idx[..., :dr], *one), k_idx[..., dr:]],
                axis=-1)
            w_idx = (u @ p["idx_w"].T) * (ih ** -0.5 * idim ** -0.5)

        def make_query(c_q, w_idx, cos, sin):
            with jax.named_scope("mla_project"):
                rows = c_q.shape[:-1]
                q = mla.query_heads(p, c_q, nh, dn + dr)
                q_idx = (c_q @ p["idx_q"].T).reshape(rows + (ih, idim))
                q_idx = jnp.concatenate(
                    [apply_rope(q_idx[..., :dr], cos, sin),
                     q_idx[..., dr:]], axis=-1)
                return (*mla.split_query(q, dn, cos, sin), q_idx, w_idx)

        def finish(heads):
            with jax.named_scope("mla_project"):
                return mla.output(p, heads)

        y, kept = view.attend_latent(
            make_query, latent, k_idx, (c_q, w_idx, cos, sin), w_uk, w_uv,
            (dn + dr) ** -0.5, finish)
        return y.reshape(lead + (-1,)), kept

    def ffn(self, p, u, live=None):
        """Dense SwiGLU, or the routed experts this replica holds plus
        the shared expert -> (y, rows each expert of the layer received
        or None).  ``live``: the rows a request owns, the only ones
        counted."""
        cfg = self.cfg
        return expert_layer_ffn(
            p, u, cfg.num_experts_per_tok, score="sigmoid",
            renormalize=cfg.norm_topk_prob,
            scale=cfg.routed_scaling_factor,
            experts_held=cfg.experts_held, live=live)

    def layer(self, p, x, rope, view):
        """``(params, x, rope rows, cache view) -> (x, what the view
        kept, expert rows)``.  A :class:`~.decoder.SelectingCausal`
        view: whole sequences, ``x`` (B, T, H), and the sequence's
        (latent rows, index keys) come back.  A
        :class:`~.decoder.StepView`: one token a slot, ``x`` (S, H),
        and the layer's updated pools come back."""
        eps = self.cfg.norm_eps
        y, kept = self.attention(p, rms_norm(x, p["attn_norm"], eps), rope,
                                 view)
        x = x + y
        y, counts = self.ffn(p, rms_norm(x, p["ffn_norm"], eps), view.live)
        return x + y, kept, counts


class GlmMoeDsaLayer(HybridBlock):
    """One layer; its Parameters carry the leaf names of
    :func:`_layer_param_shapes` and its forward is
    :meth:`GlmMath.layer` over whole sequences in the plain form."""

    def __init__(self, cfg, index, **kwargs):
        super().__init__(**kwargs)
        self._cfg, self._index = cfg, index
        self._names = sorted(_layer_param_shapes(cfg, index))
        with self.name_scope():
            for name, shape in _layer_param_shapes(cfg, index).items():
                init = "ones" if name.endswith("norm") else \
                    "zeros" if name.endswith("bias") else None
                setattr(self, name,
                        self.params.get(name, shape=shape, init=init))

    def hybrid_forward(self, F, x, **params):
        from ..ops.registry import apply_op

        names, cfg = self._names, self._cfg
        t = x.shape[1]

        def _f(xr, *raw):
            cos, sin = rope_tables(t, cfg.head_dim, cfg.rope_theta)
            return GlmMath(cfg).layer(
                dict(zip(names, raw)), xr,
                (cos[None, None], sin[None, None]),
                SelectingCausal(cfg.index_topk))[0]

        return apply_op(_f, x, *(params[n] for n in names),
                        name="glm_moe_dsa_layer")


class GlmMoeDsaForCausalLM(HybridBlock):
    """Embedding, the layers, a final RMSNorm, the untied head; the
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
                self.layers.add(GlmMoeDsaLayer(cfg, l))
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
        return GlmDecoder(self, max_len)


class GlmDecoder(PagedDecoder, GlmMath):
    """What the shared paged programs need of this family: the cache
    spec (every layer the latent kind), the weights,
    :meth:`GlmMath.layer`, the logits and the prefill's selecting
    view."""

    def cache_spec(self):
        cfg = self.cfg
        return CacheSpec(
            layers=("latent",) * cfg.num_layers, num_kv_heads=0,
            head_dim=cfg.head_dim, expert_layers=cfg.num_expert_layers,
            num_experts=cfg.num_experts, latent_dim=cfg.latent_dim,
            index_dim=cfg.index_head_dim, select_topk=cfg.index_topk)

    def expert_product(self, rows, dtype):
        cfg = self.cfg
        return expert_product(rows, cfg.num_experts_per_tok,
                              cfg.experts_held[1], cfg.hidden_size,
                              cfg.moe_intermediate_size, dtype)

    def _prefill_view(self, lp, real, lengths, t0):
        import jax.numpy as jnp

        return SelectingCausal(
            self.cfg.index_topk, real,
            jnp.broadcast_to(t0, (real.shape[0],)))

    def _weights(self):
        net = self._net
        raw = lambda p: p.data()._data  # noqa: E731
        layers = [{n: raw(getattr(lr, n)) for n in lr._names}
                  for lr in net.layers]
        return dict(layers=layers, emb=raw(net.embed_tokens.weight),
                    norm=raw(net.norm.weight), head=raw(net.lm_head.weight))

    def _logits(self, w, x):
        return rms_norm(x, w["norm"], self.cfg.norm_eps) @ w["head"].T


def glm_moe_dsa_tiny(**overrides):
    kw = dict(GLM_CONFIGS["glm_moe_dsa_tiny"])
    kw.update(overrides)
    return GlmMoeDsaForCausalLM(GlmMoeDsaConfig(**kw))
