"""Keye-VL-2.0's language model (``model_type`` ``KeyeVL2``): the
Qwen3-MoE layer whose grouped-query heads read the keys a learned
indexer selects out of the K/V cache.

Reference: NONE (the reference predates it).  Layer equations, with
``norm`` an RMSNorm with a learned weight, no biases but the index
key's norm, ``u = norm_attn(x)``:

* attention: ``q = u W_q``, ``k = u W_k``, ``v = u W_v``; q and k pass
  an RMSNorm over a head's channels (one learned weight shared by the
  heads) BEFORE their rotation; RoPE over the whole head in pairs ``(i,
  i + hd/2)``.  A token has a position triple ``(p_t, p_h, p_w)`` and
  frequency ``i`` turns by ``p_t``, ``p_h`` or ``p_w`` as
  ``mrope_section`` divides the ``hd/2`` frequencies (chunked, Qwen2-VL's
  order); for text the three are equal and the rotation is the ordinary
  one, to the bit.  ``score_h[t, s] = q_h[t] . k_{h // g}[s] / sqrt(hd)``
  over ``s`` in ``S_t``, float32 softmax, ``o = concat_h(sum_s p_h
  v_{h // g}[s]) W_o``;
* the indexer (DeepSeek sparse attention's, its queries from ``u``:
  there is no query latent): ``q_I = u W_Iq`` (``index_n_heads`` x
  ``index_head_dim``), ``k_I = LayerNorm(u W_Ik)`` (one for all heads,
  weight and bias), both rotated over ALL their values in pairs ``(i,
  i + D/2)`` by ``p_t``, ``w = u W_Iw * index_n_heads^-1/2 *
  index_head_dim^-1/2``; ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] .
  k_I[s])``; ``S_t`` = the ``index_topk`` positions ``s <= t`` of
  largest ``I[t, s]`` (a tie to the earlier), all of them while fewer
  are visible.  A cache keeps ``k``, ``v`` and ``k_I`` a token;
* experts: :func:`mxnet_tpu.models.moe.routed_ffn` with float32 softmax
  scores over all experts, the ``k`` highest renormalised, no choice
  bias, no shared expert, no dense layer;
* model: embedding, the layers, a final RMSNorm, an untied head.  The
  vision tower and its projector are not here: prompts are token ids.

One definition of the mathematics: :meth:`KeyeMath.layer` ``(params, x,
rope rows, cache view) -> (x, what the view kept, expert rows)`` is
what the Gluon blocks' ``hybrid_forward`` runs over a whole sequence (a
:class:`~.decoder.SelectingCausal` view without lengths: the plain
form) and what the paged programs that :class:`KeyeDecoder` inherits
run: the prefill in query tiles under the selection's mask, the step
over the selected rows of the K/V pools alone (a
:class:`~.decoder.StepView`).  The selection is ``ops.sparse_select``'s,
the one ``models/glm_moe_dsa.py`` runs over its latent cache.
"""
from __future__ import annotations

import numpy as np

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from .decoder import (CacheSpec, PagedDecoder, SelectingCausal,
                      headnorm_qkv, layer_norm, rms_norm, rope_tables)
from .llama import RMSNorm
from .moe import routed_ffn

__all__ = ["KeyeVl2Config", "KeyeVl2Layer", "KeyeVl2ForCausalLM",
           "KeyeMath", "KeyeDecoder", "keye_vl2_tiny", "KEYE_CONFIGS"]

#: eps of the index key's LayerNorm (the DeepSeek-V3.2 inference
#: reference, as ``models/glm_moe_dsa.py`` assumes)
INDEX_NORM_EPS = 1e-6


class KeyeVl2Config:
    def __init__(self, hidden_size=2048, moe_intermediate_size=768,
                 num_layers=48, num_heads=32, num_kv_heads=4, head_dim=128,
                 mrope_section=(16, 24, 24), index_n_heads=16,
                 index_head_dim=64, index_topk=2048, num_experts=128,
                 num_experts_per_tok=8, norm_topk_prob=True,
                 vocab_size=151936, max_seq_len=262144, rope_theta=1e7,
                 norm_eps=1e-6):
        if num_heads % num_kv_heads:
            raise MXNetError("num_kv_heads must divide the heads")
        if sum(mrope_section) * 2 != head_dim:
            raise MXNetError(
                f"mrope_section {tuple(mrope_section)} must divide the "
                f"{head_dim // 2} frequencies of a head")
        if index_head_dim % 2:
            raise MXNetError("an index head rotates in pairs")
        self.hidden_size = hidden_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.mrope_section = tuple(int(n) for n in mrope_section)
        self.index_n_heads = index_n_heads
        self.index_head_dim = index_head_dim
        self.index_topk = index_topk
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = norm_topk_prob
        self.vocab_size = vocab_size
        self.max_seq_len = max_seq_len
        self.rope_theta = rope_theta
        self.norm_eps = norm_eps
        self.tie_embeddings = False


KEYE_CONFIGS = {
    # hidden 64, 3 layers, 4 query / 2 KV heads of 16 (8 frequencies in
    # sections 2, 3, 3), an indexer of 2 x 8 that selects 8, 16 experts
    # of 32, 4 a token
    "keye_vl2_tiny": dict(
        hidden_size=64, moe_intermediate_size=32, num_layers=3, num_heads=4,
        num_kv_heads=2, head_dim=16, mrope_section=(2, 3, 3),
        index_n_heads=2, index_head_dim=8, index_topk=8, num_experts=16,
        num_experts_per_tok=4, vocab_size=256, max_seq_len=128),
}


def _layer_param_shapes(cfg):
    """Leaf name -> shape of a layer's parameters; matrices are (out,
    in) but the expert bank, which is stacked (experts, in, out)."""
    h, hd = cfg.hidden_size, cfg.head_dim
    e, i = cfg.num_experts, cfg.moe_intermediate_size
    ih, idim = cfg.index_n_heads, cfg.index_head_dim
    return {"attn_norm": (h,), "ffn_norm": (h,),
            "q": (cfg.num_heads * hd, h), "k": (cfg.num_kv_heads * hd, h),
            "v": (cfg.num_kv_heads * hd, h), "o": (h, cfg.num_heads * hd),
            "q_norm": (hd,), "k_norm": (hd,),
            "idx_q": (ih * idim, h), "idx_k": (idim, h),
            "idx_k_norm": (idim,), "idx_k_bias": (idim,), "idx_w": (ih, h),
            "router": (e, h), "w_gate": (e, h, i), "w_up": (e, h, i),
            "w_down": (e, i, h)}


def rotate_half(x, cos, sin):
    """``x`` (.., D) rotated in pairs ``(i, i + D/2)``; ``cos`` / ``sin``
    (.., D/2) broadcast against it."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def mrope_rows(tables, positions, sections):
    """The (cos, sin) rows of position triples: ``tables`` a (cos, sin)
    pair ``(P, F)`` (:func:`~.decoder.rope_tables`), ``positions`` (3,
    ..) int; frequency ``i`` takes the row of the stream its section
    names (``sections`` (n_t, n_h, n_w), summing to F) -> a pair (..,
    F)."""
    import jax.numpy as jnp

    stream = np.repeat(np.arange(3), sections)           # (F,)
    return tuple(
        jnp.take_along_axis(
            jnp.stack([jnp.asarray(tab)[positions[s]] for s in range(3)]),
            jnp.asarray(stream).reshape((1,) * positions.ndim + (-1,)),
            axis=0)[0]
        for tab in tables)


class KeyeMath:
    """The layer mathematics, once."""

    def __init__(self, cfg):
        self.cfg = cfg

    def attention(self, p, u, rope, view):
        """Grouped-query attention over the keys the indexer selects,
        over a cache view: ``u`` (B, T, H), or a step's (S, H); ``rope``
        four arrays: the (cos, sin) rows of the call's positions over
        heads-major q and k (``(B or 1, 1, T, hd/2)``, a step's ``(S, 1,
        1, hd/2)``), then the indexer's, over its ``index_head_dim / 2``
        frequencies (``(B or 1, T, D/2)``, a step's ``(S, D/2)``) -> (y,
        what the view kept)."""
        import jax

        cfg = self.cfg
        ih, idim = cfg.index_n_heads, cfg.index_head_dim
        cos, sin, icos, isin = rope
        with jax.named_scope("gqa_project"):
            q, k, v = headnorm_qkv(p, u, cfg.num_heads, cfg.num_kv_heads,
                                   cfg.norm_eps)
            q, k = rotate_half(q, cos, sin), rotate_half(k, cos, sin)
            k_idx = rotate_half(
                layer_norm(u @ p["idx_k"].T, p["idx_k_norm"],
                           p["idx_k_bias"], INDEX_NORM_EPS), icos, isin)
            q_idx = rotate_half(
                (u @ p["idx_q"].T).reshape(u.shape[:-1] + (ih, idim)),
                icos[..., None, :], isin[..., None, :])
            w_idx = (u @ p["idx_w"].T) * (ih ** -0.5 * idim ** -0.5)
        ctx, kept = view.attend_selecting(q, k, v, q_idx, w_idx, k_idx)
        with jax.named_scope("gqa_project"):
            return ctx.reshape(u.shape[:-1] + (-1,)) @ p["o"].T, kept

    def experts(self, p, u, live=None):
        """The routed expert block -> (y, rows each expert received).
        ``live``: the rows a request owns, the only ones counted."""
        import jax

        cfg = self.cfg
        with jax.named_scope("moe_ffn"):
            y, counts = routed_ffn(
                u.reshape(-1, u.shape[-1]), p["router"], p["w_gate"],
                p["w_up"], p["w_down"], cfg.num_experts_per_tok,
                score="softmax", renormalize=cfg.norm_topk_prob,
                live=None if live is None else live.reshape(-1))
        return y.reshape(u.shape), counts

    def layer(self, p, x, rope, view):
        """``(params, x, rope rows, cache view) -> (x, what the view
        kept, expert rows)``.  A :class:`~.decoder.SelectingCausal`
        view: whole sequences, ``x`` (B, T, H), and the sequence's (k,
        v, index keys) come back.  A :class:`~.decoder.StepView`: one
        token a slot, ``x`` (S, H), and the layer's three pools come
        back."""
        eps = self.cfg.norm_eps
        y, kept = self.attention(p, rms_norm(x, p["attn_norm"], eps), rope,
                                 view)
        x = x + y
        y, counts = self.experts(p, rms_norm(x, p["ffn_norm"], eps),
                                 view.live)
        return x + y, kept, counts


class KeyeVl2Layer(HybridBlock):
    """One layer; its Parameters carry the leaf names of
    :func:`_layer_param_shapes` and its forward is
    :meth:`KeyeMath.layer` over whole sequences in the plain form, at
    the position triples it is given ``(3, B, T)``."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        self._names = sorted(_layer_param_shapes(cfg))
        with self.name_scope():
            for name, shape in _layer_param_shapes(cfg).items():
                init = "ones" if name.endswith("norm") else \
                    "zeros" if name.endswith("bias") else None
                setattr(self, name,
                        self.params.get(name, shape=shape, init=init))

    def hybrid_forward(self, F, x, positions, **params):
        from ..ops.registry import apply_op

        names, cfg = self._names, self._cfg

        def _f(xr, pos3, *raw):
            import jax.numpy as jnp

            pos3 = pos3.astype(jnp.int32)
            cos, sin = mrope_rows(
                rope_tables(cfg.max_seq_len, cfg.head_dim, cfg.rope_theta),
                pos3, cfg.mrope_section)
            icos, isin = (jnp.asarray(tab)[pos3[0]] for tab in rope_tables(
                cfg.max_seq_len, cfg.index_head_dim, cfg.rope_theta))
            return KeyeMath(cfg).layer(
                dict(zip(names, raw)), xr,
                (cos[:, None], sin[:, None], icos, isin),
                SelectingCausal(cfg.index_topk))[0]

        return apply_op(_f, x, positions, *(params[n] for n in names),
                        name="keye_vl2_layer")


class KeyeVl2ForCausalLM(HybridBlock):
    """Embedding, the layers, a final RMSNorm, the untied head; the
    forward returns logits (B, T, V).  ``positions`` (3, B, T): each
    token's ``(p_t, p_h, p_w)``; without them every stream is the
    token's index (text)."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        with self.name_scope():
            self.embed_tokens = nn.Embedding(cfg.vocab_size,
                                             cfg.hidden_size,
                                             prefix="embed_")
            self.layers = nn.HybridSequential(prefix="layers_")
            for _ in range(cfg.num_layers):
                self.layers.add(KeyeVl2Layer(cfg))
            self.norm = RMSNorm(cfg.hidden_size, cfg.norm_eps,
                                prefix="norm_")
            self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False,
                                    flatten=False,
                                    in_units=cfg.hidden_size,
                                    prefix="lm_head_")

    @property
    def config(self):
        return self._cfg

    def hybrid_forward(self, F, input_ids, positions=None):
        if positions is None:
            from .. import nd

            b, t = input_ids.shape
            positions = nd.array(np.broadcast_to(
                np.arange(t, dtype=np.int32), (3, b, t)), dtype="int32")
        h = self.embed_tokens(input_ids)
        for layer in self.layers:
            h = layer(h, positions)
        return self.lm_head(self.norm(h))

    def serving_decoder(self, max_len):
        """What ``GenerativeServer``'s engine asks a model for."""
        return KeyeDecoder(self, max_len)


class KeyeDecoder(PagedDecoder, KeyeMath):
    """What the shared paged programs need of this family: the cache
    spec (every layer K/V with an index key beside: a selecting K/V
    cache), the weights, :meth:`KeyeMath.layer` with the indexer's
    rotation of the call's positions, the logits and the prefill's
    selecting view.  A served request is text: its three position
    streams are its tokens' indices."""

    def __init__(self, net, max_len):
        import jax.numpy as jnp

        super().__init__(net, max_len)
        icos, isin = rope_tables(self.max_len, self.cfg.index_head_dim,
                                 self.cfg.rope_theta)
        self._icos, self._isin = jnp.asarray(icos), jnp.asarray(isin)

    def cache_spec(self):
        cfg = self.cfg
        return CacheSpec(
            layers=("kv",) * cfg.num_layers, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim, expert_layers=cfg.num_layers,
            num_experts=cfg.num_experts, index_dim=cfg.index_head_dim,
            select_topk=cfg.index_topk)

    def layer(self, p, x, rope, view):
        # the indexer's rows of the call's positions: a step's slots
        # each at its own, a prefill's rows in order
        if view.pos is None:
            t = x.shape[1]
            irope = (self._icos[:t][None], self._isin[:t][None])
        else:
            irope = (self._icos[view.pos], self._isin[view.pos])
        return KeyeMath.layer(self, p, x, tuple(rope) + irope, view)

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


def keye_vl2_tiny(**overrides):
    kw = dict(KEYE_CONFIGS["keye_vl2_tiny"])
    kw.update(overrides)
    return KeyeVl2ForCausalLM(KeyeVl2Config(**kw))
