"""JoyAI-LLM-Flash family (``model_type`` ``joyai_llm_flash``): the
DeepSeek-V3 block, trained.  Latent attention (MLA) over every earlier
position, a dense layer then sigmoid-routed expert layers beside a shared
expert, and the multi-token-prediction module (depth 1) whose loss rides
the step.

Reference: NONE (the reference predates it).  With ``norm`` an RMSNorm
(a learned weight), no biases, rotary pairs (2i, 2i+1), ``u =
norm_attn(x)``:

* latent attention: :mod:`~mxnet_tpu.models.mla`'s projections
  (``c_q``, ``q``, ``[c_kv | k_r]``, ``[k_nope_h | v_h]``, ``W_o``);
  ``s_h[t, s] = (q_nope_h[t] . k_nope_h[s] + q_rope_h[t] . k_r[s]) /
  sqrt(nope + rope)`` over ``s <= t``, float32 softmax: the expanded
  heads, ``nope + rope`` wide for q and k and ``v_head_dim`` for v, through
  ``ops.flash_attention.flash_attention_raw`` (causal), forward and
  backward;
* feed-forward, ``m = norm_ffn(x)``: :func:`~mxnet_tpu.models.moe.
  expert_layer_ffn`: a dense SwiGLU for ``l < first_k_dense``; else
  ``routed_ffn`` (sigmoid scores over ALL ``num_experts``, a per-expert
  bias added for the choice only, the chosen weights renormalised and
  scaled) over the bank's held part plus one shared SwiGLU;
* model: ``h_0 = E[ids]``, the layers, ``logits = norm_f(h_L) W_head``
  (untied);
* the prediction module, for positions ``i`` with ``i + 2 < T``: ``h'_i =
  W_eh [norm_e(E[t_{i+1}]) ; norm_h(h_L,i)]``, ONE more expert layer of
  the same equations with its own weights, ``logits'_i = norm_f'(.)
  W_head`` with the SAME ``E`` and ``W_head``, predicting ``t_{i+2}``;
* the objective: ``mean_i CE(logits_i, t_{i+1}) + mtp_loss_weight x
  mean_i CE(logits'_i, t_{i+2})``;
* the choice bias has no gradient: after a step, from the rows ``c_e`` each
  of a layer's experts received in it, ``b_e <- b_e + bias_update_speed x
  sign(mean(c) - c_e)``.  It is a ``grad_req="null"`` Parameter that the
  training forward writes, as batch-norm writes its running statistics.

:class:`JoyAIFlashForCausalLM` returns logits; :class:`JoyAIFlashForPretraining`
returns the objective and the step's side values (the two loss terms, the
rows by expert), which :func:`pretrain_forward_loss` hands to
``gluon.step_fusion.report``.  The module runs over all ``T`` positions
(the kernels' tiles stay whole) with the last two left out of its loss and
of its experts' counts (``live``); attention is causal, so they change
nothing before them.  Recomputation by layer goes through
``memory.policy.checkpoint_wrap`` (:meth:`set_remat`), and neither loss
holds a whole ``(rows, vocab)`` float32 array: rows go in chunks, each
recomputed in the backward.  No ``serving_decoder``: served, this is
``models.glm_moe_dsa`` without its indexer.
"""
from __future__ import annotations

import numpy as np

from .. import autograd
from ..base import MXNetError
from ..gluon.block import HybridBlock
from ..memory import policy as _mem_policy
from . import mla
from .decoder import rms_norm, rope_tables
from .moe import expert_layer_ffn, expert_product

__all__ = ["JoyAIFlashConfig", "JoyAIFlashLayer", "JoyAIFlashForCausalLM",
           "JoyAIFlashForPretraining", "JoyMath", "joyai_flash_tiny",
           "pretrain_forward_loss", "count_reported", "JOYAI_CONFIGS"]

#: rows of ``(rows, vocab)`` float32 logits a loss holds at once
LOSS_CHUNK_ROWS = 2048


class JoyAIFlashConfig:
    def __init__(self, hidden_size=2048, intermediate_size=7168,
                 moe_intermediate_size=768, num_layers=40, first_k_dense=1,
                 num_heads=32, q_lora_rank=1536, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 num_experts=256, num_experts_per_tok=8, n_shared_experts=1,
                 norm_topk_prob=True, routed_scaling_factor=2.5,
                 experts_held=None, vocab_size=129280, rope_theta=32e6,
                 norm_eps=1e-6, num_nextn_predict_layers=1,
                 mtp_loss_weight=0.3, bias_update_speed=1e-3):
        first, held = experts_held or (0, num_experts)
        if not (0 <= first and held >= 1 and first + held <= num_experts):
            raise MXNetError(f"experts_held {experts_held} is not a range "
                             f"of the {num_experts} experts")
        if not 0 <= first_k_dense <= num_layers:
            raise MXNetError("first_k_dense must lie in [0, num_layers]")
        if num_nextn_predict_layers != 1:
            raise MXNetError("the prediction module is written at depth 1")
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
        #: the router's width: every expert of the layer, held or not
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.n_shared_experts = n_shared_experts
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        #: (first, count): the contiguous part of each layer's bank held
        self.experts_held = (int(first), int(held))
        self.vocab_size = vocab_size
        self.rope_theta = rope_theta
        self.norm_eps = norm_eps
        self.num_nextn_predict_layers = num_nextn_predict_layers
        self.mtp_loss_weight = mtp_loss_weight
        self.bias_update_speed = bias_update_speed

    @property
    def num_expert_layers(self):
        """Expert layers that count rows: the stack's and the module's."""
        return self.num_layers - self.first_k_dense + 1

    def is_dense(self, l):
        return l < self.first_k_dense


JOYAI_CONFIGS = {
    # hidden 64, one dense layer then two expert layers and the module;
    # 4 heads of 16 + 8 / 16 over a latent of 32 + 8; 16 experts, 4 a
    # token, one shared
    "joyai_flash_tiny": dict(
        hidden_size=64, intermediate_size=160, moe_intermediate_size=32,
        num_layers=3, first_k_dense=1, num_heads=4, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, num_experts=16, num_experts_per_tok=4,
        vocab_size=256),
}


def _layer_param_shapes(cfg, dense):
    """Leaf name -> shape of a layer's parameters; matrices are (out, in)
    but the expert bank, which is stacked (held, in, out)."""
    h, nh = cfg.hidden_size, cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ql, kl = cfg.q_lora_rank, cfg.kv_lora_rank
    out = {"attn_norm": (h,), "ffn_norm": (h,),
           "q_a": (ql, h), "q_a_norm": (ql,), "q_b": (nh * (dn + dr), ql),
           "kv_a": (kl + dr, h), "kv_a_norm": (kl,),
           "kv_b": (nh * (dn + dv), kl), "o": (h, nh * dv)}
    if dense:
        f = cfg.intermediate_size
        out.update(gate=(f, h), up=(f, h), down=(h, f))
    else:
        e, i = cfg.num_experts, cfg.moe_intermediate_size
        held, s = cfg.experts_held[1], cfg.n_shared_experts * i
        out.update(router=(e, h), expert_bias=(e,), w_gate=(held, h, i),
                   w_up=(held, h, i), w_down=(held, i, h),
                   shared_gate=(s, h), shared_up=(s, h), shared_down=(h, s))
    return out


def _autocast(x, p):
    """bf16 autocast over float32 masters (``amp.init``): the hidden state
    and every matrix in the target dtype, inside the differentiated
    function, so the masters get float32 gradients.  Norm weights, the
    router (float32 routing) and the choice bias stay as they are."""
    from .. import amp

    dt = amp._target_dtype()
    if dt is None:
        return x, p
    keep = ("router", "expert_bias")
    return x.astype(dt), {
        n: a if a.ndim < 2 or n in keep else a.astype(dt)
        for n, a in p.items()}


class JoyMath:
    """The layer mathematics over whole sequences."""

    def __init__(self, cfg):
        self.cfg = cfg

    def attention(self, p, u):
        """Causal latent attention, every earlier position read: ``u``
        (B, T, H) -> (B, T, H)."""
        import jax
        import jax.numpy as jnp

        from ..ops import latent_cache
        from ..ops.flash_attention import flash_attention_raw

        cfg = self.cfg
        nh, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        kl, eps = cfg.kv_lora_rank, cfg.norm_eps
        t = u.shape[1]
        cos, sin = (jnp.asarray(r)[None, :, None]
                    for r in rope_tables(t, dr, cfg.rope_theta))
        with jax.named_scope("mla_project"):
            c_q, latent = mla.latent_rows(
                p, u, (cos[..., 0, :], sin[..., 0, :]), kl, eps)
            q_nope, q_rope = mla.split_query(
                mla.query_heads(p, c_q, nh, dn + dr), dn, cos, sin)
            k_nope, v, k_r = latent_cache.expanded_heads(
                latent, *mla.kv_b_halves(p["kv_b"], nh, dn, cfg.v_head_dim,
                                         kl))
        with jax.named_scope("mla_train_attention"):
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_r[:, :, None], q_rope.shape)],
                axis=-1)
            heads = flash_attention_raw(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), True, (dn + dr) ** -0.5)
        with jax.named_scope("mla_project"):
            return mla.output(p, heads.transpose(0, 2, 1, 3))

    def ffn(self, p, u, live=None):
        cfg = self.cfg
        return expert_layer_ffn(
            p, u, cfg.num_experts_per_tok, score="sigmoid",
            renormalize=cfg.norm_topk_prob,
            scale=cfg.routed_scaling_factor,
            experts_held=cfg.experts_held, live=live)

    def layer(self, p, x, live=None):
        """``(leaves, x (B, T, H), live (B, T) or None) -> (x, rows each
        expert received or None)``."""
        eps = self.cfg.norm_eps
        x = x + self.attention(p, rms_norm(x, p["attn_norm"], eps))
        y, counts = self.ffn(p, rms_norm(x, p["ffn_norm"], eps), live)
        return x + y, counts

    def bias_step(self, bias, counts):
        """The choice bias after a step in which the layer's experts
        received ``counts`` rows."""
        import jax.numpy as jnp

        c = counts.astype(jnp.float32)
        return bias + jnp.float32(self.cfg.bias_update_speed) \
            * jnp.sign(c.mean() - c).astype(bias.dtype)


def token_loss(h, norm_w, head_w, labels, weights, eps, denom):
    """``sum_i weights_i CE(norm(h_i) W_head, labels_i) / denom`` over
    rows ``h`` (N, H) without a whole ``(N, V)`` array: rows go in chunks
    of ``LOSS_CHUNK_ROWS``, a chunk's float32 logits recomputed in the
    backward.  ``head_w`` (V, H) stays in its own dtype outside the chunk
    and is cast inside, so its gradient sums over the chunks in that
    dtype."""
    import jax
    import jax.numpy as jnp

    n = h.shape[0]
    rows = LOSS_CHUNK_ROWS if n % LOSS_CHUNK_ROWS == 0 else n

    def chunk(total, c):
        hc, lab, w = c
        logits = jnp.einsum(
            "nh,vh->nv", rms_norm(hc, norm_w, eps), head_w.astype(hc.dtype),
            preferred_element_type=jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lab[:, None], axis=-1)[:, 0]
        return total + ((logz - picked) * w).sum(), None

    total, _ = jax.lax.scan(
        _mem_policy.checkpoint_wrap(chunk, "layer"), jnp.float32(0.0),
        (h.reshape(-1, rows, h.shape[-1]), labels.reshape(-1, rows),
         weights.reshape(-1, rows)))
    return total / denom


def _shifted(ids, by):
    """Each position's token ``by`` later (the row's last repeated: its
    positions are left out of every sum)."""
    import jax.numpy as jnp

    return jnp.concatenate(
        [ids[:, by:], jnp.repeat(ids[:, -1:], by, axis=1)], axis=1)


class JoyAIFlashLayer(HybridBlock):
    """One layer; its Parameters carry the leaf names of
    :func:`_layer_param_shapes`.  The forward returns ``x`` for a dense
    layer and ``(x, rows each expert received (E,) float32)`` for an
    expert layer, whose training forward also moves the choice bias."""

    def __init__(self, cfg, dense, **kwargs):
        super().__init__(**kwargs)
        self._cfg, self._dense = cfg, dense
        self._remat = "none"
        shapes = _layer_param_shapes(cfg, dense)
        self._names = sorted(shapes)
        with self.name_scope():
            for name, shape in shapes.items():
                init = "ones" if name.endswith("norm") else \
                    "zeros" if name.endswith("bias") else None
                kw = {"grad_req": "null"} if name == "expert_bias" else \
                    {"wd_mult": 0.0} if name.endswith("norm") else {}
                setattr(self, name, self.params.get(
                    name, shape=shape, init=init, **kw))

    def hybrid_forward(self, F, x, live=None, **params):
        from ..ops.registry import apply_op

        names, cfg, dense = self._names, self._cfg, self._dense
        math = JoyMath(cfg)

        def _f(xr, *raw):
            import jax.numpy as jnp

            live_r, raw = (raw[0], raw[1:]) if live is not None \
                else (None, raw)
            xr, p = _autocast(xr, dict(zip(names, raw)))
            y, counts = math.layer(p, xr, live_r)
            return y if dense else (y, counts.astype(jnp.float32))

        out = apply_op(
            _mem_policy.checkpoint_wrap(_f, self._remat), x,
            *(() if live is None else (live,)),
            *(params[n] for n in names), name="joyai_flash_layer")
        if dense:
            return out
        y, counts = out
        if autograd.is_training():
            bias = params["expert_bias"]
            bias._data = apply_op(math.bias_step, bias, counts,
                                  name="joyai_flash_bias_step")._data
        return y, counts


class _Leaves(HybridBlock):
    """A bag of named Parameters."""

    def __init__(self, shapes, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            for name, shape in shapes.items():
                norm = name.endswith("norm")    # ones, and no weight decay
                setattr(self, name, self.params.get(
                    name, shape=shape, init="ones" if norm else None,
                    **({"wd_mult": 0.0} if norm else {})))


class JoyAIFlashForCausalLM(HybridBlock):
    """Embedding, the layers, a final RMSNorm, the untied head; the
    forward returns logits (B, T, V)."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        h, v = cfg.hidden_size, cfg.vocab_size
        with self.name_scope():
            self.top = _Leaves({"embed": (v, h), "norm": (h,),
                                "head": (v, h)}, prefix="top_")
            self.layers = []
            for l in range(cfg.num_layers):
                layer = JoyAIFlashLayer(cfg, cfg.is_dense(l),
                                        prefix=f"layers{l}_")
                self.register_child(layer)
                self.layers.append(layer)

    @property
    def config(self):
        return self._cfg

    def embed(self, ids):
        from ..ops.registry import apply_op

        def _f(ids_r, e):
            from .. import amp

            dt = amp._target_dtype()
            return (e if dt is None else e.astype(dt))[ids_r]

        return apply_op(_f, ids, self.top.embed.data(),
                        name="joyai_flash_embed")

    def hidden(self, ids):
        """-> (h_L (B, T, H) before the final norm, [rows by expert of
        each expert layer])."""
        h, rows = self.embed(ids), []
        for layer in self.layers:
            out = layer(h)
            if isinstance(out, tuple):
                h, c = out
                rows.append(c)
            else:
                h = out
        return h, rows

    def hybrid_forward(self, F, input_ids):
        from ..ops.registry import apply_op

        eps = self._cfg.norm_eps
        h, _ = self.hidden(input_ids)
        return apply_op(
            lambda hr, nw, hw: rms_norm(hr, nw, eps) @ hw.astype(hr.dtype).T,
            h, self.top.norm.data(), self.top.head.data(),
            name="joyai_flash_logits")


class JoyAIFlashForPretraining(HybridBlock):
    """The model with its prediction module and the objective:
    ``net(ids (B, T)) -> (loss, loss_main, loss_mtp, expert_rows (expert
    layers, E) float32)``, the module's layer last."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        h = cfg.hidden_size
        with self.name_scope():
            self.model = JoyAIFlashForCausalLM(cfg, prefix="model_")
            self.mtp = _Leaves({"e_norm": (h,), "h_norm": (h,),
                                "eh_proj": (h, 2 * h), "norm": (h,)},
                               prefix="mtp_")
            self.mtp_layer = JoyAIFlashLayer(cfg, False, prefix="mtp_layer_")

    @property
    def config(self):
        return self._cfg

    def set_remat(self, tier):
        """Recomputation by layer: ``memory.policy``'s tier for every
        layer of the stack and the module's."""
        tier = _mem_policy.normalize(tier)
        if tier == "auto":
            raise MXNetError("resolve 'auto' before set_remat()")
        for layer in (*self.model.layers, self.mtp_layer):
            layer._remat = tier
        return self

    @property
    def remat(self):
        return self.mtp_layer._remat

    def hybrid_forward(self, F, input_ids):
        import jax

        from ..ops.registry import apply_op

        cfg, model, mtp = self._cfg, self.model, self.mtp
        eps, weight = cfg.norm_eps, cfg.mtp_loss_weight
        b, t = input_ids.shape
        if t < 3:
            raise MXNetError("the prediction module needs 3 positions")
        h, rows = model.hidden(input_ids)

        def _join(hr, e_next, e_norm, h_norm, eh):
            with jax.named_scope("mtp_module"):
                both = jax.numpy.concatenate(
                    [rms_norm(e_next, e_norm, eps),
                     rms_norm(hr, h_norm, eps)], axis=-1)
                return both @ eh.astype(hr.dtype).T

        next_ids = apply_op(lambda i: _shifted(i, 1), input_ids,
                            name="joyai_flash_shift")
        live = apply_op(
            lambda i: jax.numpy.broadcast_to(
                jax.numpy.arange(t) < t - 2, i.shape),
            input_ids, name="joyai_flash_live")
        h2 = apply_op(_join, h, model.embed(next_ids), mtp.e_norm.data(),
                      mtp.h_norm.data(), mtp.eh_proj.data(),
                      name="joyai_flash_mtp_join")
        h2, c = self.mtp_layer(h2, live)
        rows = rows + [c]

        def _losses(ids_r, hr, h2r, nw, nw2, hw, *counts):
            import jax.numpy as jnp

            with jax.named_scope("lm_loss"):
                pos = jnp.broadcast_to(jnp.arange(t), ids_r.shape)
                flat = lambda a: a.reshape(b * t, *a.shape[2:])  # noqa: E731
                main = token_loss(
                    flat(hr), nw, hw, flat(_shifted(ids_r, 1)),
                    flat((pos < t - 1).astype(jnp.float32)), eps,
                    b * (t - 1))
                extra = token_loss(
                    flat(h2r), nw2, hw, flat(_shifted(ids_r, 2)),
                    flat((pos < t - 2).astype(jnp.float32)), eps,
                    b * (t - 2))
            return (main + weight * extra, main, extra,
                    jax.lax.stop_gradient(jnp.stack(counts)))

        return apply_op(_losses, input_ids, h, h2, model.top.norm.data(),
                        mtp.norm.data(), model.top.head.data(), *rows,
                        name="joyai_flash_losses")

    def step_facts(self, rows, dtype):
        """What a training step of ``rows`` rows took, for the lane log:
        which form the routed product, the remat tier, the attention's
        tiles."""
        from ..ops import flash_attention

        cfg = self._cfg
        b, t = rows
        return {
            "expert_product": expert_product(
                b * t, cfg.num_experts_per_tok, cfg.experts_held[1],
                cfg.hidden_size, cfg.moe_intermediate_size, dtype),
            "remat": self.remat,
            "flash_tiles": flash_attention.train_form(
                (b, cfg.num_heads, t,
                 cfg.qk_nope_head_dim + cfg.qk_rope_head_dim),
                cfg.v_head_dim, np.dtype(dtype).itemsize, causal=True)}


def pretrain_forward_loss(net, ids):
    """``FusedTrainStep``'s ``forward_loss`` for a
    :class:`JoyAIFlashForPretraining`: the objective, with the step's side
    values reported (``gluon.step_fusion.report``): ``loss_main``,
    ``loss_mtp``, ``expert_rows`` (expert layers, E) int32, and one number
    a step each: ``pairs_held`` (the (row, expert) pairs that fell on the
    held experts, over the layers), ``expert_rows_max`` and
    ``expert_rows_mean`` (over the held experts of every layer)."""
    from ..gluon import step_fusion
    from ..ops.registry import apply_op

    first, held = net.config.experts_held
    loss, main, extra, rows = net(ids)

    def _summary(r):
        import jax.numpy as jnp

        here = r[:, first:first + held]
        return (r.astype(jnp.int32), here.sum(), here.max(), here.mean())

    rows_i, pairs, most, mean = apply_op(_summary, rows,
                                         name="joyai_flash_rows_summary")
    step_fusion.report(loss_main=main, loss_mtp=extra, expert_rows=rows_i,
                       pairs_held=pairs, expert_rows_max=most,
                       expert_rows_mean=mean)
    from .. import amp

    step_fusion.report(**net.step_facts(
        ids.shape[-2:], amp._target_dtype() or np.float32))
    return loss


def count_reported(values):
    """The counters of a fetched dispatch (``FusedTrainStep.
    fetch_reported``'s dict): ``train.moe.pairs_held`` and
    ``train.moe.bias_updates`` (one a step an expert layer)."""
    from .. import telemetry

    telemetry.count("train.moe.pairs_held",
                    int(np.sum(values["pairs_held"])))
    telemetry.count("train.moe.bias_updates",
                    int(np.prod(np.shape(values["expert_rows"])[:-1])))


def joyai_flash_tiny(**overrides):
    kw = dict(JOYAI_CONFIGS["joyai_flash_tiny"])
    kw.update(overrides)
    return JoyAIFlashForPretraining(JoyAIFlashConfig(**kw))
