"""Qwen3-Next family (``model_type`` ``qwen3_next``): gated delta-rule
layers (linear attention with a per-slot recurrent state) beside gated
grouped-query attention once every ``full_attention_interval`` layers,
softmax-routed experts plus a sigmoid-gated shared expert in every layer.

Reference: NONE (the reference predates it).  Layer equations, pre-norm
residual, no biases, ``N(x) = x / rms(x) * (1 + w)`` (a ZERO-CENTRED
RMSNorm weight, ``norm_eps``), ``h = N(x)``; layer ``l`` is full
attention where ``(l + 1) % full_attention_interval == 0``:

* gated delta rule: ``[q | k | v | z] = h W_qkvz``, ``[b | a] = h W_ba``;
  ``[q | k | v]`` pass a causal depthwise convolution of
  ``linear_conv_kernel_dim`` taps, then SiLU; ``q``, ``k``:
  ``linear_num_key_heads`` heads, each L2-normalised, ``q`` times
  ``head_k_dim^-1/2``, each repeated to the consecutive value heads it
  serves; a head, in float32: ``beta = sigmoid(b)``, ``g = -exp(A_log)
  softplus(a + dt_bias)`` and the rule of :mod:`mxnet_tpu.ops.gated_delta`;
  ``y = (w_n o / rms(o)) SiLU(z)`` a head (a PLAIN weight), the heads
  concatenated, ``W_o``.  A cache keeps, a slot, the head's float32
  matrices and the convolution's last ``taps - 1`` inputs;
* gated attention: ``[q | gate] = h W_q`` a head, ``k``, ``v``; ``q``,
  ``k`` pass ``N`` over the head; RoPE on the first
  ``partial_rotary_factor`` of the head; causal softmax; ``y = (attn
  sigmoid(gate)) W_o``.  A cache keeps ``k``, ``v``;
* experts: :func:`mxnet_tpu.models.moe.routed_ffn` with softmax scores
  over ALL ``num_experts``, the chosen renormalised, over the bank's
  held part ``experts_held``, plus ``sigmoid(h' w_sg)`` times the shared
  expert's SwiGLU, counted once;
* model: embedding, the layers, a final ``N``, an untied head.  The
  multi-token-prediction layer of the checkpoint is not in the forward.

One definition of the mathematics: :meth:`Qwen3NextMath.layer`
``(params, x, rope rows, cache view) -> (x, what the view kept, expert
rows)`` is what the Gluon blocks run over whole sequences and what the
paged programs :class:`Qwen3NextDecoder` inherits run: the prefill scans
a delta-rule layer in chunks (``ops.gated_delta.chunk_scan``), exact at
the true length inside a padded bucket; a step advances every slot's
state by one token (``ops.gated_delta.step``).  A delta-rule layer's
cache entry is TWO arrays a slot: the convolution's ring, ``(taps - 1,
channels)`` in the weights' dtype (row ``t % (taps - 1)`` holds position
``t``'s input), and the recurrent state, float32.
"""
from __future__ import annotations

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ops import gated_delta
from .decoder import (CacheSpec, Causal, PagedDecoder, StepView, apply_rope,
                      causal_conv, ring_at_length, ring_conv, rms_norm,
                      rope_tables, split_heads)
from .moe import expert_product, routed_ffn

__all__ = ["Qwen3NextConfig", "Qwen3NextLayer", "Qwen3NextForCausalLM",
           "Qwen3NextMath", "Qwen3NextDecoder", "qwen3_next_tiny",
           "QWEN3_NEXT_CONFIGS"]

#: eps under the root of the delta rule's L2 normalisation of q and k
L2_EPS = 1e-6


class Qwen3NextConfig:
    def __init__(self, hidden_size=2048, num_layers=48,
                 full_attention_interval=4, num_heads=16, num_kv_heads=2,
                 attn_head_dim=256, partial_rotary_factor=0.25,
                 linear_num_key_heads=16, linear_num_value_heads=32,
                 linear_key_head_dim=128, linear_value_head_dim=128,
                 linear_conv_kernel_dim=4, num_experts=512,
                 num_experts_per_tok=10, moe_intermediate_size=512,
                 shared_expert_intermediate_size=512, norm_topk_prob=True,
                 experts_held=None, vocab_size=151936, max_seq_len=262144,
                 rope_theta=1e7, norm_eps=1e-6):
        first, held = experts_held or (0, num_experts)
        if not (0 <= first and held >= 1 and first + held <= num_experts):
            raise MXNetError(f"experts_held {experts_held} is not a range "
                             f"of the {num_experts} experts")
        if num_heads % num_kv_heads \
                or linear_num_value_heads % linear_num_key_heads:
            raise MXNetError("key heads must divide the heads they serve")
        rotary = int(attn_head_dim * partial_rotary_factor)
        if rotary < 2 or rotary % 2:
            raise MXNetError("the rotary part of a head is a whole "
                             "number of pairs")
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.full_attention_interval = full_attention_interval
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.attn_head_dim = attn_head_dim
        self.rotary_dim = rotary
        self.linear_num_key_heads = linear_num_key_heads
        self.linear_num_value_heads = linear_num_value_heads
        self.linear_key_head_dim = linear_key_head_dim
        self.linear_value_head_dim = linear_value_head_dim
        self.linear_conv_kernel_dim = linear_conv_kernel_dim
        #: the router's width: every expert of the layer, held or not
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.shared_expert_intermediate_size = shared_expert_intermediate_size
        self.norm_topk_prob = norm_topk_prob
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
        return self.rotary_dim

    @property
    def key_dim(self):
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self):
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self):
        """Channels through the convolution: q, k and v."""
        return 2 * self.key_dim + self.value_dim

    @property
    def num_expert_layers(self):
        return self.num_layers

    def is_linear(self, l):
        return (l + 1) % self.full_attention_interval != 0

    def state_arrays(self):
        """A delta-rule layer's arrays a slot: ((shape, dtype), ...);
        dtype None is the weights'."""
        return (((self.linear_conv_kernel_dim - 1, self.conv_dim), None),
                (gated_delta.state_shape(self.linear_num_value_heads,
                                         self.linear_key_head_dim,
                                         self.linear_value_head_dim),
                 "float32"))


QWEN3_NEXT_CONFIGS = {
    # hidden 64, one period: three delta-rule layers (2 key / 4 value
    # heads of 16) and one attention layer (4 / 2 heads of 16, 8 rotate);
    # 16 experts, 4 a token, 8 held
    "qwen3_next_tiny": dict(
        hidden_size=64, num_layers=4, full_attention_interval=4,
        num_heads=4, num_kv_heads=2, attn_head_dim=16,
        partial_rotary_factor=0.5, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=16,
        linear_value_head_dim=16, linear_conv_kernel_dim=4, num_experts=16,
        num_experts_per_tok=4, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, experts_held=(0, 8),
        vocab_size=256, max_seq_len=128),
}

#: the parameters that are not born zero or Normal: the delta rule's
#: gated output norm is a plain weight, and a ``dt_bias`` of one
_ONES = ("out_norm", "dt_bias")


def _layer_param_shapes(cfg, l):
    """Leaf name -> shape of layer ``l``'s parameters; matrices are
    (out, in) but the expert bank, stacked (held, in, out), and the
    convolution's taps, (tap, channel)."""
    h, e, i = cfg.hidden_size, cfg.num_experts, cfg.moe_intermediate_size
    held, s = cfg.experts_held[1], cfg.shared_expert_intermediate_size
    out = {"op_norm": (h,), "ffn_norm": (h,), "router": (e, h),
           "w_gate": (held, h, i), "w_up": (held, h, i),
           "w_down": (held, i, h), "shared_gate": (s, h),
           "shared_up": (s, h), "shared_down": (h, s),
           "shared_expert_gate": (1, h)}
    if cfg.is_linear(l):
        nv = cfg.linear_num_value_heads
        out.update(in_qkvz=(cfg.conv_dim + cfg.value_dim, h),
                   in_ba=(2 * nv, h),
                   conv=(cfg.linear_conv_kernel_dim, cfg.conv_dim),
                   A_log=(nv,), dt_bias=(nv,),
                   out_norm=(cfg.linear_value_head_dim,),
                   out_proj=(h, cfg.value_dim))
    else:
        hd = cfg.attn_head_dim
        out.update(q=(cfg.num_heads * 2 * hd, h), k=(cfg.num_kv_heads * hd, h),
                   v=(cfg.num_kv_heads * hd, h), o=(h, cfg.num_heads * hd),
                   q_norm=(hd,), k_norm=(hd,))
    return out


def _norm(x, w, eps):
    """``x / rms(x) * (1 + w)``, the sum in float32."""
    import jax.numpy as jnp

    return rms_norm(x, 1.0 + w.astype(jnp.float32), eps)


def _silu(x):
    import jax

    return x * jax.nn.sigmoid(x)


def _swiglu(u, gate, up, down):
    return (_silu(u @ gate.T) * (u @ up.T)) @ down.T


class Qwen3NextMath:
    """The layer mathematics, once."""

    def __init__(self, cfg):
        self.cfg = cfg

    # -- operators ------------------------------------------------------------
    def _rule_inputs(self, p, mixed, ba):
        """The convolved channels and the gate projections of any
        leading axes -> float32 (q, k, v, beta, g), a VALUE head each."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, kd = cfg.linear_key_head_dim, cfg.key_dim
        lead = mixed.shape[:-1]
        f32 = jnp.float32

        def unit(a):
            a = a.reshape(lead + (nk, dk)).astype(f32)
            a = a * jax.lax.rsqrt((a * a).sum(-1, keepdims=True) + L2_EPS)
            return jnp.repeat(a, nv // nk, axis=-2)

        q = unit(mixed[..., :kd]) * dk ** -0.5
        k = unit(mixed[..., kd:2 * kd])
        v = mixed[..., 2 * kd:].reshape(lead + (nv, -1)).astype(f32)
        beta = jax.nn.sigmoid(ba[..., :nv].astype(f32))
        g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
            ba[..., nv:].astype(f32) + p["dt_bias"].astype(f32))
        return q, k, v, beta, g

    def delta_rule(self, p, u, view):
        """The gated delta rule.  Whole sequences (a
        :class:`~.decoder.Causal` view): ``u`` (B, T, H) -> (y, (the
        convolution's input (B, T, channels), the state after each
        sequence's last live row)).  A step: ``u`` (S, H) against the
        slot's (ring, state) -> (y, (ring, state))."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        eps = cfg.norm_eps
        w = p["conv"].astype(u.dtype)                       # (taps, C)
        with jax.named_scope("delta_project"):
            qkvz = u @ p["in_qkvz"].T
            mixed, z = qkvz[..., :cfg.conv_dim], qkvz[..., cfg.conv_dim:]
            ba = u @ p["in_ba"].T
        if not isinstance(view, StepView):
            with jax.named_scope("delta_project"):
                conv = causal_conv(w, mixed)
            o, state = gated_delta.chunk_scan(
                *self._rule_inputs(p, conv, ba), live=view.live)
            kept = (mixed, state)
        else:
            ring, state = view.entry                        # (S, taps-1, C)
            with jax.named_scope("delta_project"):
                conv, ring = ring_conv(w, mixed, ring, view.pos, view.live)
            o, state = gated_delta.step(
                state, *self._rule_inputs(p, conv, ba), live=view.live,
                kernel=gated_delta.step_form(state.shape[1:])
                == "step_kernel")
            kept = (ring, state)
        with jax.named_scope("delta_project"):
            # the gated norm, a head: a plain weight, float32
            zf = z.reshape(o.shape).astype(jnp.float32)
            o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps) \
                * p["out_norm"].astype(jnp.float32) * _silu(zf)
            y = o.reshape(u.shape[:-1] + (-1,)).astype(u.dtype) \
                @ p["out_proj"].T
        return y, kept

    def attention(self, p, u, rope, view):
        """Gated GQA over a cache view: ``u`` (B, T, H), or a step's
        (S, H); ``rope`` the (cos, sin) rows of the call's positions
        over heads-major q and k -> (y, what the view kept)."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        hd, rd, eps = cfg.attn_head_dim, cfg.rotary_dim, cfg.norm_eps
        qg = split_heads(u @ p["q"].T, cfg.num_heads)       # (B, H, T, 2 hd)
        q = _norm(qg[..., :hd], p["q_norm"], eps)
        gate = qg[..., hd:]
        k = _norm(split_heads(u @ p["k"].T, cfg.num_kv_heads), p["k_norm"],
                  eps)
        v = split_heads(u @ p["v"].T, cfg.num_kv_heads)
        q, k = (jnp.concatenate([apply_rope(a[..., :rd], *rope), a[..., rd:]],
                                axis=-1) for a in (q, k))
        ctx, kept = view.attend(q, k, v)
        if u.ndim == 3:
            gate = gate.transpose(0, 2, 1, 3)               # beside ctx
        lead = u.shape[:-1]
        y = ctx.reshape(*lead, -1) * jax.nn.sigmoid(gate.reshape(*lead, -1))
        return y @ p["o"].T, kept

    def ffn(self, p, u, live=None):
        """The routed experts this replica holds plus the gated shared
        expert -> (y, rows each expert of the layer received).
        ``live``: the rows a request owns, the only ones counted."""
        import jax

        cfg = self.cfg
        lead = u.shape[:-1]
        with jax.named_scope("moe_ffn"):
            y, counts = routed_ffn(
                u.reshape(-1, u.shape[-1]), p["router"], p["w_gate"],
                p["w_up"], p["w_down"], cfg.num_experts_per_tok,
                score="softmax", renormalize=cfg.norm_topk_prob,
                experts_held=cfg.experts_held,
                live=None if live is None else live.reshape(-1))
        with jax.named_scope("shared_expert"):
            shared = _swiglu(u, p["shared_gate"], p["shared_up"],
                             p["shared_down"]) \
                * jax.nn.sigmoid(u @ p["shared_expert_gate"].T)
        return y.reshape(*lead, -1) + shared, counts

    # -- the layer ------------------------------------------------------------
    def layer(self, p, x, rope, view):
        """``(params, x, rope rows, cache view) -> (x, what the view
        kept, expert rows)``; the operator is the delta rule or the
        attention by what the layer's parameters are."""
        eps = self.cfg.norm_eps
        h = _norm(x, p["op_norm"], eps)
        if "in_qkvz" in p:
            y, kept = self.delta_rule(p, h, view)
        else:
            y, kept = self.attention(p, h, rope, view)
        x = x + y
        y, counts = self.ffn(p, _norm(x, p["ffn_norm"], eps), view.live)
        return x + y, kept, counts


class Qwen3NextLayer(HybridBlock):
    """One layer; its Parameters carry the leaf names of
    :func:`_layer_param_shapes` and its forward is
    :meth:`Qwen3NextMath.layer` over whole sequences."""

    def __init__(self, cfg, index, **kwargs):
        super().__init__(**kwargs)
        self._cfg, self._index = cfg, index
        self._names = sorted(_layer_param_shapes(cfg, index))
        with self.name_scope():
            for name, shape in _layer_param_shapes(cfg, index).items():
                init = "ones" if name in _ONES else "zeros" \
                    if name.endswith("norm") or name == "A_log" else None
                setattr(self, name,
                        self.params.get(name, shape=shape, init=init))

    def hybrid_forward(self, F, x, **params):
        from ..ops.registry import apply_op

        names, cfg = self._names, self._cfg
        t = x.shape[1]

        def _f(xr, *raw):
            cos, sin = rope_tables(t, cfg.head_dim, cfg.rope_theta)
            return Qwen3NextMath(cfg).layer(
                dict(zip(names, raw)), xr,
                (cos[None, None], sin[None, None]), Causal(t))[0]

        return apply_op(_f, x, *(params[n] for n in names),
                        name="qwen3_next_layer")


class _ZeroCentredNorm(HybridBlock):
    def __init__(self, hidden, eps, **kwargs):
        super().__init__(**kwargs)
        self._eps = eps
        with self.name_scope():
            self.weight = self.params.get("weight", shape=(hidden,),
                                          init="zeros")

    def hybrid_forward(self, F, x, weight):
        from ..ops.registry import apply_op

        return apply_op(lambda xr, wr: _norm(xr, wr, self._eps), x, weight,
                        name="zero_centred_rms_norm")


class Qwen3NextForCausalLM(HybridBlock):
    """Embedding, the layers, a final norm, the untied head; the forward
    returns logits (B, T, V)."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        with self.name_scope():
            self.embed_tokens = nn.Embedding(cfg.vocab_size,
                                             cfg.hidden_size,
                                             prefix="embed_")
            self.layers = nn.HybridSequential(prefix="layers_")
            for l in range(cfg.num_layers):
                self.layers.add(Qwen3NextLayer(cfg, l))
            self.norm = _ZeroCentredNorm(cfg.hidden_size, cfg.norm_eps,
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
        return Qwen3NextDecoder(self, max_len)


class Qwen3NextDecoder(PagedDecoder, Qwen3NextMath):
    """What the shared paged programs need of this family: the cache
    spec (a delta-rule layer two arrays a slot, an attention layer a K/V
    pool), the weights, :meth:`Qwen3NextMath.layer`, the logits and what
    prefill keeps of a delta-rule layer's sequence."""

    def cache_spec(self):
        cfg = self.cfg
        return CacheSpec(
            layers=tuple("state" if cfg.is_linear(l) else "kv"
                         for l in range(cfg.num_layers)),
            num_kv_heads=cfg.num_kv_heads, head_dim=cfg.attn_head_dim,
            state_arrays=cfg.state_arrays(),
            expert_layers=cfg.num_expert_layers,
            num_experts=cfg.num_experts)

    def expert_product(self, rows, dtype):
        cfg = self.cfg
        return expert_product(rows, cfg.num_experts_per_tok,
                              cfg.experts_held[1], cfg.hidden_size,
                              cfg.moe_intermediate_size, dtype)

    def linear_attention(self):
        """Which form a step's delta-rule layers take:
        ``"step_kernel"`` or ``"step_xla"``."""
        return gated_delta.step_form(self.cfg.state_arrays()[1][0])

    def _weights(self):
        net = self._net
        raw = lambda p: p.data()._data  # noqa: E731
        layers = [{n: raw(getattr(lr, n)) for n in lr._names}
                  for lr in net.layers]
        return dict(layers=layers, emb=raw(net.embed_tokens.weight),
                    norm=raw(net.norm.weight), head=raw(net.lm_head.weight))

    def _logits(self, w, x):
        return _norm(x, w["norm"], self.cfg.norm_eps) @ w["head"].T

    def _sequence_state(self, kept, t0):
        """A delta-rule layer's state of the TRUE length, from what its
        whole-sequence pass kept: the convolution's input at ``t0-3 ..
        t0-1`` laid out as the ring keeps it (row ``t % 3``), zeros
        where the prompt is shorter, never the padded end's; the
        recurrent state as the scan left it (padded rows do not move
        it)."""
        mixed, state = kept
        return ring_at_length(mixed, t0,
                              self.cfg.linear_conv_kernel_dim - 1), state


def qwen3_next_tiny(**overrides):
    kw = dict(QWEN3_NEXT_CONFIGS["qwen3_next_tiny"])
    kw.update(overrides)
    return Qwen3NextForCausalLM(Qwen3NextConfig(**kw))

