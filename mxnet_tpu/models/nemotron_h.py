"""Nemotron-H family (``model_type`` ``nemotron_h``; Nemotron 3 Super):
Mamba-2 state-space mixers, plain grouped-query attention and latent
routed experts, a layer being ONE of them as the letters of
``hybrid_override_pattern`` say (``M``, ``*``, ``E``).

Reference: NONE (the reference predates it).  Layer equations, pre-norm
residual, no bias but the convolution's: ``x <- x + f(N(x))`` with
``N(x) = w x / rms(x)`` (a PLAIN weight, ``norm_eps``), ``u = N(x)``, and
``f`` by the letter:

* ``M``, the Mamba-2 mixer: ``[z | xBC | dt] = u W_in``; ``xBC`` passes a
  causal depthwise convolution of ``conv_kernel`` taps with a bias, then
  SiLU; ``[x | B | C] = xBC`` (``mamba_num_heads`` heads of
  ``mamba_head_dim``; ``B``, ``C``: ``n_groups`` rows of
  ``ssm_state_size``, a group serving consecutive heads); a head, in
  float32: ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` and the
  recurrence of :mod:`mxnet_tpu.ops.ssm_scan`; the gated group norm ``y
  <- w_n (y SiLU(z)) / rms_group(y SiLU(z))`` over each of the
  ``n_groups`` groups of channels (gate first, then the norm); ``W_out``.
  A cache keeps, a slot, the heads' float32 matrices and the
  convolution's last ``taps - 1`` inputs;
* ``*``, attention: ``q``, ``k``, ``v``, causal softmax at
  ``head_dim^-1/2``, ``W_o``.  NO rotary embedding (the family takes its
  positions from the state-space layers), no head norm, no gate.  A cache
  keeps ``k``, ``v``;
* ``E``, latent experts: the router reads the FULL-width rows
  (:func:`mxnet_tpu.models.moe.route`: sigmoid scores over ALL
  ``num_experts``, a choice bias, the chosen renormalised and scaled);
  ``l = u W_dn`` into ``moe_latent_size``; the held experts' two-matrix
  products ``relu(l W1_e)^2 W2_e`` are summed IN THE LATENT SPACE
  (:func:`mxnet_tpu.models.moe.routed_ffn`, kind ``"relu2"``, over
  ``experts_held``), ``W_up`` takes the sum back, and the shared expert
  ``relu(u V1)^2 V2`` at the model's width is added, counted once;
* model: embedding, the layers in the pattern's order, a final ``N``, an
  untied head.  The multi-token-prediction module of the checkpoint is
  not in the forward.

A served layer (a cache-spec layer, a Gluon block, one dict of leaves) is
a MIXER AND THE EXPERT PART THAT FOLLOWS IT, IF ONE DOES (``ME`` / ``M`` /
``*E``): the pattern never starts with ``E`` and never has two in a row,
so every letter lies in exactly one, and no layer keeps nothing.  The
leaves a layer holds say what it is (``in_proj``: a mixer; ``router``: an
expert part follows).  One definition of the mathematics:
:meth:`NemotronHMath.layer` is what the Gluon blocks run over whole
sequences and what the paged programs :class:`NemotronHDecoder` inherits
run: the prefill scans a mixer in chunks (``ops.ssm_scan.chunk_scan``),
exact at the true length inside a padded bucket; a step advances every
slot's state by one token (``ops.ssm_scan.step``).  A mixer's cache entry
is TWO arrays a slot: the convolution's ring in the weights' dtype and
the recurrent state, float32, in the layout ``ops.ssm_scan`` stores.
"""
from __future__ import annotations

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ops import ssm_scan
from .decoder import (CacheSpec, Causal, PagedDecoder, StepView, causal_conv,
                      ring_at_length, ring_conv, rms_norm, split_heads)
from .moe import expert_product, routed_ffn

__all__ = ["NemotronHConfig", "NemotronHLayer", "NemotronHForCausalLM",
           "NemotronHMath", "NemotronHDecoder", "nemotron_h_tiny",
           "NEMOTRON_H_CONFIGS"]


class NemotronHConfig:
    def __init__(self, hidden_size=4096,
                 pattern="MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*E"
                         "MEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
                 mamba_num_heads=128, mamba_head_dim=64, ssm_state_size=128,
                 n_groups=8, conv_kernel=4, num_heads=32, num_kv_heads=2,
                 attn_head_dim=128, num_experts=512, num_experts_per_tok=22,
                 moe_latent_size=1024, moe_intermediate_size=2688,
                 shared_expert_intermediate_size=5376,
                 routed_scaling_factor=5.0, norm_topk_prob=True,
                 experts_held=None, vocab_size=131072, max_seq_len=262144,
                 norm_eps=1e-5):
        first, held = experts_held or (0, num_experts)
        if not (0 <= first and held >= 1 and first + held <= num_experts):
            raise MXNetError(f"experts_held {experts_held} is not a range "
                             f"of the {num_experts} experts")
        if num_heads % num_kv_heads or mamba_num_heads % n_groups:
            raise MXNetError("key heads must divide the heads they serve, "
                             "and groups the state-space heads")
        if not pattern or set(pattern) - set("M*E") or pattern[0] == "E" \
                or "EE" in pattern:
            raise MXNetError(
                f"pattern {pattern!r}: a served layer is a mixer (M, *) and "
                "the expert part (E) that follows it, if one does; a "
                "pattern that starts with E, has two in a row or another "
                "letter has no such reading")
        self.hidden_size = hidden_size
        self.pattern = pattern
        #: the served layers: (the mixer's letter, whether an E follows)
        self.units = tuple((m, pattern[i + 1:i + 2] == "E")
                           for i, m in enumerate(pattern) if m != "E")
        self.num_layers = len(self.units)
        self.mamba_num_heads = mamba_num_heads
        self.mamba_head_dim = mamba_head_dim
        self.ssm_state_size = ssm_state_size
        self.n_groups = n_groups
        self.conv_kernel = conv_kernel
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.attn_head_dim = attn_head_dim
        #: the router's width: every expert of a layer, held or not
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_latent_size = moe_latent_size
        self.moe_intermediate_size = moe_intermediate_size
        self.shared_expert_intermediate_size = shared_expert_intermediate_size
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = norm_topk_prob
        #: (first, count): the contiguous part of each layer's bank that
        #: this replica holds; the rest lie on other chips
        self.experts_held = (int(first), int(held))
        self.vocab_size = vocab_size
        self.max_seq_len = max_seq_len
        self.norm_eps = norm_eps
        self.tie_embeddings = False
        #: nothing rotates; the shared programs build their tables from
        #: these two and this family's layers never read them
        self.head_dim, self.rope_theta = attn_head_dim, 1e4

    @property
    def d_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        """Channels through the convolution: x, B and C."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def num_expert_layers(self):
        return sum(e for _m, e in self.units)

    @property
    def ssm_shape(self):
        """What ``ops.ssm_scan`` asks of a mixer: (heads, head_dim,
        state_size, groups)."""
        return (self.mamba_num_heads, self.mamba_head_dim,
                self.ssm_state_size, self.n_groups)

    def state_arrays(self):
        """A mixer's arrays a slot: ((shape, dtype), ...); dtype None is
        the weights'.  The recurrent state as ``ops.ssm_scan`` stores
        it."""
        return (((self.conv_kernel - 1, self.conv_dim), None),
                (ssm_scan.state_shape(*self.ssm_shape), "float32"))


NEMOTRON_H_CONFIGS = {
    # hidden 64, pattern MEM*E (served layers ME, M, *E): 4 state-space
    # heads of 8 with a state of 16 in 2 groups, 4 / 2 attention heads of
    # 16, 16 experts of 32 in a latent 32, 4 a token, 8 held, shared 64
    "nemotron_h_tiny": dict(
        hidden_size=64, pattern="MEM*E", mamba_num_heads=4, mamba_head_dim=8,
        ssm_state_size=16, n_groups=2, conv_kernel=4, num_heads=4,
        num_kv_heads=2, attn_head_dim=16, num_experts=16,
        num_experts_per_tok=4, moe_latent_size=32, moe_intermediate_size=32,
        shared_expert_intermediate_size=64, experts_held=(0, 8),
        vocab_size=256, max_seq_len=128),
}

#: the parameters that are born one (plain norm weights, the skip ``D``)
#: or zero (biases, ``A_log``, ``dt_bias``), not Normal
_ONES = ("norm", "ffn_norm", "out_norm", "D")
_ZEROS = ("conv_bias", "expert_bias", "A_log", "dt_bias")


def _layer_param_shapes(cfg, unit):
    """Leaf name -> shape of a served layer's parameters, ``unit`` =
    (the mixer's letter, whether an expert part follows); matrices are
    (out, in) but the expert bank, stacked (held, in, out), and the
    convolution's taps, (tap, channel)."""
    h = cfg.hidden_size
    mixer, experts = unit
    out = {"norm": (h,)}
    if mixer == "M":
        nh = cfg.mamba_num_heads
        out.update(in_proj=(cfg.d_inner + cfg.conv_dim + nh, h),
                   conv=(cfg.conv_kernel, cfg.conv_dim),
                   conv_bias=(cfg.conv_dim,), A_log=(nh,), dt_bias=(nh,),
                   D=(nh,), out_norm=(cfg.d_inner,),
                   out_proj=(h, cfg.d_inner))
    else:
        hd = cfg.attn_head_dim
        out.update(q=(cfg.num_heads * hd, h), k=(cfg.num_kv_heads * hd, h),
                   v=(cfg.num_kv_heads * hd, h), o=(h, cfg.num_heads * hd))
    if experts:
        e, lat = cfg.num_experts, cfg.moe_latent_size
        i, s = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
        held = cfg.experts_held[1]
        out.update(ffn_norm=(h,), router=(e, h), expert_bias=(e,),
                   latent_down=(lat, h), latent_up=(h, lat),
                   w_up=(held, lat, i), w_down=(held, i, lat),
                   shared_up=(s, h), shared_down=(h, s))
    return out


def _relu2(u, up, down):
    """A dense two-matrix squared-ReLU feed-forward, matrices (out, in)."""
    import jax
    import jax.numpy as jnp

    return jnp.square(jax.nn.relu(u @ up.T)) @ down.T


class NemotronHMath:
    """The layer mathematics, once."""

    def __init__(self, cfg):
        self.cfg = cfg

    # -- mixers ---------------------------------------------------------------
    def _scan_inputs(self, p, conv, dt):
        """The convolved channels and the step projections of any
        leading axes -> float32 (x (.., H, P), dt (.., H), A (H,), B,
        C (.., G, N), D (H,))."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        nh, g, ns = cfg.mamba_num_heads, cfg.n_groups, cfg.ssm_state_size
        di, lead, f32 = cfg.d_inner, conv.shape[:-1], jnp.float32
        x = conv[..., :di].reshape(lead + (nh, -1)).astype(f32)
        B = conv[..., di:di + g * ns].reshape(lead + (g, ns)).astype(f32)
        C = conv[..., di + g * ns:].reshape(lead + (g, ns)).astype(f32)
        dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
        return (x, dt, -jnp.exp(p["A_log"].astype(f32)), B, C,
                p["D"].astype(f32))

    def mamba(self, p, u, view):
        """The Mamba-2 mixer.  Whole sequences (a
        :class:`~.decoder.Causal` view): ``u`` (B, T, H) -> (y, (the
        convolution's input (B, T, channels), the state after each
        sequence's last live row)).  A step: ``u`` (S, H) against the
        slot's (ring, state) -> (y, (ring, state))."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        di, nh = cfg.d_inner, cfg.mamba_num_heads
        w = p["conv"].astype(u.dtype)                       # (taps, C)
        bias = p["conv_bias"].astype(u.dtype)
        with jax.named_scope("ssm_project"):
            zxd = u @ p["in_proj"].T
            z, mixed, dt = zxd[..., :di], zxd[..., di:-nh], zxd[..., -nh:]
        if not isinstance(view, StepView):
            with jax.named_scope("ssm_project"):
                conv = causal_conv(w, mixed, bias)
            y, state = ssm_scan.chunk_scan(
                *self._scan_inputs(p, conv, dt), live=view.live)
            kept = (mixed, state)
        else:
            ring, state = view.entry                        # (S, taps-1, C)
            with jax.named_scope("ssm_project"):
                conv, ring = ring_conv(w, mixed, ring, view.pos, view.live,
                                       bias)
            y, state = ssm_scan.step(
                state, *self._scan_inputs(p, conv, dt), live=view.live,
                kernel=ssm_scan.step_form(*cfg.ssm_shape) == "step_kernel")
            kept = (ring, state)
        with jax.named_scope("ssm_project"):
            # the gated group norm: the gate first, then the norm over
            # each group's channels, a plain weight, float32
            lead = u.shape[:-1]
            zf = z.astype(jnp.float32)
            y = y.reshape(lead + (cfg.n_groups, -1)) \
                * (zf * jax.nn.sigmoid(zf)).reshape(lead + (cfg.n_groups, -1))
            y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True)
                                  + cfg.norm_eps)
            y = y.reshape(lead + (di,)) * p["out_norm"].astype(jnp.float32)
            y = y.astype(u.dtype) @ p["out_proj"].T
        return y, kept

    def attention(self, p, u, view):
        """Plain GQA over a cache view, nothing rotated: ``u`` (B, T,
        H), or a step's (S, H) -> (y, what the view kept)."""
        cfg = self.cfg
        q = split_heads(u @ p["q"].T, cfg.num_heads)
        k = split_heads(u @ p["k"].T, cfg.num_kv_heads)
        v = split_heads(u @ p["v"].T, cfg.num_kv_heads)
        ctx, kept = view.attend(q, k, v)
        return ctx.reshape(*u.shape[:-1], -1) @ p["o"].T, kept

    # -- the expert part ------------------------------------------------------
    def ffn(self, p, u, live=None):
        """The latent routed experts this replica holds plus the shared
        expert -> (y, rows each expert of the layer received).
        ``live``: the rows a request owns, the only ones counted."""
        import jax

        cfg = self.cfg
        lead = u.shape[:-1]
        rows = u.reshape(-1, u.shape[-1])
        with jax.named_scope("latent_project"):
            latent = rows @ p["latent_down"].T
        with jax.named_scope("moe_ffn"):
            y, counts = routed_ffn(
                rows, p["router"], None, p["w_up"], p["w_down"],
                cfg.num_experts_per_tok, score="sigmoid",
                choice_bias=p["expert_bias"],
                renormalize=cfg.norm_topk_prob,
                scale=cfg.routed_scaling_factor,
                experts_held=cfg.experts_held,
                live=None if live is None else live.reshape(-1),
                kind="relu2", rows=latent)
        with jax.named_scope("latent_project"):
            y = y @ p["latent_up"].T
        with jax.named_scope("shared_expert"):
            shared = _relu2(u, p["shared_up"], p["shared_down"])
        return y.reshape(*lead, -1) + shared, counts

    # -- the layer ------------------------------------------------------------
    def layer(self, p, x, rope, view):
        """``(params, x, rope rows (not read), cache view) -> (x, what
        the view kept, expert rows or None)``: the mixer the layer's
        leaves name and, where they hold a router, the expert part
        behind it."""
        eps = self.cfg.norm_eps
        u = rms_norm(x, p["norm"], eps)
        y, kept = self.mamba(p, u, view) if "in_proj" in p \
            else self.attention(p, u, view)
        x = x + y
        if "router" not in p:
            return x, kept, None
        y, counts = self.ffn(p, rms_norm(x, p["ffn_norm"], eps), view.live)
        return x + y, kept, counts


class NemotronHLayer(HybridBlock):
    """One served layer; its Parameters carry the leaf names of
    :func:`_layer_param_shapes` and its forward is
    :meth:`NemotronHMath.layer` over whole sequences."""

    def __init__(self, cfg, index, **kwargs):
        super().__init__(**kwargs)
        self._cfg, self._index = cfg, index
        shapes = _layer_param_shapes(cfg, cfg.units[index])
        self._names = sorted(shapes)
        with self.name_scope():
            for name, shape in shapes.items():
                init = "ones" if name in _ONES else "zeros" \
                    if name in _ZEROS else None
                setattr(self, name,
                        self.params.get(name, shape=shape, init=init))

    def hybrid_forward(self, F, x, **params):
        from ..ops.registry import apply_op

        names, cfg = self._names, self._cfg
        t = x.shape[1]

        def _f(xr, *raw):
            return NemotronHMath(cfg).layer(dict(zip(names, raw)), xr, None,
                                            Causal(t))[0]

        return apply_op(_f, x, *(params[n] for n in names),
                        name="nemotron_h_layer")


class _Norm(HybridBlock):
    def __init__(self, hidden, eps, **kwargs):
        super().__init__(**kwargs)
        self._eps = eps
        with self.name_scope():
            self.weight = self.params.get("weight", shape=(hidden,),
                                          init="ones")

    def hybrid_forward(self, F, x, weight):
        from ..ops.registry import apply_op

        return apply_op(lambda xr, wr: rms_norm(xr, wr, self._eps), x,
                        weight, name="rms_norm")


class NemotronHForCausalLM(HybridBlock):
    """Embedding, the served layers, a final norm, the untied head; the
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
                self.layers.add(NemotronHLayer(cfg, l))
            self.norm = _Norm(cfg.hidden_size, cfg.norm_eps, prefix="norm_")
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
        return NemotronHDecoder(self, max_len)


class NemotronHDecoder(PagedDecoder, NemotronHMath):
    """What the shared paged programs need of this family: the cache
    spec (a mixer two arrays a slot, an attention layer a K/V pool), the
    weights, :meth:`NemotronHMath.layer`, the logits and what prefill
    keeps of a mixer's sequence."""

    def cache_spec(self):
        cfg = self.cfg
        return CacheSpec(
            layers=tuple("state" if m == "M" else "kv"
                         for m, _e in cfg.units),
            num_kv_heads=cfg.num_kv_heads, head_dim=cfg.attn_head_dim,
            state_arrays=cfg.state_arrays(),
            expert_layers=cfg.num_expert_layers,
            num_experts=cfg.num_experts)

    def expert_product(self, rows, dtype):
        """Asked with the LATENT width: that is what the experts
        multiply."""
        cfg = self.cfg
        return expert_product(rows, cfg.num_experts_per_tok,
                              cfg.experts_held[1], cfg.moe_latent_size,
                              cfg.moe_intermediate_size, dtype)

    def linear_attention(self):
        """Which form a step's mixers take: ``"step_kernel"`` or
        ``"step_xla"``."""
        return ssm_scan.step_form(*self.cfg.ssm_shape)

    def _weights(self):
        net = self._net
        raw = lambda p: p.data()._data  # noqa: E731
        layers = [{n: raw(getattr(lr, n)) for n in lr._names}
                  for lr in net.layers]
        return dict(layers=layers, emb=raw(net.embed_tokens.weight),
                    norm=raw(net.norm.weight), head=raw(net.lm_head.weight))

    def _logits(self, w, x):
        return rms_norm(x, w["norm"], self.cfg.norm_eps) @ w["head"].T

    def _sequence_state(self, kept, t0):
        """A mixer's state of the TRUE length, from what its
        whole-sequence pass kept: the ring of the convolution's last
        inputs before ``t0`` (``decoder.ring_at_length``) and the
        recurrent state as the scan left it (padded rows do not move
        it), laid out as the pool stores it."""
        mixed, state = kept
        return (ring_at_length(mixed, t0, self.cfg.conv_kernel - 1),
                ssm_scan.to_stored(state, self.cfg.n_groups))


def nemotron_h_tiny(**overrides):
    kw = dict(NEMOTRON_H_CONFIGS["nemotron_h_tiny"])
    kw.update(overrides)
    return NemotronHForCausalLM(NemotronHConfig(**kw))
