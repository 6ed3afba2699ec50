"""Ouro family (``model_type`` ``ouro``, the LoopLM report's looped
decoder): one stack of layers run ``total_ut_steps`` times a token.

Reference: NONE (the reference predates it).  Layer equations, with
``N_*`` an RMSNorm with a learned weight and no biases in the layer:

* layer ``l``, pass ``t`` ("sandwich" normalisation: a norm before AND
  after each sublayer): ``a = N_in(h)``; ``q, k, v = a W_q, a W_k, a
  W_v`` (plain multi-head attention: as many KV heads as query heads);
  RoPE over the whole head on q and k at the token's position; k, v go
  to **cache (t, l)**; ``o = softmax(q k^T / sqrt(hd)) v`` over the rows
  of cache (t, l) up to the position, float32 softmax; ``h = h +
  N_in2(o W_o)``; ``m = N_post(h)``; ``h = h + N_post2((silu(m W_gate)
  * (m W_up)) W_down)``;
* model: ``h = E[ids]``; for ``t`` in ``0 .. total_ut_steps - 1``: ``h``
  through every layer **with the same weights every pass**, then ``h =
  N_final(h)``: the final norm runs after EVERY pass and a pass's
  output feeds the next.  Logits are the untied head over the last
  pass's output;
* the exit gate (``early_exit_gate``: hidden -> 1, with a bias) gives a
  probability of stopping after each pass; generation leaves the loop
  where the cumulative probability reaches ``early_exit_threshold``.
  At the published threshold of 1 it cannot before the last pass, so
  the forward here runs every pass for every token and never evaluates
  the gate: its parameters are in the net (a checkpoint has them), and
  a threshold under 1 is refused by name.  No pass is skipped and no
  cache is shared between passes.

One definition of the mathematics: :meth:`OuroMath.layer` ``(params, x,
rope rows, cache view) -> (x, what the view kept, None)`` is what the
Gluon blocks' ``hybrid_forward`` runs over a whole sequence and what
the paged programs that :class:`OuroDecoder` inherits run against the
paged cache, ``CacheSpec.passes`` times over (``models.decoder``: the
loop over passes is one loop on the device, a pass's rows in its own
part of each layer's pool).
"""
from __future__ import annotations

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from .decoder import (CacheSpec, Causal, PagedDecoder, apply_rope, rms_norm,
                      rope_tables, split_heads)
from .llama import RMSNorm

__all__ = ["OuroConfig", "OuroLayer", "OuroForCausalLM", "OuroMath",
           "OuroDecoder", "ouro_tiny", "OURO_CONFIGS"]


class OuroConfig:
    def __init__(self, hidden_size=2048, intermediate_size=5632,
                 num_layers=48, num_heads=16, num_kv_heads=16, head_dim=128,
                 vocab_size=49152, max_seq_len=65536, rope_theta=1e6,
                 norm_eps=1e-6, total_ut_steps=4, early_exit_threshold=1.0):
        if num_heads % num_kv_heads:
            raise MXNetError("num_kv_heads must divide the heads")
        if total_ut_steps < 1:
            raise MXNetError("total_ut_steps must be at least 1")
        if early_exit_threshold < 1:
            raise MXNetError(
                f"early_exit_threshold {early_exit_threshold} < 1 lets a "
                "token leave the loop before its last pass, so the slots "
                "of a step would stand at different passes: this forward "
                "runs every pass for every token and serves the "
                "published threshold of 1 only")
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.vocab_size = vocab_size
        self.max_seq_len = max_seq_len
        self.rope_theta = rope_theta
        self.norm_eps = norm_eps
        self.total_ut_steps = total_ut_steps
        self.early_exit_threshold = early_exit_threshold
        self.tie_embeddings = False


OURO_CONFIGS = {
    # hidden 64, 3 layers run 3 times, 4 query and 4 KV heads of 16
    "ouro_tiny": dict(
        hidden_size=64, intermediate_size=176, num_layers=3, num_heads=4,
        num_kv_heads=4, head_dim=16, vocab_size=256, max_seq_len=128,
        total_ut_steps=3),
}


def _layer_param_shapes(cfg):
    """Leaf name -> shape of a layer's parameters; matrices are (out,
    in)."""
    h, f, hd = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    return {"ln_in": (h,), "ln_in2": (h,), "ln_post": (h,), "ln_post2": (h,),
            "q": (cfg.num_heads * hd, h), "k": (cfg.num_kv_heads * hd, h),
            "v": (cfg.num_kv_heads * hd, h), "o": (h, cfg.num_heads * hd),
            "gate": (f, h), "up": (f, h), "down": (h, f)}


class OuroMath:
    """The layer mathematics, once."""

    def __init__(self, cfg):
        self.cfg = cfg

    def layer(self, p, x, rope, view):
        """``(params, x, rope rows, cache view) -> (x, what the view
        kept, None: no expert rows)``.  ``x`` (B, T, H) over a whole
        sequence, or a step's (S, H)."""
        import jax

        cfg, eps = self.cfg, self.cfg.norm_eps
        a = rms_norm(x, p["ln_in"], eps)
        q = split_heads(a @ p["q"].T, cfg.num_heads)
        k = split_heads(a @ p["k"].T, cfg.num_kv_heads)
        v = split_heads(a @ p["v"].T, cfg.num_kv_heads)
        q, k = apply_rope(q, *rope), apply_rope(k, *rope)
        ctx, kept = view.attend(q, k, v)
        y = ctx.reshape(*x.shape[:-1], -1) @ p["o"].T
        x = x + rms_norm(y, p["ln_in2"], eps)
        m = rms_norm(x, p["ln_post"], eps)
        y = (jax.nn.silu(m @ p["gate"].T) * (m @ p["up"].T)) @ p["down"].T
        return x + rms_norm(y, p["ln_post2"], eps), kept, None


class OuroLayer(HybridBlock):
    """One layer; its Parameters carry the leaf names of
    :func:`_layer_param_shapes` and its forward is
    :meth:`OuroMath.layer` over whole sequences under the causal
    mask."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        self._names = sorted(_layer_param_shapes(cfg))
        with self.name_scope():
            for name, shape in _layer_param_shapes(cfg).items():
                init = "ones" if name.startswith("ln_") else None
                setattr(self, name,
                        self.params.get(name, shape=shape, init=init))

    def hybrid_forward(self, F, x, **params):
        from ..ops.registry import apply_op

        names, cfg = self._names, self._cfg
        t = x.shape[1]

        def _f(xr, *raw):
            cos, sin = rope_tables(t, cfg.head_dim, cfg.rope_theta)
            return OuroMath(cfg).layer(
                dict(zip(names, raw)), xr,
                (cos[None, None], sin[None, None]), Causal(t))[0]

        return apply_op(_f, x, *(params[n] for n in names),
                        name="ouro_layer")


class OuroForCausalLM(HybridBlock):
    """Embedding, the layers run ``total_ut_steps`` times with the final
    RMSNorm after every pass, the untied head; the exit gate's
    parameters, which the forward never reads (this module's
    docstring).  The forward returns logits (B, T, V)."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        with self.name_scope():
            self.embed_tokens = nn.Embedding(cfg.vocab_size,
                                             cfg.hidden_size,
                                             prefix="embed_")
            self.layers = nn.HybridSequential(prefix="layers_")
            for _ in range(cfg.num_layers):
                self.layers.add(OuroLayer(cfg))
            self.norm = RMSNorm(cfg.hidden_size, cfg.norm_eps,
                                prefix="norm_")
            self.early_exit_gate = nn.Dense(1, flatten=False,
                                            in_units=cfg.hidden_size,
                                            prefix="early_exit_gate_")
            self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False,
                                    flatten=False,
                                    in_units=cfg.hidden_size,
                                    prefix="lm_head_")

    @property
    def config(self):
        return self._cfg

    def hybrid_forward(self, F, input_ids):
        h = self.embed_tokens(input_ids)
        for _ in range(self._cfg.total_ut_steps):
            for layer in self.layers:
                h = layer(h)
            h = self.norm(h)
        return self.lm_head(h)

    def serving_decoder(self, max_len):
        """What ``GenerativeServer``'s engine asks a model for."""
        return OuroDecoder(self, max_len)


class OuroDecoder(PagedDecoder, OuroMath):
    """What the shared paged programs need of this family: the cache
    spec, which says how many times the stack runs, the weights,
    :meth:`OuroMath.layer`, what ends a pass and the logits."""

    def cache_spec(self):
        cfg = self.cfg
        return CacheSpec(("kv",) * cfg.num_layers, cfg.num_kv_heads,
                         cfg.head_dim, passes=cfg.total_ut_steps)

    def _weights(self):
        net = self._net
        raw = lambda p: p.data()._data  # noqa: E731
        layers = [{n: raw(getattr(lr, n)) for n in lr._names}
                  for lr in net.layers]
        return dict(layers=layers, emb=raw(net.embed_tokens.weight),
                    norm=raw(net.norm.weight), head=raw(net.lm_head.weight))

    def end_pass(self, w, x):
        """The final norm, after every pass."""
        return rms_norm(x, w["norm"], self.cfg.norm_eps)

    def _logits(self, w, x):
        return x @ w["head"].T


def ouro_tiny(**overrides):
    kw = dict(OURO_CONFIGS["ouro_tiny"])
    kw.update(overrides)
    return OuroForCausalLM(OuroConfig(**kw))
