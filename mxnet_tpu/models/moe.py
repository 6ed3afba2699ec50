"""Mixture-of-Experts layer + expert parallelism (EP).

Reference: NONE — MoE is ABSENT in the reference (SURVEY §2.3 D9); this is
new TPU-native capability, built so a stock ``gluon.Trainer`` trains it and
``shard_moe`` adds expert parallelism over an ``ep`` mesh axis.

TPU-first design decisions:
- Expert weights are STACKED into single (E, ...) parameters, so the whole
  expert bank is one batched einsum on the MXU — not E small matmuls.  With
  ``shard_moe`` the expert axis is sharded over ``ep`` and GSPMD derives the
  token all-to-all (dispatch einsum) / all-reduce (combine einsum), the same
  way psum is derived for dp.
- Routing is FIXED-CAPACITY (dispatch/combine tensors of static shape
  (N, E, C)); overflow tokens are dropped from the expert path (standard
  Switch/GShard semantics) and pass through the residual stream.  Dynamic
  per-expert token counts would not compile for the MXU.
- Two routers:
  * ``topk`` — tokens pick experts (GShard/Mixtral style, k experts per
    token, gates renormalised over the chosen k); needs the load-balancing
    auxiliary loss to avoid collapse (see ``collect_aux``).
  * ``expert_choice`` — experts pick tokens (top-C over the token axis);
    perfectly load-balanced by construction, no aux loss needed.  CAVEAT
    for causal decoders: expert assignment of token t depends on the
    top-C competition against LATER tokens, so training sees (weak)
    future information that autoregressive inference won't have — the
    known expert-choice-in-decoder train/inference mismatch.  Prefer
    ``topk`` for production causal-LM training; expert_choice is ideal
    for encoders and fine for routing-plumbing tests/dryruns.
- Router math runs in float32 regardless of activation dtype (bf16 routing
  logits are a known training-instability source).
"""
from __future__ import annotations

import math

from ..base import MXNetError
from ..gluon.block import HybridBlock
from ..ops import grouped_ffn

__all__ = ["MoEMLP", "collect_aux", "shard_moe", "route", "routed_ffn",
           "expert_product", "swiglu", "expert_layer_ffn"]


# --- aux-loss collection ----------------------------------------------------

# thread-local, matching autograd._AGState / parallel._STATE: concurrent
# per-thread training must not share a sink
import threading as _threading

_TLS = _threading.local()


def _sink():
    return getattr(_TLS, "aux_sink", None)


class collect_aux:
    """Collect per-layer load-balancing losses during an EAGER forward::

        with moe.collect_aux() as aux:
            logits = net(x)                       # not hybridized
            loss = ce(logits, y) + 0.01 * sum(aux)

    Each entry is a tape-connected scalar NDArray (an extra output of the
    MoE op), so ``backward()`` trains the router through it.  Under
    ``hybridize()`` tracing this raises: traced values can't escape the
    compiled graph — train un-hybridized when using the topk router with
    aux loss, or use router="expert_choice" (needs no aux loss).
    """

    def __enter__(self):
        self._prev = _sink()
        _TLS.aux_sink = []
        return _TLS.aux_sink

    def __exit__(self, *exc):
        _TLS.aux_sink = self._prev
        return False


class MoEMLP(HybridBlock):
    """Sparse SwiGLU feed-forward: each token is processed by k of E
    experts, outputs combined with the (renormalised) router gates.

    Drop-in replacement for a dense SwiGLU MLP of the same
    hidden/intermediate sizes (e.g. ``models.llama.LlamaMLP``).
    """

    def __init__(self, hidden_size, intermediate_size, num_experts,
                 num_experts_per_tok=2, capacity_factor=1.25,
                 router="topk", **kwargs):
        super().__init__(**kwargs)
        if router not in ("topk", "expert_choice"):
            raise MXNetError(f"unknown MoE router {router!r}")
        if num_experts_per_tok > num_experts:
            raise MXNetError("num_experts_per_tok must be <= num_experts")
        self._h = hidden_size
        self._i = intermediate_size
        self._e = num_experts
        self._k = num_experts_per_tok
        self._cf = capacity_factor
        self._router = router
        with self.name_scope():
            self.router_weight = self.params.get(
                "router_weight", shape=(num_experts, hidden_size))
            self.gate_weight = self.params.get(
                "gate_weight",
                shape=(num_experts, intermediate_size, hidden_size))
            self.up_weight = self.params.get(
                "up_weight",
                shape=(num_experts, intermediate_size, hidden_size))
            self.down_weight = self.params.get(
                "down_weight",
                shape=(num_experts, hidden_size, intermediate_size))

    def _capacity(self, n):
        return max(1, int(math.ceil(n * self._k * self._cf / self._e)))

    def hybrid_forward(self, F, x, router_weight, gate_weight, up_weight,
                       down_weight):
        from ..ops.registry import apply_op

        e, k, router = self._e, self._k, self._router
        cap_of = self._capacity

        def _f(xr, rw, gw, uw, dw):
            import jax
            import jax.numpy as jnp
            from jax import lax

            b, t, h = xr.shape
            n = b * t
            c = min(cap_of(n), n)  # an expert can't hold > n tokens
            xt = xr.reshape(n, h)
            logits = xt.astype(jnp.float32) @ rw.astype(jnp.float32).T
            probs = jax.nn.softmax(logits, axis=-1)          # (N, E) f32

            if router == "expert_choice":
                # experts pick tokens: balanced by construction
                gates, idx = lax.top_k(probs.T, c)           # (E, C)
                disp = jax.nn.one_hot(idx, n, dtype=xr.dtype)  # (E, C, N)
                ein = jnp.einsum("ecn,nh->ech", disp, xt)
                out_e = _expert_ffn(ein, gw, uw, dw)
                y = jnp.einsum("ecn,ec,ech->nh", disp,
                               gates.astype(xr.dtype), out_e)
                aux = jnp.zeros((), jnp.float32)
            else:
                gates, idx = lax.top_k(probs, k)             # (N, k)
                gates = gates / gates.sum(-1, keepdims=True)
                disp = jnp.zeros((n, e, c), xr.dtype)
                comb = jnp.zeros((n, e, c), xr.dtype)
                counts = jnp.zeros((e,), jnp.int32)
                rows = jnp.arange(n)
                for s in range(k):  # k is tiny; unrolled at trace time
                    sel = idx[:, s]                           # (N,)
                    onehot = jax.nn.one_hot(sel, e, dtype=jnp.int32)
                    pos = (onehot * (jnp.cumsum(onehot, axis=0) - 1
                                     + counts[None, :])).sum(-1)
                    keep = (pos < c).astype(xr.dtype)
                    slot = jnp.clip(pos, 0, c - 1)
                    disp = disp.at[rows, sel, slot].add(keep)
                    comb = comb.at[rows, sel, slot].add(
                        keep * gates[:, s].astype(xr.dtype))
                    counts = counts + onehot.sum(0)
                ein = jnp.einsum("nec,nh->ech", disp, xt)
                out_e = _expert_ffn(ein, gw, uw, dw)
                y = jnp.einsum("nec,ech->nh", comb, out_e)
                # Switch-style load-balance loss: E * sum_e f_e * P_e
                frac = jax.nn.one_hot(idx[:, 0], e,
                                      dtype=jnp.float32).mean(0)
                aux = e * (frac * probs.mean(0)).sum()
            return y.reshape(b, t, h), aux

        y, aux = apply_op(_f, x, router_weight, gate_weight, up_weight,
                          down_weight, name="moe_mlp")
        sink = _sink()
        if sink is not None:
            import jax

            if isinstance(aux._data, jax.core.Tracer):
                raise MXNetError(
                    "collect_aux() cannot cross a hybridize() trace; train "
                    "un-hybridized with the topk router, or use "
                    "router='expert_choice' (no aux loss needed)")
            sink.append(aux)
        return y


def _expert_ffn(ein, gw, uw, dw):
    """SwiGLU over the stacked expert bank: ein (E, C, H) → (E, C, H).
    One batched einsum per projection — the MXU sees E-batched matmuls."""
    import jax
    import jax.numpy as jnp

    g = jnp.einsum("ech,eih->eci", ein, gw.astype(ein.dtype))
    u = jnp.einsum("ech,eih->eci", ein, uw.astype(ein.dtype))
    act = g * jax.nn.sigmoid(g) * u
    return jnp.einsum("eci,ehi->ech", act, dw.astype(ein.dtype))


#: most rows the ``every_expert`` form of :func:`routed_ffn` computes at
#: once: its ``(rows, held, width)`` intermediates are 134 MB each at 16
#: held experts of 2,048 in bfloat16; a call of more rows (whole
#: multiples: a prefill bucket of 8k to 32k) goes in chunks of this many
EVERY_EXPERT_ROWS = 2048


def route(x, router_w, k, score="softmax", choice_bias=None,
          renormalize=True, scale=1.0):
    """Top-``k`` routing over ALL experts, in float32: ``x`` (N, H),
    ``router_w`` (E, H) -> ``(idx (N, k) int32, weights (N, k) f32)``.
    ``score`` is ``softmax`` or ``sigmoid`` of the router logits;
    ``choice_bias`` (E,) is added for the CHOICE only (the weights are
    the scores without it); ``renormalize`` divides the chosen scores
    by their sum (+1e-6); ``scale`` multiplies them."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    if score not in ("softmax", "sigmoid"):
        raise MXNetError(f"unknown router score {score!r}")
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32).T
    s = jax.nn.sigmoid(logits) if score == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    pick = s if choice_bias is None \
        else s + choice_bias.astype(jnp.float32)
    _, idx = lax.top_k(pick, k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if renormalize:
        w = w / (w.sum(-1, keepdims=True) + 1e-6)
    return idx.astype(jnp.int32), w * jnp.float32(scale)


def routed_ffn(x, router_w, w_gate, w_up, w_down, k, score="softmax",
               choice_bias=None, renormalize=True, scale=1.0,
               experts_held=None, live=None, kind="swiglu", rows=None):
    """Dropless routed experts: every token reaches its ``k`` experts, no
    capacity, nothing dropped.  ``x`` (N, H); ``router_w`` (E, H') over
    ALL experts; the expert bank holds the contiguous range
    ``experts_held = (first, count)`` (default: all), stacked
    ``w_gate`` / ``w_up`` (count, H, I) and ``w_down`` (count, I, H),
    (in, out).  ``kind`` (static) is the expert: ``"swiglu"``,
    ``(silu(x W_g) * (x W_u)) W_d``, or ``"relu2"``, ``relu(x W_u)^2
    W_d``: two matrices, ``w_gate`` None.  ``rows`` (N, H'): what the
    experts compute where that is not what the router reads (a latent
    expert layer routes on the model's width and computes in a
    projection of it); the result then has ``rows``' width.

    Routes over all experts and returns the part of the result that the
    held experts give (what the absent ones would add is left out: the
    shares of a layer divided over chips add up to the uncut layer),
    and the rows each of the E experts received, ``(E,)`` int32.
    ``live`` (N,) bool names the rows that belong to a request (a
    served program also runs vacant slots and the padded end of a
    prompt): only those are counted, and a row no request owns may come
    back zero in either form (nobody reads it).

    Two forms, one meaning, chosen by :func:`expert_product` from the
    platform, the mesh and static shapes:

    * ``every_expert``: every held expert computes every row, weighted
      by a (N, count) combine matrix that is zero where the expert was
      not chosen (past ``EVERY_EXPERT_ROWS`` rows a call, in chunks of
      that many, a chunk without a live row skipped).  Where rows are
      few the bank has to be streamed whole anyway; the product is
      finished (92% of the v5e's bf16 peak at 512 rows) and turns bound
      by operations nobody asked for near 240 rows a call.  It stays on
      a CPU, under a mesh and below ``ops.grouped_ffn.GROUPED_MIN_ROWS``
      rows.
    * ``grouped_kernel``: ``ops.grouped_ffn.grouped_expert_ffn``, the
      (row, expert) pairs listed expert by expert, each touched expert
      computed on its own rows alone: whole experts streamed once where
      two fit VMEM, else walked in width tiles.  The rows cross between
      the tokens' order and the experts' in kernels, in one of two
      forms from static shapes (``ops.grouped_ffn.rows_form``): where
      every pair fits one window and the call's rows fit VMEM (a served
      step's hundreds of rows) the rows and their float32 sum stay in
      VMEM for the whole call and a visit takes and adds its own pairs'
      rows by their token (``grouped_expert_ffn_resident``); where the
      pairs exceed one window (a long prefill, a trainer's step, over a
      bank that holds a part of the router's experts) only the pairs
      held here are listed, gathered and computed, a window of as many
      as 128 MiB of float32 rows hold at a time, dead rows left out,
      and a window's rows go back into the rows' order inside
      ``grouped_expert_ffn_rows`` (a tile of tokens in VMEM, its pairs
      added row by row in float32).  Same routing to the bit, dropless
      at any skew; float32 accumulation, weights and sum over a row's
      ``k``, so not lower than the other form anywhere, and not equal
      to it in the last bit.

    Both forms differentiate in the ``"swiglu"`` kind (a ``"relu2"`` bank
    has no backward yet and raises by name when differentiated, in
    either form alike): ``every_expert`` through XLA, the kernel
    through its ``jax.custom_vjp`` (``grouped_expert_ffn_dx`` / ``_dw``,
    the pairs' dX through ``grouped_expert_ffn_rows``), so a trainer's
    step takes the form :func:`expert_product` names; the router learns
    through the combine weights.

    Measured on the v5e (PERF.md, PRs 31 and 33 served; trained, at
    16,384 rows, 16 of 256 experts of 768 held, ``tools/
    routed_ffn_bench.py --train``: 39.7 against 75.2 ms a layer forward
    and backward with its routing, PR 44; 25.9 since PR 46, of which the
    op alone is 6.2; before them rows sorted by expert through
    ``jax.lax.ragged_dot`` lost at every size, PRs 26 and 30)."""
    import jax
    import jax.numpy as jnp

    grouped_ffn.check_kind(kind, w_gate)
    e = router_w.shape[0]
    first, held = experts_held if experts_held is not None else (0, e)
    if w_up.shape[0] != held:
        raise MXNetError(f"expert bank holds {w_up.shape[0]} experts, "
                         f"experts_held says {held}")
    idx, w = route(x, router_w, k, score, choice_bias, renormalize, scale)
    x = (x if rows is None else rows).astype(w_up.dtype)
    n, h = x.shape
    ones = jnp.ones((n,), jnp.int32) if live is None \
        else live.astype(jnp.int32)
    counts = jnp.zeros((e,), jnp.int32).at[idx].add(ones[:, None])
    if kind == "relu2":
        x = _no_backward(x, "routed_ffn's \"relu2\" experts")
    if expert_product(n, k, held, h, w_up.shape[2],
                      w_up.dtype) == "grouped_kernel":
        return grouped_ffn.grouped_expert_ffn(
            x, idx - first, w, w_gate, w_up, w_down, live, kind=kind), counts
    comb = jnp.zeros((n, e), jnp.float32) \
        .at[jnp.arange(n)[:, None], idx].add(w)
    comb = comb[:, first:first + held]

    def every_expert(x, comb):
        if kind == "relu2":
            act = jnp.square(jax.nn.relu(jnp.einsum("nh,ehi->nei", x, w_up)))
        else:
            g = jnp.einsum("nh,ehi->nei", x, w_gate)
            u = jnp.einsum("nh,ehi->nei", x, w_up)
            act = g * jax.nn.sigmoid(g) * u
        act = act * comb.astype(x.dtype)[:, :, None]
        return jnp.einsum("nei,eih->nh", act, w_down)

    if n <= EVERY_EXPERT_ROWS or n % EVERY_EXPERT_ROWS:
        return every_expert(x, comb), counts
    # a long prefill: the (rows, held, width) intermediates a chunk of
    # rows at a time, so that they are never whole; a chunk that holds no
    # live row (the padded end of a bucket) is zeros, not computed
    def chunk(c):
        xc, cc, any_live = c
        return jax.lax.cond(any_live, every_expert,
                            lambda xc, cc: jnp.zeros_like(xc), xc, cc)

    y = jax.lax.map(chunk, (x.reshape(-1, EVERY_EXPERT_ROWS, h),
                            comb.reshape(-1, EVERY_EXPERT_ROWS, held),
                            ones.reshape(-1, EVERY_EXPERT_ROWS).any(axis=1)))
    return y.reshape(n, -1), counts


def _no_backward(x, what):
    """``x`` as it is; differentiating through it raises an
    :class:`MXNetError` that names ``what`` (on every platform alike:
    which form of a product runs is not the trainer's to know)."""
    import jax

    @jax.custom_vjp
    def same(x):
        return x

    def bwd(_res, _dy):
        raise MXNetError(f"{what} have no backward yet: nothing trains "
                         "through them (ROADMAP M2)")

    same.defvjp(lambda x: (x, None), bwd)
    return same(x)


def swiglu(u, gate, up, down):
    """A dense SwiGLU, matrices (out, in)."""
    import jax

    g = u @ gate.T
    return (g * jax.nn.sigmoid(g) * (u @ up.T)) @ down.T


def expert_layer_ffn(p, u, k, score="softmax", renormalize=True, scale=1.0,
                     experts_held=None, live=None):
    """A layer's feed-forward over its leaves ``p``, written once for
    the served and the trained models: a dense SwiGLU (``gate``, ``up``,
    ``down``) where ``p`` has no ``router``; else :func:`routed_ffn` over
    the held part of the bank (``router``, ``expert_bias`` the choice
    bias, ``w_gate``, ``w_up``, ``w_down``) plus the shared expert
    (``shared_gate``, ``shared_up``, ``shared_down``), a SwiGLU every row
    takes, counted once -> (y, rows each expert of the layer received or
    None).  ``u`` (.., H); ``live`` (..) the rows a request owns, the
    only ones counted."""
    import jax

    if "router" not in p:
        return swiglu(u, p["gate"], p["up"], p["down"]), None
    lead = u.shape[:-1]
    with jax.named_scope("moe_ffn"):
        y, counts = routed_ffn(
            u.reshape(-1, u.shape[-1]), p["router"], p["w_gate"],
            p["w_up"], p["w_down"], k, score=score,
            choice_bias=p["expert_bias"], renormalize=renormalize,
            scale=scale, experts_held=experts_held,
            live=None if live is None else live.reshape(-1))
    with jax.named_scope("shared_expert"):
        shared = swiglu(u, p["shared_gate"], p["shared_up"],
                        p["shared_down"])
    return y.reshape(*lead, -1) + shared, counts


def expert_product(rows, k, held, hidden, width, dtype):
    """Which form :func:`routed_ffn` evaluates ``rows`` rows a call in,
    here and now: ``"grouped_kernel"`` or ``"every_expert"``, from the
    platform programs are compiled for, the active mesh and the static
    shapes (``ops.grouped_ffn.applicable``).  The served programs'
    ``expert_product`` counter asks the same question of the same
    function."""
    import jax
    import numpy as np

    from .. import parallel

    ok = grouped_ffn.applicable(
        jax.default_backend(), parallel.current_mesh(), rows, k, held,
        hidden, width, np.dtype(dtype).itemsize)
    return "grouped_kernel" if ok else "every_expert"


def moe_param_specs(block, ep_axis="ep", tp_axis=None):
    """{param: partition-spec tuple} for an MoE block — the rule table
    :func:`shard_moe` applies, reusable against abstract shapes (the 8B
    lowering proof).  Pass ``ep_axis``/``tp_axis`` as None when absent
    from the target mesh."""
    ep, tp = ep_axis, tp_axis
    return {
        block.router_weight: (None, None),
        block.gate_weight: (ep, tp, None),
        block.up_weight: (ep, tp, None),
        block.down_weight: (ep, None, tp),
    }


def shard_moe(block, mesh=None, ep_axis="ep", tp_axis=None):
    """Expert parallelism: shard the stacked expert bank over ``ep_axis``
    (optionally tensor-parallel within each expert over ``tp_axis``).
    Either axis may be absent from the mesh — a dp×tp mesh still gets the
    experts tp-sharded (the expert bank dominates MoE parameter memory).
    GSPMD derives the token all-to-all from the dispatch/combine einsums —
    the TPU-native analog of hand-written MoE a2a kernels."""
    from .. import parallel

    mesh = mesh or parallel.current_mesh()
    if mesh is None:
        return block
    ep = ep_axis if (ep_axis and ep_axis in mesh.shape) else None
    tp = tp_axis if (tp_axis and tp_axis in mesh.shape) else None
    if ep is None and tp is None:
        return block
    for p, spec in moe_param_specs(block, ep_axis=ep,
                                   tp_axis=tp).items():
        parallel.shard_param(p, spec, mesh)
    return block
