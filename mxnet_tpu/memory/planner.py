"""Pre-dispatch HBM planner: predict per-device peak BEFORE compiling.

The planner answers "will this (model, batch, mesh, remat tier, offload
config) fit the device budget?" from three sources, cheapest first:

1. **analytic** — exact per-device byte math over the declared
   parameter shapes, sharded by the SAME partition-rule engine the real
   placement path uses (``parallel.partition``), plus optimizer-state /
   master-copy multipliers and a coarse tier-scaled activation model.
   Microseconds; no jax import on the hot path.
2. **registry** (warm signature) — when ``telemetry.costs`` holds a
   compiled artifact for this mesh (and remat tier, per the r10 stamp),
   its measured XLA ``temp_size_in_bytes`` replaces the analytic
   activation term.
3. **lowering** (cold, offline) — a real AOT lowering via
   :mod:`mxnet_tpu.memory.lowering` (the scale_proof engine), or a
   committed ``*_LOWER_*.json`` artifact read back through
   :func:`plan_from_artifact` when offline TPU lowering is unavailable
   (libtpu lockfile / CI).  XLA's own memory analysis is then the
   load-bearing number — this is how the Mixtral dp2 overflow
   (``MIXTRAL_DP2_OVERFLOW_r05.json``, 16.09 GiB on a 15.75 GiB
   budget) is rejected pre-compile today.

The verdict is a :class:`Plan`: fit / no-fit against the device budget
with headroom and the top offending buffers named.  ``annotate_oom``
turns the last plan into a prescription via :func:`prescribe`.
"""
import math
import os

import numpy as np

from .lowering import TPU_BUDGET_GIB

#: usable-HBM budgets by accelerator generation (GiB).  v5e is the
#: compiler-enforced figure from the committed TPU lowerings; the rest
#: follow the same usable-fraction convention.  Unknown device kinds
#: (CPU CI) fall back to 16 GiB so CPU-mesh plans stay comparable to
#: the historical scale_proof budget.
DEVICE_BUDGET_GIB = {
    "v5e": TPU_BUDGET_GIB,
    "v5p": 93.0,
    "v4": 31.0,
    "v6e": 31.25,
}
_DEFAULT_BUDGET_GIB = 16.0

_budget_override = None
_last_plan = None
_last_prescription = None


def set_budget(nbytes):
    """Override the device budget (tests shrink it to force the auto
    policy up the tier ladder).  ``None`` restores device detection."""
    global _budget_override
    _budget_override = None if nbytes is None else int(nbytes)


def budget_bytes(device_kind=None):
    """Per-device budget in bytes: explicit override >
    ``MXNET_HBM_BUDGET`` env > device-kind table > 16 GiB default."""
    if _budget_override is not None:
        return _budget_override
    env = os.environ.get("MXNET_HBM_BUDGET")
    if env:
        return int(float(env))
    if device_kind is None:
        try:
            from ..telemetry import costs

            device_kind = costs.device_kind() or ""
        except Exception:
            device_kind = ""
    kind = str(device_kind).lower()
    for key, gib in DEVICE_BUDGET_GIB.items():
        if key in kind:
            return int(gib * 2 ** 30)
    return int(_DEFAULT_BUDGET_GIB * 2 ** 30)


class Plan:
    """A pre-dispatch fit verdict for one configuration."""

    __slots__ = ("predicted_peak_bytes", "budget_bytes", "fits",
                 "headroom_bytes", "breakdown", "top_buffers", "source",
                 "remat", "offload", "ctx")

    def __init__(self, predicted_peak_bytes, budget, breakdown,
                 top_buffers, source, remat, offload, ctx=None):
        self.predicted_peak_bytes = int(predicted_peak_bytes)
        self.budget_bytes = int(budget)
        self.fits = self.predicted_peak_bytes <= self.budget_bytes
        self.headroom_bytes = self.budget_bytes - self.predicted_peak_bytes
        self.breakdown = dict(breakdown)
        self.top_buffers = list(top_buffers)
        self.source = source
        self.remat = remat
        self.offload = offload
        self.ctx = ctx or {}

    def as_dict(self):
        return {
            "predicted_peak_bytes": self.predicted_peak_bytes,
            "predicted_peak_gib": round(
                self.predicted_peak_bytes / 2 ** 30, 3),
            "budget_bytes": self.budget_bytes,
            "fits": self.fits,
            "headroom_bytes": self.headroom_bytes,
            "breakdown": self.breakdown,
            "top_buffers": self.top_buffers,
            "source": self.source,
            "remat": self.remat,
            "offload": self.offload,
        }

    def __repr__(self):
        gib = self.predicted_peak_bytes / 2 ** 30
        verdict = "fits" if self.fits else "NO FIT"
        return (f"Plan({verdict}: predicted {gib:.2f} GiB vs "
                f"{self.budget_bytes / 2 ** 30:.2f} GiB budget, "
                f"remat={self.remat!r}, offload={self.offload!r}, "
                f"source={self.source})")


def last_plan():
    return _last_plan


def last_prescription():
    return _last_prescription


def _normalize_params(params):
    """{name: (shape, dtype)} from a Block, a Parameter mapping, or a
    mapping of (shape, dtype) pairs."""
    if hasattr(params, "_collect_params_with_prefix"):
        params = params._collect_params_with_prefix()
    out = {}
    for name, p in dict(params).items():
        if isinstance(p, tuple) and len(p) == 2 and not hasattr(p, "shape"):
            shape, dtype = p
        else:
            shape, dtype = p.shape, getattr(p, "dtype", None)
        shape = tuple(int(s) for s in (shape or ()))
        assert shape and all(s > 0 for s in shape), \
            f"{name} shape not fully declared: {shape}"
        out[name] = (shape, np.dtype(dtype or np.float32))
    return out


_STATE_SLOTS = {"sgd": 1, "nag": 1, "sgld": 0, "adam": 2, "adamw": 2,
    "lamb": 2, "rmsprop": 1, "adagrad": 1, None: 0, "none": 0}


def _optimizer_desc(optimizer):
    """(name, n_state_slots, multi_precision) for a name, an Optimizer
    instance, or None (inference)."""
    if optimizer is None:
        return None, 0, False
    if isinstance(optimizer, str):
        name = optimizer.lower()
        return name, _STATE_SLOTS.get(name, 1), False
    name = type(optimizer).__name__.lower()
    n = _STATE_SLOTS.get(name, 1)
    if name in ("sgd", "nag") and not getattr(optimizer, "momentum", 0.0):
        n = 0
    return name, n, bool(getattr(optimizer, "multi_precision", False))


def _mesh_axis_sizes(mesh):
    if mesh is None:
        return {}
    shape = getattr(mesh, "shape", mesh)
    return {str(k): int(v) for k, v in dict(shape).items()}


def _shard_div(spec, axes):
    div = 1
    for entry in spec or ():
        if entry:
            for ax in (entry if isinstance(entry, (tuple, list))
                       else (entry,)):
                div *= axes.get(str(ax), 1)
    return div


#: coarse activation prior: live activation bytes per byte of
#: per-device batch input, by remat tier — a transformer-shaped default
#: used only when neither a measured ``activation_hint`` nor a warm
#: registry temp figure is available.
_ACT_MULT = {"none": 12.0, "dots": 4.0, "layer": 2.0}
#: how the tier ladder scales a measured tier-"none" activation figure
_ACT_SCALE = {"none": 1.0, "dots": 0.35, "layer": 0.15}


def _registry_workspace(axes, remat):
    """Measured XLA temp bytes for a warm signature on this mesh (and,
    when the artifact carries the r10 stamp, this remat tier)."""
    try:
        from ..telemetry import costs

        if not costs._enabled:
            return None
        best = None
        for art in costs.snapshot():
            if art.get("error"):
                continue
            mesh_shape = art.get("mesh_shape")
            if axes and mesh_shape and dict(mesh_shape) != axes:
                continue
            stamp = art.get("remat")
            if stamp is not None and stamp != remat:
                continue
            t = int(art.get("temp_bytes") or 0)
            if t and (best is None or t > best):
                best = t
        return best
    except Exception:
        return None


def plan_kv_pool(num_layers, num_kv_heads, head_dim, num_blocks,
                 block_size, dtype=np.float32, mesh=None, rules=None,
                 state_layers=0, state_shape=None, num_slots=0,
                 latent_layers=0, latent_dim=0, index_dim=0,
                 state_arrays=None, passes=1):
    """Per-device bytes of the serving engine's paged cache.  The block
    pool: 2 (K and V) × ``num_layers`` × ``passes`` (``CacheSpec.passes``:
    how many times the stack runs a token, each pass keeping its own
    rows) × ``num_blocks × num_kv_heads × block_size × head_dim`` ×
    itemsize, sharded the way the serving rule
    table places the pool (``layers.{i}.kv_pool`` — KV-head axis over
    ``tp`` by default); ``num_layers`` counts the layers that OWN a K/V
    pool (``CacheSpec.kv_layers``), not the model's depth.  Plus, for a
    model whose other layers keep a fixed per-slot state:
    ``state_layers × num_slots × prod(state_shape)`` × itemsize
    (unsharded); a layer that owns several arrays states them as
    ``state_arrays``, ``((shape, dtype), ...)``
    (``CacheSpec.state_arrays``), each priced by its own dtype (None:
    ``dtype``).  Plus, for the layers that keep latent rows and index
    keys in the same block tables (``CacheSpec.latent_layers``, with
    its ``latent_dim`` and ``index_dim``): ``latent_layers ×
    num_blocks`` × the bytes a block as ``ops.latent_cache`` stores it
    (rows padded to whole lanes; unsharded).  Plus, where the K/V
    layers select what a query reads (``CacheSpec.kv_selecting``: an
    ``index_dim`` and no latent layer to own it): ``num_layers ×
    num_blocks`` × the bytes a block of index keys as
    ``ops.sparse_select`` stores them.  This is the serving analog of the allreduce-bytes
    planning the trainer gets: size the cache BEFORE building the
    engine, and feed the figure to :func:`plan_model` via
    ``kv_pool_bytes=`` to get a fit verdict that includes serving
    state.  Matches ``LlamaServingEngine.kv_pool_bytes()`` exactly."""
    dtype = np.dtype(dtype)
    shape = (int(passes) * int(num_blocks), int(num_kv_heads),
             int(block_size), int(head_dim))
    div = 1
    if mesh is not None:
        from ..parallel import partition as pt

        axes = _mesh_axis_sizes(mesh)
        specs = pt.as_rules(rules if rules is not None
                            else "llama_serving").specs(
            {"layers.0.kv_pool": shape}, mesh)
        div = _shard_div(specs.get("layers.0.kv_pool"), axes)
    n_elem = int(np.prod(shape))
    state = 0
    if state_layers:
        if state_arrays is None:
            state_arrays = ((state_shape, None),)
        state = int(state_layers) * int(num_slots) * sum(
            int(np.prod(shape)) * np.dtype(sdt or dtype).itemsize
            for shape, sdt in state_arrays)
    latent = 0
    if latent_layers:
        from ..ops import latent_cache

        latent = int(latent_layers) * int(num_blocks) \
            * latent_cache.bytes_per_block(block_size, latent_dim,
                                           index_dim, dtype.itemsize)
    if index_dim and not latent_layers:
        from ..ops import sparse_select

        latent += int(num_layers) * int(num_blocks) \
            * sparse_select.index_bytes_per_block(block_size, index_dim,
                                                  dtype.itemsize)
    return 2 * int(num_layers) * _ceil_div(n_elem * dtype.itemsize, div) \
        + state + latent


def plan_model(params, mesh=None, rules=None, optimizer=None,
               batch_bytes=0, remat="none", offload=None,
               activation_hint=None, budget=None, device_kind=None,
               training=True, use_registry=True, record=True,
               kv_pool_bytes=0):
    """Analytic per-device peak for a model configuration.

    ``params``: a Block / Parameter mapping / ``{name: (shape, dtype)}``.
    ``batch_bytes``: GLOBAL per-step input bytes (divided over the dp
    axis).  ``activation_hint``: measured live-activation bytes at tier
    "none" (scaled down the ladder); otherwise a warm costs-registry
    temp figure or a coarse batch-proportional prior is used.
    ``offload="host"`` moves optimizer state + f32 masters off-device.
    ``kv_pool_bytes``: per-device serving KV pool (from
    :func:`plan_kv_pool`) held live for the server's lifetime.
    """
    from .policy import normalize

    remat = normalize(remat)
    if remat == "auto":
        raise ValueError("plan_model plans ONE tier; use policy.auto_tier")
    if offload not in (None, "host"):
        raise ValueError(f"unknown offload {offload!r}")
    shapes = _normalize_params(params)
    axes = _mesh_axis_sizes(mesh)
    opt_name, n_state, multi_precision = _optimizer_desc(optimizer)

    specs = {}
    if rules is not None and mesh is not None:
        from ..parallel import partition as pt

        specs = pt.as_rules(rules).specs(
            {n: s for n, (s, _) in shapes.items()}, mesh)

    per_param = {}
    params_b = grads_b = state_b = masters_b = 0
    for name, (shape, dtype) in shapes.items():
        n_elem = int(np.prod(shape))
        div = _shard_div(specs.get(name), axes)
        p_b = _ceil_div(n_elem * dtype.itemsize, div)
        contrib = {"params": p_b}
        params_b += p_b
        if training:
            grads_b += p_b
            contrib["grads"] = p_b
            low_p = dtype.name in ("float16", "bfloat16")
            state_dt = 4 if low_p else dtype.itemsize
            s_b = n_state * _ceil_div(n_elem * state_dt, div)
            m_b = (_ceil_div(n_elem * 4, div)
                   if (low_p and multi_precision) else 0)
            state_b += s_b
            masters_b += m_b
            if s_b:
                contrib["optimizer_state"] = s_b
            if m_b:
                contrib["masters"] = m_b
        per_param[name] = contrib

    dp = axes.get("dp", 1)
    batch_b = _ceil_div(int(batch_bytes), dp)

    source = "analytic"
    if activation_hint is not None:
        act_b = int(activation_hint * _ACT_SCALE[remat])
        source = "analytic+hint"
    else:
        reg = _registry_workspace(axes, remat) if use_registry else None
        if reg is not None:
            act_b = reg
            source = "registry"
        else:
            act_b = int(batch_b * _ACT_MULT[remat]) if training else \
                int(batch_b * _ACT_MULT["none"] / 2)

    offload_b = 0
    if offload == "host":
        offload_b = state_b + masters_b
        state_b = masters_b = 0

    kv_b = int(kv_pool_bytes)
    breakdown = {
        "params": params_b, "grads": grads_b,
        "optimizer_state": state_b, "masters": masters_b,
        "batch": batch_b, "activations": act_b,
        "host_offloaded": offload_b,
    }
    if kv_b:
        breakdown["kv_pool"] = kv_b
    peak = params_b + grads_b + state_b + masters_b + batch_b + act_b \
        + kv_b

    top = sorted(
        ([{"name": n, "bytes": sum(c.values()), "components": c}
          for n, c in per_param.items()]
         + ([{"name": "<batch>", "bytes": batch_b,
              "components": {"batch": batch_b}}] if batch_b else [])
         + ([{"name": "<activations>", "bytes": act_b,
              "components": {"activations": act_b}}] if act_b else [])
         + ([{"name": "<kv_pool>", "bytes": kv_b,
              "components": {"kv_pool": kv_b}}] if kv_b else [])),
        key=lambda d: -d["bytes"])[:8]

    plan = Plan(
        peak, budget if budget is not None else budget_bytes(device_kind),
        breakdown, top, source, remat, offload,
        ctx={"shapes": shapes, "mesh": mesh, "rules": rules,
             "optimizer": optimizer, "batch_bytes": int(batch_bytes),
             "activation_hint": activation_hint, "budget": budget,
             "training": training, "device_kind": device_kind,
             "kv_pool_bytes": kv_b,
             "optimizer_desc": (opt_name, n_state, multi_precision)})
    if record:
        global _last_plan
        _last_plan = plan
    return plan


def _ceil_div(a, b):
    return int(math.ceil(a / b)) if b > 1 else int(a)


def plan_from_artifact(artifact, budget=None, record=True):
    """A :class:`Plan` from a committed lowering artifact (a
    ``scale_proof`` JSON path or dict) — the offline cold path when a
    fresh TPU lowering is unavailable.  XLA's per-device memory
    analysis is the load-bearing number: predicted peak = args + temp
    (the same upper bound every ``fit_verdict`` since r4 records)."""
    import json

    name = None
    if isinstance(artifact, (str, os.PathLike)):
        name = os.path.basename(str(artifact))
        with open(artifact) as f:
            artifact = json.load(f)
    mem = artifact.get("xla_memory_analysis_per_device", {})
    if "argument_size_in_bytes" not in mem:
        raise ValueError(f"artifact {name or '<dict>'} carries no XLA "
                         "memory analysis")
    args_b = int(mem["argument_size_in_bytes"])
    temp_b = int(mem.get("temp_size_in_bytes", 0))
    peak = args_b + temp_b
    backend = artifact.get("backend", "cpu")
    if backend == "cpu":
        peak -= int(artifact.get("fit_verdict", {}).get(
            "cpu_bf16_upcast_artifact_bytes", 0))
    if budget is None:
        budget = (int(TPU_BUDGET_GIB * 2 ** 30) if backend == "tpu"
                  else budget_bytes())
    breakdown = {"arguments": args_b, "temp": temp_b,
                 "output": int(mem.get("output_size_in_bytes", 0)),
                 "alias": int(mem.get("alias_size_in_bytes", 0))}
    top = [{"name": "<xla arguments>", "bytes": args_b,
            "components": {"arguments": args_b}},
           {"name": "<xla temp>", "bytes": temp_b,
            "components": {"temp": temp_b}}]
    plan = Plan(peak, budget, breakdown, top,
                source=f"lowering:{name or backend}",
                remat=artifact.get("remat"), offload=None,
                ctx={"artifact": name, "mesh": artifact.get("mesh"),
                     "per_chip_batch": artifact.get("per_chip_batch"),
                     "optimizer": artifact.get("optimizer")})
    if record:
        global _last_plan
        _last_plan = plan
    return plan


def prescribe(plan=None, margin=0.0):
    """Turn a failed (or failing) plan into the cheapest fix that fits:
    re-plan the next remat tiers, host offload, and a halved batch, in
    increasing cost-of-fix order.  Returns ``{"candidates": [...],
    "recommendation": {...}|None}`` or ``None`` when there is nothing
    to re-plan (no analytic plan context)."""
    from .policy import TIERS

    plan = plan if plan is not None else _last_plan
    if plan is None or "shapes" not in plan.ctx:
        return None
    ctx = plan.ctx
    base = dict(params=ctx["shapes"], mesh=ctx["mesh"],
                rules=ctx["rules"], optimizer=ctx["optimizer"],
                batch_bytes=ctx["batch_bytes"],
                activation_hint=ctx["activation_hint"],
                budget=ctx["budget"], training=ctx["training"],
                device_kind=ctx["device_kind"],
                kv_pool_bytes=ctx.get("kv_pool_bytes", 0), record=False)

    tier_i = TIERS.index(plan.remat) if plan.remat in TIERS else 0
    candidates = []
    for tier in TIERS[tier_i + 1:]:
        candidates.append((f'remat="{tier}"',
                           dict(base, remat=tier, offload=plan.offload)))
    if plan.offload != "host":
        candidates.append(('offload="host"',
                           dict(base, remat=plan.remat, offload="host")))
        if tier_i + 1 < len(TIERS):
            candidates.append(
                (f'remat="{TIERS[-1]}" + offload="host"',
                 dict(base, remat=TIERS[-1], offload="host")))
    candidates.append(
        ("halve the batch",
         dict(base, remat=plan.remat, offload=plan.offload,
              batch_bytes=ctx["batch_bytes"] // 2,
              activation_hint=(None if ctx["activation_hint"] is None
                               else ctx["activation_hint"] // 2))))

    out, rec = [], None
    for change, kw in candidates:
        cand = plan_model(**kw)
        fits = cand.predicted_peak_bytes <= cand.budget_bytes * (1 - margin)
        entry = {"change": change,
                 "predicted_peak_bytes": cand.predicted_peak_bytes,
                 "predicted_peak_gib": round(
                     cand.predicted_peak_bytes / 2 ** 30, 3),
                 "fits": fits,
                 "headroom_bytes": cand.headroom_bytes}
        out.append(entry)
        if fits and rec is None:
            rec = entry
    result = {"failing_plan": plan.as_dict(), "candidates": out,
              "recommendation": rec}
    global _last_prescription
    _last_prescription = result
    return result
