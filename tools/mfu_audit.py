"""MFU audit: ground every README MFU claim in XLA's OWN per-step FLOP
count instead of hand arithmetic (VERDICT r3 item 1).

For each benched workload this composes ONE pure train-step function out
of the exact framework pieces the bench executes — the hybridized net's
``_CachedGraph._pure`` forward, the bench's loss math, and the
optimizer's ``_step`` update — then asks the compiler what it costs:

    jax.jit(step).lower(abstract_args).compile().cost_analysis()

The resulting ``flops`` is XLA's count over the optimized HLO for one
full fwd+bwd+update step (matmuls, convs, attention, the full-vocab
softmax-CE, the optimizer elementwise traffic — everything; remat
recompute included when the workload trains with remat).  MFU derived
from it carries the compiler's receipt, not a spreadsheet's.

Reference posture: MXNet published measured throughput only
(docs/faq/perf.md:?); derived metrics like MFU need exactly this kind of
receipt.

Usage (each workload isolated in its own process — AMP is global state):

    python tools/mfu_audit.py resnet50          # one workload, JSON line
    python tools/mfu_audit.py bert_base
    python tools/mfu_audit.py llama1b
    python tools/mfu_audit.py all               # subprocess per workload,
                                                # writes MFU_AUDIT_r04.json

Runtime-registry mode: a run with ``MXNET_TELEMETRY=1`` (or
``telemetry.costs.enable()``) already holds every compiled artifact's
``cost_analysis()``; ``telemetry.costs.dump("COSTS.json")`` writes it and

    python tools/mfu_audit.py --from-registry COSTS.json

audits from the runtime's own numbers — no re-lowering, and the flops
are those of the artifacts that actually executed.  A missing/empty/
unreadable dump falls back to the lowering path above.

Throughput inputs default to the round-3 driver artifacts; override with
e.g. ``THROUGHPUT=5151.48`` (samples/sec) per run.  ``AUDIT_PLATFORM=cpu``
lowers on the CPU backend (identical dominant FLOPs; transcendental
counting may differ marginally — the JSON records which backend priced
the step).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# TPU v5e bf16 peak (MXU): the number every README MFU row divides by.
PEAK_BF16_TFLOPS = 197.0

# round-3 driver-captured throughputs (BENCH_r03.json) + the README's
# measured llama rate — the wall-clock side of the MFU fractions under
# audit.  Override per-run with THROUGHPUT.
DEFAULT_THROUGHPUT = {
    "resnet50": 5151.48,   # images/sec/chip, driver best-of-3
    "bert_base": 2304.3,   # samples/sec/chip, driver best-of-3
    "llama1b": 10900.0 / 2048.0,  # sequences/sec (10.9k tok/s, seq 2048)
}

# the hand counts the README used until this audit (GFLOP per sample)
HAND_GFLOP = {
    "resnet50": 24.6,      # 3 x fwd 8.2 (fwd = 4.1 GMAC)
    "bert_base": 84.0,     # 6 N_nonemb s + 3x MLM head
    "llama1b": None,       # filled from 6N at runtime
}


#: MXU flops per TensorCore cycle on v5e (4 MXUs x 128x128 MACs x 2):
#: XLA:TPU's per-fusion ``estimated_cycles`` measures in this clock
#: domain — large-matmul probes resolve ~120k flops/cycle against this
#: 131,072 ceiling (92%), which pins both the calibration and the
#: implied ~1.5 GHz clock (197e12 / 131072).
V5E_MXU_FLOPS_PER_CYCLE = 131072
V5E_CLOCK_HZ = PEAK_BF16_TFLOPS * 1e12 / V5E_MXU_FLOPS_PER_CYCLE


def _setup_platform():
    """AUDIT_PLATFORM: ``cpu`` (default) prices FLOPs/bytes on the CPU
    lowering; ``tpu_topology`` compiles against the OFFLINE libtpu
    v5e:1x1 topology client — real XLA:TPU fusions, with the
    per-fusion ``estimated_cycles`` summed into a predicted step time
    (serial-fusion model: DMA/compute overlap ignored, so the
    prediction is a floor on speed and measured throughput should land
    at or above it)."""
    plat = os.environ.get("AUDIT_PLATFORM", "cpu")
    if plat in ("cpu", "tpu_topology"):
        import jax

        jax.config.update("jax_platforms", "cpu")
    if plat == "tpu_topology":
        # the prediction must price the kernels the real chip runs:
        # route the pallas flash path (the process backend being cpu
        # would otherwise silently swap in the chunked fallback)
        os.environ.setdefault("MXT_FORCE_PALLAS_FLASH", "1")
    return plat


def _topology_mesh():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from _tpu_topology import topology_mesh

    return topology_mesh("v5e:1x1")


def _compose_step(net, loss_raw, opt, batch_for_rescale, key,
                  remat=False):
    """One pure (params, opt_states, inputs..., labels) -> loss step
    from the framework's own pieces; returns (jitted_fn, abstract_args).

    ``loss_raw(outs_raws, label_raw) -> scalar`` replicates the bench's
    loss math on raw arrays; the optimizer update reuses
    ``Optimizer._step`` verbatim (rescale_grad is set on ``opt`` exactly
    as ``gluon.Trainer.step(batch_size)`` would)."""
    import jax

    from mxnet_tpu.gluon.block import _CachedGraph

    params = list(net.collect_params().values())
    graph = _CachedGraph(net, params, training=True, remat=remat)
    diff_idx = [i for i, p in enumerate(params) if p.grad_req != "null"]
    opt.rescale_grad = 1.0 / batch_for_rescale
    # optimizer state per diff param, exactly as Trainer would create it
    states = [opt.create_state_multi_precision(i, params[i].data())
              for i in diff_idx]

    from mxnet_tpu.optimizer import _flatten_state

    flat_states = [tuple(s._data for s in _flatten_state(st))
                   for st in states]

    def step(p_raws, st_raws, in_raws, label_raw):
        def loss_of(diff_raws):
            full = list(p_raws)
            for j, i in enumerate(diff_idx):
                full[i] = diff_raws[j]
            outs, auxs, _stats = graph._pure(full, in_raws, key)
            return loss_raw(outs, label_raw), auxs

        fn = jax.checkpoint(loss_of) if remat else loss_of
        (loss, auxs), grads = jax.value_and_grad(fn, has_aux=True)(
            [p_raws[i] for i in diff_idx])
        new_ws, new_sts = [], []
        for j, i in enumerate(diff_idx):
            w, g = p_raws[i], grads[j]
            lr = opt._get_lr(i)
            wd = opt._get_wd(i)
            nw, nst = opt._step(w, g, st_raws[j], lr, wd, 1)
            new_ws.append(nw)
            new_sts.append(nst)
        return loss, new_ws, new_sts, auxs

    abstract = (
        [jax.ShapeDtypeStruct(p.shape, p.data()._data.dtype)
         for p in params],
        [tuple(jax.ShapeDtypeStruct(s.shape, s.dtype) for s in fs)
         for fs in flat_states],
    )
    return jax.jit(step), abstract


def _cost(jfn, abstract_params, abstract_states, in_structs, label_struct):
    import jax

    args = (abstract_params, abstract_states, in_structs, label_struct)
    plat = os.environ.get("AUDIT_PLATFORM", "cpu")
    if plat == "tpu_topology":
        from jax.sharding import NamedSharding, PartitionSpec as P

        repl = NamedSharding(_topology_mesh(), P())
        args = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=repl), args)
    lowered = jfn.lower(*args)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    out = {
        "flops": float(ca.get("flops", float("nan"))),
        "bytes_accessed": float(ca.get("bytes accessed",
                                       ca.get("bytes_accessed",
                                              float("nan")))),
    }
    if plat == "tpu_topology":
        from _tpu_topology import assert_tpu_hlo, estimated_cycles_sum

        hlo = compiled.as_text()
        assert_tpu_hlo(hlo, "mfu_audit")
        total, n = estimated_cycles_sum(hlo, required=True)
        out["tpu_estimated_cycles_sum"] = total
        out["tpu_estimated_fusions"] = n
    return out


def _emit(workload, per_step, batch, cost, hand_gflop, note=""):
    import jax

    thr = float(os.environ.get("THROUGHPUT",
                               DEFAULT_THROUGHPUT[workload]))
    xla_gflop_sample = cost["flops"] / batch / 1e9
    achieved_tflops = thr * xla_gflop_sample / 1e3
    mfu = achieved_tflops / PEAK_BF16_TFLOPS
    rec = {
        "workload": workload,
        "per_step": per_step,
        "batch": batch,
        # default_backend() reports the PROCESS backend (cpu even when
        # the jit target is the topology client) — record the actual
        # pricing backend
        "lowering_platform": (
            "xla:tpu (offline v5e:1x1 topology client)"
            if os.environ.get("AUDIT_PLATFORM") == "tpu_topology"
            else jax.default_backend()),
        "xla_flops_per_step": cost["flops"],
        "xla_bytes_accessed_per_step": cost["bytes_accessed"],
        "xla_gflop_per_sample": round(xla_gflop_sample, 3),
        "hand_gflop_per_sample": hand_gflop,
        "hand_vs_xla": (round(hand_gflop / xla_gflop_sample, 4)
                        if hand_gflop else None),
        "measured_throughput_per_sec": thr,
        "achieved_tflops": round(achieved_tflops, 2),
        "peak_bf16_tflops": PEAK_BF16_TFLOPS,
        "mfu": round(mfu, 4),
        "note": note,
    }
    if cost.get("tpu_estimated_cycles_sum"):
        step_s = cost["tpu_estimated_cycles_sum"] / V5E_CLOCK_HZ
        rec["tpu_estimated_cycles_sum"] = cost["tpu_estimated_cycles_sum"]
        rec["tpu_estimated_fusions"] = cost["tpu_estimated_fusions"]
        rec["predicted_step_ms"] = round(step_s * 1e3, 2)
        rec["predicted_throughput_per_sec"] = round(batch / step_s, 1)
        rec["predicted_mfu"] = round(
            cost["flops"] / step_s / 1e12 / PEAK_BF16_TFLOPS, 4)
        rec["prediction_model"] = (
            "sum of XLA:TPU per-fusion estimated_cycles / "
            f"{V5E_CLOCK_HZ/1e9:.2f} GHz; serial-fusion, no DMA "
            "overlap, and mosaic custom-calls (pallas kernels) carry "
            "NO estimate so their time is uncounted — a floor on "
            "speed, measured should land at or above "
            "predicted_throughput")
    print(json.dumps(rec))
    return rec


def audit_resnet50():
    """bench.py default leg: resnet50_v1, batch 64, 224^2, AMP bf16,
    SGD momentum 0.9, SoftmaxCE mean loss, Trainer.step(batch)."""
    _setup_platform()
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import amp, gluon, nd, optimizer

    batch = int(os.environ.get("BATCH", "64"))
    mx.random.seed(0)
    net = gluon.model_zoo.vision.get_model("resnet50_v1", classes=1000)
    net.initialize(mx.init.Xavier())
    net(nd.ones((1, 3, 32, 32)))  # resolve deferred shapes
    amp.init(target_dtype="bfloat16")

    def loss_raw(outs, label):
        logits = outs[0].astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce = -jnp.take_along_axis(logp, label[:, None], axis=-1)
        return ce.mean()

    opt = optimizer.SGD(learning_rate=0.1, momentum=0.9)
    key = jax.random.PRNGKey(0)
    jfn, (ap, ast) = _compose_step(net, loss_raw, opt, batch, key)
    x = jax.ShapeDtypeStruct((batch, 3, 224, 224), jnp.float32)
    y = jax.ShapeDtypeStruct((batch,), jnp.int32)
    cost = _cost(jfn, ap, ast, [x], y)
    return _emit("resnet50", "fwd+bwd+sgd_mom update", batch, cost,
                 HAND_GFLOP["resnet50"],
                 note="AMP bf16 active during trace, as in bench.py")


def audit_bert_base():
    """bench.py BERT leg: bert_base, batch 64, seq 128, AMP bf16, Adam,
    full-vocab MLM CE over every position."""
    _setup_platform()
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import amp, nd, optimizer
    from mxnet_tpu.models import bert

    batch = int(os.environ.get("BATCH", "64"))
    seq = int(os.environ.get("SEQ", "128"))
    vocab = 30522
    mx.random.seed(0)
    net = bert.bert_base(vocab_size=vocab)
    net.initialize(mx.init.Xavier())
    ids = nd.ones((1, 8), dtype="int32")
    net(ids, nd.zeros((1, 8), dtype="int32"))  # resolve deferred shapes
    amp.init(target_dtype="bfloat16")

    def loss_raw(outs, label):
        # the SAME fused CE the bench's _MLMLoss dispatches
        # (nn_ops.softmax_cross_entropy): f32 internal math, no f32
        # materialization of the (rows, vocab) logits
        from mxnet_tpu.ops.nn_ops import _softmax_ce_sum

        # no flatten: (b, s, vocab) direct — the reshape forced a
        # layout copy of the logits (bytes_breakdown r5)
        return _softmax_ce_sum(outs[-1],
                               label.astype(jnp.int32)) / (batch * seq)

    opt = optimizer.Adam(learning_rate=1e-4)
    key = jax.random.PRNGKey(0)
    jfn, (ap, ast) = _compose_step(net, loss_raw, opt, 1, key)
    x = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    seg = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    y = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    cost = _cost(jfn, ap, ast, [x, seg], y)
    return _emit("bert_base", "fwd+bwd+adam update", batch, cost,
                 HAND_GFLOP["bert_base"],
                 note="AMP bf16 active during trace, as in bench.py; "
                      "loss counted over all positions x full vocab")


def audit_llama1b():
    """examples/train_llama_1b.py: h2304 18L GQA 18/6, bf16 params,
    remat, flash attention, SGD momentum, token CE."""
    _setup_platform()
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd, optimizer
    from mxnet_tpu.models import llama

    batch = int(os.environ.get("BATCH", "4"))
    seq = int(os.environ.get("SEQ", "2048"))
    layers = int(os.environ.get("LAYERS", "18"))
    vocab = 32000
    mx.random.seed(0)
    net = llama.LlamaForCausalLM(llama.LlamaConfig(
        hidden_size=2304, intermediate_size=6144, num_layers=layers,
        num_heads=18, num_kv_heads=6, vocab_size=vocab,
        max_seq_len=seq, attn_mode="flash"))
    net.initialize(mx.init.Zero())  # values don't matter for pricing
    net(nd.ones((1, 8), dtype="int32"))
    n_params = sum(int(np.prod(p.shape))
                   for p in net.collect_params().values())
    net.cast("bfloat16")

    def loss_raw(outs, label):
        logits = outs[0].astype(jnp.float32).reshape((-1, vocab))
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce = -jnp.take_along_axis(logp, label.reshape((-1,))[:, None],
                                  axis=-1)
        return ce.sum() / (batch * seq)

    opt = optimizer.SGD(learning_rate=1e-3, momentum=0.9)
    key = jax.random.PRNGKey(0)
    jfn, (ap, ast) = _compose_step(net, loss_raw, opt, 1, key,
                                   remat=True)
    x = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    y = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    cost = _cost(jfn, ap, ast, [x], y)
    hand = 6 * n_params * seq / 1e9 * batch / batch  # 6N per token
    from mxnet_tpu.ops import flash_attention as _fa

    attn_path = ("pallas-flash" if _fa._on_tpu() and seq % 128 == 0
                 else "chunked-jnp")
    rec = _emit("llama1b", "fwd+bwd(remat)+sgd_mom update", batch, cost,
                round(hand, 1),
                note=f"{n_params/1e9:.2f}B params; hand = 6N/token "
                     "(remat recompute NOT in hand count, IS in "
                     f"XLA's); attention kernel priced: {attn_path}")
    return rec


WORKLOADS = {
    "resnet50": audit_resnet50,
    "bert_base": audit_bert_base,
    "llama1b": audit_llama1b,
}


# -- runtime-registry mode ---------------------------------------------------

def load_registry(path):
    """Parse a ``telemetry.costs.dump()`` JSON file; None when the file
    is missing, unreadable or holds no analyzed entries (the caller then
    falls back to the lowering path)."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            payload = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(payload, dict) or not payload.get("entries"):
        return None
    return payload


def registry_report(payload, throughput=None, step_time_s=None):
    """Audit record from a runtime cost-registry dump: per-kind flops /
    bytes totals (execution-weighted and per-execution), MFU against the
    dump's peak when a measured ``throughput`` (steps/sec) or
    ``step_time_s`` is supplied.

    ``flops_per_step`` sums ONE execution of every train-step-resident
    kind (cachedop fwd/bwd, fused updates, bulk segments) — the same
    "one full step" the lowering path prices; ``total_flops`` weights by
    recorded execution counts (the whole run's compute)."""
    per_kind = {}
    for e in payload.get("entries", []):
        k = per_kind.setdefault(e["kind"], {
            "artifacts": 0, "executions": 0, "flops_per_execution": 0.0,
            "bytes_per_execution": 0.0, "total_flops": 0.0,
            "total_bytes_accessed": 0.0, "errors": 0})
        k["artifacts"] += 1
        k["executions"] += e.get("executions", 0)
        k["flops_per_execution"] += e.get("flops", 0.0) or 0.0
        k["bytes_per_execution"] += e.get("bytes_accessed", 0.0) or 0.0
        k["total_flops"] += (e.get("flops", 0.0) or 0.0) * \
            e.get("executions", 0)
        k["total_bytes_accessed"] += \
            (e.get("bytes_accessed", 0.0) or 0.0) * e.get("executions", 0)
        if e.get("error"):
            k["errors"] += 1
    flops_per_step = sum(k["flops_per_execution"] for k in
                         per_kind.values())
    rec = {
        "source": "runtime cost registry",
        "device_kind": payload.get("device_kind"),
        "peak_flops": payload.get("peak_flops"),
        "per_kind": per_kind,
        "flops_per_step": flops_per_step,
        "bytes_accessed_per_step": sum(
            k["bytes_per_execution"] for k in per_kind.values()),
        "total_flops": sum(k["total_flops"] for k in per_kind.values()),
        "total_bytes_accessed": sum(
            k["total_bytes_accessed"] for k in per_kind.values()),
    }
    peak = payload.get("peak_flops")
    if step_time_s is None and throughput:
        step_time_s = 1.0 / float(throughput)
    if peak and step_time_s:
        rec["step_time_s"] = step_time_s
        rec["achieved_flops_per_sec"] = flops_per_step / step_time_s
        rec["mfu"] = round(flops_per_step / step_time_s / peak, 4)
    return rec


def _main_from_registry(path):
    payload = load_registry(path)
    if payload is None:
        print(f"registry dump {path!r} missing or empty; falling back "
              "to the lowering path", file=sys.stderr)
        return False
    thr = os.environ.get("THROUGHPUT")
    step_s = os.environ.get("STEP_TIME_S")
    rec = registry_report(payload,
                          throughput=float(thr) if thr else None,
                          step_time_s=float(step_s) if step_s else None)
    print(json.dumps(rec, indent=1))
    return True


def main():
    argv = list(sys.argv[1:])
    if "--from-registry" in argv:
        i = argv.index("--from-registry")
        path = argv[i + 1] if i + 1 < len(argv) else "COSTS.json"
        if _main_from_registry(path):
            return
        del argv[i:i + 2]  # fallback: audit by lowering
    which = argv[0] if argv else "all"
    if which != "all":
        WORKLOADS[which]()
        return
    out = {"peak_bf16_tflops": PEAK_BF16_TFLOPS, "workloads": []}
    for name in WORKLOADS:
        env = dict(os.environ)
        r = subprocess.run([sys.executable, os.path.abspath(__file__),
                            name], capture_output=True, text=True,
                           env=env)
        lines = [ln for ln in r.stdout.splitlines()
                 if ln.startswith("{")]
        if r.returncode != 0 or not lines:
            out["workloads"].append({"workload": name, "error":
                                     r.stderr[-2000:]})
            print(f"{name}: FAILED", file=sys.stderr)
            continue
        out["workloads"].append(json.loads(lines[-1]))
    default = ("PREDICTED_THROUGHPUT_r05.json"
               if os.environ.get("AUDIT_PLATFORM") == "tpu_topology"
               else "MFU_AUDIT_r04.json")
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), os.environ.get("AUDIT_OUT", default))
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
