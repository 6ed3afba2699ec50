#!/usr/bin/env python
"""Serving latency under load: closed-loop and Poisson open-loop lanes.

The round-8 tentpole claim: the continuous-batching server
(``mxnet_tpu.serving.InferenceServer``) holds its compiled-signature
count to the pow2 bucket grid while aggregating concurrent requests
into dynamic batches — so tail latency under load is paid in queueing
and batching, not recompilation.

Two lanes against an in-process server over a position-wise nnvm
predictor (every (batch, length) row an independent gemm row):

* **closed_loop** — ``BENCH_SERVING_CLIENTS`` threads each submitting
  ``BENCH_SERVING_REQUESTS / clients`` mixed-length requests
  back-to-back (throughput-bound: offered load tracks service rate).
* **open_loop** — one dispatcher submitting ``BENCH_SERVING_REQUESTS``
  requests at Poisson arrivals (seeded exponential gaps at
  ``BENCH_SERVING_RATE`` req/s), futures collected at the end
  (latency-bound: offered load is independent of service rate, queue
  waits show up honestly).

Every request's ``serving.request`` telemetry record is captured via a
ListSink; per lane the artifact reports p50/p90/p99 total latency,
queue-wait percentiles, the batch-size distribution, throughput, and
the predictor's compile-cache stats (signatures must stay within the
bucket grid's ceiling).

Round 11 adds the GENERATIVE lanes: the paged disaggregated server
swept open-loop over a request-rate ladder to saturation.  Per rate:
p50/p99 total latency, queue-wait percentiles, ttft, and
tokens/sec-per-chip.

Round 12 (observability) extends the sweep with TPOT percentiles and
per-rate goodput against TTFT/TPOT SLO targets
(``BENCH_SERVING_SLO_TTFT_MS`` / ``BENCH_SERVING_SLO_TPOT_MS``;
goodput counts rejected requests as misses), and adds the
**tracing_ab** lane: the same decode workload with request tracing off
vs on (min-of-repeats per arm), proving the per-decode-step overhead
of span recording stays under 3%.

Round 19 adds the **spec_radix** 2x2 A/B: speculative decoding (same-
net draft, ``BENCH_SERVING_SPEC_K`` proposals per verify) × the radix
prefix cache, over a shared-system-prompt workload submitted
sequentially so all four arms decode the identical greedy stream.
Per arm: target-forwards-per-generated-token (from the request
records' joined/done step counters), prefilled-token and prefill-ms
totals, accept rate, and the compile gate (signature-count delta of a
sanitizer-watched measured pass must be zero).

Round 20 adds the **capacity** lanes (``telemetry.capacity``):

* the paged rate sweep runs with capacity accounting ON, and the live
  λ/μ/ρ predictor's max-sustainable-rate — measured at the first
  saturated rung, where busy fraction ≈ 1 makes μ a direct capacity
  read — must agree with the offline sweep's verdict within one step
  of the rate ladder;
* a **saturation_burst** lane (small dp2 server, warm trickle then a
  deep burst) pins stream ordering: the ``{"record": "saturation"}``
  event lands *before* the first request record whose queue wait
  breaches ``GEN_SAT_QW_MS`` — ρ leads, latency follows;
* a **capacity_ab** lane clones the tracing A/B shape (alternating
  min-of-repeats arms) to bound the enabled accounting cost under 1%
  of a decode tick.

Run: ``JAX_PLATFORMS=cpu python benchmark/serving_latency.py``
Artifact: SERVING_LATENCY_r20.json (override MXT_SERVING_LATENCY_OUT).
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# the dp-replica lane needs >1 CPU device; force the virtual mesh
# BEFORE any jax import (all mxnet_tpu imports below are lazy)
if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import numpy as np

REQUESTS = int(os.environ.get("BENCH_SERVING_REQUESTS", 64))
CLIENTS = int(os.environ.get("BENCH_SERVING_CLIENTS", 4))
RATE = float(os.environ.get("BENCH_SERVING_RATE", 200.0))  # req/s, open loop
MAX_BATCH = int(os.environ.get("BENCH_SERVING_MAX_BATCH", 8))
MAX_LENGTH = int(os.environ.get("BENCH_SERVING_MAX_LEN", 64))
SEED = int(os.environ.get("BENCH_SERVING_SEED", 0))
IN_DIM = 8
HIDDEN = 8

# generative saturation sweep knobs
GEN_REQUESTS = int(os.environ.get("BENCH_SERVING_GEN_REQUESTS", 48))
GEN_RATE = float(os.environ.get("BENCH_SERVING_GEN_RATE", 512.0))
GEN_RATES = tuple(float(r) for r in os.environ.get(
    "BENCH_SERVING_GEN_RATES", "64,128,256,512,1024").split(","))
GEN_MAX_NEW = int(os.environ.get("BENCH_SERVING_GEN_MAX_NEW", 16))
# saturation criterion: an offered rate is "sustained" while queue-wait
# p99 stays under this bound (open loop: past saturation the queue —
# and with it the wait — grows without bound)
GEN_SAT_QW_MS = float(os.environ.get("BENCH_SERVING_GEN_SAT_QW_MS", 50.0))
GEN_MAX_LEN = 64
GEN_SLOTS = 4

# r12 observability knobs: SLO targets for the goodput-vs-rate columns
# (CPU-scale defaults — generous on purpose, the interesting signal is
# goodput FALLING as the rate ladder saturates, not absolute values)
# and the tracing A/B lane's shape
SLO_TTFT_MS = float(os.environ.get("BENCH_SERVING_SLO_TTFT_MS", 500.0))
SLO_TPOT_MS = float(os.environ.get("BENCH_SERVING_SLO_TPOT_MS", 100.0))
AB_REQUESTS = int(os.environ.get("BENCH_SERVING_AB_REQUESTS", 8))
AB_MAX_NEW = int(os.environ.get("BENCH_SERVING_AB_MAX_NEW", 32))
AB_REPEATS = int(os.environ.get("BENCH_SERVING_AB_REPEATS", 3))

# r19 speed-multiplier knobs: the speculative × radix 2x2 A/B over a
# shared-system-prompt workload (chat/RAG shape: one long shared prefix
# + a short per-request tail), submitted sequentially so every lane
# decodes the identical token stream
SPEC_REQUESTS = int(os.environ.get("BENCH_SERVING_SPEC_REQUESTS", 8))
SPEC_K = int(os.environ.get("BENCH_SERVING_SPEC_K", 3))
SPEC_MAX_NEW = int(os.environ.get("BENCH_SERVING_SPEC_MAX_NEW", 16))
SPEC_PREFIX = int(os.environ.get("BENCH_SERVING_SPEC_PREFIX", 160))
SPEC_MAX_LEN = int(os.environ.get("BENCH_SERVING_SPEC_MAX_LEN", 256))

# r20 capacity knobs: the saturation-burst lane's depth and the watch
# threshold it arms.  The capacity A/B gates at 1% (vs tracing's 3%),
# so it runs longer arms and more repeats: the per-tick effect under
# test is ~0.3% while single-pass jitter on a shared CPU host is ~10%,
# and only a deep min-of-repeats floor separates the two.
CAP_BURST = int(os.environ.get("BENCH_SERVING_CAP_BURST", 24))
CAP_RHO = float(os.environ.get("BENCH_SERVING_CAP_RHO", 0.85))
CAP_AB_REQUESTS = int(os.environ.get("BENCH_SERVING_CAP_AB_REQUESTS",
                                     2 * AB_REQUESTS))
CAP_AB_REPEATS = int(os.environ.get("BENCH_SERVING_CAP_AB_REPEATS", 8))


def _build_predictor(workdir):
    """Position-wise nnvm chain (FullyConnected flatten=False): padded
    batches are bit-identical to unpadded rows, so the bench measures
    scheduling, not numerics."""
    from mxnet_tpu import nd, serialization
    import mxnet_tpu.symbol as sym
    from mxnet_tpu.predictor import Predictor

    data = sym.Variable("data")
    w = sym.Variable("fc_weight")
    b = sym.Variable("fc_bias")
    out = sym.FullyConnected(data, w, b, num_hidden=HIDDEN, flatten=False,
                             name="fc")
    out = sym.Activation(out, act_type="relu")
    rs = np.random.RandomState(7)
    prefix = os.path.join(workdir, "posw")
    out.save(f"{prefix}-symbol.json")
    serialization.save_ndarrays(f"{prefix}-0000.params", {
        "arg:fc_weight": nd.array(rs.randn(HIDDEN, IN_DIM)
                                  .astype(np.float32)),
        "arg:fc_bias": nd.array(rs.randn(HIDDEN).astype(np.float32))})
    return Predictor(f"{prefix}-symbol.json", f"{prefix}-0000.params")


def _percentiles(values, ps=(50, 90, 99)):
    if not values:
        return {f"p{p}": None for p in ps}
    xs = sorted(values)
    n = len(xs)
    out = {}
    for p in ps:
        rank = max(0, min(n - 1, -(-p * n // 100) - 1))  # nearest-rank
        out[f"p{p}"] = round(xs[rank], 3)
    return out


def _lane_summary(recs, wall_s, rejected):
    # r12: the stream now carries rejected/errored records too (tagged
    # status != "ok", total_ms None) — latency math only sees completions
    recs = [r for r in recs if r.get("status", "ok") == "ok"]
    total = [r["total_ms"] for r in recs]
    waits = [r["queue_wait_ms"] for r in recs]
    sizes = {}
    for r in recs:
        sizes[str(r["batch_size"])] = sizes.get(str(r["batch_size"]), 0) + 1
    return {
        "completed": len(recs),
        "rejected": rejected,
        "wall_s": round(wall_s, 4),
        "throughput_req_per_s": round(len(recs) / wall_s, 2),
        "total_ms": _percentiles(total),
        "queue_wait_ms": _percentiles(waits),
        "queue_wait_ms_mean": round(sum(waits) / max(1, len(waits)), 3),
        "batch_size_dist": dict(sorted(sizes.items(), key=lambda kv:
                                       int(kv[0]))),
        "buckets_seen": sorted({tuple(b) if isinstance(b, (list, tuple))
                                else b for b in (r["bucket"] for r in recs)}),
    }


def _workload(n, rng):
    """Mixed-length inputs spanning the length-bucket grid."""
    lens = rng.randint(2, MAX_LENGTH + 1, size=n)
    return [rng.randn(l, IN_DIM).astype(np.float32) for l in lens]


def _make_server(pred):
    from mxnet_tpu import serving

    cfg = serving.ServerConfig(max_batch=MAX_BATCH, max_length=MAX_LENGTH,
                               min_batch=1, min_length=8,
                               queue_capacity=max(64, REQUESTS),
                               output_length_axis=0, batch_window_ms=2.0,
                               summary_every=max(16, REQUESTS // 2))
    return serving.InferenceServer(pred, cfg)


def _run_lane(pred, lane):
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry.sinks import ListSink

    rng = np.random.RandomState(SEED + (1 if lane == "open_loop" else 0))
    inputs = _workload(REQUESTS, rng)
    telemetry.enable(memory=False, cost=False)
    sink = ListSink()
    telemetry.add_sink(sink)
    srv = _make_server(pred)
    try:
        with srv:
            # warmup: touch every length bucket once so steady-state
            # latency excludes first-compile time (compile counts are
            # still reported from cache stats)
            for l in sorted({srv.config.policy.length_bucket(len(x))
                             for x in inputs}):
                srv.infer(np.zeros((l, IN_DIM), np.float32), timeout=120.0)
            sink.records.clear()
            t0 = time.perf_counter()
            if lane == "closed_loop":
                _closed_loop(srv, inputs)
            else:
                _open_loop(srv, inputs, rng)
            wall = time.perf_counter() - t0
        stats = srv.stats()
    finally:
        telemetry.disable()
        telemetry.reset()
    recs = [r for r in sink.records if r.get("record") == "serving.request"]
    out = _lane_summary(recs, wall, stats["rejected"])
    out["batches"] = stats["batches"]
    out["cache"] = stats["cache"]
    return out


def _closed_loop(srv, inputs):
    shards = [inputs[i::CLIENTS] for i in range(CLIENTS)]

    def client(shard):
        for x in shard:
            srv.infer(x, timeout=300.0)

    threads = [threading.Thread(target=client, args=(s,)) for s in shards]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def _open_loop(srv, inputs, rng):
    gaps = rng.exponential(1.0 / RATE, size=len(inputs))
    futures = []
    for x, gap in zip(inputs, gaps):
        time.sleep(gap)
        futures.append(srv.submit(x))
    for f in futures:
        f.result(timeout=300.0)


# --- generative lanes: the paged/dp server, rate ladder --------------------

def _gen_workload(n, rng):
    """Mixed-length prompts spanning the 8/16 prompt buckets."""
    lens = rng.randint(4, 17, size=n)
    return [rng.randint(1, 250, size=l).astype(np.int32) for l in lens]


def _make_gen_server(net):
    """The paged disaggregated server, dp2 mesh (two single-device
    replicas) when >=2 devices are available.  The pool holds
    ``GEN_SLOTS × GEN_MAX_LEN`` tokens and — because requests only
    reserve what they can use — serves 2× the decode slots from it."""
    import jax
    from mxnet_tpu import serving

    cfg = serving.ServerConfig(
        max_batch=GEN_SLOTS, max_length=GEN_MAX_LEN, min_batch=1,
        min_length=8, queue_capacity=max(64, GEN_REQUESTS),
        num_slots=2 * GEN_SLOTS, max_new_tokens=GEN_MAX_NEW,
        block_size=16, num_blocks=GEN_SLOTS * (GEN_MAX_LEN // 16),
        batch_window_ms=2.0, summary_every=max(64, GEN_REQUESTS))
    mesh = None
    if len(jax.devices()) >= 2:
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    return serving.GenerativeServer(net, cfg, mesh=mesh)


def _gen_rate_pass(srv, prompts, rate, rng):
    """One open-loop pass at ``rate`` req/s over a warm server."""
    from mxnet_tpu.serving import ServerOverloadedError

    gaps = rng.exponential(1.0 / rate, size=len(prompts))
    futs, accepted, rejected = [], [], 0
    t0 = time.perf_counter()
    for p, gap in zip(prompts, gaps):
        time.sleep(gap)
        try:
            futs.append(srv.submit(p, max_new_tokens=GEN_MAX_NEW))
            accepted.append(p)
        except ServerOverloadedError:
            rejected += 1
    done = [f.result(timeout=300.0) for f in futs]
    wall = time.perf_counter() - t0
    gen_tok = sum(len(d) - len(p) for d, p in zip(done, accepted))
    return wall, rejected, gen_tok


def _warm_grid(srv):
    """Compile every (batch bucket, length bucket) prefill + scatter
    signature and the decode step on every replica's engine, using
    all-sentinel slots/blocks (XLA drops out-of-bounds scatters, so no
    live KV is touched) — the measured passes never hit a cold
    compile."""
    pol = srv.config.policy
    for eng in [rep.engine for rep in srv.replicas]:
        eng.step([])
        for kb in pol.batch_buckets():
            for lb in pol.length_buckets():
                prompts = np.zeros((kb, lb), np.int32)
                t0s = np.full(kb, lb, np.int32)
                slots = np.full(kb, eng.num_slots, np.int32)
                toks, rows = eng.prefill_rows(prompts, t0s)
                eng.commit_rows(rows, slots, [None] * kb, t0s,
                                np.zeros(kb, np.int64))


def _run_gen_engine(net, rates):
    """Build ONE server (so the rate ladder shares its compiles), warm
    the signature grid on every replica, then sweep."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry.sinks import ListSink

    from mxnet_tpu.telemetry import capacity as cap

    rng = np.random.RandomState(SEED + 17)
    prompts = _gen_workload(GEN_REQUESTS, rng)
    telemetry.enable(memory=False, cost=False)
    # r20: the sweep doubles as the capacity ground truth — the live
    # λ/μ/ρ predictor runs alongside the offline saturation criterion
    cap.enable()
    sink = ListSink()
    telemetry.add_sink(sink)
    srv = _make_gen_server(net)
    chips = len(srv.replicas)
    out = {"engine": "paged", "replicas": chips, "rates": {}}
    try:
        _warm_grid(srv)
        with srv:
            # one warm request end-to-end per replica (routing, lanes,
            # demux — all compiles are already grid-warm)
            warm = [srv.submit(np.arange(1, 9, dtype=np.int32),
                               max_new_tokens=2) for _ in range(chips)]
            for f in warm:
                f.result(timeout=300.0)
            for rate in rates:
                sink.records.clear()
                cap.reset()    # clean per-rate λ/μ/ρ reads
                wall, rejected, gen_tok = _gen_rate_pass(
                    srv, prompts, rate, rng)
                recs = [r for r in sink.records
                        if r.get("record") == "serving.request"
                        and r.get("status", "ok") == "ok"]
                ttft = [r["ttft_ms"] for r in recs
                        if r.get("ttft_ms") is not None]
                tpot = [r["tpot_ms"] for r in recs
                        if r.get("tpot_ms") is not None]
                summary = _lane_summary(recs, wall, rejected)
                del summary["buckets_seen"]
                summary.pop("batches", None)
                qw99 = summary["queue_wait_ms"]["p99"]
                # goodput vs SLO: requests meeting BOTH latency targets
                # over everything offered (rejections are misses)
                met = sum(1 for r in recs
                          if r.get("ttft_ms") is not None
                          and r["ttft_ms"] <= SLO_TTFT_MS
                          and (r.get("tpot_ms") is None
                               or r["tpot_ms"] <= SLO_TPOT_MS))
                summary.update({
                    "offered_rate_req_per_s": rate,
                    "ttft_ms": _percentiles(ttft),
                    "tpot_ms": _percentiles(tpot),
                    "slo": {"ttft_ms": SLO_TTFT_MS,
                            "tpot_ms": SLO_TPOT_MS},
                    "slo_met": met,
                    "goodput_vs_slo": round(met / len(prompts), 4),
                    "tokens_per_s": round(gen_tok / wall, 2),
                    "tokens_per_s_per_chip": round(gen_tok / wall / chips,
                                                   2),
                    "sustained": (summary["completed"] == len(prompts)
                                  and rejected == 0
                                  and qw99 is not None
                                  and qw99 < GEN_SAT_QW_MS),
                })
                # live capacity read right after the pass drains (the
                # 10 s window still covers it); per-replica μ sums to
                # the fleet's predicted max rate
                views = list(cap.snapshot().values())
                preds = [v["predicted_max_rate_rps"] for v in views
                         if v.get("predicted_max_rate_rps") is not None]
                rhos = [v["rho"] for v in views
                        if v.get("rho") is not None]
                summary["capacity"] = {
                    "predicted_max_rate_rps":
                        round(sum(preds), 2) if preds else None,
                    "rho_max": round(max(rhos), 4) if rhos else None,
                    "utilization": [round(v["utilization"], 4)
                                    for v in views],
                    "saturation_events": sum(v["saturation_events"]
                                             for v in views),
                }
                out["rates"][f"{rate:g}"] = summary
        stats = srv.stats()
    finally:
        cap.disable()
        telemetry.disable()
        telemetry.reset()
    sust = [r for r in rates if out["rates"][f"{r:g}"]["sustained"]]
    out["max_sustainable_rate_req_per_s"] = max(sust) if sust else None
    out["decode_steps"] = stats["decode_steps"]
    out["kv_cache"] = stats["kv_cache"]
    return out


def _gen_sweep():
    from mxnet_tpu.models.llama import llama_tiny

    net = llama_tiny()
    net.initialize()
    rates = sorted(set(GEN_RATES) | {GEN_RATE})
    engines = {"paged": _run_gen_engine(net, rates)}
    return (engines, _tracing_ab(net), _capacity_ab(net),
            _saturation_burst(net), rates)


# --- tracing on/off A/B: span recording must not tax the decode step --------

def _ab_arm(srv, prompts, traced):
    """One measured pass: submit the batch, wait, return (decode wall
    seconds, decode steps taken) — per-step time is the ratio, so queue
    scheduling noise outside the decode loop cancels."""
    from mxnet_tpu.telemetry import tracing

    (tracing.enable if traced else tracing.disable)()
    try:
        steps0 = sum(rep.engine.steps for rep in srv.replicas)
        t0 = time.perf_counter()
        futs = [srv.submit(p, max_new_tokens=AB_MAX_NEW) for p in prompts]
        for f in futs:
            f.result(timeout=300.0)
        wall = time.perf_counter() - t0
        steps1 = sum(rep.engine.steps for rep in srv.replicas)
    finally:
        tracing.disable()
        tracing.clear()
    return wall, steps1 - steps0


def _tracing_ab(net):
    """Decode-step overhead of request tracing: the same single-replica
    paged workload with tracing off vs on, ``AB_REPEATS`` alternating
    passes per arm, min-of-repeats per arm (the min is the noise-free
    estimate on a shared machine).  Telemetry proper stays ON in both
    arms so the A/B isolates exactly the span-recording delta."""
    from mxnet_tpu import serving, telemetry

    rng = np.random.RandomState(SEED + 23)
    prompts = _gen_workload(AB_REQUESTS, rng)
    cfg = serving.ServerConfig(
        max_batch=GEN_SLOTS, max_length=GEN_MAX_LEN, min_batch=1,
        min_length=8, queue_capacity=max(64, AB_REQUESTS),
        num_slots=GEN_SLOTS, max_new_tokens=AB_MAX_NEW,
        block_size=16,
        batch_window_ms=2.0, summary_every=1 << 30)
    telemetry.enable(memory=False, cost=False)
    srv = serving.GenerativeServer(net, cfg)
    arms = {"off": [], "on": []}
    try:
        _warm_grid(srv)
        with srv:
            warm = [srv.submit(np.arange(1, 9, dtype=np.int32),
                               max_new_tokens=2) for _ in range(2)]
            for f in warm:
                f.result(timeout=300.0)
            for _ in range(AB_REPEATS):
                for arm, traced in (("off", False), ("on", True)):
                    wall, steps = _ab_arm(srv, prompts, traced)
                    if steps:
                        arms[arm].append(wall * 1e3 / steps)
    finally:
        telemetry.disable()
        telemetry.reset()
    off = min(arms["off"])
    on = min(arms["on"])
    overhead = (on - off) / off if off else 0.0
    return {
        "requests": AB_REQUESTS,
        "max_new_tokens": AB_MAX_NEW,
        "repeats": AB_REPEATS,
        "step_ms_off": round(off, 4),
        "step_ms_on": round(on, 4),
        "step_ms_off_all": [round(x, 4) for x in arms["off"]],
        "step_ms_on_all": [round(x, 4) for x in arms["on"]],
        "overhead_frac": round(overhead, 4),
    }


# --- r20 capacity lanes -----------------------------------------------------

def _saturation_burst(net):
    """Stream-order proof on a deliberately small dp2 server: a warm
    trickle, then a ``CAP_BURST``-deep instantaneous burst.  λ spikes
    at submit time while queue waits only surface on completion
    records, so the edge-triggered ``{"record": "saturation"}`` event
    must land in the JSONL stream BEFORE the first request record
    whose queue wait breaches ``GEN_SAT_QW_MS`` — the watch leads the
    latency symptom it predicts."""
    import jax
    from mxnet_tpu import serving, telemetry
    from mxnet_tpu.telemetry import capacity as cap
    from mxnet_tpu.telemetry.sinks import ListSink

    cfg = serving.ServerConfig(
        max_batch=2, max_length=GEN_MAX_LEN, min_batch=1, min_length=8,
        num_slots=2, queue_capacity=max(64, 4 * CAP_BURST),
        max_new_tokens=8, block_size=16,
        batch_window_ms=2.0, summary_every=1 << 30)
    mesh = None
    if len(jax.devices()) >= 2:
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    telemetry.enable(memory=False, cost=False, trace=True)
    cap.enable(rho_threshold=CAP_RHO, min_completions=6)
    sink = ListSink()
    telemetry.add_sink(sink)
    srv = serving.GenerativeServer(net, cfg, mesh=mesh)
    try:
        _warm_grid(srv)
        with srv:
            prompt = np.arange(1, 9, dtype=np.int32)
            # steady trickle: enough completions to seed λ and μ
            for _ in range(14):
                srv.submit(prompt, max_new_tokens=2).result(timeout=300.0)
                time.sleep(0.01)
            sink.records.clear()
            futs = [srv.submit(prompt, max_new_tokens=8)
                    for _ in range(CAP_BURST)]
            for f in futs:
                f.result(timeout=300.0)
        views = list(cap.snapshot().values())
        events = sum(v["saturation_events"] for v in views)
        records = list(sink.records)
    finally:
        cap.disable()
        telemetry.disable()
        telemetry.reset()
    sat_idx = next((i for i, r in enumerate(records)
                    if r.get("record") == "saturation"), None)
    rho_at = (records[sat_idx].get("rho")
              if sat_idx is not None else None)
    breach_idx = next(
        (i for i, r in enumerate(records)
         if r.get("record") == "serving.request"
         and (r.get("queue_wait_ms") or 0.0) > GEN_SAT_QW_MS), None)
    return {
        "burst": CAP_BURST,
        "rho_threshold": CAP_RHO,
        "queue_wait_bound_ms": GEN_SAT_QW_MS,
        "saturation_events": events,
        "saturation_index": sat_idx,
        "rho_at_event": rho_at,
        "first_queue_wait_breach_index": breach_idx,
        "saturation_precedes_breach": (
            sat_idx is not None
            and (breach_idx is None or sat_idx < breach_idx)),
    }


def _cap_arm(srv, prompts, on):
    """One measured pass with capacity accounting on/off; same
    wall-per-decode-step ratio as the tracing arms."""
    from mxnet_tpu.telemetry import capacity as cap

    (cap.enable if on else cap.disable)()
    try:
        steps0 = sum(rep.engine.steps for rep in srv.replicas)
        t0 = time.perf_counter()
        futs = [srv.submit(p, max_new_tokens=AB_MAX_NEW) for p in prompts]
        for f in futs:
            f.result(timeout=300.0)
        wall = time.perf_counter() - t0
        steps1 = sum(rep.engine.steps for rep in srv.replicas)
    finally:
        cap.disable()
    return wall, steps1 - steps0


def _capacity_ab(net):
    """Decode-tick overhead of capacity accounting, gated the way r13
    gated the fleet hook: the HOOK COST IS MEASURED DIRECTLY (the
    exact per-tick call sequence — note_tick + note_kv, plus the
    per-request arrival/completion/snapshot amortized over
    ``AB_MAX_NEW`` ticks — at serving cadence against warm full-window
    state) and divided by the capacity-off median decode tick from an
    end-to-end A/B.  The end-to-end arms ride along as context
    (``ab_overhead_frac``), but they cannot gate at 1%: single-pass
    decode-tick time swings ±20% with batching luck on a shared CPU
    host, an order of magnitude over the effect under test."""
    from mxnet_tpu import serving, telemetry
    from mxnet_tpu.telemetry import capacity as cap

    rng = np.random.RandomState(SEED + 41)
    prompts = _gen_workload(CAP_AB_REQUESTS, rng)
    cfg = serving.ServerConfig(
        max_batch=GEN_SLOTS, max_length=GEN_MAX_LEN, min_batch=1,
        min_length=8, queue_capacity=max(64, CAP_AB_REQUESTS),
        num_slots=GEN_SLOTS, max_new_tokens=AB_MAX_NEW,
        block_size=16,
        batch_window_ms=2.0, summary_every=1 << 30)
    telemetry.enable(memory=False, cost=False)
    srv = serving.GenerativeServer(net, cfg)
    arms = {"off": [], "on": []}
    try:
        _warm_grid(srv)
        with srv:
            warm = [srv.submit(np.arange(1, 9, dtype=np.int32),
                               max_new_tokens=2) for _ in range(2)]
            for f in warm:
                f.result(timeout=300.0)
            for _ in range(CAP_AB_REPEATS):
                for arm, on in (("off", False), ("on", True)):
                    wall, steps = _cap_arm(srv, prompts, on)
                    if steps:
                        arms[arm].append(wall * 1e3 / steps)
        # direct hook measurement against warm, full-window estimator
        # state (the on-arm passes above populated it), at the same
        # cadence the decode lane pays
        cap.enable()
        n, t = 5000, time.perf_counter()
        t0 = time.perf_counter()
        for _ in range(n):
            cap.note_tick(0, GEN_SLOTS, GEN_SLOTS, t, t + 0.0012)
            cap.note_kv(0, 10, 64, 0.05)
            t += 0.0013
        tick_us = (time.perf_counter() - t0) / n * 1e6
        t0 = time.perf_counter()
        for _ in range(n):
            cap.note_arrival(0, t=t)
            cap.note_completion(0, t=t + 0.001)
            cap.snapshot(0, now=t + 0.001)
            t += 0.0013
        req_us = (time.perf_counter() - t0) / n * 1e6
    finally:
        cap.disable()
        telemetry.disable()
        telemetry.reset()
    import statistics
    off = statistics.median(arms["off"])
    on = statistics.median(arms["on"])
    hook_us = tick_us + req_us / AB_MAX_NEW
    return {
        "requests": CAP_AB_REQUESTS,
        "max_new_tokens": AB_MAX_NEW,
        "repeats": CAP_AB_REPEATS,
        "step_ms_off": round(off, 4),
        "step_ms_on": round(on, 4),
        "step_ms_off_all": [round(x, 4) for x in arms["off"]],
        "step_ms_on_all": [round(x, 4) for x in arms["on"]],
        "ab_overhead_frac": round((on - off) / off if off else 0.0, 4),
        "hook_us_per_tick": round(tick_us, 3),
        "hook_us_per_request": round(req_us, 3),
        "hook_us_per_tick_amortized": round(hook_us, 3),
        # the gated number: direct hook cost as a fraction of the
        # capacity-off median decode tick
        "overhead_frac": round(hook_us / (off * 1e3), 5) if off else 0.0,
    }


def _capacity_agreement(paged, rates):
    """Live-vs-offline max-rate agreement over the paged sweep.

    The live μ is read at the FIRST UNSUSTAINED rung when the ladder
    has one — there the decode lane is busy ≈ 100% of the window, so
    μ = X/U collapses to measured throughput, the honest capacity
    number.  (At comfortably-sustained rungs μ is a linear
    extrapolation from a mostly-idle lane — still useful for headroom
    trends, but the saturated read is the falsifiable one.)  Agreement
    holds when the live prediction, bucketed onto the rate ladder,
    lands within one rung of the offline max-sustainable verdict."""
    rungs = sorted(rates)
    offline = paged["max_sustainable_rate_req_per_s"]
    first_unsust = next((r for r in rungs
                         if not paged["rates"][f"{r:g}"]["sustained"]),
                        None)
    at = first_unsust if first_unsust is not None else rungs[-1]
    live = paged["rates"][f"{at:g}"]["capacity"]["predicted_max_rate_rps"]

    def rung_index(value):
        idx = -1
        for i, r in enumerate(rungs):
            if value >= r:
                idx = i
        return idx

    agree = None
    if live is not None and offline is not None:
        agree = abs(rung_index(live) - rungs.index(offline)) <= 1
    return {
        "rate_grid": rungs,
        "offline_max_sustainable_req_per_s": offline,
        "live_predicted_max_rate_rps": live,
        "measured_at_rate": at,
        "agreement_within_one_step": agree,
    }


# --- r19: speculative decoding × radix prefix cache 2x2 A/B -----------------

def _spec_workload(rng):
    """Shared system prompt + short per-request tails (the workload the
    radix cache exists for)."""
    prefix = rng.randint(1, 250, size=SPEC_PREFIX).astype(np.int32)
    tails = [rng.randint(1, 250, size=int(n)).astype(np.int32)
             for n in rng.randint(3, 8, size=SPEC_REQUESTS)]
    return [np.concatenate([prefix, t]) for t in tails]


def _spec_radix_lane(net, prompts, spec, radix):
    """One arm of the 2x2: sequential closed-loop submission (batch
    bucket pinned at 1, so all four arms decode the same determinstic
    greedy stream), a full warm pass (compiles every signature AND
    pre-populates the radix trie), then a measured pass under the
    retrace sanitizer with the compile gate = signature-count delta."""
    from mxnet_tpu import serving, telemetry
    from mxnet_tpu.telemetry import retrace
    from mxnet_tpu.telemetry.sinks import ListSink

    cfg = serving.ServerConfig(
        max_batch=1, max_length=SPEC_MAX_LEN, min_batch=1, min_length=8,
        queue_capacity=max(64, SPEC_REQUESTS), num_slots=2,
        max_new_tokens=SPEC_MAX_NEW, block_size=16,
        batch_window_ms=0.5, summary_every=1 << 30,
        draft_net=net if spec else None, spec_k=SPEC_K,
        radix_cache=radix)
    telemetry.enable(memory=False, cost=False)
    sink = ListSink()
    telemetry.add_sink(sink)
    retrace.enable(mode="warn")
    srv = serving.GenerativeServer(net, cfg)
    rep = srv.replicas[0]
    try:
        with srv:
            for p in prompts:                      # warm pass
                srv.generate(p, max_new_tokens=SPEC_MAX_NEW,
                             timeout=300.0)
            retrace.warm()
            sigs0 = len(rep.engine.compiled_signatures()) + (
                len(rep.draft.compiled_signatures()) if spec else 0)
            sink.records.clear()
            t0 = time.perf_counter()
            outs = [srv.generate(p, max_new_tokens=SPEC_MAX_NEW,
                                 timeout=300.0) for p in prompts]
            wall = time.perf_counter() - t0
            sigs1 = len(rep.engine.compiled_signatures()) + (
                len(rep.draft.compiled_signatures()) if spec else 0)
            stats = srv.stats()
        violations = retrace.violations()
    finally:
        retrace.disable()
        retrace.reset()
        telemetry.disable()
        telemetry.reset()
    recs = sorted((r for r in sink.records
                   if r.get("record") == "serving.request"
                   and r.get("status", "ok") == "ok"),
                  key=lambda r: r["request_id"])
    assert len(recs) == len(prompts)
    prefill_ms = [r["prefill_ms"] for r in recs]
    hit = [r.get("prefix_hit_tokens", 0) or 0 for r in recs]
    prefilled = [len(p) - h for p, h in zip(prompts, hit)]
    # target dispatches while decoding (verify counts as one step), per
    # generated token — the speculation claim's numerator
    fwd = [(r["done_step"] - r["joined_step"]) / SPEC_MAX_NEW
           for r in recs]
    out = {
        "speculative": bool(spec), "radix_cache": bool(radix),
        "requests": len(prompts), "wall_s": round(wall, 4),
        "ttft_ms": _percentiles([r["ttft_ms"] for r in recs]),
        "total_ms": _percentiles([r["total_ms"] for r in recs]),
        "prefill_ms_total": round(sum(prefill_ms), 3),
        "prefilled_tokens": int(sum(prefilled)),
        "prefix_hit_tokens": int(sum(hit)),
        "target_forwards_per_token": round(sum(fwd) / len(fwd), 4),
        "compile_sig_delta": sigs1 - sigs0,
        "retrace_violations": len(violations),
        "kv_cache": {k: stats["kv_cache"][k] for k in
                     ("shared_blocks", "peak_shared_blocks",
                      "blocks_in_use")},
    }
    if spec:
        out["accept_rate"] = stats["speculative"]["accept_rate"]
        out["spec_k"] = stats["speculative"]["k"]
    if radix:
        out["radix"] = stats["radix_cache"]
    return out, [list(map(int, o)) for o in outs]


def _spec_radix_sweep():
    from mxnet_tpu.models.llama import llama_tiny

    net = llama_tiny(max_seq_len=max(SPEC_MAX_LEN, 128))
    net.initialize()
    rng = np.random.RandomState(SEED + 31)
    prompts = _spec_workload(rng)
    lanes, tokens = {}, {}
    for spec in (False, True):
        for radix in (False, True):
            name = (("spec" if spec else "base")
                    + ("+radix" if radix else ""))
            lanes[name], tokens[name] = _spec_radix_lane(
                net, prompts, spec, radix)
    ref = tokens["base"]
    lanes["token_equal_across_arms"] = all(t == ref
                                           for t in tokens.values())
    return lanes


def main():
    workdir = tempfile.mkdtemp(prefix="serving_bench_")
    try:
        pred = _build_predictor(workdir)
        lanes = {lane: _run_lane(pred, lane)
                 for lane in ("closed_loop", "open_loop")}
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    gen, tracing_ab, capacity_ab, saturation_burst, gen_rates = \
        _gen_sweep()
    capacity_agreement = _capacity_agreement(gen["paged"], gen_rates)
    spec_radix = _spec_radix_sweep()
    from mxnet_tpu import serving

    from _compile_gate import compile_once_ok

    ceiling = len(serving.BucketPolicy(
        max_batch=MAX_BATCH, max_length=MAX_LENGTH,
        min_batch=1, min_length=8).signatures())

    record = {
        "metric": "serving_open_loop_p99_ms",
        "value": lanes["open_loop"]["total_ms"]["p99"],
        "unit": "ms",
        "requests_per_lane": REQUESTS,
        "clients": CLIENTS,
        "open_loop_rate_req_per_s": RATE,
        "bucket_config": {"max_batch": MAX_BATCH, "max_length": MAX_LENGTH,
                          "signature_ceiling": ceiling},
        "lanes": lanes,
        "generative": {
            "requests_per_rate": GEN_REQUESTS,
            "max_new_tokens": GEN_MAX_NEW,
            "ab_rate_req_per_s": GEN_RATE,
            "engines": gen,
        },
        "tracing_ab": tracing_ab,
        "capacity_ab": capacity_ab,
        "saturation_burst": saturation_burst,
        "capacity_agreement": capacity_agreement,
        "spec_radix": spec_radix,
        "acceptance": {
            "signatures_within_ceiling": compile_once_ok(lanes,
                                                         ceiling=ceiling),
            "batched": any(int(k) > 1 for l in lanes.values()
                           for k in l["batch_size_dist"]),
            "no_rejections": all(l["rejected"] == 0 for l in lanes.values()),
            "tracing_step_overhead_under_3pct":
                tracing_ab["overhead_frac"] < 0.03,
            # r19 speed multipliers (all four arms decode the identical
            # greedy stream — the A/B measures speed, never tokens)
            "spec_radix_token_equal":
                spec_radix["token_equal_across_arms"],
            "spec_forwards_per_token_under_half": (
                spec_radix["spec"]["target_forwards_per_token"] < 0.5
                and spec_radix["spec"]["accept_rate"] >= 0.7),
            "radix_prefilled_tokens_reduced_2x": (
                spec_radix["base"]["prefilled_tokens"]
                >= 2 * spec_radix["base+radix"]["prefilled_tokens"]),
            "radix_prefill_ms_reduced_2x": (
                spec_radix["base"]["prefill_ms_total"]
                >= 2 * spec_radix["base+radix"]["prefill_ms_total"]),
            "spec_radix_compile_once": all(
                spec_radix[arm]["compile_sig_delta"] == 0
                and spec_radix[arm]["retrace_violations"] == 0
                for arm in ("base", "spec", "base+radix", "spec+radix")),
            # r20 capacity observability
            "capacity_live_prediction_within_one_step":
                capacity_agreement["agreement_within_one_step"] is True,
            "saturation_precedes_queue_wait_breach":
                saturation_burst["saturation_precedes_breach"],
            "capacity_overhead_under_1pct":
                capacity_ab["overhead_frac"] < 0.01,
        },
        "platform": os.environ.get("JAX_PLATFORMS", "default"),
    }
    line = json.dumps(record, indent=2, default=str)
    print(line)
    out_path = os.environ.get(
        "MXT_SERVING_LATENCY_OUT",
        os.path.join(os.path.dirname(__file__), "..",
                     "SERVING_LATENCY_r20.json"))
    with open(out_path, "w") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    main()
