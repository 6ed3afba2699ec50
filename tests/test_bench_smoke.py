"""Driver-entry-point guards: bench.py must print ONE parseable JSON
line with the tracked keys, and __graft_entry__.entry() must return a
jittable fn — a silent break in either loses the round's numbers (the
driver runs them unattended on the chip)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")


@pytest.mark.slow
def test_bench_py_emits_one_json_line():
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", BENCH_STEPS="2", BENCH_WARMUP="1",
               BENCH_REPEATS="1", BENCH_BATCH="2", BENCH_IMAGE="64",
               BENCH_BERT_BATCH="2", BENCH_SEQ="16",
               BENCH_DATA_STEPS="2")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       capture_output=True, text=True, env=env,
                       cwd=REPO, timeout=1500)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("{")]
    assert len(lines) == 1, r.stdout
    rec = json.loads(lines[0])
    assert rec["metric"] == "resnet50_v1_train_images_per_sec_per_chip"
    assert rec["value"] > 0
    assert rec["vs_baseline"] is None
    assert "bert_base_samples_per_sec_per_chip" in rec, rec
    assert "resnet50_v1_recordio_images_per_sec_per_chip" in rec, rec


def test_dryrun_multichip_inprocess_smoke(capfd):
    """Core-lane guard (VERDICT r4 #10): drive the REAL
    __graft_entry__.dryrun_multichip entry path end-to-end on the test
    session's virtual mesh — no future round may ship a red
    MULTICHIP artifact undetected.  It runs on the devices the process
    has and refuses, rather than going elsewhere, when they are too
    few."""
    sys.path.insert(0, REPO)
    try:
        import __graft_entry__ as g
        g.dryrun_multichip(2)
        out = capfd.readouterr().out
        assert "dryrun_multichip(2)" in out and "OK" in out, out
        with pytest.raises(RuntimeError, match="jax has 8 cpu device"):
            g.dryrun_multichip(16)
    finally:
        sys.path.remove(REPO)


def test_serving_latency_bench_emits_artifact(tmp_path):
    """benchmark/serving_latency.py at toy load must produce the
    SERVING_LATENCY artifact with the predictor lanes, the generative
    r8-vs-paged rate sweep, percentile blocks, and a passing
    signature-ceiling acceptance — a silent break loses the round-11
    serving numbers."""
    out = tmp_path / "serving_latency.json"
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", BENCH_SERVING_REQUESTS="8",
               BENCH_SERVING_CLIENTS="2", BENCH_SERVING_RATE="500",
               BENCH_SERVING_MAX_BATCH="4", BENCH_SERVING_MAX_LEN="16",
               BENCH_SERVING_GEN_REQUESTS="6", BENCH_SERVING_GEN_RATE="50",
               BENCH_SERVING_GEN_RATES="50", BENCH_SERVING_GEN_MAX_NEW="4",
               BENCH_SERVING_AB_REQUESTS="4", BENCH_SERVING_AB_MAX_NEW="8",
               BENCH_SERVING_AB_REPEATS="2",
               BENCH_SERVING_SPEC_REQUESTS="3", BENCH_SERVING_SPEC_K="3",
               BENCH_SERVING_SPEC_MAX_NEW="6", BENCH_SERVING_SPEC_PREFIX="48",
               BENCH_SERVING_SPEC_MAX_LEN="128",
               BENCH_SERVING_CAP_BURST="12",
               BENCH_SERVING_CAP_AB_REQUESTS="4",
               BENCH_SERVING_CAP_AB_REPEATS="2",
               # the burst lane's saturation incident dumps the flight
               # record: here, not into the checkout the bench runs from
               MXNET_TRACE_DUMP=str(tmp_path / "flight_record.json"),
               MXT_SERVING_LATENCY_OUT=str(out))
    env.pop("XLA_FLAGS", None)   # the bench forces its own 8-device flag
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark",
                                      "serving_latency.py")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["metric"] == "serving_open_loop_p99_ms"
    assert rec["value"] > 0
    for lane in ("closed_loop", "open_loop"):
        ln = rec["lanes"][lane]
        assert ln["completed"] == 8
        assert ln["total_ms"]["p50"] <= ln["total_ms"]["p99"]
        assert ln["queue_wait_ms"]["p99"] is not None
        assert ln["throughput_req_per_s"] > 0
        assert sum(ln["batch_size_dist"].values()) == 8
        assert 1 <= ln["cache"]["signatures"] <= \
            rec["bucket_config"]["signature_ceiling"]
    assert rec["acceptance"]["signatures_within_ceiling"]
    # generative sweep: the server ran every rung, completed all
    # requests, and reports the saturation verdicts
    gen = rec["generative"]["engines"]
    assert list(gen) == ["paged"]
    assert gen["paged"]["replicas"] == 2     # dp2 on the virtual mesh
    for s in gen["paged"]["rates"].values():
        assert s["completed"] == 6 and s["rejected"] == 0
        assert s["total_ms"]["p50"] <= s["total_ms"]["p99"]
        assert s["ttft_ms"]["p99"] is not None
        # r12: TPOT percentiles + goodput-vs-SLO per rate rung
        assert s["tpot_ms"]["p99"] is not None
        assert 0.0 <= s["goodput_vs_slo"] <= 1.0
        assert s["slo_met"] <= s["completed"]
        assert s["tokens_per_s_per_chip"] > 0
        assert isinstance(s["sustained"], bool)
    assert gen["paged"]["kv_cache"]["occupancy"] == 0
    assert gen["paged"]["decode_steps"] > 0
    # r12: the tracing on/off A/B ran and reports a bounded overhead
    ab = rec["tracing_ab"]
    assert ab["step_ms_off"] > 0 and ab["step_ms_on"] > 0
    assert len(ab["step_ms_off_all"]) == len(ab["step_ms_on_all"]) == 2
    assert isinstance(ab["overhead_frac"], float)
    assert "tracing_step_overhead_under_3pct" in rec["acceptance"]
    # r19: the spec × radix 2x2 sweep ran, stayed token-exact and
    # compile-clean, and the robust gates hold even at toy knobs (the
    # wall-clock prefill-ms ratio is asserted only at default scale)
    arms = rec["spec_radix"]
    assert set(arms) >= {"base", "base+radix", "spec", "spec+radix"}
    assert arms["token_equal_across_arms"] is True
    # r20: the capacity lanes ran — the A/B has both arms, the burst
    # lane reached a verdict, the paged sweep carries live λ/μ/ρ reads
    # and the agreement block names its measurement rung (the TRUTH of
    # the gates is asserted at default scale, committed in the r20
    # artifact — toy knobs only prove the lanes execute end to end)
    cab = rec["capacity_ab"]
    assert cab["step_ms_off"] > 0 and cab["step_ms_on"] > 0
    assert len(cab["step_ms_off_all"]) == len(cab["step_ms_on_all"]) == 2
    burst = rec["saturation_burst"]
    assert isinstance(burst["saturation_precedes_breach"], bool)
    assert burst["saturation_events"] >= 0
    for s in gen["paged"]["rates"].values():
        assert "capacity" in s and "predicted_max_rate_rps" in s["capacity"]
    agree = rec["capacity_agreement"]
    assert agree["measured_at_rate"] in agree["rate_grid"]
    for key in ("capacity_live_prediction_within_one_step",
                "saturation_precedes_queue_wait_breach",
                "capacity_overhead_under_1pct"):
        assert key in rec["acceptance"]
    for name in ("base", "base+radix", "spec", "spec+radix"):
        arm = arms[name]
        assert arm["requests"] == 3
        assert arm["compile_sig_delta"] == 0
        assert arm["retrace_violations"] == 0
        # at drain only radix-cache-held blocks may remain live
        expect_blocks = (arm["radix"]["cached_tokens"] // 16
                         if "radix" in arm else 0)
        assert arm["kv_cache"]["blocks_in_use"] == expect_blocks
    assert arms["spec"]["target_forwards_per_token"] < 0.5
    assert arms["spec"]["accept_rate"] >= 0.7
    assert arms["base"]["prefilled_tokens"] >= \
        2 * arms["base+radix"]["prefilled_tokens"]
    assert arms["base+radix"]["prefix_hit_tokens"] > 0
    for key in ("spec_radix_token_equal",
                "spec_forwards_per_token_under_half",
                "radix_prefilled_tokens_reduced_2x",
                "spec_radix_compile_once"):
        assert rec["acceptance"][key], key


def test_sharded_step_bench_emits_artifact(tmp_path):
    """benchmark/sharded_step.py on the 8-device CPU mesh must emit the
    SHARDED_STEP artifact with both models x both meshes, zero
    steady-state compile misses, and the per-device-peak win for dp×tp —
    the round-9 evidence that partition_rules buys memory, not just
    placement metadata."""
    out = tmp_path / "sharded_step.json"
    env = dict(os.environ)
    env.update(BENCH_PLATFORM="cpu", BENCH_STEPS="3", BENCH_WARMUP="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               MXT_SHARDED_STEP_OUT=str(out))
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark",
                                      "sharded_step.py")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["metric"] == "sharded_step_per_device_peak_ratio"
    assert 0 < rec["value"] < 1
    for model in ("mlp", "llama_tiny"):
        pair = rec["lanes"][model]
        for lane in pair.values():
            assert lane["compile_miss_steady"] == 0
            assert lane["compile_miss_warmup"] > 0
            assert len(lane["peak_live_bytes_by_device"]) == 8
        assert pair["dp4xtp2"]["placement"]["sharded_params"] > 0
        assert pair["dp8"]["placement"]["sharded_params"] == 0
        assert pair["dp4xtp2"]["per_device_peak_max"] < \
            pair["dp8"]["per_device_peak_max"]
        assert all(rec["acceptance"][model].values())


@pytest.mark.slow
def test_dispatch_bench_retrace_sanitized_lane(tmp_path):
    """benchmark/dispatch_overhead.py under MXNET_SANITIZE_RETRACE=raise:
    every compile site is observed, each mode declares warmup over
    before its timed window, and the run completes — i.e. zero
    post-warmup retraces anywhere on the dispatch paths, enforced by the
    runtime sanitizer on top of the shared compile gates."""
    out = tmp_path / "dispatch.json"
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", BENCH_CHAIN_ITERS="2",
               BENCH_MLP_ITERS="2", BENCH_REPEATS="1",
               MXNET_SANITIZE_RETRACE="raise",
               BENCH_DISPATCH_OUT=str(out))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark",
                                      "dispatch_overhead.py")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert "RetraceError" not in r.stderr, r.stderr[-2000:]
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(out.read_text())
    # the shared gate ran on every mode: caches report zero steady misses
    assert rec["segment_cache"]["miss"] > 0       # warmup compiles exist
    assert rec["chain64_usec_per_op"]["hybridized"] > 0


def test_race_harness_report_is_green():
    """python -m tools.race --report: the deterministic-interleaving
    harness's self-check — every built-in scenario replays
    bit-identically from its seed, the seeded deadlock is witnessed,
    and the runtime lock-order graph stays clean."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-m", "tools.race", "--report"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    report = json.loads(r.stdout)
    assert report["ok"]
    by_name = {sc["name"]: sc for sc in report["scenarios"]}
    assert by_name["points"]["replay_identical"]
    assert by_name["points"]["seed_changes_schedule"]
    assert by_name["locks"]["replay_identical"]
    assert by_name["locks"]["order_violations"] == []
    assert by_name["deadlock"]["witnessed_at_seed"] is not None
    assert by_name["deadlock"]["replay_identical"]


def test_fleet_overhead_bench_emits_artifact(tmp_path):
    """benchmark/sharded_step.py --fleet-overhead must emit the
    FLEET_OVERHEAD artifact: the off/stride16/stride1 A/B lanes, the
    per-step hook microbench, the stride-1 exchange cost, and a passing
    <1% acceptance — the round-13 evidence that fleet observability is
    free at the default stride."""
    out = tmp_path / "fleet_overhead.json"
    env = dict(os.environ)
    env.update(BENCH_PLATFORM="cpu", BENCH_STEPS="3", BENCH_WARMUP="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               MXT_FLEET_OVERHEAD_OUT=str(out))
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark",
                                      "sharded_step.py"),
         "--fleet-overhead"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["metric"] == "fleet_overhead_pct_stride16"
    assert 0 <= rec["value"] < 1.0
    assert set(rec["lanes"]) == {"off", "stride16", "stride1"}
    for lane in rec["lanes"].values():
        assert lane["step_ms_median"] > 0
    assert rec["lanes"]["off"]["fleet_exchanges"] == 0
    # stride 1 exchanges every measured step and reports its cost
    assert rec["lanes"]["stride1"]["fleet_exchanges"] >= 3
    assert rec["exchange_ms_stride1"] is not None
    assert rec["hook_ms_stride16"] > 0
    assert rec["hook_ms_stride1"] >= rec["hook_ms_stride16"] * 0.5
    assert rec["acceptance"]["fleet_overhead_under_1pct"]


def test_numerics_overhead_bench_emits_artifact(tmp_path):
    """benchmark/sharded_step.py --numerics-overhead must emit the
    NUMERICS_OVERHEAD artifact: the off / stats / stats+capture-armed
    A/B lanes over llama_tiny (the tapped model), the per-step
    record_compiled+step_summary microbench, and a passing <1%
    acceptance at stride 16 — the round-17 evidence that in-compile
    tensor stats are free at the default stride."""
    out = tmp_path / "numerics_overhead.json"
    env = dict(os.environ)
    env.update(BENCH_PLATFORM="cpu", BENCH_STEPS="3", BENCH_WARMUP="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               MXT_NUMERICS_OVERHEAD_OUT=str(out))
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark",
                                      "sharded_step.py"),
         "--numerics-overhead"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["metric"] == "numerics_overhead_pct_stride16"
    assert 0 <= rec["value"] < 1.0
    assert set(rec["lanes"]) == {"off", "stats", "stats_capture_armed"}
    for lane in rec["lanes"].values():
        assert lane["step_ms_median"] > 0
    # the off lane must not harvest anything; the stats lanes must
    # actually land per-path stat bundles (taps + grad/update stats)
    assert rec["lanes"]["off"]["harvested_paths"] == 0
    assert rec["lanes"]["stats"]["harvested_paths"] > 0
    assert rec["lanes"]["stats_capture_armed"]["harvested_paths"] > 0
    assert rec["lanes"]["stats_capture_armed"]["capture_armed"]
    assert rec["hook_ms_stride16"] > 0
    # stride 1 materializes every step; stride 16 must not cost more
    assert rec["hook_ms_stride1"] >= rec["hook_ms_stride16"] * 0.5
    assert rec["acceptance"]["numerics_overhead_under_1pct"]


def test_data_plane_bench_emits_artifact(tmp_path):
    """benchmark/input_pipeline.py --data-plane on the 8-device CPU mesh
    must emit the DATA_PLANE artifact with both trainer-fed lanes (image
    + packed LLM), steady-state data_wait_ms p50 ~ 0 (prefetch overlap
    holds), >= 85% packing efficiency, and zero steady compile misses
    (ONE (B, T) signature over a mixed-length corpus) — the round-14
    evidence the streaming data plane keeps a stock Trainer fed."""
    out = tmp_path / "data_plane.json"
    env = dict(os.environ)
    env.update(BENCH_PLATFORM="cpu", BENCH_STEPS="3", BENCH_WARMUP="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               MXT_DATA_PLANE_OUT=str(out))
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark",
                                      "input_pipeline.py"),
         "--data-plane"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["metric"] == "data_plane_data_wait_ms_p50"
    assert set(rec["lanes"]) == {"image", "packed_llm"}
    for lane in rec["lanes"].values():
        assert lane["compile_miss_steady"] == 0
        assert lane["compile_miss_warmup"] > 0
        assert lane["data_wait_ms_p50"] <= lane["data_wait_ms_p99"]
        assert lane["step_ms_median"] > 0
    assert rec["lanes"]["image"]["images_per_sec"] > 0
    pk = rec["lanes"]["packed_llm"]
    assert pk["packed_tokens_per_sec"] > 0
    assert pk["packing"]["efficiency"] >= 0.85
    assert pk["packing"]["docs_packed"] > 0
    assert all(rec["acceptance"].values()), rec["acceptance"]


def test_remat_ab_bench_emits_artifact(tmp_path):
    """benchmark/remat_ab.py at toy step counts must emit the REMAT_AB
    artifact with every tier lane for both models, bit-identical loss
    trajectories, zero steady-state compile misses, and an auto lane
    that resolved to a concrete tier — the round-10 evidence that the
    remat policy engine recomputes without renumbering."""
    out = tmp_path / "remat_ab.json"
    env = dict(os.environ)
    env.update(BENCH_PLATFORM="cpu", BENCH_STEPS="3", BENCH_WARMUP="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               MXT_REMAT_AB_OUT=str(out))
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "remat_ab.py")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["metric"] == "remat_auto_vs_layer_step_ratio"
    assert rec["value"] > 0
    for model in ("mlp", "llama_tiny"):
        by_tier = rec["lanes"][model]
        assert set(by_tier) == {"none", "dots", "layer", "auto"}
        ref = by_tier["layer"]["loss_trajectory"]
        for lane in by_tier.values():
            assert lane["compile_miss_steady"] == 0
            assert lane["compile_miss_warmup"] > 0
            assert lane["loss_trajectory"] == ref
        # per-layer checkpointing saves strictly fewer residuals to the
        # backward than saving everything
        assert by_tier["layer"]["bwd_residual_bytes_max"] < \
            by_tier["none"]["bwd_residual_bytes_max"]
        auto = by_tier["auto"]
        assert auto["resolved_tier"] in ("none", "dots", "layer")
        assert auto["policy_mode"] == "auto"
        assert auto["remat_policy_jsonl_field"] == auto["resolved_tier"]
        assert all(rec["acceptance"][model].values())


def test_telemetry_disabled_step_overhead():
    """Telemetry instrumentation rides the trainer/CachedOp/kvstore hot
    path; disabled it must be within noise of the seed path.  Compare
    the shipped (instrumented, telemetry off) step loop against the same
    loop with every recorder stubbed to a bare no-op — best-of-repeats
    to shed scheduler noise; the generous ratio bound catches a lock or
    allocation sneaking onto the disabled path, not microsecond drift."""
    import time

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd, telemetry

    telemetry.disable()
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"))
    net.add(gluon.nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05})
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(8, 6).astype(np.float32))
    y = nd.array(rng.randint(0, 4, (8,)))

    def steps(n):
        for _ in range(n):
            with autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            trainer.step(8)
        loss.wait_to_read()

    def best_of(repeats, n):
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            steps(n)
            best = min(best, time.perf_counter() - t0)
        return best

    steps(3)  # pay trace+compile before any timing
    instrumented = best_of(3, 20)

    class _Null:
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    null = _Null()
    noop = lambda *a, **k: None  # noqa: E731
    saved = {name: getattr(telemetry, name)
             for name in ("span", "count", "gauge", "is_enabled")}
    try:
        telemetry.span = lambda *a, **k: null
        telemetry.count = noop
        telemetry.gauge = noop
        telemetry.is_enabled = lambda: False
        steps(3)
        stubbed = best_of(3, 20)
    finally:
        for name, fn in saved.items():
            setattr(telemetry, name, fn)

    assert instrumented < stubbed * 3 + 0.01, (instrumented, stubbed)


@pytest.mark.slow
def test_graft_entry_compiles():
    """entry() returns (fn, args) that jit-lowers (what the driver
    compile-checks single-chip)."""
    code = (
        "import os, sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=2'\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import __graft_entry__ as g\n"
        "fn, args = g.entry()\n"
        "jax.jit(fn).lower(*args)\n"
        "print('ENTRY_OK')\n"
    )
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True, env=env,
                       timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "ENTRY_OK" in r.stdout


# --- perf gate: the regression ledger over committed artifacts ---------------

PERF_GATE = os.path.join(REPO, "tools", "perf_gate.py")


def _gate(*args):
    return subprocess.run([sys.executable, PERF_GATE, *args],
                          capture_output=True, text=True, timeout=120)


def test_perf_gate_committed_artifacts_pass():
    """Every family's latest committed FAMILY_rNN.json must clear the
    committed benchmark/PERF_BASELINE.json manifest — the ledger's
    standing acceptance claim."""
    r = _gate("--check-all")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "perf_gate: clean" in r.stdout


def test_perf_gate_trend_reports_every_family():
    r = _gate("--trend", "--json")
    assert r.returncode == 0, r.stderr
    entries = json.loads(r.stdout)
    fams = {e["family"] for e in entries}
    assert {"SERVING_LATENCY", "FLEET_OVERHEAD", "BENCH"} <= fams
    sl = next(e for e in entries if e["family"] == "SERVING_LATENCY")
    assert sl["direction"] == "lower"
    assert [rnd for rnd, _ in sl["rounds"]] == sorted(
        rnd for rnd, _ in sl["rounds"])


def test_perf_gate_fails_injected_regression(tmp_path):
    """Toy corpus: a 2x latency regression (and separately a flipped
    acceptance flag) must fail the gate; an in-noise wobble passes."""
    base = {"metric": "toy_latency_ms", "value": 10.0, "unit": "ms",
            "acceptance": {"compile_once": True}}
    (tmp_path / "TOY_LATENCY_r01.json").write_text(json.dumps(base))
    manifest = str(tmp_path / "PERF_BASELINE.json")
    r = _gate("--update-baseline", "--root", str(tmp_path),
              "--baseline", manifest)
    assert r.returncode == 0, r.stdout + r.stderr

    # in-noise wobble (+10% on a 25% band): passes
    ok = dict(base, value=11.0)
    p_ok = tmp_path / "TOY_LATENCY_r02.json"
    p_ok.write_text(json.dumps(ok))
    r = _gate("--check", str(p_ok), "--baseline", manifest)
    assert r.returncode == 0, r.stdout + r.stderr

    # 2x latency: fails on the metric gate
    slow = dict(base, value=20.0)
    p_slow = tmp_path / "TOY_LATENCY_r03.json"
    p_slow.write_text(json.dumps(slow))
    r = _gate("--check", str(p_slow), "--baseline", manifest)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "REGRESSION" in r.stdout and "toy_latency_ms" in r.stdout

    # lost acceptance flag: fails even with the metric flat
    lost = dict(base, acceptance={"compile_once": False})
    p_lost = tmp_path / "TOY_LATENCY_r04.json"
    p_lost.write_text(json.dumps(lost))
    r = _gate("--check", str(p_lost), "--baseline", manifest)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "compile_once" in r.stdout

    # min-of-repeats: a noisy repeat list whose BEST value is in-band
    # passes (the gate compares best-of, not worst-of)
    noisy = dict(base, value=30.0, value_all=[30.0, 10.4, 14.0])
    p_noisy = tmp_path / "TOY_LATENCY_r05.json"
    p_noisy.write_text(json.dumps(noisy))
    r = _gate("--check", str(p_noisy), "--baseline", manifest)
    assert r.returncode == 0, r.stdout + r.stderr
