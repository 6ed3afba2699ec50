"""The span readers (``layer_metrics/tick_host_ms*.py``, ``itl_p99_ms.py``,
``prefill_*_share.py``, ``train_dispatch_host_ms.py``) on hand-built windows
over a planted lane log with known answers, on a window the log knows nothing
of, and end to end through ``run.py`` at tiny sizes (``tests/data_spans``)."""
import itertools
import json
import os

import pytest

import run as harness

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data_spans")
READERS = ["tick_host_ms", "tick_host_ms.doc", "itl_p99_ms", "prefill_busy_share",
           "prefill_gated_share", "train_dispatch_host_ms"]
# each planted window in a second of its own, far ahead of any real stamp
_bases = itertools.count(500_000_000, 1000)


def reader(name):
    return harness.load_module(
        os.path.join(os.path.dirname(HERE), "layer_metrics", name + ".py"),
        "span_reader_test_" + name.replace(".", "_"))


@pytest.fixture
def window():
    tracing = pytest.importorskip("mxnet_tpu.telemetry.tracing")
    if not hasattr(tracing, "lane_log"):
        pytest.skip("this program has no lane log")
    base = float(next(_bases))
    return tracing, base, {"t0_abs": base, "window_s": 1.0}


def plant_ticks(tracing, base, n, ids, period=0.1, fetch=0.08, first_seq=1,
                replica=0, n_adopted=0):
    for k in range(n):
        t = base + k * period
        tracing.lane_record(
            "decode.tick", replica=replica, seq=first_seq + k,
            n_active=len(ids), n_adopted=n_adopted if k == 0 else 0,
            n_finished=0, request_ids=tuple(ids), t_loop=t, t_lock=t + 0.001,
            t_disp0=t + 0.002, t_disp1=t + 0.008, t_tok=t + 0.008 + fetch,
            t_book=t + 0.095)


def plant_batch(tracing, base, seq, ids, t_start, t_first, replica=0):
    tracing.lane_record(
        "prefill.batch", replica=replica, seq=seq, request_ids=tuple(ids),
        n_tokens=8, bucket=(1, 8), radix_hit_tokens=0, t_start=base + t_start,
        t_disp1=base + t_start, t_ready=base + t_first, t_lock=base + t_first,
        t_commit1=base + t_first, t_first=base + t_first)


@pytest.mark.parametrize("name", ["tick_host_ms", "tick_host_ms.doc"])
def test_tick_host_is_the_period_less_the_wait_for_tokens(window, name):
    tracing, base, obs = window
    plant_ticks(tracing, base, 5, ids=(1, 2))
    # another replica's slower ticks join the same median: 100 - 80 and 200 - 80
    plant_ticks(tracing, base, 3, ids=(3,), period=0.2, replica=1)
    assert reader(name).read(obs) == pytest.approx(20.0, abs=1e-3)      # 4 of 20, 2 of 120
    import lane_spans
    rows = lane_spans.tick_phases_ms(obs)
    assert len(rows) == 6
    r = rows[0]
    assert [r[k] for k in ("adopt", "lock", "dispatch", "fetch", "book", "tail")] \
        == pytest.approx([1.0, 1.0, 6.0, 80.0, 7.0, 5.0], abs=1e-3)
    assert r["period"] == pytest.approx(100.0, abs=1e-3)
    assert r["host"] == pytest.approx(20.0, abs=1e-3)


def test_itl_pools_every_gap_of_every_request(window):
    tracing, base, obs = window
    plant_batch(tracing, base, 1, (11, 12), 0.0, 0.05)
    plant_ticks(tracing, base + 0.102, 3, ids=(11, 12), n_adopted=2)
    # tokens at .19, .29, .39: gaps 140, 100, 100 ms for each of two requests
    assert reader("itl_p99_ms").read(obs) == pytest.approx(140.0, abs=1e-3)
    import lane_spans
    gaps = lane_spans.token_gaps(obs)
    assert {k: [round(g, 6) for g in v] for k, v in gaps.items()} == {
        11: [0.14, 0.1, 0.1], 12: [0.14, 0.1, 0.1]}


def test_itl_counts_a_gap_where_it_ends_and_reads_none_under_speculation(window):
    tracing, base, obs = window
    # adopted before the window; its third and fourth tokens land inside
    plant_batch(tracing, base, 1, (21,), -0.4, -0.35)
    plant_ticks(tracing, base - 0.198, 4, ids=(21,), n_adopted=1)
    assert reader("itl_p99_ms").read(obs) == pytest.approx(100.0, abs=1e-3)
    import lane_spans
    assert [round(g, 6) for g in lane_spans.token_gaps(obs)[21]] == [0.1, 0.1]
    tracing.lane_record(
        "decode.tick", replica=0, seq=5, n_active=1, n_adopted=0, n_finished=0,
        request_ids=(21,), accepted={21: 3}, t_loop=base + 0.2, t_lock=base + 0.2,
        t_disp0=base + 0.2, t_disp1=base + 0.2, t_tok=base + 0.3, t_book=base + 0.3)
    assert reader("itl_p99_ms").read(obs) is None


def test_prefill_shares_clip_to_the_window(window):
    tracing, base, obs = window
    plant_batch(tracing, base, 1, (31,), -0.1, 0.05)      # 0.05 inside
    plant_batch(tracing, base, 2, (32,), 0.5, 0.7)        # 0.2
    plant_batch(tracing, base, 3, (33,), 0.95, 1.4)       # 0.05 inside
    tracing.lane_record("prefill.gated", replica=0, t0=base + 0.7,
                        t1=base + 0.8, reason="slot")
    tracing.lane_record("prefill.gated", replica=0, t0=base + 0.9,
                        t1=base + 1.3, reason="block")     # 0.1 inside
    assert reader("prefill_busy_share").read(obs) == pytest.approx(30.0, abs=1e-3)
    assert reader("prefill_gated_share").read(obs) == pytest.approx(20.0, abs=1e-3)


def test_gated_share_is_zero_where_batches_ran_ungated(window):
    tracing, base, obs = window
    plant_batch(tracing, base, 1, (41,), 0.1, 0.2)
    assert reader("prefill_gated_share").read(obs) == 0.0


def test_train_dispatch_host_is_entry_to_the_calls_return(window):
    tracing, base, obs = window
    for k, host in enumerate((0.010, 0.012, 0.030)):
        t = base + 0.1 + k * 0.3
        tracing.lane_record("train.dispatch", path="fused", seq=k + 1, k=8,
                            compiled=False, t0=t, t_args=t + 0.002,
                            t_disp1=t + host, t_end=t + host + 0.001)
    assert reader("train_dispatch_host_ms").read(obs) == pytest.approx(12.0, abs=1e-3)


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_is_none(name):
    """A window the log has no record of (as every window is on a program
    without a lane log): the reader returns None and does not raise."""
    assert reader(name).read({"t0_abs": -5e8, "window_s": 1.0}) is None


def _traced(capsys, cell):
    res = harness.run(["--workload", cell, "--seed", "4000000007", "--seconds", "2",
                       "--trace", "1"], require_tpu=False, data_dir=DATA)
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1]) == res
    assert res["correct"] is True and res["failed"] == 0
    return res["metrics"]


def test_traced_open_loop_reports_the_serving_span_metrics(capsys):
    got = _traced(capsys, "tiny_llama.open")
    want = {"tick_host_ms.doc", "itl_p99_ms", "prefill_busy_share",
            "prefill_gated_share"}
    # the readers of stamps report too; no TPU plane in a CPU trace, so the
    # trace readers find nothing and are left out
    assert set(got) == want | {"gen_late_p90_ms", "queue_wait_p90_ms"}
    assert all(got[m]["value"] > 0 for m in want - {"prefill_gated_share"})
    assert 0 <= got["prefill_gated_share"]["value"] < 100
    assert 0 < got["prefill_busy_share"]["value"] < 100
    bench = json.load(open(os.path.join(DATA, "BENCHMARK.json")))
    units = {m["name"]: m["unit"] for m in bench["per_layer"]}
    assert all(got[m]["unit"] == units[m] for m in got)


def test_traced_fused_training_reports_the_dispatch_metric(capsys):
    got = _traced(capsys, "tiny_bert.fused")
    assert set(got) == {"train_dispatch_host_ms"}
    assert got["train_dispatch_host_ms"]["value"] > 0


def test_every_new_entry_of_the_benchmark_has_its_reader():
    bench = json.load(open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                        "BENCHMARK.json")))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        mod, row = reader(name), by_name[name]
        assert (mod.NAME, mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
            name, row["unit"], row["source"], row["layer"], row["moves"])
        assert row["source"] == "program_span" and row["better"] == "lower"
