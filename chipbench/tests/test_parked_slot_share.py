"""``layer_metrics/parked_slot_share.py`` on a recorded lane log
(``testdata/lane_log_parked.json``: a tiny model through ``GenerativeServer``
on the CPU with a pool that parks) and on the same records as a program
without growth on demand writes them."""
import itertools
import json
import os

import pytest

import run as harness

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
# the planted window in a second of its own, far ahead of any real stamp
_bases = itertools.count(700_000_000, 1000)


@pytest.fixture
def recorded():
    tracing = pytest.importorskip("mxnet_tpu.telemetry.tracing")
    if not hasattr(tracing, "lane_log"):
        pytest.skip("this program has no lane log")
    with open(os.path.join(BENCH, "testdata", "lane_log_parked.json")) as f:
        doc = json.load(f)
    return tracing, float(next(_bases)), doc


def _plant(tracing, base, records, drop=()):
    for rec in records:
        rec = {k: base + v if k.startswith("t_") else v
               for k, v in rec.items() if k not in drop and k != "kind"}
        tracing.lane_record("decode.tick", **rec)


def _reader():
    return harness.load_module(
        os.path.join(BENCH, "layer_metrics", "parked_slot_share.py"),
        "parked_slot_share_test")


def test_parked_slot_share_of_a_recorded_window(recorded):
    tracing, base, doc = recorded
    _plant(tracing, base, doc["records"])
    recs = doc["records"]
    span = max(r["t_book"] for r in recs) + 1.0
    obs = {"t0_abs": base - 0.5, "window_s": span, "num_slots": doc["num_slots"]}
    # 89 parked slots over 142 ticks of 3 slots
    assert (len(recs), sum(r["n_parked"] for r in recs)) == (142, 89)
    assert _reader().read(obs) == pytest.approx(100.0 * 89 / (142 * 3))
    # a window that holds the first 40 ticks only
    cut = recs[40]["t_loop"]
    obs = {"t0_abs": base - 0.5, "window_s": cut + 0.5, "num_slots": 3}
    assert _reader().read(obs) == pytest.approx(
        100.0 * sum(r["n_parked"] for r in recs[:40]) / (40 * 3))


def test_nothing_to_read_without_n_parked(recorded):
    tracing, base, doc = recorded
    _plant(tracing, base, doc["records"], drop=("n_parked",))
    obs = {"t0_abs": base - 0.5, "window_s": 60.0, "num_slots": 3}
    assert _reader().read(obs) is None
    # nor on a window the log knows nothing of
    assert _reader().read({"t0_abs": base + 500.0, "window_s": 1.0,
                           "num_slots": 3}) is None


def test_the_benchmark_lists_the_reader_for_the_pool_cell():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    mod = _reader()
    assert {m["name"]: m for m in bench["per_layer"]}[mod.NAME] == {
        "name": mod.NAME, "unit": mod.UNIT, "better": "lower",
        "source": mod.SOURCE, "layer": mod.LAYER, "moves": mod.MOVES,
        "workloads": ["ouro_2_6b.reason_decode_sat"]}
