"""The readers of an admission (``layer_metrics/slot_turn_ms.py``,
``slot_wait_lane_ms.py``, ``handoff_wait_ms.py``, ``tick_stretch_ms.py``,
``ticks_behind_prefill_share.py``, ``free_slots_at_admit.py`` over
``turn_spans.py``) on hand-built windows over a planted lane log with known
answers, on a window the log knows nothing of, on a log whose records are an
older program's (no ``slot.turn``, no ``behind``, no ``free_slots``), and end to
end through ``run.py`` at tiny sizes (``tests/data_turns``: ``data_spans``' files
and a closed-loop cell)."""
import itertools
import json
import os

import pytest

import run as harness

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data_turns")
READERS = ["slot_turn_ms", "slot_wait_lane_ms", "handoff_wait_ms", "tick_stretch_ms",
           "ticks_behind_prefill_share", "free_slots_at_admit"]
SATURATED = ["mistral7b.chat_decode_sat", "lfm2_24b.chat_decode_sat",
             "sdar_30b.chat_decode_sat"]
# each planted window in a second of its own, far ahead of any real stamp and of
# test_span_readers' windows
_bases = itertools.count(700_000_000, 1000)


def reader(name):
    return harness.load_module(
        os.path.join(os.path.dirname(HERE), "layer_metrics", name + ".py"),
        "turn_reader_test_" + name.replace(".", "_"))


@pytest.fixture
def window():
    tracing = pytest.importorskip("mxnet_tpu.telemetry.tracing")
    if "slot.turn" not in getattr(tracing, "_LANE_SPAN", ()):
        pytest.skip("this program's lane log has no slot.turn records")
    base = float(next(_bases))
    return tracing, base, {"t0_abs": base, "window_s": 1.0}


def plant_turn(tracing, base, rid, t_free, t_start, t_first, t_adopt, t_tok):
    """Seconds from the window's start; ``t_free`` None: the ramp."""
    tracing.lane_record(
        "slot.turn", replica=0, slot=rid % 4, request_id=rid, batch=rid, tick=rid,
        freed_by=None if t_free is None else rid - 1,
        prev_request_id=None if t_free is None else rid - 4,
        t_free=None if t_free is None else base + t_free, t_start=base + t_start,
        t_first=base + t_first, t_handoff=base + t_first, t_adopt=base + t_adopt,
        t_tok=base + t_tok)


def plant_tick(tracing, base, seq, t, fetch, behind=None):
    fields = {} if behind is None else {"behind": behind}
    tracing.lane_record(
        "decode.tick", replica=0, seq=seq, n_active=2, n_adopted=0, n_finished=0,
        request_ids=(1, 2), t_loop=base + t, t_lock=base + t, t_disp0=base + t,
        t_disp1=base + t + 0.002, t_tok=base + t + 0.002 + fetch,
        t_book=base + t + 0.003 + fetch, **fields)


def plant_batch(tracing, base, seq, t_start, t_first, **fields):
    tracing.lane_record(
        "prefill.batch", replica=0, seq=seq, request_ids=(seq,), n_tokens=8,
        bucket=(1, 8), radix_hit_tokens=0, t_start=base + t_start,
        t_disp1=base + t_start, t_ready=base + t_first, t_lock=base + t_first,
        t_commit1=base + t_first, t_first=base + t_first, **fields)


def test_a_turn_is_release_to_first_token_and_its_wait_for_the_lane(window):
    tracing, base, obs = window
    # released inside the window: turns of 60, 70 and 110 ms, of which 10, 25
    # and 40 ms waiting for the prefill lane
    plant_turn(tracing, base, 11, 0.100, 0.110, 0.125, 0.135, 0.160)
    plant_turn(tracing, base, 12, 0.200, 0.225, 0.240, 0.250, 0.270)
    plant_turn(tracing, base, 13, 0.300, 0.340, 0.360, 0.385, 0.410)
    # the ramp (the slot held nothing), a slot that the warm-up left free, and a
    # turn whose first token lands past the window's end: not counted
    plant_turn(tracing, base, 14, None, 0.010, 0.020, 0.030, 0.040)
    plant_turn(tracing, base, 15, -0.500, 0.050, 0.060, 0.070, 0.080)
    plant_turn(tracing, base, 16, 0.900, 0.950, 0.980, 0.990, 1.020)
    assert reader("slot_turn_ms").read(obs) == pytest.approx(70.0, abs=1e-3)
    assert reader("slot_wait_lane_ms").read(obs) == pytest.approx(25.0, abs=1e-3)
    import turn_spans
    assert [t["request_id"] for t in turn_spans.turns(obs)] == [11, 12, 13, 14, 15]
    assert [t["request_id"] for t in turn_spans.turns(obs, released=True)] \
        == [11, 12, 13]


def test_a_hand_off_waits_for_the_decode_lanes_next_turn_ramp_included(window):
    tracing, base, obs = window
    plant_turn(tracing, base, 21, None, 0.010, 0.020, 0.024, 0.050)     # 4 ms
    plant_turn(tracing, base, 22, None, 0.030, 0.040, 0.052, 0.080)     # 12 ms
    plant_turn(tracing, base, 23, 0.100, 0.110, 0.120, 0.140, 0.170)    # 20 ms
    plant_turn(tracing, base, 24, 0.900, 0.950, 0.980, 0.999, 1.001)    # ends outside
    assert reader("handoff_wait_ms").read(obs) == pytest.approx(12.0, abs=1e-3)
    # no slot was released and turned inside the window but one
    assert reader("slot_turn_ms").read(obs) == pytest.approx(70.0, abs=1e-3)


def test_a_tick_behind_a_prefill_waits_longer_for_its_tokens(window):
    tracing, base, obs = window
    for k in range(7):                                  # 20 ms of fetch each
        plant_tick(tracing, base, 1 + k, 0.05 * k, 0.020, behind=())
    for k in range(4):                                  # 30, 32, 34, 36 ms
        plant_tick(tracing, base, 8 + k, 0.4 + 0.05 * k, 0.030 + 0.002 * k,
                   behind=(40 + k,))
    share = reader("ticks_behind_prefill_share")
    assert share.read(obs) == pytest.approx(100.0 * 4 / 11)
    assert reader("tick_stretch_ms").read(obs) is None      # 4 ticks behind: under 5
    plant_tick(tracing, base, 12, 0.6, 0.038, behind=(44, 45))
    assert reader("tick_stretch_ms").read(obs) == pytest.approx(34.0 - 20.0, abs=1e-3)
    assert share.read(obs) == pytest.approx(100.0 * 5 / 12)
    # a tick whose tokens land past the window's end belongs to the next window
    plant_tick(tracing, base, 13, 0.99, 0.060, behind=(46,))
    assert share.read(obs) == pytest.approx(100.0 * 5 / 12)


def test_free_slots_are_counted_where_the_batch_was_taken(window):
    tracing, base, obs = window
    plant_batch(tracing, base, 1, 0.10, 0.12, free_slots=1, queued=64)
    plant_batch(tracing, base, 2, 0.20, 0.22, free_slots=2, queued=63)
    plant_batch(tracing, base, 3, 0.30, 0.32, free_slots=3, queued=62)
    plant_batch(tracing, base, 4, 0.98, 1.02, free_slots=9, queued=61)  # ends outside
    plant_batch(tracing, base, 5, -0.05, 0.01, free_slots=4, queued=60)
    assert reader("free_slots_at_admit").read(obs) == pytest.approx(2.5)


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_is_none(name):
    """A window the log has no record of (as every window is on a program
    without a lane log): the reader returns None and does not raise."""
    assert reader(name).read({"t0_abs": -7e8, "window_s": 1.0}) is None


@pytest.mark.parametrize("name", READERS)
def test_an_older_programs_records_are_nothing_to_read(window, name):
    """Ticks and batches as the parent commit writes them, and no turns."""
    tracing, base, obs = window
    for k in range(8):
        plant_tick(tracing, base, 1 + k, 0.1 * k, 0.020)
    plant_batch(tracing, base, 1, 0.10, 0.12)
    plant_batch(tracing, base, 2, 0.20, 0.22)
    assert reader(name).read(obs) is None


def _traced(capsys, cell):
    res = harness.run(["--workload", cell, "--seed", "4000000007", "--seconds", "2",
                       "--trace", "1"], require_tpu=False, data_dir=DATA)
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1]) == res
    assert res["correct"] is True and res["failed"] == 0
    return res["metrics"]


def test_traced_closed_loop_reports_the_turn_metrics(capsys):
    got = _traced(capsys, "tiny_llama.closed")
    # the stretch needs 5 ticks of either kind in two seconds of a CPU's time
    always = set(READERS) - {"tick_stretch_ms"}
    assert always | {"decode_occupancy", "tick_host_ms"} <= set(got) \
        <= set(READERS) | {"decode_occupancy", "tick_host_ms"}
    turn, wait, hand = (got[m]["value"] for m in
                        ("slot_turn_ms", "slot_wait_lane_ms", "handoff_wait_ms"))
    assert 0 <= wait < turn and 0 < hand < turn
    assert 0 <= got["ticks_behind_prefill_share"]["value"] <= 100
    # 4 slots, a prefill batch of 1: at least the slot it fills stood free
    assert 1 <= got["free_slots_at_admit"]["value"] <= 4
    bench = json.load(open(os.path.join(DATA, "BENCHMARK.json")))
    units = {m["name"]: m["unit"] for m in bench["per_layer"]}
    assert all(got[m]["unit"] == units[m] for m in got)


def test_traced_open_loop_reports_nothing_new(capsys):
    assert not set(READERS) & set(_traced(capsys, "tiny_llama.open"))


def test_every_new_entry_of_the_benchmark_has_its_reader():
    bench = json.load(open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                        "BENCHMARK.json")))
    assert [m["name"] for m in bench["per_layer"][-len(READERS):]] == READERS
    sources = {"ticks_behind_prefill_share": "program_counter",
               "free_slots_at_admit": "program_counter"}
    for row in bench["per_layer"][-len(READERS):]:
        mod = reader(row["name"])
        assert (mod.NAME, mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
            row["name"], row["unit"], row["source"], row["layer"], row["moves"])
        assert row["source"] == sources.get(row["name"], "program_span")
        assert (row["layer"], row["moves"], row["better"], row["workloads"]) \
            == ("serving host", "out_tok_per_s", "lower", SATURATED)
