"""The readers of the host's two clocks (``layer_metrics/tick_offcpu_ms.py``,
``stall_share.gc.py`` / ``.own`` / ``.offcpu`` / ``.doc``, ``gc_pause_max_ms.py``
over ``stall_spans.py``) on a recorded lane log with known answers
(``tests/data_stalls/lane_log.json``), on the same log as an older program
writes it (``lane_log_parent.json``: no ``c_*`` field, no ``gc.pause``), on a
window the log knows nothing of, and end to end through ``run.py`` at tiny sizes
(``tests/data_stalls``: ``data_turns``' served cells with the six entries).

The recorded second holds 21 ticks from 50 ms on, 20 ms a turn: adopt 0.5, the
lock 0.5, dispatch 2.0, the wait for tokens 14.0, booking 1.0, tail 2.0, of which
the lane thread computes 0.4 / 0 / 1.2 / 0.1 / 0.9 / 0.5.  So a turn's host part
is 20 - 14 = 6.0 ms of wall and 3.1 - 0.1 = 3.0 of the lane's CPU (the reader
takes means over the turns that did not stall: the clock ticks).  Four turns are
slowed: turn 4's booking by 60 ms under a collection of 50 ms; turn 8's booking
by 40 ms, 38 of them the lane's own CPU; turn 12's tail by 80 ms and turn 16's
dispatch by 30 ms with no collection and 0 and 1 ms of the lane's CPU.  Turn 18
queues nothing.  Eight batches of 30 ms (4 the host's) start every 100 ms; the
sixth waits 50 ms longer for the device lock.  Three more pauses: 2 ms at 0.9 s,
30 ms across the window's start, 200 ms before it.
"""
import itertools
import json
import os

import pytest

import run as harness

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data_stalls")
SATURATED = ["mistral7b.chat_decode_sat", "lfm2_24b.chat_decode_sat",
             "sdar_30b.chat_decode_sat", "qwen3_next.chat_decode_sat",
             "ouro_2_6b.reason_decode_sat", "nemotron3_super.chat_decode_sat"]
OPEN_LOOP = ["mistral7b.doc_prefill", "glm5.longdoc_prefill"]
# reader -> (unit, the end-to-end metric it moves, its cells, the recorded second's
# reading)
READERS = {
    # the 16 turns that did not stall: 6.0 of wall less 3.0 of CPU in 15, 3.5
    # less 1.8 in turn 18
    "tick_offcpu_ms": ("ms", "out_tok_per_s", SATURATED, (15 * 3.0 + 1.7) / 16),
    # turn 4: 60 ms of a window of 1 s; the pause overlaps 50 of them
    "stall_share.gc": ("%", "out_tok_per_s", SATURATED, 6.0),
    # turn 8: 40 ms, 38 of them the lane's own
    "stall_share.own": ("%", "out_tok_per_s", SATURATED, 4.0),
    # turn 12's 80 ms, turn 16's 30 and the sixth batch's 50
    "stall_share.offcpu": ("%", "out_tok_per_s", SATURATED, 16.0),
    # the collection under turn 4; the one across the window's start is 30
    "gc_pause_max_ms": ("ms", "out_tok_per_s", SATURATED, 50.0),
    # 60 + 40 + 80 + 30 + 50 ms
    "stall_share.doc": ("%", "tpot_p90_ms", OPEN_LOOP, 26.0),
}
# each planted window in a thousand seconds of its own, far ahead of any real
# stamp and of the other reader tests' windows
_bases = itertools.count(1_300_000_000, 1000)


def reader(name):
    return harness.load_module(
        os.path.join(os.path.dirname(HERE), "layer_metrics", name + ".py"),
        "stall_reader_test_" + name.replace(".", "_"))


def plant(recorded):
    """The recorded log's records into the program's lane log, its seconds
    counted from a base of this call's own -> the window's ``obs``."""
    tracing = pytest.importorskip("mxnet_tpu.telemetry.tracing")
    if not hasattr(tracing, "stalls"):
        pytest.skip("this program names no stalls")
    base = float(next(_bases))
    with open(os.path.join(DATA, recorded)) as f:
        for rec in json.load(f)["records"]:
            rec = {k: base + v if k[:2] == "t_" or k in ("t0", "t1") else v
                   for k, v in rec.items()}
            tracing.lane_record(rec.pop("kind"), **rec)
    return {"t0_abs": base, "window_s": 1.0}


@pytest.mark.parametrize("name", READERS)
def test_the_recorded_second_reads_what_the_hand_worked_out(name):
    assert reader(name).read(plant("lane_log.json")) == pytest.approx(
        READERS[name][3], abs=1e-3)


def test_a_window_without_a_stall_or_a_pause_reads_zero_not_none():
    """The second after the recorded one's ticks: the last three batches, none
    slowed, and the pause of 2 ms."""
    obs = plant("lane_log.json")
    obs.update(t0_abs=obs["t0_abs"] + 0.70, window_s=0.25)
    for cause in ("gc", "own", "offcpu", "doc"):
        assert reader("stall_share." + cause).read(obs) == 0.0
    assert reader("gc_pause_max_ms").read(obs) == pytest.approx(2.0, abs=1e-3)
    obs.update(t0_abs=obs["t0_abs"] + 0.001, window_s=0.19)   # before the pause
    assert reader("gc_pause_max_ms").read(obs) == 0.0
    assert reader("tick_offcpu_ms").read(obs) is None      # no tick in it


@pytest.mark.parametrize("name", READERS)
def test_an_older_programs_records_are_nothing_to_read(name):
    assert reader(name).read(plant("lane_log_parent.json")) is None


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_is_none(name):
    """A window the log has no record of (as every window is on a program
    without a lane log): the reader returns None and does not raise."""
    assert reader(name).read({"t0_abs": -7e8, "window_s": 1.0}) is None


def _traced(capsys, cell):
    res = harness.run(["--workload", cell, "--seed", "4000000007", "--seconds", "2",
                       "--trace", "1"], require_tpu=False, data_dir=DATA)
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1]) == res
    assert res["correct"] is True and res["failed"] == 0
    return res["metrics"]


def test_traced_closed_loop_reports_the_five(capsys):
    got = _traced(capsys, "tiny_llama.closed")
    five = {n for n, row in READERS.items() if row[1] == "out_tok_per_s"}
    assert five <= set(got) and "stall_share.doc" not in got
    # a CPU stamp is taken a fraction of a microsecond behind its wall stamp
    assert -0.01 <= got["tick_offcpu_ms"]["value"] <= got["tick_host_ms"]["value"]
    assert all(0.0 <= got["stall_share." + c]["value"] <= 100.0
               for c in ("gc", "own", "offcpu"))
    assert got["gc_pause_max_ms"]["value"] == 0.0 \
        or got["gc_pause_max_ms"]["value"] >= 1.0
    assert all(got[n]["unit"] == READERS[n][0] for n in five)


def test_traced_open_loop_reports_the_sixth_alone(capsys):
    got = _traced(capsys, "tiny_llama.open")
    assert set(READERS) & set(got) == {"stall_share.doc"}
    assert 0.0 <= got["stall_share.doc"]["value"] <= 100.0


@pytest.mark.parametrize("name", READERS)
def test_every_new_entry_of_the_benchmark_has_its_reader(name):
    """Pinned by name, wherever in ``per_layer`` a later PR leaves it."""
    bench = json.load(open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                        "BENCHMARK.json")))
    (row,) = [m for m in bench["per_layer"] if m["name"] == name]
    unit, moves, cells, _reading = READERS[name]
    mod = reader(name)
    assert (mod.NAME, mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        row["name"], row["unit"], row["source"], row["layer"], row["moves"])
    assert row == {"name": name, "unit": unit, "better": "lower",
                   "source": "program_span", "layer": "serving host",
                   "moves": moves, "workloads": row["workloads"]}
    # a later cell may be appended; these stay, in this order
    assert row["workloads"][:len(cells)] == cells
