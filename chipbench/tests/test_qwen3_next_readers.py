"""The linear-attention family's readers (``layer_metrics/linear_step_roofline.py``,
``state_bytes_share.py``, ``gated_delta_step_roofline.py``): on a window the
lane log knows nothing of, on a made-up window with a trace (the arithmetic),
and end to end through ``run.py`` at a tiny size (``tests/data_qwen3_next``)."""
import json
import os

import pytest

import run as harness

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data_qwen3_next")
NAMES = ["linear_step_roofline", "state_bytes_share", "gated_delta_step_roofline"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    return harness.load_module(
        os.path.join(os.path.dirname(HERE), "layer_metrics", name + ".py"),
        "qwen3_next_reader_test_" + name)


def _obs(config):
    return {"t0_abs": 900_000_000.0, "window_s": 1.0, "config": config,
            "chips": 1, "peaks": PEAKS, "trace": None, "trace_host_window": None,
            "programs": {"step": "^jit__step_fn", "prefill": "^jit__prefill_fn"}}


@pytest.mark.parametrize("name", NAMES)
def test_a_window_without_the_counters_reads_nothing(name):
    cell = json.load(open(os.path.join(
        os.path.dirname(HERE), "configs", "qwen3_next_80b_l8_ep4.json")))
    for config in ({}, cell):       # another family's config; no tick in the log
        assert reader(name).read(_obs(config)) is None


def test_the_shares_of_a_made_up_window(monkeypatch):
    """256 full slots at a mean of 800 tokens, every held expert touched, a
    step of 30 ms of which the kernel's six calls take 12: the table's 17.8 ms
    of bytes is 59% of the step, the state 45% of the bytes, and 6 x 2 x 512
    MiB over the bandwidth 65.5% of the kernel's time."""
    import lane_spans

    cell = json.load(open(os.path.join(
        os.path.dirname(HERE), "configs", "qwen3_next_80b_l8_ep4.json")))
    fb = harness.load_module(os.path.join(
        os.path.dirname(HERE), "flops_bytes", "qwen3_next_decode_step.py"),
        "qwen3_next_reader_test_flops")
    state = 2 * 256 * fb.state_bytes_per_slot(cell)
    # the router's 512 experts a layer were touched; 128 a layer are held
    tick = {"n_active": 256, "kv_tokens": 256 * 800, "experts_touched": 8 * 509,
            "experts_touched_held": 8 * 128, "state_bytes": state}
    monkeypatch.setattr(lane_spans, "records",
                        lambda obs, kind, from_start=False:
                        [tick] * 10 if kind == "decode.tick" else [])
    obs = _obs(cell)
    obs["trace"] = {"chips": {0: {
        "modules": {"jit__step_fn": [0.030] * 10},
        "op_seconds": {"gated_delta_step.1": 0.06, "gated_delta_step.2": 0.06,
                       "fusion.3": 0.1},
        "op_counts": {"gated_delta_step.1": 30, "gated_delta_step.2": 30,
                      "fusion.3": 7}}}}
    need = fb.bytes_needed(cell, 256, 256 * 800, 8 * 128, state)
    assert reader("linear_step_roofline").read(obs) == pytest.approx(
        100 * need / 819e9 / 0.030)
    assert 55 < reader("linear_step_roofline").read(obs) < 63
    assert reader("state_bytes_share").read(obs) == pytest.approx(
        100 * state / need)
    assert 44 < reader("state_bytes_share").read(obs) < 46
    kernel = reader("gated_delta_step_roofline").read(obs)
    assert kernel == pytest.approx(
        100 * 60 * 2 * 256 * 2 * 2 ** 20 / 819e9 / 0.12)
    assert 65 < kernel < 66
    # without the held count the reader takes no more than the bank holds
    del tick["experts_touched_held"]
    assert reader("state_bytes_share").read(obs) == pytest.approx(
        100 * state / need)


def test_rehearsal_reports_the_counter_reader(capsys, monkeypatch, tmp_path):
    # a trace directory of its own: the checkout's ``.chipbench_trace`` is
    # shared by every process that runs a traced rehearsal, and one that
    # starts elsewhere removes it under this one
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    res = harness.run(["--workload", "tiny_qwen3_next.closed", "--seed",
                       "2147483659", "--seconds", "2", "--trace", "1"],
                      require_tpu=False, data_dir=DATA)
    capsys.readouterr()
    assert res["correct"] is True and res["failed"] == 0
    # the tiny model's state is 3 x (a ring of 3 x 256 and 4 heads of 16 x 16)
    # a slot beside 1.3 MB of weights: a small share, and a share
    assert 0 < res["metrics"]["state_bytes_share"]["value"] < 100
    assert "linear_step_roofline" not in res["metrics"]      # no TPU plane here
    assert "gated_delta_step_roofline" not in res["metrics"]
