"""CPU rehearsals of ``run.py`` at tiny sizes, one per traffic driver (and on
four virtual devices for dp4): the last line's keys; the lower-precision
control read beside the served tokens; and the timed path broken underneath,
which has to come out as not correct.  The sizes and limits are ``tests/data``'s."""
import json
import os

import pytest

import run as harness

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELLS = ["tiny_llama.closed", "tiny_llama.open", "tiny_bert.fused", "tiny_bert.dp4"]


def _run(capsys, cell, *extra):
    res = harness.run(["--workload", cell, "--seed", "4000000007", "--seconds", "2",
                       *extra], require_tpu=False, data_dir=DATA)
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == res
    compared = {}
    for line in out:
        if line.startswith("compared: "):
            name, rest = line[len("compared: "):].split(" = ")
            compared[name] = float(rest.split(" limit ")[0])
    return res, compared


@pytest.mark.parametrize("cell", CELLS)
def test_last_line(capsys, cell):
    res, compared = _run(capsys, cell, "--trace", "0")
    assert set(res) == {"correct", "attempted", "failed", "metrics", "device"}
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    bench = json.load(open(os.path.join(DATA, "BENCHMARK.json")))
    want = {m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}
    assert set(res["metrics"]) == want and "setup_s" in want and len(want) >= 2
    assert all(m["value"] > 0 for m in res["metrics"].values())


def test_traced_run_reports_layer_metrics(capsys):
    res, _ = _run(capsys, "tiny_llama.open", "--trace", "1")
    assert res["correct"] is True
    # no TPU plane in a CPU trace: the trace readers find nothing and are left
    # out; the readers of stamps and counters report
    assert set(res["metrics"]) == {"gen_late_p90_ms", "queue_wait_p90_ms"}


def test_no_chip_is_an_error(capsys):
    with pytest.raises(SystemExit) as exc:
        harness.run(["--workload", "tiny_bert.fused", "--seed", "1", "--seconds", "1"],
                    data_dir=DATA)
    assert "needs a TPU" in str(exc.value)
    assert not capsys.readouterr().out.strip().startswith("{")


def test_control_reads_above_the_serving_limit(capsys):
    """The float8 reference in the program's place: its widest gap is over
    the limit that the program's served tokens are held to."""
    res, compared = _run(capsys, "tiny_llama.closed", "--control", "1")
    limit = json.load(open(os.path.join(DATA, "traffic", "closed.json")))["check"]["gap_limit"]
    assert res["correct"] is True
    assert compared["served_logit_gap_max"] <= limit < compared["control.served_logit_gap_max"]


def test_part_of_the_batch_left_out_is_not_correct(capsys, monkeypatch):
    """Half of every step's rows fall out of the loss underneath the trainer.
    (The float8 control has no test for training: with the published dropout
    the two sides' masks differ, and that noise is larger than float8's; see
    PERF.md section 4.)"""
    from mxnet_tpu.ops import nn_ops

    whole = nn_ops._softmax_ce_sum
    monkeypatch.setattr(nn_ops, "_softmax_ce_sum", lambda logits, lab: whole(
        logits[:logits.shape[0] // 2], lab[:lab.shape[0] // 2]))
    res, compared = _run(capsys, "tiny_bert.fused")
    chk = json.load(open(os.path.join(DATA, "traffic", "fused.json")))["check"]
    assert res["correct"] is False
    assert compared["moment_norm_gap_global"] > 3 * chk["moment_norm_gap_global_limit"]
    assert compared["grad_power_gap_global"] > 3 * chk["grad_power_gap_global_limit"]


def test_served_token_altered_is_not_correct(capsys, monkeypatch):
    from mxnet_tpu.serving.generative import LlamaServingEngine

    step = LlamaServingEngine.step

    def wrong(self, active):
        return (step(self, active) + 1) % 256     # every decoded token off by one

    monkeypatch.setattr(LlamaServingEngine, "step", wrong)
    res, compared = _run(capsys, "tiny_llama.closed")
    assert res["correct"] is False
    assert compared["served_logit_gap_max"] > 0.02


def test_step_that_leaves_state_unchanged_is_not_correct(capsys, monkeypatch):
    from mxnet_tpu import optimizer as opt

    monkeypatch.setattr(opt, "_fused_param_updates",
                        lambda optzr, mp, w, m, grads, s, lr, wd, t: (w, m, s))
    res, compared = _run(capsys, "tiny_bert.fused")
    assert res["correct"] is False
    assert compared["delta_norm_gap_worst_leaf"] == pytest.approx(1.0)
