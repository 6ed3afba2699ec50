"""The selecting latent family's readers (``layer_metrics/dsa_selected_share.py``,
``dsa_prefill_roofline.py``, ``mla_step_roofline.py``) end to end through
``run.py`` at a tiny size (``tests/data_glm``), and on a window the lane log
knows nothing of."""
import os

import pytest

import run as harness

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data_glm")
NAMES = ["dsa_selected_share", "dsa_prefill_roofline", "mla_step_roofline"]


def reader(name):
    return harness.load_module(
        os.path.join(os.path.dirname(HERE), "layer_metrics", name + ".py"),
        "glm_reader_test_" + name)


@pytest.mark.parametrize("name", NAMES)
def test_a_window_without_the_counters_reads_nothing(name):
    obs = {"t0_abs": 900_000_000.0, "window_s": 1.0, "config": {}, "chips": 1,
           "peaks": {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0},
           "trace": None, "trace_host_window": None,
           "programs": {"step": "^jit__step_fn", "prefill": "^jit__prefill_fn"}}
    assert reader(name).read(obs) is None


def test_rehearsal_reports_the_counter_reader(capsys):
    res = harness.run(["--workload", "tiny_glm.open", "--seed", "2147483659",
                       "--seconds", "2", "--trace", "1"],
                      require_tpu=False, data_dir=DATA)
    capsys.readouterr()
    assert res["correct"] is True and res["failed"] == 0
    # index_topk 8 under prompts of 8 to 40: a tick reads a part of what it sees
    assert 10 < res["metrics"]["dsa_selected_share"]["value"] < 60
    assert "mla_step_roofline" not in res["metrics"]      # no TPU plane here
