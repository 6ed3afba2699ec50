"""chip_smoke.py is the standing proof that the system starts on the chip.
Here, without one: it must refuse to run, its two phase functions must work
at tiny size on the CPU mesh, and the things it relies on — a compile cache
that can be placed from outside, ``mx.tpu()`` that never names a CPU device —
must hold."""
import os
import subprocess
import sys

import pytest

import mxnet_tpu as mx

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture
def smoke():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
        yield chip_smoke
    finally:
        sys.path.remove(REPO)


def test_chip_smoke_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=120)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr and "'cpu'" in r.stderr, r.stderr
    assert r.stdout == ""          # no result line, nothing to mistake for one


def test_result_line_has_the_contract_keys_only(smoke):
    """What reads the last line of stdout accepts exactly ``ok`` and
    ``device`` = platform, kind, count as jax reports them; the run's
    other facts go on the line before it."""
    import json

    import jax

    devs = jax.devices()
    assert json.loads(smoke.result_line(devs)) == {
        "ok": True,
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs)}}
    assert "\n" not in smoke.result_line(devs)


TINY_BERT = dict(vocab=512, batch=8, seq=16, steps_per_execution=4)
TINY_LLAMA = dict(prompt_lens=(5, 20, 40, 20, 5, 40), max_new_tokens=6,
                  max_length=128)


def _check_trained(res):
    assert res["optimizer_steps"] == 12
    assert res["loss_last"] < res["loss_first"]
    assert res["first"]["compiles"] > 0
    assert not mx.amp.is_active()                  # the phase cleans up
    assert mx.parallel.current_mesh() is None


def _check_served(res):
    assert res["requests"] == 12 and res["health"] == "ok"
    assert len(res["prefill_length_buckets"]) >= 2
    assert res["decode_steps"] < res["tokens_owed"]
    assert res["cached_vs_uncached"]["rel_rms"] <= \
        res["cached_vs_uncached"]["tol"]


def test_phases_run_at_tiny_size_on_cpu_mesh(smoke):
    """The same functions main() calls with bert_base / llama3_8b, on
    bert_tiny / llama_tiny: the fused trainer on one device, the server
    as two replicas of the virtual mesh."""
    from mxnet_tpu.models import bert, llama

    with smoke.CompileClock() as clock:
        fused = smoke.train_phase(bert.bert_tiny, clock, **TINY_BERT)
        served = smoke.serve_phase(llama.llama_tiny, clock, dp=2,
                                   **TINY_LLAMA)
    _check_trained(fused)
    assert fused["path"] == "FusedTrainStep"
    assert fused["steady_compiles"] == 0
    assert fused["mosaic_calls_in_step"] == 0      # no Pallas off the chip
    _check_served(served)
    assert sorted(served["spread"]["replica_devices"]) == [0, 1]
    assert all(n > 0 for n in served["spread"]["replica_completed"])


@pytest.mark.slow
def test_phases_other_layout_on_cpu_mesh(smoke):
    """The layouts the tier-1 test leaves out: the trainer over a dp=2
    mesh with dist_tpu_sync, the server on one device."""
    from mxnet_tpu.models import bert, llama

    with smoke.CompileClock() as clock:
        sharded = smoke.train_phase(bert.bert_tiny, clock, dp=2, **TINY_BERT)
        served = smoke.serve_phase(llama.llama_tiny, clock, **TINY_LLAMA)
    _check_trained(sharded)
    assert sharded["path"] == "Trainer.step+dist_tpu_sync"
    assert sharded["spread"]["param_devices"] == 2
    _check_served(served)
    assert served["spread"] is None


def test_compile_cache_dir_is_placed_from_outside(monkeypatch):
    """``configure_compile_cache`` ran at ``import mxnet_tpu``; here its
    decisions are read off the updates it asks jax for, so the test
    itself configures nothing."""
    import jax

    from mxnet_tpu import base

    in_tree = os.path.join(REPO, ".jax_cache")
    assert base.COMPILE_CACHE_DIR == in_tree
    assert jax.config.jax_compilation_cache_dir == \
        (os.environ.get("JAX_COMPILATION_CACHE_DIR") or in_tree)
    # every executable is admitted, whatever it took to compile
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes <= 0
    # and the CPU test lane never writes there (conftest.py)
    assert jax.config.jax_enable_compilation_cache is False

    asked = {}
    monkeypatch.setattr(jax.config, "update", asked.__setitem__)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    base.configure_compile_cache()
    assert asked["jax_compilation_cache_dir"] == in_tree
    # placed from outside: jax reads the variable itself, the code must
    # set no directory
    asked.clear()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    base.configure_compile_cache()
    assert "jax_compilation_cache_dir" not in asked


def test_tpu_context_never_resolves_to_cpu():
    import jax

    assert jax.default_backend() == "cpu"
    for ctx in (mx.tpu(0), mx.gpu(0), mx.Context("tpu", 3)):
        with pytest.raises(mx.MXNetError, match="no TPU device"):
            ctx.device
    assert mx.num_tpus() == 0 and mx.num_gpus() == 0
    assert mx.cpu(0).device.platform == "cpu"
    assert mx.current_context() == mx.cpu(0)
