"""JoyAI-LLM-Flash trained through ``gluon.Trainer``: the Gluon net against the
benchmark's plain reference (logits, both losses, every leaf's gradient), the
held shares of an expert layer, ``FusedTrainStep`` against eager steps with the
step's side values and the choice bias, planted faults that the cell's own
check refuses, a rehearsal of the cell and the tables of ``PERF.md``."""
import importlib.util
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon import step_fusion
from mxnet_tpu.models import joyai_flash as jf
from mxnet_tpu.models import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "chipbench")
DATA = os.path.join(BENCH, "tests", "data_joyai")


def _bench_module(*parts):
    path = os.path.join(BENCH, *parts)
    name = "test_joyai_" + "_".join(parts).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _bench_module("references", "joyai_flash.py")


@pytest.fixture(scope="module")
def family():
    return _bench_module("families", "joyai_flash.py")


@pytest.fixture(scope="module")
def cfg():
    return json.load(open(os.path.join(DATA, "configs", "tiny_joyai.json")))


def _net(family, ref, cfg, seed=3):
    """The program's net holding the reference's seeded parameters."""
    cell = family.Cell(cfg, {"steps_per_dispatch": 2, "rows_per_chip": 2,
                             "seq": 16}, seed, 1, None, ref)
    net = jf.JoyAIFlashForPretraining(cell._model_config())
    net.initialize()
    params = ref.init_params(jax.random.PRNGKey(seed), cfg)
    slots = family.Cell._slots(net)
    assert set(slots) == set(params)
    for name, p in slots.items():
        p.set_data(nd.NDArray(params[name]))
    return net, params, slots


def _ids(cfg, rows=2, seq=16, seed=0):
    return np.random.RandomState(seed).randint(
        0, cfg["vocab_size"], (rows, seq)).astype(np.int32)


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max()), \
        np.abs(a - b).max()


# --- against the plain reference ------------------------------------------------

def test_logits_losses_and_every_leafs_gradient(family, ref, cfg):
    net, params, slots = _net(family, ref, cfg)
    ids = _ids(cfg, rows=1, seq=12)
    bias = jnp.zeros((len(ref.expert_layers(cfg)), cfg["router_experts"]))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: ref.row_losses(
            p, bias, jnp.asarray(ids[0]), cfg))(params)
        _close(net.model(nd.array(ids, dtype="int32")).asnumpy()[0, :-1],
               want[3], 1e-5)
        (total, (main, extra, rows)), grads = jax.jit(jax.value_and_grad(
            lambda p: ref.objective(p, bias, jnp.asarray(ids[0]), cfg, 1),
            has_aux=True))(params)
        with autograd.record():
            loss, lm, lx, counted = net(nd.array(ids, dtype="int32"))
        loss.backward()
    _close(loss.asnumpy(), total, 1e-6)
    _close(lm.asnumpy(), main, 1e-6)
    _close(lx.asnumpy(), extra, 1e-6)
    _close(main, want[0] / 11, 1e-6)
    assert (counted.asnumpy() == np.asarray(rows)).all()
    med = np.median([float(jnp.abs(g).max()) for g in grads.values()])
    for name, p in slots.items():
        got, ref_g = p.grad().asnumpy(), np.asarray(grads[name])
        assert np.abs(got - ref_g).max() <= 2e-5 * max(
            med, np.abs(ref_g).max()), name


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(ref, cfg):
    """Four chips, four experts of the 16 each: the routed parts of the four
    shares and the shared expert, counted once, are the uncut layer's
    feed-forward, forward and in the input's gradient; the reference's loop
    over a share gives the program's share."""
    whole = dict(cfg, experts_held=[0, 16], n_routed_experts=16)
    shapes = ref.layer_shapes(whole, False)
    key = jax.random.PRNGKey(5)
    lp = {n: jax.random.normal(jax.random.fold_in(key, j), s) * 0.2
          for j, (n, s) in enumerate(sorted(shapes.items()))}
    lp["expert_bias"] = jax.random.normal(key, (16,)) * 0.1
    u = jax.random.normal(jax.random.fold_in(key, 99), (24, cfg["hidden_size"]))
    kw = dict(score="sigmoid", renormalize=True,
              scale=cfg["routed_scaling_factor"])

    def ffn(u, held):
        first, count = held
        part = dict(lp, **{n: lp[n][first:first + count]
                           for n in ("w_gate", "w_up", "w_down")})
        return moe.expert_layer_ffn(part, u, cfg["num_experts_per_tok"],
                                    experts_held=held, **kw)

    def shared(u):
        return moe.swiglu(u, lp["shared_gate"], lp["shared_up"],
                          lp["shared_down"])

    def summed(u):
        return sum(ffn(u, (f, 4))[0] - shared(u) for f in range(0, 16, 4)) \
            + shared(u)

    with jax.default_matmul_precision("highest"):
        uncut, counts = ffn(u, (0, 16))
        _close(summed(u), uncut, 1e-5)
        assert int(counts.sum()) == 24 * cfg["num_experts_per_tok"]
        dy = jax.random.normal(jax.random.fold_in(key, 7), uncut.shape)
        _close(jax.grad(lambda u: (summed(u) * dy).sum())(u),
               jax.grad(lambda u: (ffn(u, (0, 16))[0] * dy).sum())(u), 1e-5)
        for first in (0, 8):
            y, c = ref.routed(lp, u, lp["expert_bias"], whole, False,
                              held=(first, 4))
            _close(y, ffn(u, (first, 4))[0] - shared(u), 1e-5)
            assert (np.asarray(c) == np.asarray(counts)).all()


# --- the fused step's side values and the choice bias ---------------------------

def _trainer(net):
    return gluon.Trainer(net.collect_params(), "adamw",
                         {"learning_rate": 1e-3, "beta2": 0.95, "wd": 0.1})


def _biases(net):
    return np.stack([l.expert_bias.data().asnumpy()
                     for l in (*net.model.layers[1:], net.mtp_layer)])


def test_fused_k2_is_two_eager_steps(family, ref, cfg):
    ids = np.stack([_ids(cfg, seed=s) for s in (1, 2)])
    eager, _, _ = _net(family, ref, cfg)
    eager.hybridize()
    tr = _trainer(eager)
    want = []
    for k in range(2):
        with step_fusion.reported() as side:
            with autograd.record():
                loss = jf.pretrain_forward_loss(
                    eager, nd.array(ids[k], dtype="int32"))
            loss.backward()
        tr.step(1)
        want.append({"loss": float(loss.asnumpy()),
                     **{n: v.asnumpy() for n, v in side.items()
                        if isinstance(v, nd.NDArray)}})
        assert side["expert_product"] == "every_expert"
        assert side["remat"] == "none" and side["flash_tiles"] == "chunked"
    fused, _, _ = _net(family, ref, cfg)
    fused.hybridize()
    tr2 = _trainer(fused)
    step = gluon.FusedTrainStep(fused, tr2, jf.pretrain_forward_loss,
                                steps_per_execution=2, batch_size=1,
                                stacked_inputs=True)
    losses = step(nd.array(ids, dtype="int32")).asnumpy()
    assert set(step.reported) == {"loss_main", "loss_mtp", "expert_rows",
                                  "pairs_held", "expert_rows_max",
                                  "expert_rows_mean"}
    got = step.fetch_reported()
    assert step.fetch_reported() is None
    for k in range(2):
        _close(losses[k], want[k]["loss"], 1e-5)
        for name in ("loss_main", "loss_mtp"):
            _close(got[name][k], want[k][name], 1e-5)
        assert (got["expert_rows"][k] == want[k]["expert_rows"]).all()
        assert got["pairs_held"][k] == want[k]["pairs_held"]
    assert got["expert_rows"].dtype == np.int32
    # the bias: where two eager steps leave it, moved by the rule alone
    assert (_biases(fused) == _biases(eager)).all()
    assert np.abs(_biases(fused)).max() > 0
    assert set(np.unique(np.rint(_biases(fused) / 1e-3))) <= {-2, -1, 0, 1, 2}
    for tr_, net_ in ((tr, eager), (tr2, fused)):
        idx = {id(p): i for i, p in enumerate(tr_._params)}
        for l in (*net_.model.layers[1:], net_.mtp_layer):
            assert l.expert_bias.grad_req == "null"
            assert tr_._states[idx[id(l.expert_bias)]] is None
    # the record of the dispatch carries the numbers a step and the facts
    from mxnet_tpu.telemetry import tracing

    rec = tracing.lane_log("train.dispatch")[-1]
    assert rec["seq"] == 1 and len(rec["loss_main"]) == 2
    assert rec["pairs_held"] == [float(v) for v in got["pairs_held"]]
    assert rec["expert_product"] == "every_expert"
    assert "expert_rows" not in rec
    # eager code still sees a net: the gradient buffers come back on demand
    step.free_grad_buffers()
    assert fused.model.top.embed.data().grad is None
    losses2 = step(nd.array(ids, dtype="int32")).asnumpy()
    assert np.isfinite(losses2).all() and losses2[0] < losses[0]


def test_a_planted_skew_moves_the_bias_the_right_way(family, ref, cfg):
    """Every row is pushed onto expert 5 of one layer: its bias goes down,
    the starved experts' up, and with enough steps the choice follows."""
    net, _, _ = _net(family, ref, cfg)
    layer = net.model.layers[1]
    router = layer.router.data().asnumpy().copy()
    router[5] = 0.0
    layer.router.set_data(nd.array(router))
    ids = nd.array(_ids(cfg, rows=2, seq=16), dtype="int32")
    layer.expert_bias.set_data(nd.array(
        np.where(np.arange(16) == 5, 0.5, 0.0).astype(np.float32)))
    with autograd.train_mode():
        rows0 = net(ids)[3].asnumpy()[0]
    bias = layer.expert_bias.data().asnumpy()
    assert rows0[5] == rows0.max() == 32           # every row chose it
    assert bias[5] == pytest.approx(0.5 - 1e-3)
    starved = rows0 < rows0.mean()
    assert (bias[starved] == pytest.approx(1e-3))
    # not training: the bias stays
    net(ids)
    assert (layer.expert_bias.data().asnumpy() == bias).all()


# --- the cell, rehearsed; planted faults ----------------------------------------

@pytest.fixture
def harness(monkeypatch, tmp_path):
    for p in (BENCH, REPO):
        if p not in sys.path:
            sys.path.insert(0, p)
    import run as harness

    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    return harness


def _compared(out):
    rows = {}
    for line in out.splitlines():
        if line.startswith("compared: "):
            name, rest = line[len("compared: "):].split(" = ")
            rows[name] = (float(rest.split(" limit ")[0]),
                          rest.split(" limit ")[1].split()[-1])
    return rows


def _run(harness, capsys, *extra):
    res = harness.run(["--workload", "tiny_joyai.pretrain", "--seed",
                       "4000000007", "--seconds", "1", *extra],
                      require_tpu=False, data_dir=DATA)
    return res, _compared(capsys.readouterr().out)


def test_rehearsal_of_the_cell_on_the_cpu(harness, capsys):
    res, rows = _run(harness, capsys, "--trace", "1", "--control", "1")
    assert rows["bias_has_optimizer_state"] == (0.0, "ok")
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    # no device plane in a CPU trace: the readers of stamps and counters report
    assert set(res["metrics"]) == {"train_dispatch_host_ms",
                                   "train_expert_rows_max_over_mean"}
    assert res["metrics"]["train_expert_rows_max_over_mean"]["value"] >= 1.0
    assert rows["loss_rise_over_window"][0] < 0
    assert rows["loss_mtp_rise_over_window"][0] < 0
    # both controls are refused by the limits the program passes
    assert rows["control.passes_every_limit"] == (0.0, "ok")
    assert rows["control_no_mtp.passes_every_limit"] == (0.0, "ok")
    assert rows["control_no_mtp.moment_norm_gap_worst_leaf"][0] \
        == pytest.approx(1.0)


def _plant_no_extra_term(monkeypatch):
    whole = jf.JoyAIFlashConfig.__init__

    def init(self, *a, **kw):
        whole(self, *a, **dict(kw, mtp_loss_weight=0.0))

    monkeypatch.setattr(jf.JoyAIFlashConfig, "__init__", init)
    return "moment_norm_gap_worst_leaf"


def _plant_labels_shifted(monkeypatch):
    whole = jf._shifted
    monkeypatch.setattr(jf, "_shifted",
                        lambda ids, by: whole(ids, by + (by == 2)))
    return "loss_mtp_gap_max"


def _plant_embedding_not_shared(monkeypatch):
    whole, calls = jf.JoyAIFlashForCausalLM.embed, []

    def embed(self, ids):
        calls.append(1)
        out = whole(self, ids)
        if len(calls) % 2 == 0:      # the module's look-up: no gradient
            out = nd.NDArray(jax.lax.stop_gradient(out._data))
        return out

    monkeypatch.setattr(jf.JoyAIFlashForCausalLM, "embed", embed)
    return "moment_norm_gap_worst_leaf"


def _plant_bias_by_the_optimizer(monkeypatch):
    whole = jf.JoyAIFlashLayer.__init__

    def init(self, cfg, dense, **kw):
        whole(self, cfg, dense, **kw)
        if not dense:
            self.expert_bias.grad_req = "write"

    monkeypatch.setattr(jf.JoyAIFlashLayer, "__init__", init)
    return "bias_sign_flip_share"


def _plant_bfloat16_products(monkeypatch):
    def autocast(x, p):
        return x.astype(jnp.bfloat16), {
            n: a if a.ndim < 2 or n == "router" else a.astype(jnp.bfloat16)
            for n, a in p.items()}

    monkeypatch.setattr(jf, "_autocast", autocast)
    return "moment_norm_gap_worst_leaf"


@pytest.fixture(scope="module")
def followed(ref, cfg):
    """The reference's first dispatch of the rehearsal's cell, once."""
    for p in (BENCH, REPO):
        if p not in sys.path:
            sys.path.insert(0, p)
    import traffic

    mix = json.load(open(os.path.join(DATA, "traffic", "tiny_pretrain.json")))
    ring = traffic.make_train_ring(mix, SEED, cfg["vocab_size"], 1)
    return mix, ring, {}


SEED = 4000000007


def _first_dispatch(family, ref, cfg, mix, ring):
    """The program's side of the check, as the cell's set-up takes it."""
    import contextlib

    cell = family.Cell(cfg, mix, SEED, 1,
                       lambda name: contextlib.nullcontext(), ref)
    try:
        cell.build(lambda name: contextlib.nullcontext(), ring)
        return cell.first, [cell.ring[0][k] for k in range(2)], \
            [cell._lr(cell.k - 1)] * 2
    finally:
        cell.end_window()


@pytest.mark.parametrize("plant", [
    None, _plant_no_extra_term, _plant_labels_shifted,
    _plant_embedding_not_shared, _plant_bias_by_the_optimizer,
    _plant_bfloat16_products],
    ids=["sound", "extra_term_dropped", "labels_shifted_by_one",
         "embedding_not_shared", "bias_by_the_optimizer",
         "bfloat16_products"])
def test_planted_faults_are_refused_by_the_checks_limits(
        family, ref, cfg, followed, monkeypatch, plant):
    """The cell's first dispatch with a fault planted underneath, held to
    the reference by the family's own ``gaps`` and the traffic file's
    limits: the named row is over its limit (and none is, unplanted)."""
    mix, ring, cache = followed
    row = plant(monkeypatch) if plant else None
    first, batches, lrs = _first_dispatch(family, ref, cfg, mix, ring)
    if "ref" not in cache:
        cache["ref"] = ref.follow(cfg, SEED, batches, lrs)
    rows = family.gaps(first, cache["ref"],
                       cfg["assumed_values"]["bias_update_speed"])
    rows["bias_has_optimizer_state"] = first["bias_has_optimizer_state"]
    over = {n for n, v in rows.items()
            if v > mix["check"].get(n + "_limit", np.inf)}
    assert over == ({row} if row else set()) or (row in over), (rows, over)
    assert bool(over) == bool(row)


# --- the benchmark's files ---------------------------------------------------------

def test_parameter_and_operation_tables_total_to_perf_md():
    """PERF.md's table of the cut (section 4) against
    ``flops_bytes/joyai_flash_train.py``."""
    cost = _bench_module("flops_bytes", "joyai_flash_train.py")
    cfg = json.load(open(os.path.join(
        BENCH, "configs", "joyai_llm_flash_l5_ep16.json")))
    table = cost.param_table(cfg)
    assert table["total"] == 680_394_752
    text = open(os.path.join(REPO, "PERF.md")).read()
    rows = dict(re.findall(r"joyai (\w+) ([\d,]+)[;.]", text))
    assert rows, "PERF.md lost the cut's table"
    assert {k: int(v.replace(",", "")) for k, v in rows.items()} == table
    assert cost.flops_per_token(cfg, 4096) == pytest.approx(2.643e9, rel=1e-3)
    fwd = cost.forward_flops_per_token(cfg, 4096)
    m = re.search(r"joyai forward MFLOP a token: ([\d.]+)", text)
    assert m and float(m.group(1)) == pytest.approx(
        sum(fwd.values()) / 1e6, abs=0.06)
    # the configuration file holds every number of the catalog's entry but
    # what ``reduced`` names
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = [c for c in bench["configs"]
             if c["name"] == "joyai_llm_flash_l5_ep16"][0]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert set(cfg["reduced"]) == set(entry["reduced"])
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "n_routed_experts": 256, "vocab_size": 129280}
    assert (cfg["hidden_size"], cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"]) \
        == (2048, 1536, 512, 768, 8)


def test_readers_on_a_planted_log_and_trace(harness):
    """Known answers: a hand-made trace summary and two planted dispatch
    records; the grouped kernels are told from the flash kernels by name."""
    from mxnet_tpu.telemetry import tracing

    cfg = json.load(open(os.path.join(
        BENCH, "configs", "joyai_llm_flash_l5_ep16.json")))
    base = 900_000_000.0
    for seq in (1, 2):
        tracing.lane_record(
            "train.dispatch", path="fused", seq=seq, k=2, compiled=False,
            t0=base + seq, t_args=base + seq, t_disp1=base + seq + 0.004,
            t_end=base + seq + 0.005, pairs_held=[40000.0, 42000.0],
            expert_rows_max=[900.0, 800.0], expert_rows_mean=[500.0, 400.0])
    mosaic = {"flash_fwd": [0.006] * 4 + [0.05], "flash_dq": [0.007] * 2,
              "flash_dkv": [0.008] * 2, "flash_fwd_nolse": [0.001] * 3}
    chip = {"mosaic": mosaic, "busy_s": 0.9, "idle_s": 0.1,
            "op_seconds": {"grouped_expert_ffn_dx.7": 0.05,
                           "grouped_expert_ffn_dw.2": 0.002,
                           "grouped_expert_ffn.1": 0.001, "fusion.3": 0.5},
            "op_counts": {"grouped_expert_ffn_dx.7": 1,
                          "grouped_expert_ffn_dw.2": 2,
                          "grouped_expert_ffn.1": 1, "fusion.3": 9}}
    obs = {"t0_abs": base, "window_s": 10.0, "chips": 1, "rows": 4,
           "seq": 4096, "tokens": 16384 * 14, "optimizer_steps": 14,
           "config": cfg, "step_facts": {"remat": "layer"},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "trace": {"window_s": 1.0, "chips": {0: chip}}}

    def reader(name):
        return harness.load_module(
            os.path.join(BENCH, "layer_metrics", name + ".py"),
            "test_joyai_reader_" + name.replace(".", "_"))

    assert reader("train_expert_rows_max_over_mean").read(obs) \
        == pytest.approx((900 / 500 + 800 / 400) / 2)
    cost = _bench_module("flops_bytes", "joyai_flash_train.py")
    per_token = 41000 / 16384 / 5
    assert reader("train_mfu.moe").read(obs) == pytest.approx(
        100 * 16384 * 1.4 * cost.flops_per_token(cfg, 4096, per_token)
        / 197e12)
    flash = _bench_module("flops_bytes", "flash_attention_mla.py")
    flops, _ = flash.needs(4, 32, 4096, 192, 128)
    # 12 Mosaic calls, 4 of them grouped: 8 flash calls in what is left
    spent = sum(sum(d) for d in mosaic.values()) - 0.053
    assert reader("flash_attn_roofline.causal").read(obs) == pytest.approx(
        100 * 8 * flops / 197e12 / spent)
    grouped = _bench_module("flops_bytes", "grouped_ffn_train.py")
    g_flops, g_bytes = grouped.needs(cfg, 41000, 5)
    assert reader("grouped_ffn_train_roofline").read(obs) == pytest.approx(
        100 * 1.4 * max(g_flops / 197e12, g_bytes / 819e9) / 0.053)
    # a program whose records carry no counters (the parent's): nothing read
    bare = dict(obs, t0_abs=base + 100)
    for name in ("train_expert_rows_max_over_mean", "train_mfu.moe",
                 "grouped_ffn_train_roofline"):
        assert reader(name).read(bare) is None
    assert reader("flash_attn_roofline.causal").read(
        dict(obs, trace=None)) is None
