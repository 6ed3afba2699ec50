"""Benchmark: BASELINE.md tracked metrics on one chip.

Default run measures BOTH tracked training metrics back to back —
ResNet-50 v1 images/sec/chip, then BERT-base (seq 128) samples/sec/chip
— and prints ONE JSON line.  Schema keeps ``metric``/``value`` as the
ResNet number (driver compatibility); the BERT number rides alongside as
``bert_base_samples_per_sec_per_chip``.

Measurement protocol (BASELINE.md): synthetic data, hybridized net under
``gluon.Trainer``, steady state after warmup (compile) steps, best of
``BENCH_REPEATS`` windows.  ``vs_baseline`` would be measured against
the reference's published number, which was unrecoverable (empty
reference mount — BASELINE.md); reported as ``null`` = no baseline
available (never 0.0, which would read as "exactly at baseline").

``BENCH_MODEL=bert_base`` runs ONLY the BERT workload (its own JSON
schema); ``BENCH_SKIP_BERT=1`` keeps the default run ResNet-only.

Runs on whatever backend jax boots (``JAX_PLATFORMS=cpu`` for a local
sanity run).  A leg that fails raises: the exit code is non-zero and no
record is printed.
"""
from __future__ import annotations

import json
import os
import time


def main():
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd

    # 128 won the last batch sweep of the ResNet leg (r5, on a
    # shared-chip set-up that is gone — git history has the numbers and
    # they say nothing of today's code); the BERT leg pins its own
    # protocol batch below.  Disclosed in the JSON.
    batch = int(os.environ.get("BENCH_BATCH", "128"))
    # ~2s of steady state (100 steps brought repeat spread under ±4%)
    steps = int(os.environ.get("BENCH_STEPS", "100"))
    # BASELINE.md protocol: steady state = skip the first 20 steps
    warmup = int(os.environ.get("BENCH_WARMUP", "20"))
    image = int(os.environ.get("BENCH_IMAGE", "224"))
    model = os.environ.get("BENCH_MODEL", "resnet50_v1")
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")

    if model.startswith("bert"):
        # BERT runs at its protocol batch 64 (it also won the r5 sweep
        # over 128 and 256 on the shared-chip set-up that is gone); an
        # explicit BENCH_BATCH still overrides for sweeps
        if "BENCH_BATCH" not in os.environ:
            batch = int(os.environ.get("BENCH_BERT_BATCH", "64"))
        ips, repeats, spe = _bench_bert(batch, steps, warmup, dtype,
                                        model)
        print(json.dumps({
            "metric": f"{model}_pretrain_samples_per_sec_per_chip",
            "value": round(ips, 2),
            "unit": "samples/sec/chip",
            "batch": batch,
            "aggregation": f"best_of_{repeats}_windows",
            "steps_per_execution": spe,
            "vs_baseline": None,
        }))
        return

    mx.random.seed(0)
    net = gluon.model_zoo.vision.get_model(model, classes=1000)
    net.initialize(mx.init.Xavier())
    # resolve deferred shapes on a tiny input: the resolve pass runs
    # imperatively (per-op dispatch), so keep it off the 224² hot path
    net(nd.ones((1, 3, 32, 32)))
    if dtype in ("bfloat16", "float16"):
        from mxnet_tpu import amp

        amp.init(target_dtype=dtype)
    # BENCH_REMAT=1: activation checkpointing (recompute fwd in bwd) —
    # trades FLOPs for activation memory; off at the default b128
    net.hybridize(static_alloc=True, static_shape=True,
                  remat=bool(int(os.environ.get("BENCH_REMAT", "0"))))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    # the reference protocol keeps the loss in the symbolic graph
    # (SoftmaxOutput); hybridizing the loss is the gluon equivalent and
    # removes ~5 eager dispatches per step (+11% measured)
    loss_fn.hybridize()

    x = mx.random.uniform(shape=(batch, 3, image, image))
    y = nd.array(np.random.randint(0, 1000, (batch,)))

    def eager_step():
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(batch)
        return loss

    step, spe = _maybe_fuse(
        eager_step, net, trainer,
        lambda n, xx, yy: loss_fn(n(xx), yy), (x, y), batch)

    last = None
    for _ in range((warmup + spe - 1) // spe):  # ceil: >= warmup steps
        last = step()
    if last is not None:
        _hard_sync(last)  # warmup fully done before any window starts

    ips, repeats = _best_window(step, batch * spe, max(1, steps // spe))
    record = {
        "metric": f"{model}_train_images_per_sec_per_chip",
        "value": round(ips, 2),
        "unit": "images/sec/chip",
        "batch": batch,
        "aggregation": f"best_of_{repeats}_windows",
        # device-side step chaining (gluon.FusedTrainStep): K optimizer
        # steps per dispatch — chip throughput, not host dispatch rate
        "steps_per_execution": spe,
        # reference baseline unrecoverable (BASELINE.md): null = none
        "vs_baseline": None,
    }

    if not int(os.environ.get("BENCH_SKIP_DATA", "0")):
        # BASELINE protocol: "synthetic-data variant reported alongside
        # real-data to isolate input pipeline" — same net/trainer/loss,
        # but batches flow JPEG->decode->augment->HBM through
        # ImageRecordIter with thread prefetch (VERDICT r3 item 2)
        data_ips, data_note = _bench_resnet_recordio(
            net, trainer, loss_fn, batch, image,
            min(steps, int(os.environ.get("BENCH_DATA_STEPS", "20"))))
        record[f"{model}_recordio_images_per_sec_per_chip"] = \
            round(data_ips, 2)
        record[f"{model}_recordio_note"] = data_note

    if not int(os.environ.get("BENCH_SKIP_BERT", "0")):
        # release the ResNet program + arrays before the BERT compile so
        # both workloads see the full HBM
        import gc

        del net, trainer, loss_fn, x, y, step
        gc.collect()
        # the tracked BERT metric is pinned to the BASELINE protocol
        # batch (64) regardless of BENCH_BATCH overrides aimed at the
        # ResNet leg (e.g. BENCH_BATCH=256)
        bert_batch = int(os.environ.get("BENCH_BERT_BATCH", "64"))
        bert_ips, _, bert_spe = _bench_bert(bert_batch, steps, warmup,
                                            dtype, "bert_base")
        record["bert_base_samples_per_sec_per_chip"] = round(bert_ips, 2)
        record["bert_base_unit"] = "samples/sec/chip"
        record["bert_base_batch"] = bert_batch
        record["bert_base_steps_per_execution"] = bert_spe
    print(json.dumps(record))


def _bench_resnet_recordio(net, trainer, loss_fn, batch, image, steps):
    """Real-data leg: the SAME hybridized net + trainer step, fed from a
    synthetic-JPEG RecordIO file through ImageRecordIter (thread decode
    + prefetch, device-side normalize).  Returns (img/s, bottleneck
    note): on a many-core TPU-VM host the pipeline sustains the chip
    (benchmark/input_pipeline.py measures decode scaling); on a 1-core
    dev host the leg is decode-bound and says so instead of lying."""
    import os
    import time

    from mxnet_tpu import autograd
    from mxnet_tpu.io import ImageRecordIter

    repeats = int(os.environ.get("BENCH_REPEATS", "3"))
    # size the file so EVERY window (plus warm step) fits in one epoch:
    # a mid-window it.reset() tears down and respawns the prefetch
    # thread, charging ~seconds of stall to "real-data throughput"
    n_imgs = (steps * repeats + 2) * batch
    # generated from a fixed seed into the (git-ignored) output
    # directory beside this file: the same bytes at the same path on
    # every run, reused when already there
    here = os.path.dirname(os.path.abspath(__file__))
    out_dir = os.path.join(here, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    rec = os.path.join(out_dir, f"mxt_bench_{image}_{n_imgs}.rec")
    if not os.path.exists(rec):
        import sys

        sys.path.insert(0, here)
        from benchmark.input_pipeline import make_recfile

        make_recfile(rec[:-4], n_imgs, image)
    threads = max(2, (os.cpu_count() or 2))
    it = ImageRecordIter(path_imgrec=rec,
                         data_shape=(3, image, image),
                         batch_size=batch, rand_mirror=True,
                         preprocess_threads=threads,
                         prefetch_buffer=4)

    def next_batch():
        try:
            return next(it)
        except StopIteration:  # only across reruns of the leg
            it.reset()
            return next(it)

    def step():
        b = next_batch()
        with autograd.record():
            loss = loss_fn(net(b.data[0]), b.label[0])
        loss.backward()
        trainer.step(batch)
        return loss

    _hard_sync(step())  # compile with the real-data shapes
    ips, _ = _best_window(step, batch, steps, repeats=repeats)

    # attribute the bottleneck: pure-pipeline throughput with the model
    # out of the loop (fresh epoch, no device work)
    it.reset()
    t0 = time.time()
    n = 0
    for b in it:
        n += b.data[0].shape[0]
    pipe_ips = n / (time.time() - t0)
    note = (f"input-pipeline-bound: decode sustains ~{pipe_ips:.0f} "
            f"img/s on {os.cpu_count()} host core(s); scales with "
            "cores (benchmark/input_pipeline.py)"
            if pipe_ips < ips * 1.5 else
            f"pipeline headroom ok (decode ~{pipe_ips:.0f} img/s)")
    return ips, note


def _maybe_fuse(eager_step, net, trainer, forward_loss, batch_arrays,
                batch_size):
    """Wrap the training step in ``gluon.FusedTrainStep`` with
    ``BENCH_STEPS_PER_EXEC`` inner steps per dispatch (default 8) — the
    TPU step-chaining idiom that keeps the window measuring chip time
    instead of per-step host dispatch.  ``BENCH_STEPS_PER_EXEC=1`` asks
    for the per-step loop; a fusion that fails is an error, not a
    reason to measure something else."""
    from mxnet_tpu import gluon

    spe = int(os.environ.get("BENCH_STEPS_PER_EXEC", "8"))
    if spe <= 1:
        return eager_step, 1
    fstep = gluon.FusedTrainStep(
        net, trainer, forward_loss, steps_per_execution=spe,
        batch_size=batch_size)
    return (lambda: fstep(*batch_arrays)), spe


def _hard_sync(arr):
    """Force TRUE device completion, not dispatch-return: fetch the
    value to host.  Dispatch is asynchronous, and a window closed
    before the device finished measures the enqueue rate (the r4 MFU
    audit caught exactly that pricing BERT above 100% of peak).  A host
    fetch of the loss cannot complete until every queued program before
    it has executed (single in-order device stream), so the clock stops
    at real completion."""
    return arr.asnumpy()


def _best_window(step, samples_per_call, calls, repeats=None):
    """Best of ``BENCH_REPEATS`` steady-state windows, each closed by a
    hard host-fetch sync (see :func:`_hard_sync`).  ``step`` may be a
    per-step dispatch (1 batch per call) or a fused K-step execution
    (``samples_per_call`` = batch*K)."""
    import time

    repeats = repeats or int(os.environ.get("BENCH_REPEATS", "3"))
    best = 0.0
    for _ in range(repeats):
        tic = time.time()
        last = None
        for _ in range(calls):
            last = step()
        _hard_sync(last)
        wall = time.time() - tic
        best = max(best, samples_per_call * calls / wall)
    return best, repeats


def _bench_bert(batch, steps, warmup, dtype, model_name):
    """BERT-base MLM-style pretraining step (seq 128, BASELINE protocol).
    Returns (samples/sec, window repeats)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.models import bert

    seq = int(os.environ.get("BENCH_SEQ", "128"))
    vocab = 30522
    mx.random.seed(0)
    builder = getattr(bert, model_name)  # unknown names must fail loudly
    net = builder(vocab_size=vocab)
    net.initialize(mx.init.Xavier())
    rng = np.random.RandomState(0)
    ids = nd.array(rng.randint(0, vocab, (batch, seq)), dtype="int32")
    seg = nd.zeros((batch, seq), dtype="int32")
    labels = nd.array(rng.randint(0, vocab, (batch, seq)), dtype="int32")
    net(ids, seg)  # resolve deferred shapes
    if dtype in ("bfloat16", "float16"):
        from mxnet_tpu import amp

        amp.init(target_dtype=dtype)
    net.hybridize(static_alloc=True)
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-4})

    # loss-in-graph (same protocol as the ResNet leg, +11% there): the
    # MLM cross-entropy compiles with its own CachedOp instead of three
    # eager dispatches per step
    class _MLMLoss(gluon.HybridBlock):
        def hybrid_forward(self, F, mlm, lab):
            # NO reshape to (b*s, vocab): the CE op reduces over the
            # last axis of any leading shape, and flattening forced a
            # 1.5 GB layout copy of the logits (PERF_NOTES r5 cont. 6)
            return F.softmax_cross_entropy(mlm, lab) / (batch * seq)

    loss_fn = _MLMLoss()
    loss_fn.hybridize()

    def eager_step():
        with autograd.record():
            # outputs: (seq, pooled, nsp_logits, mlm_logits)
            outs = net(ids, seg)
            loss = loss_fn(outs[-1], labels)
        loss.backward()
        trainer.step(1)
        return loss

    step, spe = _maybe_fuse(
        eager_step, net, trainer,
        lambda n, i, s, l: loss_fn(n(i, s)[-1], l), (ids, seg, labels), 1)

    last = None
    for _ in range((warmup + spe - 1) // spe):  # ceil: >= warmup steps
        last = step()
    if last is not None:
        _hard_sync(last)  # warmup fully done before any window starts
    ips, repeats = _best_window(step, batch * spe, max(1, steps // spe))
    return ips, repeats, spe


if __name__ == "__main__":
    main()
