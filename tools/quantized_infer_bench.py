#!/usr/bin/env python
"""int8 end-to-end inference benchmark: quantized ResNet-18 vs bf16
(VERDICT r3 item 4 — int8 on the MXU as a deployed path, not a CPU test
fixture.  Reference: the quantization flow was a real inference
deployment path, src/operator/quantization/:? via MKLDNN/cuDNN).

Measures batched inference img/s for the SAME resnet18_v1:
  1. bf16 AMP, hybridized            (the baseline the README quotes)
  2. int8 via contrib quantize_net   (quantize->int8 conv/fc->dequantize
                                      chains, naive calibration)
plus top-1 agreement between the two on the benched batches (the
accuracy-proxy for synthetic weights).

Window protocol: hard host-fetch sync (bench.py's _hard_sync — a host
fetch cannot return before the device has finished).

Run: python tools/quantized_infer_bench.py  (env: BENCH_BATCH=64
BENCH_STEPS=50 BENCH_REPEATS=3 BENCH_PLATFORM=cpu for local smoke)
Prints one JSON line; the driver-facing artifact is OPPERF_r04.json's
int8 rows + the README line this feeds.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _win(fn, batch, steps, repeats):
    """bench.py's window protocol verbatim — ONE definition of the
    measurement (hard-sync best-of-N) so a protocol fix lands
    everywhere at once."""
    from bench import _best_window, _hard_sync

    _hard_sync(fn())  # compile + warm
    return _best_window(fn, batch, steps, repeats=repeats)[0]


def main():
    plat = os.environ.get("BENCH_PLATFORM")
    if plat:
        import jax

        jax.config.update("jax_platforms", plat)
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import amp, nd
    from mxnet_tpu.contrib import quantization as qz
    from mxnet_tpu.gluon.model_zoo import vision

    batch = int(os.environ.get("BENCH_BATCH", "64"))
    steps = int(os.environ.get("BENCH_STEPS", "50"))
    repeats = int(os.environ.get("BENCH_REPEATS", "3"))
    image = int(os.environ.get("BENCH_IMAGE", "224"))

    mx.random.seed(0)
    x = mx.random.uniform(shape=(batch, 3, image, image))

    net = vision.get_model("resnet18_v1", classes=1000)
    net.initialize(mx.init.Xavier())
    net(nd.ones((1, 3, 32, 32)))  # resolve deferred shapes
    amp.init(target_dtype="bfloat16")
    net.hybridize(static_alloc=True, static_shape=True)
    bf16_ips = _win(lambda: net(x), batch, steps, repeats)
    ref_top1 = np.argmax(net(x).asnumpy(), axis=-1)

    import tempfile

    qnet = vision.get_model("resnet18_v1", classes=1000)
    qnet.initialize(mx.init.Xavier())
    qnet(nd.ones((1, 3, 32, 32)))
    with tempfile.TemporaryDirectory() as td:
        pfile = os.path.join(td, "w.params")
        net.save_parameters(pfile)  # identical weights for both nets
        qnet.load_parameters(pfile)
    qz.quantize_net(qnet, calib_data=[x], calib_mode="naive")
    qnet.hybridize(static_alloc=True, static_shape=True)
    int8_ips = _win(lambda: qnet(x), batch, steps, repeats)
    q_top1 = np.argmax(qnet(x).asnumpy(), axis=-1)

    print(json.dumps({
        "metric": "resnet18_v1_infer_images_per_sec_per_chip",
        "bf16": round(bf16_ips, 2),
        "int8_quantized": round(int8_ips, 2),
        "int8_speedup": round(int8_ips / bf16_ips, 3),
        "top1_agreement": round(float((ref_top1 == q_top1).mean()), 4),
        "batch": batch,
        "aggregation": f"best_of_{repeats}x{steps}-step windows, "
                       "hard host-fetch sync",
    }))


if __name__ == "__main__":
    main()
