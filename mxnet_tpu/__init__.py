"""mxnet_tpu — a TPU-native framework with the capabilities of Apache MXNet.

Built from scratch against the architecture documented in /root/repo/SURVEY.md
(reference: Kh4L/incubator-mxnet, an apache/incubator-mxnet 1.x fork).  The
compute path is jax/XLA/Pallas; the user API preserves MXNet semantics:
``mx.nd.*`` imperative NDArrays, ``autograd.record()``, Gluon
``Block/HybridBlock/Trainer``, ``KVStore`` — extended with ``mx.tpu()``
contexts, a ``dist_tpu_sync`` KVStore mode (psum over the ICI mesh), and
sequence/tensor parallelism the reference never had.

Typical use (identical to reference scripts, one-line context swap):

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, autograd, nd

    ctx = mx.tpu()
    net = gluon.model_zoo.vision.resnet50_v1()
    net.initialize(ctx=ctx)
    net.hybridize(static_alloc=True)
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1}, kvstore='dist_tpu_sync')
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    trainer.step(batch_size)
"""

__version__ = "0.1.0"

from . import base
from .base import MXNetError

base.configure_compile_cache()  # before the first compile, see base.py

from .context import Context, cpu, tpu, gpu, current_context, num_gpus, \
    num_tpus, num_devices
from . import ndarray
from . import ndarray as nd  # canonical alias, reference: `mx.nd`
from .ndarray import NDArray
from . import autograd
from . import random
from . import ops
from . import engine

# subsystems imported lazily on attribute access to keep `import mxnet_tpu`
# fast (the reference generates op wrappers at import; we defer heavyweight
# subpackages instead)
_LAZY = {
    "symbol": ".symbol",
    "sym": ".symbol",
    "module": ".module",
    "mod": ".module",
    "operator": ".operator",
    "rtc": ".rtc",
    "executor": ".executor",
    "name": ".name",
    "gluon": ".gluon",
    "optimizer": ".optimizer",
    "kvstore": ".kvstore",
    "kv": ".kvstore",
    "initializer": ".initializer",
    "init": ".initializer",
    "metric": ".metric",
    "lr_scheduler": ".lr_scheduler",
    "callback": ".callback",
    "io": ".io",
    "rnn": ".rnn",
    "image": ".image",
    "parallel": ".parallel",
    "profiler": ".profiler",
    "telemetry": ".telemetry",
    "monitor": ".monitor",
    "visualization": ".visualization",
    "viz": ".visualization",
    "recordio": ".recordio",
    "serialization": ".serialization",
    "amp": ".amp",
    "contrib": ".contrib",
    "test_utils": ".test_utils",
    "numpy": ".numpy",
    "np": ".numpy",
    "numpy_extension": ".numpy_extension",
    "npx": ".numpy_extension",
    "util": ".util",
    "runtime": ".runtime",
    "models": ".models",
    "model": ".model",
    "predictor": ".predictor",
    "checkpoint": ".checkpoint",
    "elastic": ".elastic",
    "serving": ".serving",
    "data": ".data",
}


def __getattr__(name):
    if name in _LAZY:
        import importlib

        mod = importlib.import_module(_LAZY[name], __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# profiler autostart must defeat the lazy import (reference profiles from
# process start when MXNET_PROFILER_AUTOSTART=1, SURVEY §5)
import os as _os

if _os.environ.get("MXNET_PROFILER_AUTOSTART", "0") == "1":
    from . import profiler  # noqa: F401  (its import-time hook starts it)
