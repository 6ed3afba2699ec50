"""Runtime feature introspection.

Reference: ``python/mxnet/runtime.py:?`` + ``src/libinfo.cc:?`` —
``mx.runtime.Features()`` lists compile-time capabilities (CUDA, CUDNN,
MKLDNN, DIST_KVSTORE, INT64_TENSOR_SIZE, ...) with ``is_enabled(name)``
(SURVEY §2.1 row 10).

TPU-native: features reflect what this build actually provides — the jax/
XLA platforms present at runtime plus the framework's own subsystems
(native C++ runtime, recordio, pallas).  CUDA-family flags are present
and False so reference scripts probing them keep working.
"""
from __future__ import annotations

import collections


class Feature(collections.namedtuple("Feature", ["name", "enabled"])):
    def __repr__(self):
        return f"[{'✔' if self.enabled else '✖'} {self.name}]"


def _detect():
    feats = {}
    import jax

    feats["TPU"] = any(d.platform == "tpu" for d in jax.devices())
    feats["CPU"] = True
    feats["XLA"] = True
    feats["JIT"] = True
    feats["PALLAS"] = True              # ships with the pinned jax
    from . import _native

    feats["NATIVE_ENGINE"] = _native.available()
    feats["RECORDIO"] = True
    feats["DIST_KVSTORE"] = True        # dist_tpu_sync over the mesh
    feats["SPARSE"] = True              # BCOO-backed row_sparse/csr
    feats["BF16"] = True
    feats["INT64_TENSOR_SIZE"] = True
    # reference flags that are hard-off in a TPU build
    for off in ("CUDA", "CUDNN", "NCCL", "TENSORRT", "MKLDNN", "OPENCV",
                "OPENMP", "F16C", "CAFFE", "PROFILER_NVTX"):
        feats[off] = False
    feats["SIGNAL_HANDLER"] = True
    feats["PROFILER"] = True
    return feats


class Features(collections.OrderedDict):
    """Reference ``mx.runtime.Features``: mapping name → Feature."""

    instance = None

    def __new__(cls):
        if cls.instance is None:
            cls.instance = super().__new__(cls)
            cls.instance.update(
                {k: Feature(k, v) for k, v in _detect().items()})
        return cls.instance

    def __repr__(self):
        return str(list(self.values()))

    def is_enabled(self, feature_name):
        feature_name = feature_name.upper()
        if feature_name not in self:
            raise RuntimeError(f"feature '{feature_name}' is unknown; "
                               f"known: {sorted(self)}")
        return self[feature_name].enabled


def feature_list():
    """Reference ``mx.runtime.feature_list()``."""
    return list(Features().values())
