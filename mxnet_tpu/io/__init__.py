"""Data iterators (the legacy ``mx.io`` surface).

Reference: ``python/mxnet/io/io.py:?`` (``DataIter``/``DataBatch``/
``DataDesc``, ``NDArrayIter``, ``ResizeIter``, ``PrefetchingIter``) and the
C++ iterators in ``src/io/`` (``ImageRecordIter`` —
iter_image_recordio_2.cc:?, ``CSVIter``, ``LibSVMIter``, MNISTIter).

TPU-native: iterators produce host-side numpy batches; device transfer is a
single (optionally mesh-sharded) device_put at NDArray creation — the
replacement for the reference's prefetch-to-pinned-memory path.  Threaded
prefetch replicates dmlc ThreadedIter's overlap of decode with compute.
"""
from __future__ import annotations

import functools
import os
import threading
import queue as _queue
from collections import namedtuple

import numpy as np

from ..base import MXNetError
from ..ndarray import NDArray

__all__ = ["DataDesc", "DataBatch", "DataIter", "NDArrayIter", "CSVIter",
           "LibSVMIter", "ResizeIter", "PrefetchingIter",
           "ImageRecordIter", "MNISTIter"]

#: reviewed signature budget (mxlint T15): the jitted numeric-finish
#: kernel compiles once per (batch avals, dtype) of the pipeline's
#: output spec — fixed at iterator construction, so steady state is 1
__compile_signatures__ = {
    "io_numeric_finish": "1 per (batch avals, dtype) per iterator",
}


class DataDesc(namedtuple("DataDesc", ["name", "shape"])):
    """Shape/type descriptor (reference ``mx.io.DataDesc``)."""

    def __new__(cls, name, shape, dtype=np.float32, layout="NCHW"):
        ret = super().__new__(cls, name, shape)
        ret.dtype = dtype
        ret.layout = layout
        return ret

    @staticmethod
    def get_batch_axis(layout):
        if layout is None:
            return 0
        return layout.find("N")


class DataBatch:
    """One batch: data list + label list + pad/index bookkeeping."""

    def __init__(self, data, label=None, pad=None, index=None,
                 bucket_key=None, provide_data=None, provide_label=None):
        if data is not None and not isinstance(data, (list, tuple)):
            data = [data]
        if label is not None and not isinstance(label, (list, tuple)):
            label = [label]
        self.data = data
        self.label = label
        self.pad = pad
        self.index = index
        self.bucket_key = bucket_key
        self.provide_data = provide_data
        self.provide_label = provide_label


class DataIter:
    """Iterator base (reference ``mx.io.DataIter``): next/reset/iter_next +
    provide_data/provide_label descriptors."""

    def __init__(self, batch_size=0):
        self.batch_size = batch_size

    def __iter__(self):
        return self

    def reset(self):
        pass

    def next(self):
        if self.iter_next():
            return DataBatch(data=self.getdata(), label=self.getlabel(),
                             pad=self.getpad(), index=self.getindex())
        raise StopIteration

    def __next__(self):
        return self.next()

    def iter_next(self):
        raise NotImplementedError

    def getdata(self):
        raise NotImplementedError

    def getlabel(self):
        raise NotImplementedError

    def getindex(self):
        return None

    def getpad(self):
        raise NotImplementedError


def _init_data(data, allow_empty, default_name):
    if data is None:
        if not allow_empty:
            raise MXNetError("data cannot be None")
        return []
    if isinstance(data, (np.ndarray, NDArray)):
        data = [data]
    if isinstance(data, (list, tuple)):
        if not allow_empty:
            assert len(data) > 0
        if len(data) == 1:
            data = {default_name: data[0]}
        else:
            data = {f"_{i}_{default_name}": d for i, d in enumerate(data)}
    if not isinstance(data, dict):
        raise MXNetError(
            "data must be NDArray, numpy.ndarray, list or dict of them")
    return [(k, np.asarray(v.asnumpy() if isinstance(v, NDArray) else v))
            for k, v in data.items()]


class NDArrayIter(DataIter):
    """Batches over in-memory arrays with shuffle/pad/discard last-batch
    handling (reference ``mx.io.NDArrayIter``)."""

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle="pad", data_name="data",
                 label_name="softmax_label"):
        super().__init__(batch_size)
        self.data = _init_data(data, allow_empty=False,
                               default_name=data_name)
        self.label = _init_data(label, allow_empty=True,
                                default_name=label_name)
        self.num_data = self.data[0][1].shape[0]
        self.shuffle = shuffle
        self.last_batch_handle = last_batch_handle
        if last_batch_handle == "discard":
            self.num_batches = self.num_data // batch_size
        else:
            self.num_batches = (self.num_data + batch_size - 1) // batch_size
        self.reset()

    @property
    def provide_data(self):
        return [DataDesc(k, (self.batch_size,) + v.shape[1:],
                         dtype=v.dtype) for k, v in self.data]

    @property
    def provide_label(self):
        return [DataDesc(k, (self.batch_size,) + v.shape[1:],
                         dtype=v.dtype) for k, v in self.label]

    def reset(self):
        self.cursor = 0
        if self.shuffle:
            self.order = np.random.permutation(self.num_data)
        else:
            self.order = np.arange(self.num_data)

    def iter_next(self):
        return self.cursor < self.num_batches * self.batch_size and \
            self.cursor < self.num_data if \
            self.last_batch_handle != "discard" else \
            self.cursor + self.batch_size <= self.num_data

    def next(self):
        if not self.iter_next():
            raise StopIteration
        lo = self.cursor
        hi = min(lo + self.batch_size, self.num_data)
        idx = self.order[lo:hi]
        pad = self.batch_size - len(idx)
        if pad and self.last_batch_handle == "pad":
            idx = np.concatenate([idx, self.order[:pad]])
        self.cursor += self.batch_size
        data = [NDArray(arr[idx]) for _, arr in self.data]
        label = [NDArray(arr[idx]) for _, arr in self.label]
        return DataBatch(data=data, label=label or None, pad=pad,
                         index=idx,
                         provide_data=self.provide_data,
                         provide_label=self.provide_label)

    def getpad(self):
        return 0


class CSVIter(DataIter):
    """CSV reader (reference C++ ``CSVIter``, src/io/iter_csv.cc:?)."""

    def __init__(self, data_csv, data_shape, label_csv=None, label_shape=(1,),
                 batch_size=1, round_batch=True, **kwargs):
        super().__init__(batch_size)
        data = np.loadtxt(data_csv, delimiter=",", dtype=np.float32,
                          ndmin=2)
        data = data.reshape((-1,) + tuple(data_shape))
        label = None
        if label_csv is not None:
            label = np.loadtxt(label_csv, delimiter=",", dtype=np.float32,
                               ndmin=2).reshape((-1,) + tuple(label_shape))
        self._inner = NDArrayIter(
            data, label, batch_size,
            last_batch_handle="pad" if round_batch else "discard")

    def reset(self):
        self._inner.reset()

    def next(self):
        return self._inner.next()

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label


class LibSVMIter(DataIter):
    """LibSVM text reader → CSR batches (reference C++ ``LibSVMIter``,
    src/io/iter_libsvm.cc:? — the sparse pipeline feeding the
    factorization-machine / linear-model workloads, SURVEY §2.5)."""

    def __init__(self, data_libsvm, data_shape, batch_size=1,
                 label_libsvm=None, round_batch=True, **kwargs):
        super().__init__(batch_size)
        self._num_features = int(data_shape[0]) \
            if isinstance(data_shape, (tuple, list)) else int(data_shape)
        labels = []
        indices, values = [], []
        indptr = [0]
        with open(data_libsvm) as f:
            for line in f:
                parts = line.split()
                if not parts:
                    continue
                labels.append(float(parts[0]))
                for tok in parts[1:]:
                    i, v = tok.split(":")
                    indices.append(int(i))
                    values.append(float(v))
                indptr.append(len(indices))
        if label_libsvm is not None:
            # separate label file overrides the data file's lead column
            # (reference LibSVMIter contract)
            labels = []
            with open(label_libsvm) as f:
                for line in f:
                    parts = line.split()
                    if parts:
                        labels.append(float(parts[0]))
            if len(labels) != len(indptr) - 1:
                raise MXNetError(
                    f"label file has {len(labels)} rows but data file has "
                    f"{len(indptr) - 1}")
        self._labels = np.asarray(labels, np.float32)
        self._indptr = np.asarray(indptr, np.int64)
        self._indices = np.asarray(indices, np.int64)
        self._values = np.asarray(values, np.float32)
        self._n = len(labels)
        self._cursor = 0
        self._round = round_batch

    @property
    def provide_data(self):
        return [DataDesc("data", (self.batch_size, self._num_features))]

    @property
    def provide_label(self):
        return [DataDesc("softmax_label", (self.batch_size,))]

    def reset(self):
        self._cursor = 0

    def _row_slice(self, lo, hi):
        from ..ndarray import sparse as sp

        start, end = self._indptr[lo], self._indptr[hi]
        indptr = self._indptr[lo:hi + 1] - start
        return sp.CSRNDArray(self._values[start:end],
                             self._indices[start:end], indptr,
                             (hi - lo, self._num_features))

    def next(self):
        if self._cursor >= self._n:
            raise StopIteration
        lo = self._cursor
        hi = min(lo + self.batch_size, self._n)
        pad = self.batch_size - (hi - lo)
        if pad and not self._round:
            raise StopIteration
        csr = self._row_slice(lo, hi)
        label = self._labels[lo:hi]
        if pad:
            # wrap around (reference round_batch contract); loop covers
            # batch_size > dataset size
            from ..ndarray import sparse as sp

            data = [np.asarray(csr.data._data)]
            indices = [np.asarray(csr.indices._data)]
            indptr = np.asarray(csr.indptr._data)
            labels = [label]
            remaining = pad
            while remaining > 0:
                take = min(remaining, self._n)
                extra = self._row_slice(0, take)
                data.append(np.asarray(extra.data._data))
                indices.append(np.asarray(extra.indices._data))
                indptr = np.concatenate(
                    [indptr,
                     np.asarray(extra.indptr._data)[1:] + indptr[-1]])
                labels.append(self._labels[:take])
                remaining -= take
            csr = sp.CSRNDArray(np.concatenate(data),
                                np.concatenate(indices), indptr,
                                (self.batch_size, self._num_features))
            label = np.concatenate(labels)
        self._cursor = hi
        return DataBatch(data=[csr], label=[NDArray(label)], pad=pad)


class ResizeIter(DataIter):
    """Resize an iterator to a fixed number of batches per epoch
    (reference ``mx.io.ResizeIter``)."""

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__(data_iter.batch_size)
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.current_batch = None

    def reset(self):
        self.cur = 0
        if self.reset_internal:
            self.data_iter.reset()

    def iter_next(self):
        if self.cur == self.size:
            return False
        try:
            self.current_batch = self.data_iter.next()
        except StopIteration:
            self.data_iter.reset()
            self.current_batch = self.data_iter.next()
        self.cur += 1
        return True

    def next(self):
        if self.iter_next():
            return self.current_batch
        raise StopIteration

    @property
    def provide_data(self):
        return self.data_iter.provide_data

    @property
    def provide_label(self):
        return self.data_iter.provide_label


class PrefetchingIter(DataIter):
    """Threaded prefetch decorator (reference ``mx.io.PrefetchingIter`` /
    dmlc ThreadedIter — overlaps host decode with device compute)."""

    def __init__(self, iters, rename_data=None, rename_label=None,
                 prefetch_depth=2):
        if not isinstance(iters, (list, tuple)):
            iters = [iters]
        assert len(iters) == 1, "single-iter prefetch (reference parity)"
        self.iter = iters[0]
        super().__init__(self.iter.batch_size)
        self._depth = prefetch_depth
        self._queue = None
        self._thread = None
        self._start()

    def _start(self):
        self._queue = _queue.Queue(maxsize=self._depth)
        self._stop = threading.Event()

        def worker():
            while not self._stop.is_set():
                try:
                    batch = self.iter.next()
                except StopIteration:
                    self._queue.put(None)
                    return
                except Exception as e:  # propagate errors to consumer
                    self._queue.put(e)
                    return
                self._queue.put(batch)

        self._thread = threading.Thread(target=worker, daemon=True,
                                        name="mxt-io-prefetch")
        self._thread.start()

    def reset(self):
        self._stop.set()
        try:
            while True:
                self._queue.get_nowait()
        except _queue.Empty:
            pass
        self._thread.join(timeout=5)
        self.iter.reset()
        self._start()

    def next(self):
        item = self._queue.get()
        if item is None:
            raise StopIteration
        if isinstance(item, Exception):
            raise item
        return item

    @property
    def provide_data(self):
        return self.iter.provide_data

    @property
    def provide_label(self):
        return self.iter.provide_label


@functools.lru_cache(maxsize=16)  # bounded: one executable per config
def _numeric_finish(mean, std, scale):
    """One shared jitted cast+normalize+CHW program per (mean, std,
    scale) config — train/val iterator pairs reuse a single compile."""
    import jax
    import jax.numpy as jnp

    mean_a = np.asarray(mean, np.float32)
    std_a = np.asarray(std, np.float32)

    def f(x):  # (B, H, W, C) uint8
        y = x.astype(jnp.float32)
        if scale != 1.0:
            y = y * scale
        if mean_a.any():
            y = y - mean_a
        if (std_a != 1).any():
            y = y / std_a
        return jnp.transpose(y, (0, 3, 1, 2))

    return jax.jit(f)


class ImageRecordIter(DataIter):
    """RecordIO image pipeline: shard-read → decode → augment → batch →
    prefetch (reference C++ ``ImageRecordIter``,
    src/io/iter_image_recordio_2.cc:? — here a python pipeline over the
    byte-compatible recordio reader with cv2 decode)."""

    def __init__(self, path_imgrec, data_shape, batch_size, label_width=1,
                 shuffle=False, mean_r=0.0, mean_g=0.0, mean_b=0.0,
                 std_r=1.0, std_g=1.0, std_b=1.0, scale=1.0,
                 rand_crop=False, rand_mirror=False, resize=-1,
                 path_imgidx=None, num_parts=1, part_index=0,
                 preprocess_threads=2, prefetch_buffer=2,
                 round_batch=True, seed=0, **kwargs):
        super().__init__(batch_size)
        from .. import recordio

        self.data_shape = tuple(data_shape)
        self.label_width = label_width
        self._rng = np.random.RandomState(seed)
        self._aug = dict(mean=(mean_r, mean_g, mean_b),
                         std=(std_r, std_g, std_b), scale=scale,
                         rand_crop=rand_crop, rand_mirror=rand_mirror,
                         resize=resize)
        from .. import _native

        self._pf = None
        self._records = None
        if _native.available() and not kwargs.get("no_native"):
            # native streaming path: C++ indexed reader + engine-scheduled
            # batch prefetch (src/cpp/mxt_recordio.cc); records stay on
            # disk, batches are read by worker threads ahead of consumption.
            # One prefetcher lives for the iterator's lifetime (the index
            # scan + thread pool happen once, not per epoch).
            self._cap = max(int(prefetch_buffer), 1)
            self._pf = _native.Prefetcher(path_imgrec,
                                          nthreads=preprocess_threads,
                                          capacity=self._cap)
            self._sched = self._consumed = 0
            self._batches = []
            if path_imgidx and os.path.isfile(path_imgidx):
                # honour the .idx: shard by KEY order (which may be a
                # pre-shuffle or a subset), mapping byte offsets to the
                # reader's scan-order indices
                off2pos = {self._pf._reader.offset(i): i
                           for i in range(len(self._pf))}
                positions = []
                with open(path_imgidx) as fin:
                    for line in fin:
                        parts = line.strip().split("\t")
                        if len(parts) >= 2:
                            positions.append(off2pos[int(parts[1])])
                self._indices = np.asarray(
                    positions[part_index::num_parts], dtype=np.int64)
            else:
                self._indices = np.arange(
                    len(self._pf))[part_index::num_parts]
        else:
            # pure-python fallback: load the shard's records into memory
            if path_imgidx:
                rec = recordio.MXIndexedRecordIO(path_imgidx, path_imgrec,
                                                 "r")
                keys = rec.keys
            else:
                rec = recordio.MXRecordIO(path_imgrec, "r")
                keys = None
            self._records = []
            if keys is not None:
                use = keys[part_index::num_parts]
                for k in use:
                    self._records.append(rec.read_idx(k))
            else:
                i = 0
                while True:
                    payload = rec.read()
                    if payload is None:
                        break
                    if i % num_parts == part_index:
                        self._records.append(payload)
                    i += 1
            rec.close()
        self.shuffle = shuffle
        self.round_batch = round_batch
        self.reset()

    @property
    def provide_data(self):
        return [DataDesc("data", (self.batch_size,) + self.data_shape)]

    @property
    def provide_label(self):
        shape = (self.batch_size,) if self.label_width == 1 else \
            (self.batch_size, self.label_width)
        return [DataDesc("softmax_label", shape)]

    def _plan_batches(self, order):
        """Split an epoch order into (index_array, pad) batch plans;
        wrap-around padding tiles the order (shards smaller than one batch
        still fill it)."""
        plans = []
        n = len(order)
        for s in range(0, n, self.batch_size):
            idx = order[s:s + self.batch_size]
            pad = self.batch_size - len(idx)
            if pad:
                if not self.round_batch:
                    break
                idx = np.concatenate([idx, np.resize(order, pad)])
            plans.append((idx, pad))
        return plans

    def reset(self):
        if self._pf is not None:
            # drain batches scheduled but unconsumed (early reset)
            while self._consumed < self._sched:
                self._pf.next()
                self._consumed += 1
            order = self._indices.copy()
            if self.shuffle:
                self._rng.shuffle(order)
            self._batches = self._plan_batches(order)
            self._sched = self._consumed = 0
            while self._sched < min(len(self._batches), self._cap + 1):
                self._pf.schedule(self._batches[self._sched][0])
                self._sched += 1
        else:
            order = np.arange(len(self._records))
            if self.shuffle:
                self._rng.shuffle(order)
            self._batches = self._plan_batches(order)
            self._consumed = 0

    def _device_finish(self):
        """Numeric augmentation stage, ON DEVICE: batches cross host→HBM
        as HWC uint8 (4× less transfer than float32 CHW), then one jitted
        cast+normalize+transpose runs where the bandwidth is."""
        return _numeric_finish(tuple(self._aug["mean"]),
                               tuple(self._aug["std"]),
                               float(self._aug["scale"]))

    def _make_batch(self, payloads, pad):
        from .. import recordio
        from ..image import augment_geom, imdecode_raw

        datas, labels = [], []
        for payload in payloads:
            header, img_bytes = recordio.unpack(payload)
            img = imdecode_raw(img_bytes)
            img = augment_geom(img, self.data_shape, self._rng,
                               rand_crop=self._aug["rand_crop"],
                               rand_mirror=self._aug["rand_mirror"],
                               resize=self._aug["resize"])
            datas.append(img)
            label = header.label
            if isinstance(label, np.ndarray) and self.label_width == 1:
                label = label[0] if label.size else 0.0
            labels.append(label)
        batch_u8 = NDArray(np.stack(datas))
        data = NDArray(self._device_finish()(batch_u8._data))
        label = NDArray(np.asarray(labels, dtype=np.float32))
        return DataBatch(data=[data], label=[label], pad=pad,
                         provide_data=self.provide_data,
                         provide_label=self.provide_label)

    def next(self):
        if self._consumed >= len(self._batches):
            raise StopIteration
        idx, pad = self._batches[self._consumed]
        self._consumed += 1
        if self._pf is not None:
            payloads = self._pf.next()
            if self._sched < len(self._batches):
                self._pf.schedule(self._batches[self._sched][0])
                self._sched += 1
        else:
            payloads = [self._records[i] for i in idx]
        return self._make_batch(payloads, pad)


class MNISTIter(NDArrayIter):
    """MNIST idx-format reader (reference src/io/iter_mnist.cc:?)."""

    def __init__(self, image, label, batch_size=128, shuffle=True,
                 flat=False, **kwargs):
        import gzip
        import struct

        def read_idx(path):
            opener = gzip.open if path.endswith(".gz") else open
            with opener(path, "rb") as f:
                magic = struct.unpack(">I", f.read(4))[0]
                ndim = magic & 0xFF
                dims = struct.unpack(">" + "I" * ndim, f.read(4 * ndim))
                return np.frombuffer(f.read(), dtype=np.uint8).reshape(dims)

        images = read_idx(image).astype(np.float32) / 255.0
        labels = read_idx(label).astype(np.float32)
        if flat:
            images = images.reshape(len(images), -1)
        else:
            images = images.reshape(len(images), 1, *images.shape[1:])
        super().__init__(images, labels, batch_size, shuffle=shuffle,
                         last_batch_handle="discard")
