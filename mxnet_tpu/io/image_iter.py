"""ImageRecordIter: threaded JPEG-decode pipeline over RecordIO.

TPU-native redesign of the reference's v2 threaded image pipeline
(ref: src/io/iter_image_recordio_2.cc:79 ThreadedParser::ParseChunk — OMP
decode threads feeding dmlc::ThreadedIter double buffers). Two design
rules keep the Python pipeline fast enough to feed a TPU chip:

1. Workers touch ONLY GIL-releasing C code: cv2 decode/resize/crop/flip
   on uint8. No per-image numpy float math (numpy ufuncs hold the GIL,
   which is what caps a naive thread pool at a few hundred img/s).
2. Float conversion + mean/std + NCHW transpose happen ONCE per batch
   as vectorized numpy ops, and batches are assembled ahead of the
   consumer by a prefetch thread (the dmlc::ThreadedIter double-buffer
   analog).

Measured (synthetic 256x256 JPEG .rec, 224x224 rand-crop+mirror train
transform, one host): 430 img/s before this layout -> see
benchmark/input_pipeline.py for the current number.
"""
from __future__ import annotations

import concurrent.futures as _fut
import queue as _queue
import random as _pyrandom
import threading

import numpy as np

from .io import DataIter, DataBatch, DataDesc
from .._debug import locktrace as _locktrace
from ..context import cpu as _cpu
from ..ndarray import NDArray
from ..recordio import MXRecordIO, MXIndexedRecordIO, unpack

__all__ = ["ImageRecordIter"]


def _decode_and_augment(raw, data_shape, rand_crop, rand_mirror, resize,
                        rng_seed):
    """Record bytes -> (uint8 HWC RGB image, label). cv2 ops release the
    GIL; everything else here is O(1) Python. Pre-decoded raw-pixel
    records (recordio.pack_raw_img) skip cv2.imdecode entirely — the
    fast path for hosts whose cores cannot keep up with JPEG decode."""
    import cv2
    from ..recordio import decode_raw_img
    header, img_bytes = unpack(raw)
    label = header.label
    img = decode_raw_img(img_bytes)
    if img is None:
        img = cv2.imdecode(np.frombuffer(img_bytes, np.uint8),
                           cv2.IMREAD_COLOR)
    if img is None:
        raise IOError("failed to decode image record")
    rng = _pyrandom.Random(rng_seed)
    if resize:
        h, w = img.shape[:2]
        scale = resize / min(h, w)
        img = cv2.resize(img, (int(w * scale + 0.5), int(h * scale + 0.5)))
    ch, cw = data_shape[1], data_shape[2]
    h, w = img.shape[:2]
    if h < ch or w < cw:
        img = cv2.resize(img, (max(w, cw), max(h, ch)))
        h, w = img.shape[:2]
    if rand_crop:
        y0 = rng.randint(0, h - ch) if h > ch else 0
        x0 = rng.randint(0, w - cw) if w > cw else 0
    else:
        y0, x0 = (h - ch) // 2, (w - cw) // 2
    img = img[y0:y0 + ch, x0:x0 + cw]
    if rand_mirror and rng.random() < 0.5:
        img = cv2.flip(img, 1)
    img = cv2.cvtColor(img, cv2.COLOR_BGR2RGB)  # uint8 HWC
    return img, np.float32(
        label if np.isscalar(label) or getattr(label, "ndim", 0) == 0
        else label[0])


class ImageRecordIter(DataIter):
    """ref: ImageRecordIter params (src/io/image_iter_common.h
    ImageRecParserParam/ImageRecordParam + normalize/augment params).

    `prefetch_buffer` batches are assembled ahead by a background
    thread (ref: iter_prefetcher.h); `dtype="uint8"` skips host-side
    normalization entirely (do it on-device) and shrinks host->HBM
    transfers 4x — the TPU-idiomatic feed."""

    def __init__(self, path_imgrec, data_shape, batch_size, path_imgidx=None,
                 shuffle=False, rand_crop=False, rand_mirror=False, resize=0,
                 mean_r=0.0, mean_g=0.0, mean_b=0.0, std_r=1.0, std_g=1.0,
                 std_b=1.0, preprocess_threads=4, label_width=1, seed=0,
                 round_batch=True, prefetch_buffer=2, dtype="float32",
                 **kwargs):
        super().__init__(batch_size)
        self.data_shape = tuple(data_shape)
        assert len(self.data_shape) == 3, "data_shape must be (C, H, W)"
        self._shuffle = shuffle
        self._rand_crop = rand_crop
        self._rand_mirror = rand_mirror
        self._resize = resize
        mean = np.array([mean_r, mean_g, mean_b], np.float32)
        std = np.array([std_r, std_g, std_b], np.float32)
        self._mean = mean if mean.any() else None
        self._std = std if (std != 1.0).any() else None
        self._dtype = np.dtype(dtype)
        self._seed = seed
        self._epoch = 0
        self._round_batch = round_batch
        self._pool = _fut.ThreadPoolExecutor(max_workers=preprocess_threads)
        self._nprefetch = max(0, int(prefetch_buffer))

        if path_imgidx:
            self._rec = MXIndexedRecordIO(path_imgidx, path_imgrec, "r")
            self._keys = list(self._rec.keys)
        else:
            self._rec = MXRecordIO(path_imgrec, "r")
            # scan once to collect record offsets for shuffling
            self._keys = None
            self._offsets = []
            while True:
                pos = self._rec.tell()
                if self._rec.read() is None:
                    break
                self._offsets.append(pos)
        self._prefetcher = None
        self._read_lock = _locktrace.named_lock("io.image_read")
        self.reset()

    @property
    def provide_data(self):
        return [DataDesc("data", (self.batch_size,) + self.data_shape,
                         dtype=self._dtype)]

    @property
    def provide_label(self):
        return [DataDesc("softmax_label", (self.batch_size,))]

    def reset(self):
        # stop (and JOIN) the old producer FIRST — it must not observe
        # the new epoch's cursor/order and steal its first batch
        if self._prefetcher is not None:
            self._prefetcher.stop()
            self._prefetcher = None
        self._epoch += 1
        order = list(self._keys if self._keys is not None
                     else range(len(self._offsets)))
        if self._shuffle:
            _pyrandom.Random(self._seed + self._epoch).shuffle(order)
        self._order = order
        self._cursor = 0
        self._prefetcher = _Prefetcher(self, self._nprefetch) \
            if self._nprefetch > 0 else None

    def _read_raw(self, key):
        # the record file handle is shared between the consumer and the
        # prefetch thread; seek+read must be atomic
        with self._read_lock:
            if self._keys is not None:
                return self._rec.read_idx(key)
            self._rec.seek_pos(self._offsets[key])
            return self._rec.read()

    def _assemble_next(self):
        """Produce the next batch synchronously (called by the prefetch
        thread, or directly when prefetch is disabled)."""
        n = len(self._order)
        if self._cursor >= n:
            raise StopIteration
        end = self._cursor + self.batch_size
        idxs = [self._order[i % n] for i in range(self._cursor, end)]
        pad = max(0, end - n)
        if pad and not self._round_batch:
            raise StopIteration
        start = self._cursor
        self._cursor = end
        raws = [self._read_raw(k) for k in idxs]  # sequential file reads
        futs = [self._pool.submit(
            _decode_and_augment, raw, self.data_shape, self._rand_crop,
            self._rand_mirror, self._resize,
            # seed varies per (epoch, global sample index) — per-slot
            # seeding would repeat the same crop/mirror stream every batch
            self._seed + self._epoch * 1000003 + start + i)
            for i, raw in enumerate(raws)]       # parallel, GIL-free decode
        imgs, labels = zip(*[f.result() for f in futs])
        batch_hwc = np.stack(imgs)               # [N, H, W, C] uint8
        if self._dtype == np.uint8:
            data = np.ascontiguousarray(batch_hwc.transpose(0, 3, 1, 2))
        else:
            # ONE vectorized normalize pass per batch (not per image —
            # numpy holds the GIL, so per-image math serializes workers)
            x = batch_hwc.astype(self._dtype)
            if self._mean is not None:
                x -= self._mean.astype(self._dtype)
            if self._std is not None:
                x /= self._std.astype(self._dtype)
            data = np.ascontiguousarray(x.transpose(0, 3, 1, 2))
        # batches live on the HOST as plain numpy (reference iterators
        # yield CPU NDArrays; the consumer moves them to the
        # accelerator). NDArray(np, ctx=cpu) keeps them off the device:
        # a jax placement here would round-trip every batch over the
        # TPU interconnect before training even starts
        data = NDArray(data, ctx=_cpu())
        label = NDArray(np.asarray(labels, np.float32), ctx=_cpu())
        return DataBatch(data=[data], label=[label], pad=pad,
                         provide_data=self.provide_data,
                         provide_label=self.provide_label)

    def next(self):
        if self._prefetcher is not None:
            return self._prefetcher.next()
        return self._assemble_next()


class _Prefetcher:
    """Background batch assembly (ref: src/io/iter_prefetcher.h — the
    consumer overlaps device compute with host decode)."""

    def __init__(self, it, depth):
        self._q = _queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._terminal = None  # True after StopIteration, or the Exception

        def run():
            while not self._stop.is_set():
                try:
                    item = it._assemble_next()
                except StopIteration:
                    item = None
                except Exception as e:  # mxlint: disable=MX009 (forwarded to the consumer's next() and counted via _stats.bump -> profiler.account)
                    from . import _stats
                    # counted with profiling off too: _stats.bump feeds
                    # both metrics()['io'] and the unconditional
                    # profiler.account ledger
                    _stats.bump("prefetch_worker_deaths")
                    item = e
                # bounded put that keeps observing the stop flag, so
                # stop() never deadlocks against a full queue
                while not self._stop.is_set():
                    try:
                        self._q.put(item, timeout=0.1)
                        break
                    except _queue.Full:
                        continue
                if item is None or isinstance(item, Exception):
                    return

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()

    def next(self):
        if self._terminal is not None:
            # producer already finished — keep re-raising (matching the
            # non-prefetch path) instead of blocking on a dead queue
            if isinstance(self._terminal, Exception):
                raise self._terminal
            raise StopIteration
        item = self._q.get()
        if item is None:
            self._terminal = True
            raise StopIteration
        if isinstance(item, Exception):
            self._terminal = item
            raise item
        return item

    def stop(self):
        """Stop the producer and JOIN it — a reset() must not start a
        new producer while the old one still holds the record reader."""
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except _queue.Empty:
            pass
        self._thread.join(timeout=10)
