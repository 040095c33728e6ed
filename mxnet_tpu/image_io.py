"""ImageRecordIter: the packed-image training data pipeline.

Parity: ``src/io/iter_image_recordio.cc`` (+ augmenter/normalize/batch/
prefetch stages) and its Python-facing kwargs (``mx.io.ImageRecordIter``).
The heavy path runs in the native C++ library (``cpp/image_iter.cc``):
multithreaded JPEG decode + augment + normalize into pinned float batches,
overlapped with device compute — the reference's OMP parser + dmlc
ThreadedIter prefetcher collapsed into one component. A pure-Python
fallback (cv2-based) keeps unbuilt trees working.
"""
from __future__ import annotations

import ctypes
import os
import queue as _queue
import threading
import time as _time

import numpy as np

from .base import MXNetError
from .libinfo import get_lib, check_call
from . import ndarray as nd
from . import telemetry as tele
from .io import DataIter, DataBatch
from . import recordio as rec

# decode-pool metrics (doc/observability.md "IO pipeline"). The
# per-batch decode time is measured WORKER-side and rides the existing
# (epoch, batch, slot, pad) announcement tuple back to the consumer —
# no new shared state; only the consumer process feeds the registry.
_TM_DECODE_MS = tele.histogram("io.decode_batch_ms")
_TM_POOL_WAIT_MS = tele.histogram("io.pool_wait_ms")
_TM_POOL_STARVED = tele.counter("io.pool_starved")
_TM_POOL_BATCHES = tele.counter("io.pool_batches")
_TM_POOL_QDEPTH = tele.gauge("io.pool_queue_depth")

__all__ = ["ImageRecordIter", "device_augment_batch",
           "DeviceAugmentIter"]


_U64 = (1 << 64) - 1


class _LightRNG:
    """Tiny per-record RNG (splitmix64) for the augmentation draws.

    Constructing a numpy RandomState per record costs ~0.2-0.35 ms —
    a fifth of the whole 1.5 ms/img decode budget — where this is ~1 µs.
    Only the two draw kinds the augmenters use exist (numpy-convention
    ``randint`` with exclusive high, ``uniform``); numpy distribution
    parity is NOT required because BOTH engines draw from this stream —
    which is exactly what the byte-identity guarantee rests on."""

    __slots__ = ("_s",)

    def __init__(self, state):
        self._s = state & _U64

    def _next(self):
        self._s = (self._s + 0x9E3779B97F4A7C15) & _U64
        z = self._s
        z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _U64
        z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _U64
        return z ^ (z >> 31)

    def randint(self, low, high=None):
        if high is None:
            low, high = 0, low
        return low + self._next() % (high - low)

    def uniform(self, low, high):
        return low + (high - low) * (self._next() / float(1 << 64))


def _record_rng(seed, epoch, pos):
    """Per-record RNG for the augmentation draws (crop/mirror/rotate/HSL),
    keyed by (seed, epoch, position-in-epoch) instead of a sequential
    stream — so record ``pos``'s augmentation is the same no matter
    which worker decodes it (or whether any pool exists at all): the
    foundation of the num_workers byte-identical guarantee."""
    return _LightRNG((seed & 0xffffffff) * 0x9E3779B97F4A7C15
                     + (epoch & 0xffffffff) * 0xBF58476D1CE4E5B9
                     + pos * 0x94D049BB133111EB)


def device_augment_batch(data_u8, key=None, crop_shape=None,
                         rand_crop=False, rand_mirror=False,
                         mean=(0.0, 0.0, 0.0), scale=1.0):
    """The device-side augmentation stage for ``device_augment`` batches.

    Jit-friendly: put this INSIDE the compiled train step. Takes the
    iterator's ``[B, H, W, C]`` uint8 batch, applies (optionally random)
    crop to ``crop_shape=(h, w)``, random horizontal flip, and
    per-channel ``(x - mean) * scale`` normalization, returning the
    ``[B, C, h, w]`` float32 batch the host augmenter would have
    produced — but with the uint8 bytes (4x less infeed traffic) crossing
    to the device and the float work running there (reference analogue:
    iter_normalize.h + image_augmenter.h, moved on-chip). ``key`` is a
    jax PRNG key, required when rand_crop/rand_mirror."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    b, big_h, big_w, c = data_u8.shape
    h, w = crop_shape if crop_shape is not None else (big_h, big_w)
    if (rand_crop or rand_mirror) and key is None:
        raise MXNetError("device_augment_batch: random augmentation "
                         "needs a PRNG key")
    x = data_u8
    if rand_crop and (h < big_h or w < big_w):
        ky, kx, key = jax.random.split(key, 3)
        y0s = jax.random.randint(ky, (b,), 0, big_h - h + 1,
                                 dtype=jnp.int32)
        x0s = jax.random.randint(kx, (b,), 0, big_w - w + 1,
                                 dtype=jnp.int32)
        x = jax.vmap(lambda img, y0, x0: lax.dynamic_slice(
            img, (y0, x0, jnp.int32(0)), (h, w, c)))(x, y0s, x0s)
    elif h < big_h or w < big_w:
        y0 = (big_h - h) // 2
        x0 = (big_w - w) // 2
        x = x[:, y0:y0 + h, x0:x0 + w, :]
    if rand_mirror:
        km, key = jax.random.split(key)
        flip = jax.random.bernoulli(km, 0.5, (b,))
        x = jnp.where(flip[:, None, None, None], x[:, :, ::-1, :], x)
    xf = x.astype(jnp.float32)
    xf = (xf - jnp.asarray(mean, jnp.float32)[:c]) * jnp.float32(scale)
    return jnp.transpose(xf, (0, 3, 1, 2))


class ImageRecordIter(DataIter):
    """Iterate packed image records as normalized NCHW float batches.

    Parameters (reference kwarg names): path_imgrec, data_shape (c,h,w),
    batch_size, label_width, mean_r/g/b, scale, resize (shorter edge),
    rand_crop, rand_mirror, shuffle, seed, num_parts, part_index,
    preprocess_threads, prefetch_buffer, round_batch.

    TPU-era extensions: ``device_augment=True`` emits uint8 HWC batches
    at ``data_shape`` (host does decode+resize+center-crop only; apply
    ``device_augment_batch`` inside the compiled step for random
    crop/flip/normalize — 4x less infeed traffic).
    ``scaled_decode=False`` disables the reduced-DCT JPEG decode
    shortcut (on by default; exact no-op whenever no reduction fits).
    ``num_workers=N`` (default ``MXNET_IO_NUM_WORKERS``, 0) fans decode
    over N pool workers — forked processes by default
    (``worker_mode='thread'`` for debugging), each collating finished
    batches into shared memory with ``queue_depth`` batches buffered
    per worker. Epoch contents are byte-identical to the serial engine
    for any worker count under a fixed seed, a worker crash raises
    instead of hanging, and batches are served from reused slot
    buffers (consume or copy before the next iteration — the same
    contract as ``iter_numpy``). ``path_imgidx`` names the
    MXIndexedRecordIO sidecar so startup reads offsets from the index
    instead of scanning the record file. See doc/io_pipeline.md.
    """

    def __init__(self, path_imgrec, data_shape, batch_size, label_width=1,
                 mean_r=0.0, mean_g=0.0, mean_b=0.0, scale=1.0, resize=0,
                 rand_crop=False, rand_mirror=False, shuffle=False, seed=0,
                 num_parts=1, part_index=0, preprocess_threads=4,
                 prefetch_buffer=4, round_batch=True, data_name="data",
                 label_name="softmax_label", mean_img=None,
                 max_rotate_angle=0, random_h=0, random_s=0, random_l=0,
                 device_augment=False, scaled_decode=True,
                 num_workers=None, worker_mode=None, queue_depth=None,
                 path_imgidx=None):
        super().__init__()
        if len(data_shape) != 3:
            raise MXNetError("data_shape must be (channels, height, width)")
        self.batch_size = batch_size
        self._data_shape = tuple(data_shape)
        self._label_width = label_width
        self._data_name = data_name
        self._label_name = label_name
        self._pad = 0
        self._data = None
        self._label = None
        # device_augment: the host emits uint8 HWC batches at data_shape
        # (decode + resize + CENTER crop only — 4x less infeed traffic,
        # no host float pass); random crop/flip/normalize run inside the
        # compiled step via ``device_augment_batch``. rand_crop /
        # rand_mirror / mean / scale become the DEVICE stage's job.
        self._device_augment = bool(device_augment)
        if num_workers is None:
            num_workers = int(os.environ.get("MXNET_IO_NUM_WORKERS",
                                             "0") or 0)
        if worker_mode is None:
            worker_mode = os.environ.get("MXNET_IO_WORKER_MODE",
                                         "process")
        self._num_workers = int(num_workers)

        # mean-image subtraction (reference iter_normalize.h: load the
        # cached mean file, computing + saving it on first use) and the
        # rotate/HSL augmenters (image_augmenter.h) live in the Python
        # engine; requesting them — or the ``num_workers`` decode pool,
        # whose workers ARE the parallelism the native engine gets from
        # its OMP threads — routes past the native decoder.
        extended = (mean_img is not None or max_rotate_angle or random_h
                    or random_s or random_l or self._num_workers > 0)
        self._lib = None if extended else get_lib()
        if self._lib is not None:
            self.handle = ctypes.c_void_p()
            c, h, w = data_shape
            check_call(self._lib.MXTImRecIterCreateEx(
                ctypes.c_char_p(path_imgrec.encode()),
                ctypes.c_int(batch_size), ctypes.c_int(c), ctypes.c_int(h),
                ctypes.c_int(w), ctypes.c_int(label_width),
                ctypes.c_float(mean_r), ctypes.c_float(mean_g),
                ctypes.c_float(mean_b), ctypes.c_float(scale),
                ctypes.c_int(resize),
                ctypes.c_int(int(rand_crop and not device_augment)),
                ctypes.c_int(int(rand_mirror and not device_augment)),
                ctypes.c_int(int(shuffle)),
                ctypes.c_uint(seed), ctypes.c_int(num_parts),
                ctypes.c_int(part_index), ctypes.c_int(preprocess_threads),
                ctypes.c_int(prefetch_buffer), ctypes.c_int(int(round_batch)),
                ctypes.c_int(int(device_augment)),
                ctypes.c_int(int(scaled_decode)),
                ctypes.byref(self.handle)))
            if device_augment:
                self._buf_data = np.empty((batch_size, h, w, c),
                                          dtype=np.uint8)
            else:
                self._buf_data = np.empty((batch_size,) + self._data_shape,
                                          dtype=np.float32)
            self._buf_label = np.empty((batch_size, label_width),
                                       dtype=np.float32)
        else:
            self.handle = None
            kwargs = dict(mean_img=mean_img,
                          max_rotate_angle=max_rotate_angle,
                          random_h=random_h, random_s=random_s,
                          random_l=random_l,
                          out_uint8=device_augment,
                          scaled_decode=scaled_decode,
                          path_imgidx=path_imgidx)
            args = (path_imgrec, self._data_shape, batch_size,
                    label_width, (mean_r, mean_g, mean_b), scale, resize,
                    rand_crop and not device_augment,
                    rand_mirror and not device_augment, shuffle,
                    seed, num_parts, part_index, round_batch)
            if self._num_workers > 0:
                self._py = _ParallelEngine(
                    *args, num_workers=self._num_workers,
                    worker_mode=worker_mode, queue_depth=queue_depth,
                    **kwargs)
            else:
                self._py = _PyEngine(*args, **kwargs)

    @property
    def provide_data(self):
        if self._device_augment:
            c, h, w = self._data_shape
            return [(self._data_name, (self.batch_size, h, w, c))]
        return [(self._data_name, (self.batch_size,) + self._data_shape)]

    @property
    def provide_label(self):
        return [(self._label_name,
                 (self.batch_size,)
                 if self._label_width == 1
                 else (self.batch_size, self._label_width))]

    def reset(self):
        if self._lib is not None:
            check_call(self._lib.MXTImRecIterReset(self.handle))
        else:
            self._py.reset()

    def _native_next(self):
        """One native-iterator step into the reused buffers; returns
        (has_batch, pad). Shared by iter_next and iter_numpy."""
        has = ctypes.c_int()
        pad = ctypes.c_int()
        if self._device_augment:
            check_call(self._lib.MXTImRecIterNextU8(
                self.handle,
                self._buf_data.ctypes.data_as(
                    ctypes.POINTER(ctypes.c_uint8)),
                self._buf_label.ctypes.data_as(
                    ctypes.POINTER(ctypes.c_float)),
                ctypes.byref(pad), ctypes.byref(has)))
        else:
            check_call(self._lib.MXTImRecIterNext(
                self.handle,
                self._buf_data.ctypes.data_as(
                    ctypes.POINTER(ctypes.c_float)),
                self._buf_label.ctypes.data_as(
                    ctypes.POINTER(ctypes.c_float)),
                ctypes.byref(pad), ctypes.byref(has)))
        return bool(has.value), pad.value

    def iter_next(self):
        if self._lib is not None:
            has, pad = self._native_next()
            if not has:
                return False
            self._pad = pad
            data, label = self._buf_data, self._buf_label
            reused = True
        else:
            got = self._py.next()
            if got is None:
                return False
            data, label, self._pad = got
            reused = getattr(self._py, "reuses_buffers", False)
        if self._label_width == 1:
            label = label.reshape(self.batch_size)
        if reused:
            # the DataBatch protocol hands out long-lived arrays, but
            # jnp.asarray can alias page-aligned host memory ZERO-COPY
            # on the cpu backend — wrapping a reused decode buffer
            # (native double buffer, pool shm slot) uncopied would let
            # later batches mutate earlier ones under the consumer.
            # iter_numpy stays zero-copy with its documented contract.
            data = np.array(data)
            label = np.array(label)
        self._data = nd.array(data)
        self._label = nd.array(label)
        return True

    def iter_numpy(self):
        """Yield (data, label, pad) as NUMPY arrays — the zero-copy-ish
        fast path for host-side consumers (``trainer.prefetch`` feeds
        host numpy dicts; wrapping every batch in device NDArrays would
        cost a device transfer per batch for nothing). Buffers are
        reused: consume or copy before the next iteration."""
        if self._lib is None:
            while True:
                got = self._py.next()
                if got is None:
                    return
                yield got
        while True:
            has, pad = self._native_next()
            if not has:
                return
            yield self._buf_data, self._buf_label, pad

    def getdata(self):
        return [self._data]

    def getlabel(self):
        return [self._label]

    def getpad(self):
        return self._pad

    def close(self):
        """Release the native handle / shut down the decode-worker pool
        (joined and reaped — no stray processes). Idempotent; also runs
        from ``__del__``."""
        if getattr(self, "_lib", None) is not None and self.handle:
            try:
                self._lib.MXTImRecIterFree(self.handle)
            except Exception:
                pass
            self.handle = None
        py = getattr(self, "_py", None)
        if py is not None and hasattr(py, "close"):
            py.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class _PyEngine:
    """cv2-based fallback with identical semantics (single-threaded).

    Also the decode kernel of the ``num_workers`` pool: each pool worker
    constructs one of these with pre-sharded ``offsets`` (and the
    parent's ``mean_arr``) and drives ``load_batch`` directly — the
    per-record RNG (``_record_rng``) makes any batch reproducible from
    (seed, epoch, batch index) alone, with no sequential state."""

    def __init__(self, path, data_shape, batch_size, label_width, means,
                 scale, resize, rand_crop, rand_mirror, shuffle, seed,
                 num_parts, part_index, round_batch, mean_img=None,
                 max_rotate_angle=0, random_h=0, random_s=0, random_l=0,
                 out_uint8=False, scaled_decode=True, path_imgidx=None,
                 offsets=None, mean_arr=None):
        import cv2  # noqa: F401  (validates availability early)
        self.out_uint8 = out_uint8
        self.scaled_decode = scaled_decode
        self.path = path
        self.data_shape = data_shape
        self.batch_size = batch_size
        self.label_width = label_width
        self.means = np.array(means, np.float32)
        self.scale = scale
        self.resize = resize
        self.rand_crop = rand_crop
        self.rand_mirror = rand_mirror
        self.shuffle = shuffle
        self.seed = seed
        self.round_batch = round_batch
        self.max_rotate_angle = max_rotate_angle
        self.random_h = random_h
        self.random_s = random_s
        self.random_l = random_l
        self.mean_arr = mean_arr
        self._mean_img_path = mean_img
        self.part_index = part_index
        if offsets is not None:
            # pool worker: the parent already scanned and sharded
            self._all_offsets = list(offsets)
            self.offsets = list(offsets)
        else:
            # offsets once, via the .idx sidecar when one exists
            all_offsets = rec.list_record_offsets(path, path_imgidx)
            self._all_offsets = all_offsets  # mean-img is global
            self.offsets = all_offsets[part_index::num_parts]
        if not self.offsets:
            raise MXNetError("empty shard")
        self.epoch = 0
        self.reset()
        if mean_img is not None and mean_arr is None:
            self._setup_mean_img(mean_img)

    def _setup_mean_img(self, path):
        """Load the (c,h,w) mean image, computing and caching it on first
        use like the reference (iter_normalize.h: compute over the dataset
        with augmentation off, save, then subtract per sample).

        Under ``num_parts>1`` only part 0 computes (the mean is over ALL
        records — decoding the whole dataset once, not once per worker);
        other parts wait for the cache file to appear."""
        import os
        import time as _time
        if self.part_index != 0 and not os.path.exists(path):
            deadline = _time.time() + float(
                os.environ.get("MXNET_MEAN_IMG_TIMEOUT", 600))
            while not os.path.exists(path):
                if _time.time() > deadline:
                    break  # fall through: compute locally (same result)
                _time.sleep(0.2)
        from . import ndarray as _nd
        if os.path.exists(path):
            loaded = _nd.load(path)
            arr = (loaded.get("mean_img") if isinstance(loaded, dict)
                   else loaded[0])
            self.mean_arr = arr.asnumpy().astype(np.float32)
            return
        # compute over RAW pixels: augmentation off AND scalar
        # normalization off, else the cached mean would bake in
        # mean_r/g/b and scale (reference computes over raw images)
        saved = (self.rand_crop, self.rand_mirror, self.max_rotate_angle,
                 self.random_h, self.random_s, self.random_l, self.means,
                 self.scale)
        self.rand_crop = self.rand_mirror = False
        self.max_rotate_angle = self.random_h = self.random_s = \
            self.random_l = 0
        self.means = np.zeros(3, np.float32)
        self.scale = 1.0
        # mean over ALL records, not this worker's num_parts shard —
        # every worker must subtract the SAME mean or distributed runs
        # silently train on inconsistently normalized data
        total = np.zeros(self.data_shape, np.float64)
        count = 0
        dummy_rng = _record_rng(0, 0, 0)  # augmentation is off: no draws
        for off in self._all_offsets:
            img, _ = self._load(off, dummy_rng)
            total += img
            count += 1
        self.mean_arr = (total / max(count, 1)).astype(np.float32)
        # atomic cache write: workers may race on a shared filesystem;
        # tmp (unique per pid) + os.replace means readers only ever see
        # a complete file, last writer wins with identical content
        tmp = "%s.tmp.%d" % (path, os.getpid())
        _nd.save(tmp, {"mean_img": _nd.array(self.mean_arr)})
        os.replace(tmp, path)
        (self.rand_crop, self.rand_mirror, self.max_rotate_angle,
         self.random_h, self.random_s, self.random_l, self.means,
         self.scale) = saved
        # rewind the epoch counter so cold-cache (mean computed) and
        # warm-cache (mean loaded) runs see identical shuffle/RNG streams
        self.epoch -= 1
        self.reset()

    def order_for(self, epoch):
        """Epoch ``epoch``'s record order: the shard's offsets, shuffled
        under the (seed, epoch) stream. Pure function of its arguments —
        the pool workers and the consumer derive identical orders from
        the epoch number alone."""
        order = list(self.offsets)
        if self.shuffle:
            rng = np.random.RandomState(
                ((self.seed << 10) + epoch) & 0xffffffff)
            rng.shuffle(order)
        return order

    def num_batches(self):
        """Batches per epoch (the final partial batch is served padded
        under round_batch, dropped otherwise)."""
        full, rem = divmod(len(self.offsets), self.batch_size)
        return full + (1 if rem and self.round_batch else 0)

    def reset(self):
        self.cur_epoch = self.epoch
        self.order = self.order_for(self.cur_epoch)
        self.cursor = 0
        self.epoch += 1
        self.reader = rec.MXRecordIO(self.path, "r")

    def _header_label(self, header):
        label = np.zeros(self.label_width, np.float32)
        lab = header.label
        if isinstance(lab, np.ndarray):
            label[:min(self.label_width, lab.size)] = lab[:self.label_width]
        else:
            label[0] = lab
        return label

    @staticmethod
    def _probe_size(blob):
        """(rows, cols) from JPEG SOF / PNG IHDR header bytes (the
        Python port of cpp/image_iter.cc ProbeImageSize) — no decode."""
        d = blob
        n = len(d)
        if n >= 24 and d[:4] == b"\x89PNG":
            cols = int.from_bytes(d[16:20], "big")
            rows = int.from_bytes(d[20:24], "big")
            return (rows, cols) if rows and cols else None
        if n < 4 or d[0] != 0xFF or d[1] != 0xD8:
            return None
        i = 2
        while i + 9 < n:
            if d[i] != 0xFF:
                return None
            marker = d[i + 1]
            if marker == 0xD8 or 0xD0 <= marker <= 0xD9:
                i += 2
                continue
            seg = (d[i + 2] << 8) | d[i + 3]
            if (0xC0 <= marker <= 0xCF and marker not in (0xC4, 0xC8,
                                                          0xCC)):
                rows = (d[i + 5] << 8) | d[i + 6]
                cols = (d[i + 7] << 8) | d[i + 8]
                return (rows, cols) if rows and cols else None
            i += 2 + seg
        return None

    def _decode(self, raw):
        """Header + pixels; JPEG/PNG decode picks the reduced-DCT scale
        (IMREAD_REDUCED_*) exactly like the native engine when the
        resize/crop target permits (byte-level header probe, no extra
        decode)."""
        import cv2

        iscolor = 1 if self.data_shape[0] == 3 else 0
        header, blob = rec.unpack(raw)
        if blob[:4] == rec._RAW_MAGIC or not self.scaled_decode:
            return rec.unpack_img(raw, iscolor)
        probed = self._probe_size(blob)
        if probed is None:
            return rec.unpack_img(raw, iscolor)
        rows, cols = probed
        buf = np.frombuffer(blob, np.uint8)
        c, h, w = self.data_shape
        need = self.resize if self.resize > 0 else max(h, w)
        flags = {8: cv2.IMREAD_REDUCED_COLOR_8,
                 4: cv2.IMREAD_REDUCED_COLOR_4,
                 2: cv2.IMREAD_REDUCED_COLOR_2} if iscolor else \
                {8: cv2.IMREAD_REDUCED_GRAYSCALE_8,
                 4: cv2.IMREAD_REDUCED_GRAYSCALE_4,
                 2: cv2.IMREAD_REDUCED_GRAYSCALE_2}
        for k in (8, 4, 2):
            if rows // k >= max(need, h) and cols // k >= max(need, w):
                img = cv2.imdecode(buf, flags[k])
                if img is not None and img.ndim == 3:
                    img = img[:, :, ::-1]  # BGR -> RGB like unpack_img
                if img is not None:
                    return header, img
                break
        return rec.unpack_img(raw, iscolor)

    def _load(self, offset, rng):
        import cv2
        self.reader.seek(offset)
        raw = self.reader.read()
        header, img = self._decode(raw)
        c, h, w = self.data_shape
        if self.resize > 0:
            shorter = min(img.shape[0], img.shape[1])
            s = self.resize / shorter
            img = cv2.resize(img, None, fx=s, fy=s)
        if img.shape[0] < h or img.shape[1] < w:
            img = cv2.resize(img, (max(img.shape[1], w),
                                   max(img.shape[0], h)))
        if self.rand_crop:
            y0 = rng.randint(0, img.shape[0] - h + 1)
            x0 = rng.randint(0, img.shape[1] - w + 1)
        else:
            y0 = (img.shape[0] - h) // 2
            x0 = (img.shape[1] - w) // 2
        img = img[y0:y0 + h, x0:x0 + w]
        if self.rand_mirror and rng.randint(2):
            img = img[:, ::-1]
        if self.max_rotate_angle:
            # works for 2-D grayscale and 3-D color alike
            angle = rng.uniform(-self.max_rotate_angle,
                                self.max_rotate_angle)
            m = cv2.getRotationMatrix2D((w / 2.0, h / 2.0), angle, 1.0)
            img = cv2.warpAffine(np.ascontiguousarray(img), m, (w, h),
                                 borderMode=cv2.BORDER_REFLECT)
        if (self.random_h or self.random_s or self.random_l) and \
                img.ndim == 3 and img.shape[2] == 3:
            # reference image_augmenter.h HSL jitter: additive uniform
            # noise per channel in HLS space
            hls = cv2.cvtColor(np.ascontiguousarray(img), cv2.COLOR_RGB2HLS)
            hls = hls.astype(np.float32)
            hls[..., 0] += rng.uniform(-self.random_h, self.random_h)
            hls[..., 1] += rng.uniform(-self.random_l, self.random_l)
            hls[..., 2] += rng.uniform(-self.random_s, self.random_s)
            hls[..., 0] %= 180.0
            img = cv2.cvtColor(np.clip(hls, 0, 255).astype(np.uint8),
                               cv2.COLOR_HLS2RGB)
        if img.ndim == 2:
            img = img[:, :, None]
        if self.out_uint8:
            # device-augment mode: raw uint8 HWC RGB; crop already done
            return (np.ascontiguousarray(img, np.uint8),
                    self._header_label(header))
        out = img.astype(np.float32)
        if self.mean_arr is not None:
            out = out - self.mean_arr.transpose(1, 2, 0)
            out = out * self.scale
        else:
            out = (out - self.means[:c]) * self.scale
        return out.transpose(2, 0, 1), self._header_label(header)

    def batch_buffers(self):
        """Freshly allocated (data, label) arrays of one batch's shape —
        also the slot layout of the worker pool's shared-memory rings."""
        c, h, w = self.data_shape
        if self.out_uint8:
            data = np.zeros((self.batch_size, h, w, c), np.uint8)
        else:
            data = np.zeros((self.batch_size, c, h, w), np.float32)
        label = np.zeros((self.batch_size, self.label_width), np.float32)
        return data, label

    def load_batch(self, order, epoch, b, data=None, label=None):
        """Decode epoch ``epoch``'s batch ``b`` of ``order`` into
        (data, label, pad) — into the caller's buffers when given (the
        pool's shm slots). Stateless apart from the record reader, so
        any worker can produce any batch."""
        n = len(order)
        start = b * self.batch_size
        count = min(self.batch_size, n - start)
        if data is None:
            data, label = self.batch_buffers()
        for s in range(self.batch_size):
            pos = start + s
            idx = pos % n  # round-over padding
            data[s], label[s] = self._load(order[idx],
                                           _record_rng(self.seed, epoch,
                                                       pos))
        return data, label, self.batch_size - count

    def next(self):
        n = len(self.order)
        if self.cursor >= n:
            return None
        count = min(self.batch_size, n - self.cursor)
        if not self.round_batch and count < self.batch_size:
            return None
        out = self.load_batch(self.order, self.cur_epoch,
                              self.cursor // self.batch_size)
        self.cursor += self.batch_size
        return out

    def close(self):
        reader = getattr(self, "reader", None)
        if reader is not None:
            reader.close()


def _shared_batch_buffers(template, nslots, shared):
    """``nslots`` (data, label) slot pairs shaped like one batch. With
    ``shared`` they live in anonymous MAP_SHARED mmaps created BEFORE
    the fork, so decode workers collate straight into memory the
    consumer reads — the batch itself never crosses a pipe, only a
    (epoch, batch, slot, pad) tuple does."""
    import mmap

    slots = []
    for _ in range(nslots):
        data, label = template.batch_buffers()
        if shared:
            pair = []
            for a in (data, label):
                buf = mmap.mmap(-1, max(a.nbytes, 1))
                pair.append(np.frombuffer(buf, dtype=a.dtype)
                            .reshape(a.shape))
            slots.append(tuple(pair))
        else:
            slots.append((data, label))
    return slots


def _decode_worker_main(cfg, mean_arr, wid, num_workers, ctl_q, out_q,
                        gen, slots, own_process=True):
    """Decode-worker entry point (forked process, or thread in
    worker_mode='thread'): wait for an epoch command, decode this
    worker's round-robin share of the epoch's batches (batch b goes to
    worker b % num_workers) into the shared slot ring, and announce each
    as a tiny (epoch, batch_idx, slot, pad, decode_seconds) tuple on the
    bounded queue.
    A bumped ``gen`` aborts a stale epoch between batches (reset
    mid-epoch); any exception is reported on the queue — loudly — and
    ends the worker."""
    try:
        if own_process:
            # the pool IS the parallelism; nested cv2 threads would
            # oversubscribe the cores. Forked workers only — in thread
            # mode this global would degrade the PARENT's cv2 too.
            try:
                import cv2
                cv2.setNumThreads(0)
            except Exception:
                pass
        eng = _PyEngine(mean_arr=mean_arr, **cfg)
        while True:
            cmd = ctl_q.get()
            if cmd[0] == "quit":
                return
            epoch = cmd[1]
            order = eng.order_for(epoch)
            produced = 0
            for b in range(wid, eng.num_batches(), num_workers):
                if gen.value != epoch:
                    break  # epoch superseded by a reset
                data, label = slots[produced % len(slots)]
                tic = _time.perf_counter()
                _, _, pad = eng.load_batch(order, epoch, b, data, label)
                # decode seconds ride the existing slot message — the
                # consumer process observes them into io.decode_batch_ms
                out_q.put((epoch, b, produced % len(slots), pad,
                           _time.perf_counter() - tic))
                produced += 1
    except BaseException:
        import traceback
        try:
            out_q.put(("error", traceback.format_exc()))
        except Exception:
            pass


class _ParallelEngine:
    """Multi-worker decode pool behind the ``_PyEngine`` interface.

    The epoch's batch list is dealt round-robin across ``num_workers``
    decode workers (forked processes by default — JPEG decode +
    augment is CPU-bound Python/cv2 work; ``worker_mode='thread'``
    keeps everything in-process for debugging). Each worker runs
    read→decode→augment→collate straight into its shared-memory slot
    ring and announces finished batches on a bounded queue
    (``queue_depth`` per worker); the consumer pops worker ``b % W``
    for batch b, so epoch order is deterministic by construction and
    byte-identical to the serial engine (same per-record RNG, same
    per-epoch shuffle).

    Lifecycle: ``reset()`` bumps the shared epoch generation — workers
    abort a stale epoch at the next batch boundary and pick up the new
    epoch command; in-flight stale batches are discarded by tag.
    A worker death (exception OR hard crash) raises MXNetError at the
    consumer instead of hanging the queue. ``close()`` shuts the pool
    down and reaps every worker process.
    """

    #: batches are views of the slot rings — ImageRecordIter.iter_next
    #: copies before wrapping them in long-lived DataBatch arrays
    reuses_buffers = True

    def __init__(self, path, data_shape, batch_size, label_width, means,
                 scale, resize, rand_crop, rand_mirror, shuffle, seed,
                 num_parts, part_index, round_batch, mean_img=None,
                 max_rotate_angle=0, random_h=0, random_s=0, random_l=0,
                 out_uint8=False, scaled_decode=True, path_imgidx=None,
                 num_workers=1, worker_mode="process", queue_depth=None):
        if queue_depth is None:
            queue_depth = int(os.environ.get("MXNET_IO_QUEUE_DEPTH",
                                             "4") or 4)
        self.num_workers = int(num_workers)
        self.queue_depth = max(1, int(queue_depth))
        if worker_mode not in ("process", "thread"):
            raise MXNetError("worker_mode must be 'process' or 'thread', "
                             "got %r" % (worker_mode,))
        # the template engine scans offsets (via the .idx sidecar when
        # given), validates the config, and computes/loads the mean
        # image ONCE in the parent — workers inherit the result
        self._template = _PyEngine(
            path, data_shape, batch_size, label_width, means, scale,
            resize, rand_crop, rand_mirror, shuffle, seed, num_parts,
            part_index, round_batch, mean_img=mean_img,
            max_rotate_angle=max_rotate_angle, random_h=random_h,
            random_s=random_s, random_l=random_l, out_uint8=out_uint8,
            scaled_decode=scaled_decode, path_imgidx=path_imgidx)
        self._template.close()  # the parent never decodes
        self.batch_size = batch_size
        self._nb = self._template.num_batches()
        self._timeout = float(os.environ.get("MXNET_IO_WORKER_TIMEOUT",
                                             "300") or 300)

        use_proc = worker_mode == "process"
        if use_proc:
            import multiprocessing as mp
            try:
                ctx = mp.get_context("fork")
            except ValueError:  # no fork on this platform
                ctx = None
                use_proc = False
        self._is_proc = use_proc

        # worker config: pre-sharded offsets, parent's mean, no
        # mean_img (the parent already resolved it)
        cfg = dict(path=path, data_shape=data_shape,
                   batch_size=batch_size, label_width=label_width,
                   means=tuple(np.asarray(means, np.float32)),
                   scale=scale, resize=resize, rand_crop=rand_crop,
                   rand_mirror=rand_mirror, shuffle=shuffle, seed=seed,
                   num_parts=1, part_index=0, round_batch=round_batch,
                   max_rotate_angle=max_rotate_angle, random_h=random_h,
                   random_s=random_s, random_l=random_l,
                   out_uint8=out_uint8, scaled_decode=scaled_decode,
                   offsets=self._template.offsets)

        nslots = self.queue_depth + 2  # queue_depth announced + 1 the
        # consumer is viewing + 1 being written never collide
        self._slots, self._ctl, self._out, self._workers = [], [], [], []
        if use_proc:
            self._gen = ctx.Value("l", 0)
        else:
            class _Gen:
                value = 0
            self._gen = _Gen()
        for wid in range(self.num_workers):
            slots = _shared_batch_buffers(self._template, nslots,
                                          shared=use_proc)
            if use_proc:
                ctl, out = ctx.Queue(), ctx.Queue(maxsize=self.queue_depth)
                make = ctx.Process
            else:
                ctl, out = _queue.Queue(), \
                    _queue.Queue(maxsize=self.queue_depth)
                make = threading.Thread
            w = make(target=_decode_worker_main,
                     args=(cfg, self._template.mean_arr, wid,
                           self.num_workers, ctl, out, self._gen, slots,
                           use_proc),
                     daemon=True, name="mx-decode-%d" % wid)
            self._slots.append(slots)
            self._ctl.append(ctl)
            self._out.append(out)
            self._workers.append(w)
            import warnings
            with warnings.catch_warnings():
                # jax warns that os.fork() from its (multithreaded)
                # process can deadlock; the decode workers never touch
                # jax — they fork straight into cv2/numpy work, the
                # standard DataLoader-style arrangement
                warnings.filterwarnings(
                    "ignore", message=r".*os\.fork\(\).*",
                    category=RuntimeWarning)
                w.start()
        self._closed = False
        self.epoch = 0
        self.reset()

    # -- _PyEngine interface ------------------------------------------
    @property
    def offsets(self):
        return self._template.offsets

    @property
    def mean_arr(self):
        return self._template.mean_arr

    def reset(self):
        """Start the next epoch: bump the generation (workers abort any
        stale epoch at their next batch boundary) and enqueue the epoch
        command. Stale in-flight batches are discarded by tag in
        ``next`` — never served."""
        if self._closed:
            raise MXNetError("ImageRecordIter worker pool is closed")
        self.cur_epoch = self.epoch
        self.epoch += 1
        self._gen.value = self.cur_epoch
        for ctl in self._ctl:
            ctl.put(("epoch", self.cur_epoch))
        self._next_b = 0

    def _pop(self, wid):
        """Next announcement from worker ``wid``'s queue, discarding
        stale-epoch leftovers; raises on worker failure, death, or
        timeout instead of hanging."""
        deadline = _time.time() + self._timeout
        tic = _time.perf_counter()
        while True:
            try:
                item = self._out[wid].get(timeout=0.2)
            except _queue.Empty:
                if not self._workers[wid].is_alive():
                    self.close()
                    raise MXNetError(
                        "decode worker %d died (killed or crashed "
                        "without a traceback) — batch %d will never "
                        "arrive" % (wid, self._next_b))
                if _time.time() > deadline:
                    self.close()
                    raise MXNetError(
                        "decode worker %d produced nothing for %.0f s "
                        "(MXNET_IO_WORKER_TIMEOUT)"
                        % (wid, self._timeout))
                continue
            if item[0] == "error":
                self.close()
                raise MXNetError("decode worker %d failed:\n%s"
                                 % (wid, item[1]))
            if item[0] != self.cur_epoch:
                continue  # leftover from before a reset
            wait = _time.perf_counter() - tic
            _TM_POOL_WAIT_MS.observe(wait * 1e3)
            if wait > 1e-3:  # the pool starved the consumer
                _TM_POOL_STARVED.inc()
            return item

    def next(self):
        if self._next_b >= self._nb:
            return None
        b = self._next_b
        wid = b % self.num_workers
        epoch, got_b, slot, pad, decode_s = self._pop(wid)
        if got_b != b:  # pragma: no cover — protocol invariant
            self.close()
            raise MXNetError(
                "decode pool out of order: expected batch %d from "
                "worker %d, got %d" % (b, wid, got_b))
        _TM_DECODE_MS.observe(decode_s * 1e3)
        _TM_POOL_BATCHES.inc()
        try:
            # ready batches still queued across the WHOLE pool (a
            # healthy pool keeps this near num_workers * queue_depth;
            # a worker-local qsize would under-report W-fold and hide
            # a single straggler behind its siblings)
            _TM_POOL_QDEPTH.set(sum(q.qsize() for q in self._out))
        except NotImplementedError:  # qsize absent on some platforms
            pass
        self._next_b += 1
        data, label = self._slots[wid][slot]
        return data, label, pad

    def close(self):
        """Shut the pool down: abort in-flight epochs, drain queues so
        blocked workers can exit, and reap every process."""
        if getattr(self, "_closed", True):
            return
        self._closed = True
        self._gen.value = -1
        for ctl in self._ctl:
            try:
                ctl.put(("quit",))
            except Exception:
                pass
        deadline = _time.time() + 5.0
        for wid, w in enumerate(self._workers):
            while w.is_alive() and _time.time() < deadline:
                try:  # unblock a worker stuck in a full-queue put
                    self._out[wid].get_nowait()
                except _queue.Empty:
                    pass
                w.join(timeout=0.05)
            if self._is_proc and w.is_alive():
                w.terminate()
                w.join(timeout=1.0)
        if self._is_proc:
            for q in self._ctl + self._out:
                q.cancel_join_thread()
                q.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class DeviceAugmentIter(DataIter):
    """Wrap a ``device_augment=True`` ImageRecordIter: uint8 HWC batches
    cross to the device (4x less infeed traffic) and random
    crop/flip/normalize run THERE in one small jitted program; yields
    normalized float NCHW batches like the host pipeline would.

    The production recipe (doc/performance.md "Input pipeline"): host =
    decode + resize + center-crop to the storage shape; device = the
    random augmentations. ``crop_shape=(h, w)`` is the training crop
    (default: the storage shape, i.e. no crop).

    For the tightest loop, fuse ``device_augment_batch`` directly into
    your compiled train step instead; this wrapper keeps the plain
    DataIter protocol so FeedForward/Trainer code runs unchanged.
    """

    def __init__(self, base, crop_shape=None, rand_crop=True,
                 rand_mirror=True, mean=(0.0, 0.0, 0.0), scale=1.0,
                 seed=0):
        import jax

        super().__init__()
        if not getattr(base, "_device_augment", False):
            raise MXNetError("DeviceAugmentIter needs an ImageRecordIter "
                             "created with device_augment=True")
        self._base = base
        self.batch_size = base.batch_size
        c, big_h, big_w = base._data_shape
        self._crop = tuple(crop_shape) if crop_shape else (big_h, big_w)
        if self._crop[0] > big_h or self._crop[1] > big_w:
            raise MXNetError(
                "DeviceAugmentIter: crop_shape %s exceeds the base "
                "iterator's storage shape (%d, %d)"
                % (self._crop, big_h, big_w))
        self._chans = c
        self._key = jax.random.PRNGKey(seed)
        self._step = 0
        self._data = None
        self._label = None
        self._pad = 0

        rc, rm = bool(rand_crop), bool(rand_mirror)
        mean_t, scale_f = tuple(float(m) for m in mean), float(scale)
        crop = self._crop

        def _augment(u8, key):
            return device_augment_batch(
                u8, key=key, crop_shape=crop, rand_crop=rc,
                rand_mirror=rm, mean=mean_t, scale=scale_f)

        self._augment = jax.jit(_augment)

    @property
    def provide_data(self):
        h, w = self._crop
        return [(self._base._data_name,
                 (self.batch_size, self._chans, h, w))]

    @property
    def provide_label(self):
        return self._base.provide_label

    def reset(self):
        self._base.reset()

    def iter_next(self):
        import jax

        if not self._base.iter_next():
            return False
        self._step += 1
        key = jax.random.fold_in(self._key, self._step)
        u8 = self._base._data._val  # [B, H, W, C] uint8 on device
        self._data = nd.NDArray._from_jax(self._augment(u8, key),
                                          self._base._data.context)
        self._label = self._base._label
        self._pad = self._base.getpad()
        return True

    def getdata(self):
        return [self._data]

    def getlabel(self):
        return [self._label]

    def getpad(self):
        return self._pad
