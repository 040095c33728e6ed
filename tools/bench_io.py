"""Input-pipeline benchmark (run in a CLEAN subprocess).

Measures the native C++ ImageRecordIOIter in several modes and derives a
per-stage ms/img breakdown. Run via ``python tools/bench_io.py`` from
the repo root, WITHOUT importing jax first: a backend's runtime threads
contend with the decode workers for the host's cores, so the
"exclusive" number needs a process that never initialized a backend
(and, run from bench.py, one that stays off the chip its parent
holds).

Prints one JSON dict:
  jpeg_full      img/s, 480x360 q85 JPEGs, full decode, float out
  jpeg_scaled    same but reduced-DCT decode (IMREAD_REDUCED_*)
  raw            RAW0 records (no JPEG decode), float out
  u8_device      RAW0 + uint8 HWC out (device-augment mode)
  jpeg_scaled_u8 scaled decode + uint8 out (full production path)
  stage_ms       derived per-stage ms/img: decode/augment_normalize/collate
  io_pipeline    the num_workers decode pool on the jpeg_scaled
                 pipeline: {"w<k>": img/s} for k in BENCH_IO_WORKERS
                 (default 1,2,4,8), plus "w<k>_u8" for the uint8
                 device-augment flavor at the best k, "serial_py" (the
                 pool's own single-thread engine, no pool overhead) and
                 "ncpu" so speedups are read against the core budget
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_rec(tmpd, n, img_fmt, hw=(360, 480), quality=85):
    from mxnet_tpu import recordio as rec

    path = os.path.join(tmpd, "bench_%s.rec" % img_fmt.strip("."))
    rng = np.random.RandomState(0)
    w = rec.MXRecordIO(path, "w")
    # realistic content: smooth upsampled noise (JPEG-typical entropy),
    # ImageNet-ish 480x360 source size
    base = rng.randint(0, 255, (24, 32, 3)).astype(np.uint8)
    import cv2
    img = cv2.resize(base, (hw[1], hw[0]), interpolation=cv2.INTER_CUBIC)
    noise = rng.randint(0, 12, img.shape).astype(np.uint8)
    img = cv2.add(img, noise)
    for i in range(n):
        hdr = rec.IRHeader(0, float(i % 10), i, 0)
        w.write(rec.pack_img(hdr, img, quality=quality, img_fmt=img_fmt))
    w.close()
    return path


def run_iter(path, n_images, batch=128, shape=(3, 224, 224), resize=256,
             device_augment=False, scaled_decode=True, threads=2,
             center=False, num_workers=None, force_python=False):
    import mxnet_tpu as mx

    if force_python:  # the pool's serial engine, no native lib
        import mxnet_tpu.image_io as iio
        saved, iio.get_lib = iio.get_lib, lambda: None
    try:
        it = mx.ImageRecordIter(
            path_imgrec=path, data_shape=shape, batch_size=batch,
            resize=resize, rand_crop=not device_augment and not center,
            rand_mirror=not device_augment and not center, shuffle=False,
            preprocess_threads=threads, device_augment=device_augment,
            scaled_decode=scaled_decode, num_workers=num_workers)
    finally:
        if force_python:
            iio.get_lib = saved
    # iter_numpy: the host fast path (trainer.prefetch consumes numpy);
    # wrapping batches in device NDArrays would charge a device
    # transfer per batch to the IO measurement
    for _ in it.iter_numpy():  # warm epoch: thread spin-up, page cache
        pass
    best = 0.0
    for _ in range(3):  # median-free max: 1-core timing is noisy
        it.reset()
        tic = time.perf_counter()
        n = 0
        for _ in it.iter_numpy():
            n += batch
        dt = time.perf_counter() - tic
        best = max(best, n / dt)
    if hasattr(it, "close"):
        it.close()
    del it
    return best


def main():
    n = int(os.environ.get("BENCH_IO_N", 512))
    out = {}
    with tempfile.TemporaryDirectory(prefix="benchio") as tmpd:
        # each rec is written (and synced) immediately before its own
        # measurements so encode work/writeback never contends with an
        # unrelated mode's timing window
        jpg = make_rec(tmpd, n, ".jpg")
        if hasattr(os, "sync"):
            os.sync()
        out["jpeg_full"] = run_iter(jpg, n, scaled_decode=False)
        out["jpeg_scaled"] = run_iter(jpg, n, scaled_decode=True)
        out["jpeg_scaled_u8"] = run_iter(jpg, n, shape=(3, 256, 256),
                                         device_augment=True)
        raw = make_rec(tmpd, n, ".raw")
        if hasattr(os, "sync"):
            os.sync()
        out["raw"] = run_iter(raw, n)
        out["u8_device"] = run_iter(raw, n, shape=(3, 256, 256),
                                    device_augment=True)
        # same-geometry pair for the stage breakdown: float center-crop
        # 224 vs uint8 center-crop 224 isolates the host float
        # augment+normalize pass (u8_device above uses the production
        # 256 storage shape, which would conflate crop/byte deltas)
        out["raw_center224"] = run_iter(raw, n, center=True)
        out["u8_center224"] = run_iter(raw, n, shape=(3, 224, 224),
                                       device_augment=True)
        # big sources are where reduced-DCT decode actually triggers
        # (720p: shorter 720 -> 1/2 scale still covers resize=256)
        big = make_rec(tmpd, n // 2, ".jpg", hw=(720, 960), quality=85)
        if hasattr(os, "sync"):
            os.sync()
        out["jpeg_big_full"] = run_iter(big, n // 2, scaled_decode=False)
        out["jpeg_big_scaled"] = run_iter(big, n // 2, scaled_decode=True)
        # --- the num_workers decode pool (ISSUE 2 tentpole): same
        # jpeg_scaled pipeline, decode fanned over k forked workers
        # collating into shared memory. w1 is the honest single-worker
        # baseline of the ≥Nx claim; "ncpu" contextualizes the curve
        # (k beyond the core count cannot scale on a small container).
        workers = [int(w) for w in os.environ.get(
            "BENCH_IO_WORKERS", "1,2,4,8").split(",") if w.strip()]
        pipe = {"ncpu": os.cpu_count(),
                "serial_py": run_iter(jpg, n, force_python=True)}
        for k in workers:
            pipe["w%d" % k] = run_iter(jpg, n, num_workers=k)
        if workers:
            best_k = max(workers, key=lambda k: pipe["w%d" % k])
            # production flavor at the winning worker count: uint8
            # device-augment batches (4x smaller slots, no host float
            # pass)
            pipe["w%d_u8" % best_k] = run_iter(
                jpg, n, shape=(3, 256, 256), device_augment=True,
                num_workers=best_k)
        out["io_pipeline"] = pipe
    # per-stage ms/img, derived from SAME-GEOMETRY mode differences:
    #   decode      = jpeg_full - raw          (both 224 float rand-crop)
    #   augment+norm= raw_center224 - u8_center224  (same 224 center
    #                 crop; only the float normalize pass + 4x output
    #                 bytes differ)
    #   collate     = everything left in u8_center224 (record IO,
    #                 resize, memcpy, batching)
    ms = {k: 1000.0 / v for k, v in out.items()
          if isinstance(v, (int, float)) and v}
    out["stage_ms"] = {
        "decode_full": round(ms["jpeg_full"] - ms["raw"], 3),
        "decode_scaled": round(ms["jpeg_scaled"] - ms["raw"], 3),
        "augment_normalize": round(ms["raw_center224"]
                                   - ms["u8_center224"], 3),
        "collate_io": round(ms["u8_center224"], 3),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
