"""RecordIO: read/write the dmlc record container + image record packing.

Parity: ``python/mxnet/recordio.py`` (MXRecordIO, IRHeader, pack/unpack,
pack_img/unpack_img) over the same binary format, so ``.rec`` datasets
interchange with the reference. Uses the native C++ library when built
(``cpp/recordio.cc``); otherwise a pure-Python implementation of the
identical format (magic 0xced7230a, cflag/length word, 4-byte alignment,
magic-split multi-part records).
"""
from __future__ import annotations

import ctypes
import os
import struct
from collections import namedtuple

import numpy as np

from .base import MXNetError
from .libinfo import get_lib, check_call

__all__ = ["MXRecordIO", "MXIndexedRecordIO", "IRHeader", "pack", "unpack",
           "pack_img", "unpack_img", "list_record_offsets"]

_MAGIC = 0xced7230a


# ---------------------------------------------------------------------------
# pure-python fallback engines

class _PyWriter:
    def __init__(self, path):
        self._f = open(path, "wb")
        self.tell_ = 0

    def write(self, buf):
        if len(buf) >= (1 << 29):
            raise MXNetError("record too large")
        magic = struct.pack("<I", _MAGIC)
        n = len(buf)
        lower = (n >> 2) << 2
        upper = ((n + 3) >> 2) << 2
        dptr = 0
        out = []
        for i in range(0, lower, 4):
            if buf[i:i + 4] == magic:
                out.append(magic)
                out.append(struct.pack("<I", ((1 if dptr == 0 else 2) << 29)
                                       | (i - dptr)))
                out.append(buf[dptr:i])
                dptr = i + 4
        out.append(magic)
        out.append(struct.pack("<I", ((3 if dptr else 0) << 29) | (n - dptr)))
        out.append(buf[dptr:n])
        out.append(b"\x00" * (upper - n))
        blob = b"".join(out)
        self._f.write(blob)
        self.tell_ += len(blob)

    def tell(self):
        return self.tell_

    def close(self):
        self._f.close()


class _PyReader:
    def __init__(self, path):
        self._f = open(path, "rb")

    def read(self):
        parts = []
        multi = False
        while True:
            head = self._f.read(8)
            if len(head) < 8:
                return None
            magic, lrec = struct.unpack("<II", head)
            if magic != _MAGIC:
                raise MXNetError("recordio: bad magic")
            cflag, n = lrec >> 29, lrec & ((1 << 29) - 1)
            if multi:
                parts.append(struct.pack("<I", _MAGIC))
            data = self._f.read(n)
            if len(data) != n:
                raise MXNetError("recordio: truncated payload")
            pad = (((n + 3) >> 2) << 2) - n
            if pad:
                self._f.read(pad)
            parts.append(data)
            if cflag in (0, 3):
                return b"".join(parts)
            multi = True

    def seek(self, pos):
        self._f.seek(pos)

    def tell(self):
        return self._f.tell()

    def close(self):
        self._f.close()


# ---------------------------------------------------------------------------

class MXRecordIO:
    """Read/write RecordIO files (reference recordio.py:MXRecordIO)."""

    def __init__(self, uri, flag):
        self.uri = uri
        self.flag = flag
        self.is_open = False
        self.open()

    def open(self):
        lib = get_lib()
        self._lib = lib
        if self.flag == "w":
            self.writable = True
        elif self.flag == "r":
            self.writable = False
        else:
            raise ValueError("Invalid flag %s" % self.flag)
        if lib is not None:
            self.handle = ctypes.c_void_p()
            fn = (lib.MXTRecordIOWriterCreate if self.writable
                  else lib.MXTRecordIOReaderCreate)
            check_call(fn(ctypes.c_char_p(self.uri.encode()),
                          ctypes.byref(self.handle)))
        else:
            self.handle = (_PyWriter(self.uri) if self.writable
                           else _PyReader(self.uri))
        self.is_open = True

    def close(self):
        if not self.is_open:
            return
        # closed FIRST: a close that raises after the native free (at
        # interpreter shutdown the module globals, check_call among
        # them, are already None) must not be retried by __del__ — a
        # second free of the same handle corrupts the heap
        self.is_open = False
        handle, self.handle = self.handle, None
        if self._lib is not None:
            fn = (self._lib.MXTRecordIOWriterFree if self.writable
                  else self._lib.MXTRecordIOReaderFree)
            rc = fn(handle)
            if rc != 0:
                check_call(rc)
        else:
            handle.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def reset(self):
        """Reopen (truncates in 'w' mode) — reference semantics."""
        self.close()
        self.open()

    def write(self, buf):
        assert self.writable
        if isinstance(buf, str):
            buf = buf.encode()
        if self._lib is not None:
            check_call(self._lib.MXTRecordIOWriterWriteRecord(
                self.handle, ctypes.c_char_p(bytes(buf)),
                ctypes.c_size_t(len(buf))))
        else:
            self.handle.write(bytes(buf))

    def read(self):
        assert not self.writable
        if self._lib is not None:
            buf = ctypes.c_char_p()
            size = ctypes.c_size_t()
            check_call(self._lib.MXTRecordIOReaderReadRecord(
                self.handle, ctypes.byref(buf), ctypes.byref(size)))
            if not buf:  # NULL pointer -> EOF
                return None
            return ctypes.string_at(buf, size.value)
        return self.handle.read()

    def tell(self):
        if self._lib is not None:
            pos = ctypes.c_uint64()
            fn = (self._lib.MXTRecordIOWriterTell if self.writable
                  else self._lib.MXTRecordIOReaderTell)
            check_call(fn(self.handle, ctypes.byref(pos)))
            return pos.value
        return self.handle.tell()

    def seek(self, pos):
        assert not self.writable
        if self._lib is not None:
            check_call(self._lib.MXTRecordIOReaderSeek(
                self.handle, ctypes.c_uint64(pos)))
        else:
            self.handle.seek(pos)


class MXIndexedRecordIO(MXRecordIO):
    """RecordIO with a 'key\\toffset' index sidecar for random access
    (reference recordio.py:MXIndexedRecordIO)."""

    def __init__(self, idx_path, uri, flag, key_type=int):
        self.idx_path = idx_path
        self.idx = {}
        self.keys = []
        self.key_type = key_type
        super().__init__(uri, flag)
        if not self.writable and os.path.isfile(idx_path):
            with open(idx_path) as f:
                for line in f:
                    parts = line.strip().split("\t")
                    if len(parts) != 2:
                        continue
                    key = key_type(parts[0])
                    self.idx[key] = int(parts[1])
                    self.keys.append(key)

    def close(self):
        if self.is_open and self.writable:
            with open(self.idx_path, "w") as f:
                for k in self.keys:
                    f.write("%s\t%d\n" % (k, self.idx[k]))
        super().close()

    def read_idx(self, idx):
        self.seek(self.idx[idx])
        return self.read()

    def write_idx(self, idx, buf):
        key = self.key_type(idx)
        self.idx[key] = self.tell()
        self.keys.append(key)
        self.write(buf)


def list_record_offsets(uri, idx_path=None):
    """Byte offsets of every record in a RecordIO file, in file order.

    The decode-worker pool shards these offsets into batches
    (image_io._ParallelEngine); each worker then random-accesses its own
    records via ``seek``. When the ``MXIndexedRecordIO`` sidecar is
    named (``idx_path``) and exists, the offsets come from it directly —
    O(keys) text read instead of decoding every record frame; otherwise
    the container is scanned once.
    """
    if idx_path is not None and os.path.isfile(idx_path):
        offsets = []
        try:
            with open(idx_path) as f:
                for line in f:
                    if not line.strip():
                        continue  # trailing newline etc.
                    parts = line.strip().split("\t")
                    if len(parts) != 2:
                        raise ValueError("malformed index line")
                    offsets.append(int(parts[1]))
        except ValueError:
            # malformed line (a writer died mid-line): fails the sanity
            # check below, taking the same warn-and-scan degrade path a
            # stale sidecar does
            offsets = [-1]
        # index files follow write order, but sort defensively: the
        # epoch order must be the file order the scan would produce.
        # A stale/truncated sidecar (rec regenerated, old idx left
        # behind, offset digits cut short) would silently shrink or
        # mis-map the epoch — cheap sanity checks make that loud and
        # fall back to the scan. The magic probe at the LAST offset
        # catches numerically-plausible corruption (a truncated offset
        # still in bounds) without decoding anything.
        offsets = sorted(offsets)
        size = os.path.getsize(uri)
        if offsets:
            ok = (offsets[0] == 0 and offsets[-1] < size
                  and all(b > a for a, b in zip(offsets, offsets[1:])))
            if ok:
                with open(uri, "rb") as f:
                    f.seek(offsets[-1])
                    ok = f.read(4) == struct.pack("<I", _MAGIC)
            if ok:
                return offsets
            import logging
            logging.warning(
                "list_record_offsets: index %s does not fit %s "
                "(stale/truncated sidecar?) — falling back to a full "
                "scan", idx_path, uri)
    reader = MXRecordIO(uri, "r")
    offsets = []
    try:
        while True:
            pos = reader.tell()
            if reader.read() is None:
                break
            offsets.append(pos)
    finally:
        reader.close()
    return offsets


# ---------------------------------------------------------------------------
# image record packing (reference recordio.py IRHeader/pack/unpack)

IRHeader = namedtuple("HEADER", ["flag", "label", "id", "id2"])
_IRFormat = "<IfQQ"
_IRSize = struct.calcsize(_IRFormat)


def pack(header, s):
    """Pack a header + raw bytes into an image-record payload."""
    header = IRHeader(*header)
    if isinstance(header.label, (np.ndarray, list, tuple)):
        label = np.asarray(header.label, dtype=np.float32)
        header = header._replace(flag=label.size, label=0.0)
        s = label.tobytes() + s
    return struct.pack(_IRFormat, *header) + s


def unpack(s):
    """Unpack an image-record payload to (IRHeader, bytes)."""
    header = IRHeader(*struct.unpack(_IRFormat, s[:_IRSize]))
    s = s[_IRSize:]
    if header.flag > 0:
        label = np.frombuffer(s[:header.flag * 4], dtype=np.float32)
        header = header._replace(label=label)
        s = s[header.flag * 4:]
    return header, s


#: raw (unencoded) image payload: magic + u16 height + u16 width + u8
#: channels, then HWC BGR/gray uint8 pixels. A lossless fast path that
#: skips JPEG decode entirely (the reference's im2rec likewise stores
#: raw pixels when encoding is disabled; cpp/image_iter.cc reads it
#: zero-copy).
_RAW_MAGIC = b"RAW0"


def pack_img(header, img, quality=95, img_fmt=".jpg"):
    """Encode a HxWx3 (RGB) / HxW uint8 array and pack it.

    ``img_fmt=".raw"`` stores unencoded pixels (lossless, ~4x faster to
    read back on one core: no JPEG decode)."""
    import struct

    if img_fmt == ".raw":
        a = np.ascontiguousarray(
            img[:, :, ::-1] if img.ndim == 3 else img, dtype=np.uint8)
        h, w = a.shape[:2]
        c = a.shape[2] if a.ndim == 3 else 1
        blob = (_RAW_MAGIC + struct.pack("<HHB", h, w, c) + a.tobytes())
        return pack(header, blob)
    import cv2
    if img.ndim == 3:
        img = img[:, :, ::-1]  # RGB -> BGR for OpenCV encoding
    if img_fmt in (".jpg", ".jpeg"):
        params = [cv2.IMWRITE_JPEG_QUALITY, quality]
    elif img_fmt == ".png":
        params = [cv2.IMWRITE_PNG_COMPRESSION, quality]
    else:
        params = []
    ok, buf = cv2.imencode(img_fmt, img, params)
    if not ok:
        raise MXNetError("pack_img: encode failed")
    return pack(header, buf.tobytes())


def unpack_img(s, iscolor=-1):
    """Unpack to (IRHeader, decoded RGB/gray ndarray)."""
    import struct

    header, blob = unpack(s)
    if blob[:4] == _RAW_MAGIC:
        h, w, c = struct.unpack("<HHB", blob[4:9])
        a = np.frombuffer(blob[9:9 + h * w * c], np.uint8)
        a = a.reshape((h, w) if c == 1 else (h, w, c))
        if a.ndim == 3:
            a = a[:, :, ::-1]  # stored BGR -> RGB
        if iscolor == 0 and a.ndim == 3:
            import cv2
            a = cv2.cvtColor(np.ascontiguousarray(a[:, :, ::-1]),
                             cv2.COLOR_BGR2GRAY)
        elif iscolor == 1 and a.ndim == 2:
            a = np.repeat(a[:, :, None], 3, axis=2)
        return header, a
    import cv2
    img = cv2.imdecode(np.frombuffer(blob, dtype=np.uint8), iscolor)
    if img is None:
        raise MXNetError("unpack_img: decode failed")
    if img.ndim == 3:
        img = img[:, :, ::-1]  # BGR -> RGB
    return header, img
