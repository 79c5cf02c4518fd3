"""ctypes loader + wrappers for the C++ native core (native/parsers.cc).

The reference's hot byte path is C++ (src/data/); here the same role is played
by ``libdmlc_tpu_native.so``: multi-threaded chunk parsers returning numpy
arrays.  ``make -C native`` runs on first load (a no-op when the library is
newer than its sources), so what gets loaded always comes from the
committed ``native/*.cc`` — never a stale git-ignored ``.so`` that was
copied along with the tree.  Every caller falls back to the numpy path
when the library cannot be built (no compiler on the host), so the
pure-Python package remains fully functional.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

from dmlc_core_tpu.utils.logging import log_warning

__all__ = ["available", "parse_libsvm", "parse_libfm", "parse_csv",
           "find_magic_positions"]

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libdmlc_tpu_native.so")


def _build() -> bool:
    """Bring the library up to date with ``native/*.cc`` (make's own
    timestamps make this a no-op when it already is)."""
    try:
        subprocess.run(["make", "-C", _NATIVE_DIR], check=True,
                       capture_output=True, timeout=300)
        return os.path.exists(_SO_PATH)
    except (OSError, subprocess.SubprocessError) as exc:
        detail = getattr(exc, "stderr", b"") or b""
        log_warning(f"native core not built ({exc!r}); numpy parsers in "
                    f"use. {detail.decode(errors='replace')[-500:]}")
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("DMLC_TPU_DISABLE_NATIVE"):
            return None
        if not _build():
            return None
        try:
            lib = ctypes.CDLL(_SO_PATH)
        except OSError:
            return None
        # ABI handshake: a stale build with old entry-point signatures must
        # not be called through mismatched ctypes prototypes — rebuild once,
        # and disable the native path if the rebuild still disagrees
        _ABI = 5
        ver_fn = getattr(lib, "dmlc_tpu_abi_version", None)
        if ver_fn is None or int(ver_fn()) != _ABI:
            del lib
            # unlink BEFORE rebuilding: dlopen dedups by (dev, inode), so an
            # in-place relink would hand the second CDLL the already-mapped
            # stale library (and rewriting a mapped ELF risks clobbering its
            # pages); a fresh inode guarantees a fresh mapping
            try:
                os.unlink(_SO_PATH)
            except OSError:
                pass
            if not _build():
                return None
            try:
                lib = ctypes.CDLL(_SO_PATH)
            except OSError:
                return None
            ver_fn = getattr(lib, "dmlc_tpu_abi_version", None)
            if ver_fn is None or int(ver_fn()) != _ABI:
                return None
        for name in ("dmlc_tpu_parse_libsvm", "dmlc_tpu_parse_libfm"):
            fn = getattr(lib, name)
            fn.restype = ctypes.c_void_p
            fn.argtypes = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_int]
        lib.dmlc_tpu_parse_csv.restype = ctypes.c_void_p
        lib.dmlc_tpu_parse_csv.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int, ctypes.c_float]
        lib.dmlc_tpu_result_dims.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32)]
        lib.dmlc_tpu_error_msg.restype = ctypes.c_char_p
        lib.dmlc_tpu_error_msg.argtypes = [ctypes.c_void_p]
        lib.dmlc_tpu_result_fill.argtypes = [ctypes.c_void_p] + \
            [ctypes.c_void_p] * 6
        lib.dmlc_tpu_result_fill_csv.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p]
        lib.dmlc_tpu_result_free.argtypes = [ctypes.c_void_p]
        lib.dmlc_tpu_find_magic.restype = ctypes.c_int64
        lib.dmlc_tpu_find_magic.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_uint32,
            ctypes.c_void_p, ctypes.c_int64]
        lib.dmlc_tpu_recordio_scan.restype = ctypes.c_void_p
        lib.dmlc_tpu_recordio_scan.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64]
        lib.dmlc_tpu_recordio_scan_dims.argtypes = [
            ctypes.c_void_p] + [ctypes.POINTER(ctypes.c_int64)] * 3
        lib.dmlc_tpu_recordio_scan_error.restype = ctypes.c_char_p
        lib.dmlc_tpu_recordio_scan_error.argtypes = [ctypes.c_void_p]
        lib.dmlc_tpu_recordio_scan_fill.argtypes = [ctypes.c_void_p] + \
            [ctypes.c_void_p] * 3
        lib.dmlc_tpu_recordio_scan_free.argtypes = [ctypes.c_void_p]
        lib.dmlc_tpu_recordio_extract.restype = ctypes.c_int64
        lib.dmlc_tpu_recordio_extract.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_int64]
        lib.dmlc_tpu_recordio_frame.restype = ctypes.c_void_p
        lib.dmlc_tpu_recordio_frame.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int64]
        lib.dmlc_tpu_frame_dims.argtypes = [
            ctypes.c_void_p] + [ctypes.POINTER(ctypes.c_int64)] * 3
        lib.dmlc_tpu_frame_error.restype = ctypes.c_char_p
        lib.dmlc_tpu_frame_error.argtypes = [ctypes.c_void_p]
        lib.dmlc_tpu_frame_fill.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
        lib.dmlc_tpu_frame_free.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def _ptr(arr: Optional[np.ndarray]):
    if arr is None or arr.size == 0:
        return None
    return arr.ctypes.data_as(ctypes.c_void_p)


def _as_data_ptr(data):
    """bytes -> (c_char_p, len); (addr, len) -> zero-copy pointer pass.

    The (addr, len) form is the native-split fast path: the chunk stays in
    the split handle's buffer (valid until its next call) and the parser
    reads it in place — no Python bytes materialization between the C++
    split engine and the C++ parser.
    """
    if isinstance(data, tuple):
        addr, length = data
        return ctypes.c_char_p(addr), length
    return data, len(data)


def _parse_sparse(fn_name: str, data, nthread: int):
    lib = _load()
    assert lib is not None
    ptr, length = _as_data_ptr(data)
    handle = getattr(lib, fn_name)(ptr, length, nthread)
    try:
        n_rows = ctypes.c_int64()
        nnz = ctypes.c_int64()
        n_cols = ctypes.c_int64()
        flags = ctypes.c_int32()
        lib.dmlc_tpu_result_dims(handle, ctypes.byref(n_rows),
                                 ctypes.byref(nnz), ctypes.byref(n_cols),
                                 ctypes.byref(flags))
        if n_rows.value < 0:
            raise ValueError(lib.dmlc_tpu_error_msg(handle).decode())
        nr, nz, fl = n_rows.value, nnz.value, flags.value
        offset = np.empty(nr + 1, dtype=np.int64)
        label = np.empty(nr, dtype=np.float32)
        weight = np.empty(nr, dtype=np.float32) if (fl & 1) else None
        index = np.empty(nz, dtype=np.uint32)
        field = np.empty(nz, dtype=np.uint32) if (fl & 4) else None
        value = np.empty(nz, dtype=np.float32) if (fl & 2) else None
        lib.dmlc_tpu_result_fill(handle, _ptr(offset), _ptr(label),
                                 _ptr(weight), _ptr(index), _ptr(field),
                                 _ptr(value), None)
        return offset, label, weight, index, field, value
    finally:
        lib.dmlc_tpu_result_free(handle)


def parse_libsvm(data, nthread: int = 4):
    """Chunk (bytes or zero-copy ``(addr, len)``) ->
    (offset, label, weight|None, index, value|None)."""
    offset, label, weight, index, _, value = _parse_sparse(
        "dmlc_tpu_parse_libsvm", data, nthread)
    return offset, label, weight, index, value


def parse_libfm(data, nthread: int = 4):
    """Chunk (bytes or zero-copy ``(addr, len)``) ->
    (offset, label, weight|None, index, field, value)."""
    offset, label, weight, index, field, value = _parse_sparse(
        "dmlc_tpu_parse_libfm", data, nthread)
    return offset, label, weight, index, field, value


def parse_csv(data, nthread: int = 4, missing: float = 0.0,
              label_column: int = -1):
    """Chunk (bytes or zero-copy ``(addr, len)``) -> parsed CSV floats.

    With ``label_column`` out of range (default) returns the dense
    ``[n_rows, n_cols]`` float32 block.  With ``0 <= label_column <
    n_cols`` returns ``(labels, feats)`` — the split is one C pass
    (``dmlc_tpu_result_fill_csv``) instead of a full extra numpy copy.

    ``missing`` fills empty cells (reference strtof-on-empty parity = 0.0;
    NaN for sparsity-aware training).
    """
    lib = _load()
    assert lib is not None
    ptr, length = _as_data_ptr(data)
    handle = lib.dmlc_tpu_parse_csv(ptr, length, nthread,
                                    ctypes.c_float(missing))
    try:
        n_rows = ctypes.c_int64()
        nnz = ctypes.c_int64()
        n_cols = ctypes.c_int64()
        flags = ctypes.c_int32()
        lib.dmlc_tpu_result_dims(handle, ctypes.byref(n_rows),
                                 ctypes.byref(nnz), ctypes.byref(n_cols),
                                 ctypes.byref(flags))
        if n_rows.value < 0:
            raise ValueError(lib.dmlc_tpu_error_msg(handle).decode())
        if 0 <= label_column < n_cols.value:
            labels = np.empty(n_rows.value, dtype=np.float32)
            feats = np.empty((n_rows.value, n_cols.value - 1),
                             dtype=np.float32)
            lib.dmlc_tpu_result_fill_csv(handle, label_column,
                                         _ptr(labels),
                                         _ptr(feats.reshape(-1)))
            return labels, feats
        dense = np.empty((n_rows.value, n_cols.value), dtype=np.float32)
        lib.dmlc_tpu_result_fill(handle, None, None, None, None, None, None,
                                 _ptr(dense.reshape(-1)))
        return dense
    finally:
        lib.dmlc_tpu_result_free(handle)


def find_magic_positions(data: bytes, magic: int, limit: int) -> np.ndarray:
    """Aligned magic-word byte offsets (RecordIO writer escape scan)."""
    lib = _load()
    assert lib is not None
    out = np.empty(limit, dtype=np.int64)
    n = lib.dmlc_tpu_find_magic(data, len(data), magic, _ptr(out), limit)
    return out[:min(n, limit)]


def recordio_scan(data: bytes, begin: int, end: int
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int, int]:
    """One-pass record scan of a chunk partition.

    Returns ``(head, plen, escaped, pbegin, pend)``: per-record head byte
    offsets, logical payload lengths, escaped flags, and the resynced
    partition bounds (reference RecordIOChunkReader, src/recordio.cc:102-156).
    """
    lib = _load()
    assert lib is not None
    handle = lib.dmlc_tpu_recordio_scan(data, len(data), begin, end)
    try:
        n = ctypes.c_int64()
        pbegin = ctypes.c_int64()
        pend = ctypes.c_int64()
        lib.dmlc_tpu_recordio_scan_dims(handle, ctypes.byref(n),
                                        ctypes.byref(pbegin),
                                        ctypes.byref(pend))
        if n.value < 0:
            raise ValueError(lib.dmlc_tpu_recordio_scan_error(handle).decode())
        head = np.empty(n.value, dtype=np.int64)
        plen = np.empty(n.value, dtype=np.int64)
        escaped = np.empty(n.value, dtype=np.uint8)
        lib.dmlc_tpu_recordio_scan_fill(handle, _ptr(head), _ptr(plen),
                                        _ptr(escaped))
        return head, plen, escaped, pbegin.value, pend.value
    finally:
        lib.dmlc_tpu_recordio_scan_free(handle)


def recordio_extract(data: bytes, head: int, length: int) -> bytes:
    """Reassemble one (escaped) record whose head is at byte offset ``head``;
    ``length`` is its logical payload length from a prior scan."""
    lib = _load()
    assert lib is not None
    out = np.empty(length, dtype=np.uint8)
    got = lib.dmlc_tpu_recordio_extract(data, len(data), head, _ptr(out),
                                        length)
    if got < 0:
        raise ValueError("invalid RecordIO format: bad record head")
    return out[:got].tobytes()


def recordio_frame(payloads: bytes, lens: np.ndarray
                   ) -> Tuple[memoryview, np.ndarray, int]:
    """Batch-encode concatenated payloads into RecordIO framing.

    Returns ``(framed, offsets, except_count)`` where ``framed`` is a
    memoryview over a freshly-filled buffer (no extra copy) and
    ``offsets[i]`` is the start of record i within it (reference writer,
    recordio.cc:11-51).
    """
    lib = _load()
    assert lib is not None
    lens = np.ascontiguousarray(lens, dtype=np.int64)
    handle = lib.dmlc_tpu_recordio_frame(payloads, _ptr(lens), len(lens))
    try:
        size = ctypes.c_int64()
        n_off = ctypes.c_int64()
        nexc = ctypes.c_int64()
        lib.dmlc_tpu_frame_dims(handle, ctypes.byref(size),
                                ctypes.byref(n_off), ctypes.byref(nexc))
        if size.value < 0:
            raise ValueError(lib.dmlc_tpu_frame_error(handle).decode())
        out = np.empty(size.value, dtype=np.uint8)
        offsets = np.empty(n_off.value, dtype=np.int64)
        lib.dmlc_tpu_frame_fill(handle, _ptr(out), _ptr(offsets))
        return memoryview(out).cast("B"), offsets, nexc.value
    finally:
        lib.dmlc_tpu_frame_free(handle)


# ---- native line-split engine (native/input_split.cc) ----------------------

# read-at callback signature: (ctx, file_idx, offset, buf, size) -> bytes
# read (0 = EOF), <0 = error.  Python implementations run on the native
# prefetch thread; ctypes acquires the GIL per call.
READ_AT_FN = ctypes.CFUNCTYPE(ctypes.c_int64, ctypes.c_void_p,
                              ctypes.c_int64, ctypes.c_int64,
                              ctypes.POINTER(ctypes.c_char), ctypes.c_int64)


def _load_lsplit():
    lib = _load()
    if lib is None:
        return None
    if not hasattr(lib, "dmlc_tpu_span_open"):
        return None  # stale library built before the full split engine existed
    if not getattr(lib, "_lsplit_wired", False):
        open_sig = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64]
        lib.dmlc_tpu_lsplit_open.restype = ctypes.c_void_p
        lib.dmlc_tpu_lsplit_open.argtypes = open_sig
        lib.dmlc_tpu_rsplit_open.restype = ctypes.c_void_p
        lib.dmlc_tpu_rsplit_open.argtypes = open_sig
        lib.dmlc_tpu_lsplit_open2.restype = ctypes.c_void_p
        lib.dmlc_tpu_lsplit_open2.argtypes = open_sig + [
            ctypes.c_int64, ctypes.c_int64, ctypes.c_char_p, READ_AT_FN,
            ctypes.c_void_p]
        lib.dmlc_tpu_lsplit_finish_cache.restype = ctypes.c_int64
        lib.dmlc_tpu_lsplit_finish_cache.argtypes = [ctypes.c_void_p]
        lib.dmlc_tpu_creplay_open.restype = ctypes.c_void_p
        lib.dmlc_tpu_creplay_open.argtypes = [ctypes.c_char_p]
        lib.dmlc_tpu_creplay_reset.argtypes = [ctypes.c_void_p]
        lib.dmlc_tpu_creplay_next_chunk.restype = ctypes.c_int64
        lib.dmlc_tpu_creplay_next_chunk.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_char_p)]
        lib.dmlc_tpu_creplay_error.restype = ctypes.c_char_p
        lib.dmlc_tpu_creplay_error.argtypes = [ctypes.c_void_p]
        lib.dmlc_tpu_creplay_close.argtypes = [ctypes.c_void_p]
        lib.dmlc_tpu_span_open.restype = ctypes.c_void_p
        lib.dmlc_tpu_span_open.argtypes = open_sig[:4]
        lib.dmlc_tpu_span_open2.restype = ctypes.c_void_p
        lib.dmlc_tpu_span_open2.argtypes = open_sig[:4] + [
            READ_AT_FN, ctypes.c_void_p]
        lib.dmlc_tpu_span_set_plan.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_int64]
        lib.dmlc_tpu_span_next_chunk.restype = ctypes.c_int64
        lib.dmlc_tpu_span_next_chunk.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_char_p)]
        lib.dmlc_tpu_span_error.restype = ctypes.c_char_p
        lib.dmlc_tpu_span_error.argtypes = [ctypes.c_void_p]
        lib.dmlc_tpu_span_close.argtypes = [ctypes.c_void_p]
        lib.dmlc_tpu_lsplit_hint.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.dmlc_tpu_lsplit_total.restype = ctypes.c_int64
        lib.dmlc_tpu_lsplit_total.argtypes = [ctypes.c_void_p]
        lib.dmlc_tpu_lsplit_reset.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64]
        lib.dmlc_tpu_lsplit_next_chunk.restype = ctypes.c_int64
        lib.dmlc_tpu_lsplit_next_chunk.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_char_p)]
        lib.dmlc_tpu_lsplit_next_chunks.restype = ctypes.c_int64
        lib.dmlc_tpu_lsplit_next_chunks.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64]
        lib.dmlc_tpu_lsplit_error.restype = ctypes.c_char_p
        lib.dmlc_tpu_lsplit_error.argtypes = [ctypes.c_void_p]
        lib.dmlc_tpu_lsplit_close.argtypes = [ctypes.c_void_p]
        lib._lsplit_wired = True
    return lib


def lsplit_available() -> bool:
    return _load_lsplit() is not None


def _encode_files(paths, sizes):
    encoded = [p.encode() for p in paths]
    blob = b"".join(encoded)         # length-delimited: any filename byte ok
    lens = (ctypes.c_int64 * len(encoded))(*[len(e) for e in encoded])
    arr = (ctypes.c_int64 * len(sizes))(*sizes)
    return blob, lens, arr


class NativeLineSplit:
    """Handle over the C++ split engine (sharded read + prefetch thread).

    ``next_chunk`` returns bytes of whole records for the partition, or
    None at the end.  ``reset`` re-partitions (or rewinds, with the same
    arguments).  ``format`` selects the record kind: "line" or "recordio"
    (same engine, different realignment scan — native/input_split.cc).

    ``read_at`` (a ``READ_AT_FN``-compatible callable) routes all byte
    reads through Python — the remote-filesystem path; ``cache_path``
    tees epoch-1 chunks into a cache file (``finish_cache`` closes it,
    :class:`NativeCacheReplay` replays it).

    ``ring`` is the native prefetch-queue depth.  2 is the classic double
    buffer; deeper rings pre-post more read-ahead AND switch the consumer
    to the batched ``next_chunks`` pop — one Python↔C crossing (one GIL
    round-trip) amortizes over everything the ring had buffered, the
    VERDICT item-6 fix for the per-chunk crossing tax on the remote
    callback path.
    """

    def __init__(self, paths, sizes, part: int, nparts: int,
                 buffer_size: int = 8 << 20, format: str = "line",
                 read_at=None, cache_path: Optional[str] = None,
                 ring: int = 2):
        lib = _load_lsplit()
        assert lib is not None
        self._lib = lib
        self._ring = max(2, int(ring))
        # batched-pop state: arrays the C side fills in one crossing, and
        # the views already handed back from the last fill (addr, len)
        self._batch_ptrs = (ctypes.c_char_p * self._ring)()
        self._batch_lens = (ctypes.c_int64 * self._ring)()
        self._pending: list = []
        blob, lens, arr = _encode_files(paths, sizes)
        # the CFUNCTYPE object must outlive the handle (the prefetch thread
        # calls through it); keep the reference on self
        if read_at is not None and not isinstance(read_at, READ_AT_FN):
            read_at = READ_AT_FN(read_at)
        self._read_at = read_at
        self._handle = lib.dmlc_tpu_lsplit_open2(
            blob, lens, arr, len(sizes), part, nparts, buffer_size,
            1 if format == "recordio" else 0, self._ring,
            cache_path.encode() if cache_path else None,
            self._read_at if self._read_at is not None
            else ctypes.cast(None, READ_AT_FN), None)
        self._check()

    def finish_cache(self) -> None:
        """Drain the rest of the partition through the cache tee and close
        the cache file (the preproc finish of the cached split)."""
        if self._lib.dmlc_tpu_lsplit_finish_cache(self._require_open()) != 0:
            self._check()

    def _require_open(self):
        if self._handle is None:
            raise ValueError("NativeLineSplit is closed")
        return self._handle

    def _check(self):
        err = self._lib.dmlc_tpu_lsplit_error(self._require_open())
        if err:
            raise OSError(err.decode())

    def total_size(self) -> int:
        return self._lib.dmlc_tpu_lsplit_total(self._require_open())

    def reset(self, part: int, nparts: int) -> None:
        self._pending.clear()   # views into pre-reset chunks are stale
        self._lib.dmlc_tpu_lsplit_reset(self._require_open(), part, nparts)
        self._check()

    def hint_chunk_size(self, chunk_size: int) -> None:
        """Grow the typical chunk size; read position is unaffected."""
        self._lib.dmlc_tpu_lsplit_hint(self._require_open(), chunk_size)

    def next_chunk(self):
        view = self.next_chunk_view()
        if view is None:
            return None
        return ctypes.string_at(*view)

    def next_chunk_view(self):
        """Zero-copy ``(addr, len)`` over the next chunk — valid at least
        until the crossing after the batch it came from drains (with the
        default ``ring=2``: until the next call, the classic contract; the
        parser fast path consumes it in place before popping again).

        With ``ring > 2`` one batched ``next_chunks`` crossing drains
        everything the native ring had buffered and later calls serve from
        that batch without touching the GIL/ctypes boundary."""
        if self._ring > 2:
            if self._pending:
                return self._pending.pop(0)
            n = self._lib.dmlc_tpu_lsplit_next_chunks(
                self._require_open(), self._batch_ptrs, self._batch_lens,
                self._ring)
            if n < 0:
                self._check()
            if n <= 0:
                return None
            ptrs = ctypes.cast(self._batch_ptrs,
                               ctypes.POINTER(ctypes.c_void_p))
            self._pending = [(ptrs[i], self._batch_lens[i])
                             for i in range(n)]
            return self._pending.pop(0)
        ptr = ctypes.c_char_p()
        n = self._lib.dmlc_tpu_lsplit_next_chunk(self._require_open(),
                                                 ctypes.byref(ptr))
        if n < 0:
            self._check()
        if n <= 0:
            return None
        return ctypes.cast(ptr, ctypes.c_void_p).value, n

    def close(self) -> None:
        self._pending.clear()   # batched views die with the handle
        if self._handle is not None:
            self._lib.dmlc_tpu_lsplit_close(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeSpanReader:
    """C++ span-plan reader: index-driven batch reads with prefetch.

    The caller (IndexedRecordIOSplitter) computes a per-epoch plan — flat
    (offset, size) spans in the concatenated-file space plus per-batch span
    counts — and pops concatenated batch chunks; a native producer thread
    reads ahead (native/input_split.cc SpanReadEngine).
    """

    def __init__(self, paths, sizes, read_at=None):
        lib = _load_lsplit()
        assert lib is not None
        self._lib = lib
        blob, lens, arr = _encode_files(paths, sizes)
        if read_at is not None and not isinstance(read_at, READ_AT_FN):
            read_at = READ_AT_FN(read_at)
        self._read_at = read_at  # keep alive for the prefetch thread
        self._handle = lib.dmlc_tpu_span_open2(
            blob, lens, arr, len(sizes),
            self._read_at if self._read_at is not None
            else ctypes.cast(None, READ_AT_FN), None)

    def _require_open(self):
        if self._handle is None:
            raise ValueError("NativeSpanReader is closed")
        return self._handle

    def _check(self):
        err = self._lib.dmlc_tpu_span_error(self._require_open())
        if err:
            raise OSError(err.decode())

    def set_plan(self, offsets, sizes, counts) -> None:
        """Start a new epoch: spans (offsets[i], sizes[i]); batch b is the
        concatenation of counts[b] consecutive spans."""
        offs = np.ascontiguousarray(offsets, dtype=np.int64)
        szs = np.ascontiguousarray(sizes, dtype=np.int64)
        cnt = np.ascontiguousarray(counts, dtype=np.int64)
        assert len(offs) == len(szs)
        self._lib.dmlc_tpu_span_set_plan(
            self._require_open(),
            offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            szs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            cnt.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(offs), len(cnt))

    def next_chunk(self):
        ptr = ctypes.c_char_p()
        n = self._lib.dmlc_tpu_span_next_chunk(self._require_open(),
                                               ctypes.byref(ptr))
        if n < 0:
            self._check()
        if n <= 0:
            return None
        return ctypes.string_at(ptr, n)

    def close(self) -> None:
        if self._handle is not None:
            self._lib.dmlc_tpu_span_close(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeCacheReplay:
    """Replays a (u64-LE length, chunk)-framed cache file with native
    read-ahead — epoch N of the cached split (native/input_split.cc
    CacheReplayEngine; frame format shared with the Python cache writer)."""

    def __init__(self, path: str):
        lib = _load_lsplit()
        assert lib is not None
        self._lib = lib
        self._handle = lib.dmlc_tpu_creplay_open(path.encode())
        self._check()

    def _require_open(self):
        if self._handle is None:
            raise ValueError("NativeCacheReplay is closed")
        return self._handle

    def _check(self):
        err = self._lib.dmlc_tpu_creplay_error(self._require_open())
        if err:
            raise OSError(err.decode())

    def reset(self) -> None:
        """Rewind to the first frame (epoch boundary)."""
        self._lib.dmlc_tpu_creplay_reset(self._require_open())
        self._check()

    def next_chunk(self):
        view = self.next_chunk_view()
        if view is None:
            return None
        return ctypes.string_at(*view)

    def next_chunk_view(self):
        """Zero-copy ``(addr, len)``, valid until the next call."""
        ptr = ctypes.c_char_p()
        n = self._lib.dmlc_tpu_creplay_next_chunk(self._require_open(),
                                                  ctypes.byref(ptr))
        if n < 0:
            self._check()
        if n <= 0:
            return None
        return ctypes.cast(ptr, ctypes.c_void_p).value, n

    def close(self) -> None:
        if self._handle is not None:
            self._lib.dmlc_tpu_creplay_close(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
