"""ctypes binding for the C++ columnar decoder (native_src/decoder.cc).

Compiles the shared library on first use (g++ is part of the toolchain;
utils/native_build.py builds and loads it under a key of its source,
flags and host CPU) and exposes `decode_l4_payloads`, a drop-in fast
path for the flow_log decode stage. `available()` is False when no
compiler exists; the flow_log pipeline then logs the `build_error()`
and keeps the pure-Python decoder.

The native ABI emits two plane blocks per batch — a [N32, capacity] u32
block for every u32/i32 schema column and a [N64, capacity] u64 block for
the 64-bit tail (macs, flow_id, microsecond clocks) — matching
batch/schema.py L4_SCHEMA order exactly.
"""

from __future__ import annotations

import ctypes
import os
from typing import Dict, Iterable, Optional, Tuple

import numpy as np

from deepflow_tpu.batch.schema import L4_SCHEMA
from deepflow_tpu.utils.native_build import NativeLib

_SRC = os.path.join(os.path.dirname(__file__), "native_src", "decoder.cc")

# schema columns partitioned by plane width (order preserved per plane)
L4_COLS32 = tuple((n, d) for n, d in L4_SCHEMA.columns
                  if np.dtype(d).itemsize == 4)
L4_COLS64 = tuple((n, d) for n, d in L4_SCHEMA.columns
                  if np.dtype(d).itemsize == 8)


def _bind(lib: ctypes.CDLL) -> Optional[str]:
    lib.df_decode_l4.restype = ctypes.c_long
    lib.df_decode_l4.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_long,
        ctypes.POINTER(ctypes.c_long), ctypes.POINTER(ctypes.c_size_t),
    ]
    lib.df_decode_l4_mt.restype = ctypes.c_long
    lib.df_decode_l4_mt.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_long, ctypes.c_int,
        ctypes.POINTER(ctypes.c_long), ctypes.POINTER(ctypes.c_size_t),
    ]
    lib.df_n_l4_cols.restype = ctypes.c_int
    lib.df_n_l4_cols64.restype = ctypes.c_int
    n32, n64 = lib.df_n_l4_cols(), lib.df_n_l4_cols64()
    if n32 != len(L4_COLS32) or n64 != len(L4_COLS64):
        return (f"column count mismatch: native {n32}+{n64} vs "
                f"schema {len(L4_COLS32)}+{len(L4_COLS64)}")
    return None


_LIB = NativeLib(_SRC, "_native_decoder", _bind, libs=("-lpthread",))


def available() -> bool:
    return _LIB.available()


def build_error() -> Optional[str]:
    return _LIB.build_error()


def build_key() -> str:
    return _LIB.key()


def decode_l4_into(payload: bytes, out32: np.ndarray, out64: np.ndarray,
                   n_threads: int = 1) -> Tuple[int, int, int]:
    """Zero-alloc decode into caller-owned [N32, capacity] uint32 and
    [N64, capacity] uint64 buffers. Returns (rows, bad_records,
    consumed_bytes). The buffers can be reused across calls — the bench's
    double-buffer feed path (reference: server/libs/receiver/receiver.go
    tiered buffer pools play this role)."""
    lib = _LIB.load()
    if lib is None:
        raise RuntimeError(
            f"native decoder unavailable: {_LIB.build_error()}")
    assert out32.ndim == 2 and out32.shape[0] == len(L4_COLS32) and \
        out32.dtype == np.uint32 and out32.flags.c_contiguous
    assert out64.ndim == 2 and out64.shape[0] == len(L4_COLS64) and \
        out64.dtype == np.uint64 and out64.flags.c_contiguous
    assert out32.shape[1] == out64.shape[1]
    capacity = out32.shape[1]
    bad = ctypes.c_long()
    consumed = ctypes.c_size_t()
    p32 = out32.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32))
    p64 = out64.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))
    if n_threads == 1:
        rows = lib.df_decode_l4(payload, len(payload), p32, p64, capacity,
                                ctypes.byref(bad), ctypes.byref(consumed))
    else:
        rows = lib.df_decode_l4_mt(payload, len(payload), p32, p64,
                                   capacity, n_threads, ctypes.byref(bad),
                                   ctypes.byref(consumed))
    return rows, bad.value, consumed.value


def _mats_to_cols(mat32: np.ndarray,
                  mat64: np.ndarray) -> Dict[str, np.ndarray]:
    cols: Dict[str, np.ndarray] = {}
    for i, (name, dt) in enumerate(L4_COLS32):
        col = mat32[i]
        cols[name] = col.view(np.int32) if dt == np.dtype(np.int32) \
            else col
    for i, (name, _) in enumerate(L4_COLS64):
        cols[name] = mat64[i]
    return cols


def decode_l4_payload(payload: bytes, capacity: int = 65536,
                      n_threads: int = 1
                      ) -> Tuple[Dict[str, np.ndarray], int]:
    """Decode one packed-record payload -> (L4 columns, bad_record_count).

    `capacity` bounds rows per call; payload bytes beyond it are decoded
    in further passes internally, so the result always covers the whole
    payload.
    """
    n32, n64 = len(L4_COLS32), len(L4_COLS64)
    chunks = []
    bad_total = 0
    view = payload
    while True:
        out32 = np.empty((n32, capacity), np.uint32)
        out64 = np.empty((n64, capacity), np.uint64)
        rows, bad, consumed = decode_l4_into(view, out32, out64,
                                             n_threads=n_threads)
        bad_total += bad
        if rows > 0:
            chunks.append((out32[:, :rows].copy(), out64[:, :rows].copy()))
        if consumed >= len(view) or rows == 0:
            break
        view = view[consumed:]
    if chunks:
        mat32 = np.concatenate([c[0] for c in chunks], axis=1)
        mat64 = np.concatenate([c[1] for c in chunks], axis=1)
    else:
        mat32 = np.empty((n32, 0), np.uint32)
        mat64 = np.empty((n64, 0), np.uint64)
    return _mats_to_cols(mat32, mat64), bad_total


def decode_l4_records(records: Iterable[bytes]) -> Dict[str, np.ndarray]:
    """Same contract as columnar.decode_l4_records, via the native path."""
    from deepflow_tpu.wire.codec import pack_pb_records

    cols, _ = decode_l4_payload(pack_pb_records(records))
    return cols


class PipelinedDecoder:
    """Overlap protobuf decode with the consumer's device work.

    The serial compat-path loop pays decode + transfer + dispatch
    back-to-back; since decode_l4_into releases the GIL inside the C++
    walker and the transfer is mostly socket/DMA wait, running decode
    on a feeder thread overlaps the two and lifts the protobuf e2e
    toward the pure-decode ceiling (the reference's decoder goroutine
    pool in front of ckwriter plays the same role).

    Buffer discipline: a ring of >=3 (buf32, buf64) pairs cycles
    free -> decoded -> consumed; the consumer RETURNS each slot via
    done() (or just lets `for` advance: the previous slot auto-returns)
    so a decoded buffer is never overwritten while the device still
    reads from it.
    """

    def __init__(self, capacity: int, n_bufs: int = 3,
                 n_threads: int = 1) -> None:
        import queue as _q
        import threading as _t
        if n_bufs < 2:
            raise ValueError("need >=2 buffers to overlap")
        n32, n64 = len(L4_COLS32), len(L4_COLS64)
        self._bufs = [(np.empty((n32, capacity), np.uint32),
                       np.empty((n64, capacity), np.uint64))
                      for _ in range(n_bufs)]
        self.n_threads = n_threads
        self._q = _q
        self._threading = _t

    def stream(self, payloads):
        """Yield (rows, buf32, buf64) per payload, decode running one
        (or more) payloads ahead on the feeder thread. A yielded buffer
        is valid for EXACTLY ONE iteration step — fetching the next
        item frees it for the feeder to overwrite. One stream at a
        time per decoder (the buffer ring is shared); the queues are
        per-call and an early consumer break stops the feeder, so an
        aborted or failed stream never poisons the next one."""
        free: "self._q.Queue[int]" = self._q.Queue()
        for i in range(len(self._bufs)):
            free.put(i)
        ready: "self._q.Queue" = self._q.Queue()
        stop = self._threading.Event()

        from deepflow_tpu.runtime.supervisor import default_supervisor
        sup = default_supervisor()

        def feeder():
            try:
                for p in payloads:
                    while True:              # stoppable slot wait
                        if stop.is_set():
                            return
                        sup.beat()
                        try:
                            i = free.get(timeout=0.1)
                            break
                        except self._q.Empty:
                            continue
                    b32, b64 = self._bufs[i]
                    rows, _bad, _ = decode_l4_into(
                        p, b32, b64, n_threads=self.n_threads)
                    ready.put((i, rows))
            except BaseException as e:      # surfaced on the consumer
                ready.put(e)
            finally:
                ready.put(None)

        # supervised (crash capture + deadman beat from the slot wait);
        # restart=False: a re-entered feeder would double-iterate
        # `payloads` — errors already reach the consumer via `ready`
        t = sup.spawn("pb-decode", feeder, restart=False)
        held = None
        try:
            while True:
                got = ready.get()
                if got is None:
                    break
                if isinstance(got, BaseException):
                    raise got
                i, rows = got
                if held is not None:
                    free.put(held)          # previous slot now reusable
                held = i
                b32, b64 = self._bufs[i]
                yield rows, b32, b64
        finally:
            stop.set()                      # unblock an early-break feeder
            t.stop()
            t.join(timeout=5)
