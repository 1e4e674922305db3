"""ctypes binding for the dict wire's native key table
(native_src/flow_dict.cc).

`KeyTable` resolves a whole record batch's flow keys in one call that
releases the interpreter lock: the same contract, and the same output,
as the pure-Python `flow_dict.PyKeyTable`, which FlowDictPacker keeps
only where `available()` is False (no compiler; the packer then logs
the `build_error()` once).
"""

from __future__ import annotations

import ctypes
import os
import secrets
import weakref
from typing import NamedTuple, Optional

import numpy as np

from deepflow_tpu.utils.native_build import NativeLib

_SRC = os.path.join(os.path.dirname(__file__), "native_src", "flow_dict.cc")

_long_p = ctypes.POINTER(ctypes.c_long)


class Resolution(NamedTuple):
    """One batch's key resolution. `rec_idx` (each record's index) and
    `news_rows` (the first record of each key new to the table, in
    resolution order) are None when the batch was refused: `n_uniq`
    distinct keys reached capacity, and the table is unchanged."""

    rec_idx: Optional[np.ndarray]
    news_rows: Optional[np.ndarray]
    n_uniq: int          # distinct keys in the batch
    size_before: int     # keys the table held before the batch
    evicted: int         # LRU heads the batch evicted


def _bind(lib: ctypes.CDLL) -> Optional[str]:
    lib.df_dict_new.restype = ctypes.c_void_p
    lib.df_dict_new.argtypes = [ctypes.c_uint32, ctypes.c_uint64]
    lib.df_dict_free.restype = None
    lib.df_dict_free.argtypes = [ctypes.c_void_p]
    lib.df_dict_size.restype = ctypes.c_long
    lib.df_dict_size.argtypes = [ctypes.c_void_p]
    lib.df_dict_resolve.restype = ctypes.c_long
    lib.df_dict_resolve.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint32), ctypes.c_long,
        ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_int64),
        _long_p, _long_p, _long_p]
    return None


_LIB = NativeLib(_SRC, "_flow_dict", _bind)


def available() -> bool:
    return _LIB.available()


def build_error() -> Optional[str]:
    return _LIB.build_error()


class KeyTable:
    """Native key -> dictionary-index table with an exact LRU over
    `capacity` slots. Freed with the object; a device-state restore
    builds a fresh packer, and with it a fresh table."""

    native = True

    def __init__(self, capacity: int) -> None:
        lib = _LIB.load()
        if lib is None:
            raise RuntimeError(
                f"native flow dict unavailable: {_LIB.build_error()}")
        self.capacity = int(capacity)
        # a per-table hash seed: bucket placement only, never identity
        t = lib.df_dict_new(self.capacity, secrets.randbits(64))
        if not t:
            raise MemoryError(f"flow dict table of {capacity} slots")
        self._lib = lib
        self._t = t
        self._free = weakref.finalize(self, lib.df_dict_free, t)

    def __len__(self) -> int:
        return self._lib.df_dict_size(self._t)

    def resolve(self, keys: np.ndarray):
        """(n, 4) u32 key words -> Resolution."""
        keys = np.ascontiguousarray(keys, np.uint32)
        if keys.ndim != 2 or keys.shape[1] != 4:
            raise ValueError(f"keys must be (n, 4) u32, got {keys.shape}")
        n = len(keys)
        rec_idx = np.empty(n, np.uint32)
        news = np.empty(n, np.int64)
        n_uniq, size, ev = ctypes.c_long(), ctypes.c_long(), ctypes.c_long()
        u32p = ctypes.POINTER(ctypes.c_uint32)
        k = self._lib.df_dict_resolve(
            self._t, keys.ctypes.data_as(u32p), n,
            rec_idx.ctypes.data_as(u32p),
            news.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            ctypes.byref(n_uniq), ctypes.byref(size), ctypes.byref(ev))
        if k == -1:
            return Resolution(None, None, n_uniq.value, size.value, 0)
        if k < 0:
            raise MemoryError(f"flow dict resolve of {n} records")
        return Resolution(rec_idx, news[:k], n_uniq.value, size.value,
                          ev.value)
