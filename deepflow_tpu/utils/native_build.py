"""Build and load the repo's C++ helpers through ctypes.

Each library is one source file, compiled with g++ on first use. The
.so's file name carries a hash of the source, the compiler flags and
the host CPU's flags (`build_key`): `-march=native` code built on
another machine, or from another source, is never loaded — it simply
has another name, and this host builds its own. A library that cannot
be built reports why through `NativeLib.build_error()`; its callers
then keep their pure-Python path.

Libraries are loaded with `ctypes.CDLL`, which releases the interpreter
lock for the length of every call.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading
from typing import Callable, Optional, Tuple

# -O3 -march=native -funroll-loops is load-bearing: the decoder's
# varint walk runs ~3x faster than at generic -O2
CXXFLAGS = ("-O3", "-march=native", "-funroll-loops", "-shared", "-fPIC",
            "-std=c++17")


def cpu_flags() -> str:
    """What -march=native compiles for: the machine and its CPU flags."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return platform.machine() + ":" + " ".join(
                        sorted(line.split(":", 1)[1].split()))
    except OSError:
        pass
    return platform.machine() + ":" + platform.processor()


def build_key(src: str, flags: Tuple[str, ...] = CXXFLAGS,
              cpu: Optional[str] = None) -> str:
    """Hash of everything the binary depends on: source bytes, compiler
    flags and the host CPU's flags."""
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update(b"\0" + " ".join(flags).encode())
    h.update(b"\0" + (cpu_flags() if cpu is None else cpu).encode())
    return h.hexdigest()[:16]


def build(so: str, src: str, flags: Tuple[str, ...] = CXXFLAGS,
          libs: Tuple[str, ...] = ()) -> Optional[str]:
    """Compile `src` to `so` unless it exists; returns an error string
    or None."""
    if os.path.exists(so):
        return None
    # cache-dir creation failures degrade like every other build failure
    # (pure-Python fallback + build_error()), never a startup crash
    try:
        os.makedirs(os.path.dirname(so), exist_ok=True)
    except OSError as e:
        return f"native cache dir: {e}"
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", *flags, src, "-o", tmp, *libs]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        return str(e)
    if proc.returncode != 0:
        return proc.stderr[-2000:]
    os.replace(tmp, so)
    return None


class NativeLib:
    """One keyed shared library: built and loaded once, on first use.

    `bind(lib)` declares the library's function signatures and checks
    it; it returns an error string to refuse the library, or None."""

    def __init__(self, src: str, stem: str,
                 bind: Callable[[ctypes.CDLL], Optional[str]],
                 flags: Tuple[str, ...] = CXXFLAGS,
                 libs: Tuple[str, ...] = ()) -> None:
        self.src = src
        self.stem = stem
        self.flags = flags
        self.libs = libs
        self._bind = bind
        self._lock = threading.Lock()
        self._lib: Optional[ctypes.CDLL] = None
        self._error: Optional[str] = None

    def key(self, cpu: Optional[str] = None) -> str:
        return build_key(self.src, self.flags, cpu)

    def path(self, key: Optional[str] = None) -> str:
        """Build cache location. Default: beside the source.
        `DEEPFLOW_TPU_NATIVE_DIR` overrides for read-only installs (the
        docker-compose manifest bind-mounts the repo :ro and points this
        at a writable volume)."""
        d = os.environ.get("DEEPFLOW_TPU_NATIVE_DIR") or \
            os.path.dirname(self.src)
        return os.path.join(d, f"{self.stem}-{key or self.key()}.so")

    def build(self, so: Optional[str] = None) -> Optional[str]:
        return build(so or self.path(), self.src, self.flags, self.libs)

    def load(self) -> Optional[ctypes.CDLL]:
        with self._lock:
            if self._lib is not None or self._error is not None:
                return self._lib
            so = self.path()
            self._error = self.build(so)
            if self._error is not None:
                return None
            lib = ctypes.CDLL(so)
            self._error = self._bind(lib)
            if self._error is None:
                self._lib = lib
            return self._lib

    def available(self) -> bool:
        return self.load() is not None

    def build_error(self) -> Optional[str]:
        self.load()
        return self._error
