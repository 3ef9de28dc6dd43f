"""Optional native acceleration: build, cache and load the repo's C code.

Two C sources use this one path:

* `_fastcut.c` (next to this module) implements the inner streaming
  vertex-cut loop over the same flat numpy buffers the Python engines
  use: int32 edge endpoints, a float64 load vector, and replica sets
  packed as rows of uint64 bitmask limbs (one limb for p <= 64, a
  chunked `ceil(p/64)`-limb row beyond that);
* `repro/trace/_scan.c` is the NDJSON scanner's one-pass tokenizer.

`native_library` compiles a source on first use with the system C
compiler into a per-user cache directory, keyed by the source's name and
content, and loads it through ctypes — no extra Python dependencies.
When no compiler is available the callers fall back to their Python
paths transparently.

Set REPRO_NO_NATIVE=1 to disable all native code (used in CI to test the
fallback paths).
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess
import sys
import tempfile

import numpy as np

__all__ = ["native_engine", "native_available", "native_library"]

_LIBS: dict = {}            # source path -> bound library or None, per process


def _cache_dir() -> str | None:
    base = os.environ.get("XDG_CACHE_HOME") or os.path.join(
        os.path.expanduser("~"), ".cache")
    candidates = [
        os.path.join(base, "repro-fastcut"),
        # shared tmp fallback must be per-user and 0700: the .so name is
        # predictable, and ctypes.CDLL executes whatever sits there
        os.path.join(tempfile.gettempdir(),
                     f"repro-fastcut-{os.getuid()}"),
    ]
    for path in candidates:
        try:
            os.makedirs(path, mode=0o700, exist_ok=True)
            st = os.stat(path)
            if st.st_uid == os.getuid() and not (st.st_mode & 0o022):
                return path
        except OSError:
            continue
    return None


def _compiler() -> str | None:
    for cc in (os.environ.get("CC"), "cc", "gcc", "clang"):
        if cc and shutil.which(cc):
            return cc
    return None


def _build(src: str) -> ctypes.CDLL | None:
    if os.environ.get("REPRO_NO_NATIVE"):
        return None
    if sys.platform.startswith("win"):
        return None
    if not os.path.exists(src):
        return None
    cc = _compiler()
    if cc is None:
        return None
    cache = _cache_dir()
    if cache is None:
        return None
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    stem = os.path.splitext(os.path.basename(src))[0].lstrip("_")
    so_path = os.path.join(cache, f"{stem}_{digest}.so")
    if not os.path.exists(so_path):
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=os.path.dirname(so_path))
        os.close(fd)
        try:
            # plain -O3 keeps IEEE semantics (no -ffast-math), so native
            # code stays bit-identical to the Python paths
            subprocess.run([cc, "-O3", "-shared", "-fPIC", "-o", tmp, src],
                           check=True, capture_output=True, timeout=120)
            os.replace(tmp, so_path)
        except (OSError, subprocess.SubprocessError):
            if os.path.exists(tmp):
                os.unlink(tmp)
            return None
    try:
        return ctypes.CDLL(so_path)
    except OSError:
        return None


def native_library(src: str, bind):
    """`bind(lib)` for the C source `src` built and loaded, or None when it
    cannot be (no compiler, REPRO_NO_NATIVE, ...).  Built lazily on the
    first call for a source and kept for the process."""
    if src not in _LIBS:
        lib = _build(src)
        _LIBS[src] = None if lib is None else bind(lib)
    return _LIBS[src]


def _bind_stream_cut(lib):
    f64 = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")
    i32 = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    i64 = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    u64 = np.ctypeslib.ndpointer(np.uint64, flags="C_CONTIGUOUS")
    fn = lib.stream_cut
    fn.restype = None
    fn.argtypes = [ctypes.c_int64, ctypes.c_int64, i32, i32, f64,
                   ctypes.c_int32, ctypes.c_int32, ctypes.c_double,
                   f64, u64, ctypes.c_int64, i64, i32]
    return fn


def native_engine():
    """The compiled `stream_cut` entry point, or None if unavailable."""
    return native_library(
        os.path.join(os.path.dirname(__file__), "_fastcut.c"),
        _bind_stream_cut)


def native_available() -> bool:
    return native_engine() is not None
