"""Native runtime bindings.

The reference keeps its runtime (simulator, search loop, data loader) in
C++ behind a flat C API consumed by Python via cffi
(python/flexflow_c.h + flexflow_cbinding.py). This package does the
same with ctypes: `csrc/` holds the C++ sources and `flexflow_tpu_c.h`
the C API; the shared library is built on first use with g++ into the
git-ignored `_build/`, under a file name that carries a hash of the
sources and headers — so a library built from other sources (a stale
checkout, a copy that reset mtimes) is never loaded. Every caller has
a pure-Python fallback for machines without a toolchain; `status()`
says which of the two ran and why.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import sys
import threading
from typing import Optional

# csrc/ lives inside the package (shipped as package-data in the wheel,
# pyproject.toml), so installed copies can build the native runtime too
_PKG_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CSRC = os.path.join(_PKG_ROOT, "csrc")
_BUILD_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_build")
_LIB_STEM = "libflexflow_tpu_native"

_SOURCES = ("simulator.cc", "mcmc.cc", "dataloader.cc", "embedding_bag.cc")
_HEADERS = ("flexflow_tpu_c.h", "sim_core.h")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False
_status = "not loaded yet"


def source_hash() -> str:
    """sha256 over the names and bytes of every source and header the
    library is built from (first 16 hex digits)."""
    h = hashlib.sha256()
    for f in _SOURCES + _HEADERS:
        h.update(f.encode() + b"\0")
        with open(os.path.join(_CSRC, f), "rb") as fh:
            h.update(fh.read())
    return h.hexdigest()[:16]


def lib_path() -> str:
    """Where the library for the CURRENT sources lives."""
    return os.path.join(_BUILD_DIR, f"{_LIB_STEM}.{source_hash()}.so")


def build(verbose: bool = False) -> str:
    """Compile csrc/ into the shared library; returns its path.

    Compiles to a process-unique temp path and renames into place so
    concurrent builders (pytest-xdist, multi-process JAX) never expose a
    half-written library to ctypes.CDLL. Libraries of other source
    hashes are removed once the new one is in place."""
    os.makedirs(_BUILD_DIR, exist_ok=True)
    path = lib_path()
    tmp_path = f"{path}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-fPIC", "-shared", "-std=c++17", "-Wall",
           "-I", _CSRC,
           *(os.path.join(_CSRC, s) for s in _SOURCES),
           "-o", tmp_path, "-lpthread"]
    if verbose:
        print("[native]", " ".join(cmd), file=sys.stderr)
    try:
        subprocess.run(cmd, check=True, capture_output=not verbose)
        os.replace(tmp_path, path)
    finally:
        if os.path.exists(tmp_path):
            os.unlink(tmp_path)
    for old in glob.glob(os.path.join(_BUILD_DIR, f"{_LIB_STEM}*.so")):
        if old != path:
            try:
                os.unlink(old)
            except OSError:
                pass  # another process may have removed it first
    return path


def _declare(lib: ctypes.CDLL) -> None:
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    f64p = ctypes.POINTER(ctypes.c_double)
    vpp = ctypes.POINTER(ctypes.c_void_p)

    lib.ffsim_simulate.restype = ctypes.c_double
    lib.ffsim_simulate.argtypes = [ctypes.c_int32, f64p, i32p, i32p, i32p]

    lib.ffsearch_mcmc.restype = ctypes.c_double
    lib.ffsearch_mcmc.argtypes = [
        ctypes.c_int32, i32p, i32p,
        f64p, f64p, f64p, f64p, f64p, f64p,
        i32p, i32p, i32p, i32p, f64p, f64p, f64p, ctypes.c_int32,
        ctypes.c_int32, i32p, i32p, i32p, i32p,
        ctypes.c_int32, ctypes.c_double, ctypes.c_uint64,
        ctypes.c_int32, ctypes.c_int32,
        ctypes.c_double, ctypes.c_double, ctypes.c_double, i32p, i32p]

    lib.ffsearch_simulate_assignment.restype = ctypes.c_double
    lib.ffsearch_simulate_assignment.argtypes = [
        ctypes.c_int32, i32p,
        f64p, f64p, f64p, f64p, f64p, f64p,
        i32p, i32p, i32p, i32p, f64p, f64p, f64p, ctypes.c_int32,
        ctypes.c_int32, i32p, i32p,
        ctypes.c_int32, ctypes.c_double, ctypes.c_double,
        ctypes.c_double, i32p]

    lib.ffdl_create.restype = ctypes.c_void_p
    lib.ffdl_create.argtypes = [ctypes.c_int32, vpp, i64p,
                                ctypes.c_int64, ctypes.c_int32,
                                ctypes.c_int32]
    lib.ffdl_start_epoch.restype = None
    lib.ffdl_start_epoch.argtypes = [ctypes.c_void_p, i64p]
    lib.ffdl_num_batches.restype = ctypes.c_int32
    lib.ffdl_num_batches.argtypes = [ctypes.c_void_p]
    lib.ffdl_next_batch.restype = ctypes.c_int32
    lib.ffdl_next_batch.argtypes = [ctypes.c_void_p, vpp, i32p]
    lib.ffdl_destroy.restype = None
    lib.ffdl_destroy.argtypes = [ctypes.c_void_p]

    f32p = ctypes.POINTER(ctypes.c_float)
    lib.ffdl_embedding_bag.restype = None
    lib.ffdl_embedding_bag.argtypes = [
        f32p, ctypes.c_int64, ctypes.c_int32, i64p, ctypes.c_int64,
        ctypes.c_int32, ctypes.c_int32, f32p]

    lib.flexflow_tpu_native_version.restype = ctypes.c_char_p
    lib.flexflow_tpu_native_version.argtypes = []


def get_lib() -> Optional[ctypes.CDLL]:
    """The native library for the current sources, building it when
    `_build/` holds none under their hash; None if unavailable (no
    toolchain / build failure — callers fall back to Python, and
    `status()` keeps the reason)."""
    global _lib, _load_failed, _status
    if _lib is not None or _load_failed:
        return _lib
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        if os.environ.get("FLEXFLOW_TPU_NO_NATIVE"):
            _load_failed = True
            _status = "python: FLEXFLOW_TPU_NO_NATIVE is set"
            return None
        try:
            path = lib_path()
            built = not os.path.exists(path)
            if built:
                build()
            lib = ctypes.CDLL(path)
            _declare(lib)
            _lib = lib
            _status = (f"native: {os.path.basename(path)} "
                       f"({'built now' if built else 'found built'})")
        except (OSError, subprocess.CalledProcessError) as e:
            detail = ""
            stderr = getattr(e, "stderr", None)
            if stderr:
                if isinstance(stderr, bytes):
                    stderr = stderr.decode(errors="replace")
                detail = f"\n{stderr.strip()}"
            print(f"[flexflow_tpu.native] falling back to Python "
                  f"implementations ({e}){detail}", file=sys.stderr)
            _load_failed = True
            _status = f"python: native library unavailable ({e})"
    return _lib


def available() -> bool:
    return get_lib() is not None


def status() -> str:
    """Which implementation get_lib() resolved to, and why: "native:
    <file> (built now | found built)" or "python: <reason>"."""
    return _status
