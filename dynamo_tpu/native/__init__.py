"""ctypes loader for the native hot-loop library (native/src/*.cpp).

Role-equivalent to the reference's native crates for token hashing and the
router radix index (ref: lib/tokens/src/lib.rs, kv_router/indexer.rs:224).
Builds the .so with g++ on first use when it is missing or older than its
source; every entry point has a pure-Python implementation too, and
:func:`implementation` says which one a process ended up with.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import List, Optional, Tuple

import numpy as np

from ..utils.logging import get_logger

log = get_logger("native")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libdynamo_native.so")

_lib = None
_lib_lock = threading.Lock()
_build_failed = False


_SRC_PATH = os.path.join(_NATIVE_DIR, "src", "dynamo_native.cpp")


def _stale() -> bool:
    """The binary is untracked (``.gitignore``): missing on a fresh checkout
    and possibly older than the source on a lived-in one."""
    try:
        return os.path.getmtime(_SO_PATH) < os.path.getmtime(_SRC_PATH)
    except OSError:
        return True


def _build() -> bool:
    try:
        # the source hashes with the xxhash header pyarrow vendors
        import pyarrow

        tmp = f"{_SO_PATH}.{os.getpid()}.tmp"
        cmd = [
            os.environ.get("CXX", "g++"), "-O3", "-fPIC", "-shared",
            "-std=c++17", "-Wall", f"-I{pyarrow.get_include()}",
            "-o", tmp, _SRC_PATH,
        ]
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO_PATH)  # atomic: concurrent starters race safely
        log.info("native library built from source: %s", _SO_PATH)
        return True
    except Exception as e:
        detail = getattr(e, "stderr", b"") or b""
        log.warning(
            "native build FAILED (%s %s) — token hashing and the router "
            "index run their Python implementations", e,
            detail.decode(errors="replace")[-400:],
        )
        return False


def get_lib() -> Optional[ctypes.CDLL]:
    """Load the native library — (re)building it first when the binary is
    missing or older than its source — or None."""
    global _lib, _build_failed
    if _lib is not None or _build_failed:
        return _lib
    with _lib_lock:
        if _lib is not None or _build_failed:
            return _lib
        if _stale() and not _build():
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(_SO_PATH)
        except OSError as e:
            log.warning("native load failed (%s)", e)
            _build_failed = True
            return None
        lib.dyn_block_hashes.restype = ctypes.c_int64
        lib.dyn_block_hashes.argtypes = [
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_int64,
            ctypes.c_int64, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.dyn_index_new.restype = ctypes.c_void_p
        lib.dyn_index_free.argtypes = [ctypes.c_void_p]
        for name in ("dyn_index_stored", "dyn_index_removed"):
            fn = getattr(lib, name)
            fn.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                           ctypes.POINTER(ctypes.c_uint64), ctypes.c_int64]
        lib.dyn_index_clear_worker.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64]
        lib.dyn_index_num_blocks.restype = ctypes.c_int64
        lib.dyn_index_num_blocks.argtypes = [ctypes.c_void_p]
        lib.dyn_index_find_matches.restype = ctypes.c_int64
        lib.dyn_index_find_matches.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_int64, ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
        ]
        _lib = lib
        log.info("native library loaded: %s", _SO_PATH)
    return _lib


def available() -> bool:
    return get_lib() is not None


def implementation() -> str:
    """``"native"`` or ``"python"`` — which implementation of token hashing
    and the router prefix index this process runs.  Workers log it at
    start-up: the fallback is a different program, not a detail."""
    return "native" if available() else "python"


# ------------------------------ hashing -----------------------------------


def block_hashes(
    tokens, block_size: int, seed: int
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """(block_hashes, sequence_hashes) for complete blocks via the native
    path, or None when the library is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    toks = np.ascontiguousarray(tokens, dtype=np.uint32)
    n_blocks = len(toks) // block_size
    bh = np.empty(n_blocks, np.uint64)
    sh = np.empty(n_blocks, np.uint64)
    got = lib.dyn_block_hashes(
        toks.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)), len(toks),
        block_size, seed,
        bh.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        sh.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
    )
    assert got == n_blocks
    return bh, sh


# ---------------------------- prefix index ---------------------------------


class NativePrefixIndex:
    """C++ longest-prefix matcher (chained sequence hashes → workers)."""

    def __init__(self):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._h = lib.dyn_index_new()

    def close(self) -> None:
        if self._h is not None:
            self._lib.dyn_index_free(self._h)
            self._h = None

    __del__ = close

    @staticmethod
    def _arr(hashes) -> np.ndarray:
        return np.ascontiguousarray(hashes, dtype=np.uint64)

    def stored(self, worker: int, seq_hashes) -> None:
        a = self._arr(seq_hashes)
        self._lib.dyn_index_stored(
            self._h, worker,
            a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)), len(a))

    def removed(self, worker: int, seq_hashes) -> None:
        a = self._arr(seq_hashes)
        self._lib.dyn_index_removed(
            self._h, worker,
            a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)), len(a))

    def clear_worker(self, worker: int) -> None:
        self._lib.dyn_index_clear_worker(self._h, worker)

    @property
    def num_blocks(self) -> int:
        return self._lib.dyn_index_num_blocks(self._h)

    def find_matches(self, seq_hashes, max_workers: int = 4096
                     ) -> dict:
        a = self._arr(seq_hashes)
        workers = np.empty(max_workers, np.uint64)
        depths = np.empty(max_workers, np.int64)
        n = self._lib.dyn_index_find_matches(
            self._h, a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            len(a),
            workers.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            depths.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            max_workers,
        )
        return {int(workers[i]): int(depths[i]) for i in range(n)}
