"""Native (C++) runtime components, loaded via ctypes.

The reference's runtime is native where it matters — DataLoader worker pools,
tokenizers, NCCL/Gloo collectives, CUDA allocator all live in C++/Rust under
the torch/HF stack. On TPU the collective/allocator layer IS the XLA runtime;
what remains genuinely host-side is implemented here in C++ (native/src/) and
driven through a minimal ctypes ABI (no pybind11 in this image):

- ``batcher.cpp``   — prefetching batch assembler (worker pool + slot ring)
- ``wordpiece.cpp`` — multithreaded WordPiece batch encoder

Shared libraries build lazily on first use with the system toolchain and
cache under ``native/build/``. Everything degrades gracefully: if no C++
toolchain is available the loaders return None and callers fall back to the
pure-Python paths.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

from pytorch_distributed_training_tpu.utils.logging import get_logger

_log = get_logger(__name__)
_REPO_NATIVE = os.path.join(os.path.dirname(__file__), "..", "..", "native")
_SRC_DIR = os.path.abspath(os.path.join(_REPO_NATIVE, "src"))
_BUILD_DIR = os.path.abspath(os.path.join(_REPO_NATIVE, "build"))

_lock = threading.Lock()
_libs: dict[str, ctypes.CDLL | None] = {}


def _compile(name: str) -> str | None:
    src = os.path.join(_SRC_DIR, f"{name}.cpp")
    lib = os.path.join(_BUILD_DIR, f"lib{name}.so")
    try:
        os.makedirs(_BUILD_DIR, exist_ok=True)
        if os.path.exists(lib):
            # no source shipped (prebuilt deployment) -> trust the library;
            # otherwise rebuild when the source is newer than the cache
            if not os.path.exists(src) or (
                os.path.getmtime(lib) >= os.path.getmtime(src)
            ):
                return lib
        elif not os.path.exists(src):
            return None
    except OSError:
        return None
    cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
        src, "-o", lib,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except (OSError, subprocess.SubprocessError) as e:
        # callers fall back to the Python paths; the log says why
        stderr = getattr(e, "stderr", b"") or b""
        _log.warning(
            "native %s build failed (%r); using the Python path. %s",
            name, e, stderr.decode(errors="replace")[-400:],
        )
        return None
    return lib


def _load(name: str, declare) -> ctypes.CDLL | None:
    with _lock:
        if name in _libs:
            return _libs[name]
        _libs[name] = None
        path = _compile(name)
        if path is None:
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            # a stale/foreign-platform cached .so must degrade to the
            # Python path, not crash every caller
            return None
        declare(lib)
        _libs[name] = lib
        return lib


def _declare_batcher(lib: ctypes.CDLL) -> None:
    lib.batcher_create.restype = ctypes.c_void_p
    lib.batcher_create.argtypes = [
        ctypes.POINTER(ctypes.c_void_p),  # const int32** arrays
        ctypes.POINTER(ctypes.c_int64),   # row_elems
        ctypes.c_int32,                   # n_arrays
        ctypes.c_int64,                   # n_rows
        ctypes.c_int64,                   # accum
        ctypes.c_int64,                   # micro_global
        ctypes.c_int64,                   # micro_local
        ctypes.c_int64,                   # local_off
        ctypes.c_int32,                   # n_slots
        ctypes.c_int32,                   # n_threads
    ]
    lib.batcher_start_epoch.restype = ctypes.c_int64
    lib.batcher_start_epoch.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64)
    ]
    lib.batcher_next.restype = ctypes.c_int32
    lib.batcher_next.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p)
    ]
    lib.batcher_release.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.batcher_destroy.argtypes = [ctypes.c_void_p]


def _declare_wordpiece(lib: ctypes.CDLL) -> None:
    lib.wp_create.restype = ctypes.c_void_p
    lib.wp_create.argtypes = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_int32]
    lib.wp_destroy.argtypes = [ctypes.c_void_p]
    lib.wp_special_id.restype = ctypes.c_int32
    lib.wp_special_id.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.wp_encode_pairs.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,                     # n
        ctypes.c_int64,                     # max_length
        ctypes.c_int32,                     # n_threads
        ctypes.POINTER(ctypes.c_int32),     # out_ids
        ctypes.POINTER(ctypes.c_int32),     # out_types
        ctypes.POINTER(ctypes.c_int32),     # out_mask
    ]


def load_batcher_lib() -> ctypes.CDLL | None:
    """Compile (once) and load the native batcher; None if unavailable."""
    return _load("batcher", _declare_batcher)


def load_wordpiece_lib() -> ctypes.CDLL | None:
    """Compile (once) and load the native WordPiece encoder."""
    return _load("wordpiece", _declare_wordpiece)


def native_available() -> bool:
    return load_batcher_lib() is not None
