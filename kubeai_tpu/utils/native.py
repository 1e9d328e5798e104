"""Native extension loader: builds native/fasthash.cc with g++ on first
use and binds it via ctypes. The library under build/ is named by the
source's content hash, so what is loaded was built from the source that
is there (file times mean nothing in a copied tree, and build/ is not
in git). Every native entry point has a pure-Python fallback, so absence
of a toolchain degrades performance, never correctness."""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

log = logging.getLogger("kubeai_tpu.native")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load() -> ctypes.CDLL | None:
    """Build (if needed) and load the fasthash library; None on failure."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        root = _repo_root()
        # Source search order: explicit override (container images place
        # sources outside any repo checkout), then the repo layout.
        candidates = [
            os.environ.get("KUBEAI_NATIVE_DIR"),
            os.path.join(root, "native"),
        ]
        src = next(
            (
                os.path.join(d, "fasthash.cc")
                for d in candidates
                if d and os.path.exists(os.path.join(d, "fasthash.cc"))
            ),
            None,
        )
        if src is None:
            return None
        build_dir = os.environ.get("KUBEAI_BUILD_DIR") or os.path.join(root, "build")
        with open(src, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        so_path = os.path.join(build_dir, f"libfasthash-{digest}.so")
        try:
            if not os.path.exists(so_path):
                os.makedirs(build_dir, exist_ok=True)
                # Build beside the target and rename: a concurrent
                # process never loads a half-written library.
                tmp_path = f"{so_path}.{os.getpid()}.tmp"
                subprocess.run(
                    ["g++", "-O3", "-shared", "-fPIC", "-o", tmp_path, src],
                    check=True,
                    capture_output=True,
                )
                os.replace(tmp_path, so_path)
            lib = ctypes.CDLL(so_path)
            lib.xxh64.restype = ctypes.c_uint64
            lib.xxh64.argtypes = [ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64]
            lib.ring_hashes.restype = None
            lib.ring_hashes.argtypes = [
                ctypes.c_char_p,
                ctypes.c_uint64,
                ctypes.c_uint64,
                ctypes.POINTER(ctypes.c_uint64),
            ]
            lib.ring_search.restype = ctypes.c_uint64
            lib.ring_search.argtypes = [
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.c_uint64,
                ctypes.c_uint64,
            ]
            _lib = lib
            log.info("native fasthash loaded from %s", so_path)
        except (subprocess.CalledProcessError, OSError) as e:
            log.warning("native fasthash unavailable (%s); using Python fallback", e)
            _lib = None
        return _lib


def native_xxh64(data: bytes, seed: int = 0) -> int | None:
    lib = load()
    if lib is None:
        return None
    return lib.xxh64(data, len(data), seed)


def native_ring_hashes(name: bytes, replication: int) -> list[int] | None:
    lib = load()
    if lib is None:
        return None
    out = (ctypes.c_uint64 * replication)()
    lib.ring_hashes(name, len(name), replication, out)
    return list(out)
