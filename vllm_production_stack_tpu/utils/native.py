"""ctypes bindings for the repo's native (C++) runtime components.

The reference stack's hot host-side paths are native (LMCache's token
hashing, the Go gateway pickers); this module is the TPU stack's equivalent
glue: small C++ shared libraries under csrc/, compiled on first use with the
system toolchain (no pybind11 in this image — plain `extern "C"` + ctypes),
each with a pure-Python fallback so the stack never hard-requires a
compiler at runtime.

Components:
  - kvhash: batch KV chain-hasher (csrc/kvhash.cpp) — one C call hashes a
    whole prompt's full blocks for the content-addressed prefix cache
    (engine/kv_cache.py) instead of one Python sha256 round-trip per block.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import tempfile
import threading

import numpy as np

from .logging import init_logger

logger = init_logger(__name__)

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_CSRC = os.path.join(_REPO_ROOT, "csrc")
_LOCK = threading.Lock()
_KVHASH: ctypes.CDLL | None = None
_KVHASH_FAILED = False


def _build_dir() -> str | None:
    d = os.environ.get("VLLM_TPU_NATIVE_CACHE") or os.path.join(
        tempfile.gettempdir(), f"vllm-tpu-native-{os.getuid()}"
    )
    os.makedirs(d, mode=0o700, exist_ok=True)
    # refuse a cache dir we don't own: on a multi-user host an attacker could
    # pre-create the predictable path and plant a .so that CDLL would execute
    st = os.stat(d)
    if st.st_uid != os.getuid() or (st.st_mode & 0o022):
        logger.warning(
            "native cache dir %s is not private to this user; "
            "refusing to load native libraries from it", d,
        )
        return None
    return d


def _compile(name: str) -> str | None:
    """g++ -O3 -shared csrc/<name>.cpp → cached .so; None if impossible.
    The cache key embeds a content hash of the source, so two checkouts
    sharing the per-uid cache dir can never load each other's binaries."""
    import hashlib

    src = os.path.join(_CSRC, f"{name}.cpp")
    if not os.path.exists(src):
        return None
    build_dir = _build_dir()
    if build_dir is None:
        return None
    with open(src, "rb") as f:
        tag = hashlib.sha256(f.read()).hexdigest()[:16]
    out = os.path.join(build_dir, f"lib{name}-{tag}.so")
    if os.path.exists(out):
        return out
    tmp = out + f".tmp{os.getpid()}"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", src, "-o", tmp]
    try:
        subprocess.run(
            cmd, check=True, capture_output=True, text=True, timeout=120
        )
        os.replace(tmp, out)  # atomic under concurrent builders
        return out
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", "") or str(e)
        logger.warning("native %s build failed (%s); using Python fallback",
                       name, detail.strip()[:200])
        return None


def _load_kvhash() -> ctypes.CDLL | None:
    global _KVHASH, _KVHASH_FAILED
    if _KVHASH is not None or _KVHASH_FAILED:
        return _KVHASH
    with _LOCK:
        if _KVHASH is not None or _KVHASH_FAILED:
            return _KVHASH
        if sys.byteorder != "little":  # the C path reinterprets int64 bytes
            _KVHASH_FAILED = True
            return None
        path = _compile("kvhash")
        if path is None:
            _KVHASH_FAILED = True
            return None
        try:
            lib = ctypes.CDLL(path)
            lib.kvhash_chain.restype = ctypes.c_int64
            lib.kvhash_chain.argtypes = [
                ctypes.c_uint64, ctypes.c_uint64,
                ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
                ctypes.c_int64,
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
            ]
        except OSError as e:
            logger.warning("native kvhash load failed (%s)", e)
            _KVHASH_FAILED = True
            return None
        _KVHASH = lib
        logger.info("native kvhash loaded from %s", path)
        return _KVHASH


def kvhash_backend() -> str:
    """'native' when csrc/kvhash.cpp built and loaded (building it now if
    nothing asked yet), 'python' when the fallback loop serves."""
    return "native" if _load_kvhash() is not None else "python"


def chain_hashes_native(
    parent: int, token_ids, block_size: int
) -> list[int] | None:
    """All full-block chain hashes of a prompt in one native call, byte-exact
    with kv_cache.chain_hash chaining. None if the native library is
    unavailable (callers fall back to the Python loop)."""
    lib = _load_kvhash()
    if lib is None:
        return None
    toks = np.ascontiguousarray(token_ids, dtype=np.int64)
    n_full = len(toks) // block_size
    if n_full <= 0:
        return []
    lo = np.empty(n_full, np.uint64)
    hi = np.empty(n_full, np.uint64)
    lib.kvhash_chain(
        ctypes.c_uint64(parent & 0xFFFFFFFFFFFFFFFF),
        ctypes.c_uint64((parent >> 64) & 0xFFFFFFFFFFFFFFFF),
        toks.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ctypes.c_int64(len(toks)),
        ctypes.c_int64(block_size),
        lo.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        hi.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
    )
    return [int(lo[i]) | (int(hi[i]) << 64) for i in range(n_full)]
