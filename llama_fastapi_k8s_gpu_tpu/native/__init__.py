"""Native (C++) GGUF load path: build, load, and ctypes bindings.

The reference ships its native engine as a pre-built wheel
(``llama-cpp-python==0.2.77`` compiled with cuBLAS, reference
docker/Dockerfile.base:30-32).  Here the native component is in-tree C++
(``src/gguf_dequant.cpp``) compiled on first use with the host toolchain into
a cached shared library — multithreaded dequantization of the multi-GB GGUF
tensor data at model load, bit-exact with the numpy codecs in
:mod:`..gguf.quants` (the oracle; see tests/test_native.py).

Fallback story: if no C++ compiler is available or the build fails, every
entry point degrades to the numpy reference implementation.  Set
``LFKT_NATIVE=0`` to force the numpy path.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import tempfile
import threading

import numpy as np

logger = logging.getLogger(__name__)

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src", "gguf_dequant.cpp")

# -ffp-contract=off: the kernels must round exactly like numpy's separate
# multiply/subtract ops; FMA contraction would change the last bit.
_CXXFLAGS = ["-O3", "-march=native", "-ffp-contract=off", "-fPIC", "-shared",
             "-std=c++17", "-pthread"]

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_load_attempted = False
_loaded_path: str | None = None


def _enabled() -> bool:
    from ..utils.config import env_bool

    return env_bool("LFKT_NATIVE", default=True)


#: the one place a built library is kept: git-ignored, inside the package,
#: and named by the hash of its source, flags and host (``_load``), so a
#: library built from other source is never picked up in its place
_BUILD_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_build")


def _build(so_path: str) -> bool:
    cxx = os.environ.get("CXX", "g++")
    tmp = so_path + f".tmp.{os.getpid()}"
    cmd = [cxx, *_CXXFLAGS, "-o", tmp, _SRC]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as e:
        logger.info("native build unavailable (%s); using numpy dequant", e)
        return False
    if proc.returncode != 0:
        logger.warning("native build failed; using numpy dequant:\n%s", proc.stderr[-2000:])
        return False
    try:
        os.replace(tmp, so_path)
    except OSError:
        return False
    return True


def _host_tag() -> str:
    """Compiler + microarch fingerprint: -march=native binaries must never be
    reused on a different host/compiler (SIGILL on older CPUs)."""
    import platform

    cxx = os.environ.get("CXX", "g++")
    try:
        ver = subprocess.run([cxx, "-dumpfullversion", "-dumpversion"],
                             capture_output=True, text=True, timeout=10).stdout.strip()
    except (OSError, subprocess.TimeoutExpired):
        ver = "unknown"
    march = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    march = hashlib.sha256(line.encode()).hexdigest()[:8]
                    break
    except OSError:
        march = platform.machine()
    return f"{cxx}-{ver}-{march}"


def _bind(so_path: str) -> ctypes.CDLL | None:
    try:
        lib = ctypes.CDLL(so_path)
    except OSError:
        return None
    lib.lfkt_dequant.argtypes = [
        ctypes.c_int, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_int,
    ]
    lib.lfkt_dequant.restype = ctypes.c_int
    lib.lfkt_supported.argtypes = [ctypes.c_int]
    lib.lfkt_supported.restype = ctypes.c_int
    try:
        lib.lfkt_prep_q4k.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_int,
        ]
        lib.lfkt_prep_q4k.restype = ctypes.c_int
        lib.lfkt_prep_q6k.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
        ]
        lib.lfkt_prep_q6k.restype = ctypes.c_int
        lib.lfkt_prep_q5k.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
        ]
        lib.lfkt_prep_q5k.restype = ctypes.c_int
        lib.lfkt_prep_q8_0.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
        ]
        lib.lfkt_prep_q8_0.restype = ctypes.c_int
    except AttributeError:
        # stale cached .so predating the packers: dequant still works, the
        # prep entry points just fall back to numpy
        pass
    return lib


def _load() -> ctypes.CDLL | None:
    global _loaded_path
    with open(_SRC, "rb") as f:
        payload = f.read() + " ".join(_CXXFLAGS).encode() + _host_tag().encode()
    tag = hashlib.sha256(payload).hexdigest()[:16]
    name = f"gguf_dequant-{tag}.so"

    so_path = os.path.join(_BUILD_DIR, name)
    if not os.path.exists(so_path):
        # compile next to the target so the final rename is atomic; where
        # the package directory is read-only, serve from a temporary build
        try:
            os.makedirs(_BUILD_DIR, exist_ok=True)
        except OSError:
            pass
        if not os.access(_BUILD_DIR, os.W_OK):
            so_path = os.path.join(tempfile.mkdtemp(prefix="lfkt_build_"), name)
        if not _build(so_path):
            return None
    lib = _bind(so_path)
    if lib is not None:
        _loaded_path = so_path
    return lib


def loaded_path() -> str | None:
    """Path of the library this process loaded (None: not loaded — the
    numpy codecs serve).  Never triggers the build itself."""
    return _loaded_path


def get_lib() -> ctypes.CDLL | None:  # lfkt: blocks-under[_lock] -- one-time lazy native build/dlopen: concurrent callers must block until the handle exists, then every call is a cached read
    """The loaded native library, building it on first call; None if unavailable."""
    global _lib, _load_attempted
    if not _enabled():
        return None
    if _load_attempted:
        return _lib
    with _lock:
        if not _load_attempted:
            _lib = _load()
            _load_attempted = True
            if _lib is not None:
                logger.info("native GGUF dequant library loaded")
    return _lib


def _required_bytes(ggml_type: int, n_elements: int) -> int:
    from ..gguf.constants import GGML_BLOCK_SIZES, GGMLType

    block_elems, block_bytes = GGML_BLOCK_SIZES[GGMLType(ggml_type)]
    if n_elements % block_elems != 0:
        return n_elements * block_bytes  # force fallback; numpy raises cleanly
    return (n_elements // block_elems) * block_bytes


def native_supported(ggml_type: int) -> bool:
    lib = get_lib()
    return bool(lib is not None and lib.lfkt_supported(int(ggml_type)))


def native_dequantize(buf: np.ndarray, ggml_type: int, n_elements: int,
                      n_threads: int = 0) -> np.ndarray | None:
    """Flat uint8 buffer -> float32 array, or None if the native path can't
    serve this type (caller falls back to numpy)."""
    if not native_supported(ggml_type):
        return None
    lib = get_lib()
    src = np.ascontiguousarray(buf, dtype=np.uint8).reshape(-1)
    if src.size < _required_bytes(int(ggml_type), n_elements):
        # short/corrupt buffer: let the numpy path raise its shape error
        return None
    out = np.empty(n_elements, dtype=np.float32)
    rc = lib.lfkt_dequant(
        int(ggml_type),
        src.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int64(n_elements),
        out.ctypes.data_as(ctypes.c_void_p),
        int(n_threads),
    )
    if rc != 0:
        logger.warning("native dequant rc=%d for type %d; numpy fallback", rc, ggml_type)
        return None
    return out


def _bf16_view(u16: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return u16.view(ml_dtypes.bfloat16)


def _ptr(a: np.ndarray | None):
    return None if a is None else a.ctypes.data_as(ctypes.c_void_p)


def native_prep_q4k(raw: np.ndarray, n_out: int, k_in: int,
                    n_threads: int = 0) -> dict | None:
    """Raw Q4_K block bytes -> {"qs" int8 (n,k/2), "sm" bf16 (k/2048,n,128)}
    numpy arrays in the fused-kernel layout (ops/pallas/qmatmul.py), packed
    by the threaded C++ path; where ``k_in`` ends in a tail tile (``k_in %
    2048``: 512 or 1024), "qs" / "sm" hold the whole tiles and "qs_t" (n,
    tail/2) / "sm_t" (1,n,128) the tail; None -> caller uses the numpy
    packer."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "lfkt_prep_q4k"):
        return None
    src = np.ascontiguousarray(raw, dtype=np.uint8).reshape(-1)
    if src.size < (n_out * k_in // 256) * 144:
        return None
    tail = k_in % 2048
    kw = k_in - tail
    qs = np.empty((n_out, kw // 2), dtype=np.int8)
    sm = np.empty((kw // 2048, n_out, 128), dtype=np.uint16)
    qs_t = np.empty((n_out, tail // 2), dtype=np.int8) if tail else None
    sm_t = np.empty((1, n_out, 128), dtype=np.uint16) if tail else None
    rc = lib.lfkt_prep_q4k(
        _ptr(src), ctypes.c_int64(n_out), ctypes.c_int64(k_in),
        _ptr(qs), _ptr(sm), _ptr(qs_t), _ptr(sm_t), int(n_threads))
    if rc != 0:
        logger.warning("native prep_q4k rc=%d; numpy fallback", rc)
        return None
    out = {"qs": qs, "sm": _bf16_view(sm)}
    if tail:
        out.update(qs_t=qs_t, sm_t=_bf16_view(sm_t))
    return out


def native_prep_q6k(raw: np.ndarray, n_out: int, k_in: int,
                    n_threads: int = 0) -> dict | None:
    """Raw Q6_K block bytes -> {"q4", "q2", "sm6"} numpy arrays in the fused
    layout (ops/pallas/q6matmul.py), and {"q4_t", "q2_t", "sm6_t"} for a
    tail tile as :func:`native_prep_q4k`; None -> numpy packer."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "lfkt_prep_q6k"):
        return None
    src = np.ascontiguousarray(raw, dtype=np.uint8).reshape(-1)
    if src.size < (n_out * k_in // 256) * 210:
        return None
    tail = k_in % 2048
    kw = k_in - tail
    q4 = np.empty((n_out, kw // 2), dtype=np.int8)
    q2 = np.empty((n_out, kw // 4), dtype=np.int8)
    sm6 = np.empty((kw // 2048, n_out, 128), dtype=np.uint16)
    q4_t = np.empty((n_out, tail // 2), dtype=np.int8) if tail else None
    q2_t = np.empty((n_out, tail // 4), dtype=np.int8) if tail else None
    sm6_t = np.empty((1, n_out, 128), dtype=np.uint16) if tail else None
    rc = lib.lfkt_prep_q6k(
        _ptr(src), ctypes.c_int64(n_out), ctypes.c_int64(k_in),
        _ptr(q4), _ptr(q2), _ptr(sm6), _ptr(q4_t), _ptr(q2_t), _ptr(sm6_t),
        int(n_threads))
    if rc != 0:
        logger.warning("native prep_q6k rc=%d; numpy fallback", rc)
        return None
    out = {"q4": q4, "q2": q2, "sm6": _bf16_view(sm6)}
    if tail:
        out.update(q4_t=q4_t, q2_t=q2_t, sm6_t=_bf16_view(sm6_t))
    return out


def native_prep_q5k(raw: np.ndarray, n_out: int, k_in: int,
                    n_threads: int = 0) -> dict | None:
    """Raw Q5_K block bytes -> {"q5s", "q5h", "sm5"} numpy arrays in the
    fused layout (ops/pallas/q5matmul.py); None -> numpy packer."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "lfkt_prep_q5k"):
        return None
    src = np.ascontiguousarray(raw, dtype=np.uint8).reshape(-1)
    if src.size < (n_out * k_in // 256) * 176:
        return None
    q5s = np.empty((n_out, k_in // 2), dtype=np.int8)
    q5h = np.empty((n_out, k_in // 8), dtype=np.int8)
    sm5 = np.empty((k_in // 2048, n_out, 128), dtype=np.uint16)
    rc = lib.lfkt_prep_q5k(
        src.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int64(n_out), ctypes.c_int64(k_in),
        q5s.ctypes.data_as(ctypes.c_void_p),
        q5h.ctypes.data_as(ctypes.c_void_p),
        sm5.ctypes.data_as(ctypes.c_void_p), int(n_threads))
    if rc != 0:
        logger.warning("native prep_q5k rc=%d; numpy fallback", rc)
        return None
    return {"q5s": q5s, "q5h": q5h, "sm5": _bf16_view(sm5)}


def native_prep_q8_0(raw: np.ndarray, n_out: int, k_in: int,
                     n_threads: int = 0) -> dict | None:
    """Raw Q8_0 block bytes -> {"q8", "sm8"} numpy arrays in the fused
    layout (ops/pallas/q8matmul.py); None -> numpy packer."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "lfkt_prep_q8_0"):
        return None
    src = np.ascontiguousarray(raw, dtype=np.uint8).reshape(-1)
    if src.size < (n_out * k_in // 32) * 34:
        return None
    q8 = np.empty((n_out, k_in), dtype=np.int8)
    sm8 = np.empty((k_in // 2048, n_out, 128), dtype=np.uint16)
    rc = lib.lfkt_prep_q8_0(
        src.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int64(n_out), ctypes.c_int64(k_in),
        q8.ctypes.data_as(ctypes.c_void_p),
        sm8.ctypes.data_as(ctypes.c_void_p), int(n_threads))
    if rc != 0:
        logger.warning("native prep_q8_0 rc=%d; numpy fallback", rc)
        return None
    return {"q8": q8, "sm8": _bf16_view(sm8)}
