"""Native codec bindings (ctypes) with transparent numpy fallback.

Loads ``libp2tw.so``, built from ``codec.cpp`` on first import when a
compiler is available. The library is git-ignored and travels with a
copied tree, so it is only loaded when the source hash recorded beside it
(``libp2tw.so.sha256``) matches the present ``codec.cpp`` — a stale or
foreign binary is rebuilt, never trusted. Every entry point has a numpy
fallback so the framework never *requires* the native layer — it's the
fast path, not a dependency; :data:`NATIVE` says which one is in use.

API:
- :func:`quantize`   — fp32 array → (int8 array, scale)
- :func:`dequantize` — (int8 array, scale) → fp32 array
- :func:`crc32c`     — Castagnoli CRC of a bytes-like
- :data:`NATIVE`     — True when the C++ library is in use
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "codec.cpp")
_SO = os.path.join(_DIR, "libp2tw.so")
_STAMP = _SO + ".sha256"  # hex sha256 of the codec.cpp the .so was built from

_lib: Optional[ctypes.CDLL] = None


def _src_hash() -> Optional[str]:
    try:
        with open(_SRC, "rb") as f:
            return hashlib.sha256(f.read()).hexdigest()
    except OSError:
        return None


def _fresh() -> bool:
    """The .so exists and was built from the present ``codec.cpp``."""
    try:
        with open(_STAMP) as f:
            stamp = f.read().strip()
    except OSError:
        return False
    return os.path.exists(_SO) and stamp == _src_hash()


def _try_build() -> None:
    """Build the .so atomically, serialized across processes.

    Two node processes importing concurrently must not both run ``g++ -o
    libp2tw.so`` in place — one would ``CDLL`` a half-written library.
    The compile targets a private temp file promoted with :func:`os.replace`
    (atomic on POSIX), and an ``fcntl`` lockfile serializes builders: the
    loser of the race wakes up, sees the finished .so, and skips its build.
    """
    if not os.path.exists(_SRC):
        return
    try:
        import fcntl
    except ImportError:
        # no fcntl (Windows): build without the inter-process lock — the
        # temp-file + atomic os.replace promotion alone already prevents a
        # concurrent importer from CDLLing a torn .so
        fcntl = None
    tmp = f"{_SO}.tmp.{os.getpid()}"
    try:
        if fcntl is None:
            _compile(tmp)
            return
        with open(f"{_SO}.lock", "w") as lockf:
            fcntl.flock(lockf, fcntl.LOCK_EX)
            try:
                if _fresh():
                    return  # another process built it while we waited
                _compile(tmp)
            finally:
                fcntl.flock(lockf, fcntl.LOCK_UN)
    except (OSError, subprocess.SubprocessError):
        pass
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass


def _compile(tmp: str) -> None:
    digest = _src_hash()
    subprocess.run(
        ["g++", "-O3", "-shared", "-fPIC", "-o", tmp, _SRC],
        check=True,
        capture_output=True,
        timeout=120,
    )
    os.replace(tmp, _SO)
    # stamp AFTER the .so: a crash between the two leaves a mismatch, which
    # only costs a rebuild
    with open(f"{tmp}.sha256", "w") as f:
        f.write(f"{digest}\n")
    os.replace(f"{tmp}.sha256", _STAMP)


def _load() -> Optional[ctypes.CDLL]:
    if not _fresh():
        _try_build()
    if not _fresh():
        return None  # no compiler (or no source): numpy path, NATIVE False
    try:
        lib = ctypes.CDLL(_SO)
    except OSError:
        return None
    lib.p2tw_quantize_f32_i8.restype = ctypes.c_float
    lib.p2tw_quantize_f32_i8.argtypes = [
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int8),
    ]
    lib.p2tw_dequantize_i8_f32.restype = None
    lib.p2tw_dequantize_i8_f32.argtypes = [
        ctypes.POINTER(ctypes.c_int8),
        ctypes.c_int64,
        ctypes.c_float,
        ctypes.POINTER(ctypes.c_float),
    ]
    lib.p2tw_crc32c.restype = ctypes.c_uint32
    lib.p2tw_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_uint32]
    return lib


_lib = _load()
NATIVE = _lib is not None


def quantize(arr: np.ndarray) -> tuple[np.ndarray, float]:
    """Symmetric per-tensor int8 quantization. Returns (int8 array, scale)."""
    flat = np.ascontiguousarray(arr, dtype=np.float32).reshape(-1)
    out = np.empty(flat.shape, dtype=np.int8)
    if _lib is not None:
        scale = _lib.p2tw_quantize_f32_i8(
            flat.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            flat.size,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
        )
        return out.reshape(arr.shape), float(scale)
    absmax = float(np.abs(flat).max()) if flat.size else 0.0
    scale = absmax / 127.0 if absmax > 0 else 1.0
    q = np.clip(np.rint(flat / scale), -127, 127)
    return q.astype(np.int8).reshape(arr.shape), scale


def dequantize(arr: np.ndarray, scale: float) -> np.ndarray:
    flat = np.ascontiguousarray(arr, dtype=np.int8).reshape(-1)
    if _lib is not None:
        out = np.empty(flat.shape, dtype=np.float32)
        _lib.p2tw_dequantize_i8_f32(
            flat.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
            flat.size,
            ctypes.c_float(scale),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        )
        return out.reshape(arr.shape)
    return (flat.astype(np.float32) * scale).reshape(arr.shape)


def crc32c(data, seed: int = 0) -> int:
    """CRC32C of a bytes-like (``bytes`` or ``memoryview`` — the decode hot
    path passes payload-frame slices without copying them out)."""
    if _lib is not None:
        if isinstance(data, bytes):
            return int(_lib.p2tw_crc32c(data, len(data), seed))
        # zero-copy pointer into the buffer (read-only buffers included,
        # which ctypes' from_buffer would reject)
        buf = np.frombuffer(data, dtype=np.uint8)
        ptr = buf.ctypes.data_as(ctypes.c_char_p)
        return int(_lib.p2tw_crc32c(ptr, buf.size, seed))
    return _crc32c_py(data, seed)


_PY_TABLE: Optional[list[int]] = None


def _crc32c_py(data: bytes, seed: int = 0) -> int:
    global _PY_TABLE
    if _PY_TABLE is None:
        table = []
        for i in range(256):
            c = i
            for _ in range(8):
                c = (0x82F63B78 ^ (c >> 1)) if c & 1 else (c >> 1)
            table.append(c)
        _PY_TABLE = table
    c = seed ^ 0xFFFFFFFF
    for b in data:
        c = _PY_TABLE[(c ^ b) & 0xFF] ^ (c >> 8)
    return c ^ 0xFFFFFFFF


def _gf2_times(mat: list, vec: int) -> int:
    s = 0
    i = 0
    while vec:
        if vec & 1:
            s ^= mat[i]
        vec >>= 1
        i += 1
    return s


def _gf2_square(mat: list) -> list:
    return [_gf2_times(mat, mat[n]) for n in range(32)]


#: shift operators cached per byte count — every chunk of one stream has
#: the same body length, so a whole transfer pays the O(32·log n) matrix
#: build at most twice (slab size + the odd-sized final chunk)
_COMBINE_OPS: dict[int, list] = {}


def crc32c_combine(crc1: int, crc2: int, len2: int) -> int:
    """CRC32C of ``A + B`` from ``crc32c(A)``, ``crc32c(B)`` and ``len(B)``,
    touching zero payload bytes (zlib's ``crc32_combine`` over the
    Castagnoli polynomial).

    The streaming decoder verifies each arriving chunk's own CRC (one
    pass over its bytes) and folds it into the running whole-payload CRC
    with this combine — instead of a second full pass per byte, the fold
    is one cached 32×32 GF(2) matrix-vector product per chunk.
    """
    if len2 <= 0:
        return crc1
    op = _COMBINE_OPS.get(len2)
    if op is None:
        # operator for one zero BIT, squared 3× → one zero byte
        mat = [0x82F63B78] + [1 << n for n in range(31)]
        for _ in range(3):
            mat = _gf2_square(mat)
        # square-and-multiply up to len2 zero bytes
        op = [1 << n for n in range(32)]  # identity
        n = len2
        while n:
            if n & 1:
                op = [_gf2_times(mat, col) for col in op]
            n >>= 1
            if n:
                mat = _gf2_square(mat)
        if len(_COMBINE_OPS) < 256:
            _COMBINE_OPS[len2] = op
    return _gf2_times(op, crc1) ^ crc2
