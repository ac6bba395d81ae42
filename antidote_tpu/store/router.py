"""Key→shard router: native XXH64 with a bit-exact Python fallback.

Replaces the reference's crypto-NIF consistent hash
(/root/reference/src/log_utilities.erl:96-118; SURVEY §2.9 row 3).
Integer keys map directly (``key % n_shards``) exactly like the
reference's direct-int path (:75-79); other keys hash their canonical
msgpack serialization.  The native library batches thousands of keys per
FFI crossing; the Python fallback implements the same XXH64 so replicas
with and without a compiler agree on every shard assignment.
"""

from __future__ import annotations

import ctypes
from pathlib import Path
from typing import Any, Sequence

import msgpack
import numpy as np

from antidote_tpu import native_build

_SRC = Path(__file__).parent / "cpp" / "router.cc"
_SO = Path(__file__).parent / "cpp" / "_router.so"

_lib = None
_lib_tried = False

_M = (1 << 64) - 1
_P1 = 0x9E3779B185EBCA87
_P2 = 0xC2B2AE3D27D4EB4F
_P3 = 0x165667B19E3779F9
_P4 = 0x85EBCA77C2B2AE63
_P5 = 0x27D4EB2F165667C5


def _load_lib():
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    lib = native_build.load("router", _SRC, _SO)
    if lib is not None:
        lib.router_hash64.restype = ctypes.c_uint64
        lib.router_hash64.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                      ctypes.c_uint64]
        lib.router_shard_batch.restype = None
        lib.router_shard_batch.argtypes = [
            ctypes.c_char_p,
            np.ctypeslib.ndpointer(np.uint64, flags="C_CONTIGUOUS"),
            ctypes.c_int64, ctypes.c_uint64, ctypes.c_int64,
            np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        ]
    _lib = lib
    return _lib


# ---------------------------------------------------------------------------
# pure-Python XXH64 (same spec as router.cc; must agree bit-for-bit)
# ---------------------------------------------------------------------------
def _rotl(x, r):
    return ((x << r) | (x >> (64 - r))) & _M


def _round(acc, inp):
    acc = (acc + inp * _P2) & _M
    return (_rotl(acc, 31) * _P1) & _M


def _merge(acc, val):
    acc ^= _round(0, val)
    return (acc * _P1 + _P4) & _M


def xxh64_py(data: bytes, seed: int = 0) -> int:
    n = len(data)
    p = 0
    if n >= 32:
        v1 = (seed + _P1 + _P2) & _M
        v2 = (seed + _P2) & _M
        v3 = seed & _M
        v4 = (seed - _P1) & _M
        while p + 32 <= n:
            v1 = _round(v1, int.from_bytes(data[p:p + 8], "little")); p += 8
            v2 = _round(v2, int.from_bytes(data[p:p + 8], "little")); p += 8
            v3 = _round(v3, int.from_bytes(data[p:p + 8], "little")); p += 8
            v4 = _round(v4, int.from_bytes(data[p:p + 8], "little")); p += 8
        h = (_rotl(v1, 1) + _rotl(v2, 7) + _rotl(v3, 12) + _rotl(v4, 18)) & _M
        h = _merge(h, v1)
        h = _merge(h, v2)
        h = _merge(h, v3)
        h = _merge(h, v4)
    else:
        h = (seed + _P5) & _M
    h = (h + n) & _M
    while p + 8 <= n:
        h ^= _round(0, int.from_bytes(data[p:p + 8], "little"))
        h = (_rotl(h, 27) * _P1 + _P4) & _M
        p += 8
    if p + 4 <= n:
        h ^= (int.from_bytes(data[p:p + 4], "little") * _P1) & _M
        h = (_rotl(h, 23) * _P2 + _P3) & _M
        p += 4
    while p < n:
        h ^= (data[p] * _P5) & _M
        h = (_rotl(h, 11) * _P1) & _M
        p += 1
    h ^= h >> 33
    h = (h * _P2) & _M
    h ^= h >> 29
    h = (h * _P3) & _M
    h ^= h >> 32
    return h


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
def native_available() -> bool:
    return _load_lib() is not None


def key_bytes(key: Any, bucket: str) -> bytes:
    """Canonical serialization of a bound key for hashing."""
    return msgpack.packb((key, bucket), use_bin_type=True)


def hash64(data: bytes, seed: int = 0) -> int:
    lib = _load_lib()
    if lib is not None:
        return int(lib.router_hash64(data, len(data), seed))
    return xxh64_py(data, seed)


def shard_of(key: Any, bucket: str, n_shards: int) -> int:
    if isinstance(key, int) and not isinstance(key, bool):
        return key % n_shards  # reference direct-int path
    return hash64(key_bytes(key, bucket)) % n_shards


def shard_batch(keys: Sequence[Any], buckets: Sequence[str],
                n_shards: int) -> np.ndarray:
    """Vector route: one FFI crossing for the whole batch."""
    n = len(keys)
    out = np.empty(n, np.int64)
    ints = np.empty(n, bool)
    blobs = []
    offsets = [0]
    for i, (k, b) in enumerate(zip(keys, buckets)):
        if isinstance(k, int) and not isinstance(k, bool):
            ints[i] = True
            out[i] = k % n_shards
            continue
        ints[i] = False
        kb = key_bytes(k, b)
        blobs.append(kb)
        offsets.append(offsets[-1] + len(kb))
    if blobs:
        lib = _load_lib()
        m = len(blobs)
        hashed = np.empty(m, np.int64)
        if lib is not None:
            blob = b"".join(blobs)
            lib.router_shard_batch(
                blob, np.asarray(offsets, np.uint64), m, 0, n_shards, hashed
            )
        else:
            for j, kb in enumerate(blobs):
                hashed[j] = xxh64_py(kb) % n_shards
        out[~ints] = hashed
    return out
