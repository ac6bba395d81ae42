"""ctypes binding for the native inter-DC stream pump (cpp/pump.cc).

One C++ epoll thread owns every subscription socket: kernel reads and
frame assembly happen in native code (the role libzmq's io threads play
for the reference, /root/reference/src/inter_dc_sub.erl); Python drains
whole frames.  Compiled on first use like the WAL and router; loading
failure falls back to the per-subscription Python reader threads.
"""

from __future__ import annotations

import ctypes
import logging
import os
import pathlib
from typing import Optional, Tuple

from antidote_tpu import faults

log = logging.getLogger(__name__)


def _fallback(reason: Optional[str]) -> None:
    """Count + log a native-plane fallback; returns None (the create()
    contract for "use the Python readers")."""
    if reason is not None:
        log.warning("native pump unavailable (%s); falling back to "
                    "Python reader threads", reason)
    try:
        from antidote_tpu.obs.metrics import net_metrics

        net_metrics().pump_fallback.inc()
    except Exception:
        pass
    return None


_DIR = pathlib.Path(__file__).parent / "cpp"
_SRC = _DIR / "pump.cc"
_SO = _DIR / "_pump.so"

_lib = None
_lib_tried = False


def _load_lib():
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    from antidote_tpu import native_build

    lib = native_build.load("pump", _SRC, _SO)
    if lib is not None:
        lib.pump_new.restype = ctypes.c_void_p
        lib.pump_new.argtypes = []
        lib.pump_add.restype = ctypes.c_int
        lib.pump_add.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                 ctypes.c_long]
        lib.pump_take.restype = ctypes.c_long
        lib.pump_take.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_long,
            ctypes.POINTER(ctypes.c_long), ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_long), ctypes.c_int,
        ]
        lib.pump_take_batch.restype = ctypes.c_long
        lib.pump_take_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_long,
            ctypes.POINTER(ctypes.c_long), ctypes.c_long, ctypes.c_int,
        ]
        lib.pump_queued.restype = ctypes.c_long
        lib.pump_queued.argtypes = [ctypes.c_void_p]
        lib.pump_free.restype = None
        lib.pump_free.argtypes = [ctypes.c_void_p]
    _lib = lib
    return _lib


class NativePump:
    """Owns detached socket fds; yields (tag, kind, payload) frames."""

    _BATCH = 512

    def __init__(self, lib):
        self._lib = lib
        self._h = lib.pump_new()
        self._buf = ctypes.create_string_buffer(1 << 20)
        self._descs = (ctypes.c_long * (3 * self._BATCH))()

    #: frame kind queued by the native loop when a subscription socket
    #: drops (EOF/read error/corrupt frame) — carries the tag, empty
    #: payload.  The fabric resubscribes with backoff on seeing it.
    K_CONN_DROP = 0

    @staticmethod
    def create() -> Optional["NativePump"]:
        if os.environ.get("ANTIDOTE_NATIVE_PUMP", "on") == "off":
            return None
        if faults.hit("native_pump.load") is not None:
            return _fallback(None)  # injected load failure (chaos tests)
        lib = _load_lib()
        if lib is None:
            return _fallback("compile/load failed")
        p = NativePump(lib)
        if p._h is None:
            # pump_new() failed (NULL → ctypes None — fd exhaustion or a
            # blocked epoll/eventfd syscall).  A pump with no epoll loop
            # would close every detached fd handed to add(), silently
            # blackholing each subscription; report the failure so
            # TcpFabric.subscribe keeps the Python reader threads.
            return _fallback("pump_new returned NULL")
        return p

    def add(self, fd: int, tag: int) -> None:
        """Register a connected socket fd; the pump OWNS it from here
        (pass ``sock.detach()``)."""
        if self._h is None:
            os.close(fd)  # closed pump: don't leak the detached fd
            return
        self._lib.pump_add(self._h, fd, tag)

    def take(self, timeout_ms: int) -> Optional[Tuple[int, int, bytes]]:
        if self._h is None:
            return None  # closed concurrently (fabric teardown)
        tag = ctypes.c_long()
        kind = ctypes.c_int()
        need = ctypes.c_long()
        n = self._lib.pump_take(self._h, self._buf,
                                len(self._buf), ctypes.byref(tag),
                                ctypes.byref(kind), ctypes.byref(need),
                                int(timeout_ms))
        if n == -2:
            # frame larger than the scratch buffer: grow and retake
            self._buf = ctypes.create_string_buffer(int(need.value) + 1024)
            return self.take(timeout_ms)
        if n < 0:
            return None
        return (int(tag.value), int(kind.value),
                ctypes.string_at(self._buf, n))

    def take_batch(self, timeout_ms: int) -> list:
        """Drain up to _BATCH frames in one native crossing —
        [(tag, kind, payload)], [] after timeout."""
        if self._h is None:
            return []  # closed concurrently (fabric teardown)
        n = self._lib.pump_take_batch(self._h, self._buf, len(self._buf),
                                      self._descs, self._BATCH,
                                      int(timeout_ms))
        if n <= 0:
            # nothing, or the head frame alone exceeds the scratch
            # buffer — the single-frame path grows the buffer
            if n == 0 and self.queued() > 0:
                f = self.take(0)
                return [f] if f is not None else []
            return []
        d = self._descs
        total = sum(d[i * 3 + 2] for i in range(n))
        # copy only the bytes actually written, not the whole scratch
        # buffer (it only ever grows)
        raw = ctypes.string_at(self._buf, total)
        out = []
        off = 0
        for i in range(n):
            ln = d[i * 3 + 2]
            out.append((int(d[i * 3]), int(d[i * 3 + 1]),
                        raw[off:off + ln]))
            off += ln
        return out

    def queued(self) -> int:
        if self._h is None:
            return 0
        return int(self._lib.pump_queued(self._h))

    def close(self) -> None:
        if self._h is not None:
            self._lib.pump_free(self._h)
            self._h = None
