"""ctypes binding for the native serving front-end (cpp/frontend.cc).

One C++ epoll thread owns the client listen socket: accept, per-conn
read buffers, 4-byte framing, hot-read decode, admission (the
overload.py global/per-host caps + retry hints, natively) and the
whole-batch snapshot-cache fast path all run off the GIL.  Python sees
only cache misses, writes, interactive txns and apb-dialect frames via
one packed batch-drain crossing per wakeup (``take_batch`` — the
``pump_take_batch`` discipline).

The mirror protocol (kv.py and the txn manager push; the rule that keeps
the mirror coherent under writes is the mirror's own — ``struct
Frontend`` in cpp/frontend.cc — and no caller checks anything):

* ``fill(key, bucket, type_name, value, epoch_id)`` — pushed wherever
  Python itself fills/serves from the snapshot cache (the whole-batch
  bottom path, a re-proved entry); ``fill_many(entries, epoch_id)`` —
  a writeback launch's gathered keys in one call (kv.py
  ``snapshot_cache_fill``).  The mirror takes an entry only if
  ``epoch_id`` is the epoch it serves and the key was not invalidated
  in it; what it refuses is a miss later, nothing else;
* ``invalidate_many(keys)`` — pushed EAGERLY under the commit lock, one
  call for a commit group's written keys (kv.py
  ``_apply_effect_groups_inner``); ``invalidate(key, bucket)`` from
  ``drop_cached_value`` / ``mark_epoch_fallback``;
* ``advance(epoch_id, vc, clockless_ok)`` — under the commit lock, in
  the critical section that published the epoch (txn/manager.py
  ``_native_epoch_published``): every entry survives (every mutation
  since it was taken invalidated its key), the invalidation marks of
  the epoch left behind drop;
* ``reset()`` — ``drop_serving_epoch``: native serving disabled until
  the next advance.

Loading failure falls back to the Python socketserver plane.
"""

from __future__ import annotations

import ctypes
import logging
import os
import pathlib
from typing import Optional

import msgpack

from antidote_tpu import faults
from antidote_tpu.proto.codec import encode_value

log = logging.getLogger(__name__)

_DIR = pathlib.Path(__file__).parent / "cpp"
_SRC = _DIR / "frontend.cc"
_SO = _DIR / "_frontend.so"
#: entries the C++ mirror holds; past it a fill evicts an arbitrary one
_MIRROR_CAP = 1 << 18

_lib = None
_lib_tried = False


def _fallback(reason: Optional[str]) -> None:
    if reason is not None:
        log.warning("native frontend unavailable (%s); falling back to "
                    "the Python socketserver plane", reason)
    try:
        from antidote_tpu.obs.metrics import net_metrics

        net_metrics().frontend_fallback.inc()
    except Exception:
        pass
    return None


def _load_lib():
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    from antidote_tpu import native_build

    lib = native_build.load("frontend", _SRC, _SO)
    if lib is not None:
        lib.frontend_create.restype = ctypes.c_void_p
        lib.frontend_create.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_long,
            ctypes.c_long, ctypes.c_long,
        ]
        lib.frontend_port.restype = ctypes.c_int
        lib.frontend_port.argtypes = [ctypes.c_void_p]
        lib.frontend_take_batch.restype = ctypes.c_long
        lib.frontend_take_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_long,
            ctypes.POINTER(ctypes.c_long), ctypes.c_long, ctypes.c_long,
        ]
        lib.frontend_send.restype = None
        lib.frontend_send.argtypes = [
            ctypes.c_void_p, ctypes.c_long, ctypes.c_char_p,
            ctypes.c_long, ctypes.c_long,
        ]
        # a second handle on the same library whose calls KEEP the GIL:
        # send_many, fill_many, invalidate_many, advance and native_hits never block
        # (they take the front end's mutex for a few microseconds), and
        # the stage that calls them has a batch to finish (the last three
        # hold the commit lock) — giving the GIL up around the call
        # would put it back in the queue for it.  Nothing that runs
        # under that mutex calls back into Python (the io thread never
        # needs the GIL), so holding the GIL across it cannot deadlock
        keeping_gil = ctypes.PyDLL(str(_SO))
        send_many = keeping_gil.frontend_send_many
        send_many.restype = None
        send_many.argtypes = [
            ctypes.c_void_p, ctypes.c_long, ctypes.POINTER(ctypes.c_long),
            ctypes.c_char_p,
        ]
        lib.send_many_keeping_gil = send_many
        fill_many = keeping_gil.frontend_fill_many
        fill_many.restype = None
        fill_many.argtypes = [
            ctypes.c_void_p, ctypes.c_long, ctypes.POINTER(ctypes.c_long),
            ctypes.c_char_p, ctypes.c_long,
        ]
        lib.fill_many_keeping_gil = fill_many
        invalidate_many = keeping_gil.frontend_invalidate_many
        invalidate_many.restype = None
        invalidate_many.argtypes = [
            ctypes.c_void_p, ctypes.c_long, ctypes.POINTER(ctypes.c_long),
            ctypes.c_char_p,
        ]
        lib.invalidate_many_keeping_gil = invalidate_many
        advance = keeping_gil.frontend_advance
        advance.restype = None
        advance.argtypes = [
            ctypes.c_void_p, ctypes.c_long, ctypes.c_char_p,
            ctypes.c_long, ctypes.c_int,
        ]
        lib.advance_keeping_gil = advance
        native_hits = keeping_gil.frontend_native_hits
        native_hits.restype = ctypes.c_long
        native_hits.argtypes = [ctypes.c_void_p]
        lib.native_hits_keeping_gil = native_hits
        lib.frontend_close_conn.restype = None
        lib.frontend_close_conn.argtypes = [ctypes.c_void_p, ctypes.c_long]
        lib.frontend_fill.restype = None
        lib.frontend_fill.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_long,
            ctypes.c_char_p, ctypes.c_long, ctypes.c_char_p,
            ctypes.c_long, ctypes.c_long,
        ]
        lib.frontend_mirror_reset.restype = None
        lib.frontend_mirror_reset.argtypes = [ctypes.c_void_p]
        lib.frontend_set_fast_serve.restype = None
        lib.frontend_set_fast_serve.argtypes = [ctypes.c_void_p,
                                                ctypes.c_int]
        lib.frontend_set_clockless_ok.restype = None
        lib.frontend_set_clockless_ok.argtypes = [ctypes.c_void_p,
                                                  ctypes.c_int]
        lib.frontend_stats.restype = None
        lib.frontend_stats.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_long), ctypes.c_int,
        ]
        lib.frontend_stop.restype = None
        lib.frontend_stop.argtypes = [ctypes.c_void_p]
        lib.frontend_free.restype = None
        lib.frontend_free.argtypes = [ctypes.c_void_p]
    _lib = lib
    return _lib


def _packb(v) -> bytes:
    # the SAME packer settings as codec.encode — fragment-level byte
    # parity with the Python reply path depends on it
    return msgpack.packb(v, use_bin_type=True)


class NativeFrontend:
    """Owns the client listen socket; yields (conn_id, kind, aux,
    payload) frames.  kind 0 = conn closed, 1 = admitted frame (aux =
    how many of the connection's earlier frames Python still owes a
    reply: 0 = nothing of it is in Python), 2 = admission-shed frame
    (aux carries the retry hint); every frame carries the io thread's
    arrival stamp."""

    _BATCH = 512

    K_CONN_DROP = 0
    K_FRAME = 1
    K_SHED = 2

    #: cross_wait_us/cross_frames: frame complete on the io thread ->
    #: taken by Python, over admitted frames; send_wait_us/send_frames:
    #: ``send`` -> the reply's last byte written to the socket;
    #: send_calls: ``send`` + ``send_many`` calls (a writeback batch's
    #: replies are one); fill_calls: ``fill`` + ``fill_many`` calls (a
    #: writeback launch's gathered keys are one), fill_keys: the entries
    #: they carried, fill_refused: those of them the mirror's rule turned
    #: away (stamped with another epoch than the one served, or their key
    #: invalidated in it); invalidate_calls / invalidate_keys: the
    #: store's ``invalidate_many`` calls (a commit group's written keys
    #: are one) and the keys they named; mirror_marks: keys invalidated
    #: in the epoch being served (dropped at every advance)
    STAT_FIELDS = ("accepted", "closed", "frames", "native_hits",
                   "hit_objects", "sheds", "forwarded", "drains",
                   "mirror_size", "in_flight", "open_conns", "bad_frames",
                   "cross_wait_us", "cross_frames", "send_wait_us",
                   "send_frames", "send_calls", "fill_calls", "fill_keys",
                   "fill_refused", "invalidate_calls", "invalidate_keys",
                   "mirror_marks")
    #: longs per frame in the take_batch descriptor
    _DESC = 5

    def __init__(self, lib, h):
        self._lib = lib
        self._h = h
        self._buf = ctypes.create_string_buffer(1 << 20)
        self._descs = (ctypes.c_long * (self._DESC * self._BATCH))()

    @staticmethod
    def create(host: str, port: int, max_connections: int,
               max_in_flight: int,
               max_per_host: int) -> Optional["NativeFrontend"]:
        if os.environ.get("ANTIDOTE_NATIVE_FRONTEND", "on") == "off":
            return None
        if faults.hit("native_frontend.load") is not None:
            return _fallback(None)  # injected load failure (chaos tests)
        lib = _load_lib()
        if lib is None:
            return _fallback("compile/load failed")
        h = lib.frontend_create(host.encode(), int(port),
                                int(max_connections), int(max_in_flight),
                                int(max_per_host), _MIRROR_CAP)
        if not h:
            return _fallback(f"bind/listen on {host}:{port} failed")
        return NativeFrontend(lib, h)

    # -- serving plane --------------------------------------------------
    @property
    def port(self) -> int:
        return int(self._lib.frontend_port(self._h))

    def take_batch(self, timeout_ms: int) -> list:
        """Drain up to _BATCH crossings — [(conn_id, kind, aux,
        payload, t_arrive)], [] after timeout or once stopped.
        ``t_arrive`` is the io thread's ``time.monotonic()`` (seconds)
        when the frame was complete."""
        h = self._h  # capture: close() may null the handle concurrently
        if h is None:
            return []
        n = self._lib.frontend_take_batch(h, self._buf,
                                          len(self._buf), self._descs,
                                          self._BATCH, int(timeout_ms))
        if n == -2:
            # head frame alone exceeds the scratch buffer: grow, retake
            need = int(self._descs[2])
            self._buf = ctypes.create_string_buffer(need + 1024)
            return self.take_batch(timeout_ms)
        if n <= 0:
            return []
        d = self._descs[:self._DESC * n]
        total = sum(d[2::self._DESC])
        raw = ctypes.string_at(self._buf, total)
        out = []
        off = 0
        for i in range(0, len(d), self._DESC):
            ln = d[i + 2]
            out.append((d[i], d[i + 1], d[i + 3], raw[off:off + ln],
                        d[i + 4] * 1e-6))
            off += ln
        return out

    def send(self, conn_id: int, buf: bytes, admitted: int) -> None:
        """Queue one framed reply (b"" = account only); releases
        ``admitted`` admission slots."""
        h = self._h
        if h is None:
            return
        self._lib.frontend_send(h, int(conn_id), buf, len(buf),
                                int(admitted))

    def send_many(self, replies) -> None:
        """``send`` for a batch — ``[(conn_id, buf, admitted)]`` — in ONE
        native call: one lock take and one io-thread wakeup for all of
        them, per reply the accounting of ``send``."""
        h = self._h
        if h is None or not replies:
            return
        flat = []
        for conn_id, buf, admitted in replies:
            flat += (conn_id, len(buf), admitted)
        descs = (ctypes.c_long * len(flat))(*flat)
        self._lib.send_many_keeping_gil(
            h, len(replies), descs, b"".join([r[1] for r in replies]))

    def close_conn(self, conn_id: int) -> None:
        h = self._h
        if h is not None:
            self._lib.frontend_close_conn(h, int(conn_id))

    # -- mirror protocol ------------------------------------------------
    @staticmethod
    def _mirror_key(key, bucket) -> Optional[bytes]:
        try:
            return _packb(key) + _packb(bucket)
        except Exception:
            return None  # unpackable key shapes are simply never mirrored

    @classmethod
    def _mirror_entry(cls, key, bucket, type_name: str, value):
        """(key, type fragment, value) as the mirror stores them, or None
        for a key or value that does not pack (never mirrored)."""
        k = cls._mirror_key(key, bucket)
        if k is None:
            return None
        try:
            # the SAME wire shape the Python reply path produces
            # (tuple-keyed CRDT maps ride as tagged pair lists) — the
            # byte-parity contract depends on packing encode_value(v),
            # not v
            val = _packb(encode_value(value))
        except Exception:
            return None
        return k, _packb(type_name), val

    def fill(self, key, bucket, type_name: str, value, epoch_id: int):
        h = self._h
        if h is None:
            return
        ent = self._mirror_entry(key, bucket, type_name, value)
        if ent is None:
            return
        k, t, val = ent
        self._lib.frontend_fill(h, k, len(k), t, len(t), val,
                                len(val), int(epoch_id))

    def fill_many(self, entries, epoch_id: int) -> None:
        """``fill`` for a batch — ``[(key, bucket, type_name, value)]``,
        all at ``epoch_id`` — in ONE native call: one lock take for all
        of them, the GIL kept.  An entry that does not pack is skipped
        alone."""
        h = self._h
        if h is None:
            return
        lens = []
        frags = []
        for key, bucket, type_name, value in entries:
            ent = self._mirror_entry(key, bucket, type_name, value)
            if ent is not None:
                lens += map(len, ent)
                frags += ent
        if not frags:
            return
        descs = (ctypes.c_long * len(lens))(*lens)
        self._lib.fill_many_keeping_gil(h, len(lens) // 3, descs,
                                        b"".join(frags), int(epoch_id))

    def invalidate_many(self, keys) -> None:
        """Invalidate ``[(key, bucket)]`` in ONE native call: one lock
        take for all of them, the GIL kept.  A key that does not pack was
        never mirrored."""
        h = self._h
        if h is None:
            return
        packed = [k for k in (self._mirror_key(key, bucket)
                              for key, bucket in keys) if k is not None]
        if not packed:
            return
        lens = (ctypes.c_long * len(packed))(*map(len, packed))
        self._lib.invalidate_many_keeping_gil(h, len(packed), lens,
                                              b"".join(packed))

    def invalidate(self, key, bucket) -> None:
        self.invalidate_many([(key, bucket)])

    def advance(self, epoch_id: int, vc_list, clockless_ok: bool) -> None:
        """Serve at ``epoch_id`` from here on.  The caller holds the
        commit lock and has just published that epoch (the mirror's rule
        rests on it); the GIL is kept."""
        h = self._h
        if h is None:
            return
        frag = _packb([int(x) for x in vc_list])
        self._lib.advance_keeping_gil(h, int(epoch_id), frag, len(frag),
                                      1 if clockless_ok else 0)

    def native_hits(self) -> int:
        """Reads answered from the mirror so far (``stats()["native_hits"]``
        alone, the GIL kept): epoch-plane reads that Python never sees."""
        h = self._h
        return 0 if h is None else int(self._lib.native_hits_keeping_gil(h))

    def reset(self) -> None:
        h = self._h
        if h is not None:
            self._lib.frontend_mirror_reset(h)

    def set_fast_serve(self, on: bool) -> None:
        h = self._h
        if h is not None:
            self._lib.frontend_set_fast_serve(h, 1 if on else 0)

    def set_clockless_ok(self, on: bool) -> None:
        h = self._h
        if h is not None:
            self._lib.frontend_set_clockless_ok(h, 1 if on else 0)

    # -- observability / lifecycle -------------------------------------
    def stats(self) -> dict:
        h = self._h
        if h is None:
            return {}
        out = (ctypes.c_long * len(self.STAT_FIELDS))()
        self._lib.frontend_stats(h, out, len(self.STAT_FIELDS))
        return {f: int(v) for f, v in zip(self.STAT_FIELDS, out)}

    def close(self) -> None:
        if self._h is not None:
            h, self._h = self._h, None
            self._lib.frontend_stop(h)
            self._lib.frontend_free(h)
