"""Write-ahead log: ctypes bindings to the C++ WAL + Python read side.

The durable per-shard op log replacing the reference's ``logging_vnode``
over disk_log (/root/reference/src/logging_vnode.erl:896-919): every
committed transaction's effects are framed and appended before the device
tables observe them; recovery and the incomplete-read fallback replay from
here (analogue of get_all / get_up_to_time,
/root/reference/src/logging_vnode.erl:185-228).

The native library is built lazily with g++ (shipped toolchain); a pure-
Python fallback keeps the API working where no compiler exists.
"""

from __future__ import annotations

import ctypes
import errno
import heapq
import os
import struct
import threading
import time
import zlib
from pathlib import Path
from typing import Iterator, List, Optional, Sequence, Tuple
import msgpack

from antidote_tpu import faults, native_build

_MAGIC = 0xA17D07E1
_HDR = struct.Struct("<III")

_SRC = Path(__file__).parent / "cpp" / "wal.cc"
_SO = Path(__file__).parent / "cpp" / "_wal.so"

_lib = None
_lib_tried = False


def _load_lib():
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    # use_errno: a failed append/commit must surface WHICH OS error
    # (ENOSPC vs EIO vs ...) — the read-only degraded mode keys off it
    lib = native_build.load("wal", _SRC, _SO, use_errno=True)
    if lib is not None:
        lib.wal_open.restype = ctypes.c_void_p
        lib.wal_open.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_int]
        lib.wal_append.restype = ctypes.c_int64
        lib.wal_append.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_uint32]
        lib.wal_append_raw.restype = ctypes.c_int64
        lib.wal_append_raw.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                       ctypes.c_uint64]
        lib.wal_commit.restype = ctypes.c_int
        lib.wal_commit.argtypes = [ctypes.c_void_p]
        lib.wal_sync.restype = ctypes.c_int
        lib.wal_sync.argtypes = [ctypes.c_void_p]
        lib.wal_set_sync.restype = None
        lib.wal_set_sync.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.wal_tell.restype = ctypes.c_int64
        lib.wal_tell.argtypes = [ctypes.c_void_p]
        lib.wal_truncate.restype = ctypes.c_int
        lib.wal_truncate.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.wal_close.argtypes = [ctypes.c_void_p]
    _lib = lib
    return _lib


def pack_frames(payloads: Sequence[bytes]) -> bytes:
    """Frame several record payloads into one append buffer (the same
    magic|len|crc framing :func:`replay` reads).  Packing host-side lets
    a whole commit group reach the file in ONE write syscall
    (``append_packed``) instead of one native round trip per record."""
    parts = []
    for p in payloads:
        parts.append(_HDR.pack(_MAGIC, len(p), zlib.crc32(p) & 0xFFFFFFFF))
        parts.append(p)
    return b"".join(parts)


class ShardWAL:
    """Single-writer append log for one shard (or one shard segment)."""

    def __init__(self, path: str, sync_on_commit: bool = False,
                 sync_interval_ms: int = 100):
        self.path = path
        self.sync_on_commit = sync_on_commit
        #: bytes appended but not yet covered by an fsync (the
        #: per-segment WAL depth gauge's source; approximate under
        #: sync_log=false where the native background syncer drains it)
        self.pending_bytes = 0
        lib = _load_lib()
        self._lib = lib
        self._h = None
        self._f = None
        if lib is not None:
            self._h = lib.wal_open(
                path.encode(), int(sync_on_commit), sync_interval_ms
            )
        if self._h is None:
            # pure-Python fallback
            self._f = open(path, "ab")
        # end-of-file offset, tracked HOST-SIDE after the one open-time
        # probe: the append path used to pay an lseek round trip per
        # record just to learn its own rollback point (two, with the
        # group wrapper's) — at ~75 µs a ctypes call on a small host
        # that was the measured majority of the per-append floor.  The
        # fd is append-only and single-writer, so arithmetic is exact;
        # see the caveat in :meth:`append` for the failed-truncate case.
        self._end = self._tell_fs()

    @property
    def native(self) -> bool:
        return self._h is not None

    def _faulted_append(self) -> None:
        """Fault site "wal.append" (key = file basename): error/enospc/
        io_error raise before anything hits the file — the caller sees
        exactly what a full disk / dead device produces; delay sleeps in
        the append path (a stalling volume)."""
        d = faults.hit("wal.append", key=os.path.basename(self.path))
        if d is None:
            return
        if d.action == "enospc":
            raise OSError(
                errno.ENOSPC,
                f"injected fault: wal.append {self.path}: "
                "No space left on device",
            )
        if d.action == "io_error":
            raise OSError(
                errno.EIO,
                f"injected fault: wal.append {self.path}: "
                "Input/output error",
            )
        if d.action == "error":
            raise IOError(f"injected fault: wal.append {self.path}: {d.arg}")
        if d.action == "delay" and d.arg:
            time.sleep(float(d.arg))

    def append(self, record: dict) -> None:
        """Append one framed record.  On failure the torn frame is
        truncated away (replay stops at the first torn record, so torn
        bytes followed by LATER successful appends would silently hide
        those appends from recovery).  Caveat: if that heal itself fails
        (the disk is dying), the host-tracked offset can fall behind the
        torn tail — replay's CRC guard still stops there, same as the
        pre-tracking behavior."""
        self.append_packed(pack_frames(
            [msgpack.packb(record, use_bin_type=True)]))

    def append_packed(self, buf: bytes) -> None:
        """Append a :func:`pack_frames` buffer (1..N records) in one
        write; rolls the torn tail back on failure like :meth:`append`."""
        if faults.get_injector() is not None:
            self._faulted_append()
        start = self._end
        try:
            if self._h is not None:
                ctypes.set_errno(0)
                n = self._lib.wal_append_raw(self._h, buf, len(buf))
                if n < 0:
                    raise self._native_oserror("wal_append_raw")
            else:
                self._f.write(buf)
        except BaseException:
            # best-effort heal — shrinking needs no blocks
            try:
                self.rollback_to(start)
            except OSError:
                pass
            raise
        self._end = start + len(buf)
        self.pending_bytes += len(buf)

    def flush(self) -> None:
        """Hand buffered appends to the file, for a reader of it (the
        native handle writes through; the fallback's file buffers)."""
        if self._f is not None:
            self._f.flush()

    def _tell_fs(self) -> int:
        """Real end-of-file offset from the filesystem (open-time seed
        for the host-tracked offset; includes any torn tail a crash
        left, so the first rollback point is still valid)."""
        if self._h is not None:
            n = self._lib.wal_tell(self._h)
            if n < 0:
                raise self._native_oserror("wal_tell")
            return int(n)
        self._f.flush()
        return os.fstat(self._f.fileno()).st_size

    def tell(self) -> int:
        """Current end-of-file offset (a rollback point for
        :meth:`rollback_to`) — host arithmetic, no syscall."""
        return self._end

    def rollback_to(self, off: int) -> None:
        """Discard everything appended past ``off`` (failed-group
        rollback; works on a full disk — truncation frees, never
        allocates)."""
        if self._h is not None:
            ctypes.set_errno(0)
            if self._lib.wal_truncate(self._h, int(off)) != 0:
                raise self._native_oserror("wal_truncate")
        else:
            self._f.flush()
            self._f.truncate(off)
        self.pending_bytes = max(0, self.pending_bytes - (self._end - off))
        self._end = off

    def set_sync(self, sync: bool) -> None:
        """Runtime fsync-on-commit toggle, honored by both backends."""
        self.sync_on_commit = sync
        if self._h is not None:
            self._lib.wal_set_sync(self._h, int(sync))

    def _native_oserror(self, fn: str) -> OSError:
        """OSError carrying the native call's errno (the C side returns
        -1 with errno set).  A real full disk must look exactly like the
        injected one — errno is what flips the read-only degraded mode;
        0 (lost/overwritten errno) degrades to EIO so the commit still
        fails typed rather than with an errno-less IOError."""
        err = ctypes.get_errno() or errno.EIO
        return OSError(err, f"{fn} failed for {self.path}: "
                            f"{os.strerror(err)}")

    def _faulted_fsync(self) -> None:
        """Fault site "wal.fsync" (key = file basename): delay stretches
        the fsync window (chaos scenario 13 SIGKILLs inside it);
        error/io_error fail the covering group-fsync ticket."""
        d = faults.hit("wal.fsync", key=os.path.basename(self.path))
        if d is None:
            return
        if d.action == "delay" and d.arg:
            time.sleep(float(d.arg))
        elif d.action in ("error", "io_error", "enospc"):
            err = errno.ENOSPC if d.action == "enospc" else errno.EIO
            raise OSError(err, f"injected fault: wal.fsync {self.path}")

    def commit(self) -> None:
        covered = self.pending_bytes
        if self._h is None and self._f is None:
            return  # retired segment (generation rotation) — nothing to flush
        if self._h is not None:
            ctypes.set_errno(0)
            if self._lib.wal_commit(self._h) != 0:
                raise self._native_oserror("wal_commit")
        else:
            self._f.flush()
            if self.sync_on_commit:
                os.fsync(self._f.fileno())
        # a barrier (fsynced or not) drains the depth gauge: depth
        # measures bytes between commit barriers, the write-plane's
        # in-flight durability debt.  Subtract the covered delta
        # rather than zeroing: appends are serialized under the commit
        # lock while their barrier waits, but a delta can never erase
        # bytes a racing append added after the snapshot
        self.pending_bytes -= covered

    def sync(self) -> None:
        covered = self.pending_bytes
        if self._h is None and self._f is None:
            # retired segment: a commit barrier that raced the generation
            # rotation may still submit it to the fsync coordinator — its
            # records are covered by the checkpoint image by then, so a
            # no-op is the correct durability answer (never a crash)
            return
        if faults.get_injector() is not None:
            self._faulted_fsync()
        if self._h is not None:
            ctypes.set_errno(0)
            if self._lib.wal_sync(self._h) != 0:
                raise self._native_oserror("wal_sync")
        else:
            self._f.flush()
            os.fsync(self._f.fileno())
        # delta, not zero (see commit()): the fsync covers exactly the
        # bytes that existed when it started
        self.pending_bytes -= covered

    def probe(self) -> None:
        """Raise while appends would still fail; no-op once they can
        succeed again (the read-only degraded mode's auto-recovery
        probe).  Consults the same fault site as :meth:`append` (an
        injected ENOSPC keeps the probe failing until the rule stops
        firing), then proves the volume with a real, fsynced sidecar
        write — NOT an append to the log itself, which would poison
        replay with a non-effect record."""
        if faults.get_injector() is not None:
            self._faulted_append()
        p = self.path + ".probe"
        try:
            with open(p, "wb") as f:
                f.write(b"\0" * 4096)
                f.flush()
                os.fsync(f.fileno())
        finally:
            try:
                os.remove(p)
            except OSError:
                pass

    def close(self) -> None:
        if self._h is not None:
            self._lib.wal_close(self._h)
            self._h = None
        if self._f is not None:
            self._f.close()
            self._f = None

    def __del__(self):  # best-effort
        try:
            self.close()
        except Exception:
            pass


class FsyncTicket:
    """A commit barrier's handle on the group-fsync coordinator: the ack
    holding it may release once :meth:`wait` returns — the covering
    fsync completed (or the barrier needed none)."""

    __slots__ = ("_ev", "_err")

    def __init__(self, done: bool = False):
        self._ev = threading.Event()
        self._err: Optional[BaseException] = None
        if done:
            self._ev.set()

    def done(self, err: Optional[BaseException] = None) -> None:
        self._err = err
        self._ev.set()

    def wait(self, timeout: Optional[float] = 60.0) -> None:
        if not self._ev.wait(timeout):
            raise TimeoutError("WAL group fsync stalled")
        if self._err is not None:
            raise self._err


def ready_ticket() -> FsyncTicket:
    return FsyncTicket(done=True)


class GroupFsyncCoordinator:
    """Batches fsync requests across WAL segments (group commit).

    Commit barriers submit the segments they dirtied and get a ticket;
    the coordinator thread drains every pending request at once, fsyncs
    each distinct segment ONCE, and completes all covered tickets — so
    K barriers racing in (merged batches, remote-ingress applies, the
    next group arriving while the previous one syncs) cost one fsync
    per segment, not K.  A segment whose fsync fails fails exactly the
    tickets that cover it, with the OSError (the read-only degraded
    mode keys off its errno upstream)."""

    def __init__(self, on_batch=None):
        #: called with the number of barriers covered per fsync pass
        #: (the antidote_wal_fsync_batch histogram's feed)
        self.on_batch = on_batch
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        # bounded-by: commit admission — each pending entry is a parked
        # commit barrier, and those are capped by max_commit_backlog
        self._pending: List[Tuple[FsyncTicket, list]] = []
        self._thread: Optional[threading.Thread] = None
        self._stop = False

    def submit(self, segments: list) -> FsyncTicket:
        """``segments``: ShardWAL objects to make durable up to their
        current end.  Returns the covering ticket."""
        if not segments:
            return ready_ticket()
        t = FsyncTicket()
        with self._cv:
            if self._stop:
                raise RuntimeError("fsync coordinator closed")
            self._pending.append((t, list(segments)))
            if self._thread is None:  # lazy: most logs never sync
                self._thread = threading.Thread(
                    target=self._loop, daemon=True, name="antidote-wal-fsync"
                )
                self._thread.start()
            self._cv.notify()
        return t

    def _loop(self) -> None:
        while True:
            with self._cv:
                while not self._pending and not self._stop:
                    self._cv.wait()
                batch, self._pending = self._pending, []
                if not batch and self._stop:
                    return
            self._run_batch(batch)

    def _run_batch(self, batch) -> None:
        failed: dict = {}
        synced: set = set()
        for _t, segs in batch:
            for s in segs:
                if id(s) in synced or id(s) in failed:
                    continue
                try:
                    s.sync()
                except OSError as e:
                    failed[id(s)] = e
                else:
                    synced.add(id(s))
        for t, segs in batch:
            err = next((failed[id(s)] for s in segs if id(s) in failed),
                       None)
            t.done(err)
        if self.on_batch is not None:
            try:
                self.on_batch(len(batch))
            except Exception:
                pass

    def close(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
            th = self._thread
        if th is not None:
            th.join(timeout=10)
        # fail anything that raced in behind the stop
        with self._cv:
            pending, self._pending = self._pending, []
        for t, _segs in pending:
            t.done(RuntimeError("fsync coordinator closed"))


def replay_segments(paths: Sequence[str],
                    prefix: Optional[bytes] = None) -> Iterator[dict]:
    """Merge several WAL segment files of ONE shard back into commit
    order.  Records carry a per-shard append sequence ``"q"``; legacy
    records (pre-segmentation) have none, exist only in segment 0, and
    precede every sequenced record, so positional order within segment
    0 followed by a q-merge across all segments reconstructs the exact
    append order.  ``prefix``: as :func:`replay`'s."""

    def keyed(path):
        for pos, rec in enumerate(replay(path, prefix)):
            q = rec.get("q")
            yield ((0, pos) if q is None else (1, int(q))), rec

    for _k, rec in heapq.merge(*[keyed(p) for p in paths],
                               key=lambda item: item[0]):
        yield rec


def wholly_below(path: str, floor: int) -> bool:
    """True iff every decodable record in ``path`` is covered by a
    checkpoint floor: its append sequence ``"q"`` is ≤ ``floor``, or it
    is a legacy (pre-segmentation) record with no ``"q"`` at all — those
    can only predate any checkpoint, since checkpointing builds stamp a
    sequence on every record.  The reclaim guard: a WAL file may be
    deleted only when this holds (never a raw unlink)."""
    for rec in replay(path):
        q = rec.get("q")
        if q is not None and int(q) > floor:
            return False
    return True


def replay(path: str, prefix: Optional[bytes] = None) -> Iterator[dict]:
    """Yield records from a WAL file; stops cleanly at a torn tail
    (crash mid-append), like disk_log repair-on-open.  With ``prefix``
    (the packed bytes a wanted record's payload has right after its map
    header) only the records that carry it are checked and decoded: the
    walk over the others reads their headers alone."""
    if not os.path.exists(path):
        return
    with open(path, "rb") as f:
        data = f.read() if prefix is not None else None
        if data is not None:
            off, end, hdr_n = 0, len(data), _HDR.size
            while off + hdr_n <= end:
                magic, ln, crc = _HDR.unpack_from(data, off)
                off += hdr_n
                if magic != _MAGIC or off + ln > end:
                    return
                if data.startswith(prefix, off + 1):
                    payload = data[off:off + ln]
                    if (zlib.crc32(payload) & 0xFFFFFFFF) != crc:
                        return
                    yield msgpack.unpackb(payload, raw=False,
                                          strict_map_key=False)
                off += ln
            return
        while True:
            hdr = f.read(_HDR.size)
            if len(hdr) < _HDR.size:
                return
            magic, ln, crc = _HDR.unpack(hdr)
            if magic != _MAGIC:
                return  # torn/corrupt tail
            payload = f.read(ln)
            if len(payload) < ln or (zlib.crc32(payload) & 0xFFFFFFFF) != crc:
                return
            yield msgpack.unpackb(payload, raw=False, strict_map_key=False)
