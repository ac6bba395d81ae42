"""Spans, stage records and stable device-program names: the one tracing
facility of the serving and commit paths.

Three pieces, all always on (no switch, flag or environment variable):

* :func:`span` — a host span on the profiler's own clock.  A
  ``jax.profiler.TraceAnnotation`` (TraceMe level 1) and nothing else: it
  lands in the xplane's host plane beside the device's "XLA Ops" line
  while a profiler session runs and is inert otherwise.  Per batch or
  commit group, never per request.
* :class:`StageAccumulator` / :class:`PhaseAccumulator` — what
  ``node_status()`` reports: a request's stage record (plain
  ``time.monotonic()`` stamps carried by the request) is folded into its
  path's sums by ONE call (:meth:`StageAccumulator.close`, one lock take)
  when the reply has been handed to the socket; a commit group's phase
  stamps by one call per group.
* :func:`device_program` — ``jax.jit`` under a stable name, so the
  trace's "XLA Modules" line reads ``jit_antidote_<what>`` and the ops
  inside carry a ``jax.named_scope`` of the same name.  Each program
  counts its own launches, their host time and their host operands; one
  ``jax.monitoring``
  listener counts every compilation of the process by program name
  (:func:`program_status`, ``node_status()["programs"]``).
* :class:`RoundAccumulator` — the wire server's locked worker: one call
  a round (its idle wait, its busy part, the time its thread spent off
  the CPU, the programs it launched, its phases).
"""

from __future__ import annotations

import functools
import heapq
import inspect
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
from jax import monitoring
from jax._src import core as _jax_core

#: prefix of every device program's name (module ``jit_antidote_<what>``)
PROGRAM_PREFIX = "antidote_"


def span(name: str, **ids):
    """Host span ``name`` (ids ride as the annotation's metadata) on the
    device trace's clock; use as a context manager."""
    return jax.profiler.TraceAnnotation(name, **ids)


def device_program(what: str, fn=None, **jit_kw):
    """``jax.jit(fn, **jit_kw)`` named ``antidote_<what>``: the lowered
    module is ``jit_antidote_<what>`` and every op inside sits under a
    ``jax.named_scope`` of that name.  The body, its shapes and its
    donation are the caller's, untouched.  The jitted function comes back
    wrapped (:class:`_Program`): a call counts a launch of ``<what>``;
    ``.lower`` and every other jit attribute are the jitted function's.
    Usable as a decorator."""
    name = PROGRAM_PREFIX + what

    def wrap(f):
        # wraps: jit resolves static/donated argument NAMES through the
        # signature, which has to stay the body's
        @functools.wraps(f)
        def program(*args, **kwargs):
            with jax.named_scope(name):
                return f(*args, **kwargs)

        program.__name__ = program.__qualname__ = name
        # static arguments are no operands, by name or by position
        static = frozenset(jit_kw.get("static_argnames", ()))
        at = frozenset(i for i, p in enumerate(
            inspect.signature(f).parameters) if p in static)
        return _Program(what, jax.jit(program, **jit_kw), static, at)

    return wrap if fn is None else wrap(fn)


# ---------------------------------------------------------------------------
# per-program launch and compile counters
# ---------------------------------------------------------------------------
#: fields of one program's row in ``node_status()["programs"]``
PROGRAM_FIELDS = ("launches", "call_ms", "offcpu_ms", "host_operands",
                  "compiles", "compile_ms", "cache_loads")
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_TRACE_EVENT, _BACKEND_EVENT = _COMPILE_EVENTS[0], _COMPILE_EVENTS[2]
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_counts_lock = threading.Lock()
#: what -> [launches, call seconds, off-CPU seconds, host operands]
_launches: Dict[str, list] = {}
#: what (or an eager op's name) -> [compiles, seconds, cache loads]
_compiles: Dict[str, list] = {}


class _ThreadCounts(threading.local):
    #: device-program launches made on this thread (read by the locked
    #: worker around a round: :func:`thread_launches`)
    launches = 0
    #: nesting of jaxpr traces open on this thread (an inner jit traced
    #: inside an outer one is part of the outer's time)
    trace_depth = 0
    #: the compile in progress on this thread was a persistent-cache load
    cache_hit = False


_thread = _ThreadCounts()


def thread_launches() -> int:
    """Device-program launches the calling thread has made so far."""
    return _thread.launches


class _Program:
    """A jitted device program that counts its launches: per call, the
    host wall time inside it (operands transferred, the program
    dispatched; an asynchronous dispatch does not wait for the device)
    and that time less the calling thread's CPU time over the same call
    — the time the thread waited, for the interpreter lock or a
    transfer (an estimate a call, right in the sum) — and its host
    operands: the leaves of its arguments, static ones aside, that are
    not device arrays, each a transfer of its own (one walk of the
    arguments, before the call's time starts).  One lock take a launch.
    A call made while an outer program is being traced is no launch and
    is not counted."""

    def __init__(self, what: str, jitted, static=frozenset(),
                 static_at=frozenset()):
        self._jit = jitted
        #: names of the static arguments, and their positions
        self._static, self._static_at = static, static_at
        with _counts_lock:
            self._row = _launches.setdefault(what, [0, 0.0, 0.0, 0])
        functools.update_wrapper(self, jitted)

    def _host_operands(self, args, kwargs) -> int:
        if self._static:
            args = [a for i, a in enumerate(args)
                    if i not in self._static_at]
            kwargs = {k: v for k, v in kwargs.items()
                      if k not in self._static}
        return sum(not isinstance(x, jax.Array)
                   for x in jax.tree.leaves((args, kwargs)))

    def __call__(self, *args, **kwargs):
        if not _jax_core.trace_state_clean():
            return self._jit(*args, **kwargs)
        host = self._host_operands(args, kwargs)
        t0, c0 = time.perf_counter(), time.thread_time()
        out = self._jit(*args, **kwargs)
        c1, t1 = time.thread_time(), time.perf_counter()
        _thread.launches += 1
        wall = t1 - t0
        row = self._row
        with _counts_lock:
            row[0] += 1
            row[1] += wall
            # not clamped at 0: where the thread's CPU clock ticks
            # coarsely (some hosts: 10 ms) one call reads a whole tick or
            # none, and only the sum over many calls is right
            row[2] += wall - (c1 - c0)
            row[3] += host
        return out

    def __getattr__(self, attr):
        return getattr(self._jit, attr)


def _program_key(fun_name: str) -> str:
    """``jit(antidote_<what>)`` / ``antidote_<what>`` -> ``<what>``;
    ``jit(dynamic_slice)`` -> ``dynamic_slice``."""
    if fun_name.endswith(")") and "(" in fun_name:
        fun_name = fun_name[fun_name.index("(") + 1:-1]
    if fun_name.startswith(PROGRAM_PREFIX):
        fun_name = fun_name[len(PROGRAM_PREFIX):]
    return fun_name


def _on_scalar(event: str, value, **kw) -> None:
    # a compile stage's start (jax records it as a scalar on __enter__)
    if event == _TRACE_EVENT:
        _thread.trace_depth += 1


def _on_event(event: str, **kw) -> None:
    if event == _CACHE_HIT_EVENT:
        _thread.cache_hit = True


def _on_duration(event: str, seconds: float, **kw) -> None:
    if event not in _COMPILE_EVENTS:
        return
    if event == _TRACE_EVENT:
        _thread.trace_depth = depth = max(0, _thread.trace_depth - 1)
        if depth:
            return             # inside an outer trace: counted with it
    key = _program_key(str(kw.get("fun_name", "?")))
    loaded = False
    if event == _BACKEND_EVENT:
        loaded, _thread.cache_hit = _thread.cache_hit, False
    with _counts_lock:
        row = _compiles.get(key)
        if row is None:
            row = _compiles[key] = [0, 0.0, 0]
        row[1] += seconds
        if event == _BACKEND_EVENT:
            row[2 if loaded else 0] += 1


monitoring.register_scalar_listener(_on_scalar)
monitoring.register_event_listener(_on_event)
monitoring.register_event_duration_secs_listener(_on_duration)


def program_status() -> dict:
    """``{<what>: {launches, call_ms, offcpu_ms, host_operands, compiles,
    compile_ms, cache_loads}, "total": {...}}`` since the process
    started: every device program's launches (``host_operands``: the
    host arrays and scalars they were handed, summed), and every
    compilation of the process (eager operations under their own names)
    — a persistent-cache load is counted under ``cache_loads``, its time
    under ``compile_ms``."""
    with _counts_lock:
        launches = {k: tuple(v) for k, v in _launches.items()}
        compiles = {k: tuple(v) for k, v in _compiles.items()}
    out = {}
    total = dict.fromkeys(PROGRAM_FIELDS, 0)
    for key in sorted(set(launches) | set(compiles)):
        n, call_s, off_s, host = launches.get(key, (0, 0.0, 0.0, 0))
        nc, comp_s, loads = compiles.get(key, (0, 0.0, 0))
        row = {"launches": n, "call_ms": call_s * 1e3,
               "offcpu_ms": off_s * 1e3, "host_operands": host,
               "compiles": nc, "compile_ms": comp_s * 1e3,
               "cache_loads": loads}
        if any(row.values()):
            out[key] = row
            for f in PROGRAM_FIELDS:
                total[f] += row[f]
    out["total"] = total
    return out


# ---------------------------------------------------------------------------
# per-request stage records (the read path and every other wire request)
# ---------------------------------------------------------------------------
#: the stamps of a stage record, in path order; a stage is named after the
#: stamp that closes it and runs from the previous stamp the request took
STAMPS = ("arrive", "taken", "submit", "dequeued", "launched", "wb_start",
          "synced", "ready", "sent")
#: stage closed by each stamp after the first.  The stamp ``ready`` closes
#: ``wb_host`` when the writeback stage synced the device for this request
#: and ``exec`` otherwise (the locked worker's commit group or read, or a
#: request served whole on its connection thread).
STAGE_OF = {"taken": "cross", "submit": "decode", "dequeued": "parked",
            "launched": "launch", "wb_start": "wb_wait",
            "synced": "device_wait", "ready": "wb_host", "sent": "reply"}
STAGES = ("cross", "decode", "parked", "launch", "wb_wait", "device_wait",
          "wb_host", "exec", "reply")
_STAGE_IDX = {s: i for i, s in enumerate(STAGES)}
_SYNCED = STAMPS.index("synced")
_EXEC, _WB_HOST = _STAGE_IDX["exec"], _STAGE_IDX["wb_host"]
#: per stamp index > 0, the index of its stage in STAGES
_CLOSES = [None] + [_STAGE_IDX[STAGE_OF[s]] for s in STAMPS[1:]]
assert _CLOSES == [None, 0, 1, 2, 3, 4, 5, 6, 8]  # StageAccumulator.close
SLOW_KEPT = 8


def _new_path() -> list:
    #: [per-stage sums (s), per-stage counts, total sum, count]
    return [[0.0] * len(STAGES), [0] * len(STAGES), 0.0, 0]


class StageAccumulator:
    """Per-path sums of request stages, and the slowest few records since
    the last status read.  ``close`` is the one call a request makes,
    after its reply left: one lock take per request — or, for the
    requests one stage answered together, per ``close_many``."""

    def __init__(self):
        self._lock = threading.Lock()
        self._paths: Dict[str, list] = {}
        #: min-heap of (total, tiebreak, path, rid, batch, stamps)
        self._slow: List[tuple] = []
        self._n = 0

    def close(self, path: str, rid: Tuple[int, int], batch_id: int,
              stamps: Sequence[float]) -> float:
        """Fold one finished request.  ``stamps`` follows :data:`STAMPS`
        (0.0 = not taken; the first and last are always taken).  Returns
        the request's total (arrive → sent) in seconds."""
        with self._lock:
            return self._fold(path, rid, batch_id, stamps)

    def close_many(self, records) -> List[float]:
        """``close`` for the requests a stage answered together —
        ``[(path, rid, batch_id, stamps)]`` — under ONE lock take."""
        with self._lock:
            return [self._fold(*r) for r in records]

    def _fold(self, path, rid, batch_id, stamps) -> float:
        (t_arrive, t_taken, t_submit, t_dequeued, t_launched, t_wb_start,
         t_synced, t_ready, t_sent) = stamps
        total = t_sent - t_arrive
        p = self._paths.get(path)
        if p is None:
            p = self._paths[path] = _new_path()
        s, c = p[0], p[1]
        # unrolled over STAMPS (this runs once per request): each
        # stamp taken closes its stage, from the previous one taken
        prev = t_arrive
        if t_taken:
            s[0] += t_taken - prev; c[0] += 1; prev = t_taken
        if t_submit:
            s[1] += t_submit - prev; c[1] += 1; prev = t_submit
        if t_dequeued:
            s[2] += t_dequeued - prev; c[2] += 1; prev = t_dequeued
        if t_launched:
            s[3] += t_launched - prev; c[3] += 1; prev = t_launched
        if t_wb_start:
            s[4] += t_wb_start - prev; c[4] += 1; prev = t_wb_start
        if t_synced:
            s[5] += t_synced - prev; c[5] += 1; prev = t_synced
        if t_ready:
            i = _WB_HOST if t_synced else _EXEC
            s[i] += t_ready - prev; c[i] += 1; prev = t_ready
        s[8] += t_sent - prev; c[8] += 1
        p[2] += total
        p[3] += 1
        slow = self._slow
        if len(slow) < SLOW_KEPT:
            self._n += 1
            heapq.heappush(slow, (total, self._n, path, rid, batch_id,
                                  tuple(stamps)))
        elif total > slow[0][0]:
            self._n += 1
            heapq.heapreplace(slow, (total, self._n, path, rid,
                                     batch_id, tuple(stamps)))
        return total

    def status(self) -> dict:
        """``{"paths": {path: {stage: {sum_ms, count}, "total": ...}},
        "slow_requests": [...]}``; reading it starts a new slow window."""
        with self._lock:
            paths = {k: ([*p[0]], [*p[1]], p[2], p[3])
                     for k, p in self._paths.items()}
            slow, self._slow = self._slow, []
        out: dict = {"paths": {}, "slow_requests": []}
        for path, (sums, counts, total, n) in sorted(paths.items()):
            blk = {s: {"sum_ms": sums[i] * 1e3, "count": counts[i]}
                   for i, s in enumerate(STAGES) if counts[i]}
            blk["total"] = {"sum_ms": total * 1e3, "count": n}
            out["paths"][path] = blk
        slow.sort(key=lambda r: r[0], reverse=True)
        for total, _n, path, rid, batch_id, stamps in slow:
            stages, prev = {}, stamps[0]
            for idx, t in zip(_CLOSES, stamps):
                if t and idx is not None:
                    if idx == _WB_HOST and not stamps[_SYNCED]:
                        idx = _EXEC
                    stages[STAGES[idx]] = round((t - prev) * 1e3, 3)
                    prev = t
            out["slow_requests"].append({
                "id": [int(rid[0]), int(rid[1])], "path": path,
                "batch": int(batch_id), "total_ms": round(total * 1e3, 3),
                "stages_ms": stages,
            })
        return out


# ---------------------------------------------------------------------------
# per-group commit phases
# ---------------------------------------------------------------------------
#: phases of one commit group inside the commit lock, in order: they sum
#: to the group's lock-held time (``group``, = antidote_commit_seconds)
COMMIT_PHASES = ("certify", "wal_append", "scatter", "fsync_wait",
                 "listeners", "publish")
#: reported beside them: ``freeze`` is the freeze_serving dispatch inside
#: ``publish``; ``mirror_invalidate`` the invalidation of the group's keys
#: in the native front end's mirror, inside ``certify`` (counted only on a
#: node that has the mirror)
EXTRA_PHASES = ("freeze", "mirror_invalidate")


class PhaseAccumulator:
    """Sums of commit-group phases; one ``add_group`` call per group."""

    def __init__(self):
        self._lock = threading.Lock()
        self._sums = {p: 0.0 for p in COMMIT_PHASES + EXTRA_PHASES}
        self._counts = {p: 0 for p in COMMIT_PHASES + EXTRA_PHASES}

    def add_group(self, stamps: Sequence[float], freeze_s: float,
                  mirror_invalidate_s: Optional[float] = None) -> None:
        """``stamps``: lock taken, then the end of each of
        :data:`COMMIT_PHASES` (a phase the group skipped repeats the stamp
        before it, so the phases always sum to last − first);
        ``mirror_invalidate_s``: None when the group told no mirror."""
        with self._lock:
            prev = stamps[0]
            for p, t in zip(COMMIT_PHASES, stamps[1:]):
                self._sums[p] += t - prev
                self._counts[p] += 1
                prev = t
            self._sums["freeze"] += freeze_s
            self._counts["freeze"] += 1
            if mirror_invalidate_s is not None:
                self._sums["mirror_invalidate"] += mirror_invalidate_s
                self._counts["mirror_invalidate"] += 1

    def status(self) -> dict:
        with self._lock:
            return {p: {"sum_ms": self._sums[p] * 1e3,
                        "count": self._counts[p]}
                    for p in self._sums}


# ---------------------------------------------------------------------------
# rounds of the wire server's locked worker
# ---------------------------------------------------------------------------
#: phases of one round, in order, summing to its busy part: the wait for
#: the dispatch lock, the merged transaction read, the commit merge's
#: staging passes, its ``commit_transactions_group`` calls, their results
#: fanned out, the static reads handed back by the epoch plane
ROUND_PHASES = ("lock", "txn_read", "stage", "group", "ack", "read")


class RoundAccumulator:
    """Sums of the locked worker's rounds; one ``add_round`` a round."""

    def __init__(self):
        self._lock = threading.Lock()
        #: rounds, idle s, busy s, off-CPU s, programs launched
        self._totals = [0, 0.0, 0.0, 0.0, 0]
        self._sums = [0.0] * len(ROUND_PHASES)
        self._counts = [0] * len(ROUND_PHASES)

    def add_round(self, idle_s: float, busy_s: float, offcpu_s: float,
                  programs: int, phases: Sequence[Optional[float]]) -> None:
        """``idle_s``: from the end of the previous round to this one's
        dequeue; ``busy_s``: dequeue to the round's last answer;
        ``phases``: seconds per :data:`ROUND_PHASES`, None where the round
        did not meet the phase."""
        with self._lock:
            t = self._totals
            t[0] += 1
            t[1] += idle_s
            t[2] += busy_s
            t[3] += offcpu_s
            t[4] += programs
            for i, v in enumerate(phases):
                if v is not None:
                    self._sums[i] += v
                    self._counts[i] += 1

    def status(self) -> dict:
        with self._lock:
            n, idle, busy, off, programs = self._totals
            sums, counts = [*self._sums], [*self._counts]
        return {"rounds": n, "idle_ms": idle * 1e3, "busy_ms": busy * 1e3,
                "offcpu_ms": off * 1e3, "programs": programs,
                "phases": {p: {"sum_ms": sums[i] * 1e3, "count": counts[i]}
                           for i, p in enumerate(ROUND_PHASES)}}
