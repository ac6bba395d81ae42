#!/usr/bin/env python
"""Stdlib static-analysis gate (no third-party linter ships in this
image; ruff/mypy configs in pyproject.toml cover richer CI hosts).

Checks (each one has caught a real bug class in this codebase's history):
  * syntax: every file must compile (the round-4 advisor patch cycle
    shipped an IndentationError mid-session);
  * unused imports (module scope);
  * duplicate top-level / class-level function definitions (a paste slip
    silently shadows the first definition);
  * mutable default arguments;
  * bare ``except:`` (swallows KeyboardInterrupt/SystemExit);
  * broad except-and-continue inside ``while`` loops (a thread loop
    that swallows every exception and spins on is a silently-dead
    subsystem — the failure class the supervised ThreadLoop exists to
    prevent; surface the error or supervise the loop instead);
  * unbounded queue construction in the overload-protected planes
    (``proto/``, ``interdc/``, ``txn/``): ``queue.Queue()`` without a
    maxsize, ``collections.deque()`` without a maxlen, and
    queue-factory ``defaultdict``s must either carry an explicit bound
    or a ``# bounded-by: <reason>`` annotation within the three lines
    above — saturation must shed, never buffer without limit (PR 4);
  * file deletion (``os.remove``/``os.unlink``/``rmtree``) outside
    ``antidote_tpu/log/`` without a ``# reclaim-ok:`` note — WAL and
    checkpoint files are reclaimed only through the guarded floor APIs
    (ISSUE 8);
  * serving-epoch publishes in ``antidote_tpu/interdc/`` (the
    follower/replica plane) that bypass the applied-VC stamp without a
    ``# vc-stamped:`` note — a follower publishing an epoch ahead of
    its applied clock silently violates causality (ISSUE 9).

Usage: python tools/lint.py [paths...]   (default: antidote_tpu tests
chip_smoke.py __graft_entry__.py tools)
"""

from __future__ import annotations

import ast
import os
import sys


def iter_py(paths):
    for p in paths:
        if os.path.isfile(p) and p.endswith(".py"):
            yield p
        elif os.path.isdir(p):
            for root, _dirs, files in os.walk(p):
                for f in files:
                    if f.endswith(".py"):
                        yield os.path.join(root, f)


def used_names(tree: ast.AST):
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            names.add(node.id)
        elif isinstance(node, ast.Constant) and isinstance(node.value, str):
            # names listed in __all__ (and doctest-ish strings) count as
            # used — re-export surfaces are intentional
            if node.value.isidentifier():
                names.add(node.value)
    return names


def check_file(path: str):
    problems = []
    with open(path) as f:
        src = f.read()
    try:
        tree = ast.parse(src, path)
    except SyntaxError as e:
        return [f"{path}:{e.lineno}: syntax error: {e.msg}"]
    used = used_names(tree)
    # "# noqa" on the import line opts out (re-export modules etc.)
    lines = src.splitlines()

    def noqa(lineno: int) -> bool:
        return "noqa" in lines[lineno - 1]

    is_init = os.path.basename(path) == "__init__.py"
    for node in tree.body:
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            if is_init:
                continue  # package __init__: re-export surface
            if isinstance(node, ast.ImportFrom) and node.module == "__future__":
                continue
            for alias in node.names:
                if alias.name == "*":
                    continue
                bound = (alias.asname or alias.name).split(".")[0]
                if bound not in used and not noqa(node.lineno):
                    problems.append(
                        f"{path}:{node.lineno}: unused import '{bound}'"
                    )
    for scope in ast.walk(tree):
        if isinstance(scope, (ast.Module, ast.ClassDef)):
            seen = {}
            body = scope.body
            for node in body:
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    if node.name in seen and not noqa(node.lineno):
                        problems.append(
                            f"{path}:{node.lineno}: duplicate definition "
                            f"of '{node.name}' (first at line "
                            f"{seen[node.name]})"
                        )
                    seen[node.name] = node.lineno
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for d in node.args.defaults + node.args.kw_defaults:
                if isinstance(d, (ast.List, ast.Dict, ast.Set)):
                    problems.append(
                        f"{path}:{d.lineno}: mutable default argument in "
                        f"'{node.name}'"
                    )
        elif isinstance(node, ast.ExceptHandler):
            if node.type is None and not noqa(node.lineno):
                problems.append(f"{path}:{node.lineno}: bare 'except:'")
    _check_swallow_loops(tree, path, noqa, problems)
    _check_unbounded_queues(tree, path, lines, problems)
    _check_serving_syncs(path, lines, problems)
    _check_fsync_policy(path, lines, problems)
    _check_reclaim_policy(path, lines, problems)
    _check_epoch_stamp(path, lines, problems)
    _check_evict_policy(path, lines, problems)
    _check_py_socket(path, lines, problems)
    _check_tenant_labels(tree, path, lines, problems)
    return problems


#: planes under overload protection: every queue here is bounded or
#: carries a written justification (ISSUE 4 tentpole discipline)
_BOUNDED_PLANES = (
    os.path.join("antidote_tpu", "proto"),
    os.path.join("antidote_tpu", "interdc"),
    os.path.join("antidote_tpu", "txn"),
)


def _check_unbounded_queues(tree, path, lines, problems) -> None:
    """In proto/, interdc/, txn/: flag queue constructions with no bound
    (queue.Queue()/LifoQueue() without maxsize, SimpleQueue(),
    collections.deque() without maxlen, defaultdict(list|deque)
    buffer registries) unless a ``# bounded-by:`` annotation within the
    three preceding lines (or the construction line) states the bound."""
    norm = os.path.normpath(path)
    if not any(plane in norm for plane in _BOUNDED_PLANES):
        return

    def annotated(lineno: int) -> bool:
        lo = max(0, lineno - 4)
        return any("bounded-by:" in ln for ln in lines[lo:lineno])

    def call_name(fn) -> str:
        if isinstance(fn, ast.Attribute):
            return fn.attr
        if isinstance(fn, ast.Name):
            return fn.id
        return ""

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = call_name(node.func)
        bad = None
        if name in ("Queue", "LifoQueue"):
            if not node.args and not any(k.arg == "maxsize"
                                         for k in node.keywords):
                bad = f"{name}() without maxsize"
        elif name == "SimpleQueue":
            bad = "SimpleQueue() (never bounded)"
        elif name == "deque":
            if len(node.args) < 2 and not any(k.arg == "maxlen"
                                              for k in node.keywords):
                bad = "deque() without maxlen"
        elif name == "defaultdict" and node.args:
            factory = call_name(node.args[0])
            if factory in ("list", "deque"):
                bad = f"defaultdict({factory}) buffer registry"
        if bad and not annotated(node.lineno):
            problems.append(
                f"{path}:{node.lineno}: unbounded queue in an "
                f"overload-protected plane: {bad} — give it an explicit "
                "bound or justify with '# bounded-by: <reason>' above"
            )


#: files that ARE the wire-serving hot path: a device sync on a
#: dispatcher-stage thread stalls every parked request behind one
#: materialize (the staged pipeline confines syncs to the writeback
#: stage) — ISSUE 5 discipline, mirroring the unbounded-queue rule.
#: The mesh serving plane (antidote_tpu/parallel/, ISSUE 10) is held to
#: the same bar: its launch/placement/collective paths run on
#: dispatcher-stage threads, so a sync there must carry the same
#: written justification.  The materializer plane
#: (antidote_tpu/materializer/, ISSUE 15) joined when its folds became
#: the live serving path: the Pallas kernels and the assoc/long-log
#: strategies run inside jitted serving reads, where a stray sync
#: serializes the whole launch pipeline.
_SERVING_HOT_PATH = (os.path.join("antidote_tpu", "proto", "server.py"),)
_SERVING_HOT_PLANES = (
    os.path.join("antidote_tpu", "parallel") + os.sep,
    os.path.join("antidote_tpu", "materializer") + os.sep,
)
_SYNC_TOKENS = ("block_until_ready(", ".item()", "np.asarray(")


def _check_serving_syncs(path, lines, problems) -> None:
    """In the serving hot path — proto/server.py and the whole mesh
    plane (antidote_tpu/parallel/) — flag device-sync idioms:
    ``block_until_ready(``, ``.item()``, ``np.asarray(`` — unless a
    ``# sync-ok: <reason>`` annotation on the line or within the three
    preceding lines justifies it (e.g. the writeback stage, which owns
    the sync, or a conversion of host data that never touches a jax
    array)."""
    norm = os.path.normpath(path)
    if not (any(norm.endswith(p) for p in _SERVING_HOT_PATH)
            or any(pl in norm for pl in _SERVING_HOT_PLANES)):
        return

    def annotated(lineno: int) -> bool:
        lo = max(0, lineno - 4)
        return any("sync-ok:" in ln for ln in lines[lo:lineno])

    def hits(code: str, tok: str) -> bool:
        # 'np.asarray(' must not match the trace-safe 'jnp.asarray('
        start = 0
        while (j := code.find(tok, start)) >= 0:
            if not (tok == "np.asarray(" and j > 0
                    and code[j - 1].isalnum()):
                return True
            start = j + 1
        return False

    for i, ln in enumerate(lines, start=1):
        code = ln.split("#", 1)[0]
        for tok in _SYNC_TOKENS:
            if hits(code, tok) and not annotated(i) and "sync-ok:" not in ln:
                problems.append(
                    f"{path}:{i}: device-sync idiom '{tok}' in the "
                    "serving hot path — move it to the writeback stage "
                    "or justify with '# sync-ok: <reason>'"
                )


#: the one file allowed to call os.fsync freely: the WAL owns durability
#: (group-fsync coordinator, background syncer, commit barriers).  An
#: os.fsync anywhere else in the package is either a policy leak (per-
#: call fsyncs are exactly the serial floor ISSUE 6 removed) or a
#: deliberate non-log use (atomic metadata replace, probe sidecars) that
#: must say so with a ``# fsync-ok: <reason>`` note.
_FSYNC_OWNER = os.path.join("antidote_tpu", "log", "wal.py")


def _check_fsync_policy(path, lines, problems) -> None:
    """Reject direct ``os.fsync`` outside log/wal.py without a
    ``# fsync-ok: <reason>`` annotation on the line or within the three
    preceding lines — the group-fsync policy stays centralized."""
    norm = os.path.normpath(path)
    if norm.endswith(_FSYNC_OWNER) or os.sep + "tests" + os.sep in norm \
            or norm.startswith("tests" + os.sep) \
            or os.path.basename(norm) == "lint.py":  # the rule's own source
        return

    def annotated(lineno: int) -> bool:
        lo = max(0, lineno - 4)
        return any("fsync-ok:" in ln for ln in lines[lo:lineno])

    for i, ln in enumerate(lines, start=1):
        code = ln.split("#", 1)[0]
        if "os.fsync(" in code and not annotated(i) \
                and "fsync-ok:" not in ln:
            problems.append(
                f"{path}:{i}: direct os.fsync outside log/wal.py — "
                "route durability through the WAL's group-fsync "
                "coordinator, or justify with '# fsync-ok: <reason>'"
            )


#: the one package allowed to delete durable files freely: log/ owns the
#: WAL + checkpoint lifecycle and its deletions run behind guarded APIs
#: (reclaim_below scans every record against the published floor before
#: an unlink; truncate_shard is the handoff drop).  A file deletion
#: anywhere else is either a durability bug waiting to happen (WAL or
#: checkpoint data silently removed outside the floor discipline —
#: ISSUE 8) or a deliberate temp/sidecar cleanup that must say so with a
#: ``# reclaim-ok: <reason>`` note.
_RECLAIM_OWNER = os.path.join("antidote_tpu", "log") + os.sep
_RECLAIM_TOKENS = ("os.remove(", "os.unlink(", "rmtree(")


def _check_reclaim_policy(path, lines, problems) -> None:
    """Reject file deletion (``os.remove``/``os.unlink``/``rmtree``)
    outside ``antidote_tpu/log/`` without a ``# reclaim-ok: <reason>``
    annotation on the line or within the three preceding lines — WAL and
    checkpoint files are only ever reclaimed through the guarded floor
    APIs."""
    norm = os.path.normpath(path)
    if _RECLAIM_OWNER in norm or os.sep + "tests" + os.sep in norm \
            or norm.startswith("tests" + os.sep) \
            or os.path.basename(norm) == "lint.py":  # the rule's source
        return

    def annotated(lineno: int) -> bool:
        lo = max(0, lineno - 4)
        return any("reclaim-ok:" in ln for ln in lines[lo:lineno])

    for i, ln in enumerate(lines, start=1):
        code = ln.split("#", 1)[0]
        for tok in _RECLAIM_TOKENS:
            if tok in code and not annotated(i) and "reclaim-ok:" not in ln:
                problems.append(
                    f"{path}:{i}: file deletion '{tok}' outside "
                    "antidote_tpu/log/ — WAL/checkpoint reclaim must go "
                    "through the guarded floor APIs (LogManager."
                    "reclaim_below / truncate_shard), or justify with "
                    "'# reclaim-ok: <reason>'"
                )


#: the replica plane (interdc/ — follower + peer replicas): a serving
#: epoch published there claims "every op ≤ this VC has applied", and a
#: follower stamping one AHEAD of its applied clock (e.g. from the
#: owner's commit counter) is a silent causal-violation machine —
#: session reads would be told their token is covered by data that
#: never arrived.  Publishes in this plane must ride
#: FollowerReplica.publish_applied_epoch_locked (which slaves the
#: counter to the applied clock first) or carry a written
#: ``# vc-stamped: <why the VC is the applied clock>`` justification.
_EPOCH_STAMP_PLANE = os.path.join("antidote_tpu", "interdc")
_EPOCH_STAMP_TOKENS = ("publish_serving_epoch(",
                       "_publish_serving_epoch_locked(")


def _check_epoch_stamp(path, lines, problems) -> None:
    """In interdc/ (follower/replica paths), reject serving-epoch
    publishes that bypass the applied-VC stamp: flag the publish calls
    unless a ``# vc-stamped:`` annotation on the line or within the
    three preceding lines states why the published VC is exactly the
    applied clock."""
    norm = os.path.normpath(path)
    if _EPOCH_STAMP_PLANE not in norm:
        return

    def annotated(lineno: int) -> bool:
        lo = max(0, lineno - 4)
        return any("vc-stamped:" in ln for ln in lines[lo:lineno])

    for i, ln in enumerate(lines, start=1):
        code = ln.split("#", 1)[0]
        for tok in _EPOCH_STAMP_TOKENS:
            if tok in code and not annotated(i) and "vc-stamped:" not in ln:
                problems.append(
                    f"{path}:{i}: serving-epoch publish '{tok}' in the "
                    "interdc/follower plane without the applied-VC "
                    "stamp — route through FollowerReplica."
                    "publish_applied_epoch_locked, or justify with "
                    "'# vc-stamped: <reason>'"
                )


#: the one module allowed to drop device table rows freely: the cold
#: tier owns the evict lifecycle (ISSUE 13) and its calls run behind the
#: verified-coverage checks (live head_vc byte-equal to the anchor
#: sidecar's stamp).  A ``.evict_rows(`` call anywhere else is either a
#: data-loss bug waiting to happen (a device row dropped with no sidecar
#: covering it) or a deliberate compose/heal step that must say so with
#: an ``# evict-ok: <reason>`` note.
_EVICT_OWNER = os.path.join("antidote_tpu", "store", "coldtier.py")
_EVICT_DEF = os.path.join("antidote_tpu", "store", "typed_table.py")


def _check_evict_policy(path, lines, problems) -> None:
    """Reject ``.evict_rows(`` outside store/coldtier.py (and its
    defining module) without an ``# evict-ok: <reason>`` annotation on
    the line or within the three preceding lines — cold-tier
    device-buffer drops go through the guarded evict API with written
    justification."""
    norm = os.path.normpath(path)
    if norm.endswith(_EVICT_OWNER) or norm.endswith(_EVICT_DEF) \
            or os.sep + "tests" + os.sep in norm \
            or norm.startswith("tests" + os.sep) \
            or os.path.basename(norm) == "lint.py":  # the rule's source
        return

    def annotated(lineno: int) -> bool:
        lo = max(0, lineno - 4)
        return any("evict-ok:" in ln for ln in lines[lo:lineno])

    for i, ln in enumerate(lines, start=1):
        code = ln.split("#", 1)[0]
        if ".evict_rows(" in code and not annotated(i) \
                and "evict-ok:" not in ln:
            problems.append(
                f"{path}:{i}: device-row drop '.evict_rows(' outside "
                "the cold tier — route it through store/coldtier.py's "
                "guarded evict (verified sidecar coverage), or justify "
                "with '# evict-ok: <reason>'"
            )


#: the serving front-end's socket I/O belongs to the native plane
#: (proto/cpp/frontend.cc — accept, framing, hot decode, whole-batch
#: hits all off the GIL, ISSUE 16).  A raw ``.recv(`` / ``.sendall(``
#: creeping back into server.py's hot stages quietly re-serializes the
#: serving path behind the GIL; the surviving Python sites (the
#: socketserver fallback plane) must say which plane they are with a
#: ``# py-socket-ok: <reason>`` note.
_PY_SOCKET_FILE = os.path.join("antidote_tpu", "proto", "server.py")


def _check_py_socket(path, lines, problems) -> None:
    """Reject raw ``.recv(`` / ``.sendall(`` in proto/server.py without
    a ``# py-socket-ok: <reason>`` annotation on the line or within the
    three preceding lines — socket I/O on the serving path lives in the
    native front-end; Python-plane sites carry written justification."""
    norm = os.path.normpath(path)
    if not norm.endswith(_PY_SOCKET_FILE):
        return

    def annotated(lineno: int) -> bool:
        lo = max(0, lineno - 4)
        return any("py-socket-ok:" in ln for ln in lines[lo:lineno])

    for i, ln in enumerate(lines, start=1):
        code = ln.split("#", 1)[0]
        if (".recv(" in code or ".sendall(" in code) \
                and not annotated(i) and "py-socket-ok:" not in ln:
            problems.append(
                f"{path}:{i}: raw socket I/O in the serving front-end "
                "— the native plane (proto/cpp/frontend.cc) owns "
                "accept/framing/replies; a Python-plane site must "
                "justify with '# py-socket-ok: <reason>'"
            )


#: tenant-labeled metrics (ISSUE 19): a ``tenant=`` label fed from the
#: wire (a client-chosen string) is an unbounded-cardinality leak — one
#: hostile client mints one Prometheus series per request.  Every
#: tenant-labeled ``.inc(``/``.set(``/``.observe(`` in the package must
#: clamp its value through the bounded TenantRegistry label set
#: (``registry.label(...)`` / a ``TenantLanes`` lane name) and say so
#: with a ``# tenant-label-ok: <where the value was clamped>`` note on
#: the line or within the three preceding lines.
_TENANT_LABEL_PLANE = "antidote_tpu" + os.sep
_TENANT_METRIC_METHODS = ("inc", "set", "observe")


def _check_tenant_labels(tree, path, lines, problems) -> None:
    """Reject metric calls carrying a ``tenant=`` label unless annotated
    ``# tenant-label-ok:`` — the label value must come from the bounded
    TenantRegistry set, never straight from the wire."""
    norm = os.path.normpath(path)
    if not (norm.startswith(_TENANT_LABEL_PLANE)
            or os.sep + _TENANT_LABEL_PLANE in norm) \
            or os.path.basename(norm) == "tenancy.py":  # defines the clamp
        return

    def annotated(lineno: int) -> bool:
        lo = max(0, lineno - 4)
        return any("tenant-label-ok:" in ln for ln in lines[lo:lineno])

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if not (isinstance(node.func, ast.Attribute)
                and node.func.attr in _TENANT_METRIC_METHODS):
            continue
        if not any(k.arg == "tenant" for k in node.keywords):
            continue
        if not annotated(node.lineno):
            problems.append(
                f"{path}:{node.lineno}: tenant-labeled metric without a "
                "'# tenant-label-ok:' note — clamp the value through "
                "the bounded TenantRegistry label set "
                "(registry.label(...)) and annotate where it was clamped"
            )


def _broad_handler(h: ast.ExceptHandler) -> bool:
    return h.type is None or (
        isinstance(h.type, ast.Name)
        and h.type.id in ("Exception", "BaseException")
    )


def _check_swallow_loops(tree, path, noqa, problems) -> None:
    """Flag broad ``except``s whose entire body is ``continue`` when the
    nearest enclosing loop is a ``while`` — the swallow-and-spin shape
    that turns a crashed thread loop into a silent zombie.  ``for``
    loops are exempt (bounded retries over peers/attempts), as is any
    handler that records/raises/logs before continuing."""

    def visit(node, in_while):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ExceptHandler) and in_while:
                body = [s for s in child.body
                        if not isinstance(s, ast.Pass)]
                if (_broad_handler(child) and body
                        and all(isinstance(s, ast.Continue) for s in body)
                        and not noqa(child.lineno)):
                    problems.append(
                        f"{path}:{child.lineno}: broad except-and-continue "
                        "inside a while loop (silently swallows every "
                        "fault forever; surface it or supervise the loop)"
                    )
            nw = in_while
            if isinstance(child, ast.While):
                nw = True
            elif isinstance(child, (ast.For, ast.AsyncFor, ast.FunctionDef,
                                    ast.AsyncFunctionDef, ast.Lambda)):
                nw = False  # continue targets the inner loop / new scope
            visit(child, nw)

    visit(tree, False)


def main(argv):
    paths = argv[1:] or ["antidote_tpu", "tests", "chip_smoke.py",
                         "__graft_entry__.py", "tools"]
    all_problems = []
    n = 0
    for path in iter_py(paths):
        n += 1
        all_problems.extend(check_file(path))
    for p in all_problems:
        print(p)
    print(f"lint: {n} files, {len(all_problems)} problem(s)",
          file=sys.stderr)
    return 1 if all_problems else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
