"""Shared build + provenance helper for the native ``.so`` planes.

Every C++ module in the tree (interdc/cpp/pump.cc, proto/cpp/frontend.cc,
log/cpp/wal.cc, store/cpp/router.cc) compiles through ONE pinned flag
set and loads through :func:`load`; the builds embed the sha256 of their
source as ``ANTIDOTE_SRC_SHA`` (pump and frontend export a
``<name>_src_sha()`` getter).  The ``.so`` files are git-ignored: a
clean checkout builds them from the committed sources on first use.  ``make native`` rebuilds all of
them; ``make native-check`` compares each checked-in binary's embedded
sha against the current source — the drift a hand-run g++ line can't
detect (the satellite of ISSUE 16: pump.cc's .so could silently diverge
from source before this existed).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import pathlib
import subprocess
from typing import Dict, List, Optional, Tuple

log = logging.getLogger(__name__)

#: the ONE compile line — loaders and `make native` must agree, or the
#: native-check comparison would chase flag drift instead of source drift
PINNED_FLAGS = ["-O2", "-shared", "-fPIC", "-std=c++17", "-pthread"]

_ROOT = pathlib.Path(__file__).parent

#: (source, checked-in .so, exported sha getter) for every native plane
#: that participates in `make native` / `make native-check`
MODULES: List[Tuple[pathlib.Path, pathlib.Path, str]] = [
    (_ROOT / "interdc" / "cpp" / "pump.cc",
     _ROOT / "interdc" / "cpp" / "_pump.so", "pump_src_sha"),
    (_ROOT / "proto" / "cpp" / "frontend.cc",
     _ROOT / "proto" / "cpp" / "_frontend.so", "frontend_src_sha"),
]


def src_sha(src: pathlib.Path) -> str:
    return hashlib.sha256(src.read_bytes()).hexdigest()


def build(src: pathlib.Path, out: pathlib.Path) -> str:
    """Compile ``src`` into ``out`` with the pinned flags, embedding the
    source sha; returns the sha."""
    sha = src_sha(src)
    subprocess.run(
        ["g++", *PINNED_FLAGS, f'-DANTIDOTE_SRC_SHA="{sha}"',
         str(src), "-o", str(out)],
        check=True, capture_output=True,
    )
    return sha


def ensure(src: pathlib.Path, so: pathlib.Path) -> pathlib.Path:
    """Rebuild ``so`` when missing or older than its source (the lazy
    first-use compile the loaders share)."""
    if not so.exists() or so.stat().st_mtime < src.stat().st_mtime:
        build(src, so)
    return so


#: plane name -> None once its .so loaded, else why it did not (compile
#: error, missing toolchain, dlopen failure).  Every loader falls back to
#: its Python plane on failure; this is what keeps the fallback visible —
#: the node status ``native`` block reports it.
LOAD_STATE: Dict[str, Optional[str]] = {}


def load(plane: str, src: pathlib.Path, so: pathlib.Path,
         **cdll_kw) -> Optional[ctypes.CDLL]:
    """Build (when stale) and dlopen one native plane, recording the
    outcome in :data:`LOAD_STATE`; None when it cannot be had."""
    try:
        ensure(src, so)
        lib = ctypes.CDLL(str(so), **cdll_kw)
    except (OSError, subprocess.CalledProcessError) as e:
        stderr = getattr(e, "stderr", None) or b""
        LOAD_STATE[plane] = (
            f"{e!r} {stderr.decode(errors='replace')[-400:]}".strip()
        )
        log.warning("native %s plane unavailable (%s); the Python plane "
                    "serves instead", plane, LOAD_STATE[plane])
        return None
    LOAD_STATE[plane] = None
    return lib


def embedded_sha(so: pathlib.Path, getter: str) -> Optional[str]:
    """The source sha a built .so carries, or None when the binary
    predates the provenance scheme (no getter symbol)."""
    try:
        lib = ctypes.CDLL(str(so))
        fn = getattr(lib, getter)
    except (OSError, AttributeError):
        return None
    fn.restype = ctypes.c_char_p
    fn.argtypes = []
    out = fn()
    return out.decode() if out else None


def check() -> List[str]:
    """`make native-check`: one problem string per stale/missing binary
    (empty list = every checked-in .so matches its source)."""
    problems = []
    for src, so, getter in MODULES:
        if not so.exists():
            problems.append(f"{so.name}: missing (run `make native`)")
            continue
        want = src_sha(src)
        got = embedded_sha(so, getter)
        if got is None:
            problems.append(
                f"{so.name}: no embedded source sha — built outside "
                f"`make native` (rebuild to re-pin provenance)")
        elif got != want:
            problems.append(
                f"{so.name}: built from a different {src.name} "
                f"(embedded {got[:12]}…, source {want[:12]}…) — run "
                f"`make native`")
    return problems


def main() -> int:
    import sys

    if "--check" in sys.argv:
        problems = check()
        for p in problems:
            print(f"native-check: {p}")
        if not problems:
            print(f"native-check: {len(MODULES)} binaries match source")
        return 1 if problems else 0
    for src, so, _ in MODULES:
        sha = build(src, so)
        print(f"built {so.relative_to(_ROOT.parent)} ({sha[:12]}…)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
