"""The documents name what is in the tree (ISSUE 29).

Every repo-relative path (``*.py``, ``*.json``, ``*.md``, ``*.cc``) and
every ``make <target>`` that a document names must exist: a deleted
driver, record or make target may not live on in a sentence that sends
the reader to it.
"""

import functools
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = [
    "README.md",
    "docs/architecture.md",
    "docs/operations.md",
    "docs/performance.md",
    "docs/parity.md",
    "Makefile",
    ".claude/skills/verify/SKILL.md",
]

#: a path is named relative to the repo root or, as the documents do for
#: the program's modules, relative to one of these
BASES = ("", "antidote_tpu", "benchmarks", "docs", "tests")

#: files a running node writes, which the operations guide names
NOT_IN_THE_TREE = {
    "antidote_meta.json",       # a node's durable metadata, in its --log-dir
}

_PATH = re.compile(r"(?<![\w/.*<>{}-])(\.?[\w-]+(?:[/.][\w-]+)*\.(?:py|json|md|cc))\b")
_CODE = re.compile(r"```.*?```|`[^`\n]+`", re.S)
_MAKE = re.compile(r"(?<![\w-])make ([a-z][\w-]*)")


@functools.lru_cache(maxsize=None)
def _tree():
    """Every tracked-looking file of the repo, and the set of base names."""
    files = set()
    for base, dirs, names in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in (
            ".git", "__pycache__", "chipwork", "chiprun_out", ".bench_run",
            ".xla_cache", ".xla_cache_t8", ".pytest_cache")]
        for n in names:
            files.add(os.path.relpath(os.path.join(base, n), ROOT))
    return files, {os.path.basename(f) for f in files}


def _exists(path):
    files, names = _tree()
    if "/" not in path:
        return path in names
    return any(os.path.normpath(os.path.join(b, path)) in files
               for b in BASES)


def _make_targets():
    with open(os.path.join(ROOT, "Makefile")) as f:
        return set(re.findall(r"^([a-z][\w-]*):", f.read(), re.M))


@pytest.mark.parametrize("doc", DOCS)
def test_every_path_and_make_target_a_document_names_exists(doc):
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    named = {m.group(1) for m in _PATH.finditer(text)}
    assert named, f"{doc}: the pattern found no path at all"
    missing = sorted(p for p in named
                     if p not in NOT_IN_THE_TREE and not _exists(p))
    assert not missing, f"{doc} names files that are not in the tree: {missing}"

    if doc == "Makefile":
        # .PHONY and every prerequisite name a rule
        asked = set(re.findall(r"^\.PHONY:(.*)$", text, re.M)[0].split())
        for deps in re.findall(r"^[a-z][\w-]*:(.*)$", text, re.M):
            asked |= set(deps.split())
    else:
        # prose says "make sure": only code spans and fences name targets
        asked = {t for span in _CODE.findall(text)
                 for t in _MAKE.findall(span)}
    gone = sorted(asked - _make_targets())
    assert not gone, f"{doc} names make targets the Makefile lacks: {gone}"


def _operations_guide():
    with open(os.path.join(ROOT, "docs/operations.md")) as f:
        return f.read()


def test_operations_guide_lists_every_host_span():
    """Every ``obs.trace.span`` name the program opens is in the
    operations guide's list ("Tracing a live node"): a trace shows no
    span the guide does not explain."""
    rx = re.compile(r"""\bspan\(\s*["']([a-z_.]+)["']""")
    names = set()
    for base, _dirs, files in os.walk(os.path.join(ROOT, "antidote_tpu")):
        for n in files:
            if n.endswith(".py"):
                with open(os.path.join(base, n)) as f:
                    names |= set(rx.findall(f.read()))
    assert {"serve.launch", "serve.wb_host.fill", "commit.group"} <= names
    listed = set(_CODE.findall(_operations_guide()))
    missing = sorted(n for n in names if f"`{n}`" not in listed)
    assert not missing, missing


def test_operations_guide_names_every_native_stat():
    """Every counter of the status block's ``native`` section is named
    in the operations guide."""
    from antidote_tpu.proto.native_frontend import NativeFrontend

    text = _operations_guide()
    missing = [f for f in NativeFrontend.STAT_FIELDS
               if not re.search(rf"\b{f}\b", text)]
    assert not missing, missing
