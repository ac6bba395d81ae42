"""PR 25's two `store.gate_hold_*` metric files: they load, read through
`status_delta`, name counters the program's node status has, cover the
dispatcher's hold span through `idle_cover.json` as it stands, and
`selfcheck --quick` passes with them.  Counts and structure, no chip.

    python -m pytest benchmarks/tests/test_gate_hold_metrics.py -q
"""

import json
import os
import re
import subprocess
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.readers import status_delta              # noqa: E402

HERE = os.path.join(ROOT, "benchmarks")
NAMES = ("store.gate_hold_share", "store.gate_hold_ms")


def spec(name):
    with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def ctx(pre, post):
    window = tuple({"pipeline": {"gate_hold": hold}} for hold in (pre, post))
    return SimpleNamespace(status={"window": window})


@pytest.mark.parametrize("name", NAMES)
def test_file_agrees_with_its_benchmark_entry(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert len(entry) == 1
    s = spec(name)
    for key in ("unit", "better", "layer", "moves", "workloads"):
        assert s[key] == entry[0][key], key
    assert s["reader"] == "status_delta" and s["better"] == "lower"
    assert entry[0]["source"] == "program_counter"


def test_reads_share_and_mean_of_the_window():
    pre = {"rounds": 100, "held": 10, "sum_ms": 30.0}
    post = {"rounds": 300, "held": 190, "sum_ms": 930.0}
    c = ctx(pre, post)
    assert status_delta.read(spec(NAMES[0]), c) == pytest.approx(90.0)
    assert status_delta.read(spec(NAMES[1]), c) == pytest.approx(5.0)


def test_reads_nothing_where_nothing_held_or_the_program_lacks_it():
    idle = ctx({"rounds": 5, "held": 0, "sum_ms": 0.0},
               {"rounds": 9, "held": 0, "sum_ms": 0.0})
    assert status_delta.read(spec(NAMES[0]), idle) == 0.0
    assert status_delta.read(spec(NAMES[1]), idle) is None
    parent = SimpleNamespace(status={"window": ({"pipeline": {}},
                                                {"pipeline": {}})})
    for name in NAMES:
        assert status_delta.read(spec(name), parent) is None


def test_counters_are_in_the_program_s_node_status():
    """Every path of both files resolves in a live server's status."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from antidote_tpu.api.node import AntidoteNode
    from antidote_tpu.config import AntidoteConfig
    from antidote_tpu.proto.server import ProtocolServer

    node = AntidoteNode(AntidoteConfig(n_shards=2, max_dcs=2,
                                       keys_per_table=64))
    srv = ProtocolServer(node, port=0, native_frontend=False)
    try:
        status = {"pipeline": srv._pipeline_status()}
    finally:
        srv.close()
    for name in NAMES:
        s = spec(name)
        for term in s["num"] + s["den"]:
            assert status_delta.lookup(status, term["path"]) is not None, (
                name, term["path"])


def test_idle_cover_counts_the_hold_span_as_gate_wait():
    with open(os.path.join(HERE, "readers", "idle_cover.json")) as f:
        classes = json.load(f)["classes"]
    first = next(c for c, rx in classes
                 if re.search(rx, "serve.gate_wait.slot"))
    assert first == "gate_wait"


def test_selfcheck_passes_with_them():
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.selfcheck", "--quick"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    for name in NAMES:
        assert f"per-layer {name}" in out.stdout
