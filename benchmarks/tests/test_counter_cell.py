"""PR 31's cell `counter_pn_10k.update_read`: its configuration is its
Python-plane twin's but for the plane, its entries and files agree, its
rehearsal fails for the device alone and reports what the cell reports, its
comparison fails what it should, and each of its four metric files reads
through `status_delta` — on a synthetic window, and nothing on a program
without the counters.  No chip.

    python -m pytest benchmarks/tests/test_counter_cell.py -q
"""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.readers import status_delta   # noqa: E402

HERE = os.path.join(ROOT, "benchmarks")
CELL, TWIN = "counter_pn_10k.update_read", "counter_pn_10k_pyfe.update_read"
PLANE_FLAG = "--no-native-frontend"
WANT = {"frontend.mirror_hit_share": 40.0,
        "frontend.mirror_refused_share": 30.0,
        "txn.mirror_invalidate_ms": 0.15,
        "txn.mirror_invalidate_keys": 30.0}
#: metrics added later whose lists name this cell among others: a commit
#: group's transfers, the locked worker's round, a launch's host side
SHARED = ("store.scatter_transfers", "txn.round_ms", "txn.round_programs",
          "txn.round_offcpu_share", "txn.round_stage_ms", "kernels.call_ms",
          "kernels.call_offcpu_ms", "kernels.compile_ms_per_s",
          "kernels.call_host_operands")


def status(hits, served, worker, refused, fill_keys, inv_keys, inv_calls,
           inv_ms, groups):
    return {"pipeline": {
        "native": {"native_hits": hits, "fill_refused": refused,
                   "fill_keys": fill_keys, "invalidate_keys": inv_keys,
                   "invalidate_calls": inv_calls},
        "direct": {"served": served, "worker": worker}},
        "write_plane": {"phases": {"mirror_invalidate": {
            "sum_ms": inv_ms, "count": groups}}}}


PRE = status(100, 50, 10, 5, 100, 30, 1, 1.0, 10)
POST = status(500, 550, 110, 305, 1100, 3030, 101, 13.0, 90)
#: the parent's statuses of a window: the native block and the phases
#: before this PR
PARENT = ({"pipeline": {"native": {"fill_keys": 9, "fill_calls": 3},
                        "direct": {"served": 4, "worker": 1}},
           "write_plane": {"phases": {"certify": {"sum_ms": 1.0,
                                                  "count": 2}}}},
          {"pipeline": {"native": {"fill_keys": 99, "fill_calls": 30},
                        "direct": {"served": 40, "worker": 1}},
           "write_plane": {"phases": {"certify": {"sum_ms": 9.0,
                                                  "count": 20}}}})


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def spec(name):
    return load(HERE, "layer_metrics", name + ".json")


def read(name, pre, post):
    return status_delta.read(
        spec(name), SimpleNamespace(status={"window": (pre, post)}))


def run_cell(*extra, seed=4294967389):
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--seed", str(seed), "--seconds", "2", "--trace", "0", "--rehearse",
         *extra], cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    lines = p.stdout.strip().splitlines()
    assert lines and lines[-1].startswith("{"), p.stderr[-2000:]
    line = json.loads(lines[-1])
    failed = {k for k, c in line["checks"].items() if c["value"] > c["limit"]}
    assert (p.returncode == 0) == line["correct"]
    return line, failed


def test_configuration_is_the_twin_on_the_default_plane():
    bench = load(ROOT, "BENCHMARK.json")
    entry = [c for c in bench["configs"] if c["name"] == "counter_pn_10k"]
    assert len(entry) == 1 and entry[0]["reduced"] == []
    conf = load(ROOT, entry[0]["file"])
    twin = load(HERE, "configs", "counter_pn_10k_pyfe.json")
    assert conf["source"] == entry[0]["source"] and len(conf["source"]) <= 200
    assert "default (native) front end" in conf["source"]
    for key in ("guarantees", "widths", "fill", "assumed", "reduced",
                "chips", "placement", "rows_allocated"):
        assert conf[key] == twin[key], key
    assert conf["reduced"] == {} and conf["chips"] == 1
    for args, theirs in ((conf["serve_args"], twin["serve_args"]),
                         (conf["rehearse"]["serve_args"],
                          twin["rehearse"]["serve_args"])):
        assert PLANE_FLAG in theirs and PLANE_FLAG not in args
        assert args == [a for a in theirs if a != PLANE_FLAG]
        assert "--sync-log" in args
    assert {k: v for k, v in conf["rehearse"].items() if k != "serve_args"} \
        == {k: v for k, v in twin["rehearse"].items() if k != "serve_args"}


def test_entries_and_files_agree_and_name_only_this_cell():
    bench = load(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    cell, twin = cells[CELL], cells[TWIN]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "counter_pn_10k", twin["traffic"], 1)
    order = bench["workloads"]
    assert order.index(cell) > order.index(twin), "a new cell goes after"
    ours = {m["name"]: m for m in bench["per_layer"]
            if CELL in m["workloads"]}
    assert set(ours) == set(WANT) | set(SHARED)
    for name, entry in ours.items():
        s = spec(name)
        assert (entry["workloads"] == [CELL]) == (name in WANT), name
        assert s["reader"] == "status_delta"
        for key in ("unit", "better", "layer", "moves", "workloads"):
            assert s[key] == entry[key], (name, key)
    # what the cell reports end to end: not the metric whose list names
    # the twin alone
    e2e = {m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"ops_per_s", "read_p95_ms", "setup_s"}
    assert {m["moves"] for m in ours.values()} <= e2e


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_reads_the_window(name):
    assert read(name, PRE, POST) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reads_nothing_where_there_is_nothing_to_read(name):
    assert read(name, POST, POST) is None          # nothing advanced
    assert read(name, {}, {}) is None              # the Python plane
    if name != "frontend.mirror_hit_share":        # its counters predate it
        assert read(name, *PARENT) is None         # the parent


def test_rehearsal_fails_only_for_the_device():
    line, failed = run_cell()
    assert failed == {"wrong_device"} and not line["correct"]
    assert line["attempted"] > 100 and line["failed"] == 0
    assert set(line["metrics"]) == {"ops_per_s", "read_p95_ms", "setup_s"}


def test_sound_reference_is_correct():
    line, failed = run_cell("--control", "none")
    assert line["correct"] and not failed


def test_stale_reads_are_not_correct():
    line, failed = run_cell("--control", "stale_reads")
    assert not line["correct"] and "window_wrong" in failed
