"""PR 26's cell `set_aw_2m_mesh4.read_zipf`: its rehearsal fails for the
device alone, its comparison fails what it should, and every metric file it
brings reads through its reader — on a synthetic status and trace, and
nothing on a program without the counters.  No chip.

    python -m pytest benchmarks/tests/test_mesh_cell.py -q

The configuration's `rehearse` serves `--mesh-devices 1` (the routed path on
one device), so the rehearsal needs no XLA_FLAGS of the caller's.
"""

import importlib.util
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

from benchmarks import work_model                           # noqa: E402
from benchmarks.readers import module_match, status_delta   # noqa: E402

HERE = os.path.join(ROOT, "benchmarks")
CELL = "set_aw_2m_mesh4.read_zipf"
DATA = {  # metric file -> what it reads of the synthetic window below
    "mesh.route_ms": 0.5, "mesh.batch_objects": 20.0, "mesh.launch_ms": 6.0,
    "mesh.wb_host_ms": 4.0, "mesh.device_wait_ms": 3.0,
    "mesh.miss_total_ms": 40.0, "mesh.cache_hit_share": 80.0,
}
CODE = {"mesh.pad_share": 100.0 * (1 - 2000 / 102400),
        "mesh.device_skew": 800 / 500}
TRACED = ("kernels.mesh_gather_ms", "kernels.mesh_gather_roofline")
#: metrics added later whose lists name this cell among others: the direct
#: path and the mirror's fills, a launch's host side
SHARED = ("frontend.direct_share", "store.mirror_fill_keys",
          "kernels.call_ms", "kernels.call_offcpu_ms",
          "kernels.compile_ms_per_s", "kernels.call_host_operands")
MS = 1_000_000


def run_cell(*extra, seed=4294967377):
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--seed", str(seed), "--seconds", "2", "--trace", "0", "--rehearse",
         "--unlisted", *extra], cwd=ROOT, capture_output=True, text=True,
        timeout=900, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    lines = p.stdout.strip().splitlines()
    assert lines and lines[-1].startswith("{"), p.stderr[-2000:]
    line = json.loads(lines[-1])
    failed = {k for k, c in line["checks"].items() if c["value"] > c["limit"]}
    assert (p.returncode == 0) == line["correct"]
    return line, failed


def spec(name):
    with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def code(name):
    s = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_"),
        os.path.join(HERE, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def status(launches, rows, slots, by_device, route_ms, stages, hits, misses):
    stage = lambda ms: {"sum_ms": ms * rows, "count": rows}  # noqa: E731
    paths = {s: stage(ms) for s, ms in stages.items()}
    return {"pipeline": {
        "reads": {"gather": rows},
        "native": {"hit_objects": hits},
        "snapshot_cache": {"hit": 0, "miss": misses},
        "paths": {"gather": paths},
        "mesh": {"launches": launches, "slots": slots, "rows": rows,
                 "rows_by_device": {str(d): v
                                    for d, v in enumerate(by_device)},
                 "route": {"count": launches, "sum_ms": route_ms}}}}


STAGES = {"launch": 6.0, "wb_host": 4.0, "device_wait": 3.0, "total": 40.0}
PRE = status(10, 100, 10240, [25, 25, 25, 25], 7.0, STAGES, 1000, 100)
POST = status(110, 2100, 112640, [525, 825, 325, 425], 57.0, STAGES, 9000,
              2100)


def planes(n_devices):
    """A launch of 100 ms on every device with two 10 ms operations inside
    it, and one operation of another program."""
    dev = [("XLA Modules", [("jit_antidote_mesh_gather(1)", 0, 100 * MS),
                            ("jit_antidote_mesh_pmin(2)", 200 * MS, 10 * MS)]),
           ("XLA Ops", [("gather.1", 10 * MS, 10 * MS),
                        ("fusion.2", 30 * MS, 10 * MS),
                        ("all-reduce.3", 200 * MS, 10 * MS)])]
    return [("/host:CPU", [("python", [("x", 0, 999 * MS)])])] + [
        (f"/device:TPU:{d}", dev) for d in range(n_devices)]


def ctx(window=(PRE, POST), n_devices=4):
    with open(os.path.join(HERE, "configs", "set_aw_2m_mesh4.json")) as f:
        config = json.load(f)
    c = SimpleNamespace(status={"window": window, "trace": window},
                        config=config, peaks={"hbm_bytes_per_s": 819e9},
                        cell={"name": CELL}, trace=object())
    c._xplane_spans = planes(n_devices)
    return c


def test_entries_and_files_agree_and_name_only_this_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 4
    ours = {m["name"]: m for m in bench["per_layer"]
            if CELL in m["workloads"]}
    assert set(ours) == set(DATA) | set(CODE) | set(TRACED) | set(SHARED)
    for name, entry in ours.items():
        assert (entry["workloads"] == [CELL]) == (name not in SHARED), name
        if name in CODE or name == "kernels.mesh_gather_roofline":
            assert callable(code(name).read)
            continue
        s = spec(name)
        for key in ("unit", "better", "layer", "moves", "workloads"):
            assert s[key] == entry[key], (name, key)
    one = json.load(open(os.path.join(HERE, "configs", "set_aw_1m.json")))
    two = ctx().config
    assert two["guarantees"] == one["guarantees"] and two["fill"] == one["fill"]
    assert two["widths"] == dict(one["widths"], keys_per_table=131072)


@pytest.mark.parametrize("name", sorted(DATA))
def test_data_metric_reads_the_window(name):
    assert status_delta.read(spec(name), ctx()) == pytest.approx(DATA[name])


@pytest.mark.parametrize("name", sorted(CODE))
def test_code_metric_reads_the_window(name):
    assert code(name).read(ctx()) == pytest.approx(CODE[name])


def test_kernel_time_per_launch():
    assert module_match.read(spec("kernels.mesh_gather_ms"), ctx()) \
        == pytest.approx(20.0)


def test_roofline_of_four_planes_is_a_quarter_of_one_plane_s():
    """Same rows, same busy time on each device: four devices have four
    times the bandwidth, so the share is a quarter."""
    read = code("kernels.mesh_gather_roofline").read
    one, four = read(ctx(n_devices=1)), read(ctx(n_devices=4))
    config = ctx().config
    least_s = 2000 * work_model.head_row_bytes("set_aw", config["widths"]) \
        / 819e9
    assert one == pytest.approx(100.0 * least_s / 0.020)
    assert four == pytest.approx(one / 4.0)
    assert 0.0 < four < 100.0


@pytest.mark.parametrize("name", sorted(DATA) + sorted(CODE) + list(TRACED))
def test_reads_nothing_on_a_program_without_the_counters(name):
    """The parent: a mesh status block without the routed tallies, no
    launch of the program in the trace, nothing gathered."""
    bare = {"pipeline": {"mesh": {"devices": 4}, "reads": {}, "paths": {}}}
    c = ctx(window=(bare, bare))
    c._xplane_spans = [p for p in planes(4)
                       if not p[0].startswith("/device:")]
    if name in CODE or name == "kernels.mesh_gather_roofline":
        assert code(name).read(c) is None
    elif name in TRACED:
        assert module_match.read(spec(name), c) is None
    else:
        assert status_delta.read(spec(name), c) is None


def test_roofline_is_never_zero():
    idle = ctx(window=(POST, POST))          # nothing gathered in the span
    assert code("kernels.mesh_gather_roofline").read(idle) is None


def test_rehearsal_fails_only_for_the_device():
    line, failed = run_cell()
    assert failed == {"wrong_device"} and not line["correct"]
    assert line["attempted"] > 100 and line["failed"] == 0


def test_sound_reference_is_correct():
    line, failed = run_cell("--control", "none")
    assert line["correct"] and not failed


def test_stale_reads_are_not_correct():
    line, failed = run_cell("--control", "stale_reads")
    assert not line["correct"] and "window_wrong" in failed
