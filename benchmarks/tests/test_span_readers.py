"""`module_match` and `host_cover` on synthetic planes of the shape
`trace_reduce.reduce_planes` takes: counts and structure, no chip.

    python -m pytest benchmarks/tests/test_span_readers.py -q
"""

import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import trace_reduce                      # noqa: E402
from benchmarks.readers import host_cover, module_match  # noqa: E402
from benchmarks.readers import xplane_spans as xs        # noqa: E402

MS = 1_000_000  # ns


def ev(name, t0_ms, dur_ms):
    return (name, int(t0_ms * MS), int(dur_ms * MS))


# one device, 100 ms: two launches of the head gather (ops nested inside
# their module frames), one commit scatter, one unnamed program; idle
# between them.  Host: two threads whose spans overlap.
DEVICE = ("/device:TPU:0", [
    ("XLA Modules", [
        ev("jit_antidote_head_gather(123)", 0, 10),
        ev("jit_antidote_commit_scatter_ring(7)", 30, 4),
        ev("jit_antidote_head_gather(123)", 50, 10),
        ev("jit_fn(9)", 90, 10),
    ]),
    ("XLA Ops", [
        ev("gather.1", 0, 4), ev("fusion.2", 5, 4),       # in launch 1
        ev("scatter.3", 30, 2),                           # in the scatter
        ev("gather.1", 50, 4), ev("fusion.2", 54, 2),     # in launch 2
        ev("copy.9", 90, 10),                             # unnamed module
    ]),
    ("Steps", [ev("0", 0, 100)]),
])
HOST = ("/host:CPU", [
    ("antidote-proto-batch", [
        ev("serve.gate_wait", 10, 18),                    # 10..28
        ev("serve.launch", 28, 4),                        # 28..32
        ev("serve.gate_wait", 32, 16),                    # 32..48
        ev("serve.launch", 48, 3),                        # 48..51
    ]),
    ("antidote-proto-writeback", [
        ev("serve.wb_wait", 0, 9),
        ev("serve.device_wait", 9, 3),                    # 9..12
        ev("serve.wb_host", 12, 8),                       # 12..20
        ev("serve.device_wait", 56, 4),                   # 56..60
    ]),
    ("antidote-proto-locked", [
        ev("commit.group", 26, 14),                       # 26..40
        ev("commit.certify", 26, 2),
        ev("serve.locked_wait", 40, 30),                  # 40..70
    ]),
])
PLANES = [HOST, DEVICE, ("/host:metadata", [])]
CLASSES = [["launch", r"^serve\.launch"],
           ["writeback", r"^serve\.(device_wait|wb_host)"],
           ["commit", r"^commit\.group"],
           ["gate_wait", r"^serve\.(gate_wait|locked_wait)"]]


def ctx_for(planes, rows=0):
    tr = trace_reduce.reduce_planes(planes)
    return SimpleNamespace(
        trace=tr, _xplane_spans=planes, cell={"name": "synthetic"},
        status={"trace": [{"pipeline": {"reads": {"gather": 0}}},
                          {"pipeline": {"reads": {"gather": rows}}}]},
        config={"widths": {"max_dcs": 8, "set_slots": 16,
                           "ops_per_key": 16, "snap_versions": 2}},
        peaks={"hbm_bytes_per_s": 819e9})


def test_interval_arithmetic():
    a = xs.merge([(5, 9), (0, 3), (2, 4), (9, 10)])
    assert a == [(0, 4), (5, 10)]
    assert xs.subtract([(0, 10)], a) == [(4, 5)]
    assert xs.subtract(a, [(1, 2), (3, 6), (20, 30)]) == [(0, 1), (2, 3),
                                                          (6, 10)]
    assert xs.total(a) == 9


def test_module_match_counts_only_ops_inside_matching_modules():
    ctx = ctx_for(PLANES)
    gather = {"module": "antidote_head_gather", "value": "time_ms"}
    assert module_match.read(gather, ctx) == pytest.approx(14.0)
    per = {"module": "antidote_head_gather", "value": "ms_per_launch"}
    assert module_match.read(per, ctx) == pytest.approx(7.0)
    scat = {"module": "antidote_commit_scatter", "value": "ms_per_launch"}
    assert module_match.read(scat, ctx) == pytest.approx(2.0)
    # the trace_match reader, over every op, sees the unnamed program too
    assert ctx.trace.busy_s == pytest.approx(0.026)


def test_module_match_nothing_matched_is_none():
    ctx = ctx_for(PLANES)
    assert module_match.read({"module": "antidote_freeze_serving",
                              "value": "ms_per_launch"}, ctx) is None
    assert module_match.read({"module": ".", "value": "time_ms"},
                             ctx_for([HOST])) is None
    no_trace = SimpleNamespace(trace=None, cell={"name": "none"}, status={})
    assert module_match.read({"module": ".", "value": "time_ms"},
                             no_trace) is None


def test_module_match_roofline_is_at_least_the_whole_trace_roofline():
    from benchmarks.readers import trace_match
    ctx = ctx_for(PLANES, rows=100_000)
    work = {"rows": [{"path": "pipeline.reads.gather"}],
            "bytes_per_row": "head_row", "type": "set_aw"}
    inside = module_match.read({"module": "antidote_head_gather",
                                "value": "roofline", "work": work}, ctx)
    whole = trace_match.read({"match": ".", "value": "roofline",
                              "work": work}, ctx)
    least_s = 100_000 * 1188 / 819e9
    assert inside == pytest.approx(100 * least_s / 0.014)
    assert whole == pytest.approx(100 * least_s / 0.026)
    assert inside >= whole
    none_served = ctx_for(PLANES, rows=0)
    assert module_match.read({"module": "antidote_head_gather",
                              "value": "roofline", "work": work},
                             none_served) is None


def test_host_cover_splits_idle_exclusively_and_sums_to_100():
    ops = xs.device_planes(PLANES)[0][0]
    res = host_cover.split(ops, xs.host_events(PLANES), CLASSES)
    # busy: 0-4, 5-9, 30-32, 50-56, 90-100; idle 4-5, 9-30, 32-50, 56-90
    assert res["idle"] == 74 * MS
    # launch 28-30, 48-50 | writeback 9-20, 56-60 | commit 26-28, 32-40
    # | gate 20-26, 40-48, 60-70 | uncovered 4-5, 70-90
    assert res["launch"] == 4 * MS
    assert res["writeback"] == 15 * MS
    assert res["commit"] == 10 * MS
    assert res["gate_wait"] == 24 * MS
    assert res["uncovered"] == 21 * MS
    assert sum(res[k] for k, _ in CLASSES) + res["uncovered"] == res["idle"]
    assert res["spans"] == {"launch": 2, "writeback": 3, "commit": 1,
                            "gate_wait": 3}


def test_host_cover_reader_reads_the_shared_class_list():
    ctx = ctx_for(PLANES)
    shares = {v: host_cover.read({"cover": "idle_cover", "value": v}, ctx)
              for v in ("launch", "writeback", "commit", "gate_wait",
                        "uncovered")}
    assert shares["launch"] == pytest.approx(100 * 4 / 74)
    # a span no class lists (serve.wb_wait 0-9 over the idle millisecond
    # 4-5) covers nothing
    assert shares["uncovered"] == pytest.approx(100 * 21 / 74)
    assert sum(shares.values()) == pytest.approx(100.0)
    assert ctx._host_cover["idle_cover"]["idle"] == 74 * MS   # parsed once


def test_host_cover_without_spans_is_none():
    # the parent commit's trace: device lines, a host plane with other
    # TraceMes, no span of any class
    parent = [DEVICE, ("/host:CPU", [("main", [ev("PjitFunction(fn)", 0,
                                                  5)])])]
    ctx = ctx_for(parent)
    for v in ("launch", "writeback", "commit", "gate_wait", "uncovered"):
        assert host_cover.read({"cover": "idle_cover", "value": v},
                               ctx) is None
    # a class with no span of its own is left out, the others are read
    only_gate = [DEVICE, ("/host:CPU", [("t", [ev("serve.gate_wait", 10,
                                                  15)])])]
    ctx = ctx_for(only_gate)
    assert host_cover.read({"cover": "idle_cover", "value": "commit"},
                           ctx) is None
    assert host_cover.read({"cover": "idle_cover", "value": "gate_wait"},
                           ctx) == pytest.approx(100 * 15 / 74)


def test_every_new_metric_file_loads_through_its_reader():
    import importlib
    import importlib.util
    import json
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    ctx = ctx_for(PLANES, rows=1000)
    ctx.status["window"] = ctx.status["trace"]
    for m in bench["per_layer"][8:]:
        base = os.path.join(ROOT, "benchmarks", "layer_metrics", m["name"])
        if not os.path.exists(base + ".json"):
            # a metric that is code of its own (`<name>.py`, `read(ctx)`)
            spec = importlib.util.spec_from_file_location(
                "layer_metric_" + m["name"].replace(".", "_"), base + ".py")
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            assert callable(mod.read), m["name"]
            continue
        f = json.load(open(base + ".json"))
        reader = importlib.import_module("benchmarks.readers." + f["reader"])
        v = reader.read(f, ctx)      # a status without the path: nothing
        assert v is None or isinstance(v, float), m["name"]
    assert len(bench["per_layer"]) == 74
