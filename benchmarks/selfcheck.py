"""`python -m benchmarks.selfcheck [--quick]` — what can be checked of the
yardstick without a chip, in seconds (`--quick`) or a few minutes."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def ok(cond, what):
    if not cond:
        raise SystemExit(f"selfcheck FAILED: {what}")
    print(f"ok  {what}")


def check_stats():
    from benchmarks import stats
    lat = [1.0] * 94 + [2.0] * 5 + [5000.0]          # one 5 s stall
    ok(stats.percentile(lat, 50) == 1.0, "median ignores the stall")
    ok(1.0 < stats.percentile(lat, 95) <= 2.0, "p95 of 100 sits in the tail")
    ok(stats.percentile(lat, 100) == 5000.0, "the stall is the maximum")
    ok(stats.percentile([3.0], 95) == 3.0, "percentile of one value")
    ok(stats.rate(100, 20.0) == 5.0, "rate is over the whole window")
    ok(abs(stats.spread([10, 10, 11, 11, 12, 12]) - 2 / 11) < 0.05,
       "spread = interquartile distance / median")


def check_status_delta():
    from benchmarks.readers import status_delta as sd
    pre = {"pipeline": {"stages": {"parked": {"count": 10, "sum_ms": 5.0}}},
           "write_plane": {"merge_width": {"count": 4, "mean": 1.0}}}
    post = {"pipeline": {"stages": {"parked": {"count": 30, "sum_ms": 45.0}}},
            "write_plane": {"merge_width": {"count": 10, "mean": 2.2}}}
    ctx = type("C", (), {"status": {"window": [pre, post]}})()
    parked = {"span": "window",
              "num": [{"path": "pipeline.stages.parked.sum_ms"}],
              "den": [{"path": "pipeline.stages.parked.count"}]}
    ok(sd.read(parked, ctx) == 2.0, "status_delta: ratio of deltas")
    mw = {"span": "window",
          "num": [{"path": "write_plane.merge_width.count",
                   "times": "write_plane.merge_width.mean"}],
          "den": [{"path": "write_plane.merge_width.count"}]}
    ok(abs(sd.read(mw, ctx) - 3.0) < 1e-9,
       "status_delta: the window's own mean from count x mean")
    ok(sd.read({"span": "window", "num": [{"path": "no.such"}],
                "den": [{"path": "pipeline.stages.parked.count"}]},
               ctx) is None, "status_delta: a missing path reads nothing")
    ok(sd.read(dict(parked, span="trace"), ctx) is None,
       "status_delta: no sample of the span reads nothing")


def check_trace_reduce():
    from benchmarks import trace_reduce as tr
    from benchmarks.readers import trace_match
    ms = 1_000_000
    planes = [
        ("/host:CPU", [("python", [("x", 0, 999 * ms)])]),
        ("/device:TPU:0", [
            ("XLA Modules", [("jit_f", 0, 100 * ms)]),
            ("XLA Ops", [("fusion.1", 0, 10 * ms), ("gather.2", 5 * ms, 10 * ms),
                         ("fusion.1", 50 * ms, 10 * ms),
                         ("copy.3", 90 * ms, 10 * ms)]),
        ]),
    ]
    t = tr.reduce_planes(planes)
    ok(t.n_devices == 1 and abs(t.window_s - 0.100) < 1e-9,
       "trace_reduce: window from first to last device operation")
    ok(abs(t.busy_s - 0.035) < 1e-9, "trace_reduce: busy is a union")
    ok(t.top_ops(1)[0][0] == "fusion.1"
       and abs(t.top_ops(1)[0][1] - 0.020) < 1e-9,
       "trace_reduce: time per operation name")
    ok(t.idle_gaps(1)[0] == ["before:fusion.1", 0.035],
       "trace_reduce: the longest gap, named by what ended it")
    ctx = type("C", (), {})()
    ctx.trace, ctx.config = t, {"widths": {"max_dcs": 8, "set_slots": 16}}
    ctx.peaks = {"hbm_bytes_per_s": 819e9}
    ctx.status = {"trace": [{"pipeline": {"reads": {"gather": 0}}},
                            {"pipeline": {"reads": {"gather": 819_000}}}]}
    ok(abs(trace_match.read({"match": ".", "value": "idle_share"}, ctx)
           - 65.0) < 1e-9, "trace_match: idle share")
    ok(abs(trace_match.read({"match": "^gather", "value": "time_ms"}, ctx)
           - 10.0) < 1e-9, "trace_match: time of the matching operations")
    roof = trace_match.read({"match": ".", "value": "roofline", "work": {
        "rows": [{"path": "pipeline.reads.gather"}],
        "bytes_per_row": "head_row", "type": "set_aw"}}, ctx)
    ok(abs(roof - 100 * (819_000 * 1188 / 819e9) / 0.035) < 1e-9,
       "trace_match: roofline = least seconds / busy seconds")
    ctx.status = {"trace": [ctx.status["trace"][0]] * 2}
    ok(trace_match.read({"match": ".", "value": "roofline", "work": {
        "rows": [{"path": "pipeline.reads.gather"}],
        "bytes_per_row": "head_row", "type": "set_aw"}}, ctx) is None,
       "trace_match: no rows served reads nothing, never 0")
    ok(tr.reduce_planes(planes[:1]).events == [],
       "trace_reduce: host planes are not device time")


def check_work_model():
    """The bytes model against the arrays TypedTable allocates."""
    import numpy as np
    from antidote_tpu.config import AntidoteConfig
    from antidote_tpu.crdt import get_type
    from antidote_tpu.store.typed_table import TypedTable
    from benchmarks import work_model
    for cfg_file in sorted(os.listdir(os.path.join(HERE, "configs"))):
        conf = json.load(open(os.path.join(HERE, "configs", cfg_file)))
        w, ty = conf["widths"], conf["fill"]["type"]
        cfg = AntidoteConfig(n_shards=2, keys_per_table=8,
                             max_dcs=w["max_dcs"], set_slots=w["set_slots"],
                             ops_per_key=w["ops_per_key"],
                             snap_versions=w["snap_versions"])
        t = TypedTable(get_type(ty), cfg)
        rows = cfg.n_shards * cfg.keys_per_table

        def per_row(arrs):
            return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                       for a in arrs) // rows
        head = per_row(list(t.head.values()) + [t.head_vc])
        fold = per_row(list(t.snap.values()) + [t.snap_vc, t.snap_seq,
                                                t.ops_a, t.ops_b, t.ops_vc,
                                                t.ops_origin])
        ok(head == work_model.head_row_bytes(ty, w),
           f"{conf['name']}: head row = {head} B as TypedTable allocates")
        ok(fold == work_model.fold_row_bytes(ty, w),
           f"{conf['name']}: fold row = {fold} B as TypedTable allocates")
        shards, kpt = w["shards"], w["keys_per_table"]
        ok(conf["rows_allocated"] == shards * kpt,
           f"{conf['name']}: rows_allocated = shards x keys_per_table")
        sa = conf["serve_args"]
        ok(sa[sa.index("--shards") + 1] == str(shards)
           and sa[sa.index("--keys-per-table") + 1] == str(kpt),
           f"{conf['name']}: serve_args agree with widths")


GEO_SET = {"type": "set_aw", "bucket": "b", "key_prefix": "k",
           "fill_keys": 4, "add_all_elements": 2, "remove_every": 10}
GEO_COUNTER = {"type": "counter_pn", "bucket": "b", "key_prefix": "k",
               "fill_keys": 4}
GEO_SEED = 4294967311


def geo_cases():
    """Synthetic histories for the comparison's rules: (name, fill, logs,
    readback, n_dcs, the checks' values).  An update is (key, op, arg,
    sent, acked, recorded, client, DC), a read (key, in a transaction,
    sent, answered, s, r, answer, DC); client c is homed at DC c mod 2."""
    from benchmarks import data
    f = lambda i: sorted(data.fill_value(GEO_SET, GEO_SEED, i))  # noqa: E731

    def add(i, e, sent, acked, cid=1):
        return (i, "add", e, sent, acked, True, cid, cid % 2)

    def rm(i, e, sent, acked, cid=1):
        return (i, "remove", e, sent, acked, True, cid, cid % 2)

    def read(i, val, dc, s=2.0, r=2.1):
        return (i, False, s, r, s, r,
                tuple(val) if isinstance(val, list) else val, dc)

    def log(updates, reads):
        return [{"updates": updates, "reads": reads, "never": 0}]

    back = lambda *per: [list(p) for p in per]  # noqa: E731
    n = data.fill_value(GEO_COUNTER, GEO_SEED, 1)
    c1 = [add(1, "c1:1", 1.0, 1.1)]
    both = [(1, f(1) + ["c1:1"])]
    out = [
        # DC 0 has not seen DC 1's add yet, DC 1 shows it: both legal
        ("stale remote read is legal", GEO_SET,
         log(c1, [read(1, f(1), 0), read(1, f(1) + ["c1:1"], 1)]),
         back(both, both), 2,
         {"window_wrong": 0, "readback_wrong": 0, "causal_wrong": 0}),
        # the add was acknowledged at DC 1, and DC 1 does not show it
        ("missing home acknowledgement", GEO_SET,
         log(c1, [read(1, f(1), 1)]), back(both, both), 2,
         {"window_wrong": 1, "readback_wrong": 0, "causal_wrong": 0}),
        # DC 0 shows client 1's later add without its earlier one, and
        # an add that came after a remove without the remove
        ("reordered origin", GEO_SET,
         log(c1 + [add(1, "c1:2", 1.5, 1.6), add(2, "c1:3", 1.0, 1.1),
                   rm(2, "c1:3", 1.2, 1.3), add(2, "c1:4", 1.5, 1.6)],
             [read(1, f(1) + ["c1:2"], 0),
              read(2, f(2) + ["c1:3", "c1:4"], 0)]),
         back([(1, f(1) + ["c1:1", "c1:2"]), (2, f(2) + ["c1:4"])],
              [(1, f(1) + ["c1:1", "c1:2"]), (2, f(2) + ["c1:4"])]), 2,
         {"window_wrong": 0, "readback_wrong": 0, "causal_wrong": 2}),
        # DC 0 never applied DC 1's add
        ("lost remote commit", GEO_SET, log(c1, [read(1, f(1), 0)]),
         back([(1, f(1))], both), 2,
         {"window_wrong": 0, "readback_wrong": 1, "causal_wrong": 0}),
        # counters: DC 0 may lag DC 1's increment, DC 1 may not
        ("counter bound by the home DC", GEO_COUNTER,
         log([(1, "increment", 5, 1.0, 1.1, True, 1, 1)],
             [read(1, n, 0), read(1, n, 1)]),
         back([(1, n + 5)], [(1, n + 5)]), 2,
         {"window_wrong": 1, "readback_wrong": 0, "causal_wrong": 0}),
        # one DC, the tuples without a DC as before geo-replication: an
        # acknowledged add missing, an element nobody added, an answer
        # that never came (not compared), an unsure key skipped at the
        # read-back and one wrong there; no causal_wrong
        ("one DC as before", GEO_SET,
         [{"updates": [(1, "add", "c0:1", 1.0, 1.1, True, 0),
                       (2, "add", "c1:1", 1.0, None, True, 1)],
           "reads": [(1, False, 2.0, 2.1, 2.0, 2.1, tuple(f(1))),
                     (1, False, 2.0, 2.1, 2.0, 2.1,
                      tuple(f(1) + ["c0:1", "zzz"])),
                     (3, False, 2.0, None, 0.0, 0.0, None)],
           "never": 1}],
         [(1, f(1) + ["c0:1"]), (2, []), (3, [])], 1,
         {"window_wrong": 2, "readback_wrong": 1, "never_answered": 1,
          "nothing_compared": 0}),
    ]
    return out


def check_geo_rules():
    from benchmarks import check
    for name, fill, logs, back, n_dcs, want in geo_cases():
        checks, _counts = check.compare(fill, GEO_SEED, logs, back, n_dcs)
        got = {k: checks[k]["value"] for k in want}
        ok(got == want and ("causal_wrong" in checks) == (n_dcs > 1),
           f"geo rules: {name} reads {got}")


def check_benchmark_json():
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    ok(set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                  "end_to_end", "per_layer"}, "BENCHMARK.json: exactly its keys")
    ok(b["paths"] == ["benchmarks"] and 1 <= b["run_seconds"] <= 51,
       "BENCHMARK.json: paths and run_seconds")
    cfgs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"]: w for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for c in b["configs"]:
        conf = json.load(open(os.path.join(ROOT, c["file"])))
        ok(NAME.match(c["name"]) and conf["name"] == c["name"]
           and conf["source"] == c["source"] and len(c["source"]) <= 200
           and len(c["why"]) <= 200
           and sorted(conf["reduced"]) == sorted(c["reduced"]),
           f"config {c['name']}: entry and file agree")
    for w in b["workloads"]:
        ok(NAME.match(w["name"]) and w["config"] in cfgs
           and os.path.exists(os.path.join(HERE, "traffic",
                                           w["traffic"] + ".json"))
           and w["chips"] in (1, 4) and len(w["why"]) <= 200
           and json.load(open(os.path.join(
               ROOT, cfgs[w["config"]]["file"])))["chips"] == w["chips"],
           f"cell {w['name']}: its config, mix and chips")
    ok("setup_s" in e2e and all(0.01 <= m["bound"] <= 0.25
                                for m in b["end_to_end"]),
       "end_to_end: setup_s is there, bounds within 1%..25%")
    for m in b["end_to_end"] + b["per_layer"]:
        ok(NAME.match(m["name"]) and UNIT.match(m["unit"])
           and m["better"] in ("lower", "higher")
           and all(x in cells for x in m.get("workloads", [])),
           f"metric {m['name']}: name, unit, better, workloads")
    for m in b["per_layer"]:
        base = os.path.join(HERE, "layer_metrics", m["name"])
        ok(m["moves"] in e2e and (os.path.exists(base + ".json")
                                  or os.path.exists(base + ".py")),
           f"per-layer {m['name']}: moves an end-to-end metric, has a reader")
        if os.path.exists(base + ".json"):
            f = json.load(open(base + ".json"))
            ok(all(f[k] == m[k] for k in ("name", "layer", "unit", "better",
                                          "moves", "workloads")),
               f"per-layer {m['name']}: file and entry agree")
        for cell in m["workloads"]:
            moved = e2e[m["moves"]]
            ok(cell in moved.get("workloads", list(cells)),
               f"per-layer {m['name']}: {cell} reports {m['moves']}")
    peaks = json.load(open(os.path.join(HERE, "peaks.json")))
    ok(peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
       and "source" in peaks["TPU v5 lite"], "peaks.json: v5e, with its source")


def check_readme_example():
    from benchmarks import loadgen
    from benchmarks.readers import status_delta
    text = open(os.path.join(HERE, "README.md")).read()
    blocks = dict(re.findall(r"```json example:(\w+)\n(.*?)```", text, re.S))
    ok(set(blocks) == {"traffic", "layer_metric", "workload", "per_layer"},
       "README: the worked example's four blocks are there")
    mix = json.loads(blocks["traffic"])
    atoms = loadgen.atoms(mix)
    ok(len(atoms) == loadgen.BLOCK
       and set(atoms) == {("static_read", None)},
       "README example: the mix loads into request atoms")
    loadgen.KeyDraw(mix["keys"], 1000, lambda r: r)
    met = json.loads(blocks["layer_metric"])
    pre = {"pipeline": {"native": {"sheds": 1, "frames": 100}}}
    post = {"pipeline": {"native": {"sheds": 3, "frames": 300}}}
    ctx = type("C", (), {"status": {"window": [pre, post]}})()
    ok(status_delta.read(met, ctx) == 1.0,
       "README example: the metric file reads through status_delta")
    w, pl = json.loads(blocks["workload"]), json.loads(blocks["per_layer"])
    ok(NAME.match(w["name"]) and len(w["why"]) <= 200
       and w["traffic"] == mix["name"] and pl["name"] == met["name"]
       and pl["workloads"] == [w["name"]]
       and set(pl) == {"name", "unit", "better", "source", "layer", "moves",
                       "workloads"},
       "README example: the two BENCHMARK.json entries fit the schema")


def check_rehearsals():
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in b["workloads"]:
        p = subprocess.run(
            [sys.executable, os.path.join(HERE, "run.py"), "--workload",
             w["name"], "--seed", "4294967311", "--seconds", "3", "--trace",
             "0", "--rehearse"], cwd=ROOT, capture_output=True, text=True,
            timeout=900)
        last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
        ok(p.returncode == 1 and last.startswith("{"),
           f"rehearsal of {w['name']}: exit 1 with a result line")
        line = json.loads(last)
        bad = {k for k, c in line["checks"].items() if c["value"] > c["limit"]}
        ok(line["correct"] is False and bad == {"wrong_device"},
           f"rehearsal of {w['name']}: correct false, only for the device "
           f"(failed checks {sorted(bad)})")


def main() -> int:
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    check_stats()
    check_status_delta()
    check_trace_reduce()
    check_geo_rules()
    check_benchmark_json()
    check_readme_example()
    check_work_model()
    if "--quick" not in sys.argv[1:]:
        check_rehearsals()
    print("selfcheck passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
