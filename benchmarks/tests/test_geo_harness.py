"""The harness under geo-replication: a configuration with `dcs` (one
`console serve --interdc` a DC, clients homed at every DC) and the
comparison's rules for it.

    python -m pytest benchmarks/tests/test_geo_harness.py -q   (CPU, ~7 min)

The rules on synthetic histories (`selfcheck.geo_cases`); the reference over
three DCs, sound and with each geo guarantee broken; and one `--rehearse`
run of the program over three DCs with cell 5's mix file and small tables.
Every run has a time limit of its own.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import check, selfcheck  # noqa: E402

CASES = selfcheck.geo_cases()


@pytest.mark.parametrize("name,fill,logs,back,n_dcs,want", CASES,
                         ids=[c[0] for c in CASES])
def test_geo_rules_on_synthetic_histories(name, fill, logs, back, n_dcs,
                                          want):
    checks, _counts = check.compare(fill, selfcheck.GEO_SEED, logs, back,
                                    n_dcs)
    assert {k: checks[k]["value"] for k in want} == want
    assert ("causal_wrong" in checks) == (n_dcs > 1)


def geo_config(tmp_path):
    """Cell 5's configuration with `dcs` and small tables, written for the
    test: the reference's three DCs on the four-chip host (DC 0 a mesh of
    two, rehearsed on one device), no chip-specific environment."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "set_aw_1m_rw.json")) as f:
        conf = json.load(f)
    conf.update(name="geo3", chips=4)
    conf["dcs"] = [{"dc_id": 0, "serve_args": ["--mesh-devices", "2"],
                    "env": {}, "chips": 2,
                    "rehearse": {"serve_args": ["--mesh-devices", "1"]}},
                   {"dc_id": 1, "serve_args": [], "env": {}, "chips": 1},
                   {"dc_id": 2, "serve_args": [], "env": {}, "chips": 1}]
    conf["rehearse"] = {"serve_args": ["--sync-log", "--shards", "4",
                                       "--keys-per-table", "256",
                                       "--max-in-flight-per-client", "256"],
                        "fill_keys": 768, "batch_keys": 256}
    path = tmp_path / "geo3.json"
    path.write_text(json.dumps(conf))
    return str(path)


def run_geo(config, *extra, seed=4294967311, limit=400):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "geo3.mixed_zipf", "--unlisted", "--config-file",
         config, "--seed", str(seed), "--seconds", "3", "--trace", "0",
         "--rehearse", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=limit)
    lines = p.stdout.strip().splitlines()
    why = [ln for ln in p.stderr.splitlines()
           if "Error" in ln or "NO RESULT" in ln][-5:]
    assert lines and lines[-1].startswith("{"), "\n".join(lines[-8:] + why)
    line = json.loads(lines[-1])
    failed = {k for k, c in line["checks"].items() if c["value"] > c["limit"]}
    assert (p.returncode == 0) == line["correct"] == (not failed)
    assert line["geo"]["dcs"] == 3
    return line, failed


@pytest.mark.parametrize("broken,must_fail", [
    ("none", None),
    ("remote_lost", "readback_wrong"),
    ("causal_reorder", "causal_wrong"),
    ("home_stale", "window_wrong"),
])
def test_reference_over_three_dcs(tmp_path, broken, must_fail):
    line, failed = run_geo(geo_config(tmp_path), "--control", broken,
                           "--every", "20")
    if must_fail is None:
        assert line["correct"] and line["attempted"] > 100
        assert line["failed"] == 0 and line["geo"]["converge_s"] is not None
    else:
        assert not line["correct"] and must_fail in failed


def test_program_over_three_dcs_fails_only_for_the_device(tmp_path):
    """Three `console serve --interdc` processes, wired over the protocol,
    filled by shares, cell 5's mix from clients homed at every DC: every
    check holds but the device's (a CPU rehearsal).  It fails while the
    program's locked-plane reads race the remote ingress (PERF.md §7,
    fault 0: "Buffer has been deleted or donated" at a DC)."""
    line, failed = run_geo(geo_config(tmp_path), limit=150)
    assert failed == {"wrong_device"}, line["checks"]
    assert line["device"]["count"] == 3 and line["attempted"] > 100
