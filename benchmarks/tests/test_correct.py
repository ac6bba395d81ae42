"""The comparison that decides `correct` must fail what it should.

    python -m pytest benchmarks/tests -q        (CPU, no chip, ~3 minutes)

Controls: the plain reference in the program's place, with one guarantee of
the configuration broken, must read `correct: false`; sound, it must read
`correct: true`.  Faults: the program itself (off-TPU, so the look for a chip
is skipped with --rehearse) with the timed path broken underneath -- an
answer altered where it is produced, an update acknowledged with the state
unchanged -- must fail a check other than the missing device.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_cell(workload, *extra, seed=20260930):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", workload, "--seed", str(seed), "--seconds", "2",
         "--trace", "0", "--rehearse", "--unlisted", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    lines = p.stdout.strip().splitlines()
    assert lines and lines[-1].startswith("{"), p.stderr[-2000:]
    line = json.loads(lines[-1])
    failed = {k for k, c in line["checks"].items() if c["value"] > c["limit"]}
    assert (p.returncode == 0) == line["correct"]
    assert line["correct"] == (not failed)
    return line, failed


CELLS = ["set_aw_1m.read_zipf", "counter_pn_10k_pyfe.update_read",
         "set_aw_1m.mixed_zipf"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_reference_is_correct(workload):
    line, failed = run_cell(workload, "--control", "none")
    assert line["correct"] and not failed
    assert line["attempted"] > 100 and line["failed"] == 0


@pytest.mark.parametrize("workload,broken,must_fail", [
    ("set_aw_1m.read_zipf", "stale_reads", "window_wrong"),
    ("counter_pn_10k_pyfe.update_read", "lose_acked", "readback_wrong"),
    ("counter_pn_10k_pyfe.update_read", "stale_reads", "window_wrong"),
    ("set_aw_1m.mixed_zipf", "lose_acked", "readback_wrong"),
    ("set_aw_1m.mixed_zipf", "txn_reads_head", "window_wrong"),
    ("set_aw_1m.mixed_zipf", "stale_reads", "window_wrong"),
])
def test_control_is_not_correct(workload, broken, must_fail):
    line, failed = run_cell(workload, "--control", broken)
    assert not line["correct"] and must_fail in failed


@pytest.mark.parametrize("workload,fault,must_fail", [
    ("set_aw_1m.read_zipf", "alter_answer", "window_wrong"),
    ("set_aw_1m.read_zipf", "drop_update", "readback_wrong"),
    ("counter_pn_10k_pyfe.update_read", "alter_answer", "window_wrong"),
    ("counter_pn_10k_pyfe.update_read", "drop_update", "readback_wrong"),
])
def test_planted_fault_is_not_correct(workload, fault, must_fail):
    line, failed = run_cell(workload, "--fault", fault, "--every", "2")
    assert not line["correct"] and must_fail in failed


def test_program_rehearsal_fails_only_for_the_device():
    line, failed = run_cell("counter_pn_10k_pyfe.update_read")
    assert failed == {"wrong_device"}
