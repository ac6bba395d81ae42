#!/usr/bin/env python
"""Restart benchmark: full-WAL replay vs checkpoint + tail (ISSUE 8).

Flow (each phase a fresh subprocess so wall-clocks are honest —
SIGKILL'd populate, cold recoveries):

  1. populate   — N counter keys through the durable commit path
                  (WAL append + device scatter), then SIGKILL itself:
                  exactly what a crashed server leaves behind.
  2. recover-full — boot with recover=True BEFORE any checkpoint
                  exists: the seed behavior, whole-WAL replay.  Emits a
                  state digest (values sample, op-id chains, append
                  sequences, stable VC).
  3. checkpoint — recover again, publish one checkpoint (image bytes,
                  WAL bytes reclaimed), SIGKILL itself mid-flight after
                  more tail writes land.
  4. recover-fast — boot from (image + tail); time it, digest it.

The parent asserts the two digests are byte-identical (adjusted for the
tail writes), takes best-of-N for both recovery numbers, and — with
--json — freezes BENCH_RESTART_cpu.json (no ratchet: the artifact
records, the smoke gate only asserts structure: fast < full, exact
state, bytes reclaimed).

Usage:
  python tools/bench_restart.py --smoke --assert-bounds   # CI gate
  python tools/bench_restart.py --keys 1000000 --json BENCH_RESTART_cpu.json
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

_T0 = time.time()
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

#: tail writes landed between the checkpoint and the kill — the fast
#: restart must replay exactly these on top of the image
TAIL_WRITES = 512


def log(*a):
    print(f"[restart {time.time() - _T0:7.1f}s]", *a, file=sys.stderr,
          flush=True)


def _cfg(n_keys: int):
    from antidote_tpu.config import AntidoteConfig

    return AntidoteConfig(
        n_shards=16, max_dcs=4, keys_per_table=max(n_keys // 16, 1024),
        wal_segments=4,
    )


def _mk_node(n_keys: int, log_dir: str, recover: bool):
    from antidote_tpu.api import AntidoteNode

    return AntidoteNode(_cfg(n_keys), log_dir=log_dir, recover=recover)


def _digest(node, n_keys: int) -> dict:
    """Byte-identical-recovery digest: sampled values + chain state."""
    sample = list(range(0, n_keys, max(n_keys // 512, 1)))
    objs = [(k, "counter_pn", "b") for k in sample]
    vals, _ = node.read_objects(objs)
    return {
        "sample_keys": sample[:4] + sample[-4:],
        "sample_sum": int(sum(vals)),
        "sample_vals": [int(v) for v in vals[:16]],
        "keys": len(node.store.directory),
        "op_ids": node.store.log.op_ids.tolist(),
        "seqs": node.store.log.seqs.tolist(),
        "stable": [int(x) for x in node.stable_vc()],
        "commit_counter": int(node.txm.commit_counter),
    }


def _wal_bytes(log_dir: str) -> int:
    return sum(
        os.path.getsize(os.path.join(log_dir, f))
        for f in os.listdir(log_dir) if f.endswith(".wal")
    )


def _populate(node, n_keys: int, start_vc: int = 0):
    """Commit N increments through the durable path in recovery-sized
    batches (the same apply_effects + WAL append machinery a live
    commit drives, minus per-txn wire overhead)."""
    import numpy as np

    from antidote_tpu.store.kv import Effect

    store = node.store
    batch = 4096
    counter = start_vc
    keys = list(range(n_keys))
    for base in range(0, len(keys), batch):
        chunk = keys[base:base + batch]
        counter += 1
        vc = np.zeros(node.cfg.max_dcs, np.int32)
        vc[node.dc_id] = counter
        effs = [
            Effect(k, "counter_pn", "b",
                   np.asarray([1], np.int64), np.asarray([], np.int32))
            for k in chunk
        ]
        store.apply_effects(effs, [vc] * len(effs), [node.dc_id] * len(effs))
    node.txm.commit_counter = counter
    return counter


def _maxrss_mb() -> float:
    import resource

    return round(
        resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024, 1)


def child_main(argv) -> int:
    phase = argv[0]
    n_keys = int(argv[1])
    log_dir = argv[2]
    budget = int(argv[3]) if len(argv) > 3 else 0
    t0 = time.monotonic()
    if phase == "populate-cold":
        # beyond-RAM populate (ISSUE 13): resident rows bounded by the
        # budget, periodic chain stamps (full rebases carry the cold
        # appendix forward), SIGKILL at the end like a real outage
        from antidote_tpu.api import AntidoteNode

        node = AntidoteNode(_cfg(n_keys), log_dir=log_dir, recover=False,
                            resident_rows=budget)
        # evictability anchors to FULL images (delta links carry no
        # sidecar), so worst-case residency = budget + one rebase
        # window of not-yet-covered rows: rebase every other stamp
        # keeps that window at one stamp's writes — O(budget), never
        # O(total keys)
        node.start_checkpointer(interval_s=0.0, rebase_every=2)
        import numpy as np

        from antidote_tpu.store.kv import Effect

        store = node.store
        batch, counter = 4096, 0
        stamp_every = max(budget // 2, 4096)
        since_stamp = 0
        max_resident = 0
        t1 = time.monotonic()
        for base in range(0, n_keys, batch):
            chunk = range(base, min(base + batch, n_keys))
            counter += 1
            vc = np.zeros(node.cfg.max_dcs, np.int32)
            vc[node.dc_id] = counter
            effs = [Effect(k, "counter_pn", "b",
                           np.asarray([1], np.int64),
                           np.asarray([], np.int32)) for k in chunk]
            store.apply_effects(effs, [vc] * len(effs),
                                [node.dc_id] * len(effs))
            since_stamp += len(effs)
            if since_stamp >= stamp_every:
                since_stamp = 0
                node.checkpoint_now()
                max_resident = max(max_resident,
                                   store.cold.resident_rows())
        node.txm.commit_counter = counter
        node.checkpoint_now(full=True)
        store.cold.enforce_budget()
        max_resident = max(max_resident, store.cold.resident_rows())
        print(json.dumps({
            "populate_s": round(time.monotonic() - t1, 2),
            "wal_bytes": _wal_bytes(log_dir),
            "max_resident_rows": int(max_resident),
            "final_resident_rows": int(store.cold.resident_rows()),
            "cold_keys": len(store.cold.cold_set),
            "evictions": int(store.cold.evictions),
            "maxrss_mb": _maxrss_mb(),
        }), flush=True)
        os.kill(os.getpid(), signal.SIGKILL)
    if phase == "recover-cold":
        from antidote_tpu.api import AntidoteNode

        node = AntidoteNode(_cfg(n_keys), log_dir=log_dir, recover=True,
                            resident_rows=budget)
        recover_s = time.monotonic() - t0
        resident_after_install = int(node.store.cold.resident_rows())
        dig = _digest(node, n_keys)  # the sample read faults cold rows in
        print(json.dumps({
            "recover_s": round(recover_s, 2),
            "phase_checkpoint_s": round(
                node.metrics.recovery_seconds.value(phase="checkpoint"),
                3),
            "resident_rows_after_install": resident_after_install,
            "cold_keys_after_install": len(node.store.cold.cold_set)
            + node.store.cold.faults,
            "sample_faults": int(node.store.cold.faults),
            "maxrss_mb": _maxrss_mb(),
            "digest": dig,
        }), flush=True)
        return 0
    if phase == "stamp-compare":
        # incremental-vs-full stamp cost (ISSUE 13): a delta link's
        # cost tracks the dirty set, a full rebase the resident extent
        from antidote_tpu.api import AntidoteNode

        node = _mk_node(n_keys, log_dir, recover=False)
        node.start_checkpointer(interval_s=0.0, rebase_every=1 << 30)
        _populate(node, n_keys)
        t1 = time.monotonic()
        full = node.checkpoint_now(full=True)
        full_s = time.monotonic() - t1
        dirty = max(n_keys // 100, 64)  # 1% dirty working set
        _populate(node, dirty, start_vc=node.txm.commit_counter)
        t1 = time.monotonic()
        delta = node.checkpoint_now(full=False)
        delta_s = time.monotonic() - t1
        print(json.dumps({
            "full_stamp_s": round(full_s, 3),
            "full_bytes": full["image_bytes"],
            "full_rows": full["n_rows"],
            "delta_stamp_s": round(delta_s, 3),
            "delta_bytes": delta["image_bytes"],
            "delta_rows": delta["n_rows"],
            "dirty_writes": dirty,
        }), flush=True)
        return 0
    if phase == "populate":
        node = _mk_node(n_keys, log_dir, recover=False)
        boot_s = time.monotonic() - t0
        t1 = time.monotonic()
        _populate(node, n_keys)
        print(json.dumps({
            "boot_s": round(boot_s, 2),
            "populate_s": round(time.monotonic() - t1, 2),
            "wal_bytes": _wal_bytes(log_dir),
        }), flush=True)
        os.kill(os.getpid(), signal.SIGKILL)  # crash, like a real outage
    if phase == "recover-full" or phase == "recover-fast":
        node = _mk_node(n_keys, log_dir, recover=True)
        recover_s = time.monotonic() - t0
        m = node.metrics
        print(json.dumps({
            "recover_s": round(recover_s, 2),
            "phase_checkpoint_s": round(
                m.recovery_seconds.value(phase="checkpoint"), 3),
            "phase_tail_s": round(
                m.recovery_seconds.value(phase="tail"), 3),
            "records": int(m.recovery_records.value()),
            "digest": _digest(node, n_keys),
        }), flush=True)
        return 0
    if phase == "checkpoint":
        node = _mk_node(n_keys, log_dir, recover=True)
        recover_s = time.monotonic() - t0
        t1 = time.monotonic()
        summary = node.checkpoint_now()
        ckpt_s = time.monotonic() - t1
        # tail: more committed writes AFTER the stamp, then crash — the
        # fast restart must land exactly these on top of the image
        _populate(node, min(TAIL_WRITES, n_keys),
                  start_vc=node.txm.commit_counter)
        print(json.dumps({
            "recover_s": round(recover_s, 2),
            "checkpoint_s": round(ckpt_s, 2),
            "image_bytes": summary["image_bytes"],
            "reclaimed_bytes": summary["reclaimed_bytes"],
            "barrier_ms": summary.get("barrier_ms"),
            "wal_bytes_after": _wal_bytes(log_dir),
        }), flush=True)
        os.kill(os.getpid(), signal.SIGKILL)
    raise SystemExit(f"unknown phase {phase!r}")


def run_child(phase, n_keys, log_dir, timeout_s, budget=0) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    log(f"phase {phase} ...")
    res = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", phase,
         str(n_keys), log_dir, str(budget)],
        stdout=subprocess.PIPE, stderr=sys.stderr, env=env,
        timeout=timeout_s,
    )
    out = res.stdout.decode(errors="replace").strip().splitlines()
    if not out:
        raise RuntimeError(f"phase {phase} produced no output "
                           f"(rc={res.returncode})")
    parsed = json.loads(out[-1])
    log(f"phase {phase}: {parsed if len(str(parsed)) < 300 else '<ok>'}")
    return parsed


def _freeze(args, key: str, result: dict) -> None:
    if not args.json:
        return
    path = os.path.join(_REPO, args.json) \
        if not os.path.isabs(args.json) else args.json
    merged = {}
    if os.path.exists(path):
        with open(path) as f:
            merged = json.load(f)
    merged[key] = result
    with open(path, "w") as f:
        json.dump(merged, f, indent=2)
    log(f"artifact frozen to {path} [{key}]")


def main_coldtier(args) -> int:
    """Beyond-RAM bench leg: populate ``--keys`` counters under a
    ``--resident-rows`` device budget with chain stamps, SIGKILL, then
    a cold recovery whose sample reads fault rows back in.  Structural
    gates only (resident ≤ budget+slack, cold keys exist, sample
    byte-exact) — the frozen numbers are never a ratchet."""
    import tempfile

    n_keys = 100_000 if args.coldtier_smoke else args.keys
    budget = args.resident_rows or max(n_keys // 10, 4096)
    scratch = args.dir or tempfile.mkdtemp(prefix="antidote-cold-")
    log_dir = os.path.join(scratch, "wal")
    timeout_s = 900 if args.coldtier_smoke else 7200
    pop = run_child("populate-cold", n_keys, log_dir, timeout_s,
                    budget=budget)
    rec = run_child("recover-cold", n_keys, log_dir, timeout_s,
                    budget=budget)
    stride = max(n_keys // 512, 1)
    n_sampled = len(range(0, n_keys, stride))
    result = {
        "metric": "coldtier_bounded_rss",
        "n_keys": n_keys,
        "resident_rows_budget": budget,
        "populate": pop,
        "recover": rec,
        "host_note": (
            "structural gates only: resident rows ≤ budget (+ one "
            "commit batch + one uncovered stamp window of slack), cold "
            "keys exist, and the post-recovery sample reads are "
            "byte-exact after faulting their rows back in.  maxrss "
            "includes the interpreter + jax/XLA and the O(total keys) "
            "host directory — the budget bounds DEVICE TABLE rows, "
            "which are the per-key heavyweight (head + snapshot ring + "
            "op ring); never a ratchet."
        ),
    }
    print(json.dumps(result, indent=2))
    _freeze(args, f"coldtier_keys_{n_keys}", result)
    if args.assert_bounds:
        # slack: one in-flight commit batch + one REBASE WINDOW of rows
        # no full image covers yet (evictability anchors to fulls) —
        # O(budget) regardless of total keys
        slack = 4096 + 2 * max(budget // 2, 4096)
        assert pop["max_resident_rows"] <= budget + slack, pop
        assert pop["final_resident_rows"] <= budget, pop
        assert pop["cold_keys"] > 0 and pop["evictions"] > 0, pop
        assert rec["resident_rows_after_install"] <= budget + slack, rec
        assert rec["digest"]["sample_sum"] == n_sampled, rec["digest"]
        assert rec["digest"]["keys"] + rec["cold_keys_after_install"] \
            >= n_keys, rec
        assert rec["sample_faults"] > 0, rec
        log("assert-bounds: all cold-tier structural gates passed")
    return 0


def main_incremental(args) -> int:
    """Incremental-vs-full stamp cost: a delta link's cost must track
    the dirty set (rows == dirty writes), not the table extent."""
    import tempfile

    n_keys = 50_000 if args.smoke else args.keys
    scratch = args.dir or tempfile.mkdtemp(prefix="antidote-incr-")
    log_dir = os.path.join(scratch, "wal")
    cmp_ = run_child("stamp-compare", n_keys, log_dir,
                     600 if args.smoke else 3600)
    result = {
        "metric": "incremental_stamp_cost",
        "n_keys": n_keys,
        **cmp_,
        "full_over_delta_bytes": round(
            cmp_["full_bytes"] / max(cmp_["delta_bytes"], 1), 1),
        "host_note": (
            "structural gates only: the delta link's row count equals "
            "the dirty write set and its bytes/wall-clock undercut the "
            "full rebase — write cost ∝ dirty rows, not table size; "
            "never a ratchet."
        ),
    }
    print(json.dumps(result, indent=2))
    _freeze(args, f"incremental_keys_{n_keys}", result)
    if args.assert_bounds:
        assert cmp_["delta_rows"] == cmp_["dirty_writes"], cmp_
        assert cmp_["delta_bytes"] < cmp_["full_bytes"], cmp_
        assert cmp_["delta_stamp_s"] < cmp_["full_stamp_s"], cmp_
        log("assert-bounds: all incremental structural gates passed")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--keys", type=int, default=1_000_000)
    ap.add_argument("--smoke", action="store_true",
                    help="small keyspace CI gate (~1-2 min)")
    ap.add_argument("--assert-bounds", action="store_true",
                    help="fail unless fast < full, digests identical, "
                         "and WAL bytes were reclaimed")
    ap.add_argument("--best-of", type=int, default=2)
    ap.add_argument("--json", default=None,
                    help="freeze the artifact here (merge-by-n_keys; "
                         "never a ratchet)")
    ap.add_argument("--dir", default=None, help="scratch dir override")
    ap.add_argument("--coldtier", action="store_true",
                    help="beyond-RAM run (ISSUE 13): populate --keys "
                         "under --resident-rows, SIGKILL, recover cold")
    ap.add_argument("--coldtier-smoke", action="store_true",
                    help="small cold-tier CI gate (~1-2 min)")
    ap.add_argument("--incremental", action="store_true",
                    help="incremental-vs-full stamp cost comparison")
    ap.add_argument("--resident-rows", type=int, default=None,
                    help="cold-tier budget (default keys // 10)")
    args, rest = ap.parse_known_args()
    if args.child:
        return child_main(rest)

    if args.coldtier or args.coldtier_smoke:
        return main_coldtier(args)
    if args.incremental:
        return main_incremental(args)

    n_keys = 50_000 if args.smoke else args.keys
    import tempfile

    scratch = args.dir or tempfile.mkdtemp(prefix="antidote-restart-")
    log_dir = os.path.join(scratch, "wal")
    timeout_s = 600 if args.smoke else 3600

    pop = run_child("populate", n_keys, log_dir, timeout_s)
    wal_before = pop["wal_bytes"]

    fulls = [run_child("recover-full", n_keys, log_dir, timeout_s)
             for _ in range(args.best_of)]
    full = min(fulls, key=lambda r: r["recover_s"])

    ck = run_child("checkpoint", n_keys, log_dir, timeout_s)

    fasts = [run_child("recover-fast", n_keys, log_dir, timeout_s)
             for _ in range(args.best_of)]
    fast = min(fasts, key=lambda r: r["recover_s"])

    # byte-identical modulo the known tail: the checkpoint child landed
    # TAIL_WRITES more increments (one per key on the first TAIL_WRITES
    # keys, +1 commit counter lane) after the full-replay measurement
    dig_full, dig_fast = full["digest"], fast["digest"]
    tail_keys = min(TAIL_WRITES, n_keys)
    stride = max(n_keys // 512, 1)
    sampled_tail = len([k for k in range(0, n_keys, stride)
                        if k < tail_keys])
    exact = (
        dig_fast["keys"] == dig_full["keys"]
        and dig_fast["sample_sum"] == dig_full["sample_sum"] + sampled_tail
        and dig_fast["commit_counter"] > dig_full["commit_counter"]
    )
    speedup = full["recover_s"] / max(fast["recover_s"], 1e-9)
    result = {
        "metric": "restart_recovery_wall_clock",
        "n_keys": n_keys,
        "smoke": bool(args.smoke),
        "best_of": args.best_of,
        "populate_s": pop["populate_s"],
        "full_replay_s": full["recover_s"],
        "full_replay_records": full["records"],
        "fast_restart_s": fast["recover_s"],
        "fast_restart_phases": {
            "checkpoint_s": fast["phase_checkpoint_s"],
            "tail_s": fast["phase_tail_s"],
            "tail_records": fast["records"],
        },
        "speedup": round(speedup, 2),
        "checkpoint": {
            "image_bytes": ck["image_bytes"],
            "write_s": ck["checkpoint_s"],
            "stamp_barrier_ms": ck.get("barrier_ms"),
            "wal_bytes_before": wal_before,
            "wal_bytes_after": ck["wal_bytes_after"],
            "reclaimed_bytes": ck["reclaimed_bytes"],
        },
        "byte_identical": exact,
        "host_note": (
            "2-core shared-CPU container (same host class as BENCH_WIRE: "
            "co-tenant load swings adjacent windows; both recovery "
            "numbers are best-of-N cold-process wall clocks incl. "
            "jax/XLA import+init, so the floor is interpreter+backend "
            "boot, not replay).  No ratchet: the smoke gate asserts "
            "structure only (fast < full, byte-identical digest, "
            "reclaimed > 0), never this artifact's numbers."
        ),
    }
    print(json.dumps(result, indent=2))
    if args.json:
        path = os.path.join(_REPO, args.json) \
            if not os.path.isabs(args.json) else args.json
        merged = {}
        if os.path.exists(path):
            with open(path) as f:
                merged = json.load(f)
        merged[f"keys_{n_keys}"] = result
        with open(path, "w") as f:
            json.dump(merged, f, indent=2)
        log(f"artifact frozen to {path}")
    if args.assert_bounds:
        assert exact, (
            f"recovered state diverged: full={dig_full} fast={dig_fast}")
        assert fast["recover_s"] < full["recover_s"], (
            f"fast restart ({fast['recover_s']}s) not faster than full "
            f"replay ({full['recover_s']}s)")
        assert ck["reclaimed_bytes"] > 0, "no WAL bytes reclaimed"
        assert fast["phase_checkpoint_s"] > 0, "fast path not engaged"
        assert fast["records"] <= TAIL_WRITES + 1, (
            f"fast restart replayed {fast['records']} records — more "
            f"than the tail")
        log("assert-bounds: all structural gates passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
