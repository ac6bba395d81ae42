#!/usr/bin/env python
"""Celebrity-key materializer benchmark (ISSUE 15): ONE key, a
million-op log, every fold strategy.

The scenario the sequence-parallel materializer exists for: a single
hot key whose op log dwarfs the ring, replayed at read time.  The child
(fresh backend, 8 forced virtual CPU devices) builds one add-only
set_aw log of L committed ops (bottom base, <= set_slots distinct
elements — the store's slot-promotion invariant) and times every
strategy the store can route it to:

  serial      — fold.fold_key, the masked one-op-at-a-time scan oracle
  assoc       — longlog.assoc_fold, one O(log L)-depth delta window
  long        — longlog.fold_long, chunked scan (fold_chunk-sized)
  mesh_assoc  — longlog.sharded_assoc_fold_fn over the 8-device mesh
                (op axis sharded, deltas merged in sequence order)
  pallas_ring — the Pallas set_aw ring kernel at the same op volume,
                reshaped to [L/K, K] independent rings: a kernel-rate
                proxy (the kernel serves ring folds, not over-ring
                replays), parity-pinned against fold_batch separately

Parity: serial / assoc / long / mesh_assoc states must be
byte-identical on the SAME log.  While the giant assoc fold runs, a
small serving store keeps taking epoch-plane snapshot reads from
concurrent reader threads — the bench records reader throughput during
the fold vs idle (the fold must not wedge the serving plane).

The parent freezes BENCH_HOTKEY_cpu.json.  --assert-bounds is
STRUCTURAL in --smoke (parity clean, every strategy ran, readers
progressed) and NEVER a throughput ratchet; the full freeze run
additionally asserts the ISSUE 15 acceptance floor — assoc and
mesh_assoc >= 4x faster than the serial scan on this CPU proxy.

Usage:
  python tools/bench_hotkey.py --smoke --assert-bounds   # CI gate
  python tools/bench_hotkey.py --json BENCH_HOTKEY_cpu.json  # freeze
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time

_T0 = time.time()
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

HOST_NOTE = (
    "2-core shared CPU container: the 8 mesh 'devices' are XLA "
    "host-platform threads multiplexed over 2 cores with co-tenant "
    "load, so mesh_assoc measures the sequence-sharding STRUCTURE, not "
    "chip scaling, and adjacent windows swing several x.  The "
    "speedup_vs_serial figures compare compiled XLA programs on the "
    "same host and are the frozen CPU proxy for the ROADMAP item-6 "
    "giant-key target; real-TPU numbers are the success metric."
)


def log(*a):
    print(f"[hotkey {time.time() - _T0:6.1f}s]", *a, file=sys.stderr,
          flush=True)


# ---------------------------------------------------------------------------
# child: one fresh backend, every strategy over the same giant log
# ---------------------------------------------------------------------------
def run_child(l_ops: int, repeats: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from antidote_tpu.config import (AntidoteConfig,
                                     enable_compilation_cache)

    enable_compilation_cache()
    from antidote_tpu.crdt import get_type
    from antidote_tpu.materializer import fold as fold_mod
    from antidote_tpu.materializer import longlog
    from antidote_tpu.materializer import pallas_kernels as pk
    from antidote_tpu.parallel import make_mesh
    from antidote_tpu.store.kv import Effect, KVStore

    cfg = AntidoteConfig(
        n_shards=8, max_dcs=2, ops_per_key=8, set_slots=8,
        keys_per_table=4096, batch_buckets=(64, 512),
    )
    ty = get_type("set_aw")
    d, k, e = cfg.max_dcs, cfg.ops_per_key, cfg.set_slots
    chunk = cfg.fold_chunk
    assert l_ops % chunk == 0 and l_ops % 8 == 0 and l_ops % k == 0

    # -- the celebrity log: L committed add-only ops over 6 elements ----
    rng = np.random.default_rng(15)
    handles = rng.integers(1, 7, size=(l_ops,)).astype(np.int64)
    handles *= 0x1_0000_0003
    ops_a = handles[:, None]
    ops_b = np.zeros((l_ops, 1 + d), np.int32)  # all adds
    ops_origin = rng.integers(0, d, size=(l_ops,)).astype(np.int32)
    ops_vc = rng.integers(0, 1 << 20, size=(l_ops, d)).astype(np.int32)
    ops_vc[np.arange(l_ops), ops_origin] = rng.integers(
        1, 1 << 20, size=(l_ops,))
    base_vc = np.zeros((d,), np.int32)
    read_vc = np.full((d,), 1 << 21, np.int32)
    state0 = {f: jnp.zeros(s, dt)
              for f, (s, dt) in ty.state_spec(cfg).items()}
    ja, jb, jv, jo = map(jnp.asarray, (ops_a, ops_b, ops_vc, ops_origin))
    jbase, jread = jnp.asarray(base_vc), jnp.asarray(read_vc)
    n_ops = jnp.int32(l_ops)

    def timed(label, fn, reps):
        out = fn()
        jax.block_until_ready(out)  # warmup = compile
        best = float("inf")
        for _ in range(max(reps, 1)):
            t0 = time.monotonic()
            out = fn()
            jax.block_until_ready(out)
            best = min(best, time.monotonic() - t0)
        log(f"{label:12s} {best * 1e3:10.1f} ms "
            f"({l_ops / best / 1e6:8.2f} Mops/s)")
        return out, best

    # -- a small serving store + concurrent snapshot readers ------------
    store = KVStore(cfg)
    aw, bw = (get_type("counter_pn").eff_a_width(cfg),
              get_type("counter_pn").eff_b_width(cfg))
    n_keys, counter = 2048, 0
    effs, vcs = [], []
    for kk in range(n_keys):
        counter += 1
        effs.append(Effect(kk, "counter_pn", "b",
                           np.full(aw, kk % 97 + 1, np.int64),
                           np.zeros(bw, np.int32)))
        vcs.append(np.asarray([counter, 0], np.int32))
    store.apply_effects(effs, vcs, [0] * len(effs))
    store.publish_serving_epoch(store.dc_max_vc())

    reads = {"n": 0}
    stop = threading.Event()

    def reader():
        r = np.random.default_rng(threading.get_ident() % 2**32)
        while not stop.is_set():
            objs = [(int(x), "counter_pn", "b")
                    for x in r.integers(0, n_keys, size=256)]
            ep = store.pin_serving_epoch()
            pending, fb = store.epoch_read_launch(objs, ep)
            vals = store.epoch_read_finish(pending)
            store.unpin_serving_epoch(ep)
            assert not fb and len(vals) == 256
            reads["n"] += 256

    # idle reader rate (no fold competing)
    threads = [threading.Thread(target=reader, daemon=True)
               for _ in range(2)]
    for t in threads:
        t.start()
    time.sleep(0.8)
    idle_reads = reads["n"]
    idle_rate = idle_reads / 0.8

    # -- the strategies over the same log --------------------------------
    results: dict = {}
    parity: dict = {}

    serial_fn = jax.jit(lambda: fold_mod.fold_key(
        ty, cfg, state0, ja, jb, jv, jo, n_ops, jbase, jread))
    (ref_state, ref_applied), s_serial = timed(
        "serial", serial_fn, max(repeats - 1, 1))
    results["serial"] = s_serial

    t_fold0 = time.monotonic()
    assoc_fn = jax.jit(lambda: longlog.assoc_fold(
        ty, cfg, state0, ja, jb, jv, jo, n_ops, jbase, jread))
    (assoc_state, assoc_applied), s_assoc = timed(
        "assoc", assoc_fn, repeats)
    results["assoc"] = s_assoc
    during_span = time.monotonic() - t_fold0

    long_fn = jax.jit(lambda: longlog.fold_long(
        ty, cfg, state0, ja, jb, jv, jo, n_ops, jbase, jread,
        chunk=chunk))
    (long_state, long_applied), s_long = timed("long", long_fn, repeats)
    results["long"] = s_long

    mesh = make_mesh(8)
    mesh_fn = longlog.sharded_assoc_fold_fn(ty, cfg, mesh)
    (mesh_state, mesh_applied), s_mesh = timed(
        "mesh_assoc",
        lambda: mesh_fn(state0, ja, jb, jv, jo, l_ops, jbase, jread),
        repeats)
    results["mesh_assoc"] = s_mesh

    # reader progress while the giant folds were running
    during_reads = reads["n"] - idle_reads
    stop.set()
    for t in threads:
        t.join(timeout=5)

    # -- Pallas ring-rate proxy: same op volume as [L/K, K] rings --------
    b_rings = l_ops // k
    ra = ops_a.reshape(b_rings, k, 1)
    rb = ops_b.reshape(b_rings, k, 1 + d)
    rv = ops_vc.reshape(b_rings, k, d)
    ro = ops_origin.reshape(b_rings, k)
    rn = np.full((b_rings,), k, np.int32)
    rbase = np.zeros((b_rings, d), np.int32)
    rread = np.broadcast_to(read_vc, (b_rings, d)).copy()
    rstate = {f: jnp.zeros((b_rings,) + s, dt)
              for f, (s, dt) in ty.state_spec(cfg).items()}
    jra, jrb, jrv, jro, jrn, jrbase, jrread = map(
        jnp.asarray, (ra, rb, rv, ro, rn, rbase, rread))
    (p_state, p_applied), s_pallas = timed(
        "pallas_ring",
        lambda: pk.set_aw_fold(
            rstate, jra, jrb, jrv, jro, jrn, jrbase, jrread),
        repeats)
    results["pallas_ring"] = s_pallas
    # parity for the kernel: oracle fold_batch over a slice of rings
    nb = 64
    oracle_state, oracle_applied = fold_mod.fold_batch(
        ty, cfg, {f: x[:nb] for f, x in rstate.items()},
        jra[:nb], jrb[:nb], jrv[:nb], jro[:nb], jrn[:nb],
        jrbase[:nb], jrread[:nb])
    parity["pallas_ring"] = bool(
        all(np.array_equal(np.asarray(oracle_state[f]),
                           np.asarray(p_state[f][:nb]))
            for f in oracle_state)
        and np.array_equal(np.asarray(oracle_applied),
                           np.asarray(p_applied[:nb])))

    # -- byte parity across the over-ring strategies ---------------------
    ref_np = {f: np.asarray(x) for f, x in ref_state.items()}
    for name, (st, ap) in (("assoc", (assoc_state, assoc_applied)),
                           ("long", (long_state, long_applied)),
                           ("mesh_assoc", (mesh_state, mesh_applied))):
        parity[name] = bool(
            all(np.array_equal(ref_np[f], np.asarray(st[f]))
                for f in ref_np)
            and int(ap) == int(ref_applied))

    strategies = {
        name: {
            "seconds": round(s, 4),
            "mops_per_s": round(l_ops / s / 1e6, 2),
            "speedup_vs_serial": round(s_serial / s, 2),
        }
        for name, s in results.items()
    }
    return {
        "l_ops": l_ops,
        "distinct_elements": 6,
        "fold_chunk": chunk,
        "applied": int(ref_applied),
        "strategies": strategies,
        "parity": parity,
        "readers": {
            "threads": 2,
            "idle_reads_per_s": round(idle_rate, 1),
            "during_fold_reads_per_s": round(
                during_reads / during_span, 1) if during_span else 0.0,
            "during_fold_reads": int(during_reads),
        },
    }


# ---------------------------------------------------------------------------
# parent: fresh-backend child, artifact freeze, gates
# ---------------------------------------------------------------------------
def run_parent(args) -> int:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    log(f"child: one set_aw key, {args.l_ops} ops")
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--one",
         "--l-ops", str(args.l_ops), "--repeats", str(args.repeats)],
        capture_output=True, text=True, cwd=_REPO, env=env, timeout=1800,
    )
    sys.stderr.write(out.stderr)
    if out.returncode != 0:
        log("child FAILED")
        return 1
    res = json.loads(out.stdout.strip().splitlines()[-1])

    artifact = {
        "metric": "hotkey_fold_strategies",
        "unit": "one set_aw key, L-op over-ring log: seconds per full "
                "fold by strategy (+ concurrent snapshot-reader rate)",
        "driver_rev": 1,
        "result": res,
        "target": {
            "metric": "assoc + mesh_assoc >= 4x the serial scan on the "
                      "giant-key replay (ISSUE 15); real-TPU sequence "
                      "sharding is the ROADMAP item-6 success metric",
            "cpu_proxy": "frozen on the shared container; the smoke "
                         "gate is structural only",
        },
        "host_note": HOST_NOTE,
        "smoke": bool(args.smoke),
        "created_at": time.time(),
    }
    if args.json:
        path = os.path.join(_REPO, args.json)
        with open(path, "w") as f:
            json.dump(artifact, f, indent=1)
            f.write("\n")
        log(f"froze {args.json}")
    else:
        print(json.dumps(artifact, indent=1))

    if args.assert_bounds:
        st = res["strategies"]
        for name in ("serial", "assoc", "long", "mesh_assoc",
                     "pallas_ring"):
            assert name in st and st[name]["seconds"] > 0, (
                name, "strategy missing / zero time")
        for name, ok in res["parity"].items():
            assert ok, (name, "parity broke")
        assert res["readers"]["during_fold_reads"] > 0, (
            "snapshot readers starved during the giant fold")
        if not args.smoke:
            # the ISSUE 15 acceptance floor — full freeze runs only;
            # the CI smoke gate stays structural (never a ratchet)
            assert st["assoc"]["speedup_vs_serial"] >= 4, st["assoc"]
            assert st["mesh_assoc"]["speedup_vs_serial"] >= 4, (
                st["mesh_assoc"])
        log("gates OK")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--one", action="store_true",
                    help="(internal) run the child measurement")
    ap.add_argument("--l-ops", type=int, default=1_048_576)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--smoke", action="store_true",
                    help="64k-op log, structural gates (CI)")
    ap.add_argument("--assert-bounds", action="store_true")
    ap.add_argument("--json", default=None,
                    help="freeze the artifact to this repo-relative path")
    args = ap.parse_args(argv)
    if args.smoke and args.l_ops == 1_048_576:
        args.l_ops = 65_536
    if args.one:
        print(json.dumps(run_child(args.l_ops, args.repeats)))
        return 0
    return run_parent(args)


if __name__ == "__main__":
    sys.exit(main())
