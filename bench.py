#!/usr/bin/env python
"""North-star benchmark: snapshot-read throughput on a 1M-key OR-set.

BASELINE.json workload: ``antidote_crdt_set_aw``, Zipfian access, batched
snapshot reads vs a sequential host materializer re-implementing the
reference's per-key walk (clocksi_materializer:materialize_intern +
apply_operations, /root/reference/src/clocksi_materializer.erl:111-197) in
plain Python with dict vector clocks.

Two numbers are reported (r1 VERDICT items 1-2):

* ``value`` — the SERVING PATH: reads through
  ``TypedTable.read_resolved`` (host shard routing + freshness check +
  snapshot-version select + versioned ring fold + device value
  resolution), with one batch in five at a historical VC so the
  materializer fold (``fold_batch``) is inside the timed loop.  Pipelined
  batches model basho_bench's concurrent workers.
* ``device_kernel_reads_per_s`` — the device-only kernel loop (head gather
  + OR-set presence resolution), isolating what the chip does from what
  the host path around it costs.

Process layout: the parent never touches JAX (one process per chip) and
runs the real bench in a CHILD process with a hard wall-clock timeout.
The bench measures a TPU: a child that finds another platform fails, and
the parent then prints one JSON line with an ``"error"`` field and exits
1 — there is no CPU re-run.

Every phase logs start/end + elapsed on stderr so a timeout localizes
itself; the child arms ``faulthandler.dump_traceback_later`` so a hang
prints the stuck Python stack.  The landing attempt runs ``--pallas off``
(pure XLA), and Pallas is then tried as a separate UPGRADE attempt whose
failure cannot lose the landed number.

Usage: python bench.py [--smoke] [--keys N] [--pallas auto|on|off]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

_T0 = time.time()


def log(*a):
    print(f"[bench {time.time() - _T0:8.1f}s]", *a, file=sys.stderr, flush=True)


METRIC = "serving_read_throughput_set_aw_zipf"


# ---------------------------------------------------------------------------
# parent: orchestration (never touches JAX)
# ---------------------------------------------------------------------------
def _run_attempt(extra_args, timeout_s):
    """Run the child; return (parsed_json | None, note)."""
    cmd = [sys.executable, os.path.abspath(__file__), "--child"] + extra_args
    log(f"parent: attempt {' '.join(extra_args) or '(default)'} "
        f"timeout={timeout_s}s")
    try:
        res = subprocess.run(
            cmd, timeout=timeout_s,
            stdout=subprocess.PIPE, stderr=sys.stderr,
        )
    except subprocess.TimeoutExpired:
        return None, f"timeout after {timeout_s}s"
    out = res.stdout.decode(errors="replace")
    for line in reversed(out.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line), None
            except json.JSONDecodeError:
                continue
    return None, f"child rc={res.returncode}, no JSON line"


def parent(args):
    smoke = ["--smoke"] if args.smoke else []
    t_tpu = int(os.environ.get("ANTIDOTE_BENCH_TPU_TIMEOUT", "1200"))
    t_pallas = int(os.environ.get("ANTIDOTE_BENCH_PALLAS_TIMEOUT", "600"))
    if args.smoke:
        t_tpu, t_pallas = min(t_tpu, 600), min(t_pallas, 300)
    keyarg = ["--keys", str(args.keys)] if args.keys else []
    # By default the landing attempt forces --pallas off and Pallas runs
    # as an upgrade.  An explicit --pallas on/off is honored verbatim
    # (there is then nothing to upgrade to).
    land_pallas = "off" if args.pallas == "auto" else args.pallas
    t_land0 = time.time()
    got, note = _run_attempt(
        smoke + keyarg + ["--pallas", land_pallas], t_tpu
    )
    land_wall = time.time() - t_land0
    if got is None:
        print(json.dumps({
            "metric": METRIC, "value": 0.0, "unit": "reads/s", "vs_baseline": 0.0,
            "error": note,
        }))
        return 1
    # Upgrade attempt: Pallas dispatch ON.  Only replaces the landed
    # result if it finishes AND serves faster.  Budget at least 1.5x the
    # landed run's wall clock + compile margin, so a healthy-but-slower
    # Pallas run isn't misreported as a hang.
    if args.pallas == "auto" and not args.no_pallas_upgrade:
        t_pallas = max(t_pallas, int(land_wall * 1.5) + 120)
        up, unote = _run_attempt(
            smoke + keyarg + ["--pallas", "on"], t_pallas
        )
        if up is not None and up.get("value", 0) > got.get("value", 0):
            up["pallas_upgrade"] = (
                f"+{(up['value'] / max(got['value'], 1) - 1) * 100:.0f}% "
                "over XLA path"
            )
            got = up
        elif up is not None:
            got["pallas_attempt"] = (
                f"completed but not faster ({up.get('value')} reads/s)"
            )
        else:
            got["pallas_attempt"] = f"failed: {unote}"
    print(json.dumps(got))
    return 0


# ---------------------------------------------------------------------------
# child: the measured workload
# ---------------------------------------------------------------------------
def child(args):
    import faulthandler

    # a hang now dumps the stuck Python stack every 180 s instead of
    # burning the whole parent timeout silently (r2 VERDICT weak #1)
    faulthandler.dump_traceback_later(180, repeat=True, file=sys.stderr)

    phases = {}

    class phase:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log(f"phase {self.name}: start")
            self.t = time.perf_counter()
            return self

        def __exit__(self, *exc):
            dt = time.perf_counter() - self.t
            phases[self.name] = round(dt, 2)
            log(f"phase {self.name}: done in {dt:.1f}s")

    with phase("import_jax"):
        import jax

    from antidote_tpu.config import AntidoteConfig, enable_compilation_cache

    enable_compilation_cache()
    from antidote_tpu.crdt import get_type
    from antidote_tpu.store import TypedTable

    n_keys = args.keys or (20_000 if args.smoke else 1_000_000)
    n_shards = 8
    ops_per_key = 3
    pop_batch = 16384
    serve_batch = 16384 if n_keys >= 100_000 else 4096
    device_batch = 4096
    serve_batches = 20 if args.smoke else 60
    device_batches = 100 if args.smoke else 400
    baseline_reads = 500 if args.smoke else 2000
    hist_every = 5  # 1 in 5 serving batches reads at a historical VC

    with phase("backend_init"):
        platform = jax.default_backend()
        n_dev = len(jax.devices())
    if platform != "tpu":
        log(f"child: platform={platform} is not a TPU; this bench "
            "measures the chip and does not run elsewhere")
        return 1
    use_pallas = args.pallas != "off"
    cfg = AntidoteConfig(
        n_shards=n_shards,
        max_dcs=4,
        ops_per_key=16,
        snap_versions=2,
        set_slots=16,
        keys_per_table=(n_keys + n_shards - 1) // n_shards,
        # fine write buckets + exact serve buckets: a 1k-op Zipfian append
        # (with its hot-key GC chunking) must not pad to the 16k serve
        # shape — that padded the per-chunk head fold 16x (r4 mixed-load
        # collapse, VERDICT item 2)
        batch_buckets=(256, 1024, 4096, 8192, 16384),
        use_pallas=use_pallas,
    )
    ty = get_type("set_aw")
    rng = np.random.default_rng(7)
    d = cfg.max_dcs
    bw = ty.eff_b_width(cfg)
    log(f"child: platform={platform} devices={n_dev} n_keys={n_keys} "
        f"shards={n_shards} use_pallas={use_pallas}")
    n_rows = (n_keys + n_shards - 1) // n_shards
    table = TypedTable(ty, cfg, n_rows=n_rows, n_shards=n_shards)
    for s in range(n_shards):
        table.used_rows[s] = (n_keys - s + n_shards - 1) // n_shards

    def srows(keys):
        return keys % n_shards, keys // n_shards

    # ---- populate: ops_per_key adds per key (+ removes on 10% of keys) ----
    keys = np.repeat(np.arange(n_keys, dtype=np.int64), ops_per_key)
    rng.shuffle(keys)
    elems = rng.integers(1, 1 << 62, size=keys.shape[0], dtype=np.int64)
    total = keys.shape[0]
    lane0 = np.arange(1, total + 1, dtype=np.int32)  # commit order on lane 0
    # first-seen add per key (removes observe it)
    first_idx = np.full(n_keys, -1, np.int64)
    rev = np.arange(total - 1, -1, -1)
    first_idx[keys[rev]] = rev
    valid_first = first_idx >= 0
    first_add_vc = np.zeros(n_keys, np.int32)
    first_add_elem = np.zeros(n_keys, np.int64)
    first_add_vc[valid_first] = lane0[first_idx[valid_first]]
    first_add_elem[valid_first] = elems[first_idx[valid_first]]

    with phase("populate"):
        zeros_b = np.zeros((pop_batch, bw), np.int32)
        for lo in range(0, total, pop_batch):
            hi = min(lo + pop_batch, total)
            m = hi - lo
            vcs = np.zeros((m, d), np.int32)
            vcs[:, 0] = lane0[lo:hi]
            ss, rr = srows(keys[lo:hi])
            table.append(ss, rr, elems[lo:hi, None], zeros_b[:m], vcs,
                         np.zeros(m, np.int32))
            if (lo // pop_batch) % 50 == 0:
                log(f"populate: {hi}/{total}")
        clock0 = total
        rm_keys = rng.choice(n_keys, size=n_keys // 10, replace=False).astype(np.int64)
        rm_keys = rm_keys[valid_first[rm_keys]]
        nrm = rm_keys.shape[0]
        for lo in range(0, nrm, pop_batch):
            hi = min(lo + pop_batch, nrm)
            m = hi - lo
            kk = rm_keys[lo:hi]
            eff_b = np.zeros((m, bw), np.int32)
            eff_b[:, 0] = 1
            eff_b[:, 1] = first_add_vc[kk]
            vcs = np.zeros((m, d), np.int32)
            vcs[:, 0] = clock0 + 1 + lo + np.arange(m, dtype=np.int32)
            ss, rr = srows(kk)
            table.append(ss, rr, first_add_elem[kk, None], eff_b, vcs,
                         np.zeros(m, np.int32))
        final_t = clock0 + nrm
        final_clock = np.zeros(d, np.int32)
        final_clock[0] = final_t
        # pin the serving epoch at the loaded snapshot — the GST pin a
        # serving deployment performs; mixed-phase reads at final_clock
        # stay pure gathers while appends advance the live head
        table.publish_epoch()
        mid_t = int(total * 0.6)  # historical point: 60% through the add stream
        mid_clock = np.zeros(d, np.int32)
        mid_clock[0] = mid_t
        log(f"populate: {total + nrm} ops total")

    # ---- host Zipfian sampler (the serving path routes on host) ----
    w = 1.0 / np.arange(1, n_keys + 1, dtype=np.float64) ** 1.0
    cdf = np.cumsum(w / w.sum())

    def sample(size):
        return np.searchsorted(cdf, rng.random(size)).astype(np.int64)

    # =======================================================================
    # measured 1: SERVING PATH — flat one-gather serving read end to end
    # (read_resolved_flat: no [P, M'] routing/unrouting on the host —
    #  r3 VERDICT weak #3 closed this serving-vs-kernel gap)
    # =======================================================================
    vc_final_b = np.broadcast_to(final_clock, (serve_batch, d))
    vc_mid_b = np.broadcast_to(mid_clock, (serve_batch, d))
    # pre-generated key stream: the workload generator is not the system
    # under test (basho_bench pre-computes its keygen distributions too)
    n_streams = 37
    streams = [sample(serve_batch) for _ in range(n_streams)]

    def serve_one(i):
        kk = streams[i % n_streams]
        ss, rr = srows(kk)
        vcs = vc_mid_b if (i % hist_every == hist_every - 1) else vc_final_b
        return table.read_resolved_flat(ss, rr, vcs)

    def fold_snap():
        return dict(table.fold_dispatches)

    def fold_delta(before, after):
        keys = set(before) | set(after)
        d_ = {k: after.get(k, 0) - before.get(k, 0) for k in sorted(keys)}
        return {k: v for k, v in d_.items() if v}

    fold_pre_serve = fold_snap()
    # warmup/compile both VC variants; timed separately so a compile hang
    # (vs execute hang) localizes itself in the logs
    with phase("warmup_serve_fresh"):
        resolved, fresh, complete = serve_one(0)
        np.asarray(resolved["top"])
    with phase("warmup_serve_hist"):
        resolved, fresh, complete = serve_one(hist_every - 1)
        np.asarray(resolved["top"])
    # unpipelined per-batch latency
    lat = []
    stale_hist = []
    with phase("serve_latency"):
        for i in range(6):
            tb = time.perf_counter()
            resolved, fresh, complete = serve_one(i)
            np.asarray(resolved["top"]), np.asarray(resolved["count"])
            lat.append(time.perf_counter() - tb)
            log(f"serve_latency batch {i}: {lat[-1] * 1e3:.1f}ms")
            if i % hist_every == hist_every - 1:
                stale_hist.append(1.0 - np.asarray(fresh).mean())
    lat_ms = np.asarray(lat) * 1e3
    # pipelined throughput (≈ basho_bench's concurrent workers)
    import collections

    q = collections.deque()
    depth = 8
    with phase("serve_pipeline"):
        t0 = time.perf_counter()
        for i in range(serve_batches):
            resolved, fresh, complete = serve_one(i)
            for x in resolved.values():
                x.copy_to_host_async()
            q.append(resolved)
            if len(q) > depth:
                old = q.popleft()
                np.asarray(old["top"])
            if i % 10 == 9:
                log(f"serve_pipeline: {i + 1}/{serve_batches}")
        while q:
            np.asarray(q.popleft()["top"])
        serve_elapsed = time.perf_counter() - t0
    fold_serve = fold_delta(fold_pre_serve, fold_snap())
    # fresh-vs-historical latency split: the 1-in-hist_every batch is the
    # one that pays the ring fold (strategy-dispatched); the rest resolve
    # off the head and show the strategy-independent floor
    lat_fresh_ms = [lat[i] * 1e3 for i in range(len(lat))
                    if i % hist_every != hist_every - 1]
    lat_hist_ms = [lat[i] * 1e3 for i in range(len(lat))
                   if i % hist_every == hist_every - 1]
    serving_rps = serve_batches * serve_batch / serve_elapsed
    log(f"serving path: {serving_rps:,.0f} reads/s "
        f"(batch={serve_batch}, hist 1/{hist_every}, "
        f"stale_frac_hist={np.mean(stale_hist):.2f}, "
        f"batch p50={np.percentile(lat_ms, 50):.1f}ms)")

    # =======================================================================
    # measured 2: DEVICE KERNEL — head gather + presence resolve on device
    # =======================================================================
    import jax.numpy as jnp

    cdf_dev = jnp.asarray(cdf, jnp.float32)
    he, ha, hr, ho = (table.head["elems"], table.head["addvc"],
                      table.head["rmvc"], table.head["ovf"])

    @jax.jit
    def device_step(prng, cdf_d, elems_h, addvc_h, rmvc_h, ovf_h):
        prng, sub = jax.random.split(prng)
        u = jax.random.uniform(sub, (device_batch,))
        kk = jnp.searchsorted(cdf_d, u)
        s, r = kk % n_shards, kk // n_shards
        state = {
            "elems": elems_h[s, r], "addvc": addvc_h[s, r],
            "rmvc": rmvc_h[s, r], "ovf": ovf_h[s, r],
        }
        out = ty.resolve(cfg, state)
        return prng, jnp.concatenate(
            [out["top"], out["count"][:, None].astype(jnp.int64)], axis=-1
        )

    prng = jax.random.PRNGKey(3)
    with phase("warmup_device_kernel"):
        for _ in range(3):
            prng, ev = device_step(prng, cdf_dev, he, ha, hr, ho)
            np.asarray(ev)
    rtt = []
    with phase("device_latency"):
        for _ in range(5):
            tb = time.perf_counter()
            prng, ev = device_step(prng, cdf_dev, he, ha, hr, ho)
            np.asarray(ev)
            rtt.append(time.perf_counter() - tb)
    rtt_ms = np.asarray(rtt) * 1e3
    q = collections.deque()
    depth = 32
    with phase("device_pipeline"):
        t0 = time.perf_counter()
        for i in range(device_batches):
            prng, ev = device_step(prng, cdf_dev, he, ha, hr, ho)
            ev.copy_to_host_async()
            q.append(ev)
            if len(q) > depth:
                np.asarray(q.popleft())
            if i % 100 == 99:
                log(f"device_pipeline: {i + 1}/{device_batches}")
        while q:
            np.asarray(q.popleft())
        device_elapsed = time.perf_counter() - t0
    device_rps = device_batches * device_batch / device_elapsed
    log(f"device kernel: {device_rps:,.0f} reads/s  "
        f"rtt p50={np.percentile(rtt_ms, 50):.2f}ms")

    # =======================================================================
    # baseline: sequential host materializer (reference-style walk)
    # =======================================================================
    with phase("baseline_build"):
        ops_by_key = {}
        for i in range(total):
            ops_by_key.setdefault(int(keys[i]), []).append(
                ({"dc0": int(lane0[i])}, "add", int(elems[i]))
            )
        for j in range(nrm):
            k = int(rm_keys[j])
            ops_by_key.setdefault(k, []).append(
                ({"dc0": int(clock0 + 1 + j)}, "rm",
                 (int(first_add_elem[k]), {"dc0": int(first_add_vc[k])}))
            )

    def baseline_read(k, read_vc_dict):
        # the reference fold: per-op dict-VC dominance check, then apply
        adds, rms = {}, {}
        for op_vc, kind, payload in ops_by_key.get(k, ()):
            included = all(op_vc.get(dc, 0) <= read_vc_dict.get(dc, 0)
                           for dc in op_vc)
            if not included:
                continue
            if kind == "add":
                e = payload
                cur = adds.setdefault(e, {})
                for dc, t in op_vc.items():
                    cur[dc] = max(cur.get(dc, 0), t)
            else:
                e, obs = payload
                cur = rms.setdefault(e, {})
                for dc, t in obs.items():
                    cur[dc] = max(cur.get(dc, 0), t)
        return [e for e, avc in adds.items()
                if any(t > rms.get(e, {}).get(dc, 0) for dc, t in avc.items())]

    final_vc_dict = {"dc0": final_t}
    mid_vc_dict = {"dc0": mid_t}
    bkeys = sample(baseline_reads)
    with phase("baseline_run"):
        t0 = time.perf_counter()
        for k in bkeys:
            baseline_read(int(k), final_vc_dict)
        base_rps = baseline_reads / (time.perf_counter() - t0)
    log(f"baseline(host python per-key fold): {base_rps:,.0f} reads/s")

    # ---- correctness spot-check: serving values == host materializer ----
    with phase("spot_check"):
        for at_clock, at_dict, tag in (
            (final_clock, final_vc_dict, "final"),
            (mid_clock, mid_vc_dict, "historical"),
        ):
            chk = bkeys[:32].astype(np.int64)
            ss, rr = srows(chk)
            out, fresh, complete = table.read_resolved(
                ss, rr, np.broadcast_to(at_clock, (32, d))
            )
            assert complete.all()
            for i, k in enumerate(chk):
                ref = sorted(baseline_read(int(k), at_dict))
                cnt = int(out["count"][i])
                dev = sorted(int(e) for e in out["top"][i] if e != 0)
                assert cnt == len(ref), (tag, int(k), cnt, len(ref))
                if cnt <= ty.resolve_top:
                    assert dev == ref, (tag, int(k), dev, ref)
    log("spot-check: serving values match host materializer "
        "(fresh + historical) on 64 keys")

    # ---- mixed load: appends (with ring-GC folds) interleave the serve
    # pipeline — the r3 VERDICT asked for append/GC measured UNDER load,
    # not only correctness-tested (run LAST: the writes advance the table
    # past the clocks the earlier phases and the spot check read at)
    write_batch = max(256, serve_batch // 16)
    mixed_batches = max(8, serve_batches)
    writes = 0

    def mixed_append(i):
        nonlocal writes
        kk = streams[(i * 7 + 3) % n_streams][:write_batch]
        ss, rr = srows(kk)
        vcs = np.zeros((write_batch, d), np.int32)
        vcs[:, 0] = final_t + writes + 1 + np.arange(write_batch)
        table.append(ss, rr,
                     rng.integers(1, 1 << 62, size=(write_batch, 1),
                                  dtype=np.int64),
                     np.zeros((write_batch, bw), np.int32), vcs,
                     np.zeros(write_batch, np.int32))
        writes += write_batch

    fold_pre_mixed = fold_snap()
    with phase("warmup_mixed"):
        # compile the append/GC/stale-serve shapes outside the timer —
        # several appends, because Zipfian hot-key chunking exercises a
        # family of (row-bucket, fold-window) shapes, not one
        for wi in range(6):
            mixed_append(-1 - wi)
        r0, _, _ = serve_one(0)
        np.asarray(r0["top"])
    with phase("mixed_load"):
        mq = collections.deque()
        t0 = time.perf_counter()
        for i in range(mixed_batches):
            mixed_append(i)
            resolved, fresh, complete = serve_one(i)  # reads at old final
            for x in resolved.values():
                x.copy_to_host_async()
            mq.append(resolved)
            if len(mq) > 8:
                np.asarray(mq.popleft()["top"])
        while mq:
            np.asarray(mq.popleft()["top"])
        mixed_elapsed = time.perf_counter() - t0
    fold_mixed = fold_delta(fold_pre_mixed, fold_snap())
    mixed_read_rps = mixed_batches * serve_batch / mixed_elapsed
    mixed_write_rps = (writes - 6 * write_batch) / mixed_elapsed  # minus warmup
    log(f"mixed load: {mixed_read_rps:,.0f} reads/s + "
        f"{mixed_write_rps:,.0f} appends/s sustained")

    print(json.dumps({
        "metric": METRIC,
        "value": round(serving_rps, 1),
        "unit": "reads/s",
        "vs_baseline": round(serving_rps / base_rps, 2),
        "device_kernel_reads_per_s": round(device_rps, 1),
        "device_vs_baseline": round(device_rps / base_rps, 2),
        "baseline_reads_per_s": round(base_rps, 1),
        "baseline_kind": "python_host_per_key_fold",
        "n_keys": n_keys,
        "serve_batch": serve_batch,
        "historical_batch_every": hist_every,
        "stale_fraction_historical": round(float(np.mean(stale_hist)), 3),
        "serve_batch_p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
        "serve_batch_p99_ms": round(float(np.percentile(lat_ms, 99)), 2),
        "mixed_read_rps": round(mixed_read_rps, 1),
        "mixed_write_rps": round(mixed_write_rps, 1),
        "device_rtt_p50_ms": round(float(np.percentile(rtt_ms, 50)), 2),
        "use_pallas": bool(cfg.use_pallas),
        "platform": platform,
        "fold_stage": {
            # what the store's strategy picker routed the serving ring
            # fold to, and how often each phase actually dispatched it
            # (warmups included — they compile the same families)
            "serving_strategy": table._fold_strategy(),
            "dispatch_serve": fold_serve,
            "dispatch_mixed": fold_mixed,
            "serve_batch_fresh_ms_p50": round(
                float(np.percentile(lat_fresh_ms, 50)), 2),
            "serve_batch_hist_ms": [round(x, 2) for x in lat_hist_ms],
        },
        "phases_s": phases,
    }))
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="small, fast run")
    ap.add_argument("--keys", type=int, default=None)
    ap.add_argument("--pallas", choices=("auto", "on", "off"), default="auto",
                    help="force the Pallas in-path dispatch on/off "
                         "(auto = land with it off, then try it on)")
    ap.add_argument("--no-pallas-upgrade", action="store_true",
                    help="parent: skip the Pallas upgrade attempt")
    ap.add_argument("--child", action="store_true",
                    help="internal: run the measured workload in-process")
    args = ap.parse_args()
    if args.child:
        sys.exit(child(args))
    sys.exit(parent(args))


if __name__ == "__main__":
    main()
