#!/usr/bin/env python
"""BASELINE.json workload suite (one JSON line per workload on stdout).

bench.py remains the north-star single line (1M-key set_aw Zipfian reads);
this suite covers the remaining reference configs:

  counter   antidote_crdt_counter_pn single-DC update/read, 10k keys —
            also times the XLA scan fold vs the Pallas counter_fold kernel
  register  register_lww vs register_mv (LWW argmax vs multi-value resolve)
  map       map_rr nested map-of-CRDTs, full-stack read ops/s
  rga       rga sequence with a 3-DC causal merge, full-stack reads

Baselines are sequential host-Python per-key folds with dict vector
clocks — the closest stand-in for the reference's BEAM materializer walk
(clocksi_materializer:materialize_intern,
/root/reference/src/clocksi_materializer.erl:111-197) this machine can run.

Usage: python bench_suite.py [--smoke] [--workload counter|register|map|rga|all]
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def emit(obj):
    print(json.dumps(obj), flush=True)


# ---------------------------------------------------------------------------
def bench_counter(smoke: bool):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from antidote_tpu.config import AntidoteConfig
    from antidote_tpu.crdt import get_type
    from antidote_tpu.materializer import counter_fold, fold_batch
    from antidote_tpu.store import TypedTable

    n_keys = 2_000 if smoke else 10_000
    k_ops = 8
    read_batch = 4096
    timed = 50 if smoke else 200
    cfg = AntidoteConfig(n_shards=1, max_dcs=4, ops_per_key=k_ops,
                         snap_versions=2, keys_per_table=n_keys,
                         batch_buckets=(16384,))
    ty = get_type("counter_pn")
    rng = np.random.default_rng(1)
    table = TypedTable(ty, cfg, n_rows=n_keys, n_shards=1)
    table.used_rows[0] = n_keys

    keys = np.repeat(np.arange(n_keys, dtype=np.int64), k_ops)
    rng.shuffle(keys)
    deltas = rng.integers(-100, 100, size=keys.shape[0]).astype(np.int64)
    lane0 = np.arange(1, keys.shape[0] + 1, dtype=np.int32)
    bw = ty.eff_b_width(cfg)
    for lo in range(0, keys.shape[0], 16384):
        hi = min(lo + 16384, keys.shape[0])
        m = hi - lo
        vcs = np.zeros((m, cfg.max_dcs), np.int32)
        vcs[:, 0] = lane0[lo:hi]
        table.append(np.zeros(m, np.int64), keys[lo:hi],
                     deltas[lo:hi, None], np.zeros((m, bw), np.int32),
                     vcs, np.zeros(m, np.int32))
    expect = np.zeros(n_keys, np.int64)
    np.add.at(expect, keys, deltas)

    # device-resident read loop: uniform key sample + head gather
    head = table.head["cnt"]

    @jax.jit
    def read_step(prng, head):
        prng, sub = jax.random.split(prng)
        kk = jax.random.randint(sub, (read_batch,), 0, n_keys)
        return prng, head[0, kk]

    prng = jax.random.PRNGKey(0)
    for _ in range(3):
        prng, v = read_step(prng, head)
        np.asarray(v)
    t0 = time.perf_counter()
    import collections
    q = collections.deque()
    for _ in range(timed):
        prng, v = read_step(prng, head)
        v.copy_to_host_async()
        q.append(v)
        if len(q) > 32:
            np.asarray(q.popleft())
    while q:
        np.asarray(q.popleft())
    rps = timed * read_batch / (time.perf_counter() - t0)

    # ring-fold comparison at a mid-stream VC: XLA scan vs pallas kernel
    b = min(n_keys, 4096)
    rows = rng.integers(0, n_keys, b).astype(np.int64)
    mid = np.zeros((b, cfg.max_dcs), np.int32)
    mid[:, 0] = keys.shape[0] // 2
    base_vc = np.zeros((b, cfg.max_dcs), np.int32)
    base = {"cnt": jnp.zeros((b,), jnp.int64)}
    ops_a = table.ops_a[0][rows]
    ops_b_ = table.ops_b[0][rows]
    ops_vc = table.ops_vc[0][rows]
    ops_o = table.ops_origin[0][rows]
    n_ops = jnp.asarray(table.n_ops[0][rows], jnp.int32)

    xla = jax.jit(lambda *a: fold_batch(ty, cfg, *a))
    st, _ = xla(base, ops_a, ops_b_, ops_vc, ops_o, n_ops, base_vc, mid)
    jax.block_until_ready(st)
    t0 = time.perf_counter()
    reps = 20 if smoke else 50
    for _ in range(reps):
        st, _ = xla(base, ops_a, ops_b_, ops_vc, ops_o, n_ops, base_vc, mid)
    jax.block_until_ready(st)
    xla_kps = reps * b / (time.perf_counter() - t0)

    deltas_bk = np.asarray(ops_a[:, :, 0], np.int64)
    cnt, _ = counter_fold(np.zeros(b, np.int64), deltas_bk,
                          np.asarray(ops_vc), np.asarray(n_ops),
                          base_vc, mid)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(st["cnt"]))
    t0 = time.perf_counter()
    for _ in range(reps):
        cnt, _ = counter_fold(np.zeros(b, np.int64), deltas_bk,
                              np.asarray(ops_vc), np.asarray(n_ops),
                              base_vc, mid)
    jax.block_until_ready(cnt)
    pallas_kps = reps * b / (time.perf_counter() - t0)

    # host-python baseline fold
    ops_by_key = {}
    for i in range(keys.shape[0]):
        ops_by_key.setdefault(int(keys[i]), []).append(
            ({"dc0": int(lane0[i])}, int(deltas[i])))
    read_vc = {"dc0": int(keys.shape[0])}
    nb = 500 if smoke else 2000
    bkeys = rng.integers(0, n_keys, nb)
    t0 = time.perf_counter()
    for kk in bkeys:
        acc = 0
        for vc, d in ops_by_key.get(int(kk), ()):
            if all(vc.get(dc, 0) <= read_vc.get(dc, 0) for dc in vc):
                acc += d
    base_rps = nb / (time.perf_counter() - t0)
    # spot-check device values
    chk = rng.integers(0, n_keys, 64)
    np.testing.assert_array_equal(np.asarray(head[0, chk]), expect[chk])

    emit({
        "metric": "counter_pn_read_throughput",
        "value": round(rps, 1), "unit": "reads/s",
        "vs_baseline": round(rps / base_rps, 2),
        "baseline_reads_per_s": round(base_rps, 1),
        "fold_xla_keys_per_s": round(xla_kps, 1),
        "fold_pallas_keys_per_s": round(pallas_kps, 1),
        "n_keys": n_keys,
        "platform": jax.devices()[0].platform,
    })


# ---------------------------------------------------------------------------
def bench_register(smoke: bool):
    import jax
    import numpy as np

    from antidote_tpu.config import AntidoteConfig
    from antidote_tpu.crdt import get_type
    from antidote_tpu.store import TypedTable

    n_keys = 2_000 if smoke else 10_000
    read_batch = 4096
    timed = 50 if smoke else 200
    cfg = AntidoteConfig(n_shards=1, max_dcs=4, ops_per_key=8,
                         snap_versions=2, mv_slots=4, keys_per_table=n_keys,
                         batch_buckets=(16384,))
    rng = np.random.default_rng(2)
    out = {}
    for tname in ("register_lww", "register_mv"):
        ty = get_type(tname)
        table = TypedTable(ty, cfg, n_rows=n_keys, n_shards=1)
        table.used_rows[0] = n_keys
        aw, bw = ty.eff_a_width(cfg), ty.eff_b_width(cfg)
        # two DC lanes assign concurrently to every key (MV keeps both)
        for lane in (0, 1):
            keys = np.arange(n_keys, dtype=np.int64)
            vals = rng.integers(1, 1 << 62, n_keys, dtype=np.int64)
            eff_a = np.zeros((n_keys, aw), np.int64)
            eff_a[:, 0] = vals
            if tname == "register_lww":
                # ts lane: later lane wins half the keys
                eff_a[:, 1] = rng.integers(1, 1000, n_keys)
            vcs = np.zeros((n_keys, cfg.max_dcs), np.int32)
            vcs[:, lane] = np.arange(1, n_keys + 1, dtype=np.int32)
            for lo in range(0, n_keys, 16384):
                hi = min(lo + 16384, n_keys)
                table.append(np.zeros(hi - lo, np.int64), keys[lo:hi],
                             eff_a[lo:hi], np.zeros((hi - lo, bw), np.int32),
                             vcs[lo:hi],
                             np.full(hi - lo, lane, np.int32))
        head = table.head

        if tname == "register_lww":
            @jax.jit
            def read_step(prng, val, ts):
                prng, sub = jax.random.split(prng)
                kk = jax.random.randint(sub, (read_batch,), 0, n_keys)
                return prng, val[0, kk]

            args = (head["val"], head["ts"])
        else:
            import jax.numpy as jnp

            @jax.jit
            def read_step(prng, vals, ids):
                prng, sub = jax.random.split(prng)
                kk = jax.random.randint(sub, (read_batch,), 0, n_keys)
                v = vals[0, kk]                  # [B, S]
                live = (ids[0, kk] != 0) & (v != 0)
                return prng, jnp.where(live, v, 0)

            args = (head["vals"], head["ids"])

        prng = jax.random.PRNGKey(0)
        for _ in range(3):
            prng, v = read_step(prng, *args)
            np.asarray(v)
        import collections
        q = collections.deque()
        t0 = time.perf_counter()
        for _ in range(timed):
            prng, v = read_step(prng, *args)
            v.copy_to_host_async()
            q.append(v)
            if len(q) > 32:
                np.asarray(q.popleft())
        while q:
            np.asarray(q.popleft())
        out[tname] = timed * read_batch / (time.perf_counter() - t0)

    # python baseline: mv resolve with dict dots
    nb = 500 if smoke else 2000
    stored = {
        k: [({"dc0": k + 1}, rng.integers(1, 1 << 30)),
            ({"dc1": k + 1}, rng.integers(1, 1 << 30))]
        for k in range(min(n_keys, nb * 2))
    }
    bkeys = rng.integers(0, len(stored), nb)
    t0 = time.perf_counter()
    for kk in bkeys:
        ents = stored[int(kk)]
        # keep every entry not dominated by another (concurrent set)
        keep = []
        for i, (vc_i, v_i) in enumerate(ents):
            dominated = any(
                all(vc_i.get(dc, 0) <= vc_j.get(dc, 0) for dc in vc_i)
                and vc_i != vc_j
                for j, (vc_j, _) in enumerate(ents) if j != i
            )
            if not dominated:
                keep.append(v_i)
    base_rps = nb / (time.perf_counter() - t0)

    import jax as _jax
    emit({
        "metric": "register_resolve_throughput",
        "value": round(out["register_mv"], 1), "unit": "reads/s",
        "vs_baseline": round(out["register_mv"] / base_rps, 2),
        "lww_reads_per_s": round(out["register_lww"], 1),
        "mv_reads_per_s": round(out["register_mv"], 1),
        "baseline_reads_per_s": round(base_rps, 1),
        "n_keys": n_keys,
        "platform": _jax.devices()[0].platform,
    })


# ---------------------------------------------------------------------------
def bench_map(smoke: bool):
    import jax
    import numpy as np

    from antidote_tpu.api import AntidoteNode
    from antidote_tpu.config import AntidoteConfig

    n_maps = 100 if smoke else 400
    cfg = AntidoteConfig(n_shards=4, max_dcs=2, ops_per_key=16,
                         snap_versions=2, set_slots=8,
                         keys_per_table=max(64, n_maps * 4),
                         batch_buckets=(256, 4096))
    node = AntidoteNode(cfg)
    t0 = time.perf_counter()
    for i in range(n_maps):
        node.update_objects([(f"m{i}", "map_rr", "b", ("update", {
            ("clicks", "counter_pn"): ("increment", i + 1),
            ("name", "register_lww"): ("assign", f"user{i}"),
            ("tags", "set_aw"): ("add", f"t{i % 7}"),
        }))])
    pop_s = time.perf_counter() - t0
    objs = [(f"m{i}", "map_rr", "b") for i in range(n_maps)]
    # warm + verify
    vals, _ = node.read_objects(objs)
    assert vals[3][("clicks", "counter_pn")] == 4
    assert vals[3][("name", "register_lww")] == "user3"
    reps = 5 if smoke else 10
    t0 = time.perf_counter()
    for _ in range(reps):
        vals, _ = node.read_objects(objs)
    rps = reps * n_maps / (time.perf_counter() - t0)

    # python baseline: per-field materialization with dict-VC dominance
    # checks (the reference re-folds each nested field's op list per read)
    field_ops = {}
    for i in range(n_maps):
        ops = field_ops.setdefault(f"m{i}", {"clicks": [], "name": [],
                                             "tags": []})
        vc = {"dc0": i + 1}
        ops["clicks"].append((vc, ("inc", i + 1)))
        ops["name"].append((vc, ("assign", f"user{i}")))
        ops["tags"].append((vc, ("add", f"t{i % 7}")))
    read_vc = {"dc0": n_maps + 1}

    def baseline_read(key):
        out = {}
        for field, ops in field_ops[key].items():
            cnt, name, tags = 0, None, set()
            for vc, (kind, arg) in ops:
                if not all(vc.get(dc, 0) <= read_vc.get(dc, 0) for dc in vc):
                    continue
                if kind == "inc":
                    cnt += arg
                elif kind == "assign":
                    name = arg
                else:
                    tags.add(arg)
            out[field] = cnt if field == "clicks" else (
                name if field == "name" else sorted(tags))
        return out

    t0 = time.perf_counter()
    for _ in range(reps):
        for i in range(n_maps):
            baseline_read(f"m{i}")
    base_rps = reps * n_maps / (time.perf_counter() - t0)
    emit({
        "metric": "map_rr_read_throughput",
        "value": round(rps, 1), "unit": "reads/s",
        "vs_baseline": round(rps / base_rps, 4),
        "populate_s": round(pop_s, 2),
        "n_maps": n_maps,
        "note": "full-stack host path (directory+decode per field)",
        "platform": jax.devices()[0].platform,
    })


# ---------------------------------------------------------------------------
def bench_rga(smoke: bool):
    import jax
    import numpy as np

    from antidote_tpu.api import AntidoteNode
    from antidote_tpu.config import AntidoteConfig
    from antidote_tpu.interdc import DCReplica, LoopbackHub

    n_docs = 30 if smoke else 60
    inserts = 10 if smoke else 15
    cfg = AntidoteConfig(n_shards=2, max_dcs=3, ops_per_key=64,
                         snap_versions=2, rga_slots=256,
                         keys_per_table=max(64, n_docs * 2),
                         batch_buckets=(64, 1024))
    hub = LoopbackHub()
    nodes = [AntidoteNode(cfg, dc_id=i) for i in range(3)]
    reps = [DCReplica(n, hub) for n in nodes]
    DCReplica.connect_all(reps)
    # warmup doc: first-compile of the insert/fold/append kernels is not
    # steady-state write throughput (bench.py excludes warmup the same way)
    wvc = nodes[0].update_objects([("wdoc", "rga", "b", ("insert", (0, "@")))])
    hub.pump()
    for i, n in enumerate(nodes):
        n.update_objects([("wdoc", "rga", "b", ("insert", (1, f"w{i}:{j}")))
                          for j in range(3)], clock=wvc)
        hub.pump()
    hub.pump()
    t0 = time.perf_counter()
    for d in range(n_docs):
        key = f"doc{d}"
        vc = nodes[0].update_objects([(key, "rga", "b", ("insert", (0, "@")))])
        hub.pump()
        # 3 DCs append concurrently after the shared base (same stale
        # clock ⇒ the batches are causally concurrent; pump between nodes
        # so dependency chains from earlier docs can drain).  Each DC's
        # inserts ride ONE multi-update txn — the txn reads the rga state
        # once and overlays its own growing writeset (the reference's
        # update_objects is list-shaped for the same reason)
        for i, n in enumerate(nodes):
            n.update_objects(
                [(key, "rga", "b", ("insert", (1, f"{i}:{j}")))
                 for j in range(inserts)], clock=vc)
            hub.pump()
        hub.pump()
    merge_s = time.perf_counter() - t0
    target = np.max(np.stack([n.store.dc_max_vc() for n in nodes]), axis=0)
    objs = [(f"doc{d}", "rga", "b") for d in range(n_docs)]
    seqs = []
    for n in nodes:
        vals, _ = n.read_objects(objs, clock=target)
        seqs.append(vals)
    for d in range(n_docs):
        assert seqs[0][d] == seqs[1][d] == seqs[2][d], d
        assert len(seqs[0][d]) == 1 + 3 * inserts
    reps_n = 5 if smoke else 10
    t0 = time.perf_counter()
    for _ in range(reps_n):
        vals, _ = nodes[0].read_objects(objs, clock=target)
    rps = reps_n * n_docs / (time.perf_counter() - t0)
    total_elems = n_docs * (1 + 3 * inserts)

    # python baseline: per-doc sequence re-materialization — fold each
    # doc's insert ops (id-ordered tree walk with dict VCs) per read, the
    # shape of the reference's per-read materializer fold
    doc_ops = {}
    for d in range(n_docs):
        ops = doc_ops[f"doc{d}"] = []
        ops.append(((0, 0), None, "@"))  # (id), left=None
        for i in range(3):
            for j in range(inserts):
                # concurrent inserts after the base element at index 0
                ops.append(((j + 1, i + 1), (0, 0), f"{i}:{j}"))

    def baseline_read(key):
        ops = doc_ops[key]
        children = {}
        for oid, left, val in ops:
            children.setdefault(left, []).append((oid, val))
        seq = []

        def walk(parent):
            for oid, val in sorted(children.get(parent, ()),
                                   key=lambda x: x[0], reverse=True):
                seq.append(val)
                walk(oid)

        base = children.get(None, [])[0]
        seq.append(base[1])
        walk(base[0])
        return seq

    assert len(baseline_read("doc0")) == 1 + 3 * inserts
    t0 = time.perf_counter()
    for _ in range(reps_n):
        for d in range(n_docs):
            baseline_read(f"doc{d}")
    base_rps = reps_n * n_docs / (time.perf_counter() - t0)
    emit({
        "metric": "rga_3dc_merge_read_throughput",
        "value": round(rps, 1), "unit": "docs/s",
        "vs_baseline": round(rps / base_rps, 2),
        "baseline_docs_per_s": round(base_rps, 1),
        "converged_docs": n_docs,
        "elements": total_elems,
        "merge_populate_s": round(merge_s, 2),
        "note": "3-DC concurrent inserts, identical order on every replica",
        "platform": jax.devices()[0].platform,
    })


# ---------------------------------------------------------------------------
def bench_fabric(smoke: bool):
    """Inter-DC control-plane throughput over REAL sockets (the erlzmq
    stand-in, SURVEY §2.9): txn-stream delivery msgs/s end-to-end
    (publish -> TCP -> subscriber -> causal gate -> applied) and
    catch-up query round-trips/s.  The data plane is device collectives;
    this measures the TCP fabric that replaces ZeroMQ."""
    import jax
    import numpy as np

    from antidote_tpu.api import AntidoteNode
    from antidote_tpu.config import AntidoteConfig
    from antidote_tpu.interdc import DCReplica
    from antidote_tpu.interdc.tcp import TcpFabric

    cfg = AntidoteConfig(n_shards=4, max_dcs=2, ops_per_key=16,
                         snap_versions=2, keys_per_table=4096,
                         batch_buckets=(64, 1024))
    fabric = TcpFabric()
    nodes = [AntidoteNode(cfg, dc_id=i) for i in range(2)]
    reps = [DCReplica(n, fabric, f"dc{i}") for i, n in enumerate(nodes)]
    DCReplica.connect_all(reps)
    # warm
    nodes[0].update_objects([(0, "counter_pn", "b", ("increment", 1))])
    fabric.pump()
    # control-plane message throughput: serialized safe-time pings
    # (decode + per-(origin, shard) demux + gate advance, no device
    # work) — the transport + demux cost a ZeroMQ NIF would carry
    from antidote_tpu.interdc.messages import TxnMessage

    n_msgs = 2_000 if smoke else 20_000
    d = cfg.max_dcs
    base = int(reps[0].pub_opid[0])
    msgs = [
        TxnMessage(
            origin=0, shard=0, prev_opid=base, last_opid=base,
            commit_vc=np.zeros(d, np.int32),
            snapshot_vc=np.zeros(d, np.int32),
            effects=[], timestamp=10_000 + i,
        ).to_bytes()
        for i in range(n_msgs)
    ]
    t0 = time.perf_counter()
    for m in msgs:
        fabric.publish(reps[0].fabric_id, m)
    target = 10_000 + n_msgs - 1
    while int(nodes[1].store.applied_vc[0, 0]) < target:
        fabric.pump(timeout=0.02)
    dt = time.perf_counter() - t0
    msg_rps = n_msgs / dt
    # catch-up query round-trips (REQ/XREP path)
    n_q = 100 if smoke else 500
    t0 = time.perf_counter()
    for _ in range(n_q):
        fabric.request(0, "check_up", {})
    q_rps = n_q / (time.perf_counter() - t0)
    emit({
        "metric": "interdc_fabric_throughput",
        "value": round(msg_rps, 1), "unit": "msgs/s",
        "query_roundtrips_per_s": round(q_rps, 1),
        "note": "real TCP sockets: publish -> decode -> demux -> gate; "
                "queries are REQ/XREP round-trips",
        "platform": jax.devices()[0].platform,
    })


WORKLOADS = {
    "counter": bench_counter,
    "register": bench_register,
    "map": bench_map,
    "rga": bench_rga,
    "fabric": bench_fabric,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--workload", default="all",
                    choices=[*WORKLOADS, "all"])
    args = ap.parse_args()
    names = list(WORKLOADS) if args.workload == "all" else [args.workload]
    for name in names:
        log(f"== workload: {name} ==")
        t0 = time.perf_counter()
        WORKLOADS[name](args.smoke)
        log(f"== {name} done in {time.perf_counter() - t0:.1f}s ==")


if __name__ == "__main__":
    main()
