"""Write-plane tests (ISSUE 6): cross-connection group commit, the
parallel segmented WAL with group fsync, and the commutative-update
certification bypass.

The reference ships ``sync_log=false`` and batches log records per
partition precisely because a per-commit fsync kills throughput (SURVEY
§7); this suite pins the rebuilt plane's semantics: blind commutative
writers never touch certification stamps, read-bearing txns still
first-committer-abort, a merged batch appends once and fsyncs once, and
recovery merges WAL segments back into exact commit order.
"""

import threading

import numpy as np
import pytest

from antidote_tpu import faults
from antidote_tpu.api.node import AntidoteNode
from antidote_tpu.config import AntidoteConfig
from antidote_tpu.txn.manager import AbortError


@pytest.fixture
def cfg():
    return AntidoteConfig(
        n_shards=2, max_dcs=2, ops_per_key=8, snap_versions=2,
        set_slots=4, keys_per_table=64, batch_buckets=(8,),
    )


@pytest.fixture(autouse=True)
def _disarm():
    yield
    faults.uninstall()


def seg_cfg(cfg, n):
    import dataclasses

    return dataclasses.replace(cfg, wal_segments=n)


# ---------------------------------------------------------------------------
# segmented WAL + recovery merge
# ---------------------------------------------------------------------------
def test_segmented_wal_replays_in_append_order(tmp_path, cfg):
    from antidote_tpu.log import LogManager

    lm = LogManager(seg_cfg(cfg, 3), str(tmp_path / "w"))
    vc = np.zeros(2, np.int64)
    for i in range(9):
        lm.log_effects([(0, f"k{i}", "counter_pn", "b",
                         np.array([i], np.int64), np.array([], np.int32),
                         vc, 0, ())])
        lm.commit_barrier([0])  # rotates: records spread over segments
    files = [p for p in (tmp_path / "w").iterdir()
             if p.name.startswith("shard_0")]
    assert len(files) == 3, files  # shard_0.wal + .s1 + .s2
    assert all(p.stat().st_size > 0 for p in files), "rotation never moved"
    # merged replay reconstructs the exact append order via "q"
    assert [r["k"] for r in lm.replay_shard(0)] == [f"k{i}"
                                                    for i in range(9)]
    # op-id chain is one monotone sequence across segments
    assert [r["id"] for r in lm.replay_shard(0)] == list(range(1, 10))
    lm.close()


def test_segmented_node_recovery_and_truncate(tmp_path, cfg):
    scfg = seg_cfg(cfg, 3)
    node = AntidoteNode(scfg, log_dir=str(tmp_path))
    for i in range(12):
        node.update_objects([(f"k{i % 5}", "counter_pn", "b",
                              ("increment", 1))])
    vals_before, _ = node.read_objects(
        [(f"k{i}", "counter_pn", "b") for i in range(5)])
    node.store.log.close()
    re = AntidoteNode(scfg, log_dir=str(tmp_path), recover=True)
    vals_after, _ = re.read_objects(
        [(f"k{i}", "counter_pn", "b") for i in range(5)])
    assert vals_after == vals_before
    # fresh appends after recovery keep the sequence monotone (no reuse)
    re.update_objects([("k0", "counter_pn", "b", ("increment", 1))])
    for shard in range(scfg.n_shards):
        qs = [r["q"] for r in re.store.log.replay_shard(shard)]
        assert qs == sorted(qs) and len(qs) == len(set(qs))
    # truncate drops every segment of the shard
    re.store.log.truncate_shard(0)
    assert list(re.store.log.replay_shard(0)) == []
    assert int(re.store.log.seqs[0]) == 0
    re.store.log.close()


def test_opening_with_fewer_segments_still_replays_all(tmp_path, cfg):
    """A dir written with 3 segments opened with 1 must not lose the
    extra segments' records (shard_segment_paths unions on-disk files)."""
    from antidote_tpu.log import LogManager

    lm = LogManager(seg_cfg(cfg, 3), str(tmp_path / "w"))
    vc = np.zeros(2, np.int64)
    for i in range(6):
        lm.log_effects([(0, f"k{i}", "counter_pn", "b",
                         np.array([1], np.int64), np.array([], np.int32),
                         vc, 0, ())])
        lm.commit_barrier([0])
    lm.close()
    lm1 = LogManager(seg_cfg(cfg, 1), str(tmp_path / "w"))
    assert [r["k"] for r in lm1.replay_shard(0)] == [f"k{i}"
                                                     for i in range(6)]
    lm1.close()


# ---------------------------------------------------------------------------
# group fsync coordinator
# ---------------------------------------------------------------------------
def test_group_fsync_ticket_and_observer(tmp_path, cfg):
    from antidote_tpu.log import LogManager

    lm = LogManager(seg_cfg(cfg, 2), str(tmp_path / "w"),
                    sync_on_commit=True)
    batches = []
    lm.on_fsync_batch = batches.append
    vc = np.zeros(2, np.int64)
    lm.log_effects([(0, "a", "counter_pn", "b", np.array([1], np.int64),
                     np.array([], np.int32), vc, 0, ())])
    t = lm.barrier_async([0])
    t.wait()  # the covering fsync completed
    assert batches and batches[0] >= 1
    # sync_log=false: the ticket is ready immediately
    lm.set_sync(False)
    lm.log_effects([(0, "b", "counter_pn", "b", np.array([1], np.int64),
                     np.array([], np.int32), vc, 0, ())])
    t2 = lm.barrier_async([0])
    t2.wait(timeout=0.001)  # would raise TimeoutError if parked
    lm.close()


def test_fsync_fault_fails_the_covering_ticket(tmp_path, cfg):
    """An injected wal.fsync error must surface on the barrier's ticket
    (the ack gate), not vanish into the coordinator thread."""
    from antidote_tpu.log import LogManager

    lm = LogManager(seg_cfg(cfg, 1), str(tmp_path / "w"),
                    sync_on_commit=True)
    vc = np.zeros(2, np.int64)
    lm.log_effects([(0, "a", "counter_pn", "b", np.array([1], np.int64),
                     np.array([], np.int32), vc, 0, ())])
    faults.install(faults.FaultPlan(seed=3).add(
        "wal.fsync", "io_error", key="shard_0.wal", times=1))
    with pytest.raises(OSError):
        lm.commit_barrier([0])
    faults.uninstall()
    lm.commit_barrier([0])  # heals once the rule exhausts
    lm.close()


def test_fsync_failure_fails_acks_typed_and_enters_read_only(tmp_path, cfg):
    """Node level: records reach the file but the covering fsync fails —
    every write-bearing ack in the batch fails TYPED (ReadOnlyError) and
    the node flips read-only until the volume heals."""
    from antidote_tpu.overload import ReadOnlyError

    node = AntidoteNode(seg_cfg(cfg, 2), log_dir=str(tmp_path))
    node.store.log.set_sync(True)
    node.update_objects([("k", "counter_pn", "b", ("increment", 1))])
    faults.install(faults.FaultPlan(seed=4).add(
        "wal.fsync", "enospc", times=1))
    with pytest.raises(ReadOnlyError):
        node.update_objects([("k", "counter_pn", "b", ("increment", 1))])
    assert node.txm.read_only_reason is not None
    faults.uninstall()
    node.txm._ro_probe_at = 0.0
    node.update_objects([("k", "counter_pn", "b", ("increment", 1))])
    assert node.txm.read_only_reason is None


# ---------------------------------------------------------------------------
# commutativity bypass matrix (ISSUE 6 satellite)
# ---------------------------------------------------------------------------
def test_blind_commutative_updates_never_touch_stamps(cfg):
    node = AntidoteNode(cfg)
    txm = node.txm
    # blind counter / set-add / flag-enable: all commute, none stamps
    group = []
    for upd in [("c", "counter_pn", "b", ("increment", 1)),
                ("s", "set_aw", "b", ("add", "x")),
                ("f", "flag_ew", "b", ("enable", None))]:
        t = txm.start_transaction()
        txm.update_objects([upd], t)
        group.append(t)
    outs = txm.commit_transactions_group(group)
    assert all(isinstance(o, np.ndarray) for o in outs)
    assert txm.committed_keys == {}
    assert node.metrics.cert_bypass.value() == 3


def test_state_dependent_ops_keep_certification(cfg):
    """A set_aw REMOVE reads state for observed-remove semantics — no
    bypass: it stamps, and a stale read-bearing peer aborts against it."""
    node = AntidoteNode(cfg)
    txm = node.txm
    node.update_objects([("s", "set_aw", "b", ("add", "x"))])
    stale = txm.start_transaction()
    txm.read_objects([("s", "set_aw", "b")], stale)
    txm.update_objects([("s", "set_aw", "b", ("add", "y"))], stale)
    node.update_objects([("s", "set_aw", "b", ("remove", "x"))])
    assert ("s", "b") in txm.committed_keys  # the remove stamped
    with pytest.raises(AbortError):
        txm.commit_transaction(stale)


def test_explicit_certify_true_defeats_the_bypass(cfg):
    """Reference parity: a txn carrying certify=true keeps full
    first-committer-wins even for blind commutative updates."""
    node = AntidoteNode(cfg)
    txm = node.txm
    t1 = txm.start_transaction(props={"certify": True})
    t2 = txm.start_transaction(props={"certify": True})
    txm.update_objects([("k", "counter_pn", "b", ("increment", 1))], t1)
    txm.update_objects([("k", "counter_pn", "b", ("increment", 1))], t2)
    assert isinstance(txm.commit_transactions_group([t1])[0], np.ndarray)
    assert ("k", "b") in txm.committed_keys  # certified txns stamp
    with pytest.raises(AbortError):
        txm.commit_transaction(t2)


def test_bypass_skips_registers_and_escrow(cfg):
    """register_lww assigns and counter_b spends are NOT blind-
    commutative: they stamp (and escrow guards still apply)."""
    node = AntidoteNode(cfg)
    txm = node.txm
    node.update_objects([("r", "register_lww", "b", ("assign", "v"))])
    assert ("r", "b") in txm.committed_keys


# ---------------------------------------------------------------------------
# cross-connection merge point (wire level)
# ---------------------------------------------------------------------------
def test_interactive_commits_merge_across_connections(cfg):
    """N client threads run interactive blind-increment txns against one
    server: every commit acks (no spurious aborts — the bypass), the
    value adds up exactly, and the merge-width histogram proves commits
    actually fused into merged batches at the locked worker."""
    from antidote_tpu.proto.client import AntidoteClient
    from antidote_tpu.proto.server import ProtocolServer

    node = AntidoteNode(cfg)
    srv = ProtocolServer(node, port=0)
    n_threads, per = 6, 10
    errs = []
    try:
        def worker(i):
            try:
                c = AntidoteClient(port=srv.port)
                for j in range(per):
                    t = c.start_transaction()
                    t.update_objects(
                        [("hot", "counter_pn", "b", ("increment", 1))])
                    t.commit()
                c.close()
            except Exception as e:  # pragma: no cover - failure detail
                errs.append(repr(e))

        ts = [threading.Thread(target=worker, args=(i,))
              for i in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not errs, errs
        vals, _ = node.read_objects([("hot", "counter_pn", "b")])
        assert vals[0] == n_threads * per
        h = node.metrics.commit_merge_width
        assert h.count >= 1
        # the stamp table stayed empty: all blind, all bypassed
        assert node.txm.committed_keys == {}
    finally:
        srv.close()


def test_mixed_static_and_interactive_merge(cfg):
    """A static update and an interactive commit racing on different
    connections both land; the interactive rmw txn that REALLY conflicts
    still aborts with a typed remote error."""
    from antidote_tpu.proto.client import AntidoteClient, RemoteAbort
    from antidote_tpu.proto.server import ProtocolServer

    node = AntidoteNode(cfg)
    srv = ProtocolServer(node, port=0)
    try:
        c1 = AntidoteClient(port=srv.port)
        c2 = AntidoteClient(port=srv.port)
        t = c1.start_transaction()
        t.read_objects([("m", "counter_pn", "b")])
        t.update_objects([("m", "counter_pn", "b", ("increment", 10))])
        # a commit lands between the rmw txn's snapshot and its commit
        # and must stamp: make it read-bearing too
        t2 = c2.start_transaction()
        t2.read_objects([("m", "counter_pn", "b")])
        t2.update_objects([("m", "counter_pn", "b", ("increment", 100))])
        t2.commit()
        with pytest.raises(RemoteAbort):
            t.commit()
        vals, _ = c1.read_objects([("m", "counter_pn", "b")])
        assert vals[0] == 100
        c1.close(), c2.close()
    finally:
        srv.close()


def test_group_commit_window_widens_merges(cfg):
    """With a gather window, commits arriving within it fuse into one
    merged batch (merge width > 1) instead of one batch per arrival."""
    from antidote_tpu.proto.client import AntidoteClient
    from antidote_tpu.proto.server import ProtocolServer

    node = AntidoteNode(cfg)
    srv = ProtocolServer(node, port=0, group_commit_window_us=20_000)
    try:
        errs = []

        def worker(i):
            try:
                c = AntidoteClient(port=srv.port)
                for _ in range(5):
                    c.update_objects(
                        [(f"w{i}", "counter_pn", "b", ("increment", 1))])
                c.close()
            except Exception as e:  # pragma: no cover
                errs.append(repr(e))

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not errs, errs
        h = node.metrics.commit_merge_width
        assert h.percentile(0.99) >= 2, "window never merged commits"
        st = srv._pipeline_status()
        assert st["group_commit_window_us"] == 20_000.0
    finally:
        srv.close()


def test_write_plane_status_block(tmp_path, cfg):
    node = AntidoteNode(seg_cfg(cfg, 2), log_dir=str(tmp_path))
    node.update_objects([("k", "counter_pn", "b", ("increment", 1))])
    wp = node.status()["write_plane"]
    assert wp["wal_segments"] == 2
    assert len(wp["segment_depth_bytes"]) == 2
    assert wp["sync_log"] is False
    assert wp["merge_width"]["count"] >= 1
    assert wp["cert_bypass_total"] >= 1
    assert {"count", "mean", "p50", "p99"} <= set(wp["fsync_batch"])


def test_a_merged_group_crosses_to_the_device_once(cfg):
    """A merged group of N sub-groups on one table is one staged operand
    and one device program: `write_plane.scatter` moves by exactly one
    group, one transfer, one launch; `store.scatter_transfers`' file
    reads it off two statuses, and reads nothing — without raising — off
    a status that lacks the block (the parent's)."""
    import json
    import os
    from types import SimpleNamespace

    from benchmarks.readers import status_delta

    node = AntidoteNode(cfg)
    node.update_objects([("warm", "counter_pn", "b", ("increment", 1))])
    pre = node.status()
    txns = []
    for j in range(5):
        t = node.start_transaction()
        node.update_objects([(f"k{j}", "counter_pn", "b", ("increment", j))], t)
        txns.append(t)
    outs = node.txm.commit_transactions_group(txns)
    assert not any(isinstance(o, Exception) for o in outs)
    t = node.start_transaction()      # a read-only commit: no group
    node.txm.commit_transactions_group([t])
    post = node.status()
    moved = {k: post["write_plane"]["scatter"][k] - v
             for k, v in pre["write_plane"]["scatter"].items()}
    assert moved == {"groups": 1, "transfers": 1, "launches": 1}
    vals, _ = node.read_objects([(f"k{j}", "counter_pn", "b")
                                 for j in range(5)])
    assert vals == list(range(5))

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "layer_metrics",
                           "store.scatter_transfers.json")) as f:
        spec = json.load(f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry, = [m for m in json.load(f)["per_layer"]
                  if m["name"] == spec["name"]]
    assert entry["source"] == "program_counter"
    for key in ("unit", "better", "layer", "moves", "workloads"):
        assert spec[key] == entry[key], key
    ctx = lambda a, b: SimpleNamespace(status={"window": (a, b)})
    assert status_delta.read(spec, ctx(pre, post)) == 1.0
    assert status_delta.read(spec, ctx(post, post)) is None
    for st in (pre, post):
        del st["write_plane"]["scatter"]
    assert status_delta.read(spec, ctx(pre, post)) is None


# ---------------------------------------------------------------------------
# reads inside transactions at the merge point (ISSUE 34)
# ---------------------------------------------------------------------------
def _txn_read_paths(srv, n):
    """``pipeline.paths.txn_read`` once ``n`` such requests closed (a
    connection thread closes its record after the reply left)."""
    import time

    end = time.monotonic() + 30
    while True:
        blk = srv._pipeline_status()["paths"].get("txn_read", {})
        if blk.get("total", {}).get("count", 0) >= n:
            return blk
        assert time.monotonic() < end, blk
        time.sleep(0.01)


@pytest.mark.parametrize("type_name", ["counter_pn", "set_aw"])
def test_txn_reads_of_a_round_merge_each_at_its_own_snapshot(cfg,
                                                             type_name):
    """N connections, each in a transaction opened at a different point
    of a write stream to one hot key, read at once: the locked worker
    answers them with ONE batched read, every answer its own
    snapshot's."""
    from antidote_tpu.proto.client import AntidoteClient
    from antidote_tpu.proto.server import ProtocolServer
    from conftest import locked_worker_held

    node = AntidoteNode(cfg)
    srv = ProtocolServer(node, port=0)
    n = 6
    hot = ("hot", type_name, "b")

    def write(i):
        op = ("add", f"e{i}") if type_name == "set_aw" else ("increment", 1)
        node.update_objects([hot + (op,)])

    def expect(i):
        return ([f"e{j}" for j in range(i)] if type_name == "set_aw"
                else i)

    try:
        clients = [AntidoteClient(port=srv.port) for _ in range(n)]
        txns = []
        for i, c in enumerate(clients):
            write(i)
            txns.append(c.start_transaction())
        write(n), write(n + 1)
        answers: list = [None] * n

        def read(i):
            try:
                answers[i] = txns[i].read_objects([hot])[0]
            except Exception as e:  # pragma: no cover - failure detail
                answers[i] = e

        ts = [threading.Thread(target=read, args=(i,)) for i in range(n)]
        with locked_worker_held(srv, parked=n):
            for t in ts:
                t.start()
        for t in ts:
            t.join(timeout=60)
        for i in range(n):
            got = answers[i]
            assert (sorted(got) if type_name == "set_aw" else got) \
                == expect(i + 1), (i, got)
        assert srv._pipeline_status()["txn_reads"] == {
            "groups": 1, "reads": n, "rows": n, "inline": 0}
        blk = _txn_read_paths(srv, n)
        assert blk["parked"]["count"] == blk["exec"]["count"] == n
        for c in clients:
            c.close()
    finally:
        srv.close()


def test_txn_read_with_a_writeset_or_a_map_is_answered_alone(cfg):
    """What the request holds decides: a transaction that has written
    reads its own write, a read naming a composite type assembles its
    map, both one by one (`pipeline.txn_reads.inline`); a plain read of
    the same connection merges."""
    from antidote_tpu.proto.client import AntidoteClient
    from antidote_tpu.proto.server import ProtocolServer

    node = AntidoteNode(cfg)
    srv = ProtocolServer(node, port=0)
    try:
        c = AntidoteClient(port=srv.port)
        c.update_objects([("k", "counter_pn", "b", ("increment", 5))])
        node.update_objects([("m", "map_rr", "b", ("update", {
            ("n", "counter_pn"): ("increment", 3)}))])
        whole, _ = c.read_objects([("m", "map_rr", "b")])
        assert list(whole[0].values()) == [3]
        t = c.start_transaction()
        assert t.read_objects([("k", "counter_pn", "b")]) == [5]
        assert t.read_objects([("m", "map_rr", "b")]) == whole
        t.update_objects([("k", "counter_pn", "b", ("increment", 2))])
        assert t.read_objects([("k", "counter_pn", "b")]) == [7]
        t.commit()
        assert srv._pipeline_status()["txn_reads"] == {
            "groups": 1, "reads": 1, "rows": 1, "inline": 2}
        c.close()
    finally:
        srv.close()


def test_a_bad_txn_read_fails_alone_in_its_round(cfg):
    """An unknown txid, a transaction its dead connection's clean-up
    aborted while the read was parked (`_abort_orphan`) and an expired
    deadline each fail their own request; the round's sound read is
    answered, and nothing is stranded."""
    from antidote_tpu.proto.client import (
        AntidoteClient,
        RemoteDeadline,
        RemoteError,
    )
    from antidote_tpu.proto.codec import MessageCode
    from antidote_tpu.proto.server import ProtocolServer
    from conftest import locked_worker_held

    node = AntidoteNode(cfg)
    srv = ProtocolServer(node, port=0)
    obj = ("k", "counter_pn", "b")
    try:
        node.update_objects([obj + (("increment", 4),)])
        clients = [AntidoteClient(port=srv.port) for _ in range(4)]
        good, orphan = (clients[i].start_transaction() for i in (0, 1))
        late = clients[3].start_transaction()
        out: dict = {}

        def call(name, c, body):
            try:
                out[name] = c._call(MessageCode.READ_OBJECTS, body)["values"]
            except Exception as e:
                out[name] = e

        bodies = {
            "good": (clients[0], {"txid": good.txid, "objects": [obj]}),
            "orphan": (clients[1], {"txid": orphan.txid, "objects": [obj]}),
            "unknown": (clients[2], {"txid": 10 ** 9, "objects": [obj]}),
            "late": (clients[3], {"txid": late.txid, "objects": [obj],
                                  "deadline_ms": 20}),
        }
        ts = [threading.Thread(target=call, args=(k,) + v)
              for k, v in bodies.items()]
        with locked_worker_held(srv, parked=4):
            for t in ts:
                t.start()
            import time
            time.sleep(0.1)             # past the late read's deadline
            srv._abort_orphan(orphan.txid)
        for t in ts:
            t.join(timeout=60)
        assert out["good"] == [4]
        for name in ("orphan", "unknown"):
            assert isinstance(out[name], RemoteError), out
            assert "unknown or finished transaction" in str(out[name])
        assert isinstance(out["late"], RemoteDeadline), out
        assert srv._pipeline_status()["txn_reads"] == {
            "groups": 1, "reads": 1, "rows": 1, "inline": 0}
        # the connections are all alive, their transactions too
        assert late.read_objects([obj]) == [4]
        assert good.read_objects([obj]) == [4]
        for c in clients:
            c.close()
    finally:
        srv.close()
