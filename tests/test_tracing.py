"""ISSUE 24 tracing tests: per-request stage records on both front-end
planes, commit-group phases, the native plane's crossing counters, and the
stable names of the device programs; ISSUE 35's: the locked worker's round
record and the per-program launch and compile counters.

CPU, counts and structure only: no time measured here is a device metric.
"""

from __future__ import annotations

import logging
import re
import time

import jax
import pytest

from antidote_tpu.api.node import AntidoteNode
from antidote_tpu.config import AntidoteConfig
from antidote_tpu.obs import trace
from antidote_tpu.proto.client import AntidoteClient
from antidote_tpu.proto.server import ProtocolServer

pytestmark = pytest.mark.smoke

N_KEYS = 12


def mk_cfg():
    # same shapes as test_proto/test_native_frontend: warm compile cache
    return AntidoteConfig(
        n_shards=2, max_dcs=2, ops_per_key=8, snap_versions=2,
        set_slots=8, rga_slots=16, keys_per_table=64, batch_buckets=(8, 64),
    )


def _boot(native: bool):
    node = AntidoteNode(mk_cfg())
    srv = ProtocolServer(node, port=0, native_frontend=native,
                         epoch_tick_ms=25)
    if native and srv.native is None:
        srv.close()
        pytest.skip("native front-end unavailable (no g++?)")
    return node, srv


def _record_closes(monkeypatch):
    """Wrap the one closing call: every request's record."""
    seen = []
    inner = trace.StageAccumulator.close

    def close(self, path, rid, batch_id, stamps):
        seen.append((path, rid, batch_id, tuple(stamps)))
        return inner(self, path, rid, batch_id, stamps)

    monkeypatch.setattr(trace.StageAccumulator, "close", close)
    return seen


def _wait_epoch_covers(node, timeout=5.0):
    txm = node.txm
    deadline = time.monotonic() + timeout
    while (node.store.serving_epoch is None
           or int(node.store.serving_epoch.vc[txm.my_dc])
           < txm.commit_counter):
        assert time.monotonic() < deadline, "epoch never covered commits"
        time.sleep(0.005)


# ---------------------------------------------------------------------------
# (a) per-request stage records, both planes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("native", [False, True], ids=["python", "native"])
def test_every_request_has_a_monotone_stage_record(native, monkeypatch):
    seen = _record_closes(monkeypatch)
    node, srv = _boot(native)
    c = AntidoteClient(srv.host, srv.port)
    try:
        for i in range(N_KEYS):
            c.update_objects([(f"k{i}", "counter_pn", "b", ("increment", 1))])
        _wait_epoch_covers(node)
        before = c.node_status()["pipeline"]
        for i in range(N_KEYS):          # one object each: gather path
            vals, _ = c.read_objects([(f"k{i}", "counter_pn", "b")])
            assert vals == [1]
        for i in range(N_KEYS):          # again: cache / native mirror
            c.read_objects([(f"k{i}", "counter_pn", "b")])
        after = c.node_status()["pipeline"]
    finally:
        c.close()
        srv.close()
    gathered = after["reads"]["gather"] - before["reads"].get("gather", 0)
    assert gathered == N_KEYS
    paths = after["paths"]
    assert paths["gather"]["total"]["count"] == N_KEYS
    assert paths["update"]["total"]["count"] == N_KEYS
    # every request that crossed into Python closed exactly one record
    assert {p for p, *_ in seen} <= set(paths) | {"other"}
    ids = [rid for _p, rid, _b, _s in seen]
    assert len(set(ids)) == len(ids), "request ids repeat"
    for path, rid, batch_id, stamps in seen:
        taken = [t for t in stamps if t]
        assert taken == sorted(taken), (path, rid, stamps)
        assert stamps[0] and stamps[-1]
        if path == "gather":
            assert all(stamps[2:]), stamps     # every stage of the path
            assert bool(stamps[1]) is native   # the crossing: native only
            assert batch_id >= 1
        if path == "update":
            assert batch_id >= 1               # its commit group
    # the stages of a path sum to its total (telescoping, float-exact
    # to rounding), and each stage was counted once per request
    for path, blk in paths.items():
        total = blk["total"]
        parts = [v for k, v in blk.items() if k != "total"]
        assert sum(p["sum_ms"] for p in parts) == pytest.approx(
            total["sum_ms"], rel=1e-9, abs=1e-9), path
        assert all(p["count"] <= total["count"] for p in parts)
    g = paths["gather"]
    want = ["decode", "parked", "launch", "wb_wait", "device_wait",
            "wb_host", "reply"]
    assert [s for s in trace.STAGES if s in g] == (
        ["cross"] if native else []) + want
    assert all(g[s]["count"] == N_KEYS for s in want)
    # the request histogram now spans arrival -> sent, once per record
    assert after["stages"]["request"]["count"] >= len(seen) - 1


def test_slow_requests_are_the_slowest_since_the_last_status_read():
    node, srv = _boot(False)
    c = AntidoteClient(srv.host, srv.port)
    try:
        for i in range(N_KEYS):
            c.update_objects([(f"k{i}", "counter_pn", "b", ("increment", 1))])
        slow = c.node_status()["pipeline"]["slow_requests"]
        again = c.node_status()["pipeline"]["slow_requests"]
    finally:
        c.close()
        srv.close()
    assert 1 <= len(slow) <= trace.SLOW_KEPT
    totals = [r["total_ms"] for r in slow]
    assert totals == sorted(totals, reverse=True)
    for r in slow:
        assert set(r) == {"id", "path", "batch", "total_ms", "stages_ms"}
        assert sum(r["stages_ms"].values()) == pytest.approx(
            r["total_ms"], abs=0.01)      # each rounded to the microsecond
    # reading the status opened a new window: only the status call itself
    assert len(again) <= 1


def test_a_request_path_is_its_own_outcome_not_its_batch(monkeypatch):
    """One launch batch carrying a read whose object hits the snapshot
    cache and a read that needs the gather: each is counted under its own
    path; a work no stage answered is counted as shed."""
    from antidote_tpu.proto.server import _RequestTrace, _StaticWork

    seen = _record_closes(monkeypatch)
    node, srv = _boot(False)
    c = AntidoteClient(srv.host, srv.port)
    try:
        for i in range(2):
            c.update_objects([(f"k{i}", "counter_pn", "b", ("increment", 1))])
        _wait_epoch_covers(node)
        c.read_objects([("k0", "counter_pn", "b")])    # back-fills k0
        hit = _StaticWork("read", objects=[("k0", "counter_pn", "b")])
        miss = _StaticWork("read", objects=[("k1", "counter_pn", "b")])
        shed = _StaticWork("read", objects=[("k1", "counter_pn", "b")])
        now = time.monotonic()
        for w in (hit, miss, shed):
            w.t_submit = w.t_dequeued = now
        assert srv._launch_epoch_reads([hit, miss]) == []
        assert hit.event.wait(5) and miss.event.wait(5)
        del seen[:]
        for w in (hit, miss, shed):
            rec = _RequestTrace(99)
            rec.begin(now, 0.0)
            rec.work = w
            srv._close_request(rec)
    finally:
        c.close()
        srv.close()
    assert hit.result[0] == [1] and miss.result[0] == [1]
    assert hit.batch_id == miss.batch_id >= 1
    assert hit.t_wb_start and not hit.t_synced and miss.t_synced
    assert [p for p, *_ in seen] == ["cache", "gather", "shed"]


def test_a_held_read_waits_under_parked_and_its_stages_still_sum(monkeypatch):
    """ISSUE 25: with every writeback slot taken the dispatcher holds the
    gate; the hold is inside the held read's ``parked`` stage (stamped after
    the drain that follows the hold), not ``wb_wait``, and the stages of its
    record still sum to ``total``."""
    import threading

    seen = _record_closes(monkeypatch)
    node, srv = _boot(False)
    depth = ProtocolServer.DEPTH
    hold_s = 0.15
    store = node.txm.store
    release = threading.Event()
    finish = store.epoch_read_finish

    def held_finish(pending):
        assert release.wait(30)
        return finish(pending)

    clients = [AntidoteClient(srv.host, srv.port) for _ in range(depth + 1)]
    out = {}

    def read(i):
        out[i] = clients[i].read_objects([(f"k{i}", "counter_pn", "b")])[0]

    try:
        for i in range(depth + 1):
            clients[0].update_objects(
                [(f"k{i}", "counter_pn", "b", ("increment", 1))])
        _wait_epoch_covers(node)
        store.epoch_read_finish = held_finish
        del seen[:]
        threads = []
        for i in range(depth + 1):      # the last one finds no slot free
            seq = srv._launch_seq
            threads.append(threading.Thread(target=read, args=(i,),
                                            daemon=True))
            threads[-1].start()
            deadline = time.monotonic() + 10
            while i < depth and srv._launch_seq == seq:
                assert time.monotonic() < deadline
                time.sleep(0.002)
        time.sleep(hold_s)
        assert srv._launch_seq == depth
        release.set()
        for t in threads:
            t.join(10)
            assert not t.is_alive()
        # a record is closed after its reply was sent: wait for the last
        deadline = time.monotonic() + 10
        while True:
            status = srv._pipeline_status()
            if status["paths"]["gather"]["total"]["count"] > depth:
                break
            assert time.monotonic() < deadline
            time.sleep(0.002)
    finally:
        release.set()
        for c in clients:
            c.close()
        srv.close()
    assert out == {i: [1] for i in range(depth + 1)}
    gathers = [(rid, b, st) for path, rid, b, st in seen if path == "gather"]
    assert len(gathers) == depth + 1
    held = max(gathers, key=lambda g: g[1])          # the last batch
    assert held[1] == depth + 1
    (t_arrive, _taken, t_submit, t_dequeued, t_launched, t_wb_start,
     _synced, _ready, t_sent) = held[2]
    assert t_dequeued - t_submit >= hold_s * 0.9         # parked: the hold
    assert t_wb_start - t_launched < hold_s * 0.5        # wb_wait: not it
    assert status["gate_hold"]["held"] == 1
    assert status["gate_hold"]["sum_ms"] >= hold_s * 900
    g = status["paths"]["gather"]
    assert sum(v["sum_ms"] for k, v in g.items() if k != "total") == (
        pytest.approx(g["total"]["sum_ms"], rel=1e-9, abs=1e-9))
    assert g["parked"]["sum_ms"] >= hold_s * 900


# ---------------------------------------------------------------------------
# (b) commit-group phases
# ---------------------------------------------------------------------------
def test_commit_phases_sum_to_the_group_and_count_once_per_group(tmp_path):
    import dataclasses

    node = AntidoteNode(dataclasses.replace(mk_cfg(), sync_log=True),
                        log_dir=str(tmp_path))
    txm = node.txm
    txm.enable_serving_epochs()
    try:
        groups = 5
        for g in range(groups):
            txns = []
            for j in range(3):
                t = node.start_transaction()
                node.update_objects(
                    [(f"k{g}_{j}", "counter_pn", "b", ("increment", 1))], t)
                txns.append(t)
            outs = txm.commit_transactions_group(txns)
            assert not any(isinstance(o, Exception) for o in outs)
        t = node.start_transaction()      # a read-only commit: no group
        txm.commit_transactions_group([t])
        wp = node.status()["write_plane"]
    finally:
        node.store.log.close()
    assert txm.group_seq == groups
    ph = wp["phases"]
    in_lock = [ph[p] for p in trace.COMMIT_PHASES]
    assert all(p["count"] == groups for p in in_lock)
    assert ph["freeze"]["count"] == groups
    assert wp["group"]["count"] == groups
    assert sum(p["sum_ms"] for p in in_lock) == pytest.approx(
        wp["group"]["sum_ms"], rel=1e-9)
    assert all(p["sum_ms"] >= 0 for p in ph.values())
    # the WAL was on and every group published: none of these is skipped
    for p in ("certify", "wal_append", "scatter", "publish"):
        assert ph[p]["sum_ms"] > 0, p
    assert ph["freeze"]["sum_ms"] <= ph["publish"]["sum_ms"]


@pytest.mark.parametrize("mirror", [True, False],
                         ids=["mirror", "no_mirror"])
def test_mirror_invalidate_is_one_call_one_span_one_phase_a_group(
        mirror, monkeypatch):
    """A commit group tells the native mirror its written keys in ONE
    call, inside one ``commit.mirror_invalidate`` span whose interval is
    the group's ``mirror_invalidate`` phase, and the mirror advances in
    the same critical section as the publish, before the acknowledgement;
    a node without the mirror opens no span and counts no phase."""
    from conftest import RecordingMirror

    from antidote_tpu.store import kv

    calls, opened = [], []

    class Mirror(RecordingMirror):
        def invalidate_many(self, keys):
            assert node.txm.commit_lock._is_owned()
            calls.append(("invalidate", sorted(keys)))

        def advance(self, epoch_id, vc_list, clockless_ok):
            assert node.txm.commit_lock._is_owned()
            assert epoch_id == node.store.serving_epoch.id
            calls.append(("advance", epoch_id, clockless_ok))

    inner = kv.span

    def span(name, **ids):
        opened.append((name, ids))
        return inner(name, **ids)

    monkeypatch.setattr(kv, "span", span)
    node = AntidoteNode(mk_cfg())
    node.txm.enable_serving_epochs()
    # no read flows here: without this a group that follows another
    # within the window would put its publish off (the write-storm rule)
    node.txm.EPOCH_INLINE_PUBLISH_S = 0.0
    if mirror:
        node.store.native_mirror = Mirror(node.store)
    groups = 3
    for g in range(groups):
        txns = []
        for j in range(4):
            t = node.start_transaction()
            # two members write the same key: one name in the call
            node.update_objects(
                [(f"m{g}_{j // 2}", "counter_pn", "b", ("increment", 1))], t)
            txns.append(t)
        assert not any(isinstance(o, Exception)
                       for o in node.txm.commit_transactions_group(txns))
    ph = node.status()["write_plane"]["phases"]
    spans = [ids for name, ids in opened
             if name == "commit.mirror_invalidate"]
    if not mirror:
        assert not spans and ph["mirror_invalidate"] == {"sum_ms": 0.0,
                                                         "count": 0}
        return
    assert [ids["keys"] for ids in spans] == [2] * groups
    assert ph["mirror_invalidate"]["count"] == groups == ph["certify"]["count"]
    assert 0 <= ph["mirror_invalidate"]["sum_ms"] <= ph["certify"]["sum_ms"]
    # invalidate, then — once the group has published — advance; the ack
    # (commit_transactions_group returning) comes after both
    assert [c[0] for c in calls] == ["invalidate", "advance"] * groups
    assert calls[0][1] == [("m0_0", "b"), ("m0_1", "b")]
    ids = [c[1] for c in calls if c[0] == "advance"]
    assert ids == sorted(set(ids)) and all(c[2] for c in calls
                                           if c[0] == "advance")


@pytest.mark.parametrize("hits_flow", [True, False],
                         ids=["native_hits_flow", "idle"])
def test_native_hits_keep_the_epoch_plane_from_looking_idle(hits_flow):
    """The write-storm rule puts a commit group's publish off while no
    epoch read came since the last one.  Reads the C++ mirror answers
    never reach Python's counters: the mirror's hits count as reads, so a
    node whose readers all hit natively still publishes before each
    acknowledgement; with no hit and no read the second group defers."""
    from conftest import RecordingMirror

    class Mirror(RecordingMirror):
        hits = 0

        def native_hits(self):
            if hits_flow:
                Mirror.hits += 3
            return Mirror.hits

    node = AntidoteNode(mk_cfg())
    node.txm.enable_serving_epochs()
    node.txm.EPOCH_INLINE_PUBLISH_S = 60.0      # every group inside it
    node.store.native_mirror = Mirror(node.store)
    epochs = []
    for g in range(3):
        t = node.start_transaction()
        node.update_objects([(f"h{g}", "counter_pn", "b", ("increment", 1))],
                            t)
        assert not isinstance(node.txm.commit_transactions_group([t])[0],
                              Exception)
        epochs.append(node.store.serving_epoch.id)
    if hits_flow:
        assert epochs[0] < epochs[1] < epochs[2]
        assert node.txm.epoch_lag_counter == 0
    else:
        assert epochs[0] == epochs[1] == epochs[2]
        assert node.txm.epoch_lag_counter == node.txm.commit_counter


def _rounds_reach(srv, n, timeout=5.0):
    """The locked worker records a round after its last answer: wait
    until it has recorded ``n``."""
    deadline = time.monotonic() + timeout
    while srv._rounds.status()["rounds"] < n:
        assert time.monotonic() < deadline, "the round was never recorded"
        time.sleep(0.002)


def test_server_reports_ack_phase_and_locked_idle():
    node, srv = _boot(False)
    c = AntidoteClient(srv.host, srv.port)
    try:
        for i in range(4):
            c.update_objects([(f"k{i}", "counter_pn", "b", ("increment", 1))])
        _rounds_reach(srv, 4)
        st = c.node_status()
    finally:
        c.close()
        srv.close()
    wp = st["write_plane"]
    lk = wp["locked"]
    assert "locked_idle" not in wp and "ack" not in wp["phases"]
    assert lk["phases"]["ack"]["count"] == wp["phases"]["certify"]["count"]
    assert lk["phases"]["ack"]["count"] == node.txm.group_seq >= 1
    assert lk["rounds"] >= wp["group"]["count"]
    assert lk["idle_ms"] > 0 and lk["busy_ms"] > 0
    assert st["pipeline"]["paths"]["update"]["parked"]["count"] == 4


# ---------------------------------------------------------------------------
# (b') a round of the locked worker, and the metrics that read it
# ---------------------------------------------------------------------------
#: per-layer metrics of this PR, read from node_status() deltas
ROUND_METRICS = ["txn.round_ms", "txn.round_programs",
                 "txn.round_offcpu_share", "txn.round_stage_ms",
                 "kernels.call_ms", "kernels.call_offcpu_ms",
                 "kernels.compile_ms_per_s", "kernels.call_host_operands"]


@pytest.fixture(scope="module")
def round_traffic():
    """node_status() before and after: two static updates, a read inside
    an interactive transaction and its commit, and a static read the
    epoch could not serve (no epoch to pin) — one round each."""
    node, srv = _boot(False)
    c = AntidoteClient(srv.host, srv.port)
    try:
        c.update_objects([("r0", "counter_pn", "b", ("increment", 1))])
        _rounds_reach(srv, 1)
        st0 = c.node_status()
        groups0 = node.txm.group_seq
        for i in range(2):
            c.update_objects([(f"r{i}", "counter_pn", "b",
                               ("increment", 1))])
        t = c.start_transaction()
        assert t.read_objects([("r1", "counter_pn", "b")]) == [1]
        t.commit()
        node.txm.store.pin_serving_epoch = lambda: None
        vals, _ = c.read_objects([("r0", "counter_pn", "b")])
        assert vals == [2]
        _rounds_reach(srv, 6)
        st1 = c.node_status()
        groups = node.txm.group_seq - groups0
    finally:
        c.close()
        srv.close()
    return st0, st1, groups


def test_a_round_record_counts_each_phase_it_met(round_traffic):
    st0, st1, groups = round_traffic
    lk0, lk1 = (s["write_plane"]["locked"] for s in (st0, st1))
    d = {p: lk1["phases"][p]["count"] - lk0["phases"][p]["count"]
         for p in trace.ROUND_PHASES}
    rounds = lk1["rounds"] - lk0["rounds"]
    # updates x2 and the commit: three rounds that met the merge; the
    # transaction read and the static read one round each
    assert d == {"lock": rounds, "txn_read": 1, "stage": 3, "group": 3,
                 "ack": 3, "read": 1}
    assert rounds == 5 and groups >= 2
    assert lk1["programs"] > lk0["programs"]
    busy = lk1["busy_ms"] - lk0["busy_ms"]
    phases = sum(lk1["phases"][p]["sum_ms"] - lk0["phases"][p]["sum_ms"]
                 for p in trace.ROUND_PHASES)
    assert phases <= busy and phases == pytest.approx(busy, rel=0.05)
    assert 0 <= lk1["offcpu_ms"] - lk0["offcpu_ms"] <= busy
    assert lk1["idle_ms"] > lk0["idle_ms"]
    # the round's launches are the process's too
    tot0, tot1 = st0["programs"]["total"], st1["programs"]["total"]
    assert (tot1["launches"] - tot0["launches"]
            >= lk1["programs"] - lk0["programs"])


@pytest.mark.parametrize("metric", ROUND_METRICS)
def test_round_and_launch_metrics_read_a_number(round_traffic, metric):
    """Each metric file's reader finds its counters in a live status (a
    renamed counter would read nothing)."""
    import json
    import os
    from types import SimpleNamespace

    from benchmarks.readers import status_delta

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "layer_metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    st0, st1, _ = round_traffic
    v = status_delta.read(spec, SimpleNamespace(status={"window": [st0,
                                                                    st1]}))
    assert isinstance(v, float) and v >= 0, (metric, v)
    # nothing on a program without the counters
    bare = [{"pipeline": s["pipeline"]} for s in (st0, st1)]
    if metric != "kernels.compile_ms_per_s":
        assert status_delta.read(spec, SimpleNamespace(
            status={"window": bare})) is None


def test_device_program_counts_launches_and_compiles_by_name():
    import jax.numpy as jnp
    import numpy as np

    @trace.device_program("unit_counted")
    def fn(x):
        return x + 1

    zero = dict.fromkeys(trace.PROGRAM_FIELDS, 0)

    def row(name="unit_counted"):
        return trace.program_status().get(name, zero)

    def built(r):
        return r["compiles"] + r["cache_loads"]

    r0 = row()
    fn(jnp.ones((3,), jnp.float32))
    r1 = row()
    assert r1["launches"] == r0["launches"] + 1
    assert r1["host_operands"] == r0["host_operands"]    # a device array
    assert built(r1) == built(r0) + 1 and r1["compile_ms"] > r0["compile_ms"]
    fn(jnp.ones((3,), jnp.float32))                 # the same shape
    r2 = row()
    assert r2["launches"] == r1["launches"] + 1 and built(r2) == built(r1)
    fn(jnp.ones((5,), jnp.float32))                 # a new shape
    r3 = row()
    assert r3["launches"] == r2["launches"] + 1
    assert built(r3) == built(r2) + 1
    # off the CPU: part of the call (up to the CPU clock's resolution)
    assert -0.01 <= r3["offcpu_ms"] - r0["offcpu_ms"] <= (
        r3["call_ms"] - r0["call_ms"])
    # .lower is no launch, and the module keeps its name
    text = fn.lower(jnp.ones((4,), jnp.float32)).as_text()
    assert "module @jit_antidote_unit_counted " in text
    # traced inside another program: part of its launch, not one of its own
    jax.jit(lambda x: fn(x) * 2)(jnp.ones((6,), jnp.float32))
    assert row()["launches"] == r3["launches"]
    # an eager operation is counted under its own name
    x = jnp.arange(7919, dtype=jnp.int16)
    s0 = row("dynamic_slice")
    assert np.asarray(x[:3]).tolist() == [0, 1, 2]
    assert built(row("dynamic_slice")) == built(s0) + 1
    total = trace.program_status()["total"]
    assert total["launches"] >= r3["launches"]
    assert total["compiles"] + total["cache_loads"] >= built(r3) + 1
    assert set(total) == set(trace.PROGRAM_FIELDS)
    fn(np.ones((3,), np.float32))                   # a host array
    assert row()["host_operands"] == r3["host_operands"] + 1


def test_host_operands_count_only_the_host_leaves_of_a_launch():
    """A launch's host operands are the leaves of its arguments that are
    not device arrays: a table pytree and a carried state on the device
    count nothing, a static argument is no operand."""
    import jax.numpy as jnp
    import numpy as np

    @trace.device_program("unit_operands", static_argnames=("k",))
    def fn(table, staged, carried, k=1):
        return table["a"] + table["b"] + staged.sum() * k + carried

    def host():
        return trace.program_status()["unit_operands"]["host_operands"]

    table = {"a": jnp.ones((3,)), "b": jnp.ones((3,))}
    fn(table, np.ones((3,), np.float32), jnp.zeros((3,)), k=2)
    h0 = host()
    fn(table, np.ones((3,), np.float32), jnp.zeros((3,)), k=3)
    assert host() == h0 + 1                      # the staged operand alone
    fn(table, np.ones((3,), np.float32), jnp.zeros((3,)), 3)
    assert host() == h0 + 2                      # static by position too
    fn({"a": np.ones((3,)), "b": table["b"]}, np.ones((3,), np.float32),
       np.zeros((3,)), k=3)
    assert host() == h0 + 5                      # + a host field and state
    assert trace.program_status()["unit_operands"]["launches"] == 4


#: the programs that take a one-device table's read batch, or a replayed
#: log's piece, as one staged operand
ONE_OPERAND = ("head_gather", "head_state", "ckpt_gather",
               "replay_fold_serial", "read_resolved_")


@pytest.fixture(scope="module")
def warm_reads(tmp_path_factory):
    """program_status() around the reads a served mix makes of a table the
    warm walks have compiled for (``TypedTable.warm``,
    ``KVStore._warm_replay``): a versioned read on the locked plane, a read
    below the device's coverage, an epoch read, and the table's own
    head_state, versioned and checkpoint gathers."""
    import numpy as np

    node = AntidoteNode(mk_cfg(), log_dir=str(
        tmp_path_factory.mktemp("warm") / "log"))
    store, txm = node.store, node.txm
    txm.enable_serving_epochs()

    def inc(key):
        return node.update_objects([(key, "counter_pn", "b",
                                     ("increment", 1))])

    for _ in range(2):
        inc("d")
    cut_d = inc("d")
    inc("d")                                  # stale at cut_d, in the ring
    cut_c = [inc("c") for _ in range(30)][2]  # 30: GC'd past the versions
    t = store.table("counter_pn")
    assert t.warm()
    store._warm_replay(t.ty, t.cfg, with_base=False)
    txm.publish_serving_epoch()
    st0 = trace.program_status()
    vals = []
    for key, cut in (("d", cut_d), ("c", cut_c)):
        txn = node.start_transaction()
        txn.snapshot_vc = np.asarray(cut, np.int32)
        vals += node.read_objects([(key, "counter_pn", "b")], txn)
        node.commit_transaction(txn)
    ep = store.pin_serving_epoch()
    pending, fallback = store.epoch_read_launch(
        [("c", "counter_pn", "b"), ("d", "counter_pn", "b")], ep)
    vals += store.epoch_read_finish(pending)
    store.unpin_serving_epoch(ep)
    _, shard, row = store.directory[("d", "b")]
    vc = np.asarray([cut_d], np.int32)
    t.read_latest([shard], [row], vc)
    t.read([shard], [row], vc)
    t.gather_rows_dispatch([shard], [row])
    st1 = trace.program_status()
    assert fallback == [] and vals == [3, 3, 30, 4]
    assert store.replays == 1 and t.fold_launches >= 2
    return st0, st1


@pytest.mark.parametrize("program", ONE_OPERAND)
def test_a_read_launch_of_a_one_device_table_takes_one_host_operand(
        warm_reads, program):
    st0, st1 = warm_reads
    zero = dict.fromkeys(trace.PROGRAM_FIELDS, 0)
    names = [k for k in st1 if k.startswith(program)
             and (program != "read_resolved_" or k.endswith("_flat"))]
    d = {f: sum(st1[k][f] - st0.get(k, zero)[f] for k in names)
         for f in ("launches", "host_operands")}
    assert d["launches"] >= 1 and d["host_operands"] == d["launches"], (
        program, names, d)


def test_warm_walks_leave_a_served_read_nothing_to_compile(warm_reads):
    st0, st1 = warm_reads
    built = [s["total"]["compiles"] + s["total"]["cache_loads"]
             for s in (st0, st1)]
    assert built[1] == built[0], sorted(
        k for k in st1 if k != "total" and st1[k]["compiles"]
        + st1[k]["cache_loads"] != st0.get(k, st1[k])["compiles"]
        + st0.get(k, st1[k])["cache_loads"])


# ---------------------------------------------------------------------------
# (c) the native plane's crossing counters
# ---------------------------------------------------------------------------
def test_native_cross_frames_equal_forwarded():
    node, srv = _boot(True)
    c = AntidoteClient(srv.host, srv.port)
    try:
        for i in range(N_KEYS):
            c.update_objects([(f"k{i}", "counter_pn", "b", ("increment", 1))])
        for i in range(N_KEYS):
            c.read_objects([(f"k{i}", "counter_pn", "b")])
        time.sleep(0.2)        # the last reply's bytes reach the socket
        nat = c.node_status()["pipeline"]["native"]
        # the status request itself has crossed but not been answered yet
        assert nat["cross_frames"] == nat["forwarded"] >= 2 * N_KEYS
        assert nat["send_frames"] == nat["forwarded"] - 1
        assert nat["cross_wait_us"] >= 0 and nat["send_wait_us"] >= 0
    finally:
        c.close()
        srv.close()


# ---------------------------------------------------------------------------
# the accumulators and the span call themselves
# ---------------------------------------------------------------------------
def test_stage_accumulator_names_the_stage_closed_at_ready():
    acc = total = trace.StageAccumulator()
    #        arrive taken submit deq  launch wb   sync ready sent
    acc.close("gather", (1, 1), 7, (1.0, 1.1, 1.2, 1.3, 1.4, 1.5, 1.6, 1.7,
                                    1.8))
    acc.close("update", (1, 2), 3, (2.0, 0.0, 2.1, 2.2, 0.0, 0.0, 0.0, 2.6,
                                    2.7))
    acc.close("cache", (1, 3), 0, (3.0, 3.1, 0.0, 0.0, 0.0, 0.0, 0.0, 3.2,
                                   3.3))
    st = total.status()
    assert set(st["paths"]["gather"]) == set(trace.STAGES) - {"exec"} | {
        "total"}
    assert set(st["paths"]["update"]) == {"decode", "parked", "exec",
                                          "reply", "total"}
    assert st["paths"]["update"]["exec"]["sum_ms"] == pytest.approx(400.0)
    assert set(st["paths"]["cache"]) == {"cross", "exec", "reply", "total"}
    assert [r["id"] for r in st["slow_requests"]] == [[1, 1], [1, 2], [1, 3]]
    assert st["slow_requests"][0]["batch"] == 7
    assert total.status()["slow_requests"] == []        # a new window
    # the sums run on since boot, the slow records since the last read
    acc.close("cache", (2, 1), 0, (5.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 5.1,
                                   5.2))
    st2 = total.status()
    assert st2["paths"]["gather"] == st["paths"]["gather"]
    assert st2["paths"]["cache"]["total"]["count"] == 2
    assert [r["id"] for r in st2["slow_requests"]] == [[2, 1]]


def test_stage_accumulator_keeps_only_the_slowest():
    total = trace.StageAccumulator()
    for i in range(3 * trace.SLOW_KEPT):
        total.close("other", (1, i), 0,
                    (0.0 + 1e-9, 0, 0, 0, 0, 0, 0, 0, float(i + 1)))
    slow = total.status()["slow_requests"]
    assert [r["id"][1] for r in slow] == list(
        range(3 * trace.SLOW_KEPT - 1, 2 * trace.SLOW_KEPT - 1, -1))


def test_phase_accumulator_sums_to_last_minus_first():
    acc = trace.PhaseAccumulator()
    acc.add_group((10.0, 10.5, 10.5, 11.0, 11.25, 11.25, 12.0), 0.125)
    st = acc.status()
    assert sum(st[p]["sum_ms"] for p in trace.COMMIT_PHASES) == 2000.0
    assert st["wal_append"] == {"sum_ms": 0.0, "count": 1}
    assert st["freeze"]["sum_ms"] == 125.0
    assert set(st) == set(trace.COMMIT_PHASES + trace.EXTRA_PHASES)


def test_round_accumulator_counts_only_the_phases_a_round_met():
    acc = trace.RoundAccumulator()
    acc.add_round(0.5, 0.25, 0.125, 3, (0.0, None, 0.05, 0.15, 0.05, None))
    acc.add_round(1.0, 0.5, 0.25, 5, (0.0, 0.4, None, None, None, 0.1))
    st = acc.status()
    assert (st["rounds"], st["idle_ms"], st["busy_ms"], st["offcpu_ms"],
            st["programs"]) == (2, 1500.0, 750.0, 375.0, 8)
    assert {p: v["count"] for p, v in st["phases"].items()} == {
        "lock": 2, "txn_read": 1, "stage": 1, "group": 1, "ack": 1,
        "read": 1}
    assert sum(v["sum_ms"] for v in st["phases"].values()) == \
        pytest.approx(st["busy_ms"])


def test_span_is_a_trace_annotation_and_inert_without_a_session():
    s = trace.span("serve.launch", batch=3, objects=64)
    assert isinstance(s, jax.profiler.TraceAnnotation)
    with s:
        pass


# ---------------------------------------------------------------------------
# (d) every jitted serving program lowers under a stable name
# ---------------------------------------------------------------------------
class _Names(logging.Handler):
    def __init__(self):
        super().__init__(logging.DEBUG)
        self.names = []

    def emit(self, record):
        m = re.search(r"Compiling (?:jit\()?([^ ()]+)", record.getMessage())
        if m:
            self.names.append(m.group(1))


@pytest.fixture(scope="module")
def lowered_names(tmp_path_factory):
    """Names of every module lowered while a node on a 2-device mesh
    serves writes past a ring overflow, reads at the head, at an earlier
    snapshot, through the frozen serving epoch and the mesh gather, a
    tier promotion, a checkpoint gather and an eviction."""
    from antidote_tpu.parallel.mesh import MeshServingPlane

    cfg = AntidoteConfig(
        n_shards=2, max_dcs=2, ops_per_key=4, snap_versions=2, set_slots=2,
        keys_per_table=8, batch_buckets=(8,), use_pallas=False)
    h = _Names()
    lg = logging.getLogger("jax")
    lg.addHandler(h)
    try:
        with jax.log_compiles():
            for mesh in (False, True):
                node = AntidoteNode(cfg)
                store, txm = node.store, node.txm
                # tier tables built inside the commit group, on the
                # thread whose compile log is listened to
                store.prepare_tier = lambda tname: None
                if mesh:
                    MeshServingPlane(cfg, n_devices=2).attach(store)
                txm.enable_serving_epochs()
                t_old = None
                for i in range(6):       # ring of 4: GC; 2 slots: promotion
                    t = node.start_transaction()
                    # the counter twice: the commit's whole-ring window
                    node.update_objects(
                        [("s", "set_aw", "b", ("add", f"e{i}")),
                         ("c", "counter_pn", "b", ("increment", 1))]
                        + [("c", "counter_pn", "b", ("increment", 1))] * (i == 5),
                        t)
                    node.commit_transaction(t)
                    if i == 3:
                        t_old = node.start_transaction()
                txm.publish_serving_epoch()
                node.read_objects([("c", "counter_pn", "b")], t_old)
                node.commit_transaction(t_old)
                objs = [("s", "set_aw", "b"), ("c", "counter_pn", "b")]
                node.read_objects(objs)
                ep = store.pin_serving_epoch()
                pending, fallback = store.epoch_read_launch(objs, ep)
                store.epoch_read_finish(pending)
                store.unpin_serving_epoch(ep)
                t = store.table("counter_pn")
                t.gather_rows_dispatch([0], [0])
                t.publish_epoch()
                t.evict_rows([0], [0])  # evict-ok: names only, node dropped
                if mesh:
                    store.mesh.stable_vc()
    finally:
        lg.removeHandler(h)
    return h.names


PROGRAMS = [
    "antidote_commit_scatter_w1", "antidote_commit_scatter_w0",
    "antidote_gc", "antidote_tier_promote", "antidote_head_gather",
    "antidote_head_gather_routed", "antidote_row_state",
    "antidote_read_resolved_", "antidote_freeze_serving_copy",
    "antidote_freeze_serving_scatter", "antidote_clear_rows",
    "antidote_mesh_gather", "antidote_mesh_pmin", "antidote_ckpt_gather",
]


@pytest.mark.parametrize("program", PROGRAMS)
def test_device_program_lowers_under_its_stable_name(lowered_names, program):
    assert any(n.startswith(program) for n in lowered_names), (
        program, sorted(set(lowered_names)))


def test_no_device_program_lowers_under_an_inner_function_name(lowered_names):
    # what is left without the prefix are jax's own eager primitives
    # (jit(broadcast_in_dim), ...): none of them an inner function's name
    anonymous = {"fn", "read", "append", "gc", "<lambda>", "_lambda_", "body",
                 "step", "program", "<unnamed", "per_shard"}
    bad = [n for n in lowered_names
           if not n.startswith(trace.PROGRAM_PREFIX)
           and any(n.startswith(a) for a in anonymous)]
    assert not bad, sorted(set(bad))


def test_device_program_module_name_scope_and_donation():
    import jax.numpy as jnp

    @trace.device_program("unit_double", donate_argnums=(0,))
    def fn(x, y):
        return x * 2 + y

    low = fn.lower(jnp.ones((4,), jnp.float32), jnp.ones((4,), jnp.float32))
    text = low.as_text(debug_info=True)
    assert "module @jit_antidote_unit_double " in text
    assert "antidote_unit_double/" in text          # the named_scope
    assert "jax.buffer_donor" in text or "tf.aliasing_output" in text
    assert fn.__name__ == "antidote_unit_double"

    # static argument NAMES resolve through the body's own signature
    @trace.device_program("unit_scale", static_argnames=("k",))
    def scale(x, k: int):
        return x * k if k > 1 else x

    assert float(scale(jnp.ones(()), 3)) == 3.0


@pytest.mark.parametrize("kernel", ["counter_fold", "set_aw_fold",
                                    "orset_presence"])
def test_pallas_kernel_carries_its_name(kernel):
    import inspect

    from antidote_tpu.materializer import pallas_kernels as pk

    src = inspect.getsource(pk)
    assert f'name="antidote_{kernel}"' in src
    assert f'@device_program("{kernel}"' in src
