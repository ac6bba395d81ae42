"""ISSUE 5 serving-pipeline tests: lock-split epoch reads, the hot-key
snapshot cache, bounded publication cost, and the staged wire server.

The load-bearing properties:

  * epoch-pinned static reads execute OUTSIDE the server/commit locks —
    a held commit lock (a stalled commit group, a publication tick) can
    no longer stall a parked read batch;
  * every read returns a published-epoch-consistent snapshot: a commit
    group is never split across an epoch boundary (no torn reads), and
    a read admitted after a write's ack sees that write (no
    stale-past-epoch values);
  * the snapshot cache invalidates on epoch advance for written rows
    and revalidates across arbitrarily many unrelated publishes;
  * publication cost scales with rows written since the last publish
    (never table size) and is capped per tick.
"""

from __future__ import annotations

import threading
import time

import pytest

from antidote_tpu.api.node import AntidoteNode
from antidote_tpu.config import AntidoteConfig
from antidote_tpu.proto.client import AntidoteClient
from antidote_tpu.proto.server import ProtocolServer

pytestmark = pytest.mark.smoke


def _mk(**kw):
    cfg = AntidoteConfig(n_shards=4, max_dcs=2, keys_per_table=256, **kw)
    node = AntidoteNode(cfg)
    srv = ProtocolServer(node, port=0, epoch_tick_ms=25)
    return node, srv


def _wait_epoch_covers(node, timeout=5.0):
    """Wait until the published serving epoch covers every acked commit
    (rapid write batches defer inline publishes behind the ISSUE 6 rate
    limit; the ticker covers them within a tick)."""
    txm = node.txm
    deadline = time.monotonic() + timeout
    while (node.store.serving_epoch is None
           or int(node.store.serving_epoch.vc[txm.my_dc])
           < txm.commit_counter):
        assert time.monotonic() < deadline, "epoch never covered commits"
        time.sleep(0.005)


# ---------------------------------------------------------------------------
# lock-split: reads never park behind the commit/server locks
# ---------------------------------------------------------------------------
def test_epoch_reads_not_stalled_by_held_commit_lock():
    node, srv = _mk()
    c = AntidoteClient(srv.host, srv.port, timeout=30)
    try:
        c.update_objects([("hot", "counter_pn", "b", ("increment", 7))])
        c.update_objects([("cold", "counter_pn", "b", ("increment", 3))])
        c.read_objects([("hot", "counter_pn", "b")])  # prime the cache
        assert node.store.serving_epoch is not None
        # wedge BOTH locks the old path parked behind: a publication
        # tick / commit group in progress must not stall epoch reads
        with node.txm.commit_lock, srv._lock:
            c2 = AntidoteClient(srv.host, srv.port, timeout=5)
            t0 = time.monotonic()
            vals, _ = c2.read_objects([("hot", "counter_pn", "b")])
            assert vals == [7]  # cache plane
            vals, _ = c2.read_objects([("cold", "counter_pn", "b")])
            assert vals == [3]  # gather plane (first read of this key)
            elapsed = time.monotonic() - t0
            c2.close()
        assert elapsed < 4.0, f"reads stalled {elapsed:.1f}s behind locks"
    finally:
        c.close()
        srv.close()


# ---------------------------------------------------------------------------
# read/write concurrency: epoch-consistent snapshots, no torn reads
# ---------------------------------------------------------------------------
def test_concurrent_commits_and_epoch_reads_see_consistent_snapshots():
    node, srv = _mk()
    stop = time.monotonic() + 3.0
    errors: list = []
    pair = [("a", "counter_pn", "b"), ("b", "counter_pn", "b")]

    def writer():
        try:
            c = AntidoteClient(srv.host, srv.port)
            while time.monotonic() < stop:
                # ONE txn bumps both keys: any epoch-consistent snapshot
                # shows them EQUAL — a mismatch is a torn read
                c.update_objects([
                    ("a", "counter_pn", "b", ("increment", 1)),
                    ("b", "counter_pn", "b", ("increment", 1)),
                ])
            c.close()
        except Exception as e:  # pragma: no cover - failure detail
            errors.append(repr(e))

    def reader():
        try:
            c = AntidoteClient(srv.host, srv.port)
            last_v = -1
            last_vc = None
            while time.monotonic() < stop:
                vals, vc = c.read_objects(pair)
                if vals[0] != vals[1]:
                    errors.append(f"torn read: {vals}")
                    break
                if vals[0] < last_v:
                    errors.append(f"snapshot went backwards: {vals[0]} "
                                  f"< {last_v}")
                    break
                if last_vc is not None and any(
                        n < o for n, o in zip(vc, last_vc)):
                    errors.append(f"clock went backwards: {vc} < {last_vc}")
                    break
                last_v, last_vc = vals[0], vc
            c.close()
        except Exception as e:  # pragma: no cover - failure detail
            errors.append(repr(e))

    ts = [threading.Thread(target=writer)] + [
        threading.Thread(target=reader) for _ in range(3)
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    srv.close()
    assert not errors, errors
    # the epoch plane actually served (not everything fell to locked)
    m = node.metrics
    assert (m.serving_reads.value(path="cache")
            + m.serving_reads.value(path="gather")) > 0


def test_write_then_clockless_read_sees_the_write():
    node, srv = _mk()
    c = AntidoteClient(srv.host, srv.port)
    try:
        for i in range(1, 40):
            c.update_objects([("rw", "counter_pn", "b", ("increment", 1))])
            vals, _ = c.read_objects([("rw", "counter_pn", "b")])
            assert vals == [i], (i, vals)
    finally:
        c.close()
        srv.close()


# ---------------------------------------------------------------------------
# snapshot cache correctness
# ---------------------------------------------------------------------------
def test_cache_hit_after_epoch_advance_on_written_key_misses():
    node, srv = _mk()
    c = AntidoteClient(srv.host, srv.port)
    try:
        c.update_objects([("k", "set_aw", "b", ("add", 1))])
        vals, _ = c.read_objects([("k", "set_aw", "b")])
        assert vals[0] == [1]
        m = node.metrics
        hits0 = m.snapshot_cache.value(event="hit")
        # same-epoch re-read: a hit
        vals, _ = c.read_objects([("k", "set_aw", "b")])
        assert vals[0] == [1]
        assert m.snapshot_cache.value(event="hit") == hits0 + 1
        # the write advances the epoch and re-freezes k's row: the
        # cached entry MUST miss (serving it would lose the new element)
        c.update_objects([("k", "set_aw", "b", ("add", 2))])
        hits1 = m.snapshot_cache.value(event="hit")
        vals, _ = c.read_objects([("k", "set_aw", "b")])
        assert sorted(vals[0]) == [1, 2]
        assert m.snapshot_cache.value(event="hit") == hits1
    finally:
        c.close()
        srv.close()


def test_cache_revalidates_across_unrelated_epoch_advances():
    node, srv = _mk()
    c = AntidoteClient(srv.host, srv.port)
    try:
        # two priming writes first: the double buffer's first TWO
        # publishes are whole-table copies (both slots must exist), and
        # a copy in the history chain correctly blocks revalidation
        c.update_objects([("warm0", "set_aw", "b", ("add", 1))])
        c.update_objects([("warm1", "set_aw", "b", ("add", 1))])
        c.update_objects([("stable", "set_aw", "b", ("add", 9))])
        _wait_epoch_covers(node)  # rapid writes defer inline publishes
        # (ISSUE 6 rate limit); the cache fill needs a covering epoch
        vals, _ = c.read_objects([("stable", "set_aw", "b")])
        assert vals[0] == [9]
        ep0 = node.store.serving_epoch.id
        # many unrelated writes advance the epoch (rapid-fire batches
        # defer behind the inline-publish rate limit, ISSUE 6 — the
        # ticker covers them within a tick, so wait for the advance and
        # for the epoch to cover every acked commit)
        for i in range(10):
            c.update_objects([(f"other{i}", "set_aw", "b", ("add", i))])
        _wait_epoch_covers(node)
        assert node.store.serving_epoch.id > ep0
        m = node.metrics
        hits0 = m.snapshot_cache.value(event="hit")
        vals, _ = c.read_objects([("stable", "set_aw", "b")])
        assert vals[0] == [9]
        assert m.snapshot_cache.value(event="hit") == hits0 + 1, (
            "untouched key failed to revalidate across unrelated epochs")
    finally:
        c.close()
        srv.close()


# ---------------------------------------------------------------------------
# publication cost: scales with writes, capped, never stalls readers
# ---------------------------------------------------------------------------
def test_publish_cost_scales_with_rows_written_not_table_size():
    cfg = AntidoteConfig(n_shards=4, max_dcs=2, keys_per_table=512)
    node = AntidoteNode(cfg)
    txm = node.txm
    store = node.store
    m = node.metrics
    # seed + the first two publishes are whole-table copies (both slots
    # of the double buffer must exist before incremental freezes begin)
    node.update_objects([("seed", "counter_pn", "b", ("increment", 1))])
    assert store.publish_serving_epoch(txm.serving_epoch_vc()) == "published"
    node.update_objects([("seed", "counter_pn", "b", ("increment", 1))])
    assert store.publish_serving_epoch(txm.serving_epoch_vc()) == "published"
    assert m.epoch_publish.value(mode="copy") == 2
    # k rows written => the next publish scatters the rows written
    # since the SPARE slot's freeze (two publish windows: the one seed
    # row from before the second copy, plus the k fresh rows) —
    # independent of the table's 4*512 row capacity
    k = 7
    node.update_objects([
        (f"k{i}", "counter_pn", "b", ("increment", 1)) for i in range(k)
    ])
    rows0 = m.epoch_rows.value(mode="scatter")
    assert store.publish_serving_epoch(txm.serving_epoch_vc()) == "published"
    assert m.epoch_rows.value(mode="scatter") - rows0 == k + 1
    assert m.epoch_publish.value(mode="copy") == 2  # still no full copy
    # noop when nothing changed
    assert store.publish_serving_epoch(txm.serving_epoch_vc()) == "noop"
    # past the dirty cap the freeze degrades to an EXPLICIT full copy
    # (a 10k-row scatter stops beating the copy) — the cost cap is
    # visible in the mode counters either way
    t = store.table("counter_pn")
    t._SERVING_DIRTY_CAP = 4
    node.update_objects([
        (f"w{i}", "counter_pn", "b", ("increment", 1)) for i in range(6)
    ])
    assert store.publish_serving_epoch(txm.serving_epoch_vc()) == "published"
    assert m.epoch_publish.value(mode="copy") == 3


def test_table_epoch_ladder_budget_one_per_tick():
    cfg = AntidoteConfig(n_shards=4, max_dcs=2, keys_per_table=256)
    node = AntidoteNode(cfg)
    srv = ProtocolServer(node, port=0, epoch_tick_ms=0)
    # stop the ticker (it drives the ladder even with the epoch plane
    # off) so the budgeted calls below can't race it
    srv._ticker_stop.set()
    srv._ticker.join(timeout=5)
    c = AntidoteClient(srv.host, srv.port)
    try:
        store = node.store
        # two dirty tables, both eligible for a ladder publish
        c.update_objects([("x", "counter_pn", "b", ("increment", 1))])
        c.update_objects([("y", "set_aw", "b", ("add", 1))])
        for t in store.tables.values():
            t.slow_serves += 1
            t._pub_at = 0.0
            if hasattr(t, "_pub_slow_serves"):
                del t._pub_slow_serves
        n_tables = len(store.tables)
        assert n_tables >= 2
        # each tick publishes AT MOST one table's full-head epoch copy
        assert srv._publish_table_epochs_capped() == 1
        assert srv._publish_table_epochs_capped() == 1
        assert sum(
            1 for t in store.tables.values() if t.epochs
        ) == 2
    finally:
        c.close()
        srv.close()


# ---------------------------------------------------------------------------
# epoch ticker: publication without static-batch traffic
# ---------------------------------------------------------------------------
def test_ticker_publishes_without_any_static_traffic():
    cfg = AntidoteConfig(n_shards=4, max_dcs=2, keys_per_table=256)
    node = AntidoteNode(cfg)
    # data lands BEFORE the server exists (no publish hooks active)
    node.update_objects([("pre", "counter_pn", "b", ("increment", 5))])
    assert node.store.serving_epoch is None
    srv = ProtocolServer(node, port=0, epoch_tick_ms=25)
    try:
        deadline = time.monotonic() + 5.0
        while node.store.serving_epoch is None:
            assert time.monotonic() < deadline, (
                "ticker never published an epoch")
            time.sleep(0.05)
        assert int(node.store.serving_epoch.vc[0]) >= 1
    finally:
        srv.close()


def test_epoch_tick_zero_disables_the_epoch_plane():
    cfg = AntidoteConfig(n_shards=4, max_dcs=2, keys_per_table=256)
    node = AntidoteNode(cfg)
    srv = ProtocolServer(node, port=0, epoch_tick_ms=0)
    c = AntidoteClient(srv.host, srv.port)
    try:
        assert not srv._epoch_reads
        c.update_objects([("k", "counter_pn", "b", ("increment", 2))])
        vals, _ = c.read_objects([("k", "counter_pn", "b")])
        assert vals == [2]
        assert node.store.serving_epoch is None
    finally:
        c.close()
        srv.close()


# ---------------------------------------------------------------------------
# promotion: the serving epoch survives a tier crossing
# ---------------------------------------------------------------------------
def test_promotion_keeps_serving_epoch_and_reads_stay_exact():
    node, srv = _mk()
    c = AntidoteClient(srv.host, srv.port)
    try:
        store = node.store
        cap = store.cfg.set_slots
        # grow one set key across at least one slot-tier boundary while
        # reading it back between writes
        n = cap * 3
        for i in range(n):
            c.update_objects([("grow", "set_aw", "b", ("add", i))])
            if i % 7 == 0:
                vals, _ = c.read_objects([("grow", "set_aw", "b")])
                assert sorted(vals[0]) == list(range(i + 1))
        assert store.promotions >= 1
        # the fix under test: a promotion no longer nukes the serving
        # epoch (no whole-table copy republish storm)
        assert store.serving_epoch is not None
        vals, _ = c.read_objects([("grow", "set_aw", "b")])
        assert sorted(vals[0]) == list(range(n))
        # reads of OTHER keys kept their cache/gather plane alive
        c.update_objects([("bystander", "set_aw", "b", ("add", 1))])
        vals, _ = c.read_objects([("bystander", "set_aw", "b")])
        assert vals[0] == [1]
    finally:
        c.close()
        srv.close()


# ---------------------------------------------------------------------------
# clocked reads against the epoch plane
# ---------------------------------------------------------------------------
def test_clocked_read_at_returned_epoch_clock():
    node, srv = _mk()
    c = AntidoteClient(srv.host, srv.port)
    try:
        c.update_objects([("ck", "counter_pn", "b", ("increment", 4))])
        vals, vc = c.read_objects([("ck", "counter_pn", "b")])
        assert vals == [4]
        # hand the epoch clock back as the causal clock: still served,
        # still exact (covered => epoch-eligible)
        vals2, vc2 = c.read_objects([("ck", "counter_pn", "b")], clock=vc)
        assert vals2 == [4]
        assert all(b >= a for a, b in zip(vc, vc2))
        # a clock AHEAD of the epoch falls back to the locked path
        ahead = list(vc)
        ahead[0] += 1
        c.update_objects([("ck", "counter_pn", "b", ("increment", 1))])
        vals3, _ = c.read_objects([("ck", "counter_pn", "b")], clock=ahead)
        assert vals3 == [5]
    finally:
        c.close()
        srv.close()


def test_wrong_type_read_raises_even_when_cached():
    """Cache residency must never change observable behavior: a read of
    a key under the WRONG CRDT type raises the same TypeError whether
    the key's value sits in the snapshot cache or not."""
    from antidote_tpu.proto.client import RemoteError

    node, srv = _mk()
    c = AntidoteClient(srv.host, srv.port)
    try:
        c.update_objects([("typed", "counter_pn", "b", ("increment", 3))])
        vals, _ = c.read_objects([("typed", "counter_pn", "b")])
        assert vals == [3]  # cached now
        with pytest.raises(RemoteError, match="bound"):
            c.read_objects([("typed", "set_aw", "b")])
    finally:
        c.close()
        srv.close()


def test_pipeline_status_block_exposed():
    node, srv = _mk()
    c = AntidoteClient(srv.host, srv.port)
    try:
        c.update_objects([("s", "counter_pn", "b", ("increment", 1))])
        c.read_objects([("s", "counter_pn", "b")])
        st = c.node_status()
        pl = st["pipeline"]
        assert pl["epoch_reads"] is True
        assert set(pl["stages"]) == {"decode", "parked", "launch",
                                     "writeback", "request"}
        for s in pl["stages"].values():
            assert {"count", "sum_ms", "mean_us", "p50_us",
                    "p99_us"} <= set(s)
        assert pl["serving_epoch_id"] >= 1
        assert "hit" in pl["snapshot_cache"] or pl["snapshot_cache"]
        assert set(pl["gate_hold"]) == {"rounds", "held", "sum_ms"}
        assert pl["gate_hold"]["rounds"] >= 1
        assert pl["writeback_depth"] == 0
        # the Python plane: a thread waits on every work, none is direct
        assert pl["direct"] == {"served": 0, "worker": 0}
    finally:
        c.close()
        srv.close()


# ---------------------------------------------------------------------------
# ISSUE 25: the backpressure is taken before the launch — the dispatcher
# holds the batch gate until a writeback slot is free
# ---------------------------------------------------------------------------
DEPTH = ProtocolServer.DEPTH
N_HELD = 9          # reads parked while every slot is taken


class _Pipeline:
    """A Python-plane server over N counters (key i reads i + 1) whose
    writeback stage the test can hold inside ``epoch_read_finish``."""

    def __init__(self, n_keys):
        self.node = AntidoteNode(AntidoteConfig(
            n_shards=4, max_dcs=2, keys_per_table=256))
        self.srv = ProtocolServer(self.node, port=0, epoch_tick_ms=25,
                                  native_frontend=False)
        c = AntidoteClient(self.srv.host, self.srv.port)
        try:
            for i in range(n_keys):
                c.update_objects(
                    [(f"k{i}", "counter_pn", "b", ("increment", i + 1))])
        finally:
            c.close()
        _wait_epoch_covers(self.node)
        self.store = self.node.txm.store
        self.gate = threading.Event()
        self.gate.set()
        finish = self.store.epoch_read_finish

        def held_finish(pending):
            assert self.gate.wait(30), "test never released the writeback"
            return finish(pending)

        self.store.epoch_read_finish = held_finish
        self.results = {}
        self.threads = []

    def status(self):
        return self.srv._pipeline_status()

    def read(self, i, deadline=None):
        """One one-object static read on a thread of its own (what a
        connection thread does once the frame is decoded)."""
        def run():
            try:
                vals, _vc = self.srv.static_read(
                    [(f"k{i}", "counter_pn", "b")], None, deadline=deadline)
                self.results[i] = vals
            except BaseException as e:  # noqa: BLE001 — the test reads it
                self.results[i] = e

        t = threading.Thread(target=run, daemon=True)
        t.start()
        self.threads.append(t)
        return t

    def fill_slots(self):
        """Hold the writeback stage and launch DEPTH one-read batches, one
        after the other: every slot is taken, nothing is held yet."""
        self.gate.clear()
        seq0 = self.srv._launch_seq
        for i in range(DEPTH):
            self.read(i)
            self.wait(lambda: self.srv._launch_seq == seq0 + i + 1)
        assert self.srv._wb_unfinished == DEPTH

    def wait(self, cond, timeout=10.0):
        deadline = time.monotonic() + timeout
        while not cond():
            assert time.monotonic() < deadline, "condition never held"
            time.sleep(0.002)

    def wait_submitted(self, n):
        """Until n reads beyond the DEPTH launched ones are inside
        ``_submit``: parked at the gate, or in the hands of the
        dispatcher, which took the first and holds."""
        from antidote_tpu.tenancy import DEFAULT_TENANT

        self.wait(lambda: self.srv.admission.tenant_in_flight(
            DEFAULT_TENANT) == DEPTH + n)
        self.wait(lambda: self.srv._static_q.qsize() < n)
        time.sleep(0.05)

    def join(self):
        for t in self.threads:
            t.join(10)
            assert not t.is_alive()

    def close(self):
        self.gate.set()
        self.srv.close()


def test_held_gate_merges_everything_parked_into_one_launch():
    p = _Pipeline(DEPTH + N_HELD)
    try:
        before = p.status()
        p.fill_slots()
        for i in range(DEPTH, DEPTH + N_HELD):
            p.read(i)
        p.wait_submitted(N_HELD)
        time.sleep(0.1)
        # every slot taken: nothing more was launched, whatever parked
        held = p.status()
        assert (held["stages"]["launch"]["count"]
                - before["stages"]["launch"]["count"]) == DEPTH
        assert held["reads"]["gather"] - before["reads"].get(
            "gather", 0) == DEPTH
        assert p.srv._wb_unfinished == DEPTH
        p.gate.set()
        p.join()
        after = p.status()
    finally:
        p.close()
    # ... and the rest rode ONE merged launch once a slot came free
    assert (after["stages"]["launch"]["count"]
            - before["stages"]["launch"]["count"]) == DEPTH + 1
    assert after["reads"]["gather"] - before["reads"].get(
        "gather", 0) == DEPTH + N_HELD
    assert p.results == {i: [i + 1] for i in range(DEPTH + N_HELD)}
    hold0, hold1 = before["gate_hold"], after["gate_hold"]
    assert hold1["rounds"] - hold0["rounds"] == DEPTH + 1
    assert hold1["held"] - hold0["held"] == 1
    assert hold1["sum_ms"] - hold0["sum_ms"] >= 100.0
    assert p.srv._wb_unfinished == 0


def test_lone_read_on_an_idle_server_is_not_held():
    p = _Pipeline(1)
    try:
        before = p.status()["gate_hold"]
        p.read(0).join(10)
        after = p.status()["gate_hold"]
    finally:
        p.close()
    assert p.results == {0: [1]}
    assert after["rounds"] == before["rounds"] + 1
    assert after["held"] == before["held"]
    assert after["sum_ms"] == before["sum_ms"]


@pytest.mark.parametrize("fault", ["no_epoch", "below_lag_floor",
                                   "launch_raises", "all_rerouted",
                                   "clock_ahead"])
def test_rounds_that_hand_nothing_to_writeback_leak_no_slot(fault):
    """More than DEPTH consecutive rounds on each path of a launch chunk
    that hands nothing over: a leaked slot would wedge the next read."""
    import numpy as np

    from antidote_tpu.proto.server import _StaticWork

    n = DEPTH + 2
    p = _Pipeline(n + 1)
    store, txm = p.store, p.node.txm
    try:
        if fault == "clock_ahead":
            # nothing mergeable: the locked plane would park such a read
            # until its clock is covered, so the chunk is driven directly
            ahead = np.asarray(store.serving_epoch.vc) + 1
            for i in range(n):
                w = _StaticWork("read", objects=[(f"k{i}", "counter_pn",
                                                  "b")], clock=ahead)
                assert p.srv._take_wb_slot()
                assert p.srv._launch_epoch_chunk([w]) == [w]
                assert p.srv._wb_unfinished == 0
        else:
            if fault == "no_epoch":
                store.pin_serving_epoch = lambda: None
            elif fault == "below_lag_floor":
                floor, txm.epoch_lag_counter = txm.epoch_lag_counter, 1 << 60
            elif fault == "launch_raises":
                def launch(objs, ep):
                    raise RuntimeError("planted launch fault")
            else:
                real = store.epoch_read_launch

                def launch(objs, ep):
                    pending, _fb = real(objs, ep)
                    return pending, list(range(len(objs)))
            if fault in ("launch_raises", "all_rerouted"):
                store.epoch_read_launch = launch
            for i in range(n):                  # one round each
                p.read(i).join(10)
                assert p.srv._wb_unfinished == 0
            # the locked plane answered every one of them, exactly
            assert p.results == {i: [i + 1] for i in range(n)}
            assert p.status()["reads"]["locked"] == n
            if fault == "below_lag_floor":
                txm.epoch_lag_counter = floor
            else:
                for name in ("pin_serving_epoch", "epoch_read_launch"):
                    store.__dict__.pop(name, None)
        seq0 = p.srv._launch_seq
        p.read(n).join(10)                      # a plain read is served
        assert p.results[n] == [n + 1]
        assert p.srv._launch_seq == seq0 + 1
        assert p.srv._wb_unfinished == 0
    finally:
        p.close()


def test_close_during_a_hold_fails_what_is_parked_promptly():
    p = _Pipeline(DEPTH + 4)
    try:
        p.fill_slots()
        for i in range(DEPTH, DEPTH + 4):
            p.read(i)
        p.wait_submitted(4)
        seq0 = p.srv._launch_seq
        t0 = time.monotonic()
        closer = threading.Thread(target=p.srv.close, daemon=True)
        closer.start()
        # the hold ends on shutdown, not on a slot: the writeback stage is
        # still held, and what was parked fails as the gate's remainder does
        for t in p.threads[DEPTH:]:
            t.join(5)
            assert not t.is_alive()
        assert time.monotonic() - t0 < 1.0
        for i in range(DEPTH, DEPTH + 4):
            assert isinstance(p.results[i], ConnectionError), p.results[i]
        assert p.srv._launch_seq == seq0        # and nothing was launched
        p.gate.set()
        closer.join(5)
        assert not closer.is_alive()
        assert time.monotonic() - t0 < 2.0
        # what was launched before the close is still answered
        p.join()
        assert [p.results[i] for i in range(DEPTH)] == [
            [i + 1] for i in range(DEPTH)]
    finally:
        p.gate.set()


def test_deadline_passing_during_a_hold_sheds_at_dequeue():
    from antidote_tpu.overload import DeadlineExceeded

    p = _Pipeline(DEPTH + 2)
    try:
        before = p.status()
        p.fill_slots()
        p.read(DEPTH, deadline=time.monotonic() + 0.05)
        p.read(DEPTH + 1)
        p.wait_submitted(2)
        time.sleep(0.1)                 # the deadline passes in the hold
        p.gate.set()
        p.join()
        after = p.status()
    finally:
        p.close()
    assert isinstance(p.results[DEPTH], DeadlineExceeded), p.results[DEPTH]
    assert p.results[DEPTH + 1] == [DEPTH + 2]
    # the expired read was not launched: one object in the merged launch
    assert after["reads"]["gather"] - before["reads"].get(
        "gather", 0) == DEPTH + 1


def test_slots_stay_bounded_and_balanced_under_a_thread_storm():
    """More reader threads than cores against a slowed writeback stage,
    with a short switch interval: never more than DEPTH batches launched
    and unfinished, every slot given back, every answer exact."""
    import sys

    n_keys, n_threads, rounds = 48, 24, 6
    p = _Pipeline(n_keys)
    p.store.snapshot_cache_cap = 0          # every read is a gather
    finish = p.store.epoch_read_finish
    seen = []

    def slow_finish(pending):
        seen.append((p.srv._wb_unfinished, p.srv._writeback_q.qsize()))
        time.sleep(0.002)
        return finish(pending)

    p.store.epoch_read_finish = slow_finish
    wrong = []

    def reader(t):
        for r in range(rounds):
            i = (t * rounds + r) % n_keys
            vals, _vc = p.srv.static_read([(f"k{i}", "counter_pn", "b")],
                                          None)
            if vals != [i + 1]:
                wrong.append((i, vals))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=reader, args=(t,), daemon=True)
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
            assert not t.is_alive()
        hold = p.status()["gate_hold"]
    finally:
        sys.setswitchinterval(interval)
        p.close()
    assert not wrong
    assert seen and max(u for u, _q in seen) <= DEPTH
    assert max(q for _u, q in seen) <= DEPTH - 1
    assert p.srv._wb_unfinished == 0
    assert hold["held"] >= 1 and hold["rounds"] == len(seen)
    # held rounds merged what parked: fewer launches than reads
    assert len(seen) < n_threads * rounds


# ---------------------------------------------------------------------------
# ISSUE 28: a submit is a park and a wait; a batch's stage records close
# under one lock take
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("refusal", ["gate_full", "closing"])
def test_refused_park_leaves_the_tenant_account_as_it_was(refusal):
    from antidote_tpu.overload import BusyError
    from antidote_tpu.tenancy import DEFAULT_TENANT

    p = _Pipeline(1)
    try:
        p.store.snapshot_cache_cap = 0
        p.read(0).join(10)                       # a park that is served
        assert p.results.pop(0) == [1]
        if refusal == "gate_full":
            caps = p.srv._static_q.lane_caps
            p.srv._static_q.lane_caps = dict.fromkeys(caps, 0)
            want = BusyError
        else:
            p.srv._closing = True
            want = ConnectionError
        p.read(0).join(10)
        assert isinstance(p.results[0], want), p.results[0]
        assert p.srv.admission.tenant_in_flight(DEFAULT_TENANT) == 0
        if refusal == "gate_full":
            p.srv._static_q.lane_caps = caps
        else:
            p.srv._closing = False
        p.read(0).join(10)                       # and the gate still serves
        assert p.results[0] == [1]
        assert p.srv.admission.tenant_in_flight(DEFAULT_TENANT) == 0
    finally:
        p.close()


def test_close_many_folds_like_one_close_each():
    from antidote_tpu.obs.trace import StageAccumulator

    recs = [
        ("gather", (1, 1), 7, (1.0, 1.1, 1.2, 1.5, 1.6, 1.9, 2.0, 2.2, 2.3)),
        ("gather", (2, 1), 7, (1.05, 1.1, 1.3, 1.5, 1.6, 1.9, 2.0, 2.25, 2.3)),
        ("cache", (3, 4), 7, (1.0, 1.1, 1.2, 1.5, 1.6, 1.9, 0.0, 2.2, 2.3)),
        ("shed", (4, 2), 0, (1.0, 1.1, 1.2, 1.5, 0.0, 0.0, 0.0, 0.0, 1.6)),
        ("other", (5, 9), 0, (1.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 1.4, 1.45)),
    ]
    one, many = StageAccumulator(), StageAccumulator()
    totals = [one.close(*r) for r in recs]
    assert many.close_many(recs) == totals
    assert totals[0] == pytest.approx(1.3)
    a, b = one.status(), many.status()
    assert a == b
    assert a["paths"]["gather"]["total"]["count"] == 2
    assert a["paths"]["gather"]["reply"]["sum_ms"] == pytest.approx(150.0)
    assert len(a["slow_requests"]) == len(recs)


# ---------------------------------------------------------------------------
# the writeback stage fills cache and mirror once a launch (ISSUE 30)
# ---------------------------------------------------------------------------
_FILL_OBJS = ([(f"k{i}", "counter_pn", "b") for i in range(8)]
              + [(f"s{i}", "set_aw", "b") for i in range(3)])


@pytest.mark.parametrize("mirror", [True, False],
                         ids=["recording_mirror", "no_mirror"])
def test_writeback_fills_cache_and_mirror_once_a_launch(mirror):
    from conftest import check_writeback_fill

    node = AntidoteNode(AntidoteConfig(
        n_shards=4, max_dcs=2, keys_per_table=256))
    for i in range(8):
        node.update_objects(
            [(f"k{i}", "counter_pn", "b", ("increment", i + 1))])
    for i in range(3):
        node.update_objects([(f"s{i}", "set_aw", "b", ("add", f"e{i}"))])
    node.txm.publish_serving_epoch()
    vals = check_writeback_fill(node.store, _FILL_OBJS, mirror)
    assert vals[:8] == list(range(1, 9))
    assert [sorted(v) for v in vals[8:]] == [["e0"], ["e1"], ["e2"]]
    # every key is now a cache hit at that epoch: nothing left to gather
    ep = node.store.pin_serving_epoch()
    try:
        pending, _fb = node.store.epoch_read_launch(_FILL_OBJS[-5:], ep)
        assert not pending.launches
    finally:
        node.store.unpin_serving_epoch(ep)


def test_served_batch_fills_before_its_first_reply():
    """Through the server: a batch of K gathered reads makes one mirror
    fill, inside ``epoch_read_finish`` (so before any reply of the batch
    and before the epoch is unpinned)."""
    from conftest import RecordingMirror

    k = 6
    p = _Pipeline(k)
    try:
        log = []
        rec = RecordingMirror(p.store, log)
        p.store.native_mirror = rec
        finish = p.store.epoch_read_finish

        def logged_finish(pending):
            try:
                return finish(pending)
            finally:
                log.append("finished")

        p.store.epoch_read_finish = logged_finish
        launches0 = p.status()["stages"]["launch"]["count"]
        results = {}

        def read(i):
            results[i] = p.srv.static_read(
                [(f"k{i}", "counter_pn", "b")], None)[0]
            log.append("reply")

        p.gate.clear()
        threads = [threading.Thread(target=read, args=(i,), daemon=True)
                   for i in range(k)]
        threads[0].start()
        p.wait(lambda: p.srv._wb_unfinished == 1)
        for t in threads[1:]:
            t.start()
        p.wait(lambda: p.srv._wb_unfinished == DEPTH)
        time.sleep(0.05)
        p.gate.set()
        for t in threads:
            t.join(10)
            assert not t.is_alive()
        assert results == {i: [i + 1] for i in range(k)}
        n_launch = p.status()["stages"]["launch"]["count"] - launches0
        assert len(rec.many) == n_launch and not rec.single
        assert sum(len(e) for e, _id, _p in rec.many) == k
        assert all(pins >= 1 for _e, _id, pins in rec.many)
        # a launch's fill, then its finish returns, then its replies: no
        # read is answered before the fill that holds its key is done
        assert [x for x in log if x != "reply"] \
            == ["fill", "finished"] * n_launch
        filled = replied = 0
        batches = iter(rec.many)
        for x in log:
            if x == "finished":
                filled += len(next(batches)[0])
            elif x == "reply":
                replied += 1
                assert replied <= filled, log
        assert replied == k
        assert set(p.store.snapshot_cache) >= {
            (f"k{i}", "b") for i in range(k)}
    finally:
        p.close()
