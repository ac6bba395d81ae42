"""ISSUE 33: the deployment `set_aw_1m_rw` at a small size — add-wins sets
that take updates past a key's op ring (GC), past their element slots
(slot-tier promotion, three tiers up) and reads at snapshots that later
writes made history (the versioned ring fold, the log replay under it) —
held, answer by answer, to the benchmark's plain reference
(`benchmarks/reference/model.py`, a dict model fed the same operations)
and to the comparison that decides the cell's `correct`
(`benchmarks/check.py` `compare`).

Small widths (4 shards x 64 rows, `set_slots` 4, `ops_per_key` 4), so a
ring overflows every fourth op of a key and tiers 1-3 hold 16 / 64 / 256
elements: the same programs the chip runs at [16, 65536] (`tier_promote`,
`clear_rows`, the flat versioned read), one device and the mesh placement
alike, and both ways the tables are written: scattered, as a table this
small is, and row by row in the table's own layout, as a million-row
table's buckets are (`typed_table._write_rows`; fixture `writes`).

Its time limits are its own: every join and socket wait is bounded.
"""

from __future__ import annotations

import json
import os
import random
import threading
import time

import jax
import numpy as np
import pytest

from antidote_tpu.api.node import AntidoteNode
from antidote_tpu.config import AntidoteConfig
from antidote_tpu.parallel import MeshServingPlane
from antidote_tpu.proto.client import AntidoteClient
from antidote_tpu.proto.server import ProtocolServer
from antidote_tpu.store import kv, typed_table
from benchmarks import check, data, loadgen
from benchmarks.reference.model import Model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = AntidoteConfig(
    n_shards=4, max_dcs=2, ops_per_key=4, snap_versions=2, set_slots=4,
    keys_per_table=64, batch_buckets=(8, 64))
FILL = {"type": "set_aw", "bucket": "bench", "key_prefix": "k",
        "fill_keys": 48, "batch_keys": 48, "connections": 1,
        "add_all_elements": 3, "remove_every": 10}
SEED = 4294967311          # the driver's seeds pass 2**31
JOIN_S = 120.0


def _node(tmp_path, mesh_devices=None):
    plane = MeshServingPlane(CFG, mesh_devices) if mesh_devices else None
    node = AntidoteNode(
        CFG, log_dir=str(tmp_path),
        sharding=plane.sharding if plane is not None else None)
    if plane is not None:
        plane.metrics = node.metrics
        plane.attach(node.store)
    return node


def _obj(i):
    return data.obj(FILL, i)


def _upd(i, op, arg):
    return [(data.key_name(FILL, i), FILL["type"], FILL["bucket"],
             (op, arg))]


# ---------------------------------------------------------------------------
# one writer, every answer exact
# ---------------------------------------------------------------------------
@pytest.fixture(params=["scatter", "row_writes"])
def writes(request, monkeypatch):
    """Both ways `typed_table._write_rows` has: a table this small is
    scattered; with the floor at 0 every bucket of rows is written row
    by row in the table's own layout, as a million-row table's is."""
    if request.param == "row_writes":
        monkeypatch.setattr(typed_table, "_ROW_WRITE_MIN_ROWS", 0)
    return request.param


def test_row_writes_leave_the_same_tables_as_scatters(monkeypatch):
    """The same appends, GCs, freezes and clears on two tables, one
    scattered and one written row by row: every device array equal."""
    from antidote_tpu.crdt import get_type
    from antidote_tpu.store import TypedTable

    def drive():
        t = TypedTable(get_type("set_aw"), CFG)
        ty, rng = t.ty, random.Random(7)
        d = CFG.max_dcs
        for step in range(40):
            m = rng.choice([1, 2, 5, 9])
            keys = [rng.randrange(12) for _ in range(m)]
            ss = np.asarray([k % CFG.n_shards for k in keys], np.int64)
            rr = np.asarray([k // CFG.n_shards for k in keys], np.int64)
            a = np.asarray([[rng.randrange(1, 9)] for _ in keys], np.int64)
            b = np.zeros((m, ty.eff_b_width(CFG)), np.int32)
            vcs = np.zeros((m, d), np.int32)
            vcs[:, 0] = step + 1
            t.append(ss, rr, a, b, vcs, np.zeros(m, np.int32))
            if step % 5 == 4:
                t.freeze_serving(True)
            if step == 30:
                t.clear_rows([1], [0])
        return jax.tree.map(np.asarray, (t._tree(), t._serving))

    want = drive()
    monkeypatch.setattr(typed_table, "_ROW_WRITE_MIN_ROWS", 0)
    got = drive()
    for x, y in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("placement", ["one_device", "mesh4"])
def test_hot_key_through_three_tiers_equals_the_reference(tmp_path, writes,
                                                          placement):
    """One hot key driven through `api/node.py` past its ring dozens of
    times and up three slot tiers with removes in between, Zipf traffic
    over the rest, and reads inside interactive transactions whose
    snapshots later writes make history: every answer equals the
    reference's at that snapshot."""
    if placement == "mesh4":
        assert len(jax.devices()) >= 4, "conftest forces 8 devices"
    node = _node(tmp_path, 4 if placement == "mesh4" else None)
    store = node.store
    model = Model()
    rng = random.Random(SEED)
    for txn in data.fill_batch(FILL, SEED, 0, FILL["fill_keys"]):
        node.update_objects(txn)
        model.apply(txn)
    order = data.KeyOrder(SEED, FILL["fill_keys"])
    draw = loadgen.KeyDraw({"distribution": "zipf", "s": 1.0},
                           FILL["fill_keys"], order)
    hot = order(0)
    own = {}                  # key -> elements this test added, still in
    n_elem = 0
    open_txns = []            # (transaction, the model's commit number)
    compared = {"static": 0, "txn": 0, "txn_history": 0}

    def update(i):
        nonlocal n_elem
        mine = own.setdefault(i, [])
        if mine and rng.random() < 0.2:
            u = _upd(i, "remove", mine.pop(rng.randrange(len(mine))))
        else:
            n_elem += 1
            mine.append(f"e:{n_elem}")
            u = _upd(i, "add", mine[-1])
        node.update_objects(u)
        model.apply(u)

    for step in range(420):
        r = rng.random()
        if r < 0.45:
            update(hot)
        elif r < 0.65:
            update(draw(rng))
        elif r < 0.80:
            i = hot if rng.random() < 0.3 else draw(rng)
            vals, _ = node.read_objects([_obj(i)])
            assert sorted(vals[0]) == model.value(_obj(i)), (step, i)
            compared["static"] += 1
        elif r < 0.95 and open_txns:
            txn, at = open_txns[rng.randrange(len(open_txns))]
            i = hot if rng.random() < 0.5 else draw(rng)
            vals = node.read_objects([_obj(i)], txn)
            assert sorted(vals[0]) == model.value(_obj(i), at), (step, i, at)
            compared["txn"] += 1
            compared["txn_history"] += model.value(_obj(i), at) != \
                model.value(_obj(i))
        else:
            if len(open_txns) == 3:
                node.commit_transaction(open_txns.pop(0)[0])
            open_txns.append((node.start_transaction(), model.commit_no))
    for txn, at in open_txns:
        vals = node.read_objects([_obj(hot)], txn)
        assert sorted(vals[0]) == model.value(_obj(hot), at)
        node.commit_transaction(txn)

    # it was this deployment's work: the hot key went up three tiers, its
    # ring overflowed again and again, transactions read history
    assert store.directory[(data.key_name(FILL, hot), FILL["bucket"])][0] \
        == "set_aw#3"
    tiers = store.tier_status()
    assert set(tiers["tiers"]["promotions_by_tier"]) >= {"1", "2", "3"}
    assert tiers["gc"]["launches"] > 20 and tiers["gc"]["rows"] >= \
        tiers["gc"]["launches"]
    if placement == "one_device":
        assert tiers["tiers"]["prepared"] >= 3, "tiers are built ahead"
    assert compared["txn_history"] > 10, compared
    assert len(model.value(_obj(hot))) > 64
    fold = store.fold_status()
    assert fold["launches"] > 0 and fold["rows"] >= fold["launches"]
    assert fold["reads_by_fold"] > 0 and fold["reads_by_head"] > 0
    # history the device had dropped came from the log: by the key's own
    # records (one walk of the shard's files a key), folded onto a base
    assert fold["replays"] > 0 and fold["replay_sum_ms"] > 0
    assert 0 < fold["replay_records"]
    assert len(store.log._hist) <= fold["replays"]
    # and everything reads back
    keys = list(range(FILL["fill_keys"]))
    vals, _ = node.read_objects([_obj(i) for i in keys])
    assert [sorted(v) for v in vals] == [model.value(_obj(i)) for i in keys]
    store.log.close()


# ---------------------------------------------------------------------------
# the cell's own traffic over the wire
# ---------------------------------------------------------------------------
def _mix():
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "mixed_zipf.json")) as f:
        mix = json.load(f)
    return dict(mix, **mix["rehearse"])


@pytest.mark.parametrize("placement", ["one_device", "mesh4"])
def test_mixed_zipf_wire_answers_equal_the_reference(tmp_path, writes,
                                                     placement):
    """The mix file of `set_aw_1m_rw.mixed_zipf` from the benchmark's own
    generator clients, after a warm walk that takes the hottest key to
    tier 2 as the cell's does: `benchmarks/check.py` finds every answer
    inside its bounds and every key's end state exact."""
    node = _node(tmp_path, 4 if placement == "mesh4" else None)
    srv = ProtocolServer(node, port=0, epoch_tick_ms=25,
                         max_in_flight_per_client=256)
    conn = AntidoteClient("127.0.0.1", srv.port, timeout=JOIN_S)
    clients = []
    try:
        for txn in data.fill_batch(FILL, SEED, 0, FILL["fill_keys"]):
            conn.update_objects(txn)
        mix = _mix()
        order = data.KeyOrder(SEED, FILL["fill_keys"])
        hot, warm = order(0), []
        for n in range(24):            # 3 + 24 elements: tier 2 (64 slots)
            t0 = time.monotonic()
            conn.update_objects(_upd(hot, "add", f"warm:{n}"))
            warm.append((hot, "add", f"warm:{n}", t0, time.monotonic(),
                         False, -1))
        # the read paths' programs compile before the window, not in it
        txn = conn.start_transaction()
        conn.update_objects(_upd(hot, "add", "warm:x"))
        warm.append((hot, "add", "warm:x", 0.0, time.monotonic(), False, -1))
        txn.read_objects([_obj(hot), _obj(order(1))])
        txn.commit()
        conn.read_objects([_obj(i) for i in range(FILL["fill_keys"])])
        spec = {"host": "127.0.0.1", "port": srv.port, "client": "wire",
                "seed": SEED, "mix": mix, "fill": FILL, "timeout": JOIN_S,
                "atoms": [tuple(a) for a in loadgen.atoms(mix)]}
        draw = loadgen.KeyDraw(mix["keys"], FILL["fill_keys"], order)
        now = time.monotonic()
        times = [now + 0.1, now + 1.1, now + 6.1]
        clients = [loadgen.Client(spec, cid, draw, times)
                   for cid in range(mix["clients"])]
        for c in clients:
            c.start()
        for c in clients:
            c.join(JOIN_S)
        assert not any(c.is_alive() for c in clients), "a client hung"
        assert not [c.error for c in clients if c.error]
        log = {"updates": [u + (c.cid,) for c in clients for u in c.updates],
               "reads": [r for c in clients for r in c.reads],
               "never": sum(c.never for c in clients)}
        keys = list(range(FILL["fill_keys"]))
        vals, _ = conn.read_objects([_obj(i) for i in keys])
        checks, counts = check.compare(
            FILL, SEED, [log, {"updates": warm, "reads": [], "never": 0}],
            list(zip(keys, vals)))
        status = conn.node_status()
    finally:
        for c in clients:
            c.conn.close()
        conn.close()
        srv.close()
        node.store.log.close()
    assert check.verdict(checks), (checks, counts["first_wrong"])
    assert counts["window_answers_compared"] > 10
    assert any(r[1] for r in log["reads"]), "no read inside a transaction"
    # what the cell's new metrics read is there, and says what happened
    wp, fold = status["write_plane"], status["pipeline"]["fold"]
    assert wp["gc"]["launches"] > 0 and wp["gc"]["sum_ms"] > 0
    assert wp["tiers"]["promotions_by_tier"]["1"] >= 1
    assert wp["tiers"]["promotions_by_tier"]["2"] >= 1
    assert wp["tiers"]["promote_sum_ms"] > 0
    assert wp["tiers"]["rows"]["set_aw#1"] == kv.tier_rows(CFG, 1)
    assert fold["reads_by_head"] + fold["reads_by_fold"] > 0


# ---------------------------------------------------------------------------
# a tier table that grows under epoch readers
# ---------------------------------------------------------------------------
def test_tier_table_grows_while_epoch_readers_read(tmp_path, monkeypatch,
                                                   writes):
    """Tier tables start with a few rows a shard and double when a shard
    fills.  Here tier 1 starts with 2: one key after another outgrows its
    4 slots while reader connections keep reading over the wire (the
    lock-free epoch path among them), and no reader ever sees a value the
    reference does not hold at that moment."""
    monkeypatch.setattr(kv, "_TIER_ROWS", (2, 2, 2))
    node = _node(tmp_path)
    srv = ProtocolServer(node, port=0, epoch_tick_ms=5,
                         max_in_flight_per_client=256)
    conn = AntidoteClient("127.0.0.1", srv.port, timeout=JOIN_S)
    model = Model()
    stop = threading.Event()
    wrong, n_read = [], [0]
    # readers read the keys the writer leaves alone: their values are fixed
    still = list(range(24, 48))
    try:
        for txn in data.fill_batch(FILL, SEED, 0, FILL["fill_keys"]):
            conn.update_objects(txn)
            model.apply(txn)
        want = {i: model.value(_obj(i)) for i in still}

        def reader(seed):
            rng = random.Random(seed)
            c = AntidoteClient("127.0.0.1", srv.port, timeout=JOIN_S)
            try:
                while not stop.is_set():
                    i = still[rng.randrange(len(still))]
                    vals, _ = c.read_objects([_obj(i)])
                    n_read[0] += 1
                    if sorted(vals[0]) != want[i]:
                        wrong.append((i, vals[0]))
            finally:
                c.close()

        readers = [threading.Thread(target=reader, args=(s,), daemon=True)
                   for s in range(4)]
        for t in readers:
            t.start()
        # every written key takes 6 adds on top of its 2-3: tier 1, and
        # with 24 of them over 4 shards a shard's 2 tier rows fill twice
        for n in range(6):
            for i in range(24):
                u = _upd(i, "add", f"g:{i}:{n}")
                conn.update_objects(u)
                model.apply(u)
        vals, _ = conn.read_objects([_obj(i) for i in range(24)])
        stop.set()
        for t in readers:
            t.join(JOIN_S)
        tiers = conn.node_status()["write_plane"]["tiers"]
    finally:
        stop.set()
        conn.close()
        srv.close()
        node.store.log.close()
    assert not wrong, wrong[:3]
    assert n_read[0] > 50
    assert [sorted(v) for v in vals] == [model.value(_obj(i))
                                         for i in range(24)]
    assert tiers["grows"] >= 2
    assert tiers["rows"]["set_aw#1"] >= 8
    assert tiers["promotions_by_tier"]["1"] == 24


def test_a_tier_built_ahead_is_taken_or_hands_its_programs_over(monkeypatch):
    """`prepare_tier` builds a tier table on the building thread;
    `table()` takes it from there.  A promotion that comes before the
    thread is done builds the table itself and is handed the thread's
    compiled programs when that is done — also when the thread finishes
    while `table()` is still building: no table is left behind, and a
    program the thread compiled is never compiled again."""
    from antidote_tpu.store import TypedTable

    builds = []
    monkeypatch.setattr(kv, "_enqueue_build", builds.append)
    store = kv.KVStore(CFG)
    store.table("set_aw")
    # the thread is done first: the promotion takes its table
    store.prepare_tier("set_aw#1")
    store.prepare_tier("set_aw#1")           # asked once
    assert len(builds) == 1 and "set_aw#1" not in store.tables
    builds.pop()()
    ahead = store._tier_ready["set_aw#1"]
    assert store.table("set_aw#1") is ahead and not store._tier_ready
    # the promotion is first: it builds its own, and takes the programs
    store.prepare_tier("set_aw#2")
    own = store.table("set_aw#2")
    assert "_gc_fn" not in vars(own) and not own._resolved_flat_fns
    builds.pop()()
    assert store.table("set_aw#2") is own and not store._tier_ready
    assert "_gc_fn" in vars(own) and own._commit_scatter_fns.keys() == {0, 1}
    assert own._resolved_flat_fns and own.head is not None
    # the thread is done while the promotion is still building its own
    store.prepare_tier("set_aw#3")
    build_new = store._new_table

    def racing(tname):
        t = build_new(tname)
        if builds:
            builds.pop()()
        return t

    store._new_table = racing
    own3 = store.table("set_aw#3")
    assert store.tables["set_aw#3"] is own3 and not store._tier_ready
    assert "_gc_fn" in vars(own3) and own3.head is not None
    assert store.tier_status()["tiers"]["prepared"] == 3
    # every program of a table is kept under a name adopt_programs finds
    assert all(n.endswith("_fn") for n, v in vars(TypedTable).items()
               if isinstance(v, kv.functools.cached_property))
    # and what was handed over works on the table that took it
    d = CFG.max_dcs
    own.append([0], [0], np.asarray([[7]], np.int64),
               np.zeros((1, own.ty.eff_b_width(own.cfg)), np.int32),
               np.ones((1, d), np.int32), [0])
    own.gc([0], [0])
    state, _, complete = own.read([0], [0], np.ones((1, d), np.int32))
    assert complete.all() and int(state["elems"][0][0]) == 7


def test_a_replay_reads_its_keys_records_and_folds_onto_its_base(
        tmp_path, monkeypatch):
    """Reads below the device's coverage: the shard's files are walked
    once a key (`LogManager.key_history`), later replays fold what was
    logged since the key's base, the base moves up when its tail grows
    long, a snapshot older than the base is answered from the bottom —
    every answer the reference's."""
    from antidote_tpu.log import wal

    monkeypatch.setattr(kv.KVStore, "_REPLAY_TAIL_MAX", 8)
    walks = []
    walk = wal.replay_segments
    monkeypatch.setattr(
        "antidote_tpu.log.replay_segments",
        lambda paths, prefix=None: walks.append(prefix) or walk(
            paths, prefix))
    node = _node(tmp_path)
    store = node.store
    model = Model()
    rng = random.Random(SEED)
    txns = []                              # (transaction, commit number)
    seen_bases = set()
    for step in range(120):
        i = step % 2                       # two keys, both hot
        if rng.random() < 0.25 and step > 8:
            u = _upd(i, "remove", f"e{rng.randrange(step)}")
        else:
            u = _upd(i, "add", f"e{step}")
        node.update_objects(u)
        model.apply(u)
        if step % 7 == 0:
            txns.append((node.start_transaction(), model.commit_no))
        for txn, at in txns[-3:] + txns[:1]:
            vals = node.read_objects([_obj(i)], txn)
            assert sorted(vals[0]) == model.value(_obj(i), at), (step, at)
        ent = store._replay_bases.get((data.key_name(FILL, i),
                                       FILL["bucket"]))
        if ent is not None:
            seen_bases.add(tuple(ent[2]))
            assert len(ent[3]) <= 8 + 1
    fold = store.fold_status()
    assert fold["replays"] > 50
    assert len(walks) == 2 and all(walks), "one walk a key, by its prefix"
    assert len(seen_bases) > 4, "the bases moved up"
    # what a replay folds is the tail, not the key's log
    assert fold["replay_records"] < fold["replays"] * 30
    for txn, _ in txns:
        node.commit_transaction(txn)
    store.log.close()
