"""Wire-protocol tests over a real TCP socket — the analogue of
``pb_client_SUITE`` (/root/reference/test/singledc/pb_client_SUITE.erl:85-102):
per-CRDT coverage through the client, interactive transactions, abort,
error replies, and causal-clock chaining."""

import threading

import pytest

from antidote_tpu.api.node import AntidoteNode
from antidote_tpu.config import AntidoteConfig
from antidote_tpu.proto.client import AntidoteClient, RemoteAbort, RemoteError
from antidote_tpu.proto.server import ProtocolServer


@pytest.fixture(scope="module")
def server():
    cfg = AntidoteConfig(
        n_shards=2, max_dcs=2, ops_per_key=8, snap_versions=2,
        set_slots=8, rga_slots=16, keys_per_table=64, batch_buckets=(8, 64),
    )
    node = AntidoteNode(cfg)
    srv = ProtocolServer(node, port=0)
    yield srv
    srv.close()


@pytest.fixture()
def client(server):
    c = AntidoteClient(port=server.port)
    yield c
    c.close()


def test_static_counter_roundtrip(client):
    clock = client.update_objects([("pbc", "counter_pn", "b", ("increment", 4))])
    vals, _ = client.read_objects([("pbc", "counter_pn", "b")], clock=clock)
    assert vals[0] == 4


def test_interactive_txn(client):
    txn = client.start_transaction()
    txn.update_objects([("pbi", "counter_pn", "b", ("increment", 2))])
    # read-your-writes inside the txn
    assert txn.read_objects([("pbi", "counter_pn", "b")])[0] == 2
    clock = txn.commit()
    vals, _ = client.read_objects([("pbi", "counter_pn", "b")], clock=clock)
    assert vals[0] == 2


def test_abort_discards_writes(client):
    txn = client.start_transaction()
    txn.update_objects([("pba", "counter_pn", "b", ("increment", 9))])
    txn.abort()
    vals, _ = client.read_objects([("pba", "counter_pn", "b")])
    assert vals[0] == 0


def test_per_crdt_coverage(client):
    clock = client.update_objects([
        ("s", "set_aw", "b", ("add", 7)),
        ("s", "set_aw", "b", ("add", 9)),
        ("r", "register_lww", "b", ("assign", "hello")),
        ("mv", "register_mv", "b", ("assign", 5)),
        ("f", "flag_ew", "b", ("enable", None)),
        ("seq", "rga", "b", ("add_right", (0, "x"))),
    ])
    vals, _ = client.read_objects(
        [("s", "set_aw", "b"), ("r", "register_lww", "b"),
         ("mv", "register_mv", "b"), ("f", "flag_ew", "b"),
         ("seq", "rga", "b")],
        clock=clock,
    )
    assert sorted(vals[0]) == [7, 9]
    assert vals[1] == "hello"
    assert vals[2] == [5]
    assert vals[3] is True
    assert vals[4] == ["x"]


def test_map_rr_over_wire(client):
    clock = client.update_objects([
        ("m", "map_rr", "b",
         ("update", [(("cnt", "counter_pn"), ("increment", 3)),
                     (("who", "register_lww"), ("assign", "ada"))])),
    ])
    vals, _ = client.read_objects([("m", "map_rr", "b")], clock=clock)
    assert vals[0][("cnt", "counter_pn")] == 3
    assert vals[0][("who", "register_lww")] == "ada"


def test_certification_conflict_is_remote_abort(client):
    # read-bearing txns: blind increments would take the ISSUE 6
    # commutativity bypass and both commit (see next test)
    t1 = client.start_transaction()
    t2 = client.start_transaction()
    t1.read_objects([("cert", "counter_pn", "b")])
    t2.read_objects([("cert", "counter_pn", "b")])
    t1.update_objects([("cert", "counter_pn", "b", ("increment", 1))])
    t2.update_objects([("cert", "counter_pn", "b", ("increment", 1))])
    t1.commit()
    with pytest.raises(RemoteAbort):
        t2.commit()


def test_blind_interactive_commits_merge_without_conflict(client):
    """Interactive BLIND commits ride the locked worker's merge point
    and the commutativity bypass: concurrent increments to one hot key
    all land (no first-committer aborts), and the value adds up."""
    t1 = client.start_transaction()
    t2 = client.start_transaction()
    t1.update_objects([("blind", "counter_pn", "b", ("increment", 2))])
    t2.update_objects([("blind", "counter_pn", "b", ("increment", 3))])
    t1.commit()
    t2.commit()
    vals, _ = client.read_objects([("blind", "counter_pn", "b")])
    assert vals[0] == 5


def test_error_reply_keeps_connection(client):
    with pytest.raises(RemoteError):
        client.update_objects([("x", "no_such_type", "b", ("inc", 1))])
    # connection still usable
    clock = client.update_objects([("x2", "counter_pn", "b", ("increment", 1))])
    vals, _ = client.read_objects([("x2", "counter_pn", "b")], clock=clock)
    assert vals[0] == 1


def test_unknown_txid_is_error(client):
    with pytest.raises(RemoteError):
        client._call_unknown_commit()


# minimal helper used above — keeps the client API surface clean
def _call_unknown_commit(self):
    from antidote_tpu.proto.codec import MessageCode

    return self._call(MessageCode.COMMIT_TRANSACTION, {"txid": 10**9})


AntidoteClient._call_unknown_commit = _call_unknown_commit


def test_concurrent_clients(server):
    """Many clients hammer the acceptor pool concurrently; every increment
    must land exactly once (the dispatcher serializes the commit stream)."""
    n_clients, n_ops = 8, 10
    errs = []

    def work(i):
        try:
            c = AntidoteClient(port=server.port)
            for _ in range(n_ops):
                c.update_objects([("conc", "counter_pn", "b", ("increment", 1))])
            c.close()
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    c = AntidoteClient(port=server.port)
    vals, _ = c.read_objects([("conc", "counter_pn", "b")])
    c.close()
    assert vals[0] == n_clients * n_ops


# ---------------------------------------------------------------------------
# cross-connection static batch gate (r4 VERDICT item 3)
# ---------------------------------------------------------------------------
def test_static_batch_concurrent_reads_and_updates():
    import threading

    from antidote_tpu.api.node import AntidoteNode
    from antidote_tpu.config import AntidoteConfig
    from antidote_tpu.proto.client import AntidoteClient
    from antidote_tpu.proto.server import ProtocolServer

    cfg = AntidoteConfig(n_shards=4, max_dcs=2, keys_per_table=64,
                         batch_buckets=(16, 64))
    node = AntidoteNode(cfg)
    srv = ProtocolServer(node, port=0)
    try:
        n_cli, per = 8, 12
        errs = []

        def worker(i):
            try:
                c = AntidoteClient(srv.host, srv.port)
                for j in range(per):
                    c.update_objects([(i * 1000 + j, "counter_pn", "b",
                                       ("increment", 1))])
                    vals, _vc = c.read_objects(
                        [(i * 1000 + j, "counter_pn", "b")])
                    assert vals[0] == 1, vals
                c.close()
            except Exception as e:  # pragma: no cover
                errs.append(repr(e))

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(n_cli)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not errs, errs
        # all writes landed: a single merged read sees every counter
        c = AntidoteClient(srv.host, srv.port)
        objs = [(i * 1000 + j, "counter_pn", "b")
                for i in range(n_cli) for j in range(per)]
        vals, _vc = c.read_objects(objs)
        assert all(v == 1 for v in vals)
        c.close()
    finally:
        srv.close()


def test_group_commit_abort_isolation():
    """Two conflicting updates in one group: first commits, second aborts;
    an unrelated update in the same group is untouched."""
    import numpy as np

    from antidote_tpu.api.node import AntidoteNode
    from antidote_tpu.config import AntidoteConfig
    from antidote_tpu.txn.manager import AbortError

    cfg = AntidoteConfig(n_shards=4, max_dcs=2, keys_per_table=64,
                         batch_buckets=(16, 64))
    node = AntidoteNode(cfg)
    txm = node.txm
    # stage two txns on the same key with the same snapshot, plus one
    # disjoint — drive the group commit directly
    t1 = txm.start_transaction()
    t2 = txm.start_transaction()
    t3 = txm.start_transaction()
    # t1/t2 are read-bearing (rmw) so they keep certification — blind
    # increments would take the ISSUE 6 bypass and all commit
    txm.read_objects([("k", "counter_pn", "b")], t1)
    txm.read_objects([("k", "counter_pn", "b")], t2)
    txm.update_objects([("k", "counter_pn", "b", ("increment", 1))], t1)
    txm.update_objects([("k", "counter_pn", "b", ("increment", 5))], t2)
    txm.update_objects([("x", "counter_pn", "b", ("increment", 9))], t3)
    outs = txm.commit_transactions_group([t1, t2, t3])
    assert isinstance(outs[0], np.ndarray)
    assert isinstance(outs[1], AbortError)
    assert isinstance(outs[2], np.ndarray)
    vals, _ = node.read_objects(
        [("k", "counter_pn", "b"), ("x", "counter_pn", "b")]
    )
    assert vals == [1, 9]
