"""ISSUE 31: the deployment `counter_pn_10k` at a small size — a wire
server behind the native (default) front end, with a WAL and `sync_log`,
filled with the benchmark's own fill from a seed and driven by the
benchmark's own generator clients (`benchmarks/loadgen.py` `Client`, the
mix file `update_read_uniform`: 50% one-`increment` static updates, 50%
one-object static reads, both kinds on every connection) over a key set
so small that reads and increments of one key overlap all the time.

Every answer is judged by the comparison that decides the cell's
`correct` (`benchmarks/check.py` `compare`) against the benchmark's plain
reference (`benchmarks/reference/model.py`): no read may miss an increment
that was acknowledged before the read was sent — from the C++ mirror or
from anywhere else.

Its time limit is its own: the window is SECONDS long, every join and
socket wait is bounded, and the test fails rather than waits past them.
"""

from __future__ import annotations

import json
import os
import time

import pytest

from antidote_tpu.api.node import AntidoteNode
from antidote_tpu.config import AntidoteConfig
from antidote_tpu.proto.client import AntidoteClient
from antidote_tpu.proto.server import ProtocolServer
from benchmarks import check, data, loadgen
from benchmarks.reference.model import Model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: same shapes as test_native_frontend: the compile cache is warm
CFG = AntidoteConfig(
    n_shards=2, max_dcs=2, ops_per_key=8, snap_versions=2, set_slots=8,
    rga_slots=16, keys_per_table=64, batch_buckets=(8, 64), sync_log=True)
FILL = {"type": "counter_pn", "bucket": "bench", "key_prefix": "c",
        "fill_keys": 32, "batch_keys": 32, "connections": 1}
SEED = 4294967311          # the driver's seeds pass 2**31
CONNECTIONS, SECONDS, RAMP = 32, 3.0, 0.5
JOIN_S = 60.0


def _mix():
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "update_read_uniform.json")) as f:
        return json.load(f)


def test_counter_wire_answers_equal_the_reference(tmp_path):
    node = AntidoteNode(CFG, log_dir=str(tmp_path))
    srv = ProtocolServer(node, port=0, native_frontend=True,
                         epoch_tick_ms=25, max_in_flight_per_client=256)
    if srv.native is None:
        srv.close()
        pytest.skip("native frontend unavailable (no g++/epoll)")
    conn = AntidoteClient("127.0.0.1", srv.port, timeout=JOIN_S)
    clients = []
    try:
        model = Model()
        for txn in data.fill_batch(FILL, SEED, 0, FILL["fill_keys"]):
            conn.update_objects(txn)
            model.apply(txn)
        mix = _mix()
        spec = {"host": "127.0.0.1", "port": srv.port, "client": "wire",
                "seed": SEED, "mix": mix, "fill": FILL, "timeout": JOIN_S,
                "atoms": [tuple(a) for a in loadgen.atoms(mix)]}
        draw = loadgen.KeyDraw(mix["keys"], FILL["fill_keys"],
                               data.KeyOrder(SEED, FILL["fill_keys"]))
        now = time.monotonic()
        times = [now + 0.1, now + 0.1 + RAMP, now + 0.1 + RAMP + SECONDS]
        clients = [loadgen.Client(spec, cid, draw, times)
                   for cid in range(CONNECTIONS)]
        native0 = srv.native.stats()
        for c in clients:
            c.start()
        for c in clients:
            c.join(JOIN_S)
        assert not any(c.is_alive() for c in clients), "a client hung"
        assert not [c.error for c in clients if c.error]
        native1 = srv.native.stats()
        log = {"updates": [u + (c.cid,) for c in clients for u in c.updates],
               "reads": [r for c in clients for r in c.reads],
               "never": sum(c.never for c in clients)}
        keys = list(range(FILL["fill_keys"]))
        vals, _ = conn.read_objects([data.obj(FILL, i) for i in keys])
        checks, counts = check.compare(FILL, SEED, [log],
                                       list(zip(keys, vals)))
    finally:
        for c in clients:
            c.conn.close()
        conn.close()
        srv.close()
        node.store.log.close()
    assert check.verdict(checks), (checks, counts["first_wrong"])
    assert checks["window_wrong"]["value"] == 0
    assert checks["readback_wrong"]["value"] == 0
    # the reference holds the same end state: fill + acknowledged increments
    for u in log["updates"]:
        if u[4] is not None:
            model.apply([(data.key_name(FILL, u[0]), FILL["type"],
                          FILL["bucket"], (u[1], u[2]))])
    assert vals == [model.value(data.obj(FILL, i)) for i in keys]
    # it was this deployment's traffic: both kinds on the native plane, the
    # mirror answering some reads and being told of every commit group
    assert counts["window_answers_compared"] > 100
    assert sum(1 for u in log["updates"] if u[5]) > 100
    assert native1["native_hits"] > native0["native_hits"]
    assert native1["invalidate_keys"] > native0["invalidate_keys"]
