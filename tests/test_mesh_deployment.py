"""ISSUE 26: the deployment `set_aw_2m_mesh4` at a small size — a wire
server over a store sharded on 4 of the 8 virtual CPU devices (16 shards
x 256 set_aw rows at `console serve`'s default widths), filled with the
benchmark's own fill from a seed, read through the wire with the
benchmark's Zipf draw, and held to the benchmark's plain reference
(`benchmarks/reference/model.py`, a dict model fed the same operations).

What it guards: every answer equals the reference's; the same seed on a
one-device node gives byte-identical answers; and the node status'
`pipeline.mesh` counters (what the cell's `mesh.*` metrics read) say what
the routed launches really did.
"""

from __future__ import annotations

import random
import threading

import jax
import msgpack
import pytest

from antidote_tpu.api.node import AntidoteNode
from antidote_tpu.config import AntidoteConfig
from antidote_tpu.parallel import MeshServingPlane
from antidote_tpu.proto.client import AntidoteClient
from antidote_tpu.proto.codec import encode_value
from antidote_tpu.proto.server import ProtocolServer
from antidote_tpu.store import kv
from benchmarks import data, loadgen
from benchmarks.reference.model import Model

#: the configuration's shapes, 256 rows a shard instead of 131,072
CFG = AntidoteConfig(n_shards=16, max_dcs=8, keys_per_table=256)
DEVICES = 4
FILL = {"type": "set_aw", "bucket": "bench", "key_prefix": "k",
        "fill_keys": 768, "batch_keys": 256, "connections": 1,
        "add_all_elements": 3, "remove_every": 10}
SEED = 4294967311          # the driver's seeds pass 2**31
READS, READERS = 480, 8


class Served:
    """One node behind a wire server, filled from the seed."""

    def __init__(self, mesh_devices: int | None):
        plane = MeshServingPlane(CFG, mesh_devices) if mesh_devices else None
        self.node = AntidoteNode(
            CFG, sharding=plane.sharding if plane is not None else None)
        if plane is not None:
            plane.metrics = self.node.metrics
            plane.attach(self.node.store)
        self.plane = plane
        self.srv = ProtocolServer(self.node, port=0, epoch_tick_ms=25,
                                  max_in_flight_per_client=256)
        self.conn = self.client()
        for lo in range(0, FILL["fill_keys"], FILL["batch_keys"]):
            for txn in data.fill_batch(FILL, SEED, lo,
                                       lo + FILL["batch_keys"]):
                self.conn.update_objects(txn)

    def client(self):
        return AntidoteClient("127.0.0.1", self.srv.port, timeout=120)

    def pipeline(self) -> dict:
        return self.conn.node_status()["pipeline"]

    def zipf_reads(self):
        """READS one-object reads over READERS connections at once (so
        that launches merge), each reader's keys drawn as a generator
        process draws them; answers in (reader, draw) order."""
        order = data.KeyOrder(SEED, FILL["fill_keys"])
        draw = loadgen.KeyDraw({"distribution": "zipf", "s": 1.0},
                               FILL["fill_keys"], order)
        out = [None] * READERS

        def reader(cid):
            rng = random.Random(data.mix(SEED, cid, 7))
            c, got = self.client(), []
            try:
                for _ in range(READS // READERS):
                    i = draw(rng)
                    vals, clock = c.read_objects([data.obj(FILL, i)])
                    got.append((i, vals[0], clock))
            finally:
                c.close()
            out[cid] = got

        ts = [threading.Thread(target=reader, args=(cid,))
              for cid in range(READERS)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return [r for got in out for r in got]

    def close(self):
        self.conn.close()
        self.srv.close()


@pytest.fixture(scope="module")
def model():
    m = Model()
    for lo in range(0, FILL["fill_keys"], FILL["batch_keys"]):
        for txn in data.fill_batch(FILL, SEED, lo, lo + FILL["batch_keys"]):
            m.apply(txn)
    return m


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= DEVICES, "conftest forces 8 devices"
    s = Served(DEVICES)
    yield s
    s.close()


@pytest.fixture(scope="module")
def chip():
    s = Served(None)
    yield s
    s.close()


@pytest.fixture(scope="module")
def mesh_run(mesh):
    """The Zipf reads through the mesh node, with the status before and
    after them."""
    pre = mesh.pipeline()
    answers = mesh.zipf_reads()
    return pre, answers, mesh.pipeline()


def test_tables_are_created_in_their_placement(mesh):
    """Every array of a mesh table lives a quarter on each device, from
    its creation: nothing is staged whole on device 0."""
    for t in mesh.node.store.tables.values():
        for x in jax.tree.leaves((t.snap, t.head, t.ops_a, t.head_vc)):
            assert x.sharding.is_equivalent_to(mesh.plane.sharding, x.ndim)
            assert {s.data.shape[0] for s in x.addressable_shards} == {
                CFG.n_shards // DEVICES}
        per_device = t.device_bytes()
        assert len(per_device) == DEVICES
        assert len(set(per_device.values())) == 1


def test_mesh_wire_answers_equal_the_reference(mesh_run, model):
    _pre, answers, _post = mesh_run
    assert len(answers) == READS
    for i, value, _clock in answers:
        assert sorted(value) == model.value(data.obj(FILL, i)), i
    # the fill's removes are in the answers: an add-wins set really
    # dropped what its own adder removed
    assert any(len(v) == FILL["add_all_elements"] - 1
               for _i, v, _c in answers)


def test_one_device_node_answers_byte_identical(mesh_run, chip):
    """Same seed, same operations, one device and no mesh: the reply
    bodies are the same bytes."""
    _pre, answers, _post = mesh_run
    pack = lambda rs: msgpack.packb(  # noqa: E731
        [{"values": [encode_value(v)], "commit_clock": c}
         for _i, v, c in rs], use_bin_type=True, default=repr)
    assert pack(chip.zipf_reads()) == pack(answers)
    assert "mesh" not in chip.pipeline(), \
        "a one-device node never enters the routed branch"


def test_mesh_counters_say_what_the_routed_launches_did(mesh_run):
    pre, _answers, post = mesh_run
    d = lambda *path: _at(post, path) - _at(pre, path)  # noqa: E731
    gathered = d("reads", "gather")
    assert gathered > 0, "no read took the gather path"
    assert d("mesh", "rows") == gathered
    assert d("mesh", "launches") > 0
    assert d("mesh", "route", "count") == d("mesh", "launches")
    assert d("mesh", "route", "sum_ms") > 0
    # every routed launch is a whole [16, M'] matrix, M' a batch bucket
    assert d("mesh", "slots") >= d("mesh", "rows")
    assert d("mesh", "slots") % (CFG.n_shards * CFG.batch_buckets[0]) == 0
    by_device = [d("mesh", "rows_by_device", str(k)) for k in range(DEVICES)]
    assert sum(by_device) == d("mesh", "rows")
    assert set(post["mesh"]["rows_by_device"]) == {"0", "1", "2", "3"}


def test_rows_by_device_follow_shard_ownership(mesh, mesh_run):
    """Device d owns shards 4d..4d+3: keys of device 2's shards, read in
    one request past the snapshot cache, count under device 2 alone."""
    store = mesh.node.store
    spd = CFG.n_shards // DEVICES
    seen = {i for i, _v, _c in mesh_run[1]}          # maybe cached
    mine = [i for i in range(FILL["fill_keys"]) if i not in seen
            and store.directory[(data.key_name(FILL, i), FILL["bucket"])][1]
            // spd == 2][:40]
    assert len(mine) == 40
    pre = mesh.pipeline()["mesh"]
    vals, _ = mesh.conn.read_objects([data.obj(FILL, i) for i in mine])
    post = mesh.pipeline()["mesh"]
    assert all(vals)
    delta = {k: post["rows_by_device"][k] - pre["rows_by_device"][k]
             for k in post["rows_by_device"]}
    assert delta == {"0": 0, "1": 0, "2": 40, "3": 0}
    assert post["rows"] - pre["rows"] == 40


def test_serve_route_span_once_per_routed_launch(mesh, monkeypatch):
    """`serve.route` is opened per launch (never per request), inside the
    launch, with the launch's table and row count."""
    opened = []
    inner = kv.span

    def span(name, **ids):
        opened.append((name, ids))
        return inner(name, **ids)

    monkeypatch.setattr(kv, "span", span)
    fresh = [i for i in range(FILL["fill_keys"] - 64, FILL["fill_keys"])]
    pre = mesh.pipeline()["mesh"]
    mesh.conn.read_objects([data.obj(FILL, i) for i in fresh])
    post = mesh.pipeline()["mesh"]
    routes = [ids for name, ids in opened if name == "serve.route"]
    assert len(routes) == post["launches"] - pre["launches"] >= 1
    assert sum(ids["rows"] for ids in routes) == post["rows"] - pre["rows"]


def _at(status: dict, path):
    """A counter of the status; a label nothing has counted yet reads 0."""
    for part in path:
        status = status.get(part, 0) if part == path[-1] else status[part]
    return status
