"""ISSUE 10 mesh serving plane: the serving-epoch store sharded over a
device Mesh with collective stable time.

Runs on the 8 virtual CPU devices the conftest forces
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``).  The
load-bearing properties:

  * mesh-plane epoch reads are BYTE-identical to the single-chip
    serving-epoch plane at equal epoch ids (same workload, same wire
    encoding);
  * epoch publication is per-shard incremental: a hot shard's write
    burst advances only its own ``antidote_mesh_publish_total{shard}``
    label, by its dirty-row count — never table size;
  * the pmin stable-time collective equals the host-computed stable VC
    entry-wise, for any applied-clock matrix;
  * the degenerate 1-device mesh behaves like the full one;
  * the pin/graveyard donation protocol holds for sharded buffers under
    concurrent commits (no gather ever reads a donated buffer);
  * the Pallas fold inside the sharded step (shard-local extents)
    matches the generic scan fold.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import jax
import msgpack
import numpy as np
import pytest

from antidote_tpu.api.node import AntidoteNode
from antidote_tpu.config import AntidoteConfig
from antidote_tpu.crdt import get_type
from antidote_tpu.obs import NodeMetrics
from antidote_tpu.parallel import (
    MeshServingPlane,
    make_mesh,
    shard_axis_sharding,
    sharded_step_fn,
)
from antidote_tpu.proto.codec import encode_value
from antidote_tpu.store import TypedTable
from antidote_tpu.store.kv import Effect, KVStore, shard_digest, split_tier

MESH_CFG = AntidoteConfig(n_shards=8, max_dcs=2, keys_per_table=64,
                          batch_buckets=(16, 64))


def _mk_node(mesh_devices=None):
    plane = None
    if mesh_devices:
        plane = MeshServingPlane(MESH_CFG, mesh_devices)
    node = AntidoteNode(
        MESH_CFG, sharding=plane.sharding if plane is not None else None)
    if plane is not None:
        plane.metrics = node.metrics
        plane.attach(node.store)
    return node, plane


#: deterministic mixed-type workload: both replicas apply the identical
#: commit sequence, so clocks, layouts and epoch ids line up exactly
def _apply_workload(node):
    for i in range(24):
        node.update_objects([
            (i, "counter_pn", "b", ("increment", i + 1)),
            (f"s{i % 5}", "set_aw", "b", ("add", f"e{i}")),
            (f"r{i % 3}", "register_lww", "b", ("assign", f"v{i}")),
        ])


_WORKLOAD_OBJS = (
    [(i, "counter_pn", "b") for i in range(24)]
    + [(f"s{j}", "set_aw", "b") for j in range(5)]
    + [(f"r{j}", "register_lww", "b") for j in range(3)]
)


def _epoch_read(store, objs):
    ep = store.pin_serving_epoch()
    assert ep is not None
    try:
        pending, fallback = store.epoch_read_launch(objs, ep)
        assert not fallback, fallback
        vals = store.epoch_read_finish(pending)
    finally:
        store.unpin_serving_epoch(ep)
    return ep.id, [int(x) for x in ep.vc], vals


def _wire_bytes(vals, vc):
    """The reply encoding the writeback stage would serialize — the
    byte-identity oracle."""
    return msgpack.packb(
        {"values": [encode_value(v) for v in vals], "commit_clock": vc},
        use_bin_type=True, default=repr)


# ---------------------------------------------------------------------------
# parity: mesh plane ≡ single-chip plane, byte for byte
# ---------------------------------------------------------------------------
def test_mesh_reads_byte_identical_to_single_chip():
    assert len(jax.devices()) == 8, "conftest must force 8 devices"
    chip, _ = _mk_node()
    mesh, _plane = _mk_node(mesh_devices=8)
    _apply_workload(chip)
    _apply_workload(mesh)
    chip.txm.publish_serving_epoch()
    mesh.txm.publish_serving_epoch()
    cid, cvc, cvals = _epoch_read(chip.store, _WORKLOAD_OBJS)
    mid, mvc, mvals = _epoch_read(mesh.store, _WORKLOAD_OBJS)
    assert cid == mid, "epoch ids must line up for the comparison"
    assert _wire_bytes(cvals, cvc) == _wire_bytes(mvals, mvc)
    # second round: incremental publishes on both sides stay identical
    _apply_workload(chip)
    _apply_workload(mesh)
    chip.txm.publish_serving_epoch()
    mesh.txm.publish_serving_epoch()
    cid, cvc, cvals = _epoch_read(chip.store, _WORKLOAD_OBJS)
    mid, mvc, mvals = _epoch_read(mesh.store, _WORKLOAD_OBJS)
    assert cid == mid
    assert _wire_bytes(cvals, cvc) == _wire_bytes(mvals, mvc)


def test_mesh_parity_on_2_and_4_device_meshes():
    """Shards-per-device > 1: the routed layouts split 8 shards over
    fewer devices and must serve the same bytes."""
    chip, _ = _mk_node()
    _apply_workload(chip)
    chip.txm.publish_serving_epoch()
    _, cvc, cvals = _epoch_read(chip.store, _WORKLOAD_OBJS)
    for n_dev in (2, 4):
        node, _plane = _mk_node(mesh_devices=n_dev)
        _apply_workload(node)
        node.txm.publish_serving_epoch()
        _, mvc, mvals = _epoch_read(node.store, _WORKLOAD_OBJS)
        assert _wire_bytes(cvals, cvc) == _wire_bytes(mvals, mvc)


def test_degenerate_1_device_mesh():
    node, plane = _mk_node(mesh_devices=1)
    _apply_workload(node)
    node.txm.publish_serving_epoch()
    _, _, vals = _epoch_read(node.store, _WORKLOAD_OBJS)
    direct, _ = node.read_objects(_WORKLOAD_OBJS)
    assert _wire_bytes(vals, [0]) == _wire_bytes(direct, [0])
    assert (node.store.stable_vc()
            == node.store.applied_vc.min(axis=0)).all()
    assert plane.status()["shards_per_device"] == MESH_CFG.n_shards


@pytest.mark.parametrize("mirror", [True, False],
                         ids=["recording_mirror", "no_mirror"])
def test_routed_writeback_fills_cache_and_mirror_once_a_launch(mirror):
    """The routed (``pos``) branch of ``epoch_read_finish``: a launch's
    gathered keys reach cache and mirror in one fill, with the values
    the one-chip plane reads."""
    from conftest import check_writeback_fill

    chip, _ = _mk_node()
    node, _plane = _mk_node(mesh_devices=4)
    for n in (chip, node):
        _apply_workload(n)
        n.txm.publish_serving_epoch()
    _, _, cvals = _epoch_read(chip.store, _WORKLOAD_OBJS)
    ep = node.store.pin_serving_epoch()
    try:
        pending, _fb = node.store.epoch_read_launch(_WORKLOAD_OBJS[:1], ep)
        assert pending.launches[0][4] is not None, "launch was not routed"
    finally:
        node.store.unpin_serving_epoch(ep)
    vals = check_writeback_fill(node.store, _WORKLOAD_OBJS, mirror)
    assert _wire_bytes(vals, [0]) == _wire_bytes(cvals, [0])


def test_mesh_rejects_indivisible_device_count():
    with pytest.raises(ValueError):
        MeshServingPlane(MESH_CFG, 3)  # 8 % 3 != 0


# ---------------------------------------------------------------------------
# per-shard incremental publish
# ---------------------------------------------------------------------------
def test_per_shard_publish_touches_only_dirty_shard():
    """A hot shard's write burst republishes ITS device slice only:
    the per-shard counter advances for exactly that shard, by the
    dirty-row count — not table size (the acceptance criterion)."""
    plane = MeshServingPlane(MESH_CFG, 8)
    store = KVStore(MESH_CFG, sharding=plane.sharding)
    store.metrics = NodeMetrics()
    plane.attach(store)
    ty = get_type("counter_pn")
    aw, bw = ty.eff_a_width(MESH_CFG), ty.eff_b_width(MESH_CFG)
    counter = [0]

    def write(keys):
        effs = [Effect(k, "counter_pn", "b", np.full(aw, 1, np.int64),
                       np.zeros(bw, np.int32)) for k in keys]
        vcs = []
        for _ in keys:
            counter[0] += 1
            vcs.append(np.asarray([counter[0], 0], np.int32))
        store.apply_effects(effs, vcs, [0] * len(keys))

    # two copy publishes fill both double-buffer slots, a third drains
    # the cross-window scatter set, so the probed publish's scatter is
    # exactly the hot burst
    write(range(32))
    store.publish_serving_epoch(store.dc_max_vc())
    write(range(32))
    store.publish_serving_epoch(store.dc_max_vc())
    write([3, 11, 19])
    store.publish_serving_epoch(store.dc_max_vc())
    write([3, 11, 27])  # shard 3 only (integer keys map key % n_shards)
    before = dict(store.metrics.mesh_publish.snapshot())
    assert store.publish_serving_epoch(store.dc_max_vc()) == "published"
    delta = {k: v - before.get(k, 0)
             for k, v in store.metrics.mesh_publish.snapshot().items()}
    hot = {k: v for k, v in delta.items() if v}
    # only shard 3's slice was republished: 4 dirty rows across the two
    # burst windows — vs 64 rows/shard table size
    assert hot == {("3",): 4.0}, hot
    # and the published epoch still serves every key exactly
    objs = [(i, "counter_pn", "b") for i in range(32)]
    _, _, vals = _epoch_read(store, objs)
    assert vals == store.read_values(objs, store.dc_max_vc())


# ---------------------------------------------------------------------------
# stable time: pmin collective ≡ host min
# ---------------------------------------------------------------------------
def test_pmin_stable_time_equals_host_min():
    plane = MeshServingPlane(MESH_CFG, 8)
    store = KVStore(MESH_CFG, sharding=plane.sharding)
    plane.attach(store)
    rng = np.random.default_rng(7)
    for _ in range(5):
        store.applied_vc[:] = rng.integers(
            0, 1000, size=store.applied_vc.shape).astype(np.int32)
        want = store.applied_vc.min(axis=0)
        got = store.stable_vc()
        assert (got == want).all(), (got, want)
    n0 = plane.stable_collectives
    # unchanged clocks hit the cache — no relaunch per txn start
    for _ in range(10):
        store.stable_vc()
    assert plane.stable_collectives == n0


# ---------------------------------------------------------------------------
# pin/graveyard donation under concurrent commits (sharded buffers)
# ---------------------------------------------------------------------------
def test_pin_graveyard_holds_for_sharded_buffers_under_commits():
    """Concurrent commit+publish storms donate sharded spare buffers
    while lock-free gathers hold pins: no gather may ever observe a
    donated ('deleted') buffer, and served counter values must be
    monotone per key."""
    node, _plane = _mk_node(mesh_devices=8)
    store = node.store
    node.update_objects([(k, "counter_pn", "b", ("increment", 1))
                         for k in range(16)])
    node.txm.publish_serving_epoch()
    stop = time.monotonic() + 3.0
    errors: list = []

    def writer():
        try:
            while time.monotonic() < stop:
                node.update_objects(
                    [(k, "counter_pn", "b", ("increment", 1))
                     for k in range(16)])
                node.txm.publish_serving_epoch()
        except BaseException as e:  # surfaced by the main thread
            errors.append(e)

    t = threading.Thread(target=writer)
    t.start()
    objs = [(k, "counter_pn", "b") for k in range(16)]
    last = [0] * 16
    reads = 0
    try:
        while time.monotonic() < stop:
            ep = store.pin_serving_epoch()
            if ep is None:
                continue
            try:
                pending, fallback = store.epoch_read_launch(objs, ep)
                vals = store.epoch_read_finish(pending)
            finally:
                store.unpin_serving_epoch(ep)
            fb = set(fallback)
            for i, v in enumerate(vals):
                if i in fb:
                    continue
                assert v >= last[i], (i, v, last[i])
                last[i] = v
            reads += 1
    finally:
        t.join()
    assert not errors, errors
    assert reads > 5, "the reader never overlapped the write storm"


# ---------------------------------------------------------------------------
# Pallas fold inside the sharded step (shard-local extents)
# ---------------------------------------------------------------------------
def test_sharded_step_pallas_fold_matches_generic():
    cfg = AntidoteConfig(n_shards=8, max_dcs=2, ops_per_key=4,
                         snap_versions=2, keys_per_table=16,
                         batch_buckets=(8,))
    mesh = make_mesh(8)
    sharding = shard_axis_sharding(mesh)
    ty = get_type("counter_pn")

    def run(use_pallas):
        c = dataclasses.replace(cfg, use_pallas=use_pallas)
        table = TypedTable(ty, c, sharding=sharding)
        step = sharded_step_fn(ty, c, mesh)
        p, ma, mr, d = c.n_shards, 8, 8, c.max_dcs
        app_rows = np.zeros((p, ma), np.int64)
        app_rows[:, 2:] = table.n_rows  # padding
        app_slots = np.zeros((p, ma), np.int64)
        app_slots[:, 1] = 1
        app_a = np.zeros((p, ma, ty.eff_a_width(c)), np.int64)
        app_a[:, 0, 0] = np.arange(p) + 1
        app_a[:, 1, 0] = 10
        app_b = np.zeros((p, ma, ty.eff_b_width(c)), np.int32)
        app_vc = np.zeros((p, ma, d), np.int32)
        app_vc[:, 0, 0] = 1
        app_vc[:, 1, 0] = 2
        app_origin = np.zeros((p, ma), np.int32)
        read_rows = np.zeros((p, mr), np.int64)
        read_n_ops = np.full((p, mr), 2, np.int32)
        read_vcs = np.ones((p, mr, d), np.int32)  # sees op 1, not op 2
        applied_vc = np.zeros((p, d), np.int32)
        return step(
            table.snap, table.snap_vc, table.snap_seq,
            table.ops_a, table.ops_b, table.ops_vc, table.ops_origin,
            app_rows, app_slots, app_a, app_b, app_vc, app_origin,
            read_rows, read_n_ops, read_vcs, applied_vc,
        )

    o_gen, o_pal = run(False), run(True)
    assert (np.asarray(o_gen[4]["cnt"]) == np.asarray(o_pal[4]["cnt"])).all()
    assert (np.asarray(o_gen[5]) == np.asarray(o_pal[5])).all()  # applied
    assert (np.asarray(o_gen[8]) == np.asarray(o_pal[8])).all()  # stable
    # the clock-filtered fold saw exactly the first op per shard
    assert (np.asarray(o_pal[4]["cnt"])[:, 0] == np.arange(8) + 1).all()


# ---------------------------------------------------------------------------
# per-shard directory index (satellite): digests unchanged, index exact
# ---------------------------------------------------------------------------
def test_shard_digest_unchanged_by_index():
    node, _ = _mk_node()
    _apply_workload(node)
    store = node.store
    with node.txm.commit_lock:
        indexed = [shard_digest(store, s)
                   for s in range(MESH_CFG.n_shards)]
    # the pre-index oracle: filter the whole directory per shard
    import hashlib

    def legacy(shard):
        objs = []
        for (key, bucket), (tname, s, _row) in store.directory.items():
            if s == shard:
                objs.append((key, split_tier(tname)[0], bucket))
        objs.sort(key=lambda o: msgpack.packb(
            [o[0], o[2], o[1]], use_bin_type=True, default=repr))
        h = hashlib.sha256()
        h.update(np.ascontiguousarray(store.applied_vc[shard],
                                      dtype=np.int64).tobytes())
        if objs:
            vals = store.read_values(objs, store.applied_vc[shard])
            from antidote_tpu.store.kv import _canon

            for (key, tname, bucket), v in zip(objs, vals):
                h.update(msgpack.packb(
                    [_canon(key), bucket, tname, _canon(v)],
                    use_bin_type=True, default=repr))
        return h.hexdigest()

    with node.txm.commit_lock:
        assert indexed == [legacy(s) for s in range(MESH_CFG.n_shards)]


def test_shard_directory_index_tracks_mutations():
    from antidote_tpu.store import handoff

    node, _ = _mk_node()
    _apply_workload(node)
    store = node.store

    def recomputed():
        idx: dict = {}
        for dk, ent in dict.items(store.directory):
            idx.setdefault(ent[1], set()).add(dk)
        return idx

    # the lazy index matches a from-scratch grouping...
    got = {s: set(store.directory.shard_keys(s))
           for s in range(MESH_CFG.n_shards)}
    assert {s: v for s, v in got.items() if v} == recomputed()
    # ...stays exact across incremental mutation (drop_shard pops every
    # key through the index path)...
    victims = [s for s in range(MESH_CFG.n_shards)
               if store.directory.shard_keys(s)]
    victim = victims[0]
    handoff.drop_shard(store, victim)
    assert store.directory.shard_keys(victim) == set()
    got = {s: set(store.directory.shard_keys(s))
           for s in range(MESH_CFG.n_shards)}
    assert {s: v for s, v in got.items() if v} == recomputed()
    # ...and across bulk update (index rebuilds lazily)
    store.directory.update({("zz", "b"): ("counter_pn", victim, 0)})
    assert ("zz", "b") in store.directory.shard_keys(victim)
