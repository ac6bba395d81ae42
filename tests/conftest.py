"""Test harness: force an 8-device virtual CPU mesh.

Multi-chip hardware is unavailable in CI; the sharding layer is validated
on virtual CPU devices (the driver separately dry-runs multi-chip via
__graft_entry__.dryrun_multichip).  The platform and the device count
are fixed here, before any test initialises a backend.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

from antidote_tpu.config import (  # noqa: E402
    XLA_CACHE_DIR,
    enable_compilation_cache,
)

# own cache directory, next to the servers': the 8-virtual-device test
# config compiles with different machine-feature flags than 1-device
# server processes, and cross-loading the other config's AOT entries
# spams feature-mismatch warnings on every load
enable_compilation_cache(XLA_CACHE_DIR + "_t8")

import contextlib  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture
def cfg():
    from antidote_tpu.config import AntidoteConfig

    return AntidoteConfig(
        n_shards=4, max_dcs=3, ops_per_key=8, snap_versions=2,
        set_slots=8, mv_slots=4, rga_slots=16, keys_per_table=64,
        batch_buckets=(16, 64),
    )


class RecordingMirror:
    """Stand-in for ``KVStore.native_mirror``: records the fills the store
    pushes (and whether the serving epoch was pinned then)."""

    def __init__(self, store, log=None):
        self.store = store
        self.log = [] if log is None else log
        self.many = []      # (entries, epoch_id, pins) per fill_many call
        self.single = []    # (key, bucket, type_name, value, epoch_id)

    def fill_many(self, entries, epoch_id):
        ep = self.store.serving_epoch
        self.many.append((list(entries), epoch_id,
                          ep.pins if ep is not None else 0))
        self.log.append("fill")

    def fill(self, *args):
        self.single.append(args)

    def invalidate(self, key, bucket):
        pass

    def invalidate_many(self, keys):
        pass

    def advance(self, epoch_id, vc_list, clockless_ok):
        pass

    def set_clockless_ok(self, on):
        pass

    def native_hits(self):
        return 0

    def reset(self):
        pass


def check_writeback_fill(store, objs, mirror: bool, cap: int = 5):
    """One cold epoch read of ``objs`` through launch + finish (what the
    dispatcher and the writeback stage do with a batch): one mirror fill
    per launch, the snapshot cache and its evict counter as one fill per
    key in launch order leaves them, alike with no mirror at all."""
    from collections import OrderedDict

    rec = RecordingMirror(store) if mirror else None
    store.native_mirror = rec
    store.snapshot_cache_cap = cap
    evict0 = store.metrics.snapshot_cache.value(event="evict")
    ep = store.pin_serving_epoch()
    assert ep is not None
    try:
        pending, fallback = store.epoch_read_launch(objs, ep)
        assert not fallback, fallback
        assert pending.gathered == set(range(len(objs))), "read not cold"
        # the model: one fill, one eviction loop per key
        model = OrderedDict(
            (dk, ent[0]) for dk, ent in store.snapshot_cache.items())
        evicted = 0
        for _tn, items, *_rest in pending.launches:
            for i, _shard, _row in items:
                model[(objs[i][0], objs[i][2])] = ep.id
                while len(model) > cap:
                    model.popitem(last=False)
                    evicted += 1
        vals = store.epoch_read_finish(pending)
    finally:
        store.unpin_serving_epoch(ep)
    assert [(dk, ent[0]) for dk, ent in store.snapshot_cache.items()] \
        == list(model.items())
    assert evicted == len(objs) - cap > 0
    assert store.metrics.snapshot_cache.value(event="evict") - evict0 \
        == evicted
    for dk, (_eid, loc, value) in store.snapshot_cache.items():
        i = next(j for j, o in enumerate(objs) if (o[0], o[2]) == dk)
        assert value == vals[i]
        assert store.directory[dk] == loc
    if rec is not None:
        assert not rec.single
        assert len(rec.many) == len(pending.launches)
        filled = {}
        for (tn, items, *_rest), (entries, eid, pins) in zip(
                pending.launches, rec.many):
            assert eid == ep.id and pins >= 1
            assert [(k, b) for k, b, _t, _v in entries] == [
                (objs[i][0], objs[i][2]) for i, _s, _r in items]
            for k, b, t, v in entries:
                assert tn.startswith(t)
                filled[(k, b, t)] = v
        assert filled == {(o[0], o[2], o[1]): v
                          for o, v in zip(objs, vals)}
    return vals


# ---------------------------------------------------------------------------
# ISSUE 34: transactions opened along a write stream, for the reads that
# carry one read VC a row
# ---------------------------------------------------------------------------
#: small widths: a ring overflows every fourth op of a key (GC), sets
#: outgrow 4 / 16 element slots (tiers 1 and 2)
HISTORY_CFG = dict(n_shards=4, max_dcs=2, ops_per_key=4, snap_versions=2,
                   set_slots=4, keys_per_table=64, batch_buckets=(8, 64))
HISTORY_KEYS = ("head", "ring", "deep", "wide", "never")


def history_scenario(node, type_name):
    """Four transactions opened at different points of a write stream over
    ``HISTORY_KEYS`` of ``type_name`` (``set_aw`` | ``counter_pn``), the
    last one at the head.  For the older ones ``head`` is answered by the
    head state, ``ring`` by a ring fold, ``deep`` (written past its ring
    again and again since) only by the log replay, ``wide`` (a set past 4
    and 16 elements) from a slot tier; ``never`` is never written.
    Returns (objects, transactions, the value of every object at every
    transaction's snapshot)."""
    state, n = {}, [0]
    is_set = type_name == "set_aw"

    def write(key, times=1):
        for _ in range(times):
            n[0] += 1
            if is_set:
                op = ("add", f"{key}:{n[0]}")
                state.setdefault(key, []).append(op[1])
            else:
                op = ("increment", n[0])
                state[key] = state.get(key, 0) + n[0]
            node.update_objects([(key, type_name, "b", op)])

    objs = [(k, type_name, "b") for k in HISTORY_KEYS]
    txns, expect = [], []

    def snap():
        txns.append(node.start_transaction())
        expect.append([sorted(state.get(k, [])) if is_set
                       else state.get(k, 0) for k in HISTORY_KEYS])

    write("head", 2), write("ring"), write("deep", 2), write("wide", 6)
    snap()
    write("ring"), write("deep", 6), write("wide", 5)
    snap()
    write("ring"), write("deep", 6), write("wide", 6)
    snap()
    write("ring")
    snap()
    return objs, txns, expect


def history_values(type_name, vals):
    """Read values in the form ``history_scenario`` expects them."""
    return [sorted(v) if type_name == "set_aw" else v for v in vals]


@contextlib.contextmanager
def locked_worker_held(srv, parked: int, timeout: float = 60.0):
    """Hold a ``ProtocolServer``'s locked worker between two of its
    rounds until ``parked`` works wait at its queue: when the block ends
    they are ONE round's work (the block itself waits for them)."""
    gate = threading.Event()
    drain = srv._drain_batch

    def gated(q, *a, **kw):
        if q is srv._locked_q:
            gate.wait(timeout)
        return drain(q, *a, **kw)

    srv._drain_batch = gated
    # the worker sits in an ungated wait: one request takes it through
    srv.static_update([("gate", "counter_pn", "gate", ("increment", 1))],
                      None)
    try:
        yield
        end = time.monotonic() + timeout
        while srv._locked_q.qsize() < parked:
            assert time.monotonic() < end, "the works never parked"
            time.sleep(0.005)
    finally:
        del srv._drain_batch
        gate.set()
