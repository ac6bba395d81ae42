"""The fused serving read (TypedTable.read_resolved / KVStore.read_resolved)
and the in-path Pallas kernel dispatch.

Covers the read path of SURVEY §3.3 as ONE device launch: freshness check,
snapshot-version select, versioned ring fold, device value resolution — and
checks the Pallas variants (cfg.use_pallas) against the plain-XLA fold,
which remains the semantics oracle (the r1 VERDICT asked for production
call sites + dispatch tests).
"""

import dataclasses

import numpy as np
import pytest

from antidote_tpu.config import AntidoteConfig
from antidote_tpu.crdt import get_type
from antidote_tpu.store import TypedTable
from antidote_tpu.store.kv import KVStore


def _mk_cfg(**kw):
    base = dict(
        n_shards=2, max_dcs=3, ops_per_key=8, snap_versions=2,
        set_slots=8, mv_slots=4, rga_slots=16, keys_per_table=16,
        batch_buckets=(16, 64),
    )
    base.update(kw)
    return AntidoteConfig(**base)


def _populate_set(table, n_keys, d):
    """3 adds per key on lane 0, then remove the first add on even keys."""
    clock = 0
    first = {}
    for r in range(n_keys):
        for j in range(3):
            clock += 1
            vc = np.zeros(d, np.int32)
            vc[0] = clock
            elem = 100 * (r + 1) + j
            first.setdefault(r, (elem, clock))
            table.append(
                np.asarray([r % table.n_shards]), np.asarray([r]),
                np.asarray([[elem]], np.int64),
                np.zeros((1, 1 + d), np.int32), vc[None, :],
                np.asarray([0], np.int32),
            )
    mid = clock  # historical read point: before any removes
    for r in range(0, n_keys, 2):
        elem, add_t = first[r]
        clock += 1
        vc = np.zeros(d, np.int32)
        vc[0] = clock
        b = np.zeros((1, 1 + d), np.int32)
        b[0, 0] = 1
        b[0, 1] = add_t
        table.append(
            np.asarray([r % table.n_shards]), np.asarray([r]),
            np.asarray([[elem]], np.int64), b, vc[None, :],
            np.asarray([0], np.int32),
        )
    return mid, clock


@pytest.mark.parametrize("use_pallas", [False, True])
def test_set_aw_read_resolved_fresh_and_historical(use_pallas):
    cfg = _mk_cfg(use_pallas=use_pallas)
    ty = get_type("set_aw")
    d = cfg.max_dcs
    table = TypedTable(ty, cfg, n_rows=16, n_shards=2)
    n_keys = 10
    for s in range(2):
        table.used_rows[s] = n_keys
    mid, final = _populate_set(table, n_keys, d)

    rows = np.arange(n_keys, dtype=np.int64)
    shards = rows % 2
    vc_final = np.zeros((n_keys, d), np.int32)
    vc_final[:, 0] = final
    out, fresh, complete = table.read_resolved(shards, rows, vc_final)
    assert fresh.all() and complete.all()
    for r in range(n_keys):
        want = {100 * (r + 1) + j for j in range(3)}
        if r % 2 == 0:
            want.discard(100 * (r + 1))  # first add removed
        got = {int(x) for x in out["top"][r] if x != 0}
        assert got == want, r
        assert int(out["count"][r]) == len(want)

    # historical read: before the removes — the fold path (not the head)
    vc_mid = np.zeros((n_keys, d), np.int32)
    vc_mid[:, 0] = mid
    out2, fresh2, complete2 = table.read_resolved(shards, rows, vc_mid)
    assert complete2.all()
    assert not fresh2[::2].any()  # removed keys' heads are newer than mid
    for r in range(n_keys):
        want = {100 * (r + 1) + j for j in range(3)}  # removes not visible
        got = {int(x) for x in out2["top"][r] if x != 0}
        assert got == want, r


@pytest.mark.parametrize("use_pallas", [False, True])
def test_counter_read_resolved_matches_oracle(use_pallas):
    cfg = _mk_cfg(use_pallas=use_pallas)
    ty = get_type("counter_pn")
    d = cfg.max_dcs
    table = TypedTable(ty, cfg, n_rows=8, n_shards=1)
    table.used_rows[0] = 4
    rng = np.random.default_rng(0)
    clock = 0
    totals = np.zeros(4, np.int64)
    mid_totals = None
    mid = None
    for i in range(20):
        r = int(rng.integers(0, 4))
        delta = int(rng.integers(-50, 50))
        clock += 1
        vc = np.zeros(d, np.int32)
        vc[0] = clock
        table.append(
            np.asarray([0]), np.asarray([r]),
            np.asarray([[delta]], np.int64),
            np.zeros((1, 1), np.int32), vc[None, :],
            np.asarray([0], np.int32),
        )
        totals[r] += delta
        if i == 9:
            mid, mid_totals = clock, totals.copy()
    rows = np.arange(4, dtype=np.int64)
    shards = np.zeros(4, np.int64)
    for at, want in ((clock, totals), (mid, mid_totals)):
        vcs = np.zeros((4, d), np.int32)
        vcs[:, 0] = at
        out, _, complete = table.read_resolved(shards, rows, vcs)
        assert complete.all()
        assert (out["value"] == want).all(), (at, out["value"], want)
    if use_pallas:
        assert table._pallas_counter_ok()


def test_counter_pallas_falls_back_on_huge_deltas():
    cfg = _mk_cfg(use_pallas=True)
    ty = get_type("counter_pn")
    table = TypedTable(ty, cfg, n_rows=8, n_shards=1)
    table.used_rows[0] = 1
    vc = np.zeros((1, cfg.max_dcs), np.int32)
    vc[0, 0] = 1
    big = 2**40
    table.append(
        np.asarray([0]), np.asarray([0]), np.asarray([[big]], np.int64),
        np.zeros((1, 1), np.int32), vc, np.asarray([0], np.int32),
    )
    assert not table._pallas_counter_ok()  # i32 kernel would overflow
    out, _, _ = table.read_resolved(
        np.asarray([0]), np.asarray([0]), vc
    )
    assert int(out["value"][0]) == big


def test_kvstore_read_resolved_matches_read_values():
    cfg = _mk_cfg()
    store = KVStore(cfg)
    from antidote_tpu.store.kv import Effect

    clock = 0
    d = cfg.max_dcs
    objs = [(f"k{i}", "set_aw", "b") for i in range(6)]
    for i, (k, tname, bucket) in enumerate(objs):
        for j in range(2):
            ty = get_type(tname)
            eff = ty.downstream(("add", f"v{i}{j}"), None, store.blobs, cfg)[0]
            clock += 1
            vc = np.zeros(d, np.int32)
            vc[0] = clock
            store.apply_effects(
                [Effect(k, tname, bucket, eff[0], eff[1], eff[2])], [vc], [0]
            )
    at = store.dc_max_vc()
    values = store.read_values(objs, at)
    resolved = store.read_resolved(objs, at)
    for i, (k, tname, bucket) in enumerate(objs):
        got = sorted(
            store.blobs.resolve(int(h)) for h in resolved[i]["top"] if h != 0
        )
        assert got == sorted(values[i])
        assert int(resolved[i]["count"]) == len(values[i])
    # unseen key → bottom value
    bottom = store.read_resolved([("nope", "set_aw", "b")], at)[0]
    assert int(bottom["count"]) == 0


def test_stable_vc_is_the_min_of_the_applied_clocks():
    from antidote_tpu.store.kv import stable_min_of

    store = KVStore(_mk_cfg())
    store.applied_vc[:] = np.asarray([[3, 1, 9], [2, 5, 4]], np.int32)
    assert (store.stable_vc() == np.asarray([2, 1, 4])).all()
    # a large matrix (multi-node aggregation: members × shards rows)
    big = np.random.default_rng(1).integers(0, 1000, size=(4096, 3)).astype(np.int32)
    assert (stable_min_of(big) == big.min(axis=0)).all()


@pytest.mark.parametrize("clocks", [
    np.random.default_rng(0).integers(0, 1000, size=(777, 5)).astype(np.int32),
    np.asarray([[7, 3, 9]], np.int32),
], ids=["random_777x5", "single_row"])
def test_stable_min_of_matches_a_plain_loop(clocks):
    from antidote_tpu.store.kv import stable_min_of

    want = [min(int(row[j]) for row in clocks)
            for j in range(clocks.shape[1])]
    got = stable_min_of(clocks)
    assert got.dtype == np.int32
    assert got.tolist() == want


def test_handoff_preserves_serving_gates():
    """import_shard / reshard must carry max_abs_delta / max_commit_vc so
    the Pallas counter dispatch and the provably-fresh fast path stay
    sound after a shard moves (r2 review finding)."""
    from antidote_tpu.store import handoff
    from antidote_tpu.store.kv import Effect

    cfg = _mk_cfg(use_pallas=True)
    src = KVStore(cfg)
    ty = get_type("counter_pn")
    eff = ty.downstream(("increment", 2**40), None, src.blobs, cfg)[0]
    vc = np.zeros(cfg.max_dcs, np.int32)
    vc[0] = 7
    src.apply_effects([Effect("k", "counter_pn", "b", eff[0], eff[1])], [vc], [0])
    t_src = src.tables["counter_pn"]
    assert t_src.max_abs_delta >= 2**40
    shard = src.locate("k", "counter_pn", "b")[1]

    dst = KVStore(cfg)
    handoff.import_shard(dst, handoff.export_shard(src, shard, include_log=False))
    t_dst = dst.tables["counter_pn"]
    assert t_dst.max_abs_delta >= 2**40
    assert not t_dst._pallas_counter_ok()
    assert (t_dst.max_commit_vc == t_src.max_commit_vc).all()

    re = handoff.reshard(src, dataclasses.replace(cfg, n_shards=4))
    t_re = re.tables["counter_pn"]
    assert t_re.max_abs_delta >= 2**40
    assert (t_re.max_commit_vc == t_src.max_commit_vc).all()


def test_client_reads_use_fused_serving_path(monkeypatch):
    """r2 VERDICT item 2: AntidoteNode.read_objects (no-writeset txns) must
    serve through KVStore.read_resolved, with value() reconstruction from
    the resolved top-k, and re-fetch full state only on count overflow."""
    from antidote_tpu.api.node import AntidoteNode

    node = AntidoteNode(_mk_cfg())
    node.update_objects([
        ("c", "counter_pn", "b", ("increment", 7)),
        ("r", "register_lww", "b", ("assign", "hello")),
        ("f", "flag_ew", "b", ("enable", {})),
        ("s", "set_aw", "b", ("add_all", ["x", "y"])),
        # 6 elements > resolve_top=4 -> truncated view -> full-state refetch
        ("big", "set_aw", "b", ("add_all", ["e1", "e2", "e3", "e4", "e5", "e6"])),
        ("q", "rga", "b", ("add_right", (0, "head"))),  # no resolve_spec
    ])

    calls = {"resolved": 0, "states": 0}
    orig_resolved = KVStore.read_resolved
    orig_states = KVStore.read_states

    def spy_resolved(self, *a, **kw):
        calls["resolved"] += 1
        return orig_resolved(self, *a, **kw)

    def spy_states(self, *a, **kw):
        calls["states"] += 1
        return orig_states(self, *a, **kw)

    monkeypatch.setattr(KVStore, "read_resolved", spy_resolved)
    monkeypatch.setattr(KVStore, "read_states", spy_states)

    vals, _ = node.read_objects([
        ("c", "counter_pn", "b"),
        ("r", "register_lww", "b"),
        ("f", "flag_ew", "b"),
        ("s", "set_aw", "b"),
        ("big", "set_aw", "b"),
        ("q", "rga", "b"),
        ("never", "counter_pn", "b"),
    ])
    assert vals[0] == 7
    assert vals[1] == "hello"
    assert vals[2] is True
    assert vals[3] == ["x", "y"]
    assert sorted(vals[4]) == ["e1", "e2", "e3", "e4", "e5", "e6"]
    assert vals[5] == ["head"]
    assert vals[6] == 0
    # one fused launch batch served everything; full-state read happened
    # exactly once, for the truncated 6-element set
    assert calls["resolved"] == 1
    assert calls["states"] == 1

    # a txn WITH pending writes must keep the overlay (full-state) path
    calls["resolved"] = calls["states"] = 0
    txid = node.start_transaction()
    node.update_objects([("c", "counter_pn", "b", ("increment", 1))], txid)
    vals2 = node.read_objects([("c", "counter_pn", "b")], txid)
    node.commit_transaction(txid)
    assert vals2[0] == 8
    assert calls["resolved"] == 0 and calls["states"] >= 1


def test_resolved_view_ships_ovf_and_hatch_prevents_drops():
    """The resolved view carries the ovf counter (so TypedTable-direct
    deployments keep the slot-exhaustion warning on the serving path —
    see test_typed_table.py::test_set_slot_overflow_warns), while the
    KVStore-level escape hatch makes the node path drop-free: 3 adds into
    a 2-slot set promote the key instead of truncating."""
    import warnings

    from antidote_tpu.api.node import AntidoteNode

    cfg = _mk_cfg(set_slots=2)
    ty = get_type("set_aw")
    assert "ovf" in ty.resolve_spec(cfg)

    node = AntidoteNode(cfg)
    node.update_objects([
        ("k", "set_aw", "b", ("add_all", ["a", "b", "c"])),  # 3 > 2 slots
        ("k", "set_aw", "b", ("remove", "a")),
    ])
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        vals, _ = node.read_objects([("k", "set_aw", "b")])
    assert sorted(vals[0]) == ["b", "c"]  # nothing dropped
    assert not any("dropped" in str(w.message) for w in rec)
    assert node.store.promotions >= 1


def test_read_resolved_flat_matches_routed():
    """The flat single-gather serving path (read_resolved_flat) and the
    routed [P, M'] path must agree exactly — fresh, historical, and
    absent keys, with and without the Pallas counter dispatch."""
    d = 3
    for tyname, use_pallas in (("set_aw", False), ("counter_pn", False),
                               ("counter_pn", True)):
        cfg = _mk_cfg(use_pallas=use_pallas)
        ty = get_type(tyname)
        table = TypedTable(ty, cfg)
        if tyname == "set_aw":
            _, mid = _populate_set(table, 10, d)
        else:
            clock = 0
            aw = table.ops_a.shape[-1]
            bw = table.ops_b.shape[-1]
            for r in range(10):
                for j in range(3):
                    clock += 1
                    vc = np.zeros(d, np.int32)
                    vc[0] = clock
                    ea = np.zeros((1, aw), np.int64)
                    ea[0, 0] = j + 1
                    table.append(
                        np.asarray([r % table.n_shards]), np.asarray([r]),
                        ea, np.zeros((1, bw), np.int32), vc[None, :],
                        np.asarray([0], np.int32),
                    )
            mid = clock // 2
        keys = np.asarray([0, 1, 2, 5, 9, 9, 3, 0], np.int64)
        ss, rr = keys % table.n_shards, keys
        for t in (mid, 10_000):
            vcs = np.zeros((len(keys), d), np.int32)
            vcs[:, 0] = t
            flat_res, flat_fresh, flat_comp = table.read_resolved_flat(
                ss, rr, vcs)
            routed_out, routed_fresh, routed_comp = table.read_resolved(
                ss, rr, vcs)
            for f, x in routed_out.items():
                np.testing.assert_array_equal(
                    np.asarray(flat_res[f]), x, err_msg=(tyname, f, t))
            np.testing.assert_array_equal(
                np.asarray(flat_fresh), routed_fresh, err_msg=(tyname, t))
            np.testing.assert_array_equal(
                np.asarray(flat_comp), routed_comp, err_msg=(tyname, t))


@pytest.mark.parametrize("at", ["latest", "historical"])
def test_read_resolved_pads_the_flat_read_to_a_batch_bucket(at):
    """Whatever the batch size, the single-device serving read hands the
    flat programs a batch of a bucket's size (one compiled program a
    bucket, not one a size) and gives the batch's own answers back."""
    d = 3
    cfg = _mk_cfg()
    table = TypedTable(get_type("set_aw"), cfg)
    _, mid = _populate_set(table, 10, d)
    t = 10_000 if at == "latest" else mid
    flat = table.read_resolved_flat
    seen = []

    def recording(shards, rows, read_vcs, n_real=None):
        seen.append(len(rows))
        return flat(shards, rows, read_vcs, n_real=n_real)

    table.read_resolved_flat = recording
    for m in (1, 3, 16, 17, 49):
        keys = np.arange(m, dtype=np.int64) % 10
        ss, rr = keys % table.n_shards, keys
        vcs = np.zeros((m, d), np.int32)
        vcs[:, 0] = t
        out, fresh, complete = table.read_resolved(ss, rr, vcs)
        want, want_fresh, want_complete = flat(ss, rr, vcs)
        assert fresh.shape == complete.shape == (m,)
        for f, x in out.items():
            np.testing.assert_array_equal(x, np.asarray(want[f]), err_msg=f)
        np.testing.assert_array_equal(fresh, np.asarray(want_fresh))
        np.testing.assert_array_equal(complete, np.asarray(want_complete))
    assert seen == [16, 16, 16, 64, 64]
    assert set(seen) <= set(cfg.batch_buckets)


# ---------------------------------------------------------------------------
# one read VC a row (ISSUE 34): the reads of several transactions in one
# batch of KVStore.read_resolved / read_states
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("placement", ["one_device", "mesh2"])
@pytest.mark.parametrize("type_name", ["set_aw", "counter_pn"])
@pytest.mark.parametrize("read", ["read_resolved", "read_states"])
def test_kvstore_reads_with_a_vc_a_row_equal_one_vc_calls(
        tmp_path, read, type_name, placement):
    """A batch in which every row carries its own read VC — the same key
    at several snapshots among them — answers each row as a call with
    that one VC does: head rows, ring folds, rows only the log replay
    answers, slot-tier rows, a never-written key; one device and a
    2-device mesh."""
    from antidote_tpu.api.node import AntidoteNode
    from antidote_tpu.parallel import MeshServingPlane
    from conftest import HISTORY_CFG, history_scenario

    cfg = AntidoteConfig(**HISTORY_CFG)
    plane = MeshServingPlane(cfg, 2) if placement == "mesh2" else None
    node = AntidoteNode(cfg, log_dir=str(tmp_path),
                        sharding=plane.sharding if plane else None)
    if plane is not None:
        plane.metrics = node.metrics
        plane.attach(node.store)
    store = node.store
    objs, txns, _ = history_scenario(node, type_name)
    batch = [(o, t.snapshot_vc) for t in txns for o in objs]
    fn = getattr(store, read)
    resolved = read == "read_resolved"
    whole = {}  # read_resolved: rows it read whole (wide, replayed)
    got = fn([o for o, _ in batch], np.stack([vc for _, vc in batch]),
             **({"full_out": whole} if resolved else {}))
    assert store.fold_status()["replays"] > 0
    for i, (o, vc) in enumerate(batch):
        whole_one = {}
        want, = fn([o], vc, **({"full_out": whole_one} if resolved else {}))
        assert (i in whole) == bool(whole_one), (i, o)
        assert got[i].keys() == want.keys(), (i, o)
        for f in want:
            np.testing.assert_array_equal(got[i][f], want[f],
                                          err_msg=str((i, o, f)))
    assert not resolved or whole
    with pytest.raises(ValueError):
        fn([o for o, _ in batch], np.stack([vc for _, vc in batch[:3]]))
    store.log.close()
