"""Fold-strategy parity (ISSUE 15): every materializer fold strategy —
serial scan, associative delta fold, chunked long fold, mesh-sharded
sequence fold, and the Pallas set_aw kernel — must produce byte-identical
states to the serial `fold.fold_key` / `fold.fold_batch` oracle, on the
strategy's declared domain:

* counter/flags deltas are exact from ARBITRARY bases;
* set deltas are exact from the BOTTOM base (``assoc_bottom_only``), and
  set_aw additionally only for all-adds logs (``assoc_add_only``);
* chunked/sharded set delta MERGES are exact when each chunk touches at
  most ``set_slots`` distinct handles (the store's slot-promotion
  invariant), and committed ops carry a positive own-lane commit dot;
* the Pallas set_aw kernel has no such restrictions (it replays the op
  ring in order, like the oracle) — removes and arbitrary bases included.

Also covers the live dispatch: TypedTable's serving-path strategy pick,
KVStore's over-ring replay ladder, and the fold metrics both feed.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from antidote_tpu.config import AntidoteConfig
from antidote_tpu.crdt import get_type
from antidote_tpu.materializer import fold as fold_mod
from antidote_tpu.materializer import longlog
from antidote_tpu.materializer import pallas_kernels as pk


def _mk_cfg(**kw):
    base = dict(
        n_shards=2, max_dcs=3, ops_per_key=8, snap_versions=2,
        set_slots=8, mv_slots=4, rga_slots=16, keys_per_table=16,
        batch_buckets=(16, 64),
    )
    base.update(kw)
    return AntidoteConfig(**base)


def _bottom(ty, cfg):
    return {
        f: jnp.zeros(s, dt) for f, (s, dt) in ty.state_spec(cfg).items()
    }


def _rand_set_ops(rng, l, d, n_handles, add_only):
    """One key's set op log: committed ops always carry a positive dot on
    their origin lane (the delta-merge exactness precondition)."""
    handles = rng.integers(1, n_handles + 1, size=(l,)).astype(np.int64)
    handles *= 0x1_0000_0003  # exercise both i32 planes of the i64 split
    is_rm = (np.zeros((l,), np.int32) if add_only
             else rng.integers(0, 2, size=(l,)).astype(np.int32))
    obs = rng.integers(0, 5, size=(l, d)).astype(np.int32)
    ops_a = handles[..., None]
    ops_b = np.concatenate([is_rm[..., None], obs], axis=-1).astype(np.int32)
    ops_vc = rng.integers(0, 8, size=(l, d)).astype(np.int32)
    ops_origin = rng.integers(0, d, size=(l,)).astype(np.int32)
    ops_vc[np.arange(l), ops_origin] = rng.integers(1, 9, size=(l,))
    base_vc = np.zeros((d,), np.int32)
    read_vc = rng.integers(0, 8, size=(d,)).astype(np.int32)
    return ops_a, ops_b, ops_vc, ops_origin, base_vc, read_vc


def _assert_states_equal(ref, got, msg):
    for f in ref:
        np.testing.assert_array_equal(
            np.asarray(ref[f]), np.asarray(got[f]), err_msg=f"{msg}:{f}")


# ---------------------------------------------------------------------------
# Pallas set_aw kernel vs fold_batch oracle
# ---------------------------------------------------------------------------

def test_pallas_set_aw_fold_matches_oracle():
    """The kernel entry from the host and from inside a jit trace (as the
    fused serving reads call it), random op rings with removes and
    ARBITRARY non-bottom bases, n_ops edges 0 and full ring."""
    cfg = _mk_cfg(n_shards=1)
    ty = get_type("set_aw")
    b, k, e, d = 16, cfg.ops_per_key, cfg.set_slots, cfg.max_dcs
    rng = np.random.default_rng(7)
    for trial in range(2):
        handles = rng.integers(1, 6, size=(b, k)).astype(np.int64)
        handles *= 0x1_0000_0003
        is_rm = rng.integers(0, 2, size=(b, k)).astype(np.int32)
        obs = rng.integers(0, 5, size=(b, k, d)).astype(np.int32)
        ops_a = handles[..., None]
        ops_b = np.concatenate([is_rm[..., None], obs], -1).astype(np.int32)
        ops_vc = rng.integers(0, 8, size=(b, k, d)).astype(np.int32)
        ops_origin = rng.integers(0, d, size=(b, k)).astype(np.int32)
        n_ops = rng.integers(0, k + 1, size=(b,)).astype(np.int32)
        n_ops[0], n_ops[1] = 0, k
        base_vc = rng.integers(0, 4, size=(b, d)).astype(np.int32)
        read_vc = np.maximum(
            base_vc, rng.integers(0, 8, size=(b, d))).astype(np.int32)
        state = {
            "elems": jnp.asarray(
                rng.integers(0, 4, size=(b, e)).astype(np.int64)
                * 0x1_0000_0003),
            "addvc": jnp.asarray(
                rng.integers(0, 4, size=(b, e, d)).astype(np.int32)),
            "rmvc": jnp.asarray(
                rng.integers(0, 4, size=(b, e, d)).astype(np.int32)),
            "ovf": jnp.asarray(rng.integers(0, 3, size=(b,)).astype(np.int32)),
        }
        ref_state, ref_applied = fold_mod.fold_batch(
            ty, cfg, state, jnp.asarray(ops_a), jnp.asarray(ops_b),
            jnp.asarray(ops_vc), jnp.asarray(ops_origin),
            jnp.asarray(n_ops), jnp.asarray(base_vc), jnp.asarray(read_vc))
        got_state, got_applied = pk.set_aw_fold(
            state, ops_a, ops_b, ops_vc, ops_origin, n_ops, base_vc,
            read_vc, block=8)
        _assert_states_equal(ref_state, got_state, f"trial{trial}")
        np.testing.assert_array_equal(
            np.asarray(ref_applied), np.asarray(got_applied))
        got2, app2 = jax.jit(
            functools.partial(pk.set_aw_fold, block=8)
        )(
            state, jnp.asarray(ops_a), jnp.asarray(ops_b),
            jnp.asarray(ops_vc), jnp.asarray(ops_origin),
            jnp.asarray(n_ops), jnp.asarray(base_vc),
            jnp.asarray(read_vc))
        _assert_states_equal(ref_state, got2, f"trial{trial} in-trace")
        np.testing.assert_array_equal(
            np.asarray(ref_applied), np.asarray(app2))


# ---------------------------------------------------------------------------
# set delta folds (assoc_fold / delta_merge) vs fold_key oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tyname,add_only,n_handles", [
    ("set_aw", True, 6),    # within capacity
    ("set_aw", True, 20),   # single-window capacity overflow (still exact)
    ("set_go", True, 6),
    ("set_go", False, 6),   # set_go deltas are exact with removes too
    ("set_go", True, 20),
])
def test_set_assoc_fold_matches_serial(tyname, add_only, n_handles):
    cfg = _mk_cfg(n_shards=1)
    ty = get_type(tyname)
    assert ty.supports_assoc and ty.assoc_bottom_only
    d = cfg.max_dcs
    rng = np.random.default_rng(11 + n_handles)
    for l, n_ops in ((64, 57), (32, 0), (32, 32)):
        ops_a, ops_b, ops_vc, ops_origin, base_vc, read_vc = _rand_set_ops(
            rng, l, d, n_handles, add_only)
        s0 = _bottom(ty, cfg)
        ref_s, ref_n = fold_mod.fold_key(
            ty, cfg, s0, jnp.asarray(ops_a), jnp.asarray(ops_b),
            jnp.asarray(ops_vc), jnp.asarray(ops_origin), jnp.int32(n_ops),
            jnp.asarray(base_vc), jnp.asarray(read_vc))
        got_s, got_n = longlog.assoc_fold(
            ty, cfg, s0, jnp.asarray(ops_a), jnp.asarray(ops_b),
            jnp.asarray(ops_vc), jnp.asarray(ops_origin), jnp.int32(n_ops),
            jnp.asarray(base_vc), jnp.asarray(read_vc))
        _assert_states_equal(ref_s, got_s, f"{tyname} l={l}")
        assert int(got_n) == int(ref_n)
        if n_handles > cfg.set_slots:
            continue  # merge exactness needs per-chunk distinct <= slots
        mask = longlog.include_mask(
            jnp.asarray(ops_vc), jnp.int32(n_ops),
            jnp.asarray(base_vc), jnp.asarray(read_vc))
        h = l // 2
        d1 = ty.delta_of_ops(
            cfg, jnp.asarray(ops_a[:h]), jnp.asarray(ops_b[:h]),
            jnp.asarray(ops_vc[:h]), jnp.asarray(ops_origin[:h]), mask[:h])
        d2 = ty.delta_of_ops(
            cfg, jnp.asarray(ops_a[h:]), jnp.asarray(ops_b[h:]),
            jnp.asarray(ops_vc[h:]), jnp.asarray(ops_origin[h:]), mask[h:])
        merged = ty.delta_apply(s0, ty.delta_merge(d1, d2))
        _assert_states_equal(ref_s, merged, f"{tyname} merged l={l}")


# ---------------------------------------------------------------------------
# mesh-sharded sequence fold vs single-device oracle
# ---------------------------------------------------------------------------

def test_sharded_set_aw_fold_matches_single_device():
    from antidote_tpu.parallel import make_mesh

    cfg = _mk_cfg(n_shards=1)
    ty = get_type("set_aw")
    mesh = make_mesh(8)
    d = cfg.max_dcs
    rng = np.random.default_rng(5)
    l = 64  # multiple of 8 devices; 6 handles <= set_slots per chunk
    ops_a, ops_b, ops_vc, ops_origin, base_vc, read_vc = _rand_set_ops(
        rng, l, d, 6, add_only=True)
    n_ops = 57
    s0 = _bottom(ty, cfg)
    ref_s, ref_n = fold_mod.fold_key(
        ty, cfg, s0, jnp.asarray(ops_a), jnp.asarray(ops_b),
        jnp.asarray(ops_vc), jnp.asarray(ops_origin), jnp.int32(n_ops),
        jnp.asarray(base_vc), jnp.asarray(read_vc))
    fn = longlog.sharded_assoc_fold_fn(ty, cfg, mesh)
    got_s, got_n = fn(s0, ops_a, ops_b, ops_vc, ops_origin, n_ops,
                      jnp.asarray(base_vc), jnp.asarray(read_vc))
    _assert_states_equal(ref_s, got_s, "sharded set_aw")
    assert int(got_n) == int(ref_n)


def test_mesh_fold_giant_key_pads_and_matches():
    """fold_giant_key pads a non-power-of-two log up to a device multiple
    (pad slots land beyond n_ops / inside base, so the mask drops them)
    and must still equal the serial fold; works for counters too."""
    from antidote_tpu.parallel import MeshServingPlane

    cfg = _mk_cfg(n_shards=8)
    plane = MeshServingPlane(cfg, 8)
    d = cfg.max_dcs
    rng = np.random.default_rng(9)
    cases = []
    ty_set = get_type("set_aw")
    a, b, v, o, bvc, rvc = _rand_set_ops(rng, 37, d, 6, add_only=True)
    cases.append((ty_set, a, b, v, o, 33, bvc, rvc))
    ty_cnt = get_type("counter_pn")
    l = 50
    ca = rng.integers(-5, 6, size=(l, 1)).astype(np.int64)
    cb = np.zeros((l, 1), np.int32)
    cv = rng.integers(0, 10, size=(l, d)).astype(np.int32)
    co = rng.integers(0, d, size=(l,)).astype(np.int32)
    cases.append((ty_cnt, ca, cb, cv, co, 47,
                  np.asarray([1, 0, 1], np.int32),
                  np.asarray([9, 9, 9], np.int32)))
    for ty, a, b, v, o, n_ops, bvc, rvc in cases:
        s0 = _bottom(ty, cfg)
        ref_s, ref_n = fold_mod.fold_key(
            ty, cfg, s0, jnp.asarray(a), jnp.asarray(b), jnp.asarray(v),
            jnp.asarray(o), jnp.int32(n_ops), jnp.asarray(bvc),
            jnp.asarray(rvc))
        got_s, got_n = plane.fold_giant_key(
            ty, cfg, s0, a, b, v, o, np.int32(n_ops), bvc, rvc)
        _assert_states_equal(ref_s, got_s, f"giant {ty.name}")
        assert int(got_n) == int(ref_n)
    assert plane.giant_folds == len(cases)


# ---------------------------------------------------------------------------
# live serving dispatch: strategy pick + byte parity + tallies
# ---------------------------------------------------------------------------

def _populate_set_table(table, n_keys, d):
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
                np.asarray([0], np.int32))
    mid = clock
    for r in range(0, n_keys, 2):
        elem, add_t = first[r]
        clock += 1
        vc = np.zeros(d, np.int32)
        vc[0] = clock
        b = np.zeros((1, 1 + d), np.int32)
        b[0, 0], b[0, 1] = 1, add_t
        table.append(
            np.asarray([r % table.n_shards]), np.asarray([r]),
            np.asarray([[elem]], np.int64), b, vc[None, :],
            np.asarray([0], np.int32))
    return mid, clock


def test_table_set_aw_dispatch_strategies_agree(monkeypatch):
    """The serving read of the SAME populated set_aw table must be
    byte-identical with the Pallas kernel on and off, and each run must
    tally the strategy it actually dispatched.  The serving picker is
    platform-gated (interpret-mode Pallas on CPU is a regression, not an
    upgrade), so the test sets the parity-escape env flag to drive the
    interpret kernel in-path anyway."""
    from antidote_tpu.store import TypedTable

    monkeypatch.setenv("ANTIDOTE_PALLAS_INTERPRET", "1")
    d = 3
    outs = {}
    for use_pallas in (False, True):
        cfg = _mk_cfg(use_pallas=use_pallas)
        ty = get_type("set_aw")
        table = TypedTable(ty, cfg, n_rows=16, n_shards=2)
        n_keys = 8
        for s in range(2):
            table.used_rows[s] = n_keys
        mid, final = _populate_set_table(table, n_keys, d)
        want = "pallas_set_aw" if use_pallas else "serial"
        assert table._fold_strategy() == want
        rows = np.arange(n_keys, dtype=np.int64)
        shards = rows % 2
        vcs = np.zeros((n_keys, d), np.int32)
        vcs[:, 0] = mid  # historical: forces the ring fold, not the head
        out, fresh, complete = table.read_resolved(shards, rows, vcs)
        assert complete.all()
        assert table.fold_dispatches.get(want, 0) >= 1
        outs[use_pallas] = {f: np.asarray(x) for f, x in out.items()}
    for f in outs[False]:
        np.testing.assert_array_equal(
            outs[False][f], outs[True][f], err_msg=f)


def test_table_assoc_serving_strategy_matches_serial(monkeypatch):
    """flag_ew serves through the 'assoc' strategy (supports_assoc, not
    bottom-only); forcing the same table back to 'serial' must not change
    a single byte of the resolved batch."""
    from antidote_tpu.store import TypedTable

    cfg = _mk_cfg()
    ty = get_type("flag_ew")
    d = cfg.max_dcs
    rng = np.random.default_rng(3)
    table = TypedTable(ty, cfg, n_rows=16, n_shards=2)
    n_keys = 8
    for s in range(2):
        table.used_rows[s] = n_keys
    bw = table.ops_b.shape[-1]
    clock = 0
    for r in range(n_keys):
        for _ in range(4):
            clock += 1
            vc = np.zeros(d, np.int32)
            vc[0] = clock
            b = np.zeros((1, bw), np.int32)
            b[0, 0] = int(rng.integers(0, 2))  # enable/disable
            b[0, 1] = max(0, clock - 1)
            table.append(
                np.asarray([r % 2]), np.asarray([r]),
                np.zeros((1, 1), np.int64), b, vc[None, :],
                np.asarray([0], np.int32))
    assert table._fold_strategy() == "assoc"
    rows = np.arange(n_keys, dtype=np.int64)
    shards = rows % 2
    vcs = np.zeros((n_keys, d), np.int32)
    vcs[:, 0] = clock // 2
    out_a, fresh_a, comp_a = table.read_resolved(shards, rows, vcs)
    monkeypatch.setattr(
        type(table), "_fold_strategy", lambda self: "serial")
    out_s, fresh_s, comp_s = table.read_resolved(shards, rows, vcs)
    for f, x in out_a.items():
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(out_s[f]), err_msg=f)
    np.testing.assert_array_equal(np.asarray(fresh_a), np.asarray(fresh_s))
    np.testing.assert_array_equal(np.asarray(comp_a), np.asarray(comp_s))
    assert table.fold_dispatches.get("assoc", 0) >= 1


# ---------------------------------------------------------------------------
# replay ladder: strategies differ with fold_chunk, values must not
# ---------------------------------------------------------------------------

def _drive_replay_node(tmp_path, cfg, fold_chunk):
    from antidote_tpu.api.node import AntidoteNode

    rcfg = dataclasses.replace(cfg, fold_chunk=fold_chunk)
    node = AntidoteNode(rcfg, log_dir=str(tmp_path / f"logs{fold_chunk}"))
    vcs = []
    for i in range(25):
        upd = [("c", "counter_pn", "b", ("increment", 1))]
        if i < 6:
            upd.append(("sl", "set_aw", "b", ("add", f"e{i}")))
        elif i == 6:
            upd.append(("sl", "set_aw", "b", ("remove", "e0")))
        elif i == 7:
            upd.append(("sl", "set_aw", "b", ("remove", "e1")))
        elif i == 8:
            upd.append(("sl", "set_aw", "b", ("add", "e0")))
        else:
            upd.append(("sl", "set_aw", "b", ("add", f"e{2 + (i % 4)}")))
        vcs.append(upd and node.update_objects(upd))
    cut = vcs[12]
    txn = node.start_transaction()
    txn.snapshot_vc = np.asarray(cut, np.int32)
    vals = node.read_objects(
        [("c", "counter_pn", "b"), ("sl", "set_aw", "b")], txn)
    return node, vals


def test_replay_ladder_strategies_agree(tmp_path, cfg):
    """The same 25-op logs replayed with fold_chunk=8 (routing the
    order-sensitive set to 'long' and the counter to 'assoc') and with a
    huge chunk (everything 'serial') must read identical values, and each
    run's dispatch tally + fold metrics must show the expected ladder."""
    expected_c = 13                      # 13 increments at the cut
    expected_sl = ["e0", "e2", "e3", "e4", "e5"]  # e1 removed, e0 re-added

    node8, vals8 = _drive_replay_node(tmp_path, cfg, 8)
    assert vals8[0] == expected_c
    assert sorted(vals8[1]) == expected_sl
    disp = node8.store.replay_fold_dispatches
    assert disp.get("assoc", 0) >= 1    # counter log is assoc-safe
    assert disp.get("long", 0) >= 1     # set log has removes, 13 > 8 ops
    assert node8.metrics.fold_dispatch.value(strategy="long") >= 1
    assert node8.metrics.fold_seconds.count >= 2
    st = node8.store.materializer_status()
    assert st["fold_chunk"] == 8 and st["replay_folds"] == disp

    node_big, vals_big = _drive_replay_node(tmp_path, cfg, 100_000)
    assert vals_big[0] == expected_c
    assert sorted(vals_big[1]) == expected_sl
    disp_big = node_big.store.replay_fold_dispatches
    assert disp_big.get("serial", 0) >= 1  # set log now under the chunk
    assert disp_big.get("long", 0) == 0


def test_replay_mesh_assoc_over_ring(tmp_path):
    """With a mesh attached and an over-chunk assoc-safe log, the replay
    ladder dispatches the mesh-sharded giant-key fold."""
    from antidote_tpu.api.node import AntidoteNode
    from antidote_tpu.parallel import MeshServingPlane

    cfg = _mk_cfg(n_shards=8, fold_chunk=8)
    node = AntidoteNode(cfg, log_dir=str(tmp_path / "logs_mesh"))
    MeshServingPlane(cfg, 8).attach(node.store)
    vcs = [node.update_objects([("c", "counter_pn", "b", ("increment", 1))])
           for _ in range(25)]
    txn = node.start_transaction()
    txn.snapshot_vc = np.asarray(vcs[12], np.int32)
    vals = node.read_objects([("c", "counter_pn", "b")], txn)
    assert vals[0] == 13
    assert node.store.replay_fold_dispatches.get("mesh_assoc", 0) >= 1
    assert node.store.mesh.giant_folds >= 1
    assert node.store.materializer_status()["giant_folds"] >= 1


# ---------------------------------------------------------------------------
# the log replay's serial fold: one staged operand a piece
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def replay_store():
    from antidote_tpu.store.kv import KVStore

    return KVStore(_mk_cfg())


def _replay_log(ty, cfg, l, rng):
    d = cfg.max_dcs
    if ty.name == "set_aw":
        return _rand_set_ops(rng, l, d, n_handles=6, add_only=False)
    # int64 lanes past 2^31 and negative: the lo/hi halves round trip
    ops_a = rng.integers(-2**40, 2**40, size=(l, ty.eff_a_width(cfg)))
    ops_a[::3] = -(2**31) - rng.integers(1, 9, size=ops_a[::3].shape)
    ops_vc = rng.integers(0, 8, size=(l, d)).astype(np.int32)
    return (ops_a.astype(np.int64),
            np.zeros((l, ty.eff_b_width(cfg)), np.int32), ops_vc,
            rng.integers(0, d, size=(l,)).astype(np.int32),
            np.asarray([2, 0, 1], np.int32), np.asarray([6, 7, 5], np.int32))


@pytest.mark.parametrize("l", [1, 64, 65, 256, 300])
@pytest.mark.parametrize("tyname", ["counter_pn", "set_aw"])
def test_replay_fold_staged_equals_fold_key(replay_store, tyname, l):
    """The replay's serial fold — ops, clocks and a host base state packed
    into one int32 operand a piece of 64 or 256, a longer log carrying its
    state on the device from piece to piece — folds exactly what
    ``fold_key`` folds over the whole log; a base state already on the
    device stays its own operand and folds the same."""
    from antidote_tpu.obs import trace

    ty = get_type(tyname)
    cfg = replay_store.cfg
    rng = np.random.default_rng(l)
    ops_a, ops_b, ops_vc, ops_origin, base_vc, read_vc = _replay_log(
        ty, cfg, l, rng)
    base = {f: np.asarray(x) for f, x in jax.jit(functools.partial(
        fold_mod.fold_key, ty, cfg))(
        _bottom(ty, cfg), ops_a[:l // 2], ops_b[:l // 2], ops_vc[:l // 2],
        ops_origin[:l // 2], np.int32(l // 2), np.zeros_like(base_vc),
        base_vc)[0].items()}
    if tyname == "counter_pn":
        base["cnt"] = np.int64(-(2**35) - 3)
    ref, _ = jax.jit(functools.partial(fold_mod.fold_key, ty, cfg))(
        base, ops_a, ops_b, ops_vc, ops_origin, np.int32(l), base_vc,
        read_vc)
    for state0 in (base, jax.device_put(base)):
        r0 = trace.program_status().get("replay_fold_serial")
        got, strategy = replay_store._fold_over_ring(
            ty, cfg, state0, ops_a, ops_b, ops_vc, ops_origin, l, base_vc,
            read_vc, bottom=False)
        r1 = trace.program_status()["replay_fold_serial"]
        assert strategy == "serial"
        _assert_states_equal(ref, got, f"{tyname} l={l}")
        # one launch a piece, one host operand a launch (a tier-building
        # thread of an earlier test's node may launch the program too)
        launches = r1["launches"] - (r0["launches"] if r0 else 0)
        assert launches >= -(-l // 256)
        assert r1["host_operands"] - (r0["host_operands"] if r0 else 0) \
            == launches
