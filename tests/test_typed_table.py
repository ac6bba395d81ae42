"""TypedTable + materializer fold semantics.

These are the tensor analogues of the reference's materializer EUnit truth
tables (/root/reference/src/clocksi_materializer.erl:277-473): snapshot
filtering by VC dominance, base-snapshot exclusion, GC folds, and
incomplete-read detection.
"""

import numpy as np
import pytest

from antidote_tpu.crdt import get_type
from antidote_tpu.crdt.blob import BlobStore
from antidote_tpu.store import TypedTable


class Driver:
    """Tiny single-key commit driver: assigns commit VCs on one DC lane."""

    def __init__(self, ty_name, cfg, dc=0):
        self.cfg = cfg
        self.ty = get_type(ty_name)
        self.table = TypedTable(self.ty, cfg, n_rows=8, n_shards=1)
        self.blobs = BlobStore()
        self.clock = np.zeros(cfg.max_dcs, np.int32)

    def commit(self, row, op, dc=0, vc_override=None):
        state = None
        if self.ty.require_state_downstream(op):
            state = self.read(row, self.clock)[0]
        effs = self.ty.downstream(op, state, self.blobs, self.cfg)
        for a, b, _ in effs:
            if vc_override is not None:
                cvc = np.asarray(vc_override, np.int32)
                self.clock = np.maximum(self.clock, cvc)
            else:
                self.clock = self.clock.copy()
                self.clock[dc] += 1
                cvc = self.clock.copy()
            self.table.append(
                np.asarray([0]), np.asarray([row]),
                a[None, :], b[None, :], cvc[None, :],
                np.asarray([dc], np.int32),
            )
        return self.clock.copy()

    def read(self, row, at_vc):
        state, _, complete = self.table.read(
            np.asarray([0]), np.asarray([row]), np.asarray(at_vc, np.int32)[None, :]
        )
        one = {f: x[0] for f, x in state.items()}
        return one, bool(complete[0])

    def value(self, row, at_vc):
        state, complete = self.read(row, at_vc)
        assert complete
        return self.ty.value(state, self.blobs, self.cfg)


def test_counter_basic(cfg):
    d = Driver("counter_pn", cfg)
    d.commit(0, ("increment", 5))
    d.commit(0, ("increment", 3))
    vc2 = d.clock.copy()
    d.commit(0, ("decrement", 2))
    assert d.value(0, d.clock) == 6
    # snapshot isolation: read at the older VC misses the decrement
    assert d.value(0, vc2) == 8


def test_counter_snapshot_excludes_concurrent_dc(cfg):
    d = Driver("counter_pn", cfg)
    d.commit(0, ("increment", 10), dc=0, vc_override=[1, 0, 0])
    # a truly concurrent commit from DC1 (does not depend on DC0's)
    d.commit(0, ("increment", 100), dc=1, vc_override=[0, 1, 0])
    # read seeing only DC0's commit
    assert d.value(0, [1, 0, 0]) == 10
    # read seeing both
    assert d.value(0, [1, 1, 0]) == 110
    # read seeing only DC1
    assert d.value(0, [0, 1, 0]) == 100


def test_gc_fold_and_versions(cfg):
    d = Driver("counter_pn", cfg)
    # overflow the 8-slot ring twice over
    for i in range(20):
        d.commit(0, ("increment", 1))
    assert d.value(0, d.clock) == 20
    # ring was folded at least once
    assert d.table.n_ops[0, 0] < 20
    # older reads within retained coverage still work
    state, complete = d.read(0, d.clock)
    assert complete


def test_incomplete_read_detection(cfg):
    d = Driver("counter_pn", cfg)
    for i in range(20):
        d.commit(0, ("increment", 1))
    # a read far below the oldest retained snapshot version is incomplete
    _, complete = d.read(0, [1, 0, 0])
    if complete:
        # only acceptable if a retained version is exactly dominated
        seqs = np.asarray(d.table.snap_seq[0, 0])
        vcs = np.asarray(d.table.snap_vc[0, 0])
        ok = any(
            s > 0 and (v <= np.asarray([1, 0, 0])).all()
            for s, v in zip(seqs, vcs)
        )
        assert ok
    else:
        assert not complete


def test_two_keys_independent(cfg):
    d = Driver("counter_pn", cfg)
    d.commit(0, ("increment", 1))
    d.commit(1, ("increment", 7))
    assert d.value(0, d.clock) == 1
    assert d.value(1, d.clock) == 7


def test_register_lww(cfg):
    d = Driver("register_lww", cfg)
    d.commit(0, ("assign", "a"))
    d.commit(0, ("assign", "b"))
    assert d.value(0, d.clock) == "b"


def test_register_mv_concurrent_assigns_coexist(cfg):
    d = Driver("register_mv", cfg)
    d.commit(0, ("assign", "x"))
    # two concurrent assigns: neither observes the other.
    # simulate by generating both downstreams from the same observed state.
    state, _ = d.read(0, d.clock)
    e1 = d.ty.downstream(("assign", "l"), state, d.blobs, d.cfg)[0]
    e2 = d.ty.downstream(("assign", "r"), state, d.blobs, d.cfg)[0]
    vc1 = np.asarray([2, 0, 0], np.int32)
    vc2 = np.asarray([1, 1, 0], np.int32)
    d.table.append(np.asarray([0]), np.asarray([0]), e1[0][None], e1[1][None], vc1[None],
                   np.asarray([0], np.int32))
    d.table.append(np.asarray([0]), np.asarray([0]), e2[0][None], e2[1][None], vc2[None],
                   np.asarray([1], np.int32))
    assert d.value(0, [2, 1, 0]) == ["l", "r"]
    # sequential assign observing both collapses to one value
    d.clock = np.asarray([2, 1, 0], np.int32)
    d.commit(0, ("assign", "z"))
    assert d.value(0, d.clock) == ["z"]


def test_set_aw_add_remove(cfg):
    d = Driver("set_aw", cfg)
    d.commit(0, ("add", "x"))
    d.commit(0, ("add", "y"))
    assert d.value(0, d.clock) == ["x", "y"]
    d.commit(0, ("remove", "x"))
    assert d.value(0, d.clock) == ["y"]
    d.commit(0, ("add", "x"))
    assert d.value(0, d.clock) == ["x", "y"]


def test_set_aw_concurrent_add_wins(cfg):
    d = Driver("set_aw", cfg)
    d.commit(0, ("add", "x"))
    # concurrent: DC1 removes x (observing the add), DC2 re-adds x
    state, _ = d.read(0, d.clock)
    rm = d.ty.downstream(("remove", "x"), state, d.blobs, d.cfg)[0]
    ad = d.ty.downstream(("add", "x"), None, d.blobs, d.cfg)[0]
    vc_rm = np.asarray([1, 1, 0], np.int32)
    vc_ad = np.asarray([1, 0, 1], np.int32)
    d.table.append(np.asarray([0]), np.asarray([0]), rm[0][None], rm[1][None], vc_rm[None],
                   np.asarray([1], np.int32))
    d.table.append(np.asarray([0]), np.asarray([0]), ad[0][None], ad[1][None], vc_ad[None],
                   np.asarray([2], np.int32))
    # add wins: x present when both are visible
    assert d.value(0, [1, 1, 1]) == ["x"]
    # remove-only view: x absent
    assert d.value(0, [1, 1, 0]) == []


def test_set_aw_add_all(cfg):
    d = Driver("set_aw", cfg)
    d.commit(0, ("add_all", ["a", "b", "c"]))
    assert d.value(0, d.clock) == ["a", "b", "c"]
    d.commit(0, ("remove_all", ["a", "c"]))
    assert d.value(0, d.clock) == ["b"]


def test_set_rw_concurrent_remove_wins(cfg):
    d = Driver("set_rw", cfg)
    d.commit(0, ("add", "x"))
    state, _ = d.read(0, d.clock)
    ad = d.ty.downstream(("add", "x"), state, d.blobs, d.cfg)[0]
    rm = d.ty.downstream(("remove", "x"), state, d.blobs, d.cfg)[0]
    vc_ad = np.asarray([1, 1, 0], np.int32)
    vc_rm = np.asarray([1, 0, 1], np.int32)
    d.table.append(np.asarray([0]), np.asarray([0]), ad[0][None], ad[1][None], vc_ad[None],
                   np.asarray([1], np.int32))
    d.table.append(np.asarray([0]), np.asarray([0]), rm[0][None], rm[1][None], vc_rm[None],
                   np.asarray([2], np.int32))
    assert d.value(0, [1, 1, 1]) == []


def test_set_rw_sequential_add_after_remove(cfg):
    d = Driver("set_rw", cfg)
    d.commit(0, ("add", "x"))
    d.commit(0, ("remove", "x"))
    assert d.value(0, d.clock) == []
    d.commit(0, ("add", "x"))
    assert d.value(0, d.clock) == ["x"]


def test_set_go(cfg):
    d = Driver("set_go", cfg)
    d.commit(0, ("add", "p"))
    d.commit(0, ("add", "q"))
    d.commit(0, ("add", "p"))
    assert d.value(0, d.clock) == ["p", "q"]


def test_flag_ew(cfg):
    d = Driver("flag_ew", cfg)
    assert d.value(0, d.clock) is False
    d.commit(0, ("enable", None))
    assert d.value(0, d.clock) is True
    d.commit(0, ("disable", None))
    assert d.value(0, d.clock) is False
    # concurrent enable vs disable: enable wins
    state, _ = d.read(0, d.clock)
    en = d.ty.downstream(("enable", None), state, d.blobs, d.cfg)[0]
    di = d.ty.downstream(("disable", None), state, d.blobs, d.cfg)[0]
    vc_en = np.asarray([d.clock[0], 1, 0], np.int32)
    vc_di = np.asarray([d.clock[0], 0, 1], np.int32)
    d.table.append(np.asarray([0]), np.asarray([0]), en[0][None], en[1][None], vc_en[None],
                   np.asarray([1], np.int32))
    d.table.append(np.asarray([0]), np.asarray([0]), di[0][None], di[1][None], vc_di[None],
                   np.asarray([2], np.int32))
    v = d.value(0, np.maximum(vc_en, vc_di))
    assert v is True


def test_flag_dw(cfg):
    d = Driver("flag_dw", cfg)
    d.commit(0, ("enable", None))
    assert d.value(0, d.clock) is True
    # concurrent enable vs disable: disable wins
    state, _ = d.read(0, d.clock)
    en = d.ty.downstream(("enable", None), state, d.blobs, d.cfg)[0]
    di = d.ty.downstream(("disable", None), state, d.blobs, d.cfg)[0]
    vc_en = np.asarray([d.clock[0], 1, 0], np.int32)
    vc_di = np.asarray([d.clock[0], 0, 1], np.int32)
    d.table.append(np.asarray([0]), np.asarray([0]), en[0][None], en[1][None], vc_en[None],
                   np.asarray([1], np.int32))
    d.table.append(np.asarray([0]), np.asarray([0]), di[0][None], di[1][None], vc_di[None],
                   np.asarray([2], np.int32))
    assert d.value(0, np.maximum(vc_en, vc_di)) is False


def test_counter_fat_reset(cfg):
    d = Driver("counter_fat", cfg)
    d.commit(0, ("increment", 10))
    d.commit(0, ("increment", 5))
    assert d.value(0, d.clock) == 15
    d.commit(0, ("reset", None))
    assert d.value(0, d.clock) == 0
    d.commit(0, ("increment", 3))
    assert d.value(0, d.clock) == 3


def test_counter_fat_concurrent_increment_survives_reset(cfg):
    d = Driver("counter_fat", cfg)
    d.commit(0, ("increment", 10))
    state, _ = d.read(0, d.clock)
    # reset observes 10; a concurrent increment of 7 at DC1 is unobserved
    rs = d.ty.downstream(("reset", None), state, d.blobs, d.cfg)[0]
    inc = d.ty.downstream(("increment", 7), None, d.blobs, d.cfg)[0]
    vc_rs = np.asarray([2, 0, 0], np.int32)
    vc_inc = np.asarray([1, 1, 0], np.int32)
    d.table.append(np.asarray([0]), np.asarray([0]), rs[0][None], rs[1][None], vc_rs[None],
                   np.asarray([0], np.int32))
    d.table.append(np.asarray([0]), np.asarray([0]), inc[0][None], inc[1][None], vc_inc[None],
                   np.asarray([1], np.int32))
    assert d.value(0, [2, 1, 0]) == 7


def test_counter_b(cfg):
    d = Driver("counter_b", cfg)
    d.commit(0, ("increment", (10, 0)))
    assert d.value(0, d.clock) == 10
    d.commit(0, ("decrement", (4, 0)))
    assert d.value(0, d.clock) == 6
    d.commit(0, ("transfer", (3, 1, 0)))
    assert d.value(0, d.clock) == 6
    state, _ = d.read(0, d.clock)
    assert d.ty.local_rights(state, 0) == 3
    assert d.ty.local_rights(state, 1) == 3


def test_batched_read_many_keys(cfg):
    d = Driver("counter_pn", cfg)
    for row in range(6):
        d.commit(row, ("increment", row + 1))
    rows = np.arange(6)
    vcs = np.broadcast_to(d.clock, (6, cfg.max_dcs))
    state, applied, complete = d.table.read(np.zeros(6, np.int64), rows, vcs)
    assert complete.all()
    assert list(state["cnt"]) == [1, 2, 3, 4, 5, 6]


def test_read_between_versions_flagged_incomplete(cfg):
    # regression: ops folded into a newer snapshot version must not be
    # silently missing from a read served off an older version
    d = Driver("counter_pn", cfg)
    for i in range(20):
        d.commit(0, ("increment", 1))
    # two retained versions exist at [8,..] and [16,..]; ring holds 17-20
    seqs = np.asarray(d.table.snap_seq[0, 0])
    assert (seqs > 0).sum() >= 2
    vcs = np.asarray(d.table.snap_vc[0, 0])
    older = vcs[np.argsort(seqs)][-2]  # older retained version's VC
    probe = older.copy()
    probe[0] += 2  # between the two versions
    state, complete = d.read(0, probe)
    assert not complete  # must demand log-replay, not serve stale 'older'


def test_set_slot_overflow_warns(cfg):
    d = Driver("set_aw", cfg)
    for i in range(cfg.set_slots + 3):
        d.commit(0, ("add", f"e{i}"))
    import warnings as _w

    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        v = d.value(0, d.clock)
    assert len(v) == cfg.set_slots
    assert any("op(s) dropped" in str(r.message) for r in rec)


# ---------------------------------------------------------------------------
# serving epochs (read-while-write double buffer, r4 VERDICT item 2)
# ---------------------------------------------------------------------------
def _flat_value(table, ty, row, vc, blobs, cfg):
    resolved, fresh, complete = table.read_resolved_flat(
        np.asarray([0]), np.asarray([row]), np.asarray(vc, np.int32)[None, :]
    )
    return ({f: np.asarray(x)[0] for f, x in resolved.items()},
            bool(np.asarray(fresh)[0]), bool(np.asarray(complete)[0]))


def test_epoch_pinned_reads_survive_writes(cfg):
    d = Driver("counter_pn", cfg)
    t = d.table
    d.commit(0, ("increment", 5))
    d.commit(1, ("increment", 7))
    pin = d.clock.copy()
    t.publish_epoch()
    assert len(t.epochs) == 1
    # writes race ahead of the pin
    for _ in range(20):
        d.commit(0, ("increment", 1))
    # pinned read = epoch cap: pure frozen gather, all fresh
    res, fresh0, complete0 = _flat_value(t, d.ty, 0, pin, d.blobs, cfg)
    assert fresh0 and complete0
    assert int(res["value"]) == 5
    # a read at the live frontier still sees everything
    res, _, _ = _flat_value(t, d.ty, 0, d.clock, d.blobs, cfg)
    assert int(res["value"]) == 25
    # a read BELOW the pin takes the two-phase fold and is still exact
    below = pin.copy()
    below[0] -= 1  # excludes row 1's commit
    res, fresh1, complete1 = _flat_value(t, d.ty, 1, below, d.blobs, cfg)
    assert complete1
    assert int(res["value"]) == 0


def test_epoch_mixed_batch_two_phase(cfg):
    """A batch mixing frozen-fresh and epoch-stale rows merges exactly."""
    d = Driver("set_aw", cfg)
    t = d.table
    d.commit(0, ("add", 11))
    d.commit(1, ("add", 22))
    pin = d.clock.copy()
    rows = np.asarray([0, 1])
    vcs = np.broadcast_to(pin, (2, cfg.max_dcs)).astype(np.int32)

    def read_at(v):
        resolved, fresh, complete = t.read_resolved_flat(
            np.zeros(2, np.int64), rows, v
        )
        return ({f: np.asarray(x).copy() for f, x in resolved.items()},
                np.asarray(fresh).copy(), np.asarray(complete).copy())

    expect_pin, _, c0 = read_at(vcs)
    assert c0.all()
    t.publish_epoch()
    d.commit(0, ("add", 33))  # row 0 advances past the pin
    after_w = d.clock.copy()
    t.publish_epoch()  # second epoch at the later cap
    assert len(t.epochs) == 2
    vcs2 = np.broadcast_to(after_w, (2, cfg.max_dcs)).astype(np.int32)
    expect_w, _, _ = read_at(vcs2)
    d.commit(1, ("add", 44))
    # read at the OLD pin: served from the old epoch, exact pre-write values
    got, fresh, complete = read_at(vcs)
    assert complete.all() and fresh.all()  # old epoch cap == pin: pure gather
    for f in expect_pin:
        assert (got[f] == expect_pin[f]).all(), f
    # read at the second epoch's cap picks it (row 0 includes the 33 add)
    got, fresh, complete = read_at(vcs2)
    assert complete.all() and fresh.all()
    for f in expect_w:
        assert (got[f] == expect_w[f]).all(), f
    # reads below both pins still fold exactly (two-phase path)
    below = vcs.copy(); below[:, 0] -= 1
    _, _, complete = read_at(below)
    assert complete.all()


def test_epoch_invalidated_on_growth(cfg):
    d = Driver("counter_pn", cfg)
    t = d.table
    d.commit(0, ("increment", 3))
    t.publish_epoch()
    t._grow()
    assert t.epochs == []


def test_epoch_lru_retention(cfg):
    d = Driver("counter_pn", cfg)
    t = d.table
    d.commit(0, ("increment", 1))
    pin0 = d.clock.copy()
    t.publish_epoch()
    d.commit(0, ("increment", 1))
    t.publish_epoch()
    # keep epoch 0 hot: a pinned reader at its cap
    for _ in range(3):
        _flat_value(t, d.ty, 0, pin0, d.blobs, cfg)
    d.commit(0, ("increment", 1))
    t.publish_epoch()  # evicts the UNUSED middle epoch, not the hot pin
    caps = sorted(int(e["cap"][0]) for e in t.epochs)
    assert int(pin0[0]) in caps
    assert len(t.epochs) == 2


# ---------------------------------------------------------------------------
# append: one staged operand, one device program — against a plain fold
# ---------------------------------------------------------------------------
class PlainFold:
    """What ``TypedTable.append`` must leave behind, kept in dicts: every
    effect applied to its key's head one at a time, in commit order, and
    each key's ring since its last fold (a ring that would overflow is
    folded first; a batch holding more than a ring of one key goes in
    ring-sized pieces)."""

    def __init__(self, ty, cfg):
        self.ty, self.cfg = ty, cfg
        self.rings, self.heads, self.head_vcs = {}, {}, {}

    def head(self, key):
        if key not in self.heads:
            self.heads[key] = {
                f: np.zeros(shape, dtype)
                for f, (shape, dtype) in self.ty.state_spec(self.cfg).items()
            }
            self.head_vcs[key] = np.zeros(self.cfg.max_dcs, np.int32)
        return self.heads[key]

    def apply(self, s, r, a, b, vc, o):
        import jax.numpy as jnp

        new = self.ty.apply(
            self.cfg, {f: jnp.asarray(x) for f, x in self.head((s, r)).items()},
            jnp.asarray(a), jnp.asarray(b), jnp.asarray(vc), o)
        self.heads[s, r] = {f: np.asarray(x) for f, x in new.items()}
        self.head_vcs[s, r] = np.maximum(self.head_vcs[s, r], vc)

    def ring_batch(self, batch):
        k = self.cfg.ops_per_key
        by_key = {}
        for s, r, *eff in batch:
            by_key.setdefault((s, r), []).append(eff)
        for key, effs in by_key.items():
            ring = self.rings.setdefault(key, [])
            for c in range(0, len(effs), k):
                if len(ring) + len(effs[c:c + k]) > k:
                    ring.clear()
                ring.extend(effs[c:c + k])


def _append_and_compare(tyname, cfg, batches, sharding=None):
    """``batches``: lists of (shard, row, op) — or (shard, row, (eff_a,
    eff_b)) for an effect given as its lanes — appended batch by batch,
    one commit VC an effect.  After each batch the table's head, rings
    and ``n_ops`` must equal the plain fold's, and a ``read_resolved`` at
    the newest clock what the type resolves the folded heads to."""
    ty = get_type(tyname)
    table = TypedTable(ty, cfg, sharding=sharding)
    fold, blobs = PlainFold(ty, cfg), BlobStore()
    clock = np.zeros(cfg.max_dcs, np.int32)
    for batch in batches:
        effects = []
        for s, r, op in batch:
            lanes = ([e[:2] for e in ty.downstream(
                op, fold.head((s, r)), blobs, cfg)]
                if isinstance(op[0], str) else [op])
            for a, b in lanes:
                clock[0] += 1
                eff = (s, r, np.asarray(a, np.int64), np.asarray(b, np.int32),
                       clock.copy(), 0)
                effects.append(eff)
                fold.apply(*eff)  # a remove observes the batch's own adds
        fold.ring_batch(effects)
        cols = list(zip(*effects))
        table.append(np.asarray(cols[0]), np.asarray(cols[1]),
                     np.stack(cols[2]), np.stack(cols[3]), np.stack(cols[4]),
                     np.asarray(cols[5], np.int32))
        for (s, r), ring in fold.rings.items():
            n = len(ring)
            assert table.n_ops[s, r] == n, (s, r)
            for i, dev in enumerate((table.ops_a, table.ops_b, table.ops_vc,
                                     table.ops_origin)):
                np.testing.assert_array_equal(
                    np.asarray(dev)[s, r, :n],
                    np.stack([e[i] for e in ring]), err_msg=f"ring {i} {s, r}")
            for f, x in fold.heads[s, r].items():
                np.testing.assert_array_equal(
                    np.asarray(table.head[f])[s, r], x, err_msg=f"{f} {s, r}")
            np.testing.assert_array_equal(
                np.asarray(table.head_vc)[s, r], fold.head_vcs[s, r])
        keys = sorted(fold.heads)
        resolved, _, complete = table.read_resolved(
            [s for s, _ in keys], [r for _, r in keys],
            np.tile(clock, (len(keys), 1)))
        assert np.asarray(complete).all()
        want = {f: np.stack([fold.heads[key][f] for key in keys])
                for f in fold.heads[keys[0]]}
        if ty.resolve_spec(cfg) is not None:
            want = ty.resolve(cfg, want)
        for f, x in want.items():
            np.testing.assert_array_equal(
                np.asarray(resolved[f])[:len(keys)], np.asarray(x), err_msg=f)
    return table


def _spread(n, cfg):
    """``n`` distinct (shard, row) pairs over every shard."""
    return [(i % cfg.n_shards, i // cfg.n_shards) for i in range(n)]


def _lww(handle, ts, cfg):
    ty = get_type("register_lww")
    return (np.asarray([handle, ts], np.int64),
            np.zeros(ty.eff_b_width(cfg), np.int32))


BIG = [2**31 + 5, 2**40 + 3]

#: name -> (type, batches(cfg)); every case crosses the staged operand
APPEND_CASES = {
    # int64 lanes beyond 2^32, both signs: exact through the lo/hi halves
    "counter_big_deltas": ("counter_pn", lambda cfg: [
        [(0, 0, ("increment", BIG[0])), (1, 0, ("decrement", BIG[0])),
         (2, 5, ("increment", BIG[1])), (3, 7, ("decrement", BIG[1]))],
        [(0, 0, ("decrement", BIG[1])), (0, 0, ("increment", BIG[0])),
         (3, 7, ("increment", 1))],
    ]),
    "set_aw_adds_removes": ("set_aw", lambda cfg: [
        [(0, 1, ("add", "x")), (0, 1, ("add", "y")), (2, 3, ("add", "x"))],
        [(0, 1, ("remove", "x")), (2, 3, ("add_all", ["p", "q"])),
         (1, 1, ("add", "z"))],
        [(0, 1, ("add", "x")), (2, 3, ("remove_all", ["x", "q"]))],
    ]),
    "lww_2pow45_timestamps": ("register_lww", lambda cfg: [
        [(0, 0, _lww(7, 2**45 + 9, cfg)), (1, 2, _lww(8, 2**45, cfg))],
        [(0, 0, _lww(9, 2**45 + 8, cfg)),     # older: loses
         (1, 2, _lww(5, 2**45 + 2**33, cfg)),  # newer in the high half
         (1, 2, _lww(6, 2**45 + 2**33, cfg))],  # tie: the handle decides
    ]),
    # window 0 (the whole-ring scan): one key more than once in a batch
    "one_key_twice": ("counter_pn", lambda cfg: [
        [(1, 4, ("increment", 3)), (2, 2, ("increment", 1)),
         (1, 4, ("increment", 4))],
    ]),
    # more than two rings of one key in one batch: gc, then ring-sized
    # pieces with a gc between them; a bystander key rides the first piece
    "one_key_17_times": ("counter_pn", lambda cfg: [
        [(1, 4, ("increment", 1))] * 3,
        [(1, 4, ("increment", 10 ** i if i < 9 else -i))
         for i in range(2 * cfg.ops_per_key + 1)] + [(0, 0, ("increment", 5))],
    ]),
    "ring_overflow_folds_first": ("counter_pn", lambda cfg: [
        [(3, 1, ("increment", 2))] * (cfg.ops_per_key - 1),
        [(3, 1, ("increment", 7)), (3, 1, ("decrement", 1)),
         (3, 2, ("increment", 1))],
    ]),
    "batch_of_1": ("counter_pn", lambda cfg: [[(2, 9, ("increment", 1))]]),
    # the last batch bucket exactly, and one past it (the next multiple)
    "batch_of_64": ("counter_pn", lambda cfg: [
        [(s, r, ("increment", s * 100 + r)) for s, r in _spread(64, cfg)]]),
    "batch_of_65": ("counter_pn", lambda cfg: [
        [(s, r, ("decrement", s * 100 + r + 1)) for s, r in _spread(65, cfg)],
        [(s, r, ("increment", 2**33)) for s, r in _spread(3, cfg)]]),
}


@pytest.mark.parametrize("case", sorted(APPEND_CASES))
def test_append_equals_plain_fold(cfg, case):
    tyname, batches = APPEND_CASES[case]
    batches = batches(cfg)
    table = _append_and_compare(tyname, cfg, batches)
    # one transfer and one program an append call (a split batch makes
    # more calls, never more than it has effects)
    assert table.scatter_transfers == table.scatter_launches
    assert len(batches) <= table.scatter_launches <= sum(map(len, batches))


@pytest.mark.parametrize("case", ["counter_big_deltas", "one_key_17_times",
                                  "set_aw_adds_removes"])
def test_append_on_a_mesh_placed_table(cfg, case):
    """The same cases on a table placed over four (virtual) devices, one
    shard each: the program runs under the table's ``shard_map``, the
    staged operand replicated, and every table stays in its placement."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    placed = NamedSharding(Mesh(np.array(jax.devices()[:4]), ("shard",)),
                           PartitionSpec("shard"))
    tyname, batches = APPEND_CASES[case]
    table = _append_and_compare(tyname, cfg, batches(cfg), sharding=placed)
    for x in jax.tree.leaves((table.ops_a, table.ops_b, table.ops_vc,
                              table.ops_origin, table.head, table.head_vc)):
        assert x.sharding.is_equivalent_to(placed, x.ndim)


# ---------------------------------------------------------------------------
# the flat read programs of a one-device table: one staged operand a
# launch, the answers of the routed programs (one host array an operand)
# ---------------------------------------------------------------------------
def _history_table(tyname, cfg):
    """A one-device table of 12 keys over every shard and a read VC at
    which keys 0-3 are fresh (nothing after it), 4-7 stale (their later
    effects in the ring) and 8-11 below coverage (three rings' worth
    after it, folded into snapshot versions newer than it)."""
    ty = get_type(tyname)
    table = TypedTable(ty, cfg)
    blobs = BlobStore()
    clock = np.zeros(cfg.max_dcs, np.int32)
    keys = _spread(12, cfg)

    def op(i, j):
        if tyname == "counter_pn":   # int64 lanes past 2^31, both signs
            return ("increment" if j % 2 else "decrement", BIG[j % 2] + i)
        return ("add", f"e{(i + j) % 4}")

    def commit(ids, j):
        effs = []
        for i in ids:
            for a, b, _ in ty.downstream(op(i, j), None, blobs, cfg):
                clock[0] += 1
                effs.append((*keys[i], a, b, clock.copy()))
        s, r, a, b, v = zip(*effs)
        table.append(np.asarray(s), np.asarray(r), np.stack(a), np.stack(b),
                     np.stack(v), np.zeros(len(effs), np.int32))

    for j in range(2):
        commit(range(12), j)
    at = clock.copy()
    for j in range(2, 4):
        commit(range(4, 12), j)
    for j in range(4, 4 + 3 * cfg.ops_per_key):
        commit(range(8, 12), j)
    return table, keys, at


def _routed(t, ss, rr, vcs, kmax):
    """What the routed [P, M'] programs answer for a flat batch, back in
    batch order: (head_state, head_gather, the fold at ``kmax``, the
    versioned read)."""
    import jax

    row_mat, pos = t._route(ss, rr)
    p, mm = row_mat.shape
    rows = np.minimum(row_mat, t.n_rows - 1)
    vc_mat = np.zeros((p, mm, vcs.shape[-1]), np.int32)
    vc_mat[pos[:, 0], pos[:, 1]] = vcs
    n_ops = np.where(row_mat < t.n_rows,
                     t.n_ops[np.arange(p)[:, None], rows], 0)
    snap = (t.snap, t.snap_vc, t.snap_seq, t.ops_a, t.ops_b, t.ops_vc,
            t.ops_origin)

    def back(tree):
        return jax.tree.map(lambda x: np.asarray(x)[pos[:, 0], pos[:, 1]],
                            tree)

    return (back(t._read_latest_fn(t.head, t.head_vc, rows, vc_mat)),
            back(t._latest_resolved_fn(t.head, t.head_vc, rows, vc_mat)),
            back(t._read_resolved_fn(t._fold_strategy(), kmax)(
                t.head, t.head_vc, *snap, rows, n_ops, vc_mat)),
            back(t._read_fn(*snap, rows, n_ops, vc_mat)))


def _same(got, want, what):
    import jax

    got, want = jax.tree.map(np.asarray, (got, want))
    assert jax.tree.structure(got) == jax.tree.structure(want), what
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(g, w, err_msg=what)


@pytest.mark.parametrize("m", [1, 16, 65])   # a bucket's edge; past the last
@pytest.mark.parametrize("tyname", ["counter_pn", "set_aw"])
def test_staged_flat_reads_answer_as_the_routed_programs(cfg, tyname, m):
    """head_gather, head_state, the flat fold at each ring-window bucket,
    ``read`` and ``ckpt_gather`` of a one-device table, each launched
    with the batch as one staged int32 operand, answer what the routed
    programs answer — rows fresh, stale and below coverage."""
    from antidote_tpu.store.typed_table import _cut

    t, keys, at = _history_table(tyname, cfg)
    idx = np.arange(m) % len(keys)
    ss = np.asarray([keys[i][0] for i in idx], np.int64)
    rr = np.asarray([keys[i][1] for i in idx], np.int64)
    vcs = np.tile(at, (m, 1))
    kmaxes = sorted({t._kmax_bucket(int(t.n_ops[ss, rr].max())), 0})
    assert len(kmaxes) == 2, kmaxes
    latest, gathered, _, versioned = _routed(t, ss, rr, vcs, 0)
    np.testing.assert_array_equal(latest[1], idx < 4)       # fresh
    np.testing.assert_array_equal(versioned[2], idx < 8)    # covered

    _same(t.read_latest(ss, rr, vcs), latest, "head_state")
    pss, prr, pvcs = t._pad_reads(ss, rr, vcs)
    _same(_cut(t._latest_resolved_flat_fn(
        t.head, t.head_vc, t._stage_reads(pss, prr, pvcs)), m), gathered,
        "head_gather")
    tables = (t.head, t.head_vc, t.snap, t.snap_vc, t.snap_seq, t.ops_a,
              t.ops_b, t.ops_vc, t.ops_origin)
    for kmax in kmaxes:
        got = _cut(t._read_resolved_flat_fn(t._fold_strategy(), kmax)(
            *tables, t._stage_reads(pss, prr, pvcs, t.n_ops[pss, prr])), m)
        _same(got[:3], _routed(t, ss, rr, vcs, kmax)[2], f"fold k{kmax}")
        # the versioned state and count ride along; a fresh row's state
        # is its head (the same where the fold is complete)
        _same(got[3:], versioned[:2], f"state k{kmax}")
    state, applied, complete = t.read(ss, rr, vcs)
    _same((state, applied), versioned[:2], "read")
    np.testing.assert_array_equal(complete, versioned[2] | latest[1])
    head, head_vc = _cut(t.gather_rows_dispatch(ss, rr), m)
    _same((head, head_vc), ({f: np.asarray(x)[ss, rr]
                             for f, x in t.head.items()},
                            np.asarray(t.head_vc)[ss, rr]), "ckpt_gather")


def test_stage_reads_layout_and_padding(cfg):
    """shard, row, [n_ops,] the read VC's lanes; zeros past the batch; a
    new buffer each call."""
    t = TypedTable(get_type("counter_pn"), cfg)
    vcs = np.asarray([[1, 2, 3], [4, 5, 6]], np.int32)
    a = t._stage_reads(np.asarray([3, 1]), np.asarray([7, 9]), vcs,
                       np.asarray([2, 5]), mb=4)
    assert a.dtype == np.int32 and a.tolist() == [
        [3, 7, 2, 1, 2, 3], [1, 9, 5, 4, 5, 6], [0] * 6, [0] * 6]
    one_vc = t._stage_reads([0, 2], [5, 6], np.asarray([8, 0, 1]))
    assert one_vc.tolist() == [[0, 5, 8, 0, 1], [2, 6, 8, 0, 1]]
    assert t._stage_reads([1], [2]).tolist() == [[1, 2]]
    assert t._stage_reads([1], [2]) is not t._stage_reads([1], [2])
