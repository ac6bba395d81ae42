"""Transaction-layer semantics, mirroring the reference's singledc suites
(clocksi_SUITE read-your-writes/isolation/concurrency, antidote_SUITE
static+interactive API, commit_hooks_SUITE; SURVEY §4 tier-3)."""

import numpy as np
import pytest

from antidote_tpu.api import AbortError, AntidoteNode

pytestmark = pytest.mark.smoke


@pytest.fixture
def node(cfg):
    return AntidoteNode(cfg)


def test_static_update_then_read(node):
    vc = node.update_objects([("k1", "counter_pn", "b", ("increment", 4))])
    vals, _ = node.read_objects([("k1", "counter_pn", "b")], clock=vc)
    assert vals == [4]


def test_interactive_read_your_writes(node):
    txn = node.start_transaction()
    node.update_objects([("k", "counter_pn", "b", ("increment", 2))], txn)
    assert node.read_objects([("k", "counter_pn", "b")], txn) == [2]
    node.update_objects([("k", "counter_pn", "b", ("increment", 3))], txn)
    assert node.read_objects([("k", "counter_pn", "b")], txn) == [5]
    vc = node.commit_transaction(txn)
    vals, _ = node.read_objects([("k", "counter_pn", "b")], clock=vc)
    assert vals == [5]


def test_read_your_writes_set(node):
    txn = node.start_transaction()
    node.update_objects([("s", "set_aw", "b", ("add", "x"))], txn)
    assert node.read_objects([("s", "set_aw", "b")], txn) == [["x"]]
    node.update_objects([("s", "set_aw", "b", ("remove", "x"))], txn)
    assert node.read_objects([("s", "set_aw", "b")], txn) == [[]]
    node.commit_transaction(txn)
    vals, _ = node.read_objects([("s", "set_aw", "b")])
    assert vals == [[]]


def test_snapshot_isolation_between_txns(node):
    node.update_objects([("k", "counter_pn", "b", ("increment", 1))])
    txn = node.start_transaction()
    before = node.read_objects([("k", "counter_pn", "b")], txn)
    # another (static) txn commits concurrently
    node.update_objects([("k2", "counter_pn", "b", ("increment", 99))])
    node.update_objects([("k", "counter_pn", "b", ("increment", 99))],
                        clock=None)
    # the open txn still sees its snapshot
    after = node.read_objects([("k", "counter_pn", "b")], txn)
    assert before == after == [1]
    node.commit_transaction(txn)


def test_abort_discards_writes(node):
    txn = node.start_transaction()
    node.update_objects([("k", "counter_pn", "b", ("increment", 7))], txn)
    node.abort_transaction(txn)
    vals, _ = node.read_objects([("k", "counter_pn", "b")])
    assert vals == [0]


def test_certification_conflict_aborts_second_txn(node):
    """READ-BEARING (rmw) txns keep first-committer-wins; both read the
    key before writing, so neither takes the blind-commutative bypass
    (ISSUE 6)."""
    node.update_objects([("k", "counter_pn", "b", ("increment", 1))])
    t1 = node.start_transaction()
    t2 = node.start_transaction()
    node.read_objects([("k", "counter_pn", "b")], t1)
    node.read_objects([("k", "counter_pn", "b")], t2)
    node.update_objects([("k", "counter_pn", "b", ("increment", 10))], t1)
    node.update_objects([("k", "counter_pn", "b", ("increment", 100))], t2)
    node.commit_transaction(t1)
    with pytest.raises(AbortError):
        node.commit_transaction(t2)
    vals, _ = node.read_objects([("k", "counter_pn", "b")])
    assert vals == [11]


def test_blind_commutative_writes_never_conflict(node):
    """The ISSUE 6 certification bypass: BLIND counter increments from
    concurrent txns commute, so none aborts and none touches the
    certification stamp table — only the read-bearing txn above pays
    first-committer-wins."""
    t1 = node.start_transaction()
    t2 = node.start_transaction()
    node.update_objects([("k", "counter_pn", "b", ("increment", 10))], t1)
    node.update_objects([("k", "counter_pn", "b", ("increment", 100))], t2)
    node.commit_transaction(t1)
    node.commit_transaction(t2)  # would first-committer-abort pre-bypass
    vals, _ = node.read_objects([("k", "counter_pn", "b")])
    assert vals == [110]
    assert ("k", "b") not in node.txm.committed_keys


def test_certification_disabled_allows_both(cfg):
    node = AntidoteNode(cfg, cert=False)
    t1 = node.start_transaction()
    t2 = node.start_transaction()
    node.update_objects([("k", "counter_pn", "b", ("increment", 10))], t1)
    node.update_objects([("k", "counter_pn", "b", ("increment", 100))], t2)
    node.commit_transaction(t1)
    node.commit_transaction(t2)
    vals, _ = node.read_objects([("k", "counter_pn", "b")])
    assert vals == [110]


def test_read_only_txn_commits_at_snapshot(node):
    node.update_objects([("k", "counter_pn", "b", ("increment", 5))])
    txn = node.start_transaction()
    node.read_objects([("k", "counter_pn", "b")], txn)
    vc = node.commit_transaction(txn)
    assert (vc == txn.snapshot_vc).all()


def test_causal_clock_threading(node):
    vc1 = node.update_objects([("k", "counter_pn", "b", ("increment", 1))])
    vc2 = node.update_objects([("k", "counter_pn", "b", ("increment", 1))],
                              clock=vc1)
    assert vc2[node.dc_id] > vc1[node.dc_id]
    vals, _ = node.read_objects([("k", "counter_pn", "b")], clock=vc2)
    assert vals == [2]


def test_type_check_rejects_bad_ops(node):
    with pytest.raises(TypeError):
        node.update_objects([("k", "counter_pn", "b", ("assign", 5))])
    with pytest.raises(TypeError):
        node.update_objects([("k", "nosuch_type", "b", ("increment", 1))])
    # binding the same key to a different type fails
    node.update_objects([("k", "counter_pn", "b", ("increment", 1))])
    with pytest.raises(TypeError):
        node.update_objects([("k", "set_aw", "b", ("add", "x"))])


def test_pre_commit_hook_transforms_update(node):
    def double(kto):
        key, type_name, (kind, n) = kto
        return key, type_name, (kind, n * 2)

    node.register_pre_hook("hooked", double)
    node.update_objects([("k", "counter_pn", "hooked", ("increment", 3))])
    vals, _ = node.read_objects([("k", "counter_pn", "hooked")])
    assert vals == [6]


def test_pre_commit_hook_failure_aborts(node):
    def boom(kto):
        raise ValueError("nope")

    node.register_pre_hook("hooked", boom)
    with pytest.raises(AbortError):
        node.update_objects([("k", "counter_pn", "hooked", ("increment", 3))])
    vals, _ = node.read_objects([("k", "counter_pn", "hooked")])
    assert vals == [0]


def test_post_commit_hook_observes_commit(node):
    seen = []
    node.register_post_hook("hooked", lambda kto: seen.append(kto))
    node.update_objects([("k", "counter_pn", "hooked", ("increment", 3))])
    assert seen == [("k", "counter_pn", ("increment", 3))]


def test_post_commit_hook_failure_nonfatal(node):
    def boom(kto):
        raise ValueError("nope")

    node.register_post_hook("hooked", boom)
    vc = node.update_objects([("k", "counter_pn", "hooked", ("increment", 3))])
    vals, _ = node.read_objects([("k", "counter_pn", "hooked")], clock=vc)
    assert vals == [3]


def test_multi_key_multi_type_txn(node):
    txn = node.start_transaction()
    node.update_objects(
        [
            ("c", "counter_pn", "b", ("increment", 1)),
            ("r", "register_lww", "b", ("assign", "v")),
            ("s", "set_aw", "b", ("add_all", ["a", "b"])),
            ("f", "flag_ew", "b", ("enable", None)),
        ],
        txn,
    )
    vc = node.commit_transaction(txn)
    vals, _ = node.read_objects(
        [
            ("c", "counter_pn", "b"),
            ("r", "register_lww", "b"),
            ("s", "set_aw", "b"),
            ("f", "flag_ew", "b"),
        ],
        clock=vc,
    )
    assert vals == [1, "v", ["a", "b"], True]


def test_many_keys_across_shards(node):
    updates = [(i, "counter_pn", "b", ("increment", i)) for i in range(40)]
    vc = node.update_objects(updates)
    objs = [(i, "counter_pn", "b") for i in range(40)]
    vals, _ = node.read_objects(objs, clock=vc)
    assert vals == [i for i in range(40)]


# ---------------------------------------------------------------------------
# decoded-value cache (the host-level snapshot_cache analogue)
# ---------------------------------------------------------------------------
def test_value_cache_invalidation_on_write(node):
    """Repeated latest reads serve from the decoded-value cache; every
    write to the key (or to a map's field/membership) invalidates it —
    reads must never see a stale cached value."""
    node.update_objects([("c", "counter_pn", "b", ("increment", 1))])
    for expect in (1, 2, 3):
        vals, _ = node.read_objects([("c", "counter_pn", "b")])
        assert vals[0] == expect
        vals, _ = node.read_objects([("c", "counter_pn", "b")])  # cached
        assert vals[0] == expect
        node.update_objects([("c", "counter_pn", "b", ("increment", 1))])
    # composite: field write invalidates the assembled-map entry
    node.update_objects([("m", "map_rr", "b", ("update", {
        ("k", "counter_pn"): ("increment", 5)}))])
    vals, _ = node.read_objects([("m", "map_rr", "b")])
    assert vals[0][("k", "counter_pn")] == 5
    vals, _ = node.read_objects([("m", "map_rr", "b")])  # cached
    assert vals[0][("k", "counter_pn")] == 5
    node.update_objects([("m", "map_rr", "b", ("update", {
        ("k", "counter_pn"): ("increment", 2)}))])
    vals, _ = node.read_objects([("m", "map_rr", "b")])
    assert vals[0][("k", "counter_pn")] == 7


def test_value_cache_historical_reads_bypass(node):
    """A cached latest value must not serve an open txn's older
    snapshot (the clock= parameter is only a causal LOWER bound — the
    snapshot-isolation case is a txn opened before later commits)."""
    node.update_objects([("s", "set_aw", "b", ("add", "x"))])
    txn = node.start_transaction()  # snapshot: only x
    node.update_objects([("s", "set_aw", "b", ("add", "y"))])
    vals, _ = node.read_objects([("s", "set_aw", "b")])
    assert vals[0] == ["x", "y"]  # fills the cache at latest
    vals = node.read_objects([("s", "set_aw", "b")], txn)
    assert vals[0] == ["x"], "old snapshot served the newer cached value"
    node.commit_transaction(txn)
    vals, _ = node.read_objects([("s", "set_aw", "b")])
    assert vals[0] == ["x", "y"]


def test_value_cache_client_mutation_isolated(node):
    """Mutating a returned container must not poison the cache."""
    node.update_objects([("s2", "set_aw", "b", ("add_all", ["a", "b"]))])
    vals, _ = node.read_objects([("s2", "set_aw", "b")])
    vals[0].append("EVIL")
    vals2, _ = node.read_objects([("s2", "set_aw", "b")])
    assert vals2[0] == ["a", "b"]
    node.update_objects([("m2", "map_rr", "b", ("update", {
        ("t", "set_aw"): ("add", "z")}))])
    mv, _ = node.read_objects([("m2", "map_rr", "b")])
    mv[0][("t", "set_aw")].append("EVIL")
    mv[0][("extra", "counter_pn")] = 666
    mv2, _ = node.read_objects([("m2", "map_rr", "b")])
    assert mv2[0] == {("t", "set_aw"): ["z"]}


def test_value_cache_nested_map_mutation_isolated(node):
    """Deep containers: mutating an INNER dict of a nested map must not
    poison the cache (the copy is recursive, not one level)."""
    node.update_objects([("mm", "map_rr", "b", ("update", {
        ("n", "map_rr"): ("update", {("c", "counter_pn"): ("increment", 1)}),
    }))])
    v, _ = node.read_objects([("mm", "map_rr", "b")])
    assert v[0][("n", "map_rr")][("c", "counter_pn")] == 1
    v[0][("n", "map_rr")][("c", "counter_pn")] = 999
    v2, _ = node.read_objects([("mm", "map_rr", "b")])
    assert v2[0][("n", "map_rr")][("c", "counter_pn")] == 1


def test_overlay_dots_restamped_under_interleaved_commits(node):
    """A txn's remove observing its OWN in-txn add must survive other
    txns committing in between (the tentative own-lane dot is rewritten
    to the real commit ts at commit — restamp_own_dots)."""
    txn = node.start_transaction()
    node.update_objects([("s", "set_aw", "b", ("add", "x"))], txn)
    # interleaved commits advance the commit counter past the tentative
    for i in range(3):
        node.update_objects([(f"o{i}", "counter_pn", "b", ("increment", 1))])
    node.update_objects([("s", "set_aw", "b", ("remove", "x"))], txn)
    node.commit_transaction(txn)
    vals, _ = node.read_objects([("s", "set_aw", "b")])
    assert vals[0] == [], "same-txn remove lost under interleaving"
    # mv register: second assign observes the first's tentative id
    txn = node.start_transaction()
    node.update_objects([("r", "register_mv", "b", ("assign", "a"))], txn)
    node.update_objects([("x", "counter_pn", "b", ("increment", 1))])
    node.update_objects([("r", "register_mv", "b", ("assign", "b"))], txn)
    node.commit_transaction(txn)
    vals, _ = node.read_objects([("r", "register_mv", "b")])
    assert vals[0] == ["b"], "observed-overwrite lost under interleaving"


def test_rga_same_txn_inserts_have_distinct_uids(node):
    """One txn inserting several elements: each element's uid must stay
    unique (op-seq lane), so a later delete targets the RIGHT one."""
    txn = node.start_transaction()
    node.update_objects([("d", "rga", "b", ("insert", (0, "a")))], txn)
    node.update_objects([("d", "rga", "b", ("insert", (1, "b")))], txn)
    node.update_objects([("d", "rga", "b", ("insert", (2, "c")))], txn)
    node.commit_transaction(txn)
    vals, _ = node.read_objects([("d", "rga", "b")])
    assert vals[0] == ["a", "b", "c"]
    node.update_objects([("d", "rga", "b", ("delete", 1))])
    vals, _ = node.read_objects([("d", "rga", "b")])
    assert vals[0] == ["a", "c"], "delete hit the wrong same-commit uid"
    # interleaved-commit variant: delete an element inserted in an open
    # txn whose tentative ts got stale
    txn = node.start_transaction()
    node.update_objects([("d2", "rga", "b", ("insert", (0, "p")))], txn)
    node.update_objects([("z", "counter_pn", "b", ("increment", 1))])
    node.update_objects([("d2", "rga", "b", ("insert", (1, "q")))], txn)
    node.update_objects([("d2", "rga", "b", ("delete", 0))], txn)
    node.commit_transaction(txn)
    vals, _ = node.read_objects([("d2", "rga", "b")])
    assert vals[0] == ["q"]


# ---------------------------------------------------------------------------
# reads of several transactions in one batched store read (ISSUE 34)
# ---------------------------------------------------------------------------
@pytest.fixture
def history(tmp_path, request):
    from antidote_tpu.config import AntidoteConfig
    from conftest import HISTORY_CFG, history_scenario

    node = AntidoteNode(AntidoteConfig(**HISTORY_CFG),
                        log_dir=str(tmp_path))
    yield (node, request.param) + history_scenario(node, request.param)
    node.store.log.close()


both_types = pytest.mark.parametrize(
    "history", ["set_aw", "counter_pn"], indirect=True)


@both_types
def test_read_objects_group_equals_one_read_a_transaction(history):
    """`read_objects_group` over transactions with distinct snapshots
    answers every object at its own transaction's snapshot, value for
    value what `read_objects` answers one transaction at a time: rows the
    head answers, rows a ring fold answers, a row below the device's
    coverage (log replay at its own VC), a never-written key, and for
    sets a wide key in a slot tier."""
    from conftest import history_values

    node, type_name, objs, txns, expect = history
    txm, store = node.txm, node.store
    ops = node.metrics.operations.value(type="read")
    assert not any(t.did_read for t in txns)
    got = txm.read_objects_group([(objs, t) for t in txns])
    assert all(t.did_read for t in txns)
    assert node.metrics.operations.value(type="read") - ops == \
        len(objs) * len(txns)
    fold = store.fold_status()
    assert fold["replays"] > 0 and fold["reads_by_fold"] > 0 \
        and fold["reads_by_head"] > 0
    if type_name == "set_aw":
        assert store.directory[("wide", "b")][0] == "set_aw#2"
    with store._value_cache_lock:
        store._value_cache.clear()
    one = [txm.read_objects(objs, t) for t in txns]
    for i in range(len(txns)):
        assert history_values(type_name, got[i]) == expect[i], i
        assert history_values(type_name, one[i]) == expect[i], i
    assert len({repr(e) for e in expect}) == len(expect)


@both_types
def test_read_objects_group_serves_cache_hits_off_the_device(history):
    """A transaction at the head whose objects the decoded-value cache
    holds rides a group without reaching the store; the group's other
    transaction is read at its own, older snapshot, alone."""
    from conftest import history_values

    node, type_name, objs, txns, expect = history
    txm, store = node.txm, node.store
    assert history_values(type_name, txm.read_objects(objs, txns[3])) \
        == expect[3]            # a latest read: back-fills the cache
    latest = node.start_transaction()
    seen = []
    read_resolved = store.read_resolved

    def spy(objects, read_vc, full_out=None):
        seen.append((list(objects), np.array(read_vc)))
        return read_resolved(objects, read_vc, full_out=full_out)

    store.read_resolved = spy
    got = txm.read_objects_group([(objs, latest), (objs, txns[0])])
    assert history_values(type_name, got[0]) == expect[3]
    assert history_values(type_name, got[1]) == expect[0]
    (objects, vcs), = seen
    assert objects == objs and vcs.shape == (len(objs), 2)
    assert (vcs == txns[0].snapshot_vc).all()
    # every object of the latest transaction was a hit: a group of its
    # own launches nothing
    del seen[:]
    assert history_values(
        type_name, txm.read_objects_group([(objs, latest)])[0]) == expect[3]
    assert seen == []


def test_read_merges_is_decided_from_what_the_request_holds(node):
    txn = node.start_transaction()
    plain = [("k", "counter_pn", "b"), ("s", "set_aw", "b")]
    assert node.txm.read_merges(plain, txn)
    assert not node.txm.read_merges(plain + [("m", "map_rr", "b")], txn)
    node.update_objects([("k", "counter_pn", "b", ("increment", 1))], txn)
    assert not node.txm.read_merges(plain, txn)
    with pytest.raises(ValueError):  # its read overlays its own write
        node.txm.read_objects_group([(plain, txn)])
    assert node.read_objects(plain, txn) == [1, []]
