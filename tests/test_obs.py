"""Observability layer: metric parity with antidote_stats_collector
(/root/reference/src/antidote_stats_collector.erl:80-93), error monitor,
HTTP exposition, and wiring into the transaction manager."""

import logging
import urllib.request

import numpy as np
import pytest

from antidote_tpu.api.node import AntidoteNode
from antidote_tpu.config import AntidoteConfig
from antidote_tpu.obs import (
    Histogram,
    NodeMetrics,
    install_error_monitor,
)
from antidote_tpu.txn.manager import AbortError


def small_cfg():
    return AntidoteConfig(
        n_shards=2, max_dcs=2, ops_per_key=4, snap_versions=2,
        set_slots=4, keys_per_table=16, batch_buckets=(8,),
    )

pytestmark = pytest.mark.smoke


def test_txn_metrics_wiring():
    node = AntidoteNode(small_cfg())
    m = node.metrics
    txn = node.start_transaction()
    assert m.open_transactions.value() == 1
    node.update_objects([("k", "counter_pn", "b", ("increment", 3))], txn)
    node.read_objects([("k", "counter_pn", "b")], txn)
    node.commit_transaction(txn)
    assert m.open_transactions.value() == 0
    assert m.operations.value(type="update") == 1
    assert m.operations.value(type="read") == 1
    assert m.commit_batch_size.count == 1

    t2 = node.start_transaction()
    node.abort_transaction(t2)
    assert m.aborted_transactions.value() == 1
    assert m.open_transactions.value() == 0


def test_certification_abort_counts():
    node = AntidoteNode(small_cfg())
    t1 = node.start_transaction()
    t2 = node.start_transaction()
    # read-bearing txns keep certification (blind increments would take
    # the ISSUE 6 commutativity bypass and both commit)
    node.read_objects([("k", "counter_pn", "b")], t1)
    node.read_objects([("k", "counter_pn", "b")], t2)
    node.update_objects([("k", "counter_pn", "b", ("increment", 1))], t1)
    node.update_objects([("k", "counter_pn", "b", ("increment", 1))], t2)
    node.commit_transaction(t1)
    with pytest.raises(AbortError):
        node.commit_transaction(t2)
    assert node.metrics.aborted_transactions.value() == 1
    assert node.metrics.open_transactions.value() == 0


def test_certify_per_txn_property():
    """txn prop certify=False disables first-committer-wins for that txn
    (the certify txn property, reference get_txn_property)."""
    node = AntidoteNode(small_cfg())
    t1 = node.start_transaction()
    t2 = node.start_transaction(props={"certify": False})
    node.update_objects([("k", "counter_pn", "b", ("increment", 1))], t1)
    node.update_objects([("k", "counter_pn", "b", ("increment", 5))], t2)
    node.commit_transaction(t1)
    node.commit_transaction(t2)  # would abort under certification
    vals, _ = node.read_objects([("k", "counter_pn", "b")])
    assert vals[0] == 6


def test_hook_abort_keeps_gauge_exact():
    """A failing pre-commit hook must decrement open_transactions and count
    the abort (the hook-abort path closes the txn outside abort_transaction)."""
    node = AntidoteNode(small_cfg())
    node.register_pre_hook("b", lambda *a: (_ for _ in ()).throw(ValueError("no")))
    txn = node.start_transaction()
    with pytest.raises(AbortError):
        node.update_objects([("k", "counter_pn", "b", ("increment", 1))], txn)
    node.abort_transaction(txn)  # idempotent: must not double-count
    assert node.metrics.open_transactions.value() == 0
    assert node.metrics.aborted_transactions.value() == 1


def test_map_read_counts_one_client_op():
    """Composite map reads recurse internally; only the client-level read
    is counted (antidote_stats_collector counts coordinator-level ops)."""
    node = AntidoteNode(small_cfg())
    node.update_objects([
        ("m", "map_rr", "b", ("update", [(("f1", "counter_pn"), ("increment", 2)),
                                         (("f2", "counter_pn"), ("increment", 3))])),
    ])
    before = node.metrics.operations.value(type="read")
    vals, _ = node.read_objects([("m", "map_rr", "b")])
    assert vals[0][("f1", "counter_pn")] == 2
    assert node.metrics.operations.value(type="read") == before + 1
    # static reads must close their internal txn (gauge leak regression)
    assert node.metrics.open_transactions.value() == 0


def test_error_monitor_increments_error_count():
    m = NodeMetrics()
    logger = logging.getLogger("antidote_tpu.test_err")
    h = install_error_monitor(m, logger)
    try:
        logger.error("boom")
        logger.warning("not counted")
        assert m.error_count.value() == 1
    finally:
        logger.removeHandler(h)


def test_histogram_buckets_and_percentile():
    h = Histogram("h", buckets=(1, 10, 100))
    for v in (0.5, 5, 5, 50, 500):
        h.observe(v)
    assert h.count == 5
    assert h.percentile(0.5) == 10.0
    text = "\n".join(h.expose())
    assert 'h_bucket{le="10"} 3' in text
    assert "h_count 5" in text


def test_metrics_http_exposition():
    node = AntidoteNode(small_cfg())
    txn = node.start_transaction()
    node.update_objects([("k", "counter_pn", "b", ("increment", 3))], txn)
    node.commit_transaction(txn)
    node.metrics.observe_staleness(12.5)
    srv = node.serve_metrics(port=0)
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=5
        ).read().decode()
        assert 'antidote_operations_total{type="update"} 1' in body
        assert "antidote_staleness_count 1" in body
        assert "antidote_open_transactions 0" in body
    finally:
        srv.close()


def test_staleness_observed_from_stable_vc():
    node = AntidoteNode(small_cfg())
    vc = node.stable_vc()
    assert (vc == np.zeros(2)).all()
    node.metrics.observe_staleness(3.0)
    assert node.metrics.staleness.count == 1
