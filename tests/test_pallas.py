"""Pallas kernel parity: counter fold, stable min, OR-set presence.

Each kernel must agree with the generic JAX materializer path
(fold.fold_batch / vector.vmin / the set_aw presence rule) on randomized
inputs.  On the CPU test mesh the kernels run in interpret mode; the same
code compiles for the real chip.
"""

import numpy as np
import pytest

from antidote_tpu.config import AntidoteConfig
from antidote_tpu.crdt import get_type
from antidote_tpu.materializer import fold as fold_mod
from antidote_tpu.materializer import pallas_kernels as pk


@pytest.fixture
def rng():
    return np.random.default_rng(11)


def test_counter_fold_matches_generic(rng):
    cfg = AntidoteConfig(n_shards=1, max_dcs=3, ops_per_key=8,
                         snap_versions=2, keys_per_table=16)
    ty = get_type("counter_pn")
    b, k, d = 37, cfg.ops_per_key, cfg.max_dcs
    deltas = rng.integers(-50, 50, size=(b, k)).astype(np.int64)
    ops_vc = rng.integers(0, 6, size=(b, k, d)).astype(np.int32)
    n_ops = rng.integers(0, k + 1, size=(b,)).astype(np.int32)
    base_vc = rng.integers(0, 4, size=(b, d)).astype(np.int32)
    read_vc = base_vc + rng.integers(0, 4, size=(b, d)).astype(np.int32)
    base_cnt = rng.integers(-1000, 1000, size=(b,)).astype(np.int64)

    ops_a = np.zeros((b, k, ty.eff_a_width(cfg)), np.int64)
    ops_a[:, :, 0] = deltas
    ops_b = np.zeros((b, k, ty.eff_b_width(cfg)), np.int32)
    ops_origin = np.zeros((b, k), np.int32)
    state, applied_ref = fold_mod.fold_batch(
        ty, cfg, {"cnt": base_cnt}, ops_a, ops_b, ops_vc, ops_origin,
        n_ops, base_vc, read_vc,
    )
    cnt, applied = pk.counter_fold(
        base_cnt, deltas, ops_vc, n_ops, base_vc, read_vc, block=8,
    )
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(state["cnt"]))
    np.testing.assert_array_equal(np.asarray(applied), np.asarray(applied_ref))


def test_counter_fold_empty_ring():
    cfg = AntidoteConfig(n_shards=1, max_dcs=2, ops_per_key=4,
                         snap_versions=2, keys_per_table=8)
    b, k, d = 3, 4, 2
    cnt, applied = pk.counter_fold(
        np.asarray([5, -2, 0], np.int64), np.zeros((b, k), np.int32),
        np.zeros((b, k, d), np.int32), np.zeros((b,), np.int32),
        np.zeros((b, d), np.int32), np.ones((b, d), np.int32), block=8,
    )
    np.testing.assert_array_equal(np.asarray(cnt), [5, -2, 0])
    assert np.asarray(applied).sum() == 0


def test_counter_fold_overflow_guard():
    b, k, d = 2, 8, 2
    deltas = np.zeros((b, k), np.int64)
    deltas[0, 0] = 2**40  # would wrap the i32 kernel sum
    with pytest.raises(ValueError, match="fold_batch"):
        pk.counter_fold(
            np.zeros(b, np.int64), deltas, np.zeros((b, k, d), np.int32),
            np.full(b, k, np.int32), np.zeros((b, d), np.int32),
            np.ones((b, d), np.int32),
        )


def test_orset_presence_matches_rule(rng):
    b, e, d = 41, 8, 3
    addvc = rng.integers(0, 5, size=(b, e, d)).astype(np.int32)
    rmvc = rng.integers(0, 5, size=(b, e, d)).astype(np.int32)
    elems_lo = rng.integers(0, 3, size=(b, e)).astype(np.int32)
    want = (addvc > rmvc).any(-1) & (elems_lo != 0)
    got = np.asarray(pk.orset_presence(addvc, rmvc, elems_lo, block=16))
    np.testing.assert_array_equal(got.astype(bool), want)
