"""Bytes the device has to move per row served, from a configuration's
widths.  `selfcheck` holds these against the arrays TypedTable allocates."""

from __future__ import annotations


def _state_bytes(ty: str, w: dict) -> int:
    d = w["max_dcs"]
    if ty == "counter_pn":
        return 8                                   # cnt int64
    if ty == "set_aw":
        s = w["set_slots"]
        # elems int64[S], addvc int32[S,D], rmvc int32[S,D], ovf int32
        return s * 8 + 2 * s * d * 4 + 4
    raise KeyError(f"no bytes model for type {ty!r}")


def head_row_bytes(ty: str, w: dict) -> int:
    """A head read gathers one row of the head state and its clock."""
    return _state_bytes(ty, w) + w["max_dcs"] * 4


def fold_row_bytes(ty: str, w: dict) -> int:
    """A read at an earlier snapshot reads the row's snapshot versions
    (state, clock, seq) and its whole op ring (a, b, clock, origin)."""
    d, v, k = w["max_dcs"], w["snap_versions"], w["ops_per_key"]
    a = 1
    b = 1 if ty == "counter_pn" else 1 + d
    snap = v * (_state_bytes(ty, w) + d * 4 + 8)
    ring = k * (a * 8 + b * 4 + d * 4 + 4)
    return snap + ring


MODELS = {"head_row": head_row_bytes, "fold_row": fold_row_bytes}
