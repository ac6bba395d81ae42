"""Pallas TPU kernels for the materializer hot path.

The generic fold (`fold.fold_batch`) runs the CRDT-specific ``apply`` under
a ``lax.scan`` — correct for every type, but for the monoid counter family
the fold is a *masked reduction*, bandwidth-bound VPU work with tiny
per-element compute: one pass over the op ring in VMEM, inclusion mask
(the vectorized ``is_op_in_snapshot``,
/root/reference/src/clocksi_materializer.erl:214-268) fused with the
reduction, no [B, K] intermediates materialized in HBM.

On a TPU the kernels are compiled by Mosaic; a kernel the compiler
refuses is an error, never a quiet switch to another path.  Off-TPU they
run under the Pallas interpreter, which only tests reach: the serving
path dispatches here only when :func:`in_path_ok` says so.

The package enables x64 globally (i64 payload lanes) and the serving
reads call these kernels from inside their own jit traces, so every
``pallas_call`` must lower the same with x64 on or off: operands are
i32 by construction, in-kernel literals and accumulators are pinned to
i32, and the BlockSpec index maps return i32 (a bare Python ``0`` would
be i64 under x64, which Mosaic refuses).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from antidote_tpu.obs.trace import device_program

_I32_MAX = jnp.iinfo(jnp.int32).max
#: block index 0 for BlockSpec index maps, pinned to i32 (see module doc)
_Z = np.int32(0)

#: scoped VMEM a TPU kernel may use by default (v5e: 16 MiB); the block
#: chooser budgets half of it, which leaves room for the estimate's error
#: and keeps the statically-unrolled set_aw fold's compile to seconds
_VMEM_LIMIT = 16 << 20
_VMEM_BUDGET = _VMEM_LIMIT // 2
_BLOCKS = (256, 128, 64, 32, 16, 8)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def in_path_ok() -> bool:
    """Whether `use_pallas` callers route the LIVE serving path through
    these kernels: on a TPU, always (compiled).  Off-TPU they only run
    under the Pallas interpreter, which is a test vehicle, not a serving
    mode; ANTIDOTE_PALLAS_INTERPRET=1 is the parity tests' way in."""
    if os.environ.get("ANTIDOTE_PALLAS_INTERPRET") == "1":
        return True
    return _on_tpu()


def _lane_tiles(width: int) -> int:
    """(8, 128) i32 vreg columns one row of ``width`` lanes occupies: a
    [BLK, 16] tile takes as much VMEM as a [BLK, 128] one."""
    return max(1, -(-int(width) // 128))


def _block_for(tiles_per_row: int, what: str) -> int:
    """Largest row block whose VMEM need — ``tiles_per_row`` lane-padded
    i32 tiles of 512 B per row, pipeline buffers and kernel temporaries
    together — fits the budget.  The one place a block size is chosen;
    a shape no block fits is an error, not a reason to leave the kernel."""
    for block in _BLOCKS:
        if tiles_per_row * 512 * block <= _VMEM_BUDGET:
            return block
    raise ValueError(
        f"{what}: {tiles_per_row} lane-padded VMEM tiles per row do not "
        f"fit {_VMEM_BUDGET >> 20} MiB of scoped VMEM even at a "
        f"{_BLOCKS[-1]}-row block; narrow the configuration (max_dcs, "
        "set_slots, ops_per_key)"
    )


def _pad_to(x, mult, axis, fill=0):
    n = x.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads, constant_values=fill)


def _row(block, w):
    """BlockSpec of a [B, w] operand blocked over rows."""
    return pl.BlockSpec((block, w), lambda i: (i, _Z))


def _plane(d, block, w):
    """BlockSpec of a lane-transposed [D, B, w] operand blocked over rows."""
    return pl.BlockSpec((d, block, w), lambda i: (_Z, i, _Z))


# ---------------------------------------------------------------------------
# counter fold: masked sum over the op ring with VC-dominance inclusion
# ---------------------------------------------------------------------------
def _counter_fold_kernel(deltas_ref, ops_vc_ref, n_ops_ref, base_vc_ref,
                         read_vc_ref, cnt_ref, applied_ref):
    # block shapes: deltas [BLK, K]; ops_vc [D, BLK, K] (lane-transposed so
    # each per-DC comparison is a clean 2D tile — Mosaic has no minor-dim
    # bool reduction); n_ops [BLK, 1]; base_vc/read_vc [BLK, D];
    # outputs [BLK, 1]
    d = ops_vc_ref.shape[0]
    v0 = ops_vc_ref[0]                             # [BLK, K]
    in_base = v0 <= base_vc_ref[:, 0:1]
    visible = v0 <= read_vc_ref[:, 0:1]
    for dd in range(1, d):
        vd = ops_vc_ref[dd]
        in_base = in_base & (vd <= base_vc_ref[:, dd:dd + 1])
        visible = visible & (vd <= read_vc_ref[:, dd:dd + 1])
    slots = jax.lax.broadcasted_iota(jnp.int32, v0.shape, 1)
    include = (~in_base) & visible & (slots < n_ops_ref[:])  # [BLK, K]
    # dtype-pinned sums: integer reductions accumulate at the DEFAULT int
    # width, so under an x64 trace the results would silently become i64
    # and fail the i32 out_shape
    zero = jnp.int32(0)
    cnt_ref[:] = jnp.sum(
        jnp.where(include, deltas_ref[:], zero), axis=1, keepdims=True,
        dtype=jnp.int32,
    )
    applied_ref[:] = jnp.sum(
        jnp.where(include, jnp.int32(1), zero), axis=1, keepdims=True,
        dtype=jnp.int32,
    )


@device_program("counter_fold", static_argnames=("block", "interpret"))
def _counter_fold_call(deltas, ops_vc, n_ops, base_vc, read_vc,
                       block: int, interpret: bool):
    b0 = deltas.shape[0]
    deltas = _pad_to(deltas, block, 0)
    ops_vc = _pad_to(ops_vc, block, 0)
    n_ops = _pad_to(n_ops.reshape(-1, 1), block, 0)
    base_vc = _pad_to(base_vc, block, 0)
    read_vc = _pad_to(read_vc, block, 0, fill=-1)  # nothing visible in pad
    b, k = deltas.shape
    d = ops_vc.shape[-1]
    ops_vc = jnp.transpose(ops_vc, (2, 0, 1))      # [D, B, K]
    cnt, applied = pl.pallas_call(
        _counter_fold_kernel,
        grid=(b // block,),
        in_specs=[
            _row(block, k), _plane(d, block, k), _row(block, 1),
            _row(block, d), _row(block, d),
        ],
        out_specs=[_row(block, 1), _row(block, 1)],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ],
        interpret=interpret,
        name="antidote_counter_fold",
    )(deltas, ops_vc, n_ops, base_vc, read_vc)
    return cnt[:b0, 0], applied[:b0, 0]


def counter_fold_deltas(deltas, ops_vc, n_ops, base_vc, read_vc,
                        block: int | None = None,
                        interpret: bool | None = None):
    """The counter fold kernel itself: ``deltas`` i32[M, K], ``ops_vc``
    i32[M, K, D], ``n_ops`` i32[M] (valid-prefix extents), ``base_vc``/
    ``read_vc`` i32[M, D] → (delta-sum i32[M], applied i32[M]).  Callable
    from the host or from inside a jit/shard_map trace (operands are one
    block's rows, so inside a shard_map body the kernel grid never
    crosses the shard axis).  The caller adds the base counters and owns
    the i32-delta overflow bound (typed_table gates on its host-tracked
    ``max_abs_delta`` before dispatching here; :func:`counter_fold`
    checks it on the host)."""
    k, d = ops_vc.shape[-2:]
    if block is None:
        # pipeline buffers (deltas + D clock planes, three narrow inputs,
        # two narrow outputs; double-buffered) + the mask temporaries
        block = _block_for(
            2 * ((1 + d) * _lane_tiles(k) + 5) + (4 + d) * _lane_tiles(k),
            "counter fold",
        )
    if interpret is None:
        interpret = not _on_tpu()
    return _counter_fold_call(
        jnp.asarray(deltas, jnp.int32), jnp.asarray(ops_vc, jnp.int32),
        jnp.asarray(n_ops, jnp.int32), jnp.asarray(base_vc, jnp.int32),
        jnp.asarray(read_vc, jnp.int32), block, interpret,
    )


def counter_fold(base_cnt, deltas, ops_vc, n_ops, base_vc, read_vc,
                 block: int | None = None, interpret: bool | None = None):
    """Batched counter_pn materialization as one fused Pallas pass — the
    HOST entry: checks the i32 bound, runs :func:`counter_fold_deltas`,
    adds the base counters.

    ``base_cnt`` i64[B] (snapshot counters), ``deltas`` i32[B, K] (op deltas,
    lane 0 of ops_a), ``ops_vc`` i32[B, K, D], ``n_ops`` i32[B],
    ``base_vc``/``read_vc`` i32[B, D].  Returns (cnt i64[B], applied i32[B]).

    Equivalent to ``fold.fold_batch`` for counter_pn whenever the ring-window
    deltas fit the i32 kernel sum; the running total stays i64.  Deltas whose
    magnitude could overflow the per-key i32 partial sum (|delta| >
    ``INT32_MAX // K``) raise ``ValueError`` — fall back to
    ``fold.fold_batch`` for such workloads rather than wrapping silently.
    """
    k = max(int(np.shape(deltas)[-1]), 1)
    if isinstance(deltas, np.ndarray):
        # host input: the bound check is free (no device sync)
        peak = int(np.abs(deltas).max()) if deltas.size else 0
    else:
        deltas = jnp.asarray(deltas)
        # device input: one scalar readback, not a full-array copy
        peak = int(jnp.abs(deltas).max()) if deltas.size else 0
    if peak > _I32_MAX // k:
        raise ValueError(
            f"counter_fold: |delta| up to {peak} could overflow the i32 "
            f"kernel sum over a {k}-slot ring; use fold.fold_batch for "
            "this workload"
        )
    dcnt, applied = counter_fold_deltas(
        deltas, ops_vc, n_ops, base_vc, read_vc, block, interpret,
    )
    return jnp.asarray(base_cnt, jnp.int64) + dcnt.astype(jnp.int64), applied


# ---------------------------------------------------------------------------
# OR-set fold: the full add-wins apply rule over the op ring, one pass
# ---------------------------------------------------------------------------
def _split_handles(h):
    """i64 handles -> (lo, hi) i32 bit planes (Mosaic kernels are i32-only;
    equality tests compare both planes)."""
    lo = (h & 0xFFFFFFFF).astype(jnp.int32)
    hi = (h >> 32).astype(jnp.int32)
    return lo, hi


def _join_handles(lo, hi):
    return (hi.astype(jnp.int64) << 32) | (lo.astype(jnp.int64) & 0xFFFFFFFF)


def _set_aw_fold_kernel(elems_lo_ref, elems_hi_ref, addvc_ref, rmvc_ref,
                        ovf_ref, h_lo_ref, h_hi_ref, is_rm_ref, obs_ref,
                        ops_vc_ref, origin_ref, own_ref, n_ops_ref,
                        base_vc_ref, read_vc_ref,
                        out_lo_ref, out_hi_ref, out_add_ref, out_rm_ref,
                        out_ovf_ref, out_applied_ref):
    # block shapes: elems planes [BLK, E]; addvc/rmvc [D, BLK, E]
    # (lane-transposed — per-DC comparisons are clean 2D tiles, see
    # _counter_fold_kernel); ovf/n_ops [BLK, 1]; handle planes / is_rm /
    # origin / own [BLK, K]; obs/ops_vc [D, BLK, K]; base/read [BLK, D].
    # The K ring slots unroll as a static loop: each op's add-wins rule
    # (match / free-slot steal / observed-remove raise) is a masked
    # comparison over the [BLK, E] element tiles, so the whole ring folds
    # in one kernel with no [B, K, E] intermediates in HBM.
    d = ops_vc_ref.shape[0]
    k = h_lo_ref.shape[1]
    e = elems_lo_ref.shape[1]
    v0 = ops_vc_ref[0]                                  # [BLK, K]
    in_base = v0 <= base_vc_ref[:, 0:1]
    visible = v0 <= read_vc_ref[:, 0:1]
    for dd in range(1, d):
        vd = ops_vc_ref[dd]
        in_base = in_base & (vd <= base_vc_ref[:, dd:dd + 1])
        visible = visible & (vd <= read_vc_ref[:, dd:dd + 1])
    slots = jax.lax.broadcasted_iota(jnp.int32, v0.shape, 1)
    include_all = (~in_base) & visible & (slots < n_ops_ref[:])  # [BLK, K]

    elems_lo = elems_lo_ref[:]
    elems_hi = elems_hi_ref[:]
    add_p = [addvc_ref[dd] for dd in range(d)]          # each [BLK, E]
    rm_p = [rmvc_ref[dd] for dd in range(d)]
    ovf = ovf_ref[:]
    applied = jnp.zeros_like(ovf)
    iota_e = jax.lax.broadcasted_iota(jnp.int32, elems_lo.shape, 1)
    zero = jnp.int32(0)
    for kk in range(k):
        inc = include_all[:, kk:kk + 1]                 # [BLK, 1]
        h_lo = h_lo_ref[:, kk:kk + 1]
        h_hi = h_hi_ref[:, kk:kk + 1]
        is_rm = is_rm_ref[:, kk:kk + 1] == 1
        origin = origin_ref[:, kk:kk + 1]
        own = own_ref[:, kk:kk + 1]
        occ = (elems_lo | elems_hi) != 0
        match = (elems_lo == h_lo) & (elems_hi == h_hi) & occ    # [BLK, E]
        # bool minor-dim reductions don't lower — pin to i32 sums/mins
        has_match = jnp.sum(
            match.astype(jnp.int32), axis=1, keepdims=True, dtype=jnp.int32
        ) > 0
        idx_match = jnp.min(
            jnp.where(match, iota_e, jnp.int32(e)), axis=1, keepdims=True
        )
        present = add_p[0] > rm_p[0]
        for dd in range(1, d):
            present = present | (add_p[dd] > rm_p[dd])
        free = ~(present & occ)
        has_free = jnp.sum(
            free.astype(jnp.int32), axis=1, keepdims=True, dtype=jnp.int32
        ) > 0
        idx_free = jnp.min(
            jnp.where(free, iota_e, jnp.int32(e)), axis=1, keepdims=True
        )
        idx_add = jnp.where(has_match, idx_match, idx_free)
        sel_add = iota_e == idx_add
        sel_match = iota_e == idx_match
        fresh = ~has_match
        can_add = has_match | has_free
        upd_add = inc & (~is_rm) & can_add & sel_add    # [BLK, E]
        upd_rm = inc & is_rm & has_match & sel_match
        elems_lo = jnp.where(upd_add, h_lo, elems_lo)
        elems_hi = jnp.where(upd_add, h_hi, elems_hi)
        for dd in range(d):
            # per-row gathers as masked sums (one-hot row select —
            # dynamic per-row gathers don't tile)
            row_add = jnp.sum(
                jnp.where(sel_add, add_p[dd], zero), axis=1, keepdims=True,
                dtype=jnp.int32,
            )
            row_rm = jnp.sum(
                jnp.where(sel_add, rm_p[dd], zero), axis=1, keepdims=True,
                dtype=jnp.int32,
            )
            a_row = jnp.where(fresh, zero, row_add)
            r_row = jnp.where(fresh, zero, row_rm)
            a_row = jnp.where(origin == dd, jnp.maximum(a_row, own), a_row)
            m_row = jnp.sum(
                jnp.where(sel_match, rm_p[dd], zero), axis=1, keepdims=True,
                dtype=jnp.int32,
            )
            rm_row = jnp.maximum(m_row, obs_ref[dd][:, kk:kk + 1])
            add_p[dd] = jnp.where(upd_add, a_row, add_p[dd])
            rm_p[dd] = jnp.where(
                upd_add, r_row, jnp.where(upd_rm, rm_row, rm_p[dd])
            )
        dropped = inc & (~is_rm) & (~can_add)
        ovf = ovf + dropped.astype(jnp.int32)
        applied = applied + inc.astype(jnp.int32)
    out_lo_ref[:] = elems_lo
    out_hi_ref[:] = elems_hi
    for dd in range(d):
        out_add_ref[dd] = add_p[dd]
        out_rm_ref[dd] = rm_p[dd]
    out_ovf_ref[:] = ovf
    out_applied_ref[:] = applied


@device_program("set_aw_fold", static_argnames=("block", "interpret"))
def _set_aw_fold_call(elems_lo, elems_hi, addvc, rmvc, ovf,
                      h_lo, h_hi, is_rm, obs, ops_vc, ops_origin,
                      n_ops, base_vc, read_vc, block: int, interpret: bool):
    b0 = elems_lo.shape[0]
    elems_lo = _pad_to(elems_lo, block, 0)
    elems_hi = _pad_to(elems_hi, block, 0)
    addvc = _pad_to(addvc, block, 0)
    rmvc = _pad_to(rmvc, block, 0)
    ovf = _pad_to(ovf.reshape(-1, 1), block, 0)
    h_lo = _pad_to(h_lo, block, 0)
    h_hi = _pad_to(h_hi, block, 0)
    is_rm = _pad_to(is_rm, block, 0)
    obs = _pad_to(obs, block, 0)
    ops_vc = _pad_to(ops_vc, block, 0)
    ops_origin = _pad_to(ops_origin, block, 0)
    n_ops = _pad_to(n_ops.reshape(-1, 1), block, 0)
    base_vc = _pad_to(base_vc, block, 0)
    read_vc = _pad_to(read_vc, block, 0, fill=-1)   # nothing visible in pad
    b, e = elems_lo.shape
    k = h_lo.shape[1]
    d = ops_vc.shape[-1]
    # commit stamp at the origin lane — apply's .at[origin].max(commit_vc
    # [origin]); gathered here so the kernel never indexes by a dynamic lane
    own = jnp.take_along_axis(
        ops_vc, ops_origin[..., None].astype(jnp.int32), axis=2
    )[..., 0]
    addvc_t = jnp.transpose(addvc, (2, 0, 1))       # [D, B, E]
    rmvc_t = jnp.transpose(rmvc, (2, 0, 1))
    obs_t = jnp.transpose(obs, (2, 0, 1))           # [D, B, K]
    ops_vc_t = jnp.transpose(ops_vc, (2, 0, 1))
    row = functools.partial(_row, block)
    plane = functools.partial(_plane, d, block)
    lo, hi, addp, rmp, ovf2, applied = pl.pallas_call(
        _set_aw_fold_kernel,
        grid=(b // block,),
        in_specs=[
            row(e), row(e), plane(e), plane(e), row(1),
            row(k), row(k), row(k), plane(k), plane(k), row(k), row(k),
            row(1), row(d), row(d),
        ],
        out_specs=[
            row(e), row(e), plane(e), plane(e), row(1), row(1),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, e), jnp.int32),
            jax.ShapeDtypeStruct((b, e), jnp.int32),
            jax.ShapeDtypeStruct((d, b, e), jnp.int32),
            jax.ShapeDtypeStruct((d, b, e), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ],
        interpret=interpret,
        name="antidote_set_aw_fold",
    )(elems_lo, elems_hi, addvc_t, rmvc_t, ovf,
      h_lo, h_hi, is_rm, obs_t, ops_vc_t, ops_origin, own,
      n_ops, base_vc, read_vc)
    return (
        lo[:b0], hi[:b0],
        jnp.transpose(addp, (1, 2, 0))[:b0],
        jnp.transpose(rmp, (1, 2, 0))[:b0],
        ovf2[:b0, 0], applied[:b0, 0],
    )


def set_aw_fold_block(e: int, k: int, d: int) -> int:
    """Row block of the set_aw fold for element slots ``e``, ring slots
    ``k`` and clock width ``d``.  Every operand tile is lane-padded to
    128, so at the serving widths (E=16, K=16) a row costs as much VMEM
    as one eight times wider.  Tiles per row: the double-buffered
    pipeline — E-wide 2+2D in and out, K-wide 5+2D in, six narrow — plus
    the fold's live temporaries (about 45+6D E-wide tiles; calibrated
    against what Mosaic asks for at E=K=16: 18.9 MB at block 256, D=4 and
    28.0 MB at D=8, i.e. 144 and 213 tiles per row; this counts 147 and
    219)."""
    te, tk = _lane_tiles(e), _lane_tiles(k)
    pipeline = (4 + 4 * d) * te + (5 + 2 * d) * tk + 6
    return _block_for(2 * pipeline + (45 + 6 * d) * te, "set_aw fold")


def set_aw_fold(state, ops_a, ops_b, ops_vc, ops_origin, n_ops,
                base_vc, read_vc, block: int | None = None,
                interpret: bool | None = None):
    """Batched set_aw materialization as one fused Pallas pass — the
    BASELINE workload's own fold on a kernel.

    ``state`` = {elems i64[B, E], addvc/rmvc i32[B, E, D], ovf i32[B]},
    ``ops_a`` i64[B, K, A] (lane 0 = element handle), ``ops_b``
    i32[B, K, 1+D] (kind + observed add VC), ``ops_vc`` i32[B, K, D],
    ``ops_origin`` i32[B, K], ``n_ops`` i32[B], ``base_vc``/``read_vc``
    i32[B, D].  Returns (state, applied i32[B]) — byte-identical to
    ``fold.fold_batch`` for set_aw (the add-wins observed-remove rule,
    including slot-steal ordering and the ovf drop counter).

    Callable from the host or from inside a jit/shard_map trace: the i64
    handles are split into i32 planes HERE (which needs x64, the
    package's setting), and the jitted kernel call sees only i32
    operands.  Inside a shard_map body the operands are one block's rows,
    so the kernel grid never crosses the shard axis.
    """
    ops_vc = jnp.asarray(ops_vc, jnp.int32)
    d = ops_vc.shape[-1]
    elems_lo, elems_hi = _split_handles(jnp.asarray(state["elems"], jnp.int64))
    h_lo, h_hi = _split_handles(jnp.asarray(ops_a, jnp.int64)[..., 0])
    ops_b = jnp.asarray(ops_b, jnp.int32)
    if block is None:
        block = set_aw_fold_block(elems_lo.shape[-1], h_lo.shape[-1], d)
    if interpret is None:
        interpret = not _on_tpu()
    lo, hi, addvc, rmvc, ovf, applied = _set_aw_fold_call(
        elems_lo, elems_hi,
        jnp.asarray(state["addvc"], jnp.int32),
        jnp.asarray(state["rmvc"], jnp.int32),
        jnp.asarray(state["ovf"], jnp.int32),
        h_lo, h_hi, ops_b[..., 0], ops_b[..., 1:1 + d],
        ops_vc, jnp.asarray(ops_origin, jnp.int32),
        jnp.asarray(n_ops, jnp.int32), jnp.asarray(base_vc, jnp.int32),
        jnp.asarray(read_vc, jnp.int32), block, interpret,
    )
    return {
        "elems": _join_handles(lo, hi),
        "addvc": addvc, "rmvc": rmvc, "ovf": ovf,
    }, applied


# ---------------------------------------------------------------------------
# OR-set presence: fused add/remove dot comparison over gathered head rows
# ---------------------------------------------------------------------------
def _presence_kernel(addvc_ref, rmvc_ref, elems_lo_ref, out_ref):
    # block: addvc/rmvc [D, BLK, E] (lane-transposed); elems_lo [BLK, E]
    d = addvc_ref.shape[0]
    present = addvc_ref[0] > rmvc_ref[0]           # [BLK, E]
    for dd in range(1, d):
        present = present | (addvc_ref[dd] > rmvc_ref[dd])
    present = present & (elems_lo_ref[:] != 0)
    # dtype-pinned (not weak-literal where): see _counter_fold_kernel
    out_ref[:] = present.astype(jnp.int32)


@device_program("orset_presence", static_argnames=("block", "interpret"))
def _presence_call(addvc, rmvc, elems_lo, block: int, interpret: bool):
    b0 = addvc.shape[0]
    addvc = _pad_to(addvc, block, 0)
    rmvc = _pad_to(rmvc, block, 0)
    elems_lo = _pad_to(elems_lo, block, 0)
    b, e, d = addvc.shape
    addvc = jnp.transpose(addvc, (2, 0, 1))        # [D, B, E]
    rmvc = jnp.transpose(rmvc, (2, 0, 1))
    out = pl.pallas_call(
        _presence_kernel,
        grid=(b // block,),
        in_specs=[_plane(d, block, e), _plane(d, block, e), _row(block, e)],
        out_specs=_row(block, e),
        out_shape=jax.ShapeDtypeStruct((b, e), jnp.int32),
        interpret=interpret,
        name="antidote_orset_presence",
    )(addvc, rmvc, elems_lo)
    return out[:b0]


def orset_presence(addvc, rmvc, elems_lo, block: int | None = None,
                   interpret: bool | None = None):
    """OR-set element presence for gathered head rows.

    ``addvc``/``rmvc`` i32[B, E, D] (per-slot add/remove dots), ``elems_lo``
    i32[B, E] (nonzero ⇔ slot occupied; low 32 bits suffice for the
    occupancy test).  present ⟺ ∃d: addvc > rmvc — the observed-remove
    rule of ``antidote_crdt_set_aw`` resolved as one fused comparison.
    Returns i32[B, E] (0/1).  Callable from the host or from inside a
    jit/shard_map trace.
    """
    e, d = addvc.shape[-2:]
    if block is None:
        # two D-plane inputs + occupancy + output, double-buffered, and
        # the running mask
        block = _block_for((2 * (2 * d + 2) + 2) * _lane_tiles(e),
                           "orset presence")
    if interpret is None:
        interpret = not _on_tpu()
    return _presence_call(
        jnp.asarray(addvc, jnp.int32), jnp.asarray(rmvc, jnp.int32),
        jnp.asarray(elems_lo, jnp.int32), block, interpret,
    )
