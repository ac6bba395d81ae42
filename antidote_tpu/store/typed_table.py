"""Per-type sharded device table: key slots, snapshot versions, op rings.

The tensor re-design of ``materializer_vnode``'s two ETS tables
(/root/reference/src/materializer_vnode.erl:76): ``ops_cache`` becomes a
fixed op ring per key slot, ``snapshot_cache`` a fixed ring of materialized
snapshot versions.  The riak_core ring (16 partitions by default,
/root/reference/config/vars.config:5) becomes a leading shard axis ``P`` on
every array; device kernels are per-shard bodies vmapped over that axis, so
when the arrays are laid out over a ``Mesh(('shard',))`` XLA partitions the
batch across devices with no cross-device traffic on the data plane.

Layout per type (P shards, N key slots, V versions, K ring slots, D lanes):

  snap[f]     : [P, N, V, *field_shape]   materialized snapshot fields
  snap_vc     : i32[P, N, V, D]           snapshot clocks
  snap_seq    : i64[P, N, V]              insertion sequence (0 = empty)
  ops_a       : i64[P, N, K, A]           effect payload lanes
  ops_b       : i32[P, N, K, B]
  ops_vc      : i32[P, N, K, D]           commit-augmented op clocks
  ops_origin  : i32[P, N, K]              origin DC lane
  n_ops       : host-mirrored i32[P, N]   valid ring prefix length

Host API is flat — (shards[M], rows[M], ...) — and is routed into padded
``[P, M']`` per-shard blocks internally.  Padding uses out-of-range indices:
scatters drop them (mode="drop"), gathers clip and the caller masks.

GC policy (replaces op_insert_gc / snapshot_insert_gc,
/root/reference/src/materializer_vnode.erl:513-647): when a key's ring
would overflow, fold the whole ring into a new snapshot version (evicting
the oldest) at a self-derived safe VC — the per-lane max of ring-op and
retained-snapshot clocks.  Causal in-order delivery guarantees no later op
can be dominated by that merge, so stored snapshots never contain holes.

Reads below the oldest retained coverage are flagged *incomplete*; the
caller falls back to a host-side log replay, mirroring the reference's
``get_from_snapshot_log`` (/root/reference/src/materializer_vnode.erl:415-419).
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from antidote_tpu.clock import orddict
from antidote_tpu.config import AntidoteConfig
from antidote_tpu.crdt.base import CRDTType
from antidote_tpu.materializer import fold as fold_mod
from antidote_tpu.materializer import longlog
from antidote_tpu.obs.trace import device_program, span


def _bucket(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    return ((n + buckets[-1] - 1) // buckets[-1]) * buckets[-1]


# ---------------------------------------------------------------------------
# row writes in the tables' own layout
#
# A table array is [P, N, *field] with small trailing dims, and the TPU
# keeps such an array with the ROW axis minor-most (rows on the 128
# lanes): one key's state is a lane column through the field's tiles.
# XLA's scatter wants its window dims minor instead, so `x.at[s, r].set`
# re-lays the whole array out and back — temporaries of several times the
# table for a bucket of rows (8.0 GB for a GC at 1M set_aw rows).  Moving
# the row axis last is free in that layout, and a row is then written by
# reading the aligned block of <= 128 rows it lies in, replacing its lane
# and writing the block back with dynamic_update_slice: in place, a few
# tiles a row, nothing that grows with the table (the int64 fields
# excepted: the compiler's 64-bit rewrite still splits those whole).
# ---------------------------------------------------------------------------
_LANES = 128


def _view_perm(shape) -> Tuple[int, ...]:
    """Order of a table array's dims ([P, N, *rest]) that matches how
    the TPU lays it out, so that transposing to it moves nothing: rows
    last (on the lanes), before them the dim that fills the sublanes —
    the last one, unless its size is no multiple of 8 and another's is
    (ops_b [.., K=16, 9] is kept [.., 9, K, N]).  Only speed hangs on
    the guess: a view the layout does not match is copied there and
    back."""
    rest = list(range(2, len(shape)))
    wide = [d for d in rest if shape[d] > 1]
    if wide and shape[wide[-1]] % 8:
        for d in reversed(wide[:-1]):
            if shape[d] % 8 == 0:
                rest.remove(d)
                rest.append(d)
                break
    return (0, *rest, 1)


def _rows_last(x):
    """[P, N, ...] -> [P, ..., N] in :func:`_view_perm`'s order."""
    return jnp.transpose(x, _view_perm(x.shape))


def _rows_back(xv, shape):
    """:func:`_rows_last`'s inverse for an array of ``shape``."""
    return jnp.transpose(xv, np.argsort(_view_perm(shape)))


def _write_row(xv, shape, lead, r, value, valid=True):
    """Write ``value`` [*field] at row ``r`` of ``xv``, the rows-last
    view of a table array of ``shape`` [P, N, *slot dims, *field];
    ``lead`` = (shard, *slot indices).  ``valid`` False writes nothing
    (the row and ``lead`` must still be in range)."""
    perm = _view_perm(shape)
    n = shape[1]
    lw = min(_LANES, n)
    r = jnp.asarray(r, jnp.int32)
    start = jnp.clip((r // lw) * lw, 0, n - lw)
    zero = jnp.zeros((), jnp.int32)
    # per original dim: where the block starts and how long it is
    at = [jnp.asarray(lead[0], jnp.int32), start] + [
        jnp.asarray(i, jnp.int32) for i in lead[1:]]
    at += [zero] * (len(shape) - len(at))
    size = [1, lw] + [1] * (len(lead) - 1) + list(shape[1 + len(lead):])
    idx = tuple(at[d] for d in perm)
    old = jax.lax.dynamic_slice(xv, idx, tuple(size[d] for d in perm))
    # the value as a [1, 1, *1s, *field] block, in the view's order
    blk = jnp.reshape(value, [1] * (1 + len(lead)) + size[1 + len(lead):])
    blk = jnp.transpose(blk, perm).astype(xv.dtype)
    hit = (jnp.arange(lw, dtype=jnp.int32) == r - start) & valid
    return jax.lax.dynamic_update_slice(xv, jnp.where(hit, blk, old), idx)


#: a table of at least this many rows (a device's block of it) ...
_ROW_WRITE_MIN_ROWS = 1 << 17
#: ... takes a batch of at most this many rows by row writes (the two
#: buckets a commit group of requests fills); a larger one, a fill's
#: thousands of rows, is scattered
_ROW_WRITE_MAX_BATCH = 512


def _write_rows(tree, lead, rows, values, count, valid):
    """Write a batch of rows into every array of ``tree`` (a pytree of
    [P, N, *slot dims, *field] tables) in place: row ``i`` of the batch
    goes to (``lead[0][i]``, ``rows[i]``, *``lead[1:]`` at ``i``) and
    takes ``values`` leaf ``[i]`` (``values`` mirrors ``tree``, leaves
    [M, *field]); only the first ``count`` rows are looked at, and
    ``valid`` [M] masks padding among them (whose indices must still be
    in range).

    Two ways, chosen from static shapes.  A bucket of rows
    (<= ``_ROW_WRITE_MAX_BATCH``) of a large table is written row by row
    in the table's own layout (:func:`_write_row`, ``count`` turns of a
    loop, ~80 us a turn on a v5e): nothing is reserved that grows with
    the table, where a scatter re-lays whole fields out and back (8 GB
    for a GC at a million set_aw rows).  A small table is scattered — its
    re-layout is a few microseconds, a loop's turns are not — and so is
    a bulk batch (a fill's thousands of rows): one re-layout for all of
    them beats thousands of turns, and a fill finds the memory a
    deployment's tier tables and commit groups have not taken yet."""
    leaves = jax.tree.leaves(tree)
    p, n = leaves[0].shape[:2]
    m = rows.shape[0]
    if p * n < _ROW_WRITE_MIN_ROWS or m > _ROW_WRITE_MAX_BATCH:
        ok = valid & (jnp.arange(m) < count)
        at = (jnp.where(ok, lead[0], p), rows, *lead[1:])
        return jax.tree.map(lambda x, v: x.at[at].set(v, mode="drop"),
                            tree, values)
    views = jax.tree.map(_rows_last, tree)

    def body(i, views):
        at = tuple(ix[i] for ix in lead)
        return jax.tree.map(
            lambda xv, x, v: _write_row(xv, x.shape, at, rows[i], v[i],
                                        valid[i]),
            views, tree, values)

    views = jax.lax.fori_loop(0, count, body, views)
    return jax.tree.map(lambda xv, x: _rows_back(xv, x.shape), views, tree)


def _cut(tree, m: int):
    """The first ``m`` rows of every device array of a pytree as host
    copies: a large array (a bucket of tier rows) is cut on the device, a
    small one is fetched whole — an eager slice is a dispatch of its own.
    The device's cut is the next power of two (an eager slice compiles
    once a size, and a merged batch has any number of rows of a tier).
    The transfers start together and are waited for once: one after the
    other each is a round trip of its own (~0.7 ms on the chip)."""
    mb = 1 << (m - 1).bit_length()
    tree = jax.tree.map(
        lambda x: x[:mb] if x.nbytes > (1 << 20) else x, tree)
    return jax.tree.map(lambda x: np.array(x[:m]), jax.device_get(tree))


def _join_i64(halves):
    """int64 values from the int32 halves NumPy's ``.view(np.int32)``
    splits them into (lo, hi on the last axis): (hi << 32) | lo,
    exactly."""
    return ((halves[..., 1].astype(jnp.int64) << 32)
            | halves[..., 0].astype(jnp.uint32).astype(jnp.int64))


def _unstage_reads(staged, lanes: int):
    """A staged read operand (:meth:`TypedTable._stage_reads`) taken
    apart inside its program: its first ``lanes`` columns as vectors
    (shard, row, and for a fold the used ring prefix), then the read VCs
    [M, D]."""
    return (*(staged[:, i] for i in range(lanes)), staged[:, lanes:])


def _head_update_body(ty, cfg, window: int = 0):
    """Write-time fold: apply ring slots [start, end) of each touched key
    onto its *head* state (the eagerly-materialized snapshot at the key's
    full applied history).  This is the write-side analogue of the
    reference pushing committed ops into the materializer at commit time
    (clocksi_vnode:update_materializer,
    /root/reference/src/clocksi_vnode.erl:634-657) — paying the fold once
    per commit so hot reads are pure gathers.

    The keys come flat: ``shards`` / ``rows`` / ``starts`` / ``ends``
    [M], each key once; an entry whose shard is out of range is padding,
    and only the first ``count`` entries are looked at (default: all).

    ``window`` > 0 scans only a ``window``-slot dynamic slice at each
    key's start instead of the whole ring — a 1-op commit folds 1 slot,
    not ops_per_key (the write-amplification fix for small commits)."""

    def update(head, head_vc, ops_a, ops_b, ops_vc, ops_origin,
               shards, rows, starts, ends, count=None):
        def one(h, hvc, a, b, v, o, start, end):
            k = v.shape[0]
            if 0 < window < k:
                # clamped slice keeps [start, start+window) in range; the
                # include mask re-anchors to the true [start, end) span
                s0 = jnp.clip(start, 0, k - window)
                a = jax.lax.dynamic_slice_in_dim(a, s0, window, 0)
                b = jax.lax.dynamic_slice_in_dim(b, s0, window, 0)
                v = jax.lax.dynamic_slice_in_dim(v, s0, window, 0)
                o = jax.lax.dynamic_slice_in_dim(o, s0, window, 0)
                slots = s0 + jnp.arange(window, dtype=jnp.int64)
            else:
                slots = jnp.arange(k, dtype=jnp.int64)

            def step(carry, xs):
                state, cvc = carry
                ea, eb, op_vc, origin, slot = xs
                include = (slot >= start) & (slot < end)
                new = ty.apply(cfg, state, ea, eb, op_vc, origin)
                merged = jax.tree.map(
                    lambda n_, o_: jnp.where(include, n_, o_), new, state
                )
                cvc = jnp.where(include, jnp.maximum(cvc, op_vc), cvc)
                return (merged, cvc), None

            (state, cvc), _ = jax.lax.scan(
                step, (h, hvc), (a, b, v, o, slots),
            )
            return state, cvc

        p, n = head_vc.shape[:2]
        # clip padding for the gathers
        at = (jnp.minimum(shards, p - 1), jnp.minimum(rows, n - 1))
        state, cvc = jax.vmap(one)(
            {f: x[at] for f, x in head.items()}, head_vc[at],
            ops_a[at], ops_b[at], ops_vc[at], ops_origin[at], starts, ends,
        )
        # padding (a shard out of range) writes nothing
        return _write_rows(
            (head, head_vc), (at[0],), at[1], (state, cvc),
            shards.shape[0] if count is None else count, shards < p)

    return update


def _shard_read_latest_body(ty, cfg):
    """Per-shard fast read: gather head rows; a row is *fresh* iff its head
    VC is dominated by the read VC (then head == the exact snapshot).
    Stale rows must take the versioned fold path."""

    def read(head, head_vc, rows, read_vcs):
        hvc = head_vc[rows]
        state = {f: x[rows] for f, x in head.items()}
        fresh = jnp.all(hvc <= read_vcs, axis=-1)
        return state, fresh

    return read


def _shard_base_select_body(ty, cfg):
    """Per-shard snapshot-version selection: the newest retained version
    dominated by each read VC becomes the fold base (vector_orddict
    get_smaller, /root/reference/src/vector_orddict.erl:74-87)."""

    def select(snap, snap_vc, snap_seq, rows, read_vcs):
        svc = snap_vc[rows]            # [M, V, D]
        sseq = snap_seq[rows]          # [M, V]
        idx, found = orddict.get_smaller(svc, sseq, read_vcs)
        m = rows.shape[0]
        take = jnp.arange(m)
        base_vc = jnp.where(found[:, None], svc[take, idx], 0)
        base_state = {
            f: jnp.where(
                found.reshape((m,) + (1,) * (x.ndim - 2)),
                x[rows][take, idx],
                jnp.zeros_like(x[rows][take, idx]),
            )
            for f, x in snap.items()
        }
        # complete ⟺ the key was never GC'd (ring holds its whole history),
        # or the selected base is the NEWEST retained version — the ring
        # only holds ops after the newest version, so folding onto an older
        # version would silently miss the ops GC'd into newer ones.
        never_gcd = jnp.max(sseq, axis=-1) == 0
        newest = jnp.max(sseq, axis=-1)
        picked_newest = found & (sseq[take, idx] == newest)
        complete = picked_newest | never_gcd
        return base_state, base_vc, complete

    return select


def _shard_read_body(ty, cfg):
    """Per-shard read kernel: operates on one shard's block."""

    select = _shard_base_select_body(ty, cfg)

    def read(snap, snap_vc, snap_seq, ops_a, ops_b, ops_vc, ops_origin,
             rows, n_ops_rows, read_vcs):
        base_state, base_vc, complete = select(
            snap, snap_vc, snap_seq, rows, read_vcs
        )
        state, applied = fold_mod.fold_batch(
            ty, cfg, base_state,
            ops_a[rows], ops_b[rows], ops_vc[rows], ops_origin[rows],
            n_ops_rows, base_vc, read_vcs,
        )
        return state, applied, complete

    return read


class TypedTable:
    """Host handle for one CRDT type's sharded device arrays."""

    def __init__(
        self,
        ty: CRDTType,
        cfg: AntidoteConfig,
        n_rows: int | None = None,
        n_shards: int | None = None,
        sharding=None,
        metrics=None,
    ):
        self.ty = ty
        self.cfg = cfg
        self.metrics = metrics
        #: per-strategy serving-fold dispatch counts (host tallies; the
        #: node status' materializer block and the
        #: antidote_fold_dispatch_total metric read these)
        self.fold_dispatches: Dict[str, int] = {}
        self.n_rows = n_rows or cfg.keys_per_table
        self.n_shards = n_shards or cfg.n_shards
        self.sharding = sharding
        self.used_rows = np.zeros((self.n_shards,), np.int64)
        #: per-shard reusable rows freed by the cold tier's guarded evict
        #: (store/coldtier.py) — ``alloc_row`` pops here before advancing
        #: the high-water mark, which is what keeps device residency
        #: BOUNDED under a beyond-RAM keyspace instead of growing the
        #: table forever.  ``used_rows`` stays the row-extent high-water
        #: mark (freed rows sit below it holding zeros).
        self.free_rows: Dict[int, list] = {}
        self.next_seq = 1
        self._resolved_fns: Dict[bool, Any] = {}
        self._resolved_flat_fns: Dict[bool, Any] = {}
        self._commit_scatter_fns: Dict[int, Any] = {}
        #: host arrays :meth:`append` handed to the device, and the device
        #: programs it launched (node status ``write_plane.scatter``)
        self.scatter_transfers = 0
        self.scatter_launches = 0
        #: GC launches, the rows they folded into a snapshot version and
        #: the host seconds their dispatch took (``write_plane.gc``)
        self.gc_launches = 0
        self.gc_rows = 0
        self.gc_seconds = 0.0
        #: times the table doubled its rows (``write_plane.tiers``)
        self.grows = 0
        #: the versioned read (``pipeline.fold``): launches of the fold
        #: program, rows it folded, and of the rows the locked read
        #: plane gathered, those the head answered against those a fold did
        self.fold_launches = 0
        self.fold_rows = 0
        self.fold_seconds = 0.0
        self.reads_by_head = 0
        self.reads_by_fold = 0
        # host-tracked bound on |eff_a lane 0| — gates the i32 Pallas
        # counter-fold dispatch without any device readback (the r1 advisor
        # flagged the per-call jnp.abs().max() guard as a blocking sync)
        self.max_abs_delta = 0
        # host-tracked entry-wise max over all appended commit VCs: a read
        # VC dominating this makes EVERY row fresh, so the serving read can
        # skip the versioned fold without any device round trip (the
        # common read-at-current-VC case — the reference's reads also take
        # the cached-snapshot fast path when nothing concurrent is
        # prepared, /root/reference/src/materializer_vnode.erl:382-413)
        self.max_commit_vc = np.zeros((cfg.max_dcs,), np.int32)
        d, v, k = cfg.max_dcs, cfg.snap_versions, cfg.ops_per_key
        a, b = ty.eff_a_width(cfg), ty.eff_b_width(cfg)
        #: int32 columns of one effect in the staged commit operand
        #: (:meth:`append`): shard, row, slot, origin, end of the key's
        #: new ring span, eff_a as lo/hi halves, eff_b, commit vc
        self._staged_cols = 5 + 2 * a + b + d
        p, n = self.n_shards, self.n_rows
        spec = ty.state_spec(cfg)

        def mk(shape, dtype):
            # created IN its placement: a mesh table's array is never
            # whole on one device (at 2M set_aw rows the largest is 2.1 GB)
            return jnp.zeros(shape, dtype, device=sharding)

        self.snap = {
            f: mk((p, n, v) + shape, dtype) for f, (shape, dtype) in spec.items()
        }
        self.snap_vc = mk((p, n, v, d), jnp.int32)
        self.snap_seq = mk((p, n, v), jnp.int64)
        self.ops_a = mk((p, n, k, a), jnp.int64)
        self.ops_b = mk((p, n, k, b), jnp.int32)
        self.ops_vc = mk((p, n, k, d), jnp.int32)
        self.ops_origin = mk((p, n, k), jnp.int32)
        self.n_ops = np.zeros((p, n), np.int32)  # host-authoritative mirror
        # host-side conservative bound on per-key used element slots —
        # drives the overflow escape hatch (KVStore._promote_key): only
        # ever over-counts, reset to the exact count at promotion
        self.slots_ub = np.zeros((p, n), np.int32)
        # head = eagerly-materialized state at each key's full applied
        # history (folded at append time; reads at VC ≥ head_vc are gathers)
        self.head = {
            f: mk((p, n) + shape, dtype) for f, (shape, dtype) in spec.items()
        }
        self.head_vc = mk((p, n, d), jnp.int32)
        # published serving epochs: frozen copies of (head, head_vc) plus
        # the max-commit-VC cap at publish time — the read-while-write
        # double buffer (r4 VERDICT item 2).  Reads pinned at a VC ≤ cap
        # serve from the frozen copy as pure gathers while the live head
        # absorbs writes; see :meth:`publish_epoch` for the correctness
        # contract.  LRU-retained (an epoch a pinned snapshot still reads
        # stays alive; at most ``_EPOCH_CAP`` kept).
        self.epochs: list = []
        self._epoch_uses = 0
        #: serves that missed both gather fast paths (epoch publication
        #: is pointless while every read is provably fresh — publishers
        #: key off this)
        self.slow_serves = 0
        # --- serving-epoch double buffer (ISSUE 5 lock-split reads) ----
        # Two alternating frozen (head, head_vc) snapshots that the wire
        # server's lock-free read stage gathers from.  Unlike ``epochs``
        # (whole-head jnp.copy per publish), these are maintained
        # INCREMENTALLY: the publish scatters only the rows appended
        # since the spare buffer's freeze into the DONATED spare — so
        # publish cost scales with the write working set, not table size
        # (the satellite "bound publish_epoch cost per tick").
        self._serving = [None, None]
        self._serving_cur = 0
        #: (shard, row) pairs appended since the current / spare slot's
        #: freeze; None = unbounded (overflow or invalidation) — the next
        #: freeze must full-copy
        self._serving_dirty: "set | None" = set()
        self._serving_spare_dirty: "set | None" = None
        #: called (no args) whenever an out-of-band mutation invalidates
        #: the frozen buffers — the KVStore points this at its
        #: serving-epoch drop so stale store-wide epochs die with them
        self.on_serving_invalidate = None
        self._serving_conservative = False
        #: (shard, row) pairs written since the last CHECKPOINT capture —
        #: the incremental-chain stamp's dirty window (independent of the
        #: serving-freeze windows above, which publishes consume on their
        #: own cadence).  None = untracked (overflow past the cap or an
        #: out-of-band mutation): the next stamp must be a full rebase.
        self._ckpt_dirty: "set | None" = set()

    #: checkpoint dirty windows larger than this stop tracking: a delta
    #: link that would carry most of the table has no cost advantage
    #: over a rebase, and the set itself must stay bounded
    _CKPT_DIRTY_CAP = 262144

    def take_ckpt_dirty(self) -> "set | None":
        """Consume the checkpoint dirty window (called under the commit
        lock by the stamp capture): returns the written (shard, row) set
        since the previous capture, or None when a rebase is required;
        the window restarts empty either way."""
        out = self._ckpt_dirty
        self._ckpt_dirty = set()
        return out

    # ------------------------------------------------------------------
    # serving-epoch double buffer (lock-free wire reads)
    # ------------------------------------------------------------------
    #: dirty sets past this size stop tracking rows; the next freeze
    #: full-copies (a scatter of 10k+ rows stops beating the copy)
    _SERVING_DIRTY_CAP = 8192

    def note_serving_touch(self, shards, rows) -> None:
        """Record appended rows for the incremental serving freeze AND
        the incremental checkpoint stamp (separate windows, separate
        consumers)."""
        pairs = list(zip(shards.tolist(), rows.tolist()))
        for attr in ("_serving_dirty", "_serving_spare_dirty"):
            s = getattr(self, attr)
            if s is None:
                continue
            s.update(pairs)
            if len(s) > self._SERVING_DIRTY_CAP:
                setattr(self, attr, None)
        ck = self._ckpt_dirty
        if ck is not None:
            ck.update(pairs)
            if len(ck) > self._CKPT_DIRTY_CAP:
                self._ckpt_dirty = None

    def serving_slot(self):
        """The current frozen serving buffer (or None before any freeze)."""
        return self._serving[self._serving_cur]

    def serving_spare(self):
        """The slot the NEXT freeze would donate — publishers check it
        against the live epoch's buffers (donating a buffer the current
        epoch still gathers from would delete it under a reader)."""
        return self._serving[1 - self._serving_cur]

    def serving_dirty(self) -> bool:
        cur = self._serving[self._serving_cur]
        return cur is None or self._serving_dirty is None or bool(
            self._serving_dirty)

    def invalidate_serving(self) -> None:
        """Drop both frozen buffers after any out-of-band table mutation
        (row growth, handoff install)."""
        self._serving = [None, None]
        self._serving_dirty = set()
        self._serving_spare_dirty = None
        #: the out-of-band mutation isn't row-tracked: the next freeze
        #: must report its write-set as UNKNOWN (touched=None) so cache
        #: entries cannot revalidate across it
        self._serving_conservative = True
        # same for the checkpoint window: a handoff install / promotion
        # moved rows the window didn't see — the next stamp must rebase
        self._ckpt_dirty = None
        cb = self.on_serving_invalidate
        if cb is not None:
            cb()

    @functools.cached_property
    def _freeze_scatter_fn(self):
        """Jitted incremental freeze: donate the spare buffer and write
        the dirty rows' live head state over it, row by row
        (:func:`_write_rows`) — the first ``count`` of the flat (shard,
        row) batch, compiled per batch bucket.  On a mesh-placed table
        each device writes the rows of its own shards into its slice of
        the spare (:meth:`_jit_rows`): a clean shard's slice is
        untouched."""
        def fn(sp_head, sp_vc, head, head_vc, ss, rr, count):
            sl, mine = self._local(ss, sp_vc.shape[0])
            at = (sl, rr)
            return _write_rows(
                (sp_head, sp_vc), (sl,), rr,
                ({f: x[at] for f, x in head.items()}, head_vc[at]),
                count, mine)

        return self._jit_rows("freeze_serving_scatter", fn, 4, 3, (0, 1))

    def freeze_serving(self, can_donate: bool, force_copy: bool = False):
        """Freeze the live head into the spare serving slot and make it
        current.  Returns (slot, mode, touched, rows, shard_rows): mode
        "scatter" (incremental — ``rows`` rows re-frozen) or "copy"
        (full).  ``touched`` is the frozenset of rows WRITTEN since the
        previous publish (one window — the snapshot cache's validity
        set; the scatter set itself spans two windows, one per buffer
        slot), or None when unknown (untracked overflow / after an
        out-of-band invalidation).  ``shard_rows`` maps shard → rows
        re-frozen in that shard's slice (the mesh plane's per-shard
        publish observable; tracked only for mesh-placed tables), or
        None — a full copy (every slice rebuilt) or an untracked
        single-chip scatter.  Returns None when the freeze must be
        DEFERRED (the
        spare may still be read by a pinned epoch and cannot be
        donated).  ``force_copy`` rebuilds the slot from scratch instead
        of donating — required when the spare is still referenced by the
        LIVE epoch (a partial publish left it there; waiting can never
        free it).

        Caller must hold the commit lock (no concurrent appends)."""
        spare_i = 1 - self._serving_cur
        spare = self._serving[spare_i]
        dirty = self._serving_spare_dirty
        if force_copy or spare is None or dirty is None:
            frozen = self._copy_tree_fn((self.head, self.head_vc))
            mode, rows, shard_rows = "copy", self.n_shards * self.n_rows, None
        elif not can_donate:
            return None
        else:
            pairs = sorted(dirty)
            m = len(pairs)
            shard_rows = None
            if self.sharding is not None:
                # per-shard counts are only consumed by the mesh
                # publisher — single-chip publishes skip the loop
                shard_rows = {}
                for s, _ in pairs:
                    shard_rows[int(s)] = shard_rows.get(int(s), 0) + 1
            ss, rr = self._pad_rows([p[0] for p in pairs],
                                    [p[1] for p in pairs])
            frozen = self._freeze_scatter_fn(
                spare["head"], spare["head_vc"], self.head, self.head_vc,
                ss, rr, np.int32(m))
            mode, rows = "scatter", m
        slot = {"head": frozen[0], "head_vc": frozen[1],
                "cap": self.max_commit_vc.copy()}
        if self._serving_conservative or self._serving_dirty is None:
            touched = None
            self._serving_conservative = False
        else:
            touched = frozenset(self._serving_dirty)
        self._serving[spare_i] = slot
        self._serving_cur = spare_i
        self._serving_spare_dirty = self._serving_dirty
        self._serving_dirty = set()
        return slot, mode, touched, rows, shard_rows

    # ------------------------------------------------------------------
    # row allocation / growth
    # ------------------------------------------------------------------
    def alloc_row(self, shard: int) -> int:
        free = self.free_rows.get(shard)
        if free:
            # evicted row reuse: the guarded evict zeroed the row's whole
            # device state, so the new occupant starts from bottom exactly
            # like a fresh row (the evictor also marked the row touched +
            # epoch-promoted, so no frozen buffer serves stale bytes)
            return free.pop()
        if self.used_rows[shard] == self.n_rows:
            self._grow()
        r = int(self.used_rows[shard])
        self.used_rows[shard] += 1
        return r

    def resident_rows(self) -> int:
        """Device rows currently holding key state: the allocation
        high-water mark minus the freed (evicted, reusable) rows — the
        quantity the cold tier's ``--resident-rows`` budget bounds."""
        return int(self.used_rows.sum()) - sum(
            len(v) for v in self.free_rows.values())

    @functools.cached_property
    def _clear_rows_fn(self):
        """One-launch row clear (cold-tier evict, the source row of a
        tier promotion): zero every device array at the first ``count``
        (shard, row) pairs of the batch, in place."""
        def fn(tree, ss, rr, count):
            sl, mine = self._local(ss, tree["head_vc"].shape[0])
            # a row's versions and ring slots lie between its shard and
            # its fields: one write of [V or K, *field] clears them all
            zeros = jax.tree.map(
                lambda x: jnp.zeros(rr.shape + x.shape[2:], x.dtype), tree)
            return _write_rows(tree, (sl,), rr, zeros, count, mine)

        return self._jit_rows("clear_rows", fn, 1, 3, (0,))

    def _tree(self) -> dict:
        """Every device array of the table, by name."""
        return {
            "snap": self.snap, "head": self.head,
            "snap_vc": self.snap_vc, "snap_seq": self.snap_seq,
            "ops_a": self.ops_a, "ops_b": self.ops_b,
            "ops_vc": self.ops_vc, "ops_origin": self.ops_origin,
            "head_vc": self.head_vc,
        }

    def _set_tree(self, tree: dict) -> None:
        self.snap, self.head = tree["snap"], tree["head"]
        self.snap_vc, self.snap_seq = tree["snap_vc"], tree["snap_seq"]
        self.ops_a, self.ops_b = tree["ops_a"], tree["ops_b"]
        self.ops_vc, self.ops_origin = tree["ops_vc"], tree["ops_origin"]
        self.head_vc = tree["head_vc"]

    def clear_rows(self, shards, rows) -> None:
        """Zero the whole device state of the given rows (host mirrors
        are the caller's)."""
        ss, rr = self._pad_rows(shards, rows)
        self._set_tree(self._clear_rows_fn(
            self._tree(), ss, rr, np.int32(len(rows))))

    @functools.cached_property
    def _row_state_fn(self):
        @device_program("row_state")
        def fn(tree, s, r):
            return jax.tree.map(lambda x: x[s, r], tree)

        return fn

    def row_state(self, shard: int, row: int) -> dict:
        """One row's whole device state (every array of :meth:`_tree`,
        [*field]) as device arrays: dispatched, not waited for."""
        return self._row_state_fn(self._tree(), np.int32(shard),
                                  np.int32(row))

    @functools.cached_property
    def _install_row_fn(self):
        """The destination half of a tier promotion: embed one row's
        state from a narrower tier (:meth:`row_state` of the source
        table) into this table's widths — zero-padding the widened slot
        and lane axes, zeros being empty slots in every slotted layout —
        and write it at (shard, row) in place.  Version seqs move above
        everything this table has numbered, so the key's newest-version
        order survives.  Compiled per source tier."""
        def fn(tree, state, ss, rr, seq_shift, count):
            sl, mine = self._local(ss, tree["head_vc"].shape[0])

            def emb(v, x):
                out = jnp.zeros(x.shape[2:], x.dtype)
                return out.at[tuple(slice(0, n) for n in v.shape)].set(
                    v.astype(x.dtype))[None]

            state = dict(state)
            seq = state["snap_seq"]
            state["snap_seq"] = jnp.where(seq > 0, seq + seq_shift, 0)
            return _write_rows(tree, (sl,), rr,
                               jax.tree.map(emb, state, tree), count, mine)

        return self._jit_rows("tier_promote", fn, 1, 5, (0,))

    def install_row(self, shard: int, row: int, state: dict,
                    seq_shift: int, count: int = 1) -> None:
        """Write ``state`` (another tier's :meth:`row_state`) at (shard,
        row); ``count`` 0 compiles the program and writes nothing."""
        self._set_tree(self._install_row_fn(
            self._tree(), state, np.full(1, shard, np.int32),
            np.full(1, row, np.int32), np.int64(seq_shift),
            np.int32(count)))

    def warm(self, src_row=None, stop=None) -> bool:
        """Compile what a commit group, a publish and a read of this
        table launch at the smallest batch bucket, by running each
        program on padding: nothing is written.  For a tier table that
        no one can reach yet (KVStore builds and warms it off the commit
        path; ``src_row``: the shapes of a :meth:`row_state` of the tier
        below), so that the first promotion into it finds every program
        compiled.  ``stop()`` is asked between programs; True ends the
        walk (returns False)."""
        mb = self.cfg.batch_buckets[0]
        none = np.zeros(0, np.int64)
        z = np.zeros(mb, np.int64)
        vcs = np.zeros((mb, self.cfg.max_dcs), np.int32)

        def commit(window):
            staged = np.zeros((mb, self._staged_cols), np.int32)
            staged[:, 0] = self.n_shards
            (self.ops_a, self.ops_b, self.ops_vc, self.ops_origin,
             self.head, self.head_vc) = self._commit_scatter_for(window)(
                self.ops_a, self.ops_b, self.ops_vc, self.ops_origin,
                self.head, self.head_vc, staged)

        def gc():
            ss, rr = self._pad_rows(none, none)
            self.snap, self.snap_vc, self.snap_seq = self._gc_fn(
                self.snap, self.snap_vc, self.snap_seq, self.head,
                self.head_vc, ss, rr, np.zeros(mb, np.int64), np.int32(0))

        def fold(kmax):
            strategy = self._fold_strategy()
            out = self._read_resolved_flat_fn(strategy, kmax)(
                self.head, self.head_vc, self.snap, self.snap_vc,
                self.snap_seq, self.ops_a, self.ops_b, self.ops_vc,
                self.ops_origin, self._stage_reads(z, z, vcs, z))[0]
            self._merge_scatter_fn(out, np.full(mb, mb, np.int64), out)

        steps = [
            lambda: self.install_row(0, 0, jax.tree.map(
                lambda x: np.zeros(x.shape, x.dtype), src_row), 0, count=0)
            if src_row is not None else None,
            lambda: commit(1), lambda: commit(0), gc,
            # both slots by copy, then the incremental program
            lambda: [self.freeze_serving(True) for _ in range(3)],
            lambda: self.row_state(0, 0),
            lambda: self.clear_rows(none, none),
            lambda: self.gather_rows_dispatch(none, none),
        ]
        if self.sharding is not None:
            steps += [lambda: self.read_latest(z[:1], z[:1], vcs[:1]),
                      lambda: self.read(z[:1], z[:1], vcs[:1])]
        else:
            steps += [
                lambda: self._latest_resolved_flat_fn(
                    self.head, self.head_vc, self._stage_reads(z, z, vcs)),
                lambda: self._head_state_flat_fn(
                    self.head, self.head_vc, self._stage_reads(z, z, vcs)),
            ] + [functools.partial(fold, k)
                 for k in sorted({self._kmax_bucket(1), 0})]
        for step in steps:
            if stop is not None and stop():
                return False
            step()
        return True

    def adopt_programs(self, twin: "TypedTable") -> None:
        """Take over the compiled programs of ``twin``, a table of the
        same type, widths and placement that was warmed while this one
        was already serving: a program this table has not built yet is
        the twin's from now on (its bodies read the type, the widths and
        the placement, never a table's arrays), so this table's first
        launch of it compiles nothing.  A table keeps its programs in
        its ``__dict__`` under names that end in ``_fn`` (one program)
        or ``_fns`` (a dict of variants): that is the whole list.  The
        twin gives its arrays up."""
        assert (twin.ty, twin.cfg, twin.sharding) == (
            self.ty, self.cfg, self.sharding)
        for name, theirs in list(vars(twin).items()):
            if name.endswith("_fns"):
                mine = getattr(self, name)
                for k, fn in theirs.items():
                    mine.setdefault(k, fn)
            elif name.endswith("_fn"):
                self.__dict__.setdefault(name, theirs)
        twin._set_tree(dict.fromkeys(twin._tree()))
        twin._serving = [None, None]

    def evict_rows(self, shards, rows) -> None:
        """The GUARDED device-buffer drop of the cold tier (tools/lint.py
        enforces that nothing outside store/coldtier.py calls this
        without an ``# evict-ok:`` note): clear the rows' whole device
        state — head, snapshot versions, op ring — and push them onto the
        per-shard free lists for reuse.  The CALLER owns the correctness
        obligations: the rows' state must be covered by a retained
        checkpoint sidecar, the owning keys unbound from the directory,
        and every live serving epoch told to fall back for them."""
        shards = np.asarray(shards, np.int64)
        rows = np.asarray(rows, np.int64)
        m = len(rows)
        if m == 0:
            return
        self.clear_rows(shards, rows)
        self.n_ops[shards, rows] = 0
        self.slots_ub[shards, rows] = 0
        for s, r in zip(shards.tolist(), rows.tolist()):
            self.free_rows.setdefault(s, []).append(int(r))
        # the cleared rows must not serve from any frozen buffer: the
        # next publish re-freezes them (callers additionally mark the
        # evicted keys promoted on live epochs for the interim)
        self.note_serving_touch(shards, rows)
        # older whole-head epoch copies (the VC-pinned ladder) still hold
        # the evicted bytes; they'd serve them for the row's NEXT tenant
        self.epochs.clear()

    @functools.cached_property
    def _cold_install_fn(self):
        """One-launch cold fault-in / range-heal row install: set the
        head fields + head_vc at (shard, row) pairs and seed ONE snapshot
        version from the installed head (same discipline as
        checkpoint.install_image: versioned reads at clocks ≥ head_vc
        fold the empty ring on this base exactly; reads below surface the
        compaction horizon instead of a silently wrong value)."""
        @device_program("cold_install", donate_argnums=(0,))
        def fn(tree, ss, rr, head_rows, hvc_rows, seqs):
            out = dict(tree)
            out["head"] = {
                f: x.at[ss, rr].set(head_rows[f], mode="drop")
                for f, x in tree["head"].items()
            }
            out["snap"] = {
                f: x.at[ss, rr, 0].set(head_rows[f], mode="drop")
                for f, x in tree["snap"].items()
            }
            out["head_vc"] = tree["head_vc"].at[ss, rr].set(
                hvc_rows, mode="drop")
            out["snap_vc"] = tree["snap_vc"].at[ss, rr, 0].set(
                hvc_rows, mode="drop")
            out["snap_seq"] = tree["snap_seq"].at[ss, rr, 0].set(
                seqs, mode="drop")
            return out

        return fn

    def install_rows(self, shards, rows, head_rows, head_vc_rows) -> None:
        """Install per-row head states (cold-tier fault-in / Merkle range
        heal).  ``head_rows`` maps field -> [M, *field_shape] host
        arrays; the rows must be freshly-allocated or evict-cleared (the
        ring is empty, so the seeded snapshot version is the row's entire
        retained history)."""
        shards = np.asarray(shards, np.int64)
        rows = np.asarray(rows, np.int64)
        m = len(rows)
        if m == 0:
            return
        mb = _bucket(m, self.cfg.batch_buckets)
        pad = mb - m
        ss = np.concatenate([shards, np.full(pad, self.n_shards, np.int64)])
        rr = np.concatenate([rows, np.zeros(pad, np.int64)])
        hr = {}
        for f, x in self.head.items():
            src = np.asarray(head_rows[f])
            buf = np.zeros((mb,) + x.shape[2:], np.dtype(x.dtype))
            buf[:m] = src
            hr[f] = buf
        hvc = np.zeros((mb, self.head_vc.shape[-1]), np.int32)
        hvc[:m] = np.asarray(head_vc_rows, np.int32)
        seqs = np.zeros(mb, np.int64)
        seqs[:m] = np.arange(self.next_seq, self.next_seq + m)
        self.next_seq += m
        tree = {
            "snap": self.snap, "head": self.head,
            "snap_vc": self.snap_vc, "snap_seq": self.snap_seq,
            "head_vc": self.head_vc,
        }
        tree = self._cold_install_fn(tree, ss, rr, hr, hvc, seqs)
        self.snap, self.head = tree["snap"], tree["head"]
        self.snap_vc, self.snap_seq = tree["snap_vc"], tree["snap_seq"]
        self.head_vc = tree["head_vc"]
        self.n_ops[shards, rows] = 0
        np.maximum(self.max_commit_vc,
                   np.asarray(head_vc_rows, np.int32).max(axis=0)
                   if m else self.max_commit_vc,
                   out=self.max_commit_vc)
        self.note_serving_touch(shards, rows)
        self.epochs.clear()

    @functools.cached_property
    def _gather_rows_fn(self):
        """Dispatch-only gather of (head, head_vc) rows — the delta
        checkpoint's capture primitive: launched under the commit-lock
        barrier, materialized outside it."""
        @device_program("ckpt_gather")
        def fn(head, head_vc, staged):
            ss, rr = staged[:, 0], staged[:, 1]
            return ({f: x[ss, rr] for f, x in head.items()},
                    head_vc[ss, rr])

        return fn

    def gather_rows_dispatch(self, shards, rows, head=None, head_vc=None):
        """Launch a (head, head_vc) gather for the given rows — of the
        live head, or of a frozen copy of it handed in; returns
        DEVICE handles padded to a batch bucket (the caller slices to
        the true length after materializing off the lock — padding
        keeps each delta stamp from minting a fresh XLA trace for its
        particular dirty-row count).  The rows cross as one staged
        operand (:meth:`_stage_reads`, no read VC)."""
        staged = self._stage_reads(
            np.minimum(np.asarray(shards, np.int64), self.n_shards - 1),
            np.minimum(np.asarray(rows, np.int64), self.n_rows - 1),
            mb=_bucket(max(len(rows), 1), self.cfg.batch_buckets))
        if head is None:
            head, head_vc = self.head, self.head_vc
        return self._gather_rows_fn(head, head_vc, staged)

    @functools.cached_property
    def _grow_fn(self):
        """Every array of the table with twice the rows (zeros behind the
        old ones), in the table's placement: one program, what it
        reserves is the grown table."""
        def fn(tree):
            return jax.tree.map(
                lambda x: jnp.pad(
                    x, [(0, 0), (0, x.shape[1])] + [(0, 0)] * (x.ndim - 2)),
                tree)

        return device_program("grow", fn, out_shardings=self.sharding)

    def _grow(self):
        new_n = self.n_rows * 2
        self._set_tree(self._grow_fn(self._tree()))
        self.n_ops = np.pad(self.n_ops, ((0, 0), (0, new_n - self.n_rows)))
        self.slots_ub = np.pad(self.slots_ub, ((0, 0), (0, new_n - self.n_rows)))
        self.n_rows = new_n
        self.grows += 1
        # epoch copies still have the old row extent — row indices past it
        # would gather-clip onto the wrong key.  The CHECKPOINT dirty
        # window survives: growth moves no row and changes no content, so
        # the incremental stamp's tracking stays exact (new rows enter it
        # through their first touch)
        ck = self._ckpt_dirty
        self.invalidate_epochs()
        self._ckpt_dirty = ck

    # ------------------------------------------------------------------
    # serving epochs (read-while-write double buffer)
    # ------------------------------------------------------------------
    _EPOCH_CAP = 2

    @functools.cached_property
    def _copy_tree_fn(self):
        return device_program(
            "freeze_serving_copy",
            lambda tree: jax.tree.map(jnp.copy, tree))

    def publish_epoch(self) -> None:
        """Freeze the current head as a serving epoch.

        Correctness contract (the reason an epoch gather is an *exact*
        snapshot read): ``cap`` is the entry-wise max commit VC this table
        has absorbed at publish time.  Appends are causally gated — an op
        from origin ``o`` carries a commit timestamp on lane ``o`` strictly
        above every lane-``o`` value previously appended (local sequencer
        monotonicity; remote chains apply in op-id order behind the causal
        gate, so a cross-origin snapshot entry can never outrun its
        origin's applied ops).  Hence any op appended AFTER publish is
        invisible at any read VC ``R ≤ cap``, and a row whose frozen
        ``head_vc ≤ R`` serves exactly — the double-buffered analogue of
        the reference's lock-free reads against a single writer
        (/root/reference/src/materializer_vnode.erl:93-102)."""
        frozen = self._copy_tree_fn((self.head, self.head_vc))
        self._epoch_uses += 1
        self.epochs.append({
            "head": frozen[0],
            "head_vc": frozen[1],
            "cap": self.max_commit_vc.copy(),
            "seq": self._epoch_uses,   # publish order (age)
            "used": self._epoch_uses,  # recency (eviction only)
        })
        if len(self.epochs) > self._EPOCH_CAP:
            victim = min(self.epochs, key=lambda e: e["used"])
            self.epochs = [e for e in self.epochs if e is not victim]

    def device_bytes(self) -> Dict[int, int]:
        """Bytes of this table's arrays (op rings, snapshot versions,
        head, frozen epochs and serving buffers) resident on each device,
        by device id — from the arrays' own addressable shards, so a
        mesh-placed table shows what each device really holds."""
        frozen = [(e["head"], e["head_vc"]) for e in self.epochs]
        frozen += [s for s in self._serving if s is not None]
        out: Dict[int, int] = {}
        for x in jax.tree.leaves((
            self.snap, self.snap_vc, self.snap_seq, self.ops_a, self.ops_b,
            self.ops_vc, self.ops_origin, self.head, self.head_vc, frozen,
        )):
            if not isinstance(x, jax.Array):
                continue
            try:
                for sh in x.addressable_shards:
                    out[sh.device.id] = (
                        out.get(sh.device.id, 0) + sh.data.nbytes
                    )
            except RuntimeError:
                # donated to a commit that ran while status was read
                # (status takes no lock); its successor is counted next
                continue
        return out

    def invalidate_epochs(self) -> None:
        """Drop every published epoch — required after any out-of-band
        table mutation (row growth, key promotion, handoff install)."""
        self.epochs.clear()
        self.invalidate_serving()

    def _epoch_for(self, read_vcs: np.ndarray):
        """Oldest epoch whose cap dominates every read VC in the batch
        (oldest = closest above the pin = most rows frozen-fresh)."""
        best = None
        for e in self.epochs:
            if (read_vcs <= e["cap"]).all():
                if best is None or e["seq"] < best["seq"]:
                    best = e
        if best is not None:
            self._epoch_uses += 1
            best["used"] = self._epoch_uses
        return best

    # ------------------------------------------------------------------
    # device kernels
    # ------------------------------------------------------------------
    @functools.cached_property
    def _read_fn(self):
        body = _shard_read_body(self.ty, self.cfg)

        @device_program("read_versioned")
        def read(snap, snap_vc, snap_seq, ops_a, ops_b, ops_vc, ops_origin,
                 rows, n_ops_rows, read_vcs):
            return jax.vmap(body)(
                snap, snap_vc, snap_seq, ops_a, ops_b, ops_vc, ops_origin,
                rows, n_ops_rows, read_vcs,
            )

        return read

    def _jit_rows(self, what: str, fn, n_tables: int, n_batch: int,
                  donate: Tuple[int, ...]):
        """jit a row-writing program ``antidote_<what>`` whose first
        ``n_tables`` operands are table pytrees and whose last
        ``n_batch`` are a flat batch (shards, rows, ..., count).  On a
        mesh-placed table the body runs under an explicit ``shard_map``
        over the shard axis with the batch replicated, so each device
        writes the rows of its own shards; the body gets the batch's
        shard indices as they are and calls :meth:`_local` on them."""
        sh = self.sharding
        if sh is not None:
            rep = jax.sharding.PartitionSpec()
            fn = jax.shard_map(
                fn, mesh=sh.mesh,
                in_specs=(sh.spec,) * n_tables + (rep,) * n_batch,
                out_specs=sh.spec, check_vma=False)
        return device_program(what, fn, donate_argnums=donate)

    def _local(self, ss, pl: int):
        """(this device's index of each shard, clipped into range; whether
        the shard is this device's) for a block of ``pl`` shards."""
        sh = self.sharding
        if sh is not None:
            ss = ss - jax.lax.axis_index(sh.spec[0]) * pl
        return jnp.clip(ss, 0, pl - 1), (ss >= 0) & (ss < pl)

    def _pad_rows(self, shards, rows):
        """A flat (shard, row) batch as int32 operands zero-padded to a
        batch bucket; the programs look at the first ``len(rows)``."""
        m = len(rows)
        mb = _bucket(max(m, 1), self.cfg.batch_buckets)
        ss = np.zeros(mb, np.int32)
        rr = np.zeros(mb, np.int32)
        ss[:m] = shards
        rr[:m] = rows
        return ss, rr

    @functools.cached_property
    def _gc_fn(self):
        # GC = copy the head (already the exact fold of the full ring +
        # prior history) into a fresh snapshot version; no fold needed.
        # The rows are read by gathers and written one by one in the
        # tables' own layout (:func:`_write_rows`): what the program
        # reserves and the time it takes follow the rows, not the table.
        def gc(snap, snap_vc, snap_seq, head, head_vc, ss, rr, seqs, count):
            sl, mine = self._local(ss, snap_vc.shape[0])
            at = (sl, rr)
            slot = orddict.insert_slot(snap_seq[at])
            return _write_rows(
                (snap, snap_vc, snap_seq), (sl, slot), rr,
                ({f: x[at] for f, x in head.items()}, head_vc[at], seqs),
                count, mine)

        return self._jit_rows("gc", gc, 5, 4, (0, 1, 2))

    def _commit_scatter_for(self, window: int):
        """The commit group's one device program: unpack the staged
        operand (:meth:`append`), write the effects into the op rings row
        by row (:func:`_write_rows`; padding carries an out-of-range
        shard and writes nothing), then fold
        each touched key's new ring slots onto its head, scanning a
        ``window``-slot slice (0 = the whole ring).  One jitted fn per
        window, compiled per batch bucket.  On a mesh-placed table the
        body runs under an explicit ``shard_map`` over the shard axis,
        the staged operand replicated: each device takes the effects of
        its own shards, and no table is resharded."""
        fn = self._commit_scatter_fns.get(window)
        if fn is None:
            head_update = _head_update_body(self.ty, self.cfg, window)
            aw, bw = self.ops_a.shape[-1], self.ops_b.shape[-1]
            b0 = 5 + 2 * aw
            sh = self.sharding

            def fn(ops_a, ops_b, ops_vc, ops_origin, head, head_vc, staged):
                shards, rows, slots, ends = (staged[:, i] for i in (0, 1, 2, 4))
                pl = ops_a.shape[0]
                # the effects are the operand's first rows; padding
                # carries the table's shard count
                count = jnp.sum(shards < self.n_shards, dtype=jnp.int32)
                if sh is not None:
                    # this device's block of shards: the others' effects
                    # become padding
                    s0 = jax.lax.axis_index(sh.spec[0]) * pl
                    mine = (shards >= s0) & (shards < s0 + pl)
                    shards = jnp.where(mine, shards - s0, pl)
                mine = shards < pl
                sl = jnp.minimum(shards, pl - 1)
                # int64 lanes travel as exact halves
                a = _join_i64(staged[:, 5:b0].reshape(-1, aw, 2))
                ops_a, ops_b, ops_vc, ops_origin = _write_rows(
                    (ops_a, ops_b, ops_vc, ops_origin), (sl, slots), rows,
                    (a, staged[:, b0: b0 + bw], staged[:, b0 + bw:],
                     staged[:, 3]), count, mine)
                # a key's first effect carries the end of the key's span
                # [slot, end); its other effects carry 0 and are padding
                head, head_vc = head_update(
                    head, head_vc, ops_a, ops_b, ops_vc, ops_origin,
                    jnp.where(ends > 0, shards, pl), rows, slots, ends,
                    count,
                )
                return ops_a, ops_b, ops_vc, ops_origin, head, head_vc

            if sh is not None:
                fn = jax.shard_map(
                    fn, mesh=sh.mesh,
                    in_specs=(sh.spec,) * 6 + (jax.sharding.PartitionSpec(),),
                    out_specs=sh.spec, check_vma=False)
            fn = self._commit_scatter_fns[window] = device_program(
                f"commit_scatter_w{window}", fn,
                donate_argnums=(0, 1, 2, 3, 4, 5))
        return fn

    @functools.cached_property
    def _read_latest_fn(self):
        body = _shard_read_latest_body(self.ty, self.cfg)

        @device_program("read_latest")
        def read(head, head_vc, rows, read_vcs):
            return jax.vmap(body)(head, head_vc, rows, read_vcs)

        return read

    def _jit_routed(self, what: str, fn):
        """jit a routed serving read as the device program
        ``antidote_<what>``: every operand and result carries
        the leading shard axis ([P, M', ...]).  On a mesh-placed table
        the body runs under an explicit ``shard_map`` over that axis —
        each device works on its own shards' block, which the vmapped
        per-shard gathers are by construction — because Mosaic kernels
        (the fold strategies, set_aw's presence resolve) cannot be
        partitioned automatically."""
        sh = self.sharding
        if sh is not None:
            fn = jax.shard_map(fn, mesh=sh.mesh, in_specs=sh.spec,
                               out_specs=sh.spec, check_vma=False)
        return device_program(what, fn)

    def set_sharding(self, sharding) -> None:
        """Adopt a new placement (the mesh plane's ``place_table``): the
        routed read programs are built for the placement they were
        traced under, so they are dropped with the old one."""
        self.sharding = sharding
        self._resolved_fns.clear()
        self._commit_scatter_fns.clear()
        for fn in ("_latest_resolved_fn", "_gc_fn", "_clear_rows_fn",
                   "_freeze_scatter_fn", "_install_row_fn", "_grow_fn"):
            self.__dict__.pop(fn, None)

    @functools.cached_property
    def _latest_resolved_fn(self):
        """Fold-free serving read for read VCs that dominate every commit
        this table has seen (host-decided via ``max_commit_vc``): head
        gather + device value resolution only."""
        ty, cfg = self.ty, self.cfg
        latest = _shard_read_latest_body(ty, cfg)

        def fn(head, head_vc, rows, read_vcs):
            state, fresh = jax.vmap(latest)(head, head_vc, rows, read_vcs)
            resolved = (
                ty.resolve(cfg, state)
                if ty.resolve_spec(cfg) is not None
                else state
            )
            return resolved, fresh

        return self._jit_routed("head_gather_routed", fn)

    def _read_resolved_fn(self, strategy: str, kmax: int = 0):
        """The fused serving read: head gather + snapshot-version select +
        versioned ring fold + freshness select + device value resolution,
        all in ONE launch — the whole read path of SURVEY §3.3
        (check-freshness ≈ check_clock, fold ≈ clocksi_materializer:
        materialize, resolution ≈ Type:value) without intermediate host
        round trips.  ``strategy`` (from :meth:`_fold_strategy`) picks the
        ring fold: ``pallas_counter``/``pallas_set_aw`` dispatch the fused
        Pallas kernels (VERDICT r1 item 3; this PR puts the BASELINE
        workload's own fold on a kernel), ``assoc`` the O(log K) monoid
        reduction (materializer/longlog.py), ``serial`` the masked scan.
        ``kmax`` > 0 folds only ring slots [0, kmax) — valid whenever the
        host-tracked ``n_ops`` max over the batch is ≤ kmax (rings fill
        from 0 and reset at GC), cutting fold work from ops_per_key to the
        actual used prefix (r4 VERDICT item 4)."""
        cached = self._resolved_fns.get((strategy, kmax))
        if cached is not None:
            return cached
        ty, cfg = self.ty, self.cfg
        latest = _shard_read_latest_body(ty, cfg)
        select = _shard_base_select_body(ty, cfg)

        def fn(head, head_vc, snap, snap_vc, snap_seq,
               ops_a, ops_b, ops_vc, ops_origin, rows, n_ops_rows, read_vcs):
            state_h, fresh = jax.vmap(latest)(head, head_vc, rows, read_vcs)
            base_state, base_vc, complete = jax.vmap(select)(
                snap, snap_vc, snap_seq, rows, read_vcs
            )
            if kmax:
                # slice the fold to the used ring prefix AFTER the row
                # gather (fuses; never materializes a sliced table copy)
                gat = jax.vmap(lambda x, r: x[r, :kmax])
            else:
                gat = jax.vmap(lambda x, r: x[r])
            opa, opv = gat(ops_a, rows), gat(ops_vc, rows)
            if strategy == "pallas_counter":
                from antidote_tpu.materializer import pallas_kernels as pk

                p, m = rows.shape
                k, d = opv.shape[2], opv.shape[3]
                dcnt, applied = pk.counter_fold_deltas(
                    opa[..., 0].reshape(p * m, k),
                    opv.reshape(p * m, k, d),
                    n_ops_rows.reshape(p * m),
                    base_vc.reshape(p * m, d),
                    read_vcs.reshape(p * m, d),
                )
                state_f = {
                    "cnt": base_state["cnt"]
                    + dcnt.astype(jnp.int64).reshape(p, m)
                }
                applied = applied.reshape(p, m)
            elif strategy == "pallas_set_aw":
                from antidote_tpu.materializer import pallas_kernels as pk

                p, m = rows.shape
                opb, opo = gat(ops_b, rows), gat(ops_origin, rows)
                flat = lambda x: x.reshape((p * m,) + x.shape[2:])
                state_pm, applied = pk.set_aw_fold(
                    {f: flat(x) for f, x in base_state.items()},
                    flat(opa), flat(opb), flat(opv), flat(opo),
                    n_ops_rows.reshape(p * m),
                    base_vc.reshape(p * m, -1), read_vcs.reshape(p * m, -1),
                )
                state_f = {
                    f: x.reshape((p, m) + x.shape[1:])
                    for f, x in state_pm.items()
                }
                applied = applied.reshape(p, m)
            elif strategy == "assoc":
                opb, opo = gat(ops_b, rows), gat(ops_origin, rows)
                state_f, applied = jax.vmap(jax.vmap(
                    lambda s, a, b, v, o, n, bv, rv: longlog.assoc_fold(
                        ty, cfg, s, a, b, v, o, n, bv, rv
                    )
                ))(base_state, opa, opb, opv, opo, n_ops_rows, base_vc,
                   read_vcs)
            else:
                opb, opo = gat(ops_b, rows), gat(ops_origin, rows)
                state_f, applied = jax.vmap(
                    lambda s, a, b, v, o, n, bv, rv: fold_mod.fold_batch(
                        ty, cfg, s, a, b, v, o, n, bv, rv
                    )
                )(base_state, opa, opb, opv, opo, n_ops_rows, base_vc, read_vcs)
            state = {
                f: jnp.where(
                    fresh.reshape(fresh.shape + (1,) * (x.ndim - 2)),
                    state_h[f], x,
                )
                for f, x in state_f.items()
            }
            complete = complete | fresh
            resolved = (
                ty.resolve(cfg, state)
                if ty.resolve_spec(cfg) is not None
                else state
            )
            return resolved, fresh, complete

        fn = self._jit_routed(f"read_resolved_{strategy}_k{kmax}", fn)
        self._resolved_fns[(strategy, kmax)] = fn
        return fn

    @functools.cached_property
    def _latest_resolved_flat_fn(self):
        """Flat single-gather variant of :meth:`_latest_resolved_fn` —
        no [P, M'] routing: index the tables by (shard, row) pairs in one
        advanced-indexing gather.  Serving hot path on a single device;
        mesh-sharded tables keep the routed layout (a flat gather across
        the sharded axis would induce collectives).  The batch is one
        staged operand (:meth:`_stage_reads`)."""
        ty, cfg = self.ty, self.cfg

        @device_program("head_gather")
        def fn(head, head_vc, staged):
            ss, rr, read_vcs = _unstage_reads(staged, 2)
            hvc = head_vc[ss, rr]
            state = {f: x[ss, rr] for f, x in head.items()}
            fresh = jnp.all(hvc <= read_vcs, axis=-1)
            resolved = (
                ty.resolve(cfg, state)
                if ty.resolve_spec(cfg) is not None
                else state
            )
            return resolved, fresh

        return fn

    @functools.cached_property
    def _head_state_flat_fn(self):
        """Flat gather of whole head states and their freshness
        (:meth:`read_latest` of a one-device table), the batch one staged
        operand (:meth:`_stage_reads`)."""
        @device_program("head_state")
        def fn(head, head_vc, staged):
            ss, rr, read_vcs = _unstage_reads(staged, 2)
            fresh = jnp.all(head_vc[ss, rr] <= read_vcs, axis=-1)
            return {f: x[ss, rr] for f, x in head.items()}, fresh

        return fn

    def _pad_reads(self, shards, rows, read_vcs):
        """A flat read batch padded to a batch bucket with copies of its
        last row: the flat programs compile once a bucket."""
        m = len(rows)
        pad = _bucket(m, self.cfg.batch_buckets) - m if m else 0
        if pad:
            shards = np.concatenate([shards, np.repeat(shards[-1:], pad)])
            rows = np.concatenate([rows, np.repeat(rows[-1:], pad)])
            read_vcs = np.concatenate(
                [read_vcs, np.repeat(read_vcs[-1:], pad, axis=0)])
        return shards, rows, read_vcs

    def _stage_reads(self, shards, rows, read_vcs=None, n_ops=None,
                     mb=None):
        """The one host operand of a flat read program of a one-device
        table (and of the checkpoint's gather), so that the batch crosses
        to the device once: int32 [``mb``, cols], one row a read — its
        shard, its row, for a program that folds the row's used ring
        prefix (``n_ops``), then the read VC's lanes (``read_vcs`` [M,
        D], or one [D] for every read) — and zeros after the first
        ``len(rows)`` rows (``mb`` None: no more rows).  int32 holds each
        (shards and rows < 2**31).  A buffer of its own for every launch,
        as for :meth:`append`."""
        m = len(rows)
        lanes = (shards, rows) if n_ops is None else (shards, rows, n_ops)
        d = 0 if read_vcs is None else np.shape(read_vcs)[-1]
        staged = np.zeros((m if mb is None else mb, len(lanes) + d),
                          np.int32)
        for i, x in enumerate(lanes):
            staged[:m, i] = x
        if d:
            staged[:m, len(lanes):] = read_vcs
        return staged

    def _read_resolved_flat_fn(self, strategy: str, kmax: int = 0):
        """Flat single-gather variant of :meth:`_read_resolved_fn`: the
        same fused serving read (freshness + version select + ring fold +
        resolution, one launch) with the batch as the leading axis — the
        per-shard bodies run on pre-gathered rows via an identity index.
        ``strategy``/``kmax`` as in :meth:`_read_resolved_fn`.  Returns
        (resolved, fresh, complete, state, applied): the materialized
        state and the count of ops applied ride along on the device for
        :meth:`read`, which would otherwise need a program of its own
        (25 s of compile at the served widths, on first use in a
        transaction's read of a set over ``resolve_top``).  The batch is
        one staged operand (:meth:`_stage_reads` with ``n_ops``)."""
        cached = self._resolved_flat_fns.get((strategy, kmax))
        if cached is not None:
            return cached
        ty, cfg = self.ty, self.cfg
        select = _shard_base_select_body(ty, cfg)

        @device_program(f"read_resolved_{strategy}_k{kmax}_flat")
        def fn(head, head_vc, snap, snap_vc, snap_seq,
               ops_a, ops_b, ops_vc, ops_origin, staged):
            ss, rr, n_ops_flat, read_vcs = _unstage_reads(staged, 3)
            m = ss.shape[0]
            idx = jnp.arange(m)
            hvc = head_vc[ss, rr]
            state_h = {f: x[ss, rr] for f, x in head.items()}
            fresh = jnp.all(hvc <= read_vcs, axis=-1)
            base_state, base_vc, complete = select(
                {f: x[ss, rr] for f, x in snap.items()},
                snap_vc[ss, rr], snap_seq[ss, rr], idx, read_vcs,
            )
            if kmax:
                opa = ops_a[ss, rr][:, :kmax]
                opv = ops_vc[ss, rr][:, :kmax]
            else:
                opa, opv = ops_a[ss, rr], ops_vc[ss, rr]
            if strategy == "pallas_counter":
                from antidote_tpu.materializer import pallas_kernels as pk

                dcnt, applied = pk.counter_fold_deltas(
                    opa[..., 0], opv, n_ops_flat, base_vc, read_vcs,
                )
                state_f = {"cnt": base_state["cnt"] + dcnt.astype(jnp.int64)}
            elif strategy == "pallas_set_aw":
                from antidote_tpu.materializer import pallas_kernels as pk

                opb, opo = ops_b[ss, rr], ops_origin[ss, rr]
                if kmax:
                    opb, opo = opb[:, :kmax], opo[:, :kmax]
                state_f, applied = pk.set_aw_fold(
                    base_state, opa, opb, opv, opo,
                    n_ops_flat, base_vc, read_vcs,
                )
            elif strategy == "assoc":
                opb, opo = ops_b[ss, rr], ops_origin[ss, rr]
                if kmax:
                    opb, opo = opb[:, :kmax], opo[:, :kmax]
                state_f, applied = jax.vmap(
                    lambda s, a, b, v, o, n, bv, rv: longlog.assoc_fold(
                        ty, cfg, s, a, b, v, o, n, bv, rv
                    )
                )(base_state, opa, opb, opv, opo, n_ops_flat, base_vc,
                  read_vcs)
            else:
                opb, opo = ops_b[ss, rr], ops_origin[ss, rr]
                if kmax:
                    opb, opo = opb[:, :kmax], opo[:, :kmax]
                state_f, applied = fold_mod.fold_batch(
                    ty, cfg, base_state, opa, opb, opv,
                    opo, n_ops_flat, base_vc, read_vcs,
                )
            state = {
                f: jnp.where(
                    fresh.reshape(fresh.shape + (1,) * (x.ndim - 1)),
                    state_h[f], x,
                )
                for f, x in state_f.items()
            }
            complete = complete | fresh
            resolved = (
                ty.resolve(cfg, state)
                if ty.resolve_spec(cfg) is not None
                else state
            )
            return resolved, fresh, complete, state, applied

        self._resolved_flat_fns[(strategy, kmax)] = fn
        return fn

    @functools.cached_property
    def _merge_scatter_fn(self):
        @device_program("merge_scatter")
        def fn(dst_tree, idx, src_tree):
            return jax.tree.map(
                lambda d, s: d.at[idx].set(s, mode="drop"), dst_tree, src_tree
            )

        return fn

    def _kmax_bucket(self, n: int) -> int:
        """Power-of-4 fold-window bucket covering ``n`` used ring slots
        (0 = fold the whole ring).  Coarse on purpose: every distinct
        kmax is a separate XLA compile of the whole serve path, and on a
        small host a compile is a multi-second serving outage — fewer,
        slightly-wider folds beat a tight ladder."""
        w = 4
        while w < n:
            w *= 4
        return 0 if w >= self.cfg.ops_per_key else w

    def read_resolved_flat(self, shards, rows, read_vcs, n_real=None):
        """Flat serving read — no host routing, no unroute: returns
        (resolved fields [M, ...], fresh [M], complete [M]) in input
        order (device arrays on the all-gather paths, the fold path
        merges on device but returns host fresh/complete).  The
        single-device fast path; callers on a mesh use
        :meth:`read_resolved_raw` (routed layout keeps gathers
        shard-local).  ``n_real``: how many of the batch's first rows
        are reads (the rest pad it to a bucket), for the read counters.

        Dispatch ladder (r4 VERDICT item 2 — reads must not collapse
        under a concurrent write stream):

        1. read VC dominates every commit seen → live head gather.
        2. read VC pinned exactly at a published epoch's cap → frozen
           head gather (the double-buffer hot path: writers advance the
           live head, pinned readers never see them).
        3. otherwise two-phase: gather (frozen epoch if one covers the
           VC, else live head), host-check freshness, and run the
           versioned ring fold ONLY on the stale remainder — fold work
           scales with the write working set, not the read batch.
        """
        shards = np.asarray(shards, np.int64)
        rows = np.asarray(rows, np.int64)
        read_vcs = np.asarray(read_vcs, np.int32)
        # each way down the ladder launches one head gather of the batch
        staged = self._stage_reads(shards, rows, read_vcs)
        if (read_vcs >= self.max_commit_vc).all():
            resolved, fresh = self._latest_resolved_flat_fn(
                self.head, self.head_vc, staged
            )
            return resolved, fresh, fresh
        epoch = self._epoch_for(read_vcs)
        if epoch is not None and (read_vcs >= epoch["cap"]).all():
            # pinned exactly at the epoch cap: every row frozen-fresh
            # (head_vc ≤ cap = R row-wise) — pure gather, no host sync
            resolved, fresh = self._latest_resolved_flat_fn(
                epoch["head"], epoch["head_vc"], staged
            )
            return resolved, fresh, fresh
        self.slow_serves += 1
        if epoch is not None:
            src_head, src_vc = epoch["head"], epoch["head_vc"]
        else:
            src_head, src_vc = self.head, self.head_vc
        resolved_h, fresh_d = self._latest_resolved_flat_fn(
            src_head, src_vc, staged
        )
        fresh = np.asarray(fresh_d)
        stale = np.nonzero(~fresh)[0]
        ns = len(stale)
        n_real = len(fresh) if n_real is None else n_real
        real_stale = int((stale < n_real).sum())
        self.reads_by_head += n_real - real_stale
        self.reads_by_fold += real_stale
        if ns == 0:
            return resolved_h, fresh, fresh
        mb = _bucket(ns, self.cfg.batch_buckets)
        pad = mb - ns
        sss, rrs = shards[stale], rows[stale]
        n_ops_flat = self.n_ops[sss, rrs]
        kmax = self._kmax_bucket(int(n_ops_flat.max()))
        strategy = self._fold_strategy()
        self._count_dispatch(strategy)
        fn = self._read_resolved_flat_fn(strategy, kmax)
        t0 = time.monotonic()
        with span("serve.fold", rows=ns, bucket=mb):
            # padding: row (0, 0) at VC 0 with an empty ring
            resolved_s, _, complete_s, _, _ = fn(
                self.head, self.head_vc, self.snap, self.snap_vc,
                self.snap_seq, self.ops_a, self.ops_b, self.ops_vc,
                self.ops_origin,
                self._stage_reads(sss, rrs, read_vcs[stale], n_ops_flat, mb),
            )
            # scatter the folded rows back over the gathered batch on
            # device (padding scatters at index M → dropped)
            midx = np.concatenate(
                [stale, np.full(pad, len(shards), np.int64)])
            merged = self._merge_scatter_fn(resolved_h, midx, resolved_s)
            complete = fresh.copy()
            complete[stale] = np.asarray(complete_s)[:ns]
        self.fold_launches += 1
        self.fold_rows += ns
        self.fold_seconds += time.monotonic() - t0
        return merged, fresh, complete

    # ------------------------------------------------------------------
    # host routing helpers
    # ------------------------------------------------------------------
    def _route(self, shards, rows):
        """Group a flat (shard, row) batch into padded [P, M'] blocks.

        Returns (row_mat i64[P, M'], pos — list of (shard, slot) per input).
        Padding rows use index ``n_rows`` (dropped/clipped on device).
        """
        p = self.n_shards
        mtot = len(shards)
        counts = np.bincount(shards, minlength=p)
        m = _bucket(max(int(counts.max()), 1), self.cfg.batch_buckets)
        order = np.argsort(shards, kind="stable")
        sorted_shards = shards[order]
        starts = np.searchsorted(sorted_shards, np.arange(p))
        slot_in_shard = np.arange(mtot) - starts[sorted_shards]
        row_mat = np.full((p, m), self.n_rows, np.int64)
        row_mat[sorted_shards, slot_in_shard] = rows[order]
        pos = np.empty((mtot, 2), np.int64)
        pos[order, 0] = sorted_shards
        pos[order, 1] = slot_in_shard
        return row_mat, pos

    # ------------------------------------------------------------------
    # host API (flat batches)
    # ------------------------------------------------------------------
    def append(self, shards, rows, eff_a, eff_b, vcs, origins):
        """Append a commit-ordered batch of effects.

        ``shards`` i64[M]; ``rows`` i64[M]; ``eff_a`` [M, A]; ``eff_b``
        [M, B]; ``vcs`` [M, D]; ``origins`` [M].  Ring overflow triggers a
        GC fold of the affected keys first.  The batch crosses to the
        device once: one staged int32 operand [batch bucket,
        ``_staged_cols``], one row an effect — a layout of the table's
        static widths and the bucket alone — and one program
        (:meth:`_commit_scatter_for`).
        """
        shards = np.asarray(shards, np.int64)
        rows = np.asarray(rows, np.int64)
        m = len(rows)
        if m == 0:
            return
        eff_a = np.ascontiguousarray(eff_a, np.int64)
        eff_b = np.asarray(eff_b, np.int32)
        vcs = np.asarray(vcs, np.int32)
        origins = np.asarray(origins, np.int32)
        k = self.cfg.ops_per_key
        # group the batch by (shard, row), keeping commit order inside a
        # key: ``first`` indexes each distinct key's first effect (keys in
        # (shard, row) order), ``occ`` is an effect's occurrence index
        # within its key
        combined = shards * np.int64(self.n_rows) + rows
        order = np.argsort(combined, kind="stable")
        sorted_c = combined[order]
        new_key = np.ones(m, bool)
        np.not_equal(sorted_c[1:], sorted_c[:-1], out=new_key[1:])
        group_start = np.flatnonzero(new_key)
        occ = np.empty(m, np.int64)
        occ[order] = np.arange(m) - group_start[np.cumsum(new_key) - 1]
        slots = self.n_ops[shards, rows] + occ
        over = slots >= k
        if over.any():
            su, ru = shards[over], rows[over]
            uniq = np.unique(np.stack([su, ru], axis=1), axis=0)
            self.gc(uniq[:, 0], uniq[:, 1])
            slots = self.n_ops[shards, rows] + occ
            if (slots >= k).any():
                # a single batch carries more ops for one key than the
                # ring holds (e.g. one txn add_all of 3x ops_per_key):
                # split by per-key occurrence so each sub-batch fits, with
                # a GC fold between them — per-key commit order preserved
                chunk = occ // k
                for c in range(int(chunk.max()) + 1):
                    sel = chunk == c
                    self.append(shards[sel], rows[sel], eff_a[sel],
                                eff_b[sel], vcs[sel], origins[sel])
                return
        if eff_a.shape[1] > 0:
            self.max_abs_delta = max(
                self.max_abs_delta, int(np.abs(eff_a[:, 0]).max())
            )
        np.maximum(self.max_commit_vc, vcs.max(axis=0), out=self.max_commit_vc)
        # each touched key once (its first effect), and the end of the
        # ring span its effects take
        first = order[group_start]
        us, ur = shards[first], rows[first]
        ends = slots[first]
        ends[:-1] += group_start[1:] - group_start[:-1]
        ends[-1] += m - group_start[-1]
        # a buffer of its own for every group: the runtime may still read
        # a NumPy operand after the jitted call returned (the CPU
        # backend does), so one reused across groups would be overwritten
        # under a transfer
        staged = np.zeros(
            (_bucket(m, self.cfg.batch_buckets), self._staged_cols), np.int32)
        b0 = 5 + 2 * eff_a.shape[1]
        staged[:m, 0] = shards
        staged[m:, 0] = self.n_shards  # padding: out of range, dropped
        staged[:m, 1] = rows
        staged[:m, 2] = slots
        staged[:m, 3] = origins
        staged[first, 4] = ends
        staged[:m, 5:b0] = eff_a.view(np.int32)  # a lane's lo, hi halves
        staged[:m, b0: b0 + eff_b.shape[1]] = eff_b
        staged[:m, b0 + eff_b.shape[1]:] = vcs
        # window choice is deliberately binary (1-op commits vs full-ring
        # scan): each window is a separate XLA compile of the head fold,
        # and compile outages cost more than the extra masked slots
        window = 1 if len(first) == m and k > 1 else 0
        (self.ops_a, self.ops_b, self.ops_vc, self.ops_origin,
         self.head, self.head_vc) = self._commit_scatter_for(window)(
            self.ops_a, self.ops_b, self.ops_vc, self.ops_origin,
            self.head, self.head_vc, staged,
        )
        self.scatter_transfers += 1
        self.scatter_launches += 1
        self.n_ops[us, ur] = ends
        self.note_serving_touch(us, ur)

    def gc(self, shards, rows):
        """Fold the given keys' rings into a fresh snapshot version."""
        shards = np.asarray(shards, np.int64)
        rows = np.asarray(rows, np.int64)
        if len(rows) == 0:
            return
        count = len(rows)
        ss, rr = self._pad_rows(shards, rows)
        seqs = np.zeros(len(rr), np.int64)
        seqs[:count] = np.arange(self.next_seq, self.next_seq + count)
        self.next_seq += count
        t0 = time.monotonic()
        with span("commit.gc", rows=count):
            self.snap, self.snap_vc, self.snap_seq = self._gc_fn(
                self.snap, self.snap_vc, self.snap_seq,
                self.head, self.head_vc, ss, rr, seqs, np.int32(count),
            )
        self.gc_launches += 1
        self.gc_rows += count
        self.gc_seconds += time.monotonic() - t0
        self.n_ops[shards, rows] = 0

    def read_latest(
        self, shards, rows, read_vcs
    ) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
        """Fast path: gather head states.  Returns (state fields [M, ...],
        fresh [M]).  A row is fresh iff head_vc ≤ its read VC — then the
        head IS the exact snapshot.  Stale rows must use :meth:`read`."""
        shards = np.asarray(shards, np.int64)
        rows = np.asarray(rows, np.int64)
        read_vcs = np.asarray(read_vcs, np.int32)
        m = len(rows)
        if self.sharding is None and m:
            # one device: a flat gather of the batch's bucket, cut to the
            # batch before it crosses to the host (the routed [P, M']
            # form moves P x M' states for one: 76 MB at tier 3).
            # Copies: callers patch stale rows into them
            state, fresh = self._head_state_flat_fn(
                self.head, self.head_vc,
                self._stage_reads(*self._pad_reads(shards, rows, read_vcs)))
            return _cut((state, fresh), m)
        row_mat, pos = self._route(shards, rows)
        p, mm = row_mat.shape
        vc_mat = np.zeros((p, mm, read_vcs.shape[-1]), np.int32)
        vc_mat[pos[:, 0], pos[:, 1]] = read_vcs
        row_gather = np.minimum(row_mat, self.n_rows - 1)
        state, fresh = self._read_latest_fn(
            self.head, self.head_vc, row_gather, vc_mat
        )
        s, j = pos[:, 0], pos[:, 1]
        out = {f: np.asarray(x)[s, j] for f, x in state.items()}
        return out, np.asarray(fresh)[s, j]

    @staticmethod
    def _pallas_platform_ok() -> bool:
        """Pallas strategies need a real TPU backend to pay off — on CPU
        the interpreter-mode kernels regress serve ~2x and mixed load
        ~16x (see pallas_kernels.in_path_ok, which also honors the
        ANTIDOTE_PALLAS_INTERPRET=1 parity-test escape)."""
        from antidote_tpu.materializer import pallas_kernels as pk

        return pk.in_path_ok()

    def _pallas_counter_ok(self) -> bool:
        return (
            getattr(self.cfg, "use_pallas", False)
            and self.ty.name == "counter_pn"
            and self.max_abs_delta
            <= (2**31 - 1) // max(self.cfg.ops_per_key, 1)
        )

    def _fold_strategy(self) -> str:
        """Pick the ring fold for the serving read's stale remainder.

        Pallas kernels first (TPU-gated — see ``_pallas_platform_ok``;
        counter masked-sum when the i32 bound holds; the set_aw add-wins
        fold — the BASELINE workload — needs no bound, it has no sums),
        then the O(log K) assoc reduction for monoid types whose delta
        is exact from an ARBITRARY base (counter without the kernel,
        flags; sets are bottom-only — see
        crdt/base.py::assoc_bottom_only), serial masked scan as fallback.
        """
        if self._pallas_counter_ok() and self._pallas_platform_ok():
            return "pallas_counter"
        if (
            getattr(self.cfg, "use_pallas", False)
            and self.ty.name == "set_aw"
            and self._pallas_platform_ok()
        ):
            return "pallas_set_aw"
        if self.ty.supports_assoc and not self.ty.assoc_bottom_only:
            return "assoc"
        return "serial"

    def _count_dispatch(self, strategy: str, n: int = 1):
        self.fold_dispatches[strategy] = (
            self.fold_dispatches.get(strategy, 0) + n
        )
        m = getattr(self.metrics, "fold_dispatch", None)
        if m is not None:
            m.inc(n, strategy=strategy)

    def read_resolved_raw(self, shards, rows, read_vcs):
        """One-launch serving read; returns DEVICE arrays still in routed
        [P, M'] layout plus the (shard, slot) positions — callers that
        pipeline batches fetch/unroute later (``copy_to_host_async``).

        Output: (resolved fields or full state [P, M', ...], fresh
        [P, M'], complete [P, M'], pos [M, 2]).
        """
        shards = np.asarray(shards, np.int64)
        rows = np.asarray(rows, np.int64)
        read_vcs = np.asarray(read_vcs, np.int32)
        row_mat, pos = self._route(shards, rows)
        p, mm = row_mat.shape
        row_gather = np.minimum(row_mat, self.n_rows - 1)
        vc_mat = np.zeros((p, mm, read_vcs.shape[-1]), np.int32)
        vc_mat[pos[:, 0], pos[:, 1]] = read_vcs
        if (read_vcs >= self.max_commit_vc).all():
            # every row is provably fresh: skip the versioned fold
            resolved, fresh = self._latest_resolved_fn(
                self.head, self.head_vc, row_gather, vc_mat
            )
            return resolved, fresh, fresh, pos
        n_ops_mat = self.n_ops[np.arange(p)[:, None], row_gather]
        n_ops_mat = np.where(row_mat < self.n_rows, n_ops_mat, 0)
        kmax = self._kmax_bucket(int(n_ops_mat.max()) if n_ops_mat.size else 1)
        strategy = self._fold_strategy()
        self._count_dispatch(strategy)
        fn = self._read_resolved_fn(strategy, kmax)
        with span("serve.fold", rows=len(rows), bucket=p * mm):
            resolved, fresh, complete = fn(
                self.head, self.head_vc, self.snap, self.snap_vc,
                self.snap_seq, self.ops_a, self.ops_b, self.ops_vc,
                self.ops_origin, row_gather, n_ops_mat, vc_mat,
            )
        self.fold_launches += 1
        self.fold_rows += len(rows)
        return resolved, fresh, complete, pos

    def read_resolved(self, shards, rows, read_vcs):
        """Serving read with device value resolution, one launch, flat
        output.  Returns (resolved fields [M, ...], fresh [M], complete
        [M]).  For types without ``resolve_spec`` the fields are the full
        materialized state.  Incomplete rows (read VC below retained device
        coverage) need the caller's log-replay fallback, as with
        :meth:`read`.

        Single-device tables serve through the flat path (one gather, no
        [P, M'] routing/unrouting); mesh-sharded tables keep the routed
        layout so gathers stay shard-local."""
        if self.sharding is None:
            # padded to a batch bucket with copies of the last row (same
            # freshness, so the dispatch ladder decides as for the batch
            # itself): the flat programs compile once a bucket, not once
            # a batch size — on the chip each compile is most of a second
            # of the locked worker's time, and this is the path reads
            # take while a publish is put off
            shards = np.asarray(shards, np.int64)
            rows = np.asarray(rows, np.int64)
            read_vcs = np.asarray(read_vcs, np.int32)
            m = len(rows)
            shards, rows, read_vcs = self._pad_reads(shards, rows, read_vcs)
            resolved, fresh, complete = self.read_resolved_flat(
                shards, rows, read_vcs, n_real=m
            )
            return _cut((resolved, fresh, complete), m)
        launches = self.fold_launches
        resolved, fresh, complete, pos = self.read_resolved_raw(
            shards, rows, read_vcs
        )
        s, j = pos[:, 0], pos[:, 1]
        out = {f: np.asarray(x)[s, j] for f, x in resolved.items()}
        fresh = np.asarray(fresh)[s, j]
        if self.fold_launches > launches:
            # the routed launch folds every row; the head answers the
            # fresh ones all the same
            self.reads_by_head += int(fresh.sum())
            self.reads_by_fold += int((~fresh).sum())
        return out, fresh, np.asarray(complete)[s, j]

    def read(self, shards, rows, read_vcs) -> Tuple[Dict[str, np.ndarray], np.ndarray, np.ndarray]:
        """Materialize a flat batch of keys at per-key read VCs.

        Returns host copies (state fields [M, ...], n_applied [M],
        complete [M]).  Incomplete rows need a log-replay fallback.
        """
        shards = np.asarray(shards, np.int64)
        rows = np.asarray(rows, np.int64)
        read_vcs = np.asarray(read_vcs, np.int32)
        m = len(rows)
        if self.sharding is None and m:
            # one device: the flat versioned read on the batch's bucket
            # (see read_latest)
            ss, rr, vcs = self._pad_reads(shards, rows, read_vcs)
            n_ops_flat = self.n_ops[ss, rr]
            strategy = self._fold_strategy()
            self._count_dispatch(strategy)
            t0 = time.monotonic()
            with span("serve.fold", rows=m, bucket=len(rr)):
                _, _, complete, state, applied = self._read_resolved_flat_fn(
                    strategy, self._kmax_bucket(int(n_ops_flat.max())))(
                    self.head, self.head_vc, self.snap, self.snap_vc,
                    self.snap_seq, self.ops_a, self.ops_b, self.ops_vc,
                    self.ops_origin,
                    self._stage_reads(ss, rr, vcs, n_ops_flat))
                out = _cut((state, applied, complete), m)
            self.fold_launches += 1
            self.fold_rows += m
            self.fold_seconds += time.monotonic() - t0
            return out
        row_mat, pos = self._route(shards, rows)
        p, mm = row_mat.shape
        # clip padding rows for the gather path
        row_gather = np.minimum(row_mat, self.n_rows - 1)
        n_ops_mat = self.n_ops[np.arange(p)[:, None], row_gather]
        n_ops_mat = np.where(row_mat < self.n_rows, n_ops_mat, 0)
        vc_mat = np.zeros((p, mm, read_vcs.shape[-1]), np.int32)
        vc_mat[pos[:, 0], pos[:, 1]] = read_vcs
        t0 = time.monotonic()
        with span("serve.fold", rows=m, bucket=p * mm):
            state, applied, complete = self._read_fn(
                self.snap, self.snap_vc, self.snap_seq,
                self.ops_a, self.ops_b, self.ops_vc, self.ops_origin,
                row_gather, n_ops_mat, vc_mat,
            )
            s, j = pos[:, 0], pos[:, 1]
            out = {f: np.asarray(x)[s, j] for f, x in state.items()}
        self.fold_launches += 1
        self.fold_rows += m
        self.fold_seconds += time.monotonic() - t0
        return out, np.asarray(applied)[s, j], np.asarray(complete)[s, j]
