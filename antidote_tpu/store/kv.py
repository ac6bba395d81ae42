"""KVStore — the sharded object store over per-type device tables.

Combines the roles of the reference's ``log_utilities`` key→partition map
(/root/reference/src/log_utilities.erl:59-118), the per-partition
``materializer_vnode`` caches, and the partition clock bookkeeping that
feeds the stable snapshot (/root/reference/src/inter_dc_dep_vnode.erl:205-232).

One KVStore instance is one replica ("DC"): it owns all shards locally.
Keys are ``(key, bucket)`` pairs bound to a CRDT type on first use, exactly
like Antidote's ``{Key, Type, Bucket}`` bound objects.
"""

from __future__ import annotations

import atexit
import collections
import functools
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from antidote_tpu.config import AntidoteConfig
from antidote_tpu.crdt import get_type, is_type
from antidote_tpu.crdt.blob import BlobStore
from antidote_tpu.obs.trace import device_program, span
from antidote_tpu.store.router import shard_batch, shard_of
from antidote_tpu.store.typed_table import (TypedTable, _bucket, _cut,
                                            _join_i64)

BoundObject = Tuple[Any, str, str]  # (key, type_name, bucket)

# ---------------------------------------------------------------------------
# slot tiers — the overflow escape hatch
#
# The reference's slotted types (sets, maps, mv-register, rga) are
# unbounded; fixed device layouts are not.  A key that outgrows its slot
# budget is PROMOTED to a wider-slot sibling table (slot widths x4 per
# tier) BEFORE any op would be dropped (SURVEY §7 "slotted layouts +
# overflow-to-host escape hatch", matching unbounded antidote_crdt_set_aw
# semantics).  The tier rides in the table name ("set_aw#2"), so the
# directory entry shape, handoff packages and reshard stay unchanged.
# ---------------------------------------------------------------------------
_TIER_SCALE = 4
_MAX_TIER = 8  # 4^8 = 65536x the base slot width
#: rows a shard of a tier table starts with: sized by demand — under a
#: Zipf mix a few dozen keys of a million outgrow the base slots, fewer
#: each tier after — not by the base table's key count (a tier's row is
#: 4x its predecessor's, so a tier as long as the base table would be as
#: large as it).  A table that fills doubles (TypedTable._grow).
_TIER_ROWS = (32, 16, 8)


#: tier tables to build ahead of need (KVStore.prepare_tier), and the one
#: thread of the process that builds them, one after the other: a compile
#: uses every core, and two at once (two tiers, or two stores of a cluster
#: in one process) would only slow the commit groups beside them.  The
#: interpreter must not be torn down under a compile: at exit the thread
#: is told to stop after the program it is at, and waited for.
_BUILDS: "collections.deque" = collections.deque()
_BUILD_LOCK = threading.Lock()
_BUILDER: List[Any] = []          # the live builder thread, if any
_EXITING = False


def _build_tiers() -> None:
    while True:
        with _BUILD_LOCK:
            if not _BUILDS or _EXITING:
                _BUILDER.clear()
                return
            build = _BUILDS.popleft()
        build()


def _enqueue_build(build) -> None:
    with _BUILD_LOCK:
        _BUILDS.append(build)
        if not _BUILDER:
            _BUILDER.append(threading.Thread(
                target=_build_tiers, daemon=True,
                name="antidote-tier-prep"))
            _BUILDER[0].start()


def _join_tier_builder() -> None:
    global _EXITING
    _EXITING = True
    for th in list(_BUILDER):
        th.join(timeout=120.0)


atexit.register(_join_tier_builder)


def tier_rows(cfg: AntidoteConfig, tier: int) -> int:
    """Rows a shard of tier ``tier``'s table is created with."""
    return min(cfg.keys_per_table,
               _TIER_ROWS[min(tier, len(_TIER_ROWS)) - 1])


def split_tier(tname: str) -> Tuple[str, int]:
    """"set_aw#2" -> ("set_aw", 2); bare names are tier 0."""
    base, _, t = tname.partition("#")
    return base, int(t) if t else 0


def tiered_name(base: str, tier: int) -> str:
    return base if tier == 0 else f"{base}#{tier}"


def scaled_cfg(cfg: AntidoteConfig, tier: int) -> AntidoteConfig:
    """The config a tier table sizes its slotted state (and slot-scaled
    effect lanes, e.g. register_mv observed ids) from."""
    if tier == 0:
        return cfg
    import dataclasses

    s = _TIER_SCALE ** tier
    return dataclasses.replace(
        cfg,
        set_slots=cfg.set_slots * s,
        mv_slots=cfg.mv_slots * s,
        rga_slots=cfg.rga_slots * s,
        # the Pallas kernels are tiled for the base table's widths, where
        # the rows are: a tier table holds a few dozen rows, its kernels
        # would take a minute to compile at 1,024 slots and fit no VMEM
        # block at 4,096 — tiers fold and resolve in plain XLA
        use_pallas=False,
    )


def stable_min_of(clock_rows: np.ndarray) -> np.ndarray:
    """Entry-wise min over a clock matrix ``i32[N, D]`` — the stable-time
    merge for ANY collection of per-shard / per-node clocks
    (stable_time_functions:get_min_time,
    /root/reference/src/stable_time_functions.erl:51-85), on the host,
    where the matrix lives."""
    return np.asarray(clock_rows).min(axis=0)


def _canon(v: Any) -> Any:
    """Canonical msgpack-able form of a client-visible CRDT value for
    digesting: dicts become sorted pair lists (msgpack maps can't carry
    tuple keys and dict order is insertion order), numpy scalars become
    ints — so two replicas holding the same logical value always hash
    identically."""
    if isinstance(v, dict):
        pairs = [[_canon(k), _canon(x)] for k, x in v.items()]
        import msgpack as _mp

        pairs.sort(key=lambda p: _mp.packb(p[0], use_bin_type=True,
                                           default=repr))
        return ["\x00map", pairs]
    if isinstance(v, (list, tuple)):
        return [_canon(x) for x in v]
    if isinstance(v, np.integer):
        return int(v)
    if isinstance(v, np.floating):
        return float(v)
    return v


def shard_digest(store: "KVStore", shard: int) -> str:
    """Content digest of one shard's materialized state at its CURRENT
    applied clock — the divergence-detection primitive of the follower
    read tier (ISSUE 9).

    Hashes every directory entry of the shard (sorted canonically) with
    its decoded client-visible value at ``applied_vc[shard]``, plus the
    clock itself.  Values (not raw table rows) make the digest
    independent of slot-tier promotion timing and row-allocation order,
    which legitimately differ between a replica applying effects in
    commit batches and one applying them in drain batches.  Two
    replicas whose ``applied_vc[shard]`` are EQUAL have applied the
    same per-chain prefixes (chain timestamps are monotone and a lane
    only advances past ts once the op carrying ts applied), so equal
    clocks ⇒ the digests MUST match; a mismatch is silent corruption.

    Caller must hold the commit lock (the clock and the heads must be
    one cut).  Cost: one device gather per touched table + one decode
    per key — a periodic-check price, not a serving-path one.  The
    shard's keys come from the directory's per-shard index
    (:class:`ShardDirectory`), not an O(total keys) filter under the
    lock.
    """
    import hashlib

    import msgpack as _mp

    objs = []
    for key, bucket in store.directory.shard_keys(shard):
        tname = store.directory[(key, bucket)][0]
        objs.append((key, split_tier(tname)[0], bucket))
    if store.cold is not None:
        # cold keys are shard members like any other: two replicas at
        # equal clocks must digest identically regardless of which side
        # happens to hold a key resident.  Their tiered name comes from
        # the cold REF (never an up-front whole-shard fault-in — the
        # chunked read below faults each batch in and the post-batch
        # eviction keeps the resident budget honest throughout)
        for key, bucket in list(store.cold.shard_cold_keys(shard)):
            ref = store.cold.refs[(key, bucket)]
            objs.append((key, split_tier(ref.tname)[0], bucket))
    objs.sort(key=lambda o: _mp.packb([o[0], o[2], o[1]],
                                      use_bin_type=True, default=repr))
    h = hashlib.sha256()
    h.update(np.ascontiguousarray(store.applied_vc[shard],
                                  dtype=np.int64).tobytes())
    for lo in range(0, len(objs), 4096):
        chunk = objs[lo:lo + 4096]
        vals = store.read_values(chunk, store.applied_vc[shard])
        for (key, tname, bucket), v in zip(chunk, vals):
            h.update(_mp.packb([_canon(key), bucket, tname, _canon(v)],
                               use_bin_type=True, default=repr))
    return h.hexdigest()


def freeze_key(key: Any) -> Any:
    """Normalize a key after wire/log deserialization: msgpack returns
    tuples as lists, but directory keys must be hashable."""
    if isinstance(key, list):
        return tuple(freeze_key(k) for k in key)
    return key


class ShardDirectory(dict):
    """``(key, bucket) -> (tiered_name, shard, row)`` with a per-shard
    key index (ISSUE 10 satellite / ROADMAP item 2 residual).

    Shard-scoped sweeps — divergence digests, handoff export, shard
    relinquish — used to filter the whole O(total keys) directory under
    the owner's commit lock.  The index makes them O(shard keys): lazy
    (bulk ``update``/construction stay one C-speed dict pass and just
    drop the index; the first :meth:`shard_keys` rebuilds it once), then
    maintained incrementally by every ``[dk] = ent`` / ``pop`` / ``del``.
    Merkle-style splitting of the digests themselves stays future work.
    """

    __slots__ = ("_by_shard",)

    def __init__(self, items=()):
        super().__init__(items)
        self._by_shard = None  # lazy — built on first shard_keys()

    def __setitem__(self, dk, ent):
        idx = self._by_shard
        if idx is not None:
            old = dict.get(self, dk)
            if old is not None and old[1] != ent[1]:
                s = idx.get(old[1])
                if s is not None:
                    s.discard(dk)
            idx.setdefault(ent[1], set()).add(dk)
        dict.__setitem__(self, dk, ent)

    def __delitem__(self, dk):
        ent = dict.pop(self, dk)
        idx = self._by_shard
        if idx is not None:
            s = idx.get(ent[1])
            if s is not None:
                s.discard(dk)

    def pop(self, dk, *default):
        idx = self._by_shard
        if idx is not None and dk in self:
            s = idx.get(dict.__getitem__(self, dk)[1])
            if s is not None:
                s.discard(dk)
        return dict.pop(self, dk, *default)

    def update(self, *a, **kw):  # noqa — bulk path: index rebuilds lazily
        self._by_shard = None
        dict.update(self, *a, **kw)

    def clear(self):
        dict.clear(self)
        self._by_shard = {}

    def shard_keys(self, shard: int):
        """The shard's directory keys — the live index set when the
        shard has entries (copy before mutating the directory while
        iterating), an empty frozenset otherwise (consistent set
        semantics either way; never an accidentally-mutable miss)."""
        idx = self._by_shard
        if idx is None:
            idx = {}
            for dk, ent in self.items():
                idx.setdefault(ent[1], set()).add(dk)
            self._by_shard = idx
        return idx.get(shard, frozenset())


def key_to_shard(key: Any, bucket: str, n_shards: int) -> int:
    """Key→shard map.  Integer keys map directly (mod n_shards), other keys
    hash via the native router — mirroring log_utilities:get_key_partition
    (/root/reference/src/log_utilities.erl:75-79,96-118)."""
    return shard_of(key, bucket, n_shards)


def _row_vcs(read_vc, n: int) -> np.ndarray:
    """A batch's read VCs, one a row (``[n, D]``): a single VC is the
    batch whose rows all read at it."""
    vc = np.asarray(read_vc, np.int32)
    return np.broadcast_to(vc, (n, vc.shape[-1]))


def _pad_lane(x, width: int, dtype) -> np.ndarray:
    """Zero-pad an effect lane to a (wider) tier's width."""
    x = np.asarray(x, dtype)
    if x.shape[0] == width:
        return x
    assert x.shape[0] < width, (x.shape, width)
    out = np.zeros((width,), dtype)
    out[: x.shape[0]] = x
    return out


class _ReplayFold:
    """The log replay's serial fold for one (type, widths): one device
    program, ``replay_fold_serial``, launched once a piece of a log (its
    ops padded to 64 or 256), whose one host operand is a staged int32
    array [to + 1 + ``state_rows``, ``cols``] (:meth:`stage`):

    * rows [0, to): the piece's ops, zeros past its ``n`` — each int64
      lane of ``eff_a`` as its (lo, hi) halves, then ``eff_b``, the
      commit VC, the origin;
    * row ``to``: ``n``, whether the rows below hold the state to fold
      onto, the base VC, the read VC;
    * the rows after: a host state's fields as int32 words, in
      ``state_spec`` order.

    The state a previous piece left on the device is an operand of its
    own; a device zero state takes its place beside a host one, so one
    signature a padded length serves every piece."""

    def __init__(self, ty, cfg_t, d: int):
        import jax.numpy as jnp

        from antidote_tpu.materializer import fold as fold_mod

        wa, wb = ty.eff_a_width(cfg_t), ty.eff_b_width(cfg_t)
        self.spec = {f: (tuple(s), np.dtype(dt))
                     for f, (s, dt) in ty.state_spec(cfg_t).items()}
        assert all(dt in (np.int32, np.int64)
                   for _, dt in self.spec.values()), self.spec
        self.d = d
        self.b0, self.v0, self.o0 = 2 * wa, 2 * wa + wb, 2 * wa + wb + d
        self.cols = max(self.o0 + 1, 2 + 2 * d)
        words = sum(int(np.prod(s)) * dt.itemsize // 4
                    for s, dt in self.spec.values())
        self.state_rows = -(-words // self.cols)
        self.zero = jax.device_put(
            {f: np.zeros(s, dt) for f, (s, dt) in self.spec.items()})
        b0, v0, o0 = self.b0, self.v0, self.o0

        def fold(staged, carried):
            to = staged.shape[0] - 1 - self.state_rows
            ops, hdr = staged[:to], staged[to]
            words = staged[to + 1:].reshape(-1)
            state, off = {}, 0
            for f, (s, dt) in self.spec.items():
                k = int(np.prod(s)) * dt.itemsize // 4
                w = words[off:off + k]
                off += k
                x = (_join_i64(w.reshape(s + (2,))) if dt == np.int64
                     else w.reshape(s))
                state[f] = jnp.where(hdr[1] != 0, x, carried[f])
            return fold_mod.fold_key(
                ty, cfg_t, state, _join_i64(ops[:, :b0].reshape(to, wa, 2)),
                ops[:, b0:v0], ops[:, v0:o0], ops[:, o0], hdr[0],
                hdr[2:2 + d], hdr[2 + d:2 + 2 * d])

        self.fn = device_program("replay_fold_serial", fold)

    def stage(self, ops_a, ops_b, ops_vc, ops_origin, to: int, base_vc,
              read_vc, state=None) -> np.ndarray:
        """The operand of one piece: its ``n`` ops (host arrays, leading
        axis ``n`` <= ``to``) and ``state``, the host state to fold onto
        (None: the device state handed in beside it).  A buffer of its
        own for every launch (see TypedTable.append)."""
        n, d = len(ops_origin), self.d
        staged = np.zeros((to + 1 + self.state_rows, self.cols), np.int32)
        staged[:n, :self.b0] = np.ascontiguousarray(
            ops_a, np.int64).view(np.int32)
        staged[:n, self.b0:self.v0] = ops_b
        staged[:n, self.v0:self.o0] = ops_vc
        staged[:n, self.o0] = ops_origin
        hdr = staged[to]
        hdr[0] = n
        hdr[2:2 + d] = base_vc
        hdr[2 + d:2 + 2 * d] = read_vc
        if state is not None:
            hdr[1] = 1
            words, off = staged[to + 1:].reshape(-1), 0
            for f, (_s, dt) in self.spec.items():
                w = np.ascontiguousarray(state[f], dt).view(np.int32).ravel()
                words[off:off + w.size] = w
                off += w.size
        return staged


def effect_from_rec(rec: dict) -> "Effect":
    """Decode one WAL record (LogManager.log_effect's wire dict) back into
    an Effect — the single place that knows the record's lane encoding."""
    return Effect(
        freeze_key(rec["k"]), rec["t"], rec["b"],
        np.frombuffer(rec["a"], np.int64),
        np.frombuffer(rec["eb"], np.int32),
        [(h, d) for h, d in rec.get("bl", [])],
    )


class Effect:
    """One downstream effect bound to a key — the unit the log stores and
    replication ships (analogue of #clocksi_payload{},
    /root/reference/include/antidote.hrl)."""

    __slots__ = ("key", "type_name", "bucket", "eff_a", "eff_b", "blob_refs")

    def __init__(self, key, type_name, bucket, eff_a, eff_b, blob_refs=()):
        self.key = key
        self.type_name = type_name
        self.bucket = bucket
        self.eff_a = eff_a
        self.eff_b = eff_b
        self.blob_refs = list(blob_refs)


#: distinct miss marker (None is a legitimate cached value)
_CACHE_MISS = object()


class ServingEpoch:
    """One published store-wide serving snapshot (ISSUE 5 lock-split).

    ``vc`` is the snapshot clock E: every applied op is ≤ E entry-wise and
    every op applied after publication is invisible at E (local commits
    mint own-lane counters above E; remote chains apply in op-id order, so
    their next op's origin lane exceeds E too).  ``tables`` maps tiered
    table names to frozen (head, head_vc, cap) buffers exact at E;
    ``used_rows`` snapshots row allocation so rows born after publication
    read as bottom; ``promoted`` collects keys tier-promoted after
    publication (their frozen location went stale — readers fall back).

    Readers pin the epoch (under the store's epoch lock) for the lifetime
    of a launch+writeback so a later publish never donates buffers a
    lock-free gather still references.
    """

    __slots__ = ("id", "prev_id", "vc", "mut_epoch", "tables", "used_rows",
                 "touched", "promoted", "pins", "born", "applied")

    def __init__(self, id_, prev_id, vc, mut_epoch, tables, used_rows,
                 touched, applied=None):
        self.id = id_
        self.prev_id = prev_id
        self.vc = vc
        self.mut_epoch = mut_epoch
        self.tables = tables
        self.used_rows = used_rows
        #: per-shard applied-clock cut at capture (i32[n_shards, D]) —
        #: the follower session gate's evidence that this epoch's frozen
        #: buffers actually contain a token's per-shard coverage (the
        #: cross-shard-max ``vc`` alone can claim lanes a lagging
        #: shard's buffer lacks, via ping-skew)
        self.applied = applied
        #: tname -> frozenset of (shard, row) re-frozen at THIS publish
        #: (None = full copy / unknown) — drives snapshot-cache
        #: revalidation across epoch advances for untouched keys
        self.touched = touched
        self.promoted: set = set()
        self.pins = 0
        import time as _time

        self.born = _time.monotonic()


class _EpochReadPending:
    """Launched-but-unmaterialized epoch read batch: device handles only
    (the dispatcher stage must never sync)."""

    __slots__ = ("ep", "objects", "vals", "launches", "gathered",
                 "t_synced")

    def __init__(self, ep, objects, vals, launches, gathered):
        self.ep = ep
        self.objects = objects
        self.vals = vals
        self.launches = launches
        #: indices of the objects a device gather serves (the others hit
        #: the snapshot cache, read bottom, or fell back)
        self.gathered = gathered
        #: ``time.monotonic()`` after the last device-to-host transfer of
        #: :meth:`KVStore.epoch_read_finish` (0.0: nothing was launched)
        self.t_synced = 0.0

#: composite-key namespaces (crdt/maps.py field_key/member_key): an effect
#: on a derived key must also invalidate the PARENT map's cached value
_DERIVED_NS = ("\x00mapfield", "\x00mapmember")


def _copy_out(v):
    """Deep-copy a cached value's containers on the way out — clients may
    mutate what they're handed at any nesting level (nested maps hand out
    inner dicts), and a shared container would poison the cache."""
    if type(v) is list:
        return [_copy_out(x) for x in v]
    if type(v) is dict:
        return {k: _copy_out(x) for k, x in v.items()}
    return v


class KVStore:
    def __init__(self, cfg: AntidoteConfig, sharding=None, log=None):
        self.cfg = cfg
        self.sharding = sharding
        #: MeshServingPlane when the serving plane is sharded over a
        #: device mesh (ISSUE 10); attached via MeshServingPlane.attach.
        #: Routes stable-time through the pmin collective and epoch
        #: gathers through the routed shard_map path.
        self.mesh = None
        self.tables: Dict[str, TypedTable] = {}
        self.directory: Dict[Tuple[Any, str], Tuple[str, int, int]] = (
            ShardDirectory())
        self.blobs = BlobStore()
        #: optional LogManager — when set, effects are logged (with blob
        #: payloads) before the device tables observe them
        self.log = log
        # per-shard applied VC (partition clock) — min over shards is the
        # DC's stable snapshot (stable_time_functions:get_min_time,
        # /root/reference/src/stable_time_functions.erl:51-85)
        self.applied_vc = np.zeros((cfg.n_shards, cfg.max_dcs), np.int32)
        #: per-type cached bottom (never-written) resolved view
        self._bottom_cache: Dict[str, Dict[str, np.ndarray]] = {}
        #: keys promoted to a wider slot tier (observability + tests),
        #: by destination tier, and the seconds the promotions took
        self.promotions = 0
        self.promotions_by_tier: Dict[int, int] = {}
        self.promote_seconds = 0.0
        #: tier tables asked of the building thread (tname -> True), those
        #: built and not yet asked for (the lock orders the thread's
        #: hand-over against :meth:`table`'s), and how many were started
        self._tier_prep: Dict[str, Any] = {}
        self._tier_ready: Dict[str, TypedTable] = {}
        self._tier_lock = threading.Lock()
        self.tier_preps = 0
        #: per-strategy replay-path fold dispatch counts (the
        #: materializer status block; see _fold_over_ring)
        self.replay_fold_dispatches: Dict[str, int] = {}
        #: (type, tier config) -> serial fold of a replayed log
        #: (:class:`_ReplayFold`, compiled once a padded log length)
        self._replay_fold_fns: Dict[tuple, Any] = {}
        #: (key, bucket) -> [tiered name, state, vc, tail, pos]: a
        #: below-coverage read's answer kept as the next one's base —
        #: ``state`` holds exactly the effects among the key's first
        #: ``pos`` logged ones (LogManager.key_history) that are <= ``vc``,
        #: ``tail`` the others among them, in log order.  Least recently
        #: read keys go first; the lock is the replay's own (a read
        #: inside a transaction and a remote commit hold different ones)
        self._replay_bases: "collections.OrderedDict" = (
            collections.OrderedDict())
        self._replay_lock = threading.Lock()
        #: below-coverage reads answered from the log, the logged
        #: effects they folded, and the host seconds they took
        self.replays = 0
        self.replay_records = 0
        self.replay_seconds = 0.0
        #: type_name -> whether the type has slot accounting (cached so the
        #: apply_effects demand pre-pass skips unslotted effects cheaply)
        self._slotted: Dict[str, bool] = {}
        #: decoded-value cache: (key, bucket) -> (value, fill_vc tuple).
        #: The host-level analogue of the reference's snapshot_cache
        #: (/root/reference/src/materializer_vnode.erl:37-39): where the
        #: device head skips the fold for hot keys, this skips the
        #: gather+decode for UNCHANGED keys — an entry is valid for any
        #: read VC that dominates the table-wide max commit VC at fill
        #: time (then latest == cached), and every write to the key
        #: invalidates it.  LRU-bounded.
        from collections import OrderedDict as _OD

        self._value_cache: "_OD[Tuple[Any, str], tuple]" = _OD()
        self._value_cache_cap = 65536
        #: guards every _value_cache access: the ProtocolServer happens
        #: to serialize txm calls today, but an embedder driving reads
        #: from one thread while inter-DC ingress applies effects from
        #: another would race get/move_to_end against pop (r4 advisor)
        import threading as _threading

        self._value_cache_lock = _threading.Lock()
        #: bumped at BOTH ends of every apply_effects batch (with
        #: ``_mutating`` covering the window between): fills racing a
        #: concurrent commit are dropped whether they captured their
        #: epoch before the apply, or mid-apply — either could otherwise
        #: cache a pre-apply value whose fill clock claims coverage of
        #: the commit it never saw
        self.mutation_epoch = 0
        self._mutating = False
        #: commit groups whose effects went to the device (node status
        #: ``write_plane.scatter``, beside the tables' own tallies)
        self.scatter_groups = 0
        # --- serving epochs + hot-key snapshot cache (ISSUE 5) ---------
        #: NodeMetrics (attached by AntidoteNode) — snapshot-cache and
        #: epoch-publish counters land here when present
        self.metrics = None
        #: the last published store-wide serving snapshot (ServingEpoch)
        self.serving_epoch: "ServingEpoch | None" = None
        self._serving_seq = 0
        #: retired epochs whose reader pins have not drained yet — a
        #: publish may only donate spare buffers once this is pin-free
        #: (bounded-by: pruned to pinned entries at every publish; pins
        #: drain with each read batch)
        self._epoch_graveyard: List["ServingEpoch"] = []
        self._epoch_lock = _threading.Lock()
        #: hot-key snapshot cache: (key, bucket) -> (epoch_id, location,
        #: decoded value) — the TPU-side analogue of materializer_vnode's
        #: snapshot cache (/root/reference/src/materializer_vnode.erl:37-39):
        #: a Zipfian-hot key re-read at an unchanged epoch is a dict hit
        #: that skips the gather/decode entirely.  Invalidated by epoch
        #: advance (entries carry their epoch id; an entry from the
        #: immediately-previous epoch revalidates iff its row was not
        #: re-frozen).  LRU-bounded.
        self.snapshot_cache: "_OD[Tuple[Any, str], tuple]" = _OD()
        self.snapshot_cache_cap = 65536
        self._snapshot_cache_lock = _threading.Lock()
        #: publish history: epoch id -> {tname: frozenset of re-frozen
        #: (shard, row) | None=full copy} — lets a cache entry from N
        #: epochs ago revalidate by proving its row untouched across
        #: every publish since (Zipf-tail keys survive arbitrarily many
        #: epoch advances; any gap or copy in the chain = miss).
        #: bounded-by: _EPOCH_HISTORY entries, pruned at every publish
        self._epoch_touch_log: "_OD[int, dict]" = _OD()
        #: decoded bottom (never-written) value per type — served for
        #: keys born after the epoch without any device work
        self._bottom_values: Dict[str, Any] = {}
        # --- cold tier + incremental-stamp tracking (ISSUE 13) ---------
        #: ColdTier when beyond-RAM mode is enabled (AntidoteNode
        #: attaches it); None = every key stays device-resident
        self.cold = None
        #: MerkleIndex for split divergence digests (built lazily by the
        #: replica planes; None until the first tree is requested)
        self.merkle = None
        #: NativeFrontend mirror (ISSUE 16) — the C++ serving loop's
        #: epoch-stamped copy of the snapshot cache.  Wired by the
        #: protocol server when native whole-batch serving is on;
        #: pushed from the fill/invalidate/drop paths below; which
        #: fill it takes is the mirror's own rule (frontend.cc), so the
        #: native plane can never serve a value Python would not.
        self.native_mirror = None
        #: (key, bucket) pairs written/born/promoted since the last
        #: checkpoint capture — the incremental chain's dirty-key window.
        #: None = untracked overflow: the next stamp must rebase.
        self.ckpt_dirty_keys: "set | None" = set()
        #: blob hashes interned in the same window (their WAL records
        #: fall below the delta's floor, so the link must carry them);
        #: None = overflow — bounded like the key window above
        self._ckpt_dirty_blobs: "set | None" = set()
        #: keys EVICTED to the cold tier in the window: dk -> sidecar
        #: coords (the delta link records the transition so a composed
        #: recovery re-registers them cold instead of resurrecting a
        #: stale resident row over a reused slot)
        self._ckpt_evicted: Dict[Tuple[Any, str], tuple] = {}

    #: dirty-key windows past this size stop tracking (rebase instead)
    _CKPT_KEYS_CAP = 262144

    def note_ckpt_dirty(self, dk) -> None:
        ks = self.ckpt_dirty_keys
        if ks is not None:
            ks.add(dk)
            if len(ks) > self._CKPT_KEYS_CAP:
                self.ckpt_dirty_keys = None

    def mark_epoch_fallback(self, dk) -> None:
        """Make every live serving epoch fall back to the locked path
        for one key — the row-reuse discipline shared by tier promotion,
        cold eviction and cold fault-in (a frozen buffer may hold the
        row's previous tenant)."""
        with self._epoch_lock:
            eps = list(self._epoch_graveyard)
            if self.serving_epoch is not None:
                eps.append(self.serving_epoch)
        for e in eps:
            e.promoted.add(dk)
        nm = self.native_mirror
        if nm is not None:
            # epoch-ineligible key: the native mirror must miss too
            nm.invalidate(dk[0], dk[1])

    def drop_cached_value(self, dk) -> None:
        """Invalidate both decoded-value caches for one key (eviction /
        range heal: the cached decode may outlive the device row)."""
        with self._value_cache_lock:
            self._value_cache.pop(dk, None)
        with self._snapshot_cache_lock:
            self.snapshot_cache.pop(dk, None)
        nm = self.native_mirror
        if nm is not None:
            nm.invalidate(dk[0], dk[1])

    def _is_slotted(self, type_name: str) -> bool:
        hit = self._slotted.get(type_name)
        if hit is None:
            hit = get_type(type_name).slot_capacity(self.cfg) is not None
            self._slotted[type_name] = hit
        return hit

    # ------------------------------------------------------------------
    def _new_table(self, tname: str) -> TypedTable:
        base, tier = split_tier(tname)
        return TypedTable(
            get_type(base), scaled_cfg(self.cfg, tier),
            n_rows=None if tier == 0 else tier_rows(self.cfg, tier),
            sharding=self.sharding, metrics=self.metrics,
        )

    def table(self, tname: str) -> TypedTable:
        """Table for a (possibly tiered) name.  A tier table has x4 slot
        widths per tier and starts with :func:`tier_rows` rows a shard —
        by demand, not by the base table's key count — and doubles when
        a shard of it fills (``TypedTable._grow``).  It is taken from
        :meth:`prepare_tier`'s hands when that has built it ahead of
        need, and built here otherwise — also when that is still at it:
        a commit group never waits for a compile it can do without (the
        thread goes on, and the table built here takes the programs it
        compiled when it is done: ``TypedTable.adopt_programs``)."""
        t = self.tables.get(tname)
        if t is None:
            with self._tier_lock:
                t = self._tier_ready.pop(tname, None)
            if t is None:
                t = self._new_table(tname)
            # out-of-band mutations (grow/promote/handoff) invalidate the
            # table's frozen serving buffers; the store-wide epoch that
            # references them must die with them
            t.on_serving_invalidate = self.drop_serving_epoch
            with self._tier_lock:
                # (either this sees the thread's table or the thread
                # sees this one: the lock orders the two hand-overs)
                self.tables[tname] = t
                late = self._tier_ready.pop(tname, None)
                if late is not None:
                    t.adopt_programs(late)
        if t.metrics is None and self.metrics is not None:
            # metrics attach after store construction; adopt lazily
            t.metrics = self.metrics
        return t

    def prepare_tier(self, tname: str) -> None:
        """Have table ``tname`` built and its programs compiled on the
        process's tier-building thread, off the commit path (caller
        holds the commit lock): a synchronous build is 20 s of compile
        inside a commit group on a cold cache, past what a forwarded
        write waits.  The table stays out of ``tables``
        until :meth:`table` is asked for it, so nothing else can reach
        it meanwhile."""
        if (tname in self.tables or tname in self._tier_prep
                or tname in self._tier_ready or self.sharding is not None):
            # (on a mesh every program runs on all devices, and two
            # threads launching such programs can interleave their
            # per-device queues: there the tier is built when needed,
            # inside its commit group)
            return
        base, tier = split_tier(tname)
        src = self.tables.get(tiered_name(base, tier - 1))
        # the shapes of the tier below's row, taken here: its arrays are
        # donated from under any other thread
        src_row = None if src is None else jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[2:], x.dtype),
            src._tree())

        def build():
            try:
                t = self._new_table(tname)
                if not t.warm(src_row, stop=lambda: _EXITING):
                    return
                self._warm_replay(t.ty, t.cfg, with_base=tier == 1)
                with self._tier_lock:
                    live = self.tables.get(tname)
                    if live is None:
                        self._tier_ready[tname] = t
                    else:
                        # a promotion came first and built the table
                        # itself: it takes the programs compiled here
                        live.adopt_programs(t)
            except Exception:  # noqa: BLE001 - table() then builds it
                import logging

                logging.getLogger(__name__).exception(
                    "preparing tier table %s failed", tname)

        self._tier_prep[tname] = True
        self.tier_preps += 1
        _enqueue_build(build)

    def locate(self, key, type_name: str, bucket: str, create: bool = True):
        """(tiered_name, shard, row) for a bound object; allocates on first
        use.  The first element names the table (base type + slot tier);
        callers needing the CRDT type use ``split_tier(...)[0]``."""
        dk = (key, bucket)
        hit = self.directory.get(dk)
        if hit is not None:
            if split_tier(hit[0])[0] != type_name:
                raise TypeError(
                    f"key {key!r} bucket {bucket!r} already bound to {hit[0]}, "
                    f"not {type_name}"
                )
            return hit
        if self.cold is not None and self.cold.is_cold(dk):
            # cold key: fault the device row back in through the locked
            # path (typed ColdMiss past the rate cap — never bottom)
            hit = self.cold.fault_in(dk)
            if split_tier(hit[0])[0] != type_name:
                raise TypeError(
                    f"key {key!r} bucket {bucket!r} already bound to "
                    f"{hit[0]}, not {type_name}"
                )
            return hit
        if not create:
            return None
        shard = key_to_shard(key, bucket, self.cfg.n_shards)
        row = self.table(type_name).alloc_row(shard)
        ent = (type_name, shard, row)
        self.directory[dk] = ent
        self.note_ckpt_dirty(dk)
        if self.cold is not None:
            self.cold.note_birth(dk)
        return ent

    def locate_many(self, objects: Sequence[BoundObject]) -> None:
        """Pre-bind a batch of objects: unseen keys are routed with ONE
        native ``shard_batch`` FFI crossing (the batched path router.cc is
        built for), then rows allocated.  Subsequent ``locate`` calls are
        pure dict hits."""
        missing = [
            (key, type_name, bucket)
            for key, type_name, bucket in objects
            if (key, bucket) not in self.directory
        ]
        if not missing:
            return
        if self.cold is not None:
            still = []
            for key, type_name, bucket in missing:
                if self.cold.is_cold((key, bucket)):
                    self.cold.fault_in((key, bucket))
                else:
                    still.append((key, type_name, bucket))
            missing = still
            if not missing:
                return
        shards = shard_batch(
            [m[0] for m in missing], [m[2] for m in missing],
            self.cfg.n_shards,
        )
        for (key, type_name, bucket), shard in zip(missing, shards):
            dk = (key, bucket)
            if dk in self.directory:  # duplicate within the batch
                continue
            row = self.table(type_name).alloc_row(int(shard))
            self.directory[dk] = (type_name, int(shard), int(row))
            self.note_ckpt_dirty(dk)
            if self.cold is not None:
                self.cold.note_birth(dk)

    # ------------------------------------------------------------------
    def apply_effects(
        self,
        effects: Sequence[Effect],
        commit_vcs: Sequence[np.ndarray],
        origins: Sequence[int],
    ) -> None:
        """Apply a commit-ordered batch of effects to the device tables.

        ``effects[i]`` committed with clock ``commit_vcs[i]`` from DC
        ``origins[i]``.  Groups by type into single scatter+ring appends
        (the batched analogue of clocksi_vnode:update_materializer,
        /root/reference/src/clocksi_vnode.erl:634-657).

        Blocking form: ONE failure-atomic group — a WAL refusal raises
        before any device table mutates, and the commit barrier (fsync
        under sync_log=true) completes before the device apply, so the
        callers with retry loops (remote ingress, recovery) never
        double-apply.
        """
        errors, _ticket, _wal = self.apply_effect_groups(
            [(list(effects), list(commit_vcs), list(origins))],
            defer_sync=False,
        )
        if errors[0] is not None:
            raise errors[0]

    def apply_effect_groups(self, groups, defer_sync: bool = True):
        """Apply a MERGED commit batch: several independent sub-groups
        (one per source transaction), each failure-atomic on its own —
        the write-plane merge seam (ISSUE 6).  A sub-group whose WAL
        append is refused (ENOSPC mid-batch) is NACKed and rolled back
        alone; sibling sub-groups still log, scatter and ack.

        ``groups``: list of ``(effects, commit_vcs, origins)`` per
        sub-group.  Returns ``(errors, ticket, wal)``: one ``None`` or
        ``Exception`` per sub-group; with ``defer_sync`` the group-fsync
        ticket acks must wait on (None when nothing was logged; the fsync
        runs CONCURRENTLY with the device scatter); and, for the commit
        path's phase split, ``time.monotonic()`` at the start and the end
        of the WAL phase (append + fsync submitted) and the seconds the
        native mirror's invalidation took (None without a mirror)."""
        self._mutating = True
        self.mutation_epoch += 1
        try:
            return self._apply_effect_groups_inner(groups, defer_sync)
        finally:
            self.mutation_epoch += 1
            self._mutating = False

    def _apply_effect_groups_inner(self, groups, defer_sync):
        effects = [e for g in groups for e in g[0]]
        self.locate_many([(e.key, e.type_name, e.bucket) for e in effects])
        nm = self.native_mirror
        mirror_s = None
        if nm is not None:
            # EAGER native-mirror invalidation, under the commit lock,
            # BEFORE any table observes the effects, the group's keys in
            # one native call: from here to the advance that follows the
            # group's publish the C++ loop misses on these keys, and the
            # mirror itself refuses whatever a read launched before this
            # line would still push for them (frontend.cc, the mirror's
            # rule) — it never serves a value this group made history
            keys = {(e.key, e.bucket) for e in effects}
            t_inv = time.monotonic()
            with span("commit.mirror_invalidate", keys=len(keys)):
                nm.invalidate_many(keys)
            mirror_s = time.monotonic() - t_inv
        # ---- overflow escape hatch: promote BEFORE anything can drop.
        # Aggregate each key's worst-case fresh-slot demand (+ the minimum
        # tier its effect lanes require — a remote DC may ship wider
        # lanes); keys whose conservative bound would exceed capacity
        # migrate to a wider tier now, so the device fold below never hits
        # a full slot table.
        demand: Dict[Tuple[Any, str], List[int]] = {}
        for eff in effects:
            if not self._is_slotted(eff.type_name):
                continue  # counters/flags/lww can never overflow
            ent = self.locate(eff.key, eff.type_name, eff.bucket)
            base, tier = split_tier(ent[0])
            ty = get_type(base)
            d = ty.slot_demand(eff.eff_a, eff.eff_b)
            need_t = self._tier_for_lanes(ty, len(eff.eff_a), len(eff.eff_b))
            if d or need_t > tier:
                cur = demand.setdefault((eff.key, eff.bucket), [0, 0])
                cur[0] += d
                cur[1] = max(cur[1], need_t)
        for dk, (d, need_t) in demand.items():
            tname_t, shard, row = self.directory[dk]
            base, tier = split_tier(tname_t)
            ty = get_type(base)
            t = self.table(tname_t)
            cap = ty.slot_capacity(t.cfg)
            if need_t <= tier and (
                cap is None or t.slots_ub[shard, row] + d <= cap
            ):
                t.slots_ub[shard, row] += d
                if (cap is not None and tier < _MAX_TIER
                        and 2 * t.slots_ub[shard, row] > cap):
                    # past half of its tier: have the next tier's table
                    # and programs ready before the key needs them
                    self.prepare_tier(tiered_name(base, tier + 1))
                continue
            self._promote_key(dk, extra_demand=d, min_tier=need_t)
        # per-sub-group record build (blob intern rides along, as
        # before); the resolved (tiered name, shard, row) rides to the
        # scatter loop so the hot path locates each effect once
        to_log_groups: List[List[tuple]] = []
        located: List[List[tuple]] = []
        for effs, vcs, orgs in groups:
            entries: List[tuple] = []
            locs: List[tuple] = []
            for i, eff in enumerate(effs):
                loc = self.locate(eff.key, eff.type_name, eff.bucket)
                locs.append(loc)
                for h, data in eff.blob_refs:
                    self.blobs.intern_bytes(h, data)
                    bl = self._ckpt_dirty_blobs
                    if bl is not None:
                        bl.add(h)
                        if len(bl) > self._CKPT_KEYS_CAP:
                            self._ckpt_dirty_blobs = None
                if self.log is not None:
                    entries.append((
                        loc[1], eff.key, eff.type_name, eff.bucket,
                        eff.eff_a, eff.eff_b, vcs[i], orgs[i],
                        eff.blob_refs,
                    ))
            to_log_groups.append(entries)
            located.append(locs)
        # durability first: log (with blob payloads) before any device
        # apply — failure-atomically PER SUB-GROUP: a mid-batch ENOSPC
        # NACKs and rolls back exactly the refused sub-group(s); a
        # NACKed group can never partially resurrect on recovery, and
        # its siblings still commit
        errors: List[Optional[Exception]] = [None] * len(groups)
        t_wal = time.monotonic()
        with span("commit.wal_append"):
            if self.log is not None and any(to_log_groups):
                errors = self.log.log_effect_groups(to_log_groups)
            # survivors only: cache invalidation, device scatter, clocks
            ticket = None
            by_table: Dict[str, list] = {}
            touched = []
            inval: List[Tuple[Any, str]] = []
            for (effs, vcs, orgs), locs, err in zip(groups, located, errors):
                if err is not None:
                    continue
                for i, eff in enumerate(effs):
                    tname_t, shard, row = locs[i]
                    inval.append((eff.key, eff.bucket))
                    self.note_ckpt_dirty((eff.key, eff.bucket))
                    if self.merkle is not None:
                        self.merkle.mark(shard, (eff.key, eff.bucket))
                    # composite invalidation: a field/membership write
                    # kills the parent map's assembled value (recursively
                    # for nested maps)
                    k = eff.key
                    while (type(k) is tuple and len(k) >= 2
                           and k[0] in _DERIVED_NS):
                        k = k[1]
                        inval.append((k, eff.bucket))
                    by_table.setdefault(tname_t, []).append(
                        (shard, row, eff.eff_a, eff.eff_b, vcs[i], orgs[i])
                    )
                    touched.append((shard, np.asarray(vcs[i], np.int32)))
            if self.log is not None and touched:
                # group fsync: deferred acks wait on the ticket AFTER the
                # commit lock releases, so the fsync overlaps the device
                # scatter below and the NEXT merged batch's certification;
                # the blocking form (remote ingress, recovery) keeps the
                # barrier-before-apply ordering so its retry loops never
                # double-apply a device mutation
                ticket = self.log.barrier_async([s for s, _ in touched])
                if not defer_sync:
                    ticket.wait()
                    ticket = None
        t_wal_end = time.monotonic()
        if inval:
            # one locked sweep per batch, not one acquisition per effect
            with self._value_cache_lock:
                for dk in inval:
                    self._value_cache.pop(dk, None)
        with span("commit.scatter", effects=len(inval)):
            for tname_t, items in by_table.items():
                t = self.table(tname_t)
                shards, rows, las, lbs, vcs, orgs = zip(*items)
                # lanes narrower than the table's tier are zero-padded
                eff_a = np.zeros((len(items), t.ty.eff_a_width(t.cfg)),
                                 np.int64)
                eff_b = np.zeros((len(items), t.ty.eff_b_width(t.cfg)),
                                 np.int32)
                for i, (la, lb) in enumerate(zip(las, lbs)):
                    eff_a[i, :len(la)] = la
                    eff_b[i, :len(lb)] = lb
                t.append(shards, rows, eff_a, eff_b, np.asarray(vcs), orgs)
            if by_table:
                self.scatter_groups += 1
        # only after every append succeeded may the partition clocks claim
        # these commits (the stable snapshot must never dominate unapplied
        # ops — the causal gate trusts it)
        for shard, vc in touched:
            np.maximum(self.applied_vc[shard], vc, out=self.applied_vc[shard])
        if self.cold is not None and inval:
            # LRU touch for the written keys, then bounded budget
            # enforcement — both on the commit path (the caller already
            # holds the commit lock; eviction mutates tables)
            self.cold.note_writes(inval)
            self.cold.maybe_evict()
        return errors, ticket, (t_wal, t_wal_end, mirror_s)

    def scatter_status(self) -> Dict[str, int]:
        """``write_plane.scatter`` of the node status: commit groups that
        reached the device, and what their tables' ``append`` sent there
        — host arrays transferred, device programs launched."""
        tables = list(self.tables.values())
        return {
            "groups": self.scatter_groups,
            "transfers": sum(t.scatter_transfers for t in tables),
            "launches": sum(t.scatter_launches for t in tables),
        }

    def tier_status(self) -> Dict[str, Any]:
        """``write_plane.gc`` and ``write_plane.tiers`` of the node
        status: GC launches and the rows they folded into a snapshot
        version; promotions by destination tier; tier tables built ahead
        of need; rows a shard of each tier table has and how often a
        table doubled."""
        tables = dict(self.tables)
        tiers = {t: v for t, v in tables.items() if split_tier(t)[1] > 0}
        return {
            "gc": {
                "launches": sum(t.gc_launches for t in tables.values()),
                "rows": sum(t.gc_rows for t in tables.values()),
                "sum_ms": sum(t.gc_seconds for t in tables.values()) * 1e3,
            },
            "tiers": {
                "promotions": self.promotions,
                "promotions_by_tier": {
                    str(k): v
                    for k, v in sorted(self.promotions_by_tier.items())},
                "promote_sum_ms": self.promote_seconds * 1e3,
                "prepared": self.tier_preps,
                "grows": sum(t.grows for t in tiers.values()),
                "rows": {t: int(v.n_rows) for t, v in sorted(tiers.items())},
                "rows_used": {t: int(v.used_rows.max())
                              for t, v in sorted(tiers.items())},
            },
        }

    def fold_status(self) -> Dict[str, Any]:
        """``pipeline.fold`` of the node status: the versioned read's
        launches, the rows it folded and the host seconds from launch to
        answer; of the rows the locked read plane gathered, those the
        head answered against those a fold did; and the reads below the
        device's coverage that the log answered (``replays``), the
        logged effects they folded and the host milliseconds they took
        (:meth:`_replay_read_many`)."""
        tables = list(self.tables.values())
        return {
            "launches": sum(t.fold_launches for t in tables),
            "rows": sum(t.fold_rows for t in tables),
            "sum_ms": sum(t.fold_seconds for t in tables) * 1e3,
            "reads_by_head": sum(t.reads_by_head for t in tables),
            "reads_by_fold": sum(t.reads_by_fold for t in tables),
            "replays": self.replays,
            "replay_records": self.replay_records,
            "replay_sum_ms": self.replay_seconds * 1e3,
        }

    # ------------------------------------------------------------------
    # serving epochs (lock-split wire reads — ISSUE 5)
    # ------------------------------------------------------------------
    def pin_serving_epoch(self) -> "ServingEpoch | None":
        """Grab + pin the current serving epoch (None when none is
        published).  The pin keeps a later publish from donating frozen
        buffers a lock-free gather still references; release with
        :meth:`unpin_serving_epoch` once the batch is materialized."""
        with self._epoch_lock:
            ep = self.serving_epoch
            if ep is not None:
                ep.pins += 1
            return ep

    def unpin_serving_epoch(self, ep: "ServingEpoch") -> None:
        with self._epoch_lock:
            ep.pins -= 1

    def drop_serving_epoch(self) -> None:
        """Retire the current epoch without a successor (out-of-band
        table mutation): lock-free reads fall back to the locked path
        until the next publish."""
        with self._epoch_lock:
            ep = self.serving_epoch
            if ep is not None:
                self.serving_epoch = None
                self._epoch_graveyard.append(ep)
        nm = self.native_mirror
        if nm is not None:
            # no epoch, no native serving — until the next advance()
            nm.reset()

    def publish_serving_epoch(self, vc: np.ndarray) -> str:
        """:meth:`publish_serving_epoch_timed`'s status alone."""
        return self.publish_serving_epoch_timed(vc)[0]

    def publish_serving_epoch_timed(self, vc: np.ndarray
                                    ) -> Tuple[str, float]:
        """Publish a new store-wide serving snapshot at clock ``vc``.

        Caller must hold the commit lock (``vc`` and the frozen heads
        must be captured with no concurrent apply).  Dirty tables are
        re-frozen — incrementally where their spare buffer can be
        donated (cost ∝ rows written since the last freeze, NOT table
        size), by full copy on the first freezes or after invalidation.
        Returns "published", "noop" (epoch already current) or
        "deferred" (a reader still pins a retired epoch whose buffers
        the freeze would donate — retried on the next publish trigger),
        and the seconds spent dispatching freeze_serving programs (the
        commit path's ``freeze`` phase, inside ``publish``).
        """
        cur = self.serving_epoch
        freeze_s = 0.0
        if cur is not None and cur.mut_epoch == self.mutation_epoch:
            # safe-time PINGS advance the applied clocks without any
            # data apply (mutation epoch unchanged ⇒ the frozen buffers
            # still hold every applied op): refresh the epoch's
            # applied-clock cut so a follower's session gate — which
            # trusts the cut, not the cross-shard-max vc — doesn't spin
            # on a stale capture after the last write of a burst
            cur.applied = self.applied_vc.copy()
            return "noop", freeze_s
        m = self.metrics
        with self._epoch_lock:
            can_donate = all(e.pins == 0 for e in self._epoch_graveyard)
            if can_donate:
                # unpinned retired epochs are unreachable (readers only
                # ever pin the current one): their buffer refs drop here,
                # freeing the spare slots for donation
                self._epoch_graveyard.clear()
        slots: Dict[str, dict] = {}
        used: Dict[str, np.ndarray] = {}
        touched: Dict[str, Any] = {}
        for tname, t in self.tables.items():
            # write-windows frozen by EARLIER publish attempts that then
            # deferred: their rows must stay in this epoch's touched set
            # or cache entries would revalidate across those writes
            pend = getattr(t, "_pending_touched", frozenset())
            if t.serving_slot() is None or t.serving_dirty():
                # a PARTIAL earlier publish (mid-loop defer) can leave the
                # LIVE epoch referencing this table's spare slot: donating
                # it would delete buffers a lock-free gather still reads.
                # Waiting can never free it (it stays live until a publish
                # succeeds, which needs this freeze) — rebuild by copy.
                spare_live = (cur is not None
                              and cur.tables.get(tname) is t.serving_spare())
                t_frz = time.monotonic()
                res = t.freeze_serving(can_donate and not spare_live,
                                       force_copy=spare_live)
                freeze_s += time.monotonic() - t_frz
                if res is None:
                    if m is not None:
                        m.epoch_publish.inc(mode="defer")
                    return "deferred", freeze_s
                slot, mode, tch, rows, shard_rows = res
                tch = None if (tch is None or pend is None) else tch | pend
                t._pending_touched = tch
                touched[tname] = tch
                if m is not None:
                    m.epoch_publish.inc(mode=mode)
                    m.epoch_rows.inc(rows, mode=mode)
                    if self.mesh is not None:
                        # per-shard incremental publish observable
                        # (ISSUE 10): a scatter republishes exactly the
                        # dirty shards' device slices; a full copy
                        # rebuilds every slice
                        sr = (shard_rows if shard_rows is not None
                              else {s: t.n_rows
                                    for s in range(self.cfg.n_shards)})
                        for s, n in sr.items():
                            m.mesh_publish.inc(n, shard=s)
            else:
                touched[tname] = pend  # clean since the last success
            slots[tname] = t.serving_slot()
            used[tname] = t.used_rows.copy()
        self._serving_seq += 1
        ep = ServingEpoch(
            self._serving_seq, cur.id if cur is not None else None,
            np.asarray(vc, np.int32), self.mutation_epoch, slots, used,
            touched, applied=self.applied_vc.copy(),
        )
        with self._epoch_lock:
            old = self.serving_epoch
            self.serving_epoch = ep
            self._epoch_graveyard = [
                e for e in self._epoch_graveyard if e.pins > 0
            ]
            if old is not None:
                self._epoch_graveyard.append(old)
        with self._snapshot_cache_lock:
            self._epoch_touch_log[ep.id] = touched
            while len(self._epoch_touch_log) > self._EPOCH_HISTORY:
                self._epoch_touch_log.popitem(last=False)
        for t in self.tables.values():
            t._pending_touched = frozenset()  # this epoch carries them
        if m is not None:
            m.serving_epoch_id.set(ep.id)
        return "published", freeze_s

    # ------------------------------------------------------------------
    # hot-key snapshot cache
    # ------------------------------------------------------------------
    #: publish-history retention (epochs): a cache entry older than this
    #: many publishes can no longer prove itself untouched and misses
    _EPOCH_HISTORY = 256

    def epoch_cache_read(self, objects: Sequence[BoundObject],
                         ep: "ServingEpoch"):
        """Whole-batch cache fast path: decoded values for every object
        from the snapshot cache and per-type bottoms alone — no device
        work, no lock, no queue hop (the handler thread serves the reply
        itself).  Returns None as soon as any object needs a gather or
        the locked path; misses are then re-counted by the gate's launch,
        so only hits are counted here."""
        vals: List[Any] = []
        n_hits = 0
        for key, type_name, bucket in objects:
            if not is_type(type_name):
                return None
            ty = get_type(type_name)
            if getattr(ty, "composite", False):
                return None
            dk = (key, bucket)
            hit = self.snapshot_cache_get(dk, ep, type_name, count=False)
            if hit is not _CACHE_MISS:
                vals.append(hit)
                n_hits += 1
                continue
            # directory BEFORE the promoted check — the promotion path
            # marks ep.promoted and THEN flips the directory (GIL-
            # ordered), so a reader that sees the post-flip entry is
            # guaranteed to see the mark and fall back; checking
            # promoted first could miss the mark, then read the flipped
            # entry and serve bottom for a key with data
            ent = self.directory.get(dk)
            if dk in ep.promoted:
                return None
            nm = self.native_mirror
            if ent is None:
                if self.cold is not None and self.cold.is_cold(dk):
                    return None  # cold key: the locked path faults it in
                bottom = self._bottom_value(type_name)
                if nm is not None:
                    # teach the native mirror the bottom: its first
                    # write invalidates eagerly, so serving it at ep is
                    # exactly what this path serves
                    nm.fill(key, bucket, type_name, bottom, ep.id)
                vals.append(bottom)
                continue
            tname_t, shard, row = ent
            ur = ep.used_rows.get(tname_t)
            if (split_tier(tname_t)[0] == type_name and ur is not None
                    and row >= ur[shard]):
                # row born after the epoch: bottom at E
                bottom = self._bottom_value(type_name)
                if nm is not None:
                    nm.fill(key, bucket, type_name, bottom, ep.id)
                vals.append(bottom)
                continue
            return None  # needs a frozen-head gather (or the locked path)
        if self.metrics is not None:
            # counted only on WHOLE-batch success: a bailed batch is
            # re-probed (and counted) by the gate's launch path
            if n_hits:
                self.metrics.snapshot_cache.inc(n_hits, event="hit")
            self.metrics.serving_reads.inc(len(vals), path="cache")
        return vals

    def snapshot_cache_get(self, dk, ep: "ServingEpoch",
                           type_name: str | None = None,
                           count: bool = True):
        """Cached decoded value for ``dk`` at epoch ``ep``, or the miss
        marker.  A stale-stamped entry revalidates (and is re-stamped)
        by walking the publish history: its row untouched by EVERY
        publish since its stamp — Zipf-tail keys survive arbitrarily
        many epoch advances; a written key's entry correctly misses (so
        does anything older than the retained history, or spanning a
        full-copy publish).

        ``type_name``, when given, must match the entry's bound type: a
        wrong-type read must take the miss path so the locked plane can
        raise the same TypeError it raises on a cache-cold request —
        cache residency must never change observable behavior.
        ``count=False`` suppresses the hit/miss counters (batch callers
        count once per batch)."""
        m = self.metrics if count else None
        with self._snapshot_cache_lock:
            ent = self.snapshot_cache.get(dk)
            if ent is not None:
                eid, loc, value = ent
                if (type_name is not None and loc is not None
                        and split_tier(loc[0])[0] != type_name):
                    ent = None  # bound to another type: miss -> TypeError
            if ent is not None:
                ok = eid == ep.id
                if (not ok and eid < ep.id and loc is not None
                        and dk not in ep.promoted):
                    tname, shard, row = loc
                    log_ = self._epoch_touch_log
                    for e in range(eid + 1, ep.id + 1):
                        tl = log_.get(e)
                        tch = None if tl is None else tl.get(tname)
                        if tch is None or (shard, row) in tch:
                            break  # gap / full copy / row re-frozen
                    else:
                        self.snapshot_cache[dk] = (ep.id, loc, value)
                        ok = True
                        nm = self.native_mirror
                        if nm is not None:
                            # re-prove the entry to the native mirror
                            # too: it lacks what it refused or evicted,
                            # and this walk has just shown the value to
                            # be the one at ep
                            nm.fill(dk[0], dk[1], split_tier(loc[0])[0],
                                    value, ep.id)
                if ok:
                    self.snapshot_cache.move_to_end(dk)
                    if m is not None:
                        m.snapshot_cache.inc(event="hit")
                    return _copy_out(value)
        if m is not None:
            m.snapshot_cache.inc(event="miss")
        return _CACHE_MISS

    def snapshot_cache_fill(self, ep: "ServingEpoch", tname: str,
                            filled) -> None:
        """Back-fill one launch's gathered keys — ``[(dk, shard, row,
        value)]`` of table ``tname`` — at epoch ``ep``: one take of the
        cache lock for every insert and the eviction, then one native
        call for the mirror (a launch of one key is a batch of one)."""
        with span("serve.wb_host.fill", table=tname, rows=len(filled)):
            cache = self.snapshot_cache
            with self._snapshot_cache_lock:
                for dk, shard, row, value in filled:
                    cache[dk] = (ep.id, (tname, shard, row),
                                 _copy_out(value))
                evicted = len(cache) - self.snapshot_cache_cap
                for _ in range(evicted):
                    cache.popitem(last=False)
            if evicted > 0 and self.metrics is not None:
                self.metrics.snapshot_cache.inc(evicted, event="evict")
            nm = self.native_mirror
            if nm is not None:
                type_name = split_tier(tname)[0]
                nm.fill_many([(dk[0], dk[1], type_name, value)
                              for dk, _s, _r, value in filled], ep.id)

    def _bottom_value(self, type_name: str):
        """Decoded client-visible value of a never-written key."""
        hit = self._bottom_values.get(type_name)
        if hit is None:
            ty = get_type(type_name)
            zero = {
                f: np.zeros(shape, dtype)
                for f, (shape, dtype) in ty.state_spec(self.cfg).items()
            }
            hit = ty.value(zero, self.blobs, self.cfg)
            self._bottom_values[type_name] = hit
        return _copy_out(hit)

    # ------------------------------------------------------------------
    # epoch reads: launch (dispatcher stage, never syncs) + finish
    # (writeback stage, materializes and decodes)
    # ------------------------------------------------------------------
    def epoch_read_launch(self, objects: Sequence[BoundObject],
                          ep: "ServingEpoch"):
        """Resolve a batch of bound objects at epoch ``ep`` without any
        lock and without any device sync: snapshot-cache hits and bottom
        values are filled immediately; the misses are grouped per table
        into frozen-head gather+resolve launches whose DEVICE handles ride
        in the returned pending object.  Returns (pending, fallback_idx):
        objects that cannot be served at the epoch (composite maps,
        promoted keys, tables with no frozen buffer) are listed in
        ``fallback_idx`` for the caller's locked path."""
        n = len(objects)
        vals: List[Any] = [None] * n
        fallback: List[int] = []
        need: Dict[str, list] = {}
        m = self.metrics
        n_cached = 0
        for i, (key, type_name, bucket) in enumerate(objects):
            ty = get_type(type_name) if is_type(type_name) else None
            if ty is None or getattr(ty, "composite", False):
                fallback.append(i)
                continue
            dk = (key, bucket)
            hit = self.snapshot_cache_get(dk, ep, type_name)
            if hit is not _CACHE_MISS:
                vals[i] = hit
                n_cached += 1
                continue
            ent = self.directory.get(dk)
            if ent is None:
                if self.cold is not None and self.cold.is_cold(dk):
                    fallback.append(i)  # faulted in by the locked path
                    continue
                vals[i] = self._bottom_value(type_name)
                continue
            if dk in ep.promoted:
                fallback.append(i)
                continue
            tname_t, shard, row = ent
            if split_tier(tname_t)[0] != type_name:
                fallback.append(i)  # type clash: locked path raises it
                continue
            slot = ep.tables.get(tname_t)
            ur = ep.used_rows.get(tname_t)
            if slot is None or ur is None:
                fallback.append(i)
                continue
            if row >= ur[shard]:
                # row allocated after the epoch: invisible at E
                vals[i] = self._bottom_value(type_name)
                continue
            need.setdefault(tname_t, []).append((i, shard, row))
        if m is not None and n_cached:
            m.serving_reads.inc(n_cached, path="cache")
        launches = []
        for tname_t, items in need.items():
            t = self.table(tname_t)
            slot = ep.tables[tname_t]
            mcount = len(items)
            if self.mesh is not None and t.sharding is not None:
                # mesh table (ISSUE 10): ROUTED per-shard gather through
                # the explicit shard_map — each device gathers its own
                # shards' rows from its local slice of the frozen epoch
                # buffers; the result stays one (sharded) device array,
                # no host-side concat on the hot path
                ss = np.asarray([x[1] for x in items], np.int64)
                rr = np.asarray([x[2] for x in items], np.int64)
                t_route = time.monotonic()
                with span("serve.route", table=tname_t, rows=mcount):
                    row_mat, pos = t._route(ss, rr)
                    row_gather = np.minimum(row_mat, t.n_rows - 1)
                    p, mm = row_mat.shape
                    vc_mat = np.zeros((p, mm, ep.vc.shape[-1]), np.int32)
                    vc_mat[pos[:, 0], pos[:, 1]] = ep.vc
                self.mesh.note_routed(ss, p * mm,
                                      time.monotonic() - t_route)
                resolved, fresh = self.mesh.epoch_gather(
                    t, slot["head"], slot["head_vc"], row_gather, vc_mat
                )
                launches.append((tname_t, items, resolved, fresh, pos))
            else:
                resolved, fresh = t._latest_resolved_flat_fn(
                    slot["head"], slot["head_vc"], t._stage_reads(
                        [x[1] for x in items], [x[2] for x in items], ep.vc,
                        mb=_bucket(mcount, t.cfg.batch_buckets))
                )
                launches.append((tname_t, items, resolved, fresh, None))
            if m is not None:
                m.serving_reads.inc(mcount, path="gather")
        gathered = {x[0] for items in need.values() for x in items}
        return (_EpochReadPending(ep, objects, vals, launches, gathered),
                fallback)

    def epoch_read_finish(self, pending: "_EpochReadPending") -> List[Any]:
        """Materialize + decode a launched epoch read batch (the ONLY
        stage allowed to block on the device) and back-fill the snapshot
        cache.  Returns the decoded values in object order (entries for
        objects the caller rerouted stay None)."""
        from antidote_tpu.crdt.base import RESOLVE_OVERFLOW

        ep = pending.ep
        vals = pending.vals
        for tname_t, items, resolved, fresh, pos in pending.launches:
            t = self.table(tname_t)
            ty = t.ty
            # routed (mesh) launches materialize the global [P, M']
            # array in ONE transfer here — the writeback stage owns the
            # sync; unrouting is host indexing, never a concat loop
            with span("serve.device_wait", table=tname_t, rows=len(items)):
                host = {f: np.asarray(x) for f, x in resolved.items()}
            pending.t_synced = time.monotonic()
            del fresh  # provably all-fresh: frozen head_vc ≤ cap ≤ E
            has_resolve = ty.resolve_spec(t.cfg) is not None
            slot = ep.tables[tname_t]
            with span("serve.wb_host.decode", table=tname_t,
                      rows=len(items)):
                filled = []
                over = []
                for j, (i, shard, row) in enumerate(items):
                    if pos is not None:
                        view = {f: x[pos[j, 0], pos[j, 1]]
                                for f, x in host.items()}
                    else:
                        view = {f: x[j] for f, x in host.items()}
                    if has_resolve:
                        v = ty.value_from_resolved(view, self.blobs, t.cfg)
                        if v is RESOLVE_OVERFLOW:
                            over.append(len(filled))
                    else:
                        v = ty.value(view, self.blobs, t.cfg)
                    key, _tn, bucket = pending.objects[i]
                    filled.append([(key, bucket), shard, row, v])
                if over:
                    # truncated top-count views (a set of more than
                    # resolve_top elements): the launch's full frozen
                    # states in one gather and one transfer
                    full, _vc = t.gather_rows_dispatch(
                        [filled[n][1] for n in over],
                        [filled[n][2] for n in over],
                        slot["head"], slot["head_vc"])
                    full = _cut(full, len(over))
                    for k, n in enumerate(over):
                        filled[n][3] = ty.value(
                            {f: x[k] for f, x in full.items()},
                            self.blobs, t.cfg)
                for (i, _s, _r), ent in zip(items, filled):
                    vals[i] = ent[3]
                # after the launch's last decode and before any reply of
                # the batch: a client that has its answer finds the key
                # in the mirror, at an epoch that is still pinned
                self.snapshot_cache_fill(ep, tname_t, filled)
        return vals

    # ------------------------------------------------------------------
    # decoded-value cache (serving hot path)
    # ------------------------------------------------------------------
    def value_cache_get(self, key, bucket, read_vc_tuple):
        """Cached decoded value, or None-marker miss.  Valid iff the read
        VC dominates the fill clock (then the unchanged key's latest
        state IS the cached one)."""
        with self._value_cache_lock:
            ent = self._value_cache.get((key, bucket))
            if ent is None:
                return _CACHE_MISS
            value, fill_vc = ent
            if all(r >= f for r, f in zip(read_vc_tuple, fill_vc)):
                self._value_cache.move_to_end((key, bucket))
                return _copy_out(value)
        return _CACHE_MISS

    def value_cache_bulk_get(self, objects, read_vc_tuple):
        """One-pass cache probe for a batch: returns (values, miss_idx).
        When the read VC covers the store's current applied max, every
        present entry is valid (entries always hold the key's latest
        value) — one comparison for the whole batch instead of one per
        entry."""
        cache = self._value_cache
        out: List[Any] = [None] * len(objects)
        miss: List[int] = []
        if all(r >= f for r, f in zip(read_vc_tuple,
                                      self.applied_vc.max(axis=0))):
            with self._value_cache_lock:
                for j, (key, _t, bucket) in enumerate(objects):
                    ent = cache.get((key, bucket))
                    if ent is None:
                        miss.append(j)
                    else:
                        cache.move_to_end((key, bucket))
                        out[j] = _copy_out(ent[0])
            return out, miss
        for j, (key, _t, bucket) in enumerate(objects):
            hit = self.value_cache_get(key, bucket, read_vc_tuple)
            if hit is _CACHE_MISS:
                miss.append(j)
            else:
                out[j] = hit
        return out, miss

    def value_cache_fill(self, key, bucket, value, fill_vc_tuple,
                         epoch: int) -> None:
        """Record a LATEST-read decode.  ``fill_vc_tuple`` must be the
        store-wide max applied VC captured BEFORE the read and ``epoch``
        the mutation epoch at the same point — a concurrent commit in
        between drops the fill instead of caching a value that claims
        coverage it does not have."""
        if epoch != self.mutation_epoch or self._mutating:
            return
        # own a copy: the caller's value is handed to the client, who may
        # mutate it
        with self._value_cache_lock:
            self._value_cache[(key, bucket)] = (
                _copy_out(value), fill_vc_tuple
            )
            while len(self._value_cache) > self._value_cache_cap:
                self._value_cache.popitem(last=False)

    def applied_max_tuple(self) -> tuple:
        return tuple(int(x) for x in self.applied_vc.max(axis=0))

    # ------------------------------------------------------------------
    def _tier_for_lanes(self, ty, len_a: int, len_b: int) -> int:
        """Smallest tier whose effect-lane widths fit the given lanes
        (register_mv observed-id lanes scale with the origin's tier)."""
        tier = 0
        while tier < _MAX_TIER:
            cfg_t = scaled_cfg(self.cfg, tier)
            if len_a <= ty.eff_a_width(cfg_t) and len_b <= ty.eff_b_width(cfg_t):
                return tier
            tier += 1
        raise OverflowError(
            f"{ty.name}: effect lanes ({len_a}, {len_b}) exceed every slot "
            f"tier up to {_MAX_TIER}"
        )

    def _promote_key(self, dk, extra_demand: int = 0, min_tier: int = 0) -> None:
        """Migrate one key to a wider-slot tier table, exactly.

        The whole per-key device state moves — head, snapshot versions,
        op ring — embedded into the wider layout by zero-padding the
        widened slot axes (zeros are empty slots in every slotted layout)
        and the op lanes.  The migration happens BEFORE the batch that
        would overflow applies, so no op is ever dropped; the reference's
        unbounded set/map/rga growth is matched tier by tier."""
        tname_t, shard, row = self.directory[dk]
        base, tier = split_tier(tname_t)
        ty = get_type(base)
        t_old = self.table(tname_t)
        t0 = time.monotonic()
        state = t_old.row_state(shard, row)
        used = ty.used_slots(
            {f: np.asarray(x) for f, x in state["head"].items()})
        cap_cur = ty.slot_capacity(t_old.cfg)
        if (min_tier <= tier and cap_cur is not None
                and used + extra_demand <= cap_cur):
            # the conservative bound went stale (add/remove or re-add
            # churn): the key actually fits its current tier — re-tighten
            # the bound in place instead of ratcheting up a tier
            t_old.slots_ub[shard, row] = used + extra_demand
            return
        new_tier = max(tier + 1, min_tier)
        while True:
            if new_tier > _MAX_TIER:
                raise OverflowError(
                    f"{base} key {dk!r}: {used + extra_demand} slots exceed "
                    f"the widest tier ({_MAX_TIER})"
                )
            cap = ty.slot_capacity(scaled_cfg(self.cfg, new_tier))
            if cap is None or used + extra_demand <= cap:
                break
            new_tier += 1
        with span("commit.promote", tier=new_tier):
            t_new = self.table(tiered_name(base, new_tier))
            new_row = t_new.alloc_row(shard)
            # the row's state goes from table to table on the device: a
            # gather of one row, then row writes in both tables' own
            # layouts (TypedTable.install_row / clear_rows)
            t_new.install_row(shard, new_row, state, t_new.next_seq)
            t_old.clear_rows([shard], [row])
        t_new.next_seq += int(t_old.next_seq)
        t_new.n_ops[shard, new_row] = t_old.n_ops[shard, row]
        t_new.slots_ub[shard, new_row] = used + extra_demand
        t_new.max_abs_delta = max(t_new.max_abs_delta, t_old.max_abs_delta)
        np.maximum(t_new.max_commit_vc, t_old.max_commit_vc,
                   out=t_new.max_commit_vc)
        t_old.n_ops[shard, row] = 0
        t_old.slots_ub[shard, row] = 0
        # both tables mutated outside the append path: the LADDER's
        # frozen epoch copies would serve the pre-promotion (old table) /
        # bottom (new table) row — drop them.  The SERVING double buffer
        # survives: the move touches exactly two rows, both marked dirty
        # below (re-frozen at the next publish), and the promoted mark
        # makes epoch readers fall back for this key meanwhile — a
        # promotion no longer costs a whole-store epoch invalidation
        # (which forced full-table copy republishes, a Zipf-workload
        # serving-latency cliff).
        t_old.epochs.clear()
        t_new.epochs.clear()
        t_old.note_serving_touch(np.asarray([shard]), np.asarray([row]))
        t_new.note_serving_touch(np.asarray([shard]), np.asarray([new_row]))
        # mark the key promoted on every live epoch BEFORE the directory
        # flips: a lock-free epoch reader that sees the new entry also
        # sees the promoted mark and falls back (GIL-ordered)
        self.mark_epoch_fallback(dk)
        self.directory[dk] = (tiered_name(base, new_tier), shard, new_row)
        self.note_ckpt_dirty(dk)
        self.promotions += 1
        self.promotions_by_tier[new_tier] = (
            self.promotions_by_tier.get(new_tier, 0) + 1)
        self.promote_seconds += time.monotonic() - t0
        if new_tier < _MAX_TIER:
            # a key that grows fast is through a tier's upper half before
            # the next tier is compiled: start on it at the key's entry
            self.prepare_tier(tiered_name(base, new_tier + 1))

    # ------------------------------------------------------------------
    def read_states(
        self, objects: Sequence[BoundObject], read_vc: np.ndarray,
        served: bool = False,
    ) -> List[Dict[str, np.ndarray]]:
        """Materialized per-key states for a batch of bound objects, each
        at its own read VC (``read_vc``: ``[n, D]``, or one VC that every
        row reads at), grouped by table into batched device folds.
        ``served``: the rows are a client's reads, counted as the locked
        read plane's (``pipeline.fold.reads_by_head`` / ``reads_by_fold``)."""
        read_vcs = _row_vcs(read_vc, len(objects))
        by_type: Dict[str, list] = {}
        out: List[Dict[str, np.ndarray] | None] = [None] * len(objects)
        for i, (key, type_name, bucket) in enumerate(objects):
            ent = self.locate(key, type_name, bucket, create=False)
            if ent is None:
                # never-written key: the bottom state (Type:new()), no row
                # allocated — reads must not grow the tables
                ty = get_type(type_name)
                out[i] = {
                    f: np.zeros(shape, dtype)
                    for f, (shape, dtype) in ty.state_spec(self.cfg).items()
                }
                continue
            tname_t, shard, row = ent
            by_type.setdefault(tname_t, []).append((i, shard, row))
        for tname_t, items in by_type.items():
            t = self.table(tname_t)
            shards = np.asarray([x[1] for x in items], np.int64)
            rows = np.asarray([x[2] for x in items], np.int64)
            vcs = read_vcs[[x[0] for x in items]]
            # head gather first (exact for rows whose head VC ≤ read VC:
            # a remove's downstream reads the state it observes this way,
            # a tenth of a fill), then the versioned snapshot + ring fold
            # at the read VC for the stale rows
            state, fresh = t.read_latest(shards, rows, vcs)
            idxs = np.nonzero(~fresh)[0]  # positions within this batch
            if served:
                t.reads_by_head += len(rows) - len(idxs)
                t.reads_by_fold += len(idxs)
            complete = np.ones(0, bool)
            if len(idxs):
                s2, _, complete = t.read(shards[idxs], rows[idxs],
                                         vcs[idxs])
                for f in state:
                    state[f][idxs] = s2[f]
            if not complete.all():
                # below retained device coverage: host log-replay
                # fallback (get_from_snapshot_log,
                # /root/reference/src/materializer_vnode.erl:415-419);
                # group by shard so each shard's WAL is scanned once
                by_shard: Dict[int, list] = {}
                for j in (int(idxs[j]) for j in np.nonzero(~complete)[0]):
                    gi = items[j][0]  # global object index
                    key, _, bucket = objects[gi]
                    by_shard.setdefault(items[j][1], []).append(
                        (j, key, tname_t, bucket, vcs[j])
                    )
                for shard, wants in by_shard.items():
                    reps = self._replay_read_many(shard, wants)
                    for j, rep in reps.items():
                        for f in state:
                            state[f][j] = rep[f]
            for j, (i, _, _) in enumerate(items):
                out[i] = {f: x[j] for f, x in state.items()}
        if self.cold is not None:
            # a read batch that faulted cold rows in can overshoot the
            # resident budget (reads never evict mid-batch — a row
            # located earlier in THIS batch must survive its gather);
            # here everything is materialized host-side, so re-enforce
            self.cold.maybe_evict()
        return out  # type: ignore[return-value]

    def _bottom_resolved(self, type_name: str) -> Dict[str, np.ndarray]:
        """The resolved view of a never-written key (Type:new()) — constant
        per type, computed once and copied, never a per-key device launch."""
        hit = self._bottom_cache.get(type_name)
        if hit is None:
            ty = get_type(type_name)
            zero = {
                f: np.zeros(shape, dtype)
                for f, (shape, dtype) in ty.state_spec(self.cfg).items()
            }
            if ty.resolve_spec(self.cfg) is not None:
                hit = {
                    f: np.asarray(x)
                    for f, x in ty.resolve(self.cfg, zero).items()
                }
            else:
                hit = zero
            self._bottom_cache[type_name] = hit
        return {f: x.copy() for f, x in hit.items()}

    def read_resolved(
        self, objects: Sequence[BoundObject], read_vc: np.ndarray,
        full_out: Dict[int, Dict[str, np.ndarray]] | None = None,
    ) -> List[Dict[str, np.ndarray]]:
        """Serving fast path: batched reads with DEVICE value resolution.

        One launch per touched type does freshness check + versioned fold +
        ``Type.resolve`` compaction (TypedTable.read_resolved); only the
        compact value view crosses the host boundary — the batched,
        device-resident rendering of the read path in SURVEY §3.3
        (materializer_vnode:read + cure:transform_reads).  Types without a
        ``resolve_spec`` return their full state; rows below retained
        device coverage fall back to the host log replay + host-side
        resolution.

        When ``full_out`` is given, the caller can decode a full state
        and wants it wherever the resolved view would not do: full
        states are recorded there keyed by object index (and returned in
        place of the view) for rows the replay fallback rebuilt (no
        second log replay for them) and for rows that hold, by the
        host's count of their slots, more than the view's
        ``resolve_top`` — :meth:`read_states` answers those at once,
        where the view's launch would only say "truncated".

        ``read_vc`` is ``[n, D]``, a row's own read VC (the reads of
        several transactions in one batch), or one VC that every row
        reads at."""
        read_vcs = _row_vcs(read_vc, len(objects))
        out: List[Dict[str, np.ndarray] | None] = [None] * len(objects)
        by_type: Dict[str, list] = {}
        wide: List[int] = []
        for i, (key, type_name, bucket) in enumerate(objects):
            ent = self.locate(key, type_name, bucket, create=False)
            if ent is None:
                out[i] = self._bottom_resolved(type_name)
                continue
            tname_t, shard, row = ent
            if full_out is not None:
                t = self.table(tname_t)
                if (t.slots_ub[shard, row] > t.ty.resolve_top
                        and t.ty.resolve_spec(self.cfg) is not None):
                    wide.append(i)
                    continue
            by_type.setdefault(tname_t, []).append((i, shard, row))
        if wide:
            states = self.read_states(
                [objects[i] for i in wide], read_vcs[wide], served=True)
            for i, st in zip(wide, states):
                out[i] = full_out[i] = st
        for tname_t, items in by_type.items():
            t = self.table(tname_t)
            ty = t.ty
            shards = np.asarray([x[1] for x in items], np.int64)
            rows = np.asarray([x[2] for x in items], np.int64)
            vcs = read_vcs[[x[0] for x in items]]
            resolved, _, complete = t.read_resolved(shards, rows, vcs)
            for j, (i, _, _) in enumerate(items):
                out[i] = {f: x[j] for f, x in resolved.items()}
            if not complete.all():
                # host log-replay fallback + host-side resolution
                bad = [j for j in np.nonzero(~complete)[0]]
                by_shard: Dict[int, list] = {}
                for j in bad:
                    gi = items[j][0]
                    key, _, bucket = objects[gi]
                    by_shard.setdefault(items[j][1], []).append(
                        (int(j), key, tname_t, bucket, vcs[j])
                    )
                for shard, wants in by_shard.items():
                    reps = self._replay_read_many(shard, wants)
                    for j, rep in reps.items():
                        gi = items[j][0]
                        if full_out is not None:
                            # caller decodes the full state directly; a
                            # host-side resolve launch here would be
                            # wasted work on the replay (slowest) branch
                            full_out[gi] = rep
                            out[gi] = rep
                        elif ty.resolve_spec(self.cfg) is not None:
                            out[gi] = {
                                f: np.asarray(x)
                                for f, x in ty.resolve(t.cfg, rep).items()
                            }
                        else:
                            out[gi] = rep
        if self.cold is not None:
            self.cold.maybe_evict()  # see read_states: post-batch only
        return out  # type: ignore[return-value]

    def read_values(
        self, objects: Sequence[BoundObject], read_vc: np.ndarray
    ) -> List[Any]:
        """Client-visible values (Type:value per object, cure:transform_reads,
        /root/reference/src/cure.erl:186-192)."""
        states = self.read_states(objects, read_vc)
        return [
            get_type(type_name).value(states[i], self.blobs, self.cfg)
            for i, (_, type_name, _) in enumerate(objects)
        ]

    # ------------------------------------------------------------------
    def _replay_read_many(self, shard: int, wants):
        """Several keys' states, each at its own read VC, rebuilt from
        the shard's durable log: the answer to a read whose snapshot is
        older than the device still holds history for (a row keeps what
        came after its last GC).  ``wants`` = [(result_idx, key,
        tiered_name, bucket, read_vc)] — a state is rebuilt at the key's
        CURRENT tier width
        (wide enough for every logged effect, since the live store
        promoted before any wide effect applied).

        A key's logged effects come from the log's index by key
        (``LogManager.key_history``: no scan after the key's first), and
        are folded onto the key's replay base when that is not newer
        than the read VC — what such a read costs is the effects since
        the base, not the log's length nor the key's."""
        if self.log is None:
            raise RuntimeError(
                f"incomplete read for {[w[1] for w in wants]!r} and no log "
                "attached: read VC below retained snapshot coverage"
            )
        if (int(self.log.floor_seqs[shard]) > 0
                or self.log.chain_floor[shard].any()):
            # the shard's WAL was compacted below a checkpoint floor
            # (chain_floor alone marks a shard IMPORTED from a compacted
            # source — its ride-along log was tail-only): the
            # prefix this rebuild would need is covered only by the image
            # (which holds heads, not per-op history), so replaying the
            # tail alone would silently produce a state missing the
            # pre-checkpoint ops.  Surface the horizon instead — the
            # reference's prune_ops draws the same line at the min cached
            # snapshot (SURVEY §2.3), lifted here to the store level.
            raise RuntimeError(
                f"read below the compaction horizon for "
                f"{[w[1] for w in wants]!r}: shard {shard}'s log is "
                "checkpoint-truncated and no longer holds history below "
                "the checkpoint stamp"
            )
        t0 = time.monotonic()
        with span("serve.replay", shard=shard, keys=len(wants)), \
                self._replay_lock:
            out = {
                j: self._replay_one(shard, key, bucket, tname_t, read_vc)
                for j, key, tname_t, bucket, read_vc in wants
            }
        self.replays += len(wants)
        self.replay_seconds += time.monotonic() - t0
        return out

    #: effects a replay base leaves unfolded at most; one that has more
    #: moves up so that the newer half stay (a transaction's snapshot is
    #: seldom older than they are), and keys that keep a base
    _REPLAY_TAIL_MAX = 256
    _REPLAY_BASES = 256

    def _replay_one(self, shard, key, bucket, tname_t, read_vc):
        base, tier = split_tier(tname_t)
        ty = get_type(base)
        cfg_t = scaled_cfg(self.cfg, tier)
        hist = self.log.key_history(shard, key, bucket)
        n = len(hist)  # (an append may follow: this read ends here)
        dk = (key, bucket)
        ent = self._replay_bases.get(dk)
        if ent is not None and ent[0] != tname_t:
            # promoted since: its state has the old tier's width
            del self._replay_bases[dk]
            ent = None
        fold = functools.partial(self._fold_log, ty, cfg_t)
        if ent is None or not (ent[2] <= read_vc).all():
            # no base, or one that is newer than the snapshot (it stays
            # for the reads that come after): from the bottom state
            state0 = {f: np.zeros(shape, dtype) for f, (shape, dtype)
                      in ty.state_spec(cfg_t).items()}
            zero = np.zeros_like(read_vc)
            self.replay_records += n
            state = fold(state0, hist[:n], zero, read_vc, bottom=True)
            if ent is None:
                self._replay_bases[dk] = [
                    tname_t, state, read_vc.copy(),
                    [o for o in hist[:n] if not (o[2] <= read_vc).all()], n]
                while len(self._replay_bases) > self._REPLAY_BASES:
                    self._replay_bases.popitem(last=False)
            return state
        self._replay_bases.move_to_end(dk)
        _, state0, base_vc, tail, pos = ent
        tail.extend(hist[pos:n])
        ent[4] = n
        self.replay_records += len(tail)
        state = fold(state0, tail, base_vc, read_vc)
        if len(tail) > self._REPLAY_TAIL_MAX:
            new_vc = np.maximum(base_vc, tail[-self._REPLAY_TAIL_MAX // 2][2])
            ent[1] = fold(state0, tail, base_vc, new_vc)
            ent[2] = new_vc
            ent[3] = [o for o in tail if not (o[2] <= new_vc).all()]
        return state

    def _fold_log(self, ty, cfg_t, state0, ops, base_vc, read_vc,
                  bottom: bool = False):
        """``state0`` (which holds what is <= ``base_vc``) with those of
        ``ops`` [(eff_a, eff_b, commit_vc, origin)] folded in that are
        <= ``read_vc`` and not <= ``base_vc``, as host arrays."""
        l = len(ops)
        if l == 0:
            return state0
        t0 = time.monotonic()
        wa, wb = ty.eff_a_width(cfg_t), ty.eff_b_width(cfg_t)
        state, strategy = self._fold_over_ring(
            ty, cfg_t, state0,
            np.stack([_pad_lane(o[0], wa, np.int64) for o in ops]),
            np.stack([_pad_lane(o[1], wb, np.int32) for o in ops]),
            np.stack([o[2] for o in ops]),
            np.asarray([o[3] for o in ops], np.int32),
            l, base_vc, read_vc, bottom=bottom)
        state = jax.device_get(state)  # sync-ok: replay fallback path
        # materializes host states for the caller (one wait for all)
        self._observe_fold(strategy, ty.name, time.monotonic() - t0)
        return state

    def _warm_replay(self, ty, cfg_t, with_base: bool) -> None:
        """Compile the log replay's serial fold for a tier's widths (and
        the base table's with the first tier) at its two shortest padded
        lengths, on a log of nothing visible."""
        for cfg in [cfg_t] + ([self.cfg] if with_base else []):
            state0 = {f: np.zeros(shape, dtype) for f, (shape, dtype)
                      in ty.state_spec(cfg).items()}
            vc = np.zeros((self.cfg.max_dcs,), np.int32)
            for l in (64, 256):
                # kind 1 in lane 0 keeps an add-only type off its
                # associative fold: the serial scan is what compiles
                self._fold_over_ring(
                    ty, cfg, state0,
                    np.zeros((l, ty.eff_a_width(cfg)), np.int64),
                    np.ones((l, ty.eff_b_width(cfg)), np.int32),
                    np.ones((l, self.cfg.max_dcs), np.int32),
                    np.zeros((l,), np.int32), l, vc, vc)

    def _fold_over_ring(self, ty, cfg_t, state0, ops_a, ops_b, ops_vc,
                        ops_origin, l, base_vc, read_vc, bottom=True):
        """Route one host-assembled op log (leading axis L; ``bottom``:
        onto the bottom state) to a fold strategy; returns (device state
        pytree, strategy name).

        Strategy ladder (docs/performance.md "Sequence-axis parallel
        folds"):

        * ``mesh_assoc`` — assoc-safe log of ≥ fold_chunk ops with a mesh
          attached: op axis sharded over devices, partial deltas merged
          in sequence order (``MeshServingPlane.fold_giant_key``).
        * ``assoc`` — assoc-safe log of ≥ fold_chunk ops: one
          O(log L)-depth delta window.
          Assoc-safe = ``ty.supports_assoc``, plus (set_aw) an all-adds
          log, plus a bottom base where the type's delta is exact from
          that alone (``assoc_bottom_only``).
        * ``long`` — order-sensitive log over fold_chunk ops: chunked
          scan, zero-padded to a chunk multiple (pad slots sit at index
          ≥ n_ops, so the inclusion mask drops them).
        * ``serial`` — short order-sensitive log: plain masked scan.
        """
        from antidote_tpu.materializer import longlog

        import jax.numpy as jnp

        chunk = max(int(getattr(self.cfg, "fold_chunk", 4096)), 2)
        assoc_ok = ty.supports_assoc and (
            not ty.assoc_add_only or not (ops_b[:, 0] == 1).any()
        ) and (bottom or not ty.assoc_bottom_only)
        n_ops = np.int32(l)
        if assoc_ok and self.mesh is not None and l >= chunk:
            state, _ = self.mesh.fold_giant_key(
                ty, cfg_t, state0, ops_a, ops_b, ops_vc, ops_origin,
                n_ops, base_vc, read_vc,
            )
            return state, "mesh_assoc"
        if assoc_ok and l >= chunk:
            # (a short log takes the compiled serial scan below: the
            # associative fold runs op by op, ~50 small programs that
            # compile anew for every log length, under the commit lock —
            # it pays from a chunk's length on, where depth matters)
            state, _ = longlog.assoc_fold(
                ty, cfg_t, state0, jnp.asarray(ops_a), jnp.asarray(ops_b),
                jnp.asarray(ops_vc), jnp.asarray(ops_origin), n_ops,
                jnp.asarray(base_vc), jnp.asarray(read_vc),
            )
            return state, "assoc"
        def padded(x, to):  # pad slots sit at index ≥ n_ops: masked out
            return np.concatenate(
                [x, np.zeros((to - l,) + x.shape[1:], x.dtype)]
            ) if to > l else x

        if l > chunk:
            to = l + (-l) % chunk
            state, _ = longlog.fold_long(
                ty, cfg_t, state0, jnp.asarray(padded(ops_a, to)),
                jnp.asarray(padded(ops_b, to)),
                jnp.asarray(padded(ops_vc, to)),
                jnp.asarray(padded(ops_origin, to)), n_ops,
                jnp.asarray(base_vc), jnp.asarray(read_vc), chunk=chunk,
            )
            return state, "long"
        # one compiled scan a (type, tier) at two padded lengths, 64 and
        # 256, a longer log in pieces of 256: an eager scan compiles
        # anew for every log length, under the commit lock, and these
        # two a served mix has met before its first replay
        # (_warm_replay); a padded slot costs a masked step
        prog = self._replay_fold_fns.get((ty.name, cfg_t))
        if prog is None:
            prog = self._replay_fold_fns[(ty.name, cfg_t)] = _ReplayFold(
                ty, cfg_t, cfg_t.max_dcs)
        # a host state crosses inside the first piece's operand; a
        # device one (and every piece's result) stays where it is
        host = not any(isinstance(x, jax.Array)
                       for x in jax.tree.leaves(state0))
        state = prog.zero if host else state0
        for lo in range(0, l, 256):
            n = min(l - lo, 256)
            sl = slice(lo, lo + n)
            state, _ = prog.fn(prog.stage(
                ops_a[sl], ops_b[sl], ops_vc[sl], ops_origin[sl],
                64 if n <= 64 else 256, base_vc, read_vc,
                state0 if host and lo == 0 else None), state)
        return state, "serial"

    def _observe_fold(self, strategy: str, tname: str, seconds: float):
        """Tally a replay-path fold dispatch (host dict + metrics)."""
        self.replay_fold_dispatches[strategy] = (
            self.replay_fold_dispatches.get(strategy, 0) + 1
        )
        m = self.metrics
        if m is not None:
            fd = getattr(m, "fold_dispatch", None)
            if fd is not None:
                fd.inc(strategy=strategy)
            fs = getattr(m, "fold_seconds", None)
            if fs is not None:
                fs.observe(seconds, strategy=strategy, type=tname)

    def materializer_status(self) -> dict:
        """The node-status ``materializer`` block: which fold strategies
        the serving/replay paths actually dispatched, plus the knobs."""
        per_table: Dict[str, int] = {}
        for t in self.tables.values():
            for s, n in t.fold_dispatches.items():
                per_table[s] = per_table.get(s, 0) + n
        out = {
            "use_pallas": bool(getattr(self.cfg, "use_pallas", False)),
            "fold_chunk": int(getattr(self.cfg, "fold_chunk", 4096)),
            "serving_folds": per_table,
            "replay_folds": dict(self.replay_fold_dispatches),
        }
        if self.mesh is not None:
            out["giant_folds"] = self.mesh.giant_folds
        return out

    def recover(self, track_origin: int | None = None) -> Dict:
        """Rebuild tables, clocks, blobs and op-id chains from the log
        (boot-time recover_from_log,
        /root/reference/src/materializer_vnode.erl:192-216 and op-id scan,
        /root/reference/src/logging_vnode.erl:595-643).

        When ``track_origin`` is given, returns {(key, bucket): last commit
        counter at that origin} — used to rebuild the certification table.
        """
        assert self.log is not None
        last_commit: Dict = {}
        #: records replayed by the last recover() call (the recovery
        #: observability satellite; tail-only under a checkpoint floor)
        self.last_recovery_records = 0
        saved_cap = None
        if self.cold is not None:
            # replay is operator-paced: a fault-rate cap sized for
            # client traffic must not refuse the tail's own fault-ins
            # (the node would fail to boot at the same record forever)
            saved_cap, self.cold.fault_rate_cap = \
                self.cold.fault_rate_cap, 0.0
        try:
            return self._recover_inner(track_origin, last_commit)
        finally:
            if self.cold is not None and saved_cap is not None:
                self.cold.fault_rate_cap = saved_cap

    def _recover_inner(self, track_origin, last_commit) -> Dict:
        for shard in range(self.cfg.n_shards):
            batch: List[Effect] = []
            vcs: List[np.ndarray] = []
            orgs: List[int] = []
            for rec in self.log.replay_shard(shard):
                self.last_recovery_records += 1
                eff = effect_from_rec(rec)
                for h, data in eff.blob_refs:
                    self.blobs.intern_bytes(h, data)
                    # already durable: don't re-log these payloads later
                    self.log._blob_seen[shard].add(h)
                eff.blob_refs = []  # re-logging during replay is disabled
                batch.append(eff)
                vcs.append(np.asarray(rec["vc"], np.int32))
                orgs.append(int(rec["o"]))
                self.log.op_ids[shard, rec["o"]] = max(
                    self.log.op_ids[shard, rec["o"]], rec["id"]
                )
                if track_origin is not None and rec["o"] == track_origin:
                    last_commit[(freeze_key(rec["k"]), rec["b"])] = int(
                        rec["vc"][track_origin]
                    )
                if len(batch) >= 4096:
                    self._apply_recovered(batch, vcs, orgs)
                    batch, vcs, orgs = [], [], []
            if batch:
                self._apply_recovered(batch, vcs, orgs)
        return last_commit

    def _apply_recovered(self, batch, vcs, orgs):
        log, self.log = self.log, None  # don't re-log during replay
        try:
            self.apply_effects(batch, vcs, orgs)
        finally:
            self.log = log

    def stable_vc(self) -> np.ndarray:
        """DC-wide stable snapshot = entry-wise min of per-shard clocks
        (stable_time_functions:get_min_time,
        /root/reference/src/stable_time_functions.erl:51-85).  A
        mesh-resident store (ISSUE 10) computes it as the ``pmin``
        collective over the per-device applied clocks — identical by
        construction, cached per clock version; otherwise it is
        :func:`stable_min_of`, the host ``min``."""
        if self.mesh is not None:
            return self.mesh.stable_vc(self.applied_vc)
        return stable_min_of(self.applied_vc)

    def dc_max_vc(self) -> np.ndarray:
        """Entry-wise max of per-shard clocks — the freshest local view."""
        return self.applied_vc.max(axis=0)
