"""Transaction layer: snapshot transactions over the sharded store.

The host-side rebuild of the reference's Cure/ClockSI protocol stack
(``cure`` + ``clocksi_interactive_coord`` + ``clocksi_vnode``; SURVEY
§2.2, §3.1-3.3), restructured for a single-writer-per-replica host
runtime in front of batched device kernels:

  * snapshot selection: txn snapshot VC = freshest local applied VC merged
    with the client's causal clock (create_transaction_record,
    /root/reference/src/clocksi_interactive_coord.erl:675-702).  Clocks are
    logical per-DC commit counters, so the reference's physical-clock waits
    (wait_for_clock / check_clock) vanish.
  * reads: batched device materializer folds at the snapshot VC, with the
    transaction's own pending writes overlaid on top (the analogue of
    apply_tx_updates_to_snapshot → materialize_eager,
    /root/reference/src/clocksi_interactive_coord.erl:882-894).
  * updates: type-check against the CRDT registry, run pre-commit hooks,
    generate downstream effects (reading current state when the type
    requires it — clocksi_downstream:generate_downstream_op,
    /root/reference/src/clocksi_downstream.erl:38-68), buffer in the
    write-set.
  * commit: first-committer-wins certification per key (the ETS
    committed_tx check, /root/reference/src/clocksi_vnode.erl:588-632),
    then a single commit-counter bump mints the commit VC and the effects
    are applied to the device tables in commit order.
"""

from __future__ import annotations

import errno
import functools
import itertools
import logging
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from antidote_tpu.config import AntidoteConfig
from antidote_tpu.crdt import TYPES, get_type, is_type
from antidote_tpu.obs.trace import PhaseAccumulator, device_program, span
from antidote_tpu.overload import (
    BusyError,
    DeadlineExceeded,
    InsufficientRightsError,
    ReadOnlyError,
    check_deadline,
)
from antidote_tpu.store.kv import BoundObject, Effect, KVStore
from antidote_tpu.txn.bcounter import BCounterManager
from antidote_tpu.txn.hooks import HookRegistry

log = logging.getLogger(__name__)

@functools.lru_cache(maxsize=1)
def _composite_names() -> frozenset:
    return frozenset(
        n for n, t in TYPES.items() if getattr(t, "composite", False)
    )


@functools.lru_cache(maxsize=256)
def _jitted_apply(ty_name: str, cfg: AntidoteConfig):
    """Compiled single-effect fold for the write-set overlay: a txn
    overlaying N of its own effects would otherwise dispatch ~25 eager
    primitives per effect (the rga populate hot spot)."""
    ty = get_type(ty_name)
    return device_program(f"overlay_apply_{ty_name}",
                          functools.partial(ty.apply, cfg))


Update = Tuple[Any, str, str, Tuple[str, Any]]  # (key, type_name, bucket, op)


class AbortError(Exception):
    """Transaction aborted (certification conflict or pre-commit hook)."""


class Transaction:
    _ids = itertools.count(1)

    def __init__(self, snapshot_vc: np.ndarray, props: Optional[dict] = None):
        self.txid = next(Transaction._ids)
        self.snapshot_vc = np.asarray(snapshot_vc, np.int32)
        self.props = dict(props or {})
        self.writeset: List[Tuple[Effect, Tuple[str, Any]]] = []
        self.active = True
        #: (key, bucket) -> base state at the snapshot, cached across the
        #: txn's state-dependent downstream generations — a txn inserting
        #: N elements into one rga reads the device state ONCE and
        #: overlays its own growing writeset on host (the r3 VERDICT's
        #: "batch downstream-state reads across a txn's inserts")
        self.base_states: Dict[Tuple[Any, str], Dict[str, Any]] = {}
        #: (key, bucket) -> (overlaid state, n effects folded): the
        #: overlay advances incrementally as the writeset grows — N
        #: same-key updates fold N effects total, not N^2
        self.overlay_cache: Dict[Tuple[Any, str], Tuple[Any, int]] = {}
        #: tentative commit VC frozen at first overlay: all of the txn's
        #: uncommitted dots share one stamp (re-stamped at real commit)
        self.tentative_vc: Optional[np.ndarray] = None
        #: True once the txn performed a client-level read — a
        #: read-bearing txn is (potentially) read-modify-write and must
        #: keep first-committer-wins certification
        self.did_read = False
        #: True once the txn buffered an update that certification must
        #: cover: state-dependent downstreams (observed-remove, mv,
        #: rga), escrow-guarded counter_b spends, composite maps, or
        #: any type not marked ``commutative_blind``.  A txn with
        #: neither flag set is a BLIND COMMUTATIVE writer and skips the
        #: certification round entirely (ISSUE 6 bypass)
        self.cert_required = False

    def pending_for(self, key, bucket) -> List[Effect]:
        return [e for e, _ in self.writeset if e.key == key and e.bucket == bucket]


class TransactionManager:
    """One per replica process — owns the commit stream for ``my_dc``."""

    def __init__(self, store: KVStore, my_dc: int = 0, cert: bool = True,
                 protocol: str = "clocksi"):
        self.store = store
        self.cfg: AntidoteConfig = store.cfg
        self.my_dc = my_dc
        #: txn_cert app-env flag (/root/reference/src/antidote.app.src:31-35)
        self.cert = cert
        #: txn_prot app-env flag: "clocksi" (Cure, full-VC snapshots) or
        #: "gr" (GentleRain: scalar global-stable-time snapshots —
        #: cure:gr_snapshot_obtain, /root/reference/src/cure.erl:234-257)
        assert protocol in ("clocksi", "gr"), protocol
        self.protocol = protocol
        self.commit_counter = 0
        #: held across counter increment → apply → publish listeners, and
        #: taken by anything deriving a SAFE time from the counter (the
        #: inter-DC heartbeat): a ping minted from a mid-commit counter
        #: would claim a ts whose txn has not reached the wire yet, and
        #: the subscriber's chain-clock duplicate suppression would then
        #: drop the real txn as already-applied.  Reentrant: commit
        #: listeners themselves trigger heartbeats.
        import threading as _threading

        self.commit_lock = _threading.RLock()
        # --- overload protection (PR 4): bounded commit backlog + the
        # read-only degraded mode -------------------------------------
        #: threads allowed to park on the commit lock before new commit
        #: attempts are refused with a typed BusyError (the riak_core
        #: vnode overload cap: a saturated vnode answers {error,
        #: overload} instead of queueing unboundedly)
        self.max_commit_backlog = 64
        self._backlog_lock = _threading.Lock()
        self._commit_backlog = 0
        #: multi-tenant QoS (ISSUE 19): when the serving layer installs
        #: a TenantRegistry here, a merged group-commit batch is split
        #: into weight-proportional ROUNDS so no tenant's writes occupy
        #: more than its share of the merge (work-conserving: a lone
        #: tenant still gets the whole batch).  None = untenanted.
        self.tenants = None
        #: non-None while the node is in degraded READ-ONLY mode: the
        #: WAL refused an append (ENOSPC / EIO).  Writes are rejected
        #: with ReadOnlyError, reads keep serving, and the mode exits
        #: automatically once an append probe succeeds again.
        self.read_only_reason: Optional[str] = None
        #: earliest monotonic time of the next recovery probe (the probe
        #: fsyncs a sidecar file — rate-limit it under write storms)
        self._ro_probe_at = 0.0
        #: True while a multi-txn group is mid-publish: counters for the
        #: whole group are already minted, so safe-time reads (heartbeat
        #: pings) must wait for the group's last egress publish or they
        #: outrun the stream (see _commit_group_locked)
        self._publishing_group = False
        #: (key, bucket) -> my-lane counter of its last local commit.
        #: Bounded: entries at or below every open txn's snapshot can
        #: never conflict again and are GC'd periodically (the reference
        #: prunes its committed_tx ETS against the stable time the same
        #: way, /root/reference/src/clocksi_vnode.erl:671-678)
        self.committed_keys: Dict[Tuple[Any, str], int] = {}
        #: certification stamps touched since the last checkpoint
        #: capture — the incremental chain's committed-keys delta window
        #: (consumed by Checkpointer._consume_windows_locked).  None =
        #: overflow past the cap (the next stamp rebases) — without it a
        #: long-running NON-checkpointing node would grow this forever
        self.ckpt_dirty_committed: "set | None" = set()
        #: open txid -> its own-lane snapshot (the GC floor)
        self._open_snaps: Dict[int, int] = {}
        self._cert_gc_every = 1024
        self._next_cert_gc = self._cert_gc_every
        self.hooks = HookRegistry()
        #: escrow guard for counter_b (bcounter_mgr, SURVEY §2.5)
        self.bcounters = BCounterManager(my_dc)
        #: called with (effects, commit_vc, origin) after every local commit
        #: — the inter-DC egress seam (inter_dc_log_sender_vnode:send,
        #: /root/reference/src/inter_dc_log_sender_vnode.erl:80-81)
        self.commit_listeners: List = []
        #: called while waiting for the stable snapshot to reach a client
        #: clock (wait_for_clock,
        #: /root/reference/src/clocksi_interactive_coord.erl:915-926);
        #: the inter-DC layer points this at its message pump
        self.on_clock_wait = lambda: None
        #: NodeMetrics — the coordinator's counter bumps
        #: (/root/reference/src/clocksi_interactive_coord.erl:667,734,849-870)
        self.metrics = None
        #: serving-epoch publication (ISSUE 5): when enabled (by the wire
        #: server), every write-bearing commit group and remote-ingress
        #: apply publishes a fresh store-wide serving snapshot before it
        #: acks, so the server's lock-free read stage serves at a clock
        #: that covers everything the client was told is committed
        self.serving_epochs = False
        #: highest own-lane commit counter that was ACKED while its
        #: publish deferred/failed — the wire server's clockless reads
        #: may serve from an epoch only when it covers this floor
        #: (write-then-read freshness survives deferred publishes; 0 =
        #: every ack so far went out under a covering epoch)
        self.epoch_lag_counter = 0
        #: monotonic time of the last INLINE (commit-path) epoch publish
        #: and the epoch-plane read count seen then — see
        #: EPOCH_INLINE_PUBLISH_S
        self._last_inline_publish = 0.0
        self._reads_at_last_publish = -1.0
        #: commit-path phase split (ISSUE 24): one ``add_group`` per
        #: write-bearing commit round — certify / wal_append / scatter /
        #: fsync_wait / listeners / publish, summing to the round's
        #: lock-held time (``antidote_commit_seconds``); node status
        #: ``write_plane.phases``
        self.phases = PhaseAccumulator()
        #: write-bearing commit rounds so far: the id a round's host
        #: spans and its members' stage records carry
        self.group_seq = 0

    # ------------------------------------------------------------------
    # serving-epoch publication (lock-split wire reads)
    # ------------------------------------------------------------------
    def enable_serving_epochs(self) -> None:
        # clocksi-only: gr hands clients SCALARIZED snapshot clocks, and
        # an epoch's full-vector VC handed back as a gr causal clock
        # could stall behind the scalar GST forever
        if self.protocol == "clocksi":
            self.serving_epochs = True

    def serving_epoch_vc(self) -> np.ndarray:
        """The publishable snapshot clock E: freshest applied lanes with
        the own lane raised to the commit counter.  Caller must hold the
        commit lock (E must be captured with no apply in flight)."""
        vc = self.store.dc_max_vc().copy()
        vc[self.my_dc] = max(int(vc[self.my_dc]), self.commit_counter)
        return vc

    def publish_serving_epoch(self) -> str:
        """Ticker-driven publication: take the commit lock and publish
        (no-ops when the current epoch already covers the store)."""
        with self.commit_lock:
            return self._publish_serving_epoch_locked()

    def _publish_serving_epoch_locked(self) -> str:
        st = self.store.publish_serving_epoch(self.serving_epoch_vc())
        self._native_epoch_published()
        return st

    def _native_epoch_published(self) -> None:
        """Push the serving epoch to the C++ mirror, in the critical
        section (commit lock held) of the publish that made it: every
        invalidation — they run under this lock too — then finds the
        mirror at the epoch Python serves, which is what the mirror's
        rule for fills rests on (proto/cpp/frontend.cc), and a commit's
        acknowledgement never leaves before the mirror has left the
        epoch that lacks it.  Also after a publish that made no epoch
        (noop, deferred): the id is then the mirror's own and only the
        lag gate's verdict is refreshed."""
        nm = getattr(self.store, "native_mirror", None)
        ep = self.store.serving_epoch
        if nm is not None and ep is not None:
            nm.advance(int(ep.id), [int(x) for x in ep.vc],
                       int(ep.vc[self.my_dc]) >= self.epoch_lag_counter)

    def _native_lag_raised(self) -> None:
        """The serving epoch just started lagging the commit counter:
        the native front-end must stop serving clockless reads from it
        (Python's ``_try_cache_read`` refuses via ``epoch_lag_counter``;
        the C++ loop learns the same fact here).  The next publish that
        catches up — a commit group's or the epoch ticker's —
        re-enables it (``_native_epoch_published``)."""
        nm = getattr(self.store, "native_mirror", None)
        if nm is not None:
            nm.set_clockless_ok(False)

    @property
    def checkpoint_barrier(self):
        """The lock a checkpoint stamp must hold (ISSUE 8): under it, no
        commit, remote-ingress apply, WAL append or membership move is in
        flight, so (applied VC, commit counter, certification stamps,
        directory, WAL append sequences) form one consistent cut — the
        image's clock stamp and per-shard floors.  The barrier is SHORT
        by design (host copies + device copy dispatches; the image
        streams to disk outside it).

        RO-mode interplay: the degraded read-only mode is the WAL APPEND
        path's contract (``_enter_read_only`` fires only on a refused
        commit append/fsync).  A checkpoint hitting ENOSPC while
        streaming its image fails that checkpoint alone —
        :class:`~antidote_tpu.log.checkpoint.CheckpointError`, nothing
        published, nothing truncated — and must never flip this mode:
        the log is intact, so writes remain exactly as durable as they
        were.  Conversely a store already read-only can still checkpoint
        (and a checkpoint-based restart of it must come back serving
        reads)."""
        return self.commit_lock

    # ------------------------------------------------------------------
    # transaction lifecycle (antidote.erl API shapes)
    # ------------------------------------------------------------------
    def _snapshot_vc(self) -> np.ndarray:
        """Txn snapshot: remote lanes from the DC stable snapshot (safe —
        every shard has applied at least this much), own lane from the
        commit counter (local commits apply synchronously).

        GentleRain mode replaces the vector with the scalar GST — the min
        entry across lanes (get_scalar_stable_time,
        /root/reference/src/dc_utilities.erl:294-317) — trading snapshot
        freshness for O(1) clock metadata, exactly the gr trade-off."""
        snap = self.store.stable_vc().copy()
        snap[self.my_dc] = self.commit_counter
        if self.protocol == "gr":
            gst = int(snap.min())
            snap = np.full_like(snap, gst)
            snap[self.my_dc] = self.commit_counter
        return snap

    def start_transaction(
        self, clock: Optional[np.ndarray] = None, props: Optional[dict] = None
    ) -> Transaction:
        snap = self._snapshot_vc()
        if clock is not None:
            clock = np.asarray(clock, np.int32)
            mask = np.arange(len(snap)) != self.my_dc
            for _ in range(10_000):
                if (clock[mask] <= snap[mask]).all():
                    break
                self.on_clock_wait()
                snap = self._snapshot_vc()
            else:
                raise TimeoutError(
                    f"stable snapshot {snap} never reached client clock "
                    f"{clock}"
                )
            snap = np.maximum(snap, clock)
        if self.metrics is not None:
            self.metrics.open_transactions.inc()
        txn = Transaction(snap, props)
        self._open_snaps[txn.txid] = int(snap[self.my_dc])
        return txn

    def read_objects(self, objects: Sequence[BoundObject], txn: Transaction,
                     _internal: bool = False):
        assert txn.active
        # count client-level reads only — internal recursions (map fields,
        # downstream state reads) would inflate the dashboard rates
        if not _internal:
            # a client-level read makes the txn read-bearing: whatever it
            # writes may depend on what it saw, so the commutativity
            # bypass is off for it (internal downstream-state reads mark
            # cert_required at the update site instead)
            txn.did_read = True
            if self.metrics is not None:
                self.metrics.operations.inc(len(objects), type="read")
        out: List[Any] = [None] * len(objects)
        plain, comp = [], []
        composite_names = _composite_names()
        for i, (key, t, bucket) in enumerate(objects):
            (comp if t in composite_names else plain).append(i)
        if plain:
            objs = [objects[i] for i in plain]
            if txn.writeset:
                # pending-write overlay needs full states on host
                states = self._read_states_with_overlay(objs, txn)
                for j, i in enumerate(plain):
                    _, t, _ = objects[i]
                    out[i] = get_type(t).value(
                        states[j], self.store.blobs, self.cfg
                    )
            else:
                # SERVING PATH: no writeset to overlay, so the fused
                # device read (freshness + fold + Type.resolve in one
                # launch, KVStore.read_resolved) serves the value; only
                # the compact resolved view crosses the host boundary
                vals = self._read_values_resolved(objs, txn)
                for j, i in enumerate(plain):
                    out[i] = vals[j]
        if comp:
            vals = self._read_maps([objects[i] for i in comp], txn)
            for j, i in enumerate(comp):
                out[i] = vals[j]
        return out

    def read_merges(self, objects: Sequence[BoundObject],
                    txn: Transaction) -> bool:
        """Whether this read of ``txn`` is the fused serving read alone,
        which :meth:`read_objects_group` batches across transactions: the
        transaction has no write of its own to overlay on what it reads
        and no object is a composite (a map reads level by level)."""
        if txn.writeset:
            return False
        composite_names = _composite_names()
        return not any(t in composite_names for _, t, _ in objects)

    def read_objects_group(
        self, reads: Sequence[Tuple[Sequence[BoundObject], Transaction]]
    ) -> List[List[Any]]:
        """:meth:`read_objects` for the reads of several transactions at
        once — ``[(objects, txn), ...]``, each of which
        :meth:`read_merges` — in one batched store read in which every
        row carries its own transaction's ``snapshot_vc``: one device
        round trip a touched table for the group, where one call a
        transaction makes one each.  Per transaction it keeps what
        ``read_objects`` does (the txn turns read-bearing, the operation
        count, the decoded-value cache probed and back-filled at that
        transaction's snapshot)."""
        for objects, txn in reads:
            assert txn.active
            if not self.read_merges(objects, txn):
                raise ValueError(
                    f"transaction {txn.txid}'s read does not merge "
                    "(a writeset to overlay, or a composite type)")
        vals = self._cached_values_group(reads,
                                         self._values_resolved_uncached)
        for objects, txn in reads:
            txn.did_read = True
            if self.metrics is not None:
                self.metrics.operations.inc(len(objects), type="read")
        return vals

    def _read_values_resolved(self, objs, txn: Transaction) -> List[Any]:
        """Values via the fused serving read.  Types with device resolution
        decode the compact view host-side (``value_from_resolved``);
        truncated views (count > resolve_top) and resolution-less types
        re-fetch/ship the full state and decode with ``value``.

        Unchanged keys serve straight from the store's decoded-value
        cache (the host-level snapshot_cache analogue): a hit skips the
        device gather AND the decode; misses fall through, and latest
        reads back-fill the cache."""
        return self._cached_values_group(
            [(objs, txn)], self._values_resolved_uncached)[0]

    def _cached_values_group(self, reads, compute) -> List[List[Any]]:
        """The decoded-value-cache protocol shared by plain and composite
        reads, for ``[(objs, txn), ...]``: bulk probe, each transaction's
        objects at its own snapshot (a hit never reaches the device);
        the misses of all of them computed by ONE ``compute(miss_objs,
        read_vcs)`` (``read_vcs`` ``[n, D]``: a miss's own transaction's
        snapshot); latest reads back-filled under the epoch guard (a
        commit between capture and fill drops the fill)."""
        outs: List[List[Any]] = []
        miss: List[Tuple[int, int, bool]] = []  # (read, position, latest)
        fill_vc = fill_epoch = None
        for r, (objs, txn) in enumerate(reads):
            read_tup = tuple(int(x) for x in txn.snapshot_vc)
            allv, miss_idx = self.store.value_cache_bulk_get(objs, read_tup)
            outs.append(allv)
            if not miss_idx:
                continue
            if fill_vc is None:
                fill_vc = self.store.applied_max_tuple()
                fill_epoch = self.store.mutation_epoch
            is_latest = all(x >= f for x, f in zip(read_tup, fill_vc))
            miss.extend((r, j, is_latest) for j in miss_idx)
        if not miss:
            return outs
        miss_objs = [reads[r][0][j] for r, j, _ in miss]
        vals = compute(miss_objs, np.stack(
            [reads[r][1].snapshot_vc for r, _, _ in miss]))
        for (r, j, is_latest), (key, _t, bucket), v in zip(
                miss, miss_objs, vals):
            if is_latest:
                self.store.value_cache_fill(key, bucket, v, fill_vc,
                                            fill_epoch)
            outs[r][j] = v
        return outs

    def _values_resolved_uncached(self, objs, read_vcs) -> List[Any]:
        """``objs`` decoded from the store, each at its row of
        ``read_vcs`` (``[n, D]``)."""
        from antidote_tpu.crdt.base import RESOLVE_OVERFLOW

        replayed: Dict[int, Dict[str, Any]] = {}
        resolved = self.store.read_resolved(
            objs, read_vcs, full_out=replayed
        )
        vals: List[Any] = [None] * len(objs)
        refetch = []
        for j, (key, t, bucket) in enumerate(objs):
            ty = get_type(t)
            if j in replayed:
                # the log-replay fallback already rebuilt the full state;
                # decode it directly (a truncated resolved view here must
                # not trigger a second WAL scan)
                vals[j] = ty.value(replayed[j], self.store.blobs, self.cfg)
                continue
            if ty.resolve_spec(self.cfg) is None:
                # read_resolved returned the full state for these
                vals[j] = ty.value(resolved[j], self.store.blobs, self.cfg)
                continue
            v = ty.value_from_resolved(resolved[j], self.store.blobs, self.cfg)
            if v is RESOLVE_OVERFLOW:
                refetch.append(j)
            else:
                vals[j] = v
        if refetch:
            states = self.store.read_states(
                [objs[j] for j in refetch], read_vcs[refetch]
            )
            for j, st in zip(refetch, states):
                _, t, _ = objs[j]
                vals[j] = get_type(t).value(st, self.store.blobs, self.cfg)
        return vals

    def _read_maps(self, objects, txn: Transaction) -> List[dict]:
        """Assemble composite map values, batched per nesting level: ONE
        membership read for every map in the batch, then ONE field read
        across all maps (nested maps recurse — device launches scale with
        nesting depth, not map count).  Assembled maps are value-cached
        whole; any write to a field or the membership invalidates the
        parent entry (the derived-key walk in KVStore.apply_effects)."""
        if not txn.writeset:
            return self._cached_values_group(
                [(objects, txn)],
                lambda miss, _vcs: self._assemble_maps(miss, txn))[0]
        return self._assemble_maps(objects, txn)

    def _assemble_maps(self, objects, txn: Transaction) -> List[dict]:
        from antidote_tpu.crdt import maps as maps_mod

        membs = self.read_objects(
            [(maps_mod.member_key(key), maps_mod.MAP_MEMBERSHIP[t], bucket)
             for key, t, bucket in objects],
            txn, _internal=True,
        )
        field_objs, spans = [], []
        for (key, t, bucket), memb in zip(objects, membs):
            fields = [tuple(x) for x in memb]
            spans.append((len(field_objs), fields))
            field_objs.extend(
                (maps_mod.field_key(key, f, ft), ft, bucket)
                for f, ft in fields
            )
        nested = (
            self.read_objects(field_objs, txn, _internal=True)
            if field_objs else []
        )
        return [
            {(f, ft): nested[base + j] for j, (f, ft) in enumerate(fields)}
            for base, fields in spans
        ]

    def update_objects(self, updates: Sequence[Update], txn: Transaction) -> None:
        assert txn.active
        if self.metrics is not None:
            self.metrics.operations.inc(len(updates), type="update")
        for u in updates:
            self._apply_update(u, txn, run_hooks=True)

    def _apply_update(self, update, txn: Transaction, run_hooks: bool = False) -> None:
        key, type_name, bucket, op = update
        if not is_type(type_name):
            raise TypeError(f"unknown CRDT type {type_name!r}")
        ty = get_type(type_name)
        if not ty.is_operation(op):
            raise TypeError(f"invalid operation {op!r} for {type_name}")
        if run_hooks:
            try:
                key, type_name, op = self.hooks.execute_pre_commit_hook(
                    key, type_name, bucket, op
                )
            except Exception as e:
                self._mark_aborted(txn)
                raise AbortError(f"pre-commit hook failed: {e}") from e
            # re-validate the hook-transformed update: a misbehaving hook
            # must abort, not generate malformed effects
            if not is_type(type_name):
                self._mark_aborted(txn)
                raise AbortError(
                    f"pre-commit hook produced unknown type {type_name!r}"
                )
            ty = get_type(type_name)
            if not ty.is_operation(op):
                self._mark_aborted(txn)
                raise AbortError(
                    f"pre-commit hook produced invalid op {op!r} for {type_name}"
                )
        if getattr(ty, "composite", False):
            # maps expand into membership + nested-field updates; children
            # skip bucket hooks (they already ran on the map op above)
            txn.cert_required = True
            from antidote_tpu.crdt import maps as maps_mod

            def read_field_value(fk, ft):
                return self.read_objects([(fk, ft, bucket)], txn,
                                         _internal=True)[0]

            for sub in maps_mod.expand_update(
                key, type_name, bucket, op, read_field_value
            ):
                self._apply_update(sub, txn)
            return
        guarded_b = type_name == "counter_b" and op[0] in ("decrement",
                                                           "transfer")
        # commutativity-bypass eligibility (ISSUE 6): only a blind
        # effect of a commutative type leaves the flag untouched
        if (guarded_b or ty.require_state_downstream(op)
                or not getattr(ty, "commutative_blind", False)):
            txn.cert_required = True
        state = None
        # the key's slot-tier cfg: a promoted key's state (and the effect
        # lanes its downstream emits, e.g. mv observed ids) has the wider
        # tier's widths
        cfg_k = self.cfg
        if ty.require_state_downstream(op):
            state = self._read_states_with_overlay(
                [(key, type_name, bucket)], txn
            )[0]
            ent = self.store.locate(key, type_name, bucket, create=False)
            if ent is not None:
                cfg_k = self.store.table(ent[0]).cfg
        # escrow lane guard: counter_b decrements and outgoing transfers
        # must act on THIS replica's lane — any other lane would spend
        # rights this replica does not own (clocksi_downstream routes the
        # bounded counter through bcounter_mgr,
        # /root/reference/src/clocksi_downstream.erl:38-68).  The RIGHTS
        # check itself moved to commit time (ISSUE 18): the merged
        # certification pass reserves rights once per key against a
        # batch-local view instead of re-reading state per update here.
        if guarded_b:
            if op[0] == "decrement":
                _amount, src_lane = op[1]
            else:
                _amount, _to_dc, src_lane = op[1]
            if src_lane != self.my_dc:
                self._mark_aborted(txn)
                raise AbortError(
                    f"counter_b {op[0]} must spend this replica's lane "
                    f"{self.my_dc}, not {src_lane}"
                )
        seq = len(txn.pending_for(key, bucket))
        for eff_a, eff_b, blob_refs in ty.downstream(
            op, state, self.store.blobs, cfg_k
        ):
            eff_a, eff_b = ty.stamp_op_seq(eff_a, eff_b, seq)
            seq += 1
            txn.writeset.append(
                (Effect(key, type_name, bucket, eff_a, eff_b, blob_refs), op)
            )

    def commit_transaction(self, txn: Transaction) -> np.ndarray:
        out = self.commit_transactions_group([txn])[0]
        if isinstance(out, Exception):
            raise out
        return out

    #: recovery probes while read-only are spaced at least this far apart
    RO_PROBE_INTERVAL_S = 0.25

    #: while the epoch plane is IDLE (no epoch-path read since the last
    #: inline publish — a pure write storm), inline publishes are rate-
    #: limited to one per window: deferring batches raise the epoch-lag
    #: floor, so any read that does arrive falls back to the always-
    #: fresh locked path, and the next publish (or the ticker) covers
    #: them.  The moment epoch reads flow again, every write batch
    #: publishes before its ack as before — deferring under a MIXED
    #: load would reroute the read majority to the locked plane and
    #: blow up its tail (measured: config-3 p99 0.5 s → 2.9 s).
    EPOCH_INLINE_PUBLISH_S = 0.025

    def check_writable(self) -> None:
        """Raise :class:`ReadOnlyError` while the node is in degraded
        read-only mode.  Each call past the probe interval re-probes the
        WAL first, so the mode exits automatically (on the next write
        attempt) once appends succeed again."""
        if self.read_only_reason is None:
            return
        now = time.monotonic()
        if now >= self._ro_probe_at and self.store.log is not None:
            self._ro_probe_at = now + self.RO_PROBE_INTERVAL_S
            try:
                self.store.log.probe_append()
            except OSError:
                pass
            else:
                log.warning("WAL appends succeed again; leaving degraded "
                            "read-only mode (was: %s)", self.read_only_reason)
                self.read_only_reason = None
                if self.metrics is not None:
                    self.metrics.degraded_read_only.set(0)
                return
        if self.metrics is not None:
            self.metrics.shed.inc(plane="read_only")
        raise ReadOnlyError(self.read_only_reason)

    def _enter_read_only(self, exc: OSError) -> None:
        self.read_only_reason = (
            f"WAL append failed ({errno.errorcode.get(exc.errno, exc.errno)}"
            f"): {exc}"
        )
        self._ro_probe_at = time.monotonic() + self.RO_PROBE_INTERVAL_S
        if self.metrics is not None:
            self.metrics.degraded_read_only.set(1)
        log.error("entering degraded READ-ONLY mode: %s",
                  self.read_only_reason)

    def commit_transactions_group(self, txns: Sequence[Transaction],
                                  deadline: Optional[float] = None):
        """Commit several independent transactions as ONE device append —
        the group-commit seam the batched wire server drives (r4 VERDICT
        item 3).  Semantically identical to committing them sequentially:
        each txn gets its own commit timestamp, certification is
        first-committer-wins INCLUDING against earlier txns in the group,
        and effects reach the store in commit order.  Returns, per txn,
        the commit VC or the AbortError it would have raised.

        Certification: abort if any written key saw a commit after the
        txn's snapshot (certification_check,
        /root/reference/src/clocksi_vnode.erl:588-632); the per-txn
        certify prop mirrors the reference's txn_props certify flag
        (/root/reference/src/clocksi_interactive_coord.erl
        get_txn_property).

        Overload discipline (PR 4): admission is BOUNDED — at most
        ``max_commit_backlog`` threads may park on the commit lock; past
        the cap the group is refused with a typed :class:`BusyError`
        instead of growing the convoy.  ``deadline`` (absolute monotonic)
        is re-checked once the lock is held: work that outlived its
        caller while queued is aborted at dequeue, not executed.  A
        write-bearing group is refused with :class:`ReadOnlyError` while
        the node is in degraded read-only mode (the check also runs the
        auto-recovery probe).

        Multi-tenant QoS (ISSUE 19): with a :class:`TenantRegistry`
        installed (``self.tenants``), the group is split into weight-
        proportional ROUNDS — each a full merged batch of its own — so
        one tenant's write storm cannot occupy an entire merged batch
        while a sibling's single commit waits behind it.  Work-
        conserving: a single-tenant group stays one round (the exact
        pre-tenancy path).  Backlog admission, the deadline check and
        the writable check cover the whole group up front; a FIRST-
        round failure re-raises (nothing committed), a LATER-round
        failure must NOT raise — earlier rounds' commit VCs are already
        final, so the error surfaces as the failed txns' per-txn
        results instead (their txns aborted), never as a group-level
        exception that would make the caller retry acked work."""
        has_writes = any(t.writeset for t in txns)
        rounds = self._tenant_rounds(txns)
        # backlog admission OUTSIDE the abort-cleanup scope: a backlog
        # shed happens before the group's state is touched, so the txns
        # stay OPEN and the caller may retry the same commit — the busy
        # retry-after hint stays honest for interactive commits
        with self._backlog_lock:
            if self._commit_backlog >= self.max_commit_backlog:
                if self.metrics is not None:
                    self.metrics.shed.inc(plane="txn")
                raise BusyError(
                    f"commit backlog at max_commit_backlog="
                    f"{self.max_commit_backlog}"
                )
            self._commit_backlog += 1
        try:
            try:
                results: dict = {}
                for t, r in zip(rounds[0],
                                self._commit_round(rounds[0], deadline,
                                                   has_writes, first=True)):
                    results[id(t)] = r
                for ri in range(1, len(rounds)):
                    try:
                        outs = self._commit_round(rounds[ri], deadline,
                                                  has_writes, first=False)
                    except BaseException as e:
                        # rounds before this one COMMITTED and their VCs
                        # already sit in `results`: re-raising would make
                        # the server error every member — including works
                        # whose commits landed — and a client's blind
                        # resend would double-apply them.  Fail the rest
                        # per-txn instead: abort their still-active txns
                        # and surface the error as each one's result
                        # (the same closed-txn contract the per-txn
                        # AbortError entries carry).
                        err = e if isinstance(e, Exception) \
                            else RuntimeError(f"commit round failed: {e!r}")
                        for rnd in rounds[ri:]:
                            for t in rnd:
                                if t.active:
                                    self._mark_aborted(t)
                                results[id(t)] = err
                        break
                    for t, r in zip(rounds[ri], outs):
                        results[id(t)] = r
                if (self.metrics is not None and has_writes
                        and self.store.log is not None):
                    for i, d in enumerate(self.store.log.segment_depths()):
                        self.metrics.wal_segment_depth.set(d,
                                                           segment=str(i))
                return [results[id(t)] for t in txns]
            finally:
                with self._backlog_lock:
                    self._commit_backlog -= 1
        except BaseException:
            # a shed/failed group must not leak open transactions: they
            # pin the certification-GC floor forever (the same reason the
            # server aborts orphans of dead connections).  Only round 1
            # can land here (deadline/writable/WAL refusal before any
            # commit) — later-round failures were converted to per-txn
            # results above.  Whatever _commit_group_locked already
            # closed stays closed.
            for t in txns:
                if t.active:
                    self._mark_aborted(t)
            raise

    def _tenant_rounds(self, txns: Sequence[Transaction]) -> List[List]:
        """Weight-proportional round split of one merged commit group
        (ISSUE 19).  Untenanted managers, single-member groups and
        groups whose members all belong to one tenant keep the
        one-round fast path — byte-for-byte the pre-tenancy batch,
        zero extra lock cycles."""
        reg = self.tenants
        if reg is None or not getattr(reg, "multi", False) or len(txns) <= 1:
            return [list(txns)]
        from antidote_tpu.tenancy import batch_rounds

        def tenant_of(t):
            return reg.resolve(None, (e.bucket for e, _ in t.writeset))

        return batch_rounds(list(txns), tenant_of, reg)

    def _commit_round(self, txns: Sequence[Transaction],
                      deadline: Optional[float], has_writes: bool,
                      first: bool) -> List[Any]:
        """One merged batch under the commit lock — the pre-tenancy
        ``commit_transactions_group`` critical section, verbatim.  The
        deadline/writable admission checks run on the FIRST round only:
        they gate the group (nothing committed yet, failure is cleanly
        retryable); later rounds must run to completion so the split
        never strands a group half-checked."""
        round_writes = any(t.writeset for t in txns)
        with self.commit_lock:
            if first:
                try:
                    check_deadline(deadline, "commit dequeue")
                except DeadlineExceeded:
                    if self.metrics is not None:
                        self.metrics.shed.inc(plane="deadline")
                    raise
                if has_writes:
                    self.check_writable()
            if round_writes:
                self.group_seq += 1
            with span("commit.group", id=self.group_seq, txns=len(txns)):
                return self._commit_round_locked(txns, round_writes)

    def _commit_round_locked(self, txns: Sequence[Transaction],
                             round_writes: bool) -> List[Any]:
        """The round's critical section (commit lock held): the merged
        commit group, then the serving-epoch publish before any ack
        leaves; a write-bearing round's phase stamps go to
        ``self.phases`` and its lock-held time to ``commit_seconds``."""
        t0 = time.monotonic()
        stamps, freeze_s, mirror_s = None, 0.0, None
        try:
            out, inner, mirror_s = self._commit_group_locked(txns)
            stamps = (t0, *inner, time.monotonic())
            if round_writes and self.serving_epochs:
                # publish BEFORE the ack leaves: a clockless
                # read admitted after this commit's reply must
                # find an epoch that covers it (read-your-
                # writes stays intact under the lock split).
                # A deferred/failed publish raises the lag
                # floor instead — epoch reads below it fall
                # back to the (always-fresh) locked path.
                # WRITE-STORM DEFERRAL (ISSUE 6): with the
                # epoch plane idle (no epoch-path read since
                # the last publish), the per-batch publish
                # scatter was >60% of batch cost serving
                # nobody — those batches defer (lag floor
                # up; any arriving read stays correct via
                # the locked path) up to the rate window.
                # The moment epoch reads flow, every batch
                # publishes before its ack again (deferring
                # mixed loads reroutes the read majority to
                # the locked plane and blows up its tail).
                now2 = time.monotonic()
                reads_now = -1.0
                if self.metrics is not None:
                    sr = self.metrics.serving_reads
                    reads_now = (sr.value(path="cache")
                                 + sr.value(path="gather"))
                    nm = getattr(self.store, "native_mirror", None)
                    if nm is not None:
                        # the C++ mirror's hits are epoch reads too, the
                        # only ones Python never counts: while they flow
                        # the plane is not idle
                        reads_now += nm.native_hits()
                idle = (reads_now ==
                        self._reads_at_last_publish)
                if (idle and now2 - self._last_inline_publish
                        < self.EPOCH_INLINE_PUBLISH_S):
                    self.epoch_lag_counter = self.commit_counter
                    self._native_lag_raised()
                else:
                    self._last_inline_publish = now2
                    self._reads_at_last_publish = reads_now
                    try:
                        with span("commit.publish"):
                            st, freeze_s = (
                                self.store.publish_serving_epoch_timed(
                                    self.serving_epoch_vc()))
                    except Exception:
                        st = "error"
                        log.exception(
                            "serving-epoch publish failed")
                    if st not in ("published", "noop"):
                        self.epoch_lag_counter = (
                            self.commit_counter)
                    # before the ack: the mirror leaves the epoch that
                    # lacks this group with the lock still held
                    self._native_epoch_published()
        except OSError as e:
            if round_writes and e.errno in (errno.ENOSPC,
                                            errno.EIO,
                                            errno.EROFS,
                                            errno.EDQUOT):
                # the WAL refused the append BEFORE any device
                # table mutated (durability-first ordering in
                # KVStore.apply_effects): fail the round and
                # flip into read-only degraded mode
                self._enter_read_only(e)
                raise ReadOnlyError(
                    self.read_only_reason) from e
            raise
        finally:
            if round_writes:
                t_end = time.monotonic()
                if stamps is not None:
                    # (a round that failed before its listeners ran has
                    # no phase split to report.)  The one accumulator
                    # call per group: lock taken, then the end of
                    # certify (to the WAL append: certification, escrow,
                    # the store's locate/promote/record build),
                    # wal_append, scatter, fsync_wait, listeners and
                    # publish.  A phase the round skipped is zero-long,
                    # so the six always sum to ``t_end - t0``, the
                    # round's ``antidote_commit_seconds`` observation.
                    self.phases.add_group((*stamps, t_end), freeze_s,
                                          mirror_s)
                if self.metrics is not None:
                    self.metrics.commit_seconds.observe(t_end - t0)
                    self.metrics.commit_merge_width.observe(
                        sum(1 for t in txns if t.writeset))
        return out

    def _wal_refusal(self, e: Exception) -> Exception:
        """Map a sub-group's WAL refusal to the client-facing error: a
        disk-class errno flips the read-only degraded mode (once) and
        surfaces typed; anything else passes through."""
        if isinstance(e, OSError) and e.errno in (errno.ENOSPC, errno.EIO,
                                                  errno.EROFS, errno.EDQUOT):
            if self.read_only_reason is None:
                self._enter_read_only(e)
            out = ReadOnlyError(self.read_only_reason)
            out.__cause__ = e
            return out
        return e

    def _commit_group_locked(self, txns: Sequence[Transaction]):
        """One merged commit batch under the lock: vectorized
        certification, one counter mint per member, ONE grouped
        WAL-append + device scatter, then — under sync_log=true — the
        covering group fsync (overlapped with the scatter; awaited
        BEFORE listeners run, so nothing non-durable ever reaches the
        serving epoch or the inter-DC stream), listeners per member.
        Returns the per-txn results, four stamps — ``time.monotonic()``
        at the start and the end of the WAL append, the end of the scatter
        and the end of the fsync wait (all four the end of certification
        when no member survived it) — and the seconds the store spent
        invalidating the group's keys in the native mirror (None when
        there is no mirror or nothing was applied)."""
        with span("commit.certify"):
            out, pend = self._certify_locked(txns)
        if pend:
            stamps, mirror_s = self._apply_certified_locked(out, pend)
        else:
            stamps, mirror_s = (time.monotonic(),) * 4, None
        if self.commit_counter >= self._next_cert_gc:
            self._gc_committed_keys()
            self._next_cert_gc = self.commit_counter + self._cert_gc_every
        return out, stamps, mirror_s

    def _certify_locked(self, txns: Sequence[Transaction]):
        """The ``certify`` phase of a commit group: vectorised
        certification and escrow reservation, one counter mint per
        surviving member.  Returns (per-txn results so far, the members to
        apply as (out idx, txn, commit_vc, effects, stamped {ck: prev},
        counter))."""
        out: List[Any] = []
        pend: List[tuple] = []
        # vectorized certification (ISSUE 6): ONE pass over the stamp
        # table up front — each unique written key is looked up once for
        # the whole merged batch (Zipf batches repeat hot keys across
        # members), then members check/update the small batch-local view
        last_seen: Dict[tuple, int] = {}
        for txn in txns:
            for eff, _ in txn.writeset:
                ck = (eff.key, eff.bucket)
                if ck not in last_seen:
                    last_seen[ck] = self.committed_keys.get(ck, 0)
        # vectorized escrow certification (ISSUE 18): reserve counter_b
        # rights ONCE per key for the whole merged batch — one state
        # read per unique spend key instead of one per update, and a
        # batch-local ledger serializes the members' spends (two txns
        # racing the same last 5 rights: the first reserves, the second
        # refuses typed).  Within a txn, spends net against its OWN
        # own-lane increments (effects apply atomically) but a surplus
        # never credits the batch ledger — a WAL-subgroup NACK of the
        # crediting member would otherwise un-happen rights a sibling
        # already spent (oversell).
        esc_spends: Dict[int, Dict[tuple, Tuple[int, int]]] = {}
        esc_avail: Dict[tuple, int] = {}
        for txn in txns:
            dec: Dict[tuple, int] = {}
            spend: Dict[tuple, int] = {}
            credit: Dict[tuple, int] = {}
            for eff, op in txn.writeset:
                if eff.type_name != "counter_b":
                    continue
                ck = (eff.key, eff.bucket)
                if op[0] == "decrement":
                    spend[ck] = spend.get(ck, 0) + int(op[1][0])
                    dec[ck] = dec.get(ck, 0) + int(op[1][0])
                elif op[0] == "transfer":
                    spend[ck] = spend.get(ck, 0) + int(op[1][0])
                elif op[0] == "increment" and op[1][1] == self.my_dc:
                    credit[ck] = credit.get(ck, 0) + int(op[1][0])
            net = {
                ck: (max(0, n - credit.get(ck, 0)), dec.get(ck, 0))
                for ck, n in spend.items()
                if max(0, n - credit.get(ck, 0)) > 0
            }
            if net:
                esc_spends[txn.txid] = net
                for ck in net:
                    esc_avail.setdefault(ck, 0)
        if esc_avail:
            ty_b = get_type("counter_b")
            esc_keys = list(esc_avail)
            states = self.store.read_states(
                [(k, "counter_b", b) for k, b in esc_keys],
                self.store.dc_max_vc(),
            )
            for ck, st in zip(esc_keys, states):
                esc_avail[ck] = (0 if st is None
                                 else int(ty_b.local_rights(st, self.my_dc)))
        for txn in txns:
            assert txn.active
            txn.active = False
            self._open_snaps.pop(txn.txid, None)
            if self.metrics is not None:
                self.metrics.open_transactions.dec()
            if not txn.writeset:
                out.append(txn.snapshot_vc.copy())
                continue
            explicit = txn.props.get("certify")
            cert = self.cert if explicit is None else bool(explicit)
            # commutativity bypass: blind updates of commutative types
            # from a txn that read nothing need no first-committer-wins
            # round — their effects commute, so every interleaving
            # converges (reference certify=false analogue, automatic).
            # An EXPLICIT certify=true prop opts back in (parity).
            bypass = (cert and explicit is None and not txn.did_read
                      and not txn.cert_required)
            if bypass:
                cert = False
                if self.metrics is not None:
                    self.metrics.cert_bypass.inc()
            conflict = None
            if cert:
                snap_here = int(txn.snapshot_vc[self.my_dc])
                for eff, _ in txn.writeset:
                    if last_seen[(eff.key, eff.bucket)] > snap_here:
                        conflict = eff.key
                        break
            if conflict is not None:
                if self.metrics is not None:
                    self.metrics.aborted_transactions.inc()
                out.append(AbortError(
                    f"certification conflict on key {conflict!r}"
                ))
                continue
            # escrow reservation against the batch-local rights ledger:
            # a shortfall NACKs exactly this member (typed, with a hint
            # scaled by the expected grant arrival) and feeds the
            # background transfer loop's demand estimate
            sp = esc_spends.get(txn.txid)
            if sp is not None:
                short = next(
                    ((ck, n, d) for ck, (n, d) in sp.items()
                     if n > esc_avail.get(ck, 0)), None)
                if short is not None:
                    (key, bucket), needed, dec_amt = short
                    held = esc_avail.get((key, bucket), 0)
                    if dec_amt > 0:
                        self.bcounters.note_refusal(key, bucket, dec_amt)
                    else:
                        # refused outgoing transfers are not re-driven
                        # by the rights loop (the requester's own loop
                        # re-asks); they still count as refusals
                        self.bcounters.refused_total += 1
                    if self.metrics is not None:
                        self.metrics.aborted_transactions.inc()
                        self.metrics.escrow_refusals.inc()
                        self.metrics.escrow_shortfall.set(
                            self.bcounters.shortfall())
                    out.append(InsufficientRightsError(
                        f"insufficient rights for {key!r}: need "
                        f"{needed}, hold {held}",
                        retry_after_ms=self.bcounters.grant_hint_ms(
                            key, bucket),
                        key=key, needed=needed, held=held,
                    ))
                    continue
                for ck, (n, _d) in sp.items():
                    esc_avail[ck] -= n
                    self.bcounters.satisfied(*ck)
            self.commit_counter += 1
            commit_vc = txn.snapshot_vc.copy()
            commit_vc[self.my_dc] = self.commit_counter
            # dots observed from the txn's OWN overlay carry the tentative
            # own-lane ts; if other txns committed in between, the real ts
            # differs — rewrite them (observed-remove/mv-id/rga-uid safety)
            if txn.tentative_vc is not None:
                tent_own = int(txn.tentative_vc[self.my_dc])
                if tent_own != self.commit_counter:
                    for eff, _ in txn.writeset:
                        ty_e = get_type(eff.type_name)
                        eff.eff_a, eff.eff_b = ty_e.restamp_own_dots(
                            self.cfg, eff.eff_a, eff.eff_b, self.my_dc,
                            tent_own, self.commit_counter)
            effects = [e for e, _ in txn.writeset]
            if self.metrics is not None:
                self.metrics.commit_batch_size.observe(len(effects))
            # mark BEFORE later group members certify: a group peer whose
            # snapshot predates this commit must first-committer-abort.
            # Bypassed (blind commutative) members never touch the stamp
            # table at all — a blind write invalidates nobody, and under
            # Zipf blind-heavy load the table stays small.
            stamped: Dict[tuple, Optional[int]] = {}
            if not bypass:
                for eff, _ in txn.writeset:
                    ck = (eff.key, eff.bucket)
                    if ck not in stamped:
                        stamped[ck] = self.committed_keys.get(ck)
                    self.committed_keys[ck] = self.commit_counter
                    ckd = self.ckpt_dirty_committed
                    if ckd is not None:
                        ckd.add(ck)
                        if len(ckd) > 262144:  # bounded like the
                            # store's key window: overflow → rebase
                            self.ckpt_dirty_committed = None
                    last_seen[ck] = self.commit_counter
            pend.append((len(out), txn, commit_vc, effects, stamped,
                         self.commit_counter))
            out.append(commit_vc)
        return out, pend

    def _apply_certified_locked(self, out: List[Any], pend: List[tuple]):
        """The rest of a commit group: ONE grouped WAL append + device
        scatter, the covering fsync's wait, listeners per member.  NACKed
        members' entries of ``out`` become their errors.  Returns
        ``time.monotonic()`` at the start and the end of the WAL append,
        the end of the scatter and the end of the fsync wait, and beside
        them the seconds of the native mirror's invalidation (None
        without a mirror)."""
        groups = [
            (effs, [vc] * len(effs), [self.my_dc] * len(effs))
            for _i, _t, vc, effs, _s, _c in pend
        ]
        try:
            errors, ticket, (t_wal0, t_wal1, mirror_s) = (
                self.store.apply_effect_groups(groups))
        except BaseException:
            # a non-WAL failure (device error): nothing scattered —
            # un-stamp every member's marks and counters, or later
            # txns would first-committer-abort against writes that
            # never existed
            for _i, _t, _vc, _e, stamped, ctr in reversed(pend):
                for ck, old in stamped.items():
                    if self.committed_keys.get(ck) == ctr:
                        if old is None:
                            self.committed_keys.pop(ck, None)
                        else:
                            self.committed_keys[ck] = old
            self.commit_counter = pend[0][5] - 1
            raise
        t_scat = t_fsync = time.monotonic()
        ok: List[tuple] = []
        # failure-atomic PER SUB-GROUP: a NACKed member rolls back
        # only its own stamps (reverse order unwinds same-key
        # overwrites; a sibling's newer stamp survives) and keeps
        # its counter hole — holes are safe, certification compares
        # magnitudes and safe-time pings may claim a ts that owns
        # no txn (nothing will arrive for it)
        for (i, txn, vc, effs, stamped, ctr), err in zip(
                reversed(pend), reversed(errors)):
            if err is None:
                ok.append((i, txn, vc, effs))
                continue
            for ck, old in stamped.items():
                if self.committed_keys.get(ck) == ctr:
                    if old is None:
                        self.committed_keys.pop(ck, None)
                    else:
                        self.committed_keys[ck] = old
            out[i] = self._wal_refusal(err)
        ok.reverse()  # commit order for listeners
        # ACK/VISIBILITY GATE: the group fsync was submitted before
        # the device scatter and ran concurrently with it; it must
        # COMPLETE before commit listeners publish to the inter-DC
        # stream (or the serving epoch publishes) — effects a crash
        # could un-happen must never be externally visible, or a
        # recovered node re-mints the same (shard, origin, opid)
        # and remote DCs drop the new ops as duplicates.  A failed
        # or stalled fsync fails every ack in the batch typed and
        # flips read-only: the durable state is ambiguous until the
        # volume heals (see docs/operations.md).
        if ticket is not None:
            try:
                try:
                    with span("commit.fsync_wait"):
                        ticket.wait()
                except TimeoutError as e:
                    raise OSError(
                        errno.EIO, f"WAL group fsync stalled: {e}"
                    ) from e
            except OSError as e:
                err = self._wal_refusal(e)
                for i, _t, _vc, _e in ok:
                    out[i] = err
                ok = []
            t_fsync = time.monotonic()
        # the group minted EVERY member's commit counter above, but
        # members publish one at a time below — so a safe-time read
        # from inside an early member's egress listener (the
        # commit-path heartbeat threshold) would return a counter
        # covering still-unpublished members.  A subscriber that
        # trusts such a ping advances its chain clock past them and
        # then drops their real messages as duplicates: permanently
        # lost effects.  The flag makes listeners defer heartbeats
        # until the whole group is on the stream.
        self._publishing_group = len(ok) > 1
        try:
            for _i, txn, commit_vc, effects in ok:
                for listener in self.commit_listeners:
                    listener(effects, commit_vc, self.my_dc)
                for eff, op in txn.writeset:
                    self.hooks.execute_post_commit_hook(
                        eff.key, eff.type_name, eff.bucket, op
                    )
        finally:
            self._publishing_group = False
        return (t_wal0, t_wal1, t_scat, t_fsync), mirror_s

    def _gc_committed_keys(self) -> None:
        """Drop certification entries no open (or future) txn can conflict
        with: cert aborts iff last_commit > snapshot, every open txn's
        own-lane snapshot is ≥ the floor, and future txns start at the
        current counter — so entries ≤ floor are dead weight."""
        floor = min(self._open_snaps.values(), default=self.commit_counter)
        if self.commit_counter - floor > 64 * self._cert_gc_every:
            # an ancient open transaction (leaked coordinator?) is pinning
            # the floor — the certification table cannot shrink past it.
            # Server-side connection cleanup aborts orphans; surface the
            # stragglers loudly rather than silently growing.
            import warnings

            warnings.warn(
                f"certification GC floor lags {self.commit_counter - floor} "
                f"commits behind: {len(self._open_snaps)} transaction(s) "
                "left open",
                RuntimeWarning,
                stacklevel=2,
            )
        if floor <= 0:
            return
        self.committed_keys = {
            k: v for k, v in self.committed_keys.items() if v > floor
        }

    def _mark_aborted(self, txn: Transaction) -> None:
        """Close an active txn as aborted, keeping the gauge/counter exact."""
        self._open_snaps.pop(txn.txid, None)
        if txn.active and self.metrics is not None:
            self.metrics.open_transactions.dec()
            self.metrics.aborted_transactions.inc()
        txn.active = False

    def abort_transaction(self, txn: Transaction) -> None:
        self._mark_aborted(txn)
        txn.writeset.clear()

    # ------------------------------------------------------------------
    # static transactions (cure.erl fast paths, :118-183)
    # ------------------------------------------------------------------
    def update_objects_static(
        self, updates: Sequence[Update], clock: Optional[np.ndarray] = None
    ) -> np.ndarray:
        txn = self.start_transaction(clock)
        try:
            self.update_objects(updates, txn)
            return self.commit_transaction(txn)
        except Exception:
            # the static caller owns this txn and can never retry its
            # txid — a commit shed (backlog BusyError leaves the txn
            # OPEN for interactive retries) must not leak it into the
            # certification-GC floor
            if txn.active:
                self.abort_transaction(txn)
            raise

    def read_objects_static(
        self, objects: Sequence[BoundObject], clock: Optional[np.ndarray] = None
    ):
        txn = self.start_transaction(clock)
        try:
            vals = self.read_objects(objects, txn)
            self.commit_transaction(txn)  # empty writeset: closes the txn
        except Exception:
            if txn.active:
                self.abort_transaction(txn)
            raise
        return vals, txn.snapshot_vc

    # ------------------------------------------------------------------
    # remote ingestion (used by the inter-DC layer's causal gate)
    # ------------------------------------------------------------------
    def apply_remote(
        self, effects: Sequence[Effect], commit_vc: np.ndarray, origin: int
    ) -> None:
        commit_vc = np.asarray(commit_vc, np.int32)
        self.store.apply_effects(
            effects, [commit_vc] * len(effects), [origin] * len(effects)
        )
        if self.serving_epochs:
            # keep the lock-free read plane's snapshot moving with
            # replication (callers already hold the reentrant commit lock)
            with self.commit_lock:
                try:
                    self._publish_serving_epoch_locked()
                except Exception:
                    log.exception("serving-epoch publish failed")
                # no lag-floor bump here: remote effects were never acked
                # to a local client, so clockless reads owe them nothing
                # (the ticker's retry publishes them within a tick)

    # ------------------------------------------------------------------
    def _read_states_with_overlay(self, objects, txn):
        # snapshot base states are immutable for the txn's lifetime:
        # serve repeats from the txn cache, read only the misses
        miss = [i for i, (k, _t, b) in enumerate(objects)
                if (k, b) not in txn.base_states]
        if miss:
            fresh = self.store.read_states(
                [objects[i] for i in miss], txn.snapshot_vc)
            for i, st in zip(miss, fresh):
                k, _t, b = objects[i]
                txn.base_states[(k, b)] = st
        states = [txn.base_states[(k, b)] for k, _t, b in objects]
        if not txn.writeset:
            return states
        # overlay pending writes (materialize_eager,
        # /root/reference/src/clocksi_materializer.erl:272-274); a tentative
        # commit VC one past the snapshot stamps uncommitted dots (frozen
        # at the txn's first overlay so all its dots share one stamp)
        if txn.tentative_vc is None:
            tentative = txn.snapshot_vc.copy()
            tentative[self.my_dc] = self.commit_counter + 1
            txn.tentative_vc = tentative
        import jax.numpy as jnp

        tvc = jnp.asarray(txn.tentative_vc, jnp.int32)
        origin = jnp.int32(self.my_dc)
        from antidote_tpu.store.kv import _pad_lane

        for i, (key, type_name, bucket) in enumerate(objects):
            pend = txn.pending_for(key, bucket)
            if not pend:
                continue
            ty = get_type(type_name)
            # overlay at the key's slot-tier widths (promoted keys carry
            # wider state; pending effect lanes pad up to match)
            ent = self.store.locate(key, type_name, bucket, create=False)
            cfg_k = self.store.table(ent[0]).cfg if ent else self.cfg
            apply_host = getattr(ty, "apply_host", None)
            dk = (key, bucket)
            cached = txn.overlay_cache.get(dk)
            if cached is not None and cached[1] <= len(pend):
                state, done = cached
            else:
                state = states[i]
                if apply_host is None:
                    state = {f: jnp.asarray(x) for f, x in state.items()}
                done = 0
            if apply_host is not None:
                # host twin (e.g. rga): a few numpy ops per effect beat a
                # compiled-fn dispatch on the per-op overlay path
                tvc_np = np.asarray(txn.tentative_vc, np.int32)
                for eff in pend[done:]:
                    state = apply_host(
                        cfg_k, state,
                        _pad_lane(eff.eff_a, ty.eff_a_width(cfg_k),
                                  np.int64),
                        _pad_lane(eff.eff_b, ty.eff_b_width(cfg_k),
                                  np.int32),
                        tvc_np, self.my_dc,
                    )
            else:
                apply_fn = _jitted_apply(ty.name, cfg_k)
                for eff in pend[done:]:
                    state = apply_fn(
                        state,
                        jnp.asarray(_pad_lane(
                            eff.eff_a, ty.eff_a_width(cfg_k), np.int64)),
                        jnp.asarray(_pad_lane(
                            eff.eff_b, ty.eff_b_width(cfg_k), np.int32)),
                        tvc,
                        origin,
                    )
            txn.overlay_cache[dk] = (state, len(pend))
            # hand back the overlaid state as-is (device arrays for
            # jitted types, host numpy for apply_host types): consumers
            # np.asarray only the fields they touch — converting all of
            # them eagerly was the rga populate hot spot
            states[i] = state
        return states
