"""ClusterMember — one node of a multi-node DC.

The reference builds a DC from several BEAM nodes via riak_core staged
join (/root/reference/src/antidote_dc_manager.erl:53-81): the ring
assigns each node a subset of partitions, vnode commands route to owners,
and per-node stable-time gossip aggregates the DC's stable snapshot
(/root/reference/src/meta_data_sender.erl:224-255).  Here:

  * shard ownership: member ``i`` of ``n`` owns shards {s : s % n == i}
    (an explicit list may override);
  * member 0 is the DC's commit SEQUENCER: it mints the DC-wide own-lane
    commit timestamps, returning per-shard previous-ts chains so owners
    apply own-DC commits gap-free in ts order (the same chain discipline
    the inter-DC opid protocol uses);
  * owners certify at prepare (first-committer-wins per key + a prepared
    lock, the prepared_tx ETS of
    /root/reference/src/clocksi_vnode.erl:83-87,588-632) and apply at
    commit;
  * stable time: each member gossips its owned shards' applied clock
    rows; the DC stable snapshot is the entry-wise min over the
    assembled (members x shards) matrix via ``stable_min_of`` — the
    large-matrix path that dispatches to the streaming Pallas kernel.

Coordinators (cluster/coordinator.py) run on any member and drive these
handlers over the intra-DC RPC.

Fault tolerance (the reference's supervised-coordinator/vnode-takeover
story, /root/reference/src/clocksi_interactive_coord_sup.erl:44,
/root/reference/src/antidote_sup.erl:57-158, exercised by
/root/reference/test/multidc/multiple_dcs_node_failure_SUITE.erl:79-99):

  * PREPARE LOG: with a ``log_dir``, every prepare/commit/abort and
    every sequencer issue is appended to a durable ``prepare.wal`` next
    to the shard WALs, so staged write-sets and the ts ledger survive a
    member crash (the reference writes prepare records to
    logging_vnode before commit for the same reason).
  * TAKEOVER: a coordinator dying between sequencing and the commit
    fan-out leaves a hole in a shard's ts chain.  Any member can call
    ``resolve_wedged()``: the sequencer looks up the blocking txn,
    polls every member for its outcome, and either completes the commit
    (someone already applied it — atomicity) or aborts it everywhere
    after a block barrier that shuts the door on a still-racing zombie
    coordinator.  Decisions are recorded at the sequencer, so
    re-resolution is idempotent.
  * REJOIN: boot with ``recover=True`` on the same ``log_dir`` — the
    store replays its WAL, the prepare log restores staged txns +
    prepared locks + the sequencer ledger, and ``resolve_wedged()``
    settles anything issued around the crash.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from antidote_tpu.api.node import AntidoteNode
from antidote_tpu.cluster.rpc import RpcClient, RpcServer, eff_from_wire
from antidote_tpu.config import AntidoteConfig
from antidote_tpu.crdt import get_type
from antidote_tpu.store.kv import freeze_key, key_to_shard, stable_min_of

log = logging.getLogger(__name__)


def owned_shards(cfg: AntidoteConfig, member_id: int, n_members: int):
    """The INITIAL (boot-time) modular layout.  Ownership afterwards is
    governed solely by the explicit shard map + live join/leave moves."""
    return [s for s in range(cfg.n_shards) if s % n_members == member_id]


def _count_shard_move(role: str) -> None:
    try:
        from antidote_tpu.obs.metrics import net_metrics

        net_metrics().shard_moves.inc(role=role)
    except Exception:  # metrics must never break a move
        pass


#: bound on remembered txn outcomes / ledger entries (GC floor)
_LEDGER_CAP = 8192


def overlay_digest(seed: int, wires) -> int:
    """Rolling, process-independent fingerprint of an effect-wire
    sequence (incremental overlay shipping)."""
    import zlib

    d = seed
    for w in wires:
        d = zlib.crc32(w["eb"], zlib.crc32(w["a"], d)) & 0xFFFFFFFF
    return d


class Sequencer:
    """DC-wide commit-timestamp authority (member 0).

    ``next_ts(shards, txid)`` -> (ts, {shard: previous ts issued for
    it}) — the per-shard chain lets owners apply own-DC commits
    contiguously.  The ledger (``issued`` + per-shard ``chain``) is what
    takeover consults to identify the txn blocking a wedged chain."""

    def __init__(self):
        self._lock = threading.Lock()
        self.counter = 0
        self.last_ts: Dict[int, int] = {}
        #: ts -> (txid, [shards], {shard: prev}, monotonic issue time)
        self.issued: "OrderedDict[int, tuple]" = OrderedDict()
        #: shard -> [(ts, txid)] ascending (bounded)
        self.chain: Dict[int, List[Tuple[int, int]]] = {}
        #: txid -> ts (was this txn ever issued a ts? bounded like issued)
        self.txid_index: "OrderedDict[int, int]" = OrderedDict()
        #: txid -> takeover decision tuple (idempotent re-resolution);
        #: trimmed to _LEDGER_CAP like every other outcome ledger —
        #: stickiness is already best-effort once those GC (r4 advisor)
        self.resolutions: "OrderedDict[int, tuple]" = OrderedDict()

    def next_ts(self, shards, txid: int = 0) -> Tuple[int, Dict[int, int]]:
        with self._lock:
            self.counter += 1
            ts = self.counter
            prev = {}
            for s in shards:
                s = int(s)
                prev[s] = self.last_ts.get(s, 0)
                self.last_ts[s] = ts
                self.chain.setdefault(s, []).append((ts, int(txid)))
                if len(self.chain[s]) > _LEDGER_CAP:
                    del self.chain[s][: -_LEDGER_CAP // 2]
            self.issued[ts] = (int(txid), [int(s) for s in shards], prev,
                               time.monotonic())
            if txid:
                self.txid_index[int(txid)] = ts
            while len(self.issued) > _LEDGER_CAP:
                self.issued.popitem(last=False)
            while len(self.txid_index) > _LEDGER_CAP:
                self.txid_index.popitem(last=False)
            return ts, prev

    def trim_resolutions(self) -> None:
        with self._lock:
            while len(self.resolutions) > _LEDGER_CAP:
                self.resolutions.popitem(last=False)

    def restore_issue(self, ts: int, txid: int, shards, prev) -> None:
        """Rebuild one ledger entry from the prepare log (recovery).
        Restored entries carry issue-time 0 — older than any grace."""
        with self._lock:
            self.counter = max(self.counter, int(ts))
            for s in shards:
                s = int(s)
                self.last_ts[s] = max(self.last_ts.get(s, 0), int(ts))
                self.chain.setdefault(s, []).append((int(ts), int(txid)))
            self.issued[int(ts)] = (
                int(txid), [int(s) for s in shards],
                {int(k): int(v) for k, v in prev.items()}, 0.0,
            )
            if txid:
                self.txid_index[int(txid)] = int(ts)

    def entry_after(self, shard: int, after_ts: int):
        """The earliest issued (ts, txid) on ``shard`` with ts >
        after_ts — the txn a wedged chain is waiting for."""
        with self._lock:
            for ts, txid in self.chain.get(int(shard), ()):
                if ts > after_ts:
                    return ts, txid
            return None


class ClusterMember:
    def __init__(self, cfg: AntidoteConfig, dc_id: int, member_id: int,
                 n_members: int, log_dir: Optional[str] = None,
                 host: str = "127.0.0.1", shards=None,
                 recover: bool = False, meta=None):
        self.cfg = cfg
        self.dc_id = dc_id
        self.member_id = member_id
        self.n_members = n_members
        self.shards = set(shards if shards is not None
                          else owned_shards(cfg, member_id, n_members))
        if (n_members > 1 and self.shards
                and self.shards != set(owned_shards(cfg, member_id,
                                                    n_members))):
            # the DEFAULT layout is modular; arbitrary static assignments
            # would desynchronize every member's shard_map.  (An EMPTY
            # set is the live-join boot state: the joiner owns nothing
            # until shards stream over, cluster/join.py.)
            raise ValueError(
                "multi-member DCs boot with the modular shard layout "
                "(shard s owned by member s % n_members, or an empty set "
                "for a live-joining member); ownership then moves only "
                "through the live join/leave protocol so every member's "
                "shard map stays consistent")
        #: shard -> owning member id — the explicit ownership map (the
        #: riak_core ring analogue) and the SINGLE routing truth: starts
        #: modular, then live join/leave updates it in lock-step with
        #: the data moves (durable own events), and stale coordinators
        #: converge through not_owner retry.  ``n_members`` is the
        #: member-id-space BOUND (max assigned id + 1), not a live
        #: count — a mid-id live leave opens a gap that nothing modular
        #: routes across.
        #
        #: A live-joining member (explicit EMPTY shard set) boots with a
        #: GUESS of the current layout — modular over the pre-join
        #: count — not the future one: epoch-guarded refreshes never
        #: downgrade a map entry, so a speculative future-layout guess
        #: would leave the joiner routing to not-yet-owners for the
        #: whole join.  The live_join driver then seeds the REAL map
        #: (m_seed_map), which matters once earlier joins/leaves have
        #: reshaped it away from modular.
        layout_n = n_members
        if shards is not None and not self.shards and n_members > 1:
            layout_n = n_members - 1
        self.shard_map: Dict[int, int] = {
            s: s % layout_n for s in range(cfg.n_shards)
        }
        for s in self.shards:
            self.shard_map[s] = member_id
        self.node = AntidoteNode(cfg, dc_id=dc_id, log_dir=log_dir,
                                 recover=recover, meta=meta)
        self._coordinator = None
        #: sequencer lives on member 0 only
        self.seq = Sequencer() if member_id == 0 else None
        #: peer member_id -> RpcClient
        self.peers: Dict[int, RpcClient] = {}
        #: peer member_id -> last gossiped [n_shards, D] clock rows
        #: (only the peer's owned rows are meaningful)
        self.peer_clocks: Dict[int, np.ndarray] = {}
        # reentrant: m_commit holds the lock while its apply fires the
        # inter-DC commit listeners, whose heartbeat path re-enters
        # prepared_on_shard for the safe-time check
        self._lock = threading.RLock()
        #: (key, bucket) -> txid holding the prepare lock
        self.prepared: Dict[Tuple[Any, str], int] = {}
        #: txid -> (effects, [keys]) buffered between prepare and commit
        self.staged: Dict[int, Tuple[list, list]] = {}
        #: (key, bucket) -> own-lane ts of its last commit (cert table)
        self.last_commit: Dict[Tuple[Any, str], int] = {}
        #: shards mid-move (exported, not yet relinquished): prepares and
        #: reads refuse retryably so the in-flight package stays exact.
        #: Deliberately VOLATILE — a crash wipes it, reopening the shard
        #: (ownership only flips durably at relinquish)
        self.moving: set = set()
        #: per-shard ownership VERSION (the riak_core ring-epoch role):
        #: every completed move bumps it by one, and stale gossip is
        #: rejected by epoch comparison — without this, two members can
        #: re-infect each other with a pre-move owner forever (each
        #: pulling the other's stale map entry after a refresh race)
        self.shard_epoch: Dict[int, int] = {
            s: 0 for s in range(cfg.n_shards)
        }
        #: per owned shard: last own-DC ts applied (chain frontier)
        self.applied_ts: Dict[int, int] = {s: 0 for s in self.shards}
        #: per shard: {prev_ts: (txid, effects, commit_vc)} awaiting chain
        self.chain_wait: Dict[int, Dict[int, tuple]] = {
            s: {} for s in self.shards
        }
        #: member ids that live-LEFT this cluster (durable): a departed
        #: id must never be handed out again — its log dir and the
        #: (owner, epoch) routes remote DCs learned for its fabric id
        #: would alias the new member.  Wiring alone cannot distinguish
        #: an interrupted-join re-run from a reuse; this set can.
        self.departed: set = set()
        #: commit listeners (inter-DC egress seam): (effects, vc, origin)
        self.on_commit: List = []
        #: live-move seams for the inter-DC plane (attach_interdc):
        #: export_extras(shard) dicts merge into the handoff package's
        #: "x" namespace; on_shard_import(shard, extras) installs them at
        #: the destination; on_shard_relinquish(shard) clears the
        #: source's egress/ingress chain state.  All three run under the
        #: cross-plane commit lock, so they are serialized against the
        #: remote-ingress drain.
        self.export_extras: List = []
        self.on_shard_import: List = []
        self.on_shard_relinquish: List = []
        #: txid -> (vc_wire, prev_wire) of applied commits (takeover polls)
        self.committed_txns: "OrderedDict[int, tuple]" = OrderedDict()
        #: txids barred from committing pending a takeover decision
        self.blocked_txns: set = set()
        #: txids resolved-aborted by takeover (bounded)
        self.aborted_txns: "OrderedDict[int, bool]" = OrderedDict()
        #: txid -> monotonic stage time (stale-prepare sweeps)
        self.staged_at: Dict[int, float] = {}
        #: (key, bucket, read_vc bytes) -> (folded state, n, prefix digest)
        #: — incremental overlay folds: a txn's Nth same-key overlay call
        #: folds only the new effects, not the whole prefix again
        self._overlay_fold_cache: "OrderedDict[tuple, tuple]" = OrderedDict()
        #: durable prepare log (staged txns + sequencer ledger).  Honors
        #: cfg.sync_log like the shard WALs: fsync-per-commit off by
        #: default (the reference's sync_log=false stance — bounded loss
        #: on power failure, none on process kill).
        self._prep_wal = None
        self._prep_dir = log_dir
        self._prep_appends = 0
        if log_dir is not None:
            from antidote_tpu.log.wal import ShardWAL

            os.makedirs(log_dir, exist_ok=True)
            fresh = not os.path.exists(os.path.join(log_dir, "prepare.wal"))
            self._prep_wal = ShardWAL(os.path.join(log_dir, "prepare.wal"),
                                      sync_on_commit=cfg.sync_log)
            if fresh and not recover:
                # durable boot layout: recovery derives ownership from
                # THIS + the own-event trail, never from the (possibly
                # since-grown) member count passed at recover time — a
                # member crashing mid-live-join must come back owning
                # exactly what it durably owned.  The ACTUAL shard set
                # is recorded (a joiner boots with an EMPTY set, not the
                # modular share of its member count)
                self._prep_append({"ev": "boot_layout", "txid": 0,
                                   "n": int(n_members),
                                   "member": int(member_id),
                                   "shards": sorted(int(s)
                                                    for s in self.shards)})
        self._seq_cache = 0
        self._seq_cache_at = 0.0
        if recover:
            pending = self._recover_prepare_log(log_dir)
            # chain frontier = last own-DC ts applied per shard (the WAL
            # replay rebuilt applied_vc; own lane only advances by applied
            # own-DC commits, so its value IS the frontier)
            for s in self.shards:
                self.applied_ts[s] = int(
                    self.node.store.applied_vc[s, self.dc_id])
            self._replay_recovered_commits(pending)
        # checkpoint image extras (ISSUE 8): the membership + departed-id
        # state rides in every checkpoint this member's node publishes.
        # INFORMATIONAL in this build — the prepare log stays the
        # authoritative ownership record at recovery (it compacts
        # independently and re-emits the full membership state) — but it
        # makes `console inspect-checkpoint` show who owned what at the
        # stamp, and the durable shard-reset epoch (bumped by the
        # relinquish path's truncate_shard) is what guarantees a shard
        # moved AFTER a checkpoint never resurrects here from the image.
        self.node.checkpoint_extras_providers["membership"] = (
            self._checkpoint_membership)
        self.rpc = RpcServer(host=host)
        for name in ("m_read_values", "m_downstream", "m_prepare",
                     "m_commit", "m_abort", "m_clocks", "m_seq",
                     "m_ready", "m_seq_counter", "m_txn_status",
                     "m_block_txn", "m_forget_txn", "m_resolve_chain",
                     "m_txn_sequenced", "m_resolve_stale_txn",
                     "m_process_transfer", "m_shard_map", "m_membership",
                     "m_join_begin",
                     "m_seed_map", "m_export_shard", "m_import_shard",
                     "m_relinquish_shard", "m_cancel_export", "m_set_owner",
                     "m_forget_member"):
            self.rpc.register(name, getattr(self, name))

    def _checkpoint_membership(self) -> dict:
        """Membership snapshot for the checkpoint image (called under the
        commit lock by the checkpointer's stamp barrier)."""
        with self._lock:
            return {
                "member_id": int(self.member_id),
                "n_members": int(self.n_members),
                "shards": sorted(int(s) for s in self.shards),
                "shard_map": {str(s): int(o)
                              for s, o in self.shard_map.items()},
                "shard_epoch": {str(s): int(e)
                                for s, e in self.shard_epoch.items()},
                "departed": sorted(int(m) for m in self.departed),
            }

    @property
    def _xlock(self):
        """Cross-plane writer lock (the node's reentrant commit lock).

        ``KVStore.apply_effects`` is a read-modify-reassign of the
        device tables, so the store tolerates exactly ONE concurrent
        writer.  For a clustered member there are two writer planes: own
        commits (RPC server threads, ``m_commit``/``m_forget_txn``) and
        remote inter-DC ingress (the fabric pump's gate drain, which
        already serializes under ``node.txm.commit_lock`` — the r5
        advisor high).  Every member path that mutates or snapshots
        store state takes THIS lock first, then ``self._lock`` — the
        one consistent order (nothing acquires the commit lock while
        holding the member lock), so a pump drain can never interleave
        with a member-side apply and silently drop a batch.  Shard
        export/import/relinquish take it too: a package must not be
        built (or installed) while remote effects are landing."""
        return self.node.txm.commit_lock

    def coordinator(self):
        """This member's own transaction coordinator (any member may
        coordinate; lazily built to avoid an import cycle)."""
        if self._coordinator is None:
            from antidote_tpu.cluster.coordinator import ClusterNode

            self._coordinator = ClusterNode(self)
        return self._coordinator

    # ------------------------------------------------------------------
    # durable prepare log
    # ------------------------------------------------------------------
    def _prep_append(self, rec: dict) -> None:
        if self._prep_wal is not None:
            self._prep_wal.append(rec)
            self._prep_wal.commit()
            self._prep_appends += 1
            if self._prep_appends >= _LEDGER_CAP * 2:
                self._compact_prepare_log()

    def _compact_prepare_log(self) -> None:
        """Rewrite prepare.wal from live state: undecided preps + the
        outcome/ledger tails.  Bounds disk use and recovery replay time
        to O(in-flight + LEDGER_CAP), not O(all txns ever).  Caller must
        hold (or be on a path that holds) the member lock; seq_ts also
        serializes through it."""
        from antidote_tpu.cluster.rpc import eff_to_wire
        from antidote_tpu.log.wal import ShardWAL

        with self._lock:
            path = os.path.join(self._prep_dir, "prepare.wal")
            tmp = path + ".tmp"
            if os.path.exists(tmp):
                os.remove(tmp)  # reclaim-ok: stale compaction temp from
                # a crashed rewrite; the live prepare.wal is untouched
            w = ShardWAL(tmp, sync_on_commit=False)
            # MEMBERSHIP STATE FIRST: compaction rewrites the log from
            # live state, and without these records a post-move member
            # would recover with the modular GUESS of its recover-time
            # count — silently claiming shards it gave away.  One
            # boot_layout (actual owned set + id-space bound), the full
            # current map with epochs, and the departed-id set.
            w.append({"ev": "boot_layout", "txid": 0,
                      "n": int(self.n_members),
                      "member": int(self.member_id),
                      "shards": sorted(int(s) for s in self.shards)})
            for s in range(self.cfg.n_shards):
                w.append({"ev": "own", "txid": 0, "shard": int(s),
                          "owner": int(self.shard_map.get(s, 0)),
                          "epoch": int(self.shard_epoch.get(s, 0))})
            w.append({"ev": "members", "txid": 0,
                      "n": int(self.n_members)})
            for mid in sorted(self.departed):
                w.append({"ev": "departed", "txid": 0,
                          "member": int(mid)})
            if self.seq is not None:
                for ts, (txid, shards, prev, _) in self.seq.issued.items():
                    w.append({"ev": "seq", "ts": int(ts), "txid": int(txid),
                              "shards": shards,
                              "prev": {int(k): int(v)
                                       for k, v in prev.items()}})
            for txid, (effects, _, snap_own) in self.staged.items():
                rec = {"ev": "prep", "txid": int(txid),
                       "effs": [eff_to_wire(e) for e in effects]}
                if snap_own is not None:
                    rec["snap"] = int(snap_own)
                w.append(rec)
            for txid, (vc, prev) in self.committed_txns.items():
                w.append({"ev": "commit", "txid": int(txid), "vc": vc,
                          "prev": {int(k): int(v) for k, v in prev.items()}})
            for txid in self.aborted_txns:
                w.append({"ev": "abort", "txid": int(txid)})
            w.commit()
            w.sync()
            w.close()
            self._prep_wal.close()
            os.replace(tmp, path)
            from antidote_tpu.log.wal import ShardWAL as _W

            self._prep_wal = _W(path, sync_on_commit=self.cfg.sync_log)
            self._prep_appends = 0

    def _recover_prepare_log(self, log_dir: Optional[str]) -> list:
        """Fold prepare.wal: staged-but-undecided txns come back with
        their prepared locks; decided txns restore the outcome tables;
        sequencer issues rebuild the ts ledger (member 0).

        Returns the committed txns in log order WITHOUT dropping their
        staged effects — a crash may have landed between the durable
        commit record and the store apply, so the caller re-applies any
        whose chain frontier shows them unapplied
        (:meth:`_replay_recovered_commits`)."""
        pending: list = []
        if log_dir is None:
            return pending
        from antidote_tpu.log.wal import replay

        path = os.path.join(log_dir, "prepare.wal")
        if not os.path.exists(path):
            return pending
        for rec in replay(path):
            ev = rec.get("ev")
            txid = int(rec.get("txid", 0))
            if ev == "prep":
                effects = [eff_from_wire(w) for w in rec["effs"]]
                keys = [(e.key, e.bucket) for e in effects]
                snap = rec.get("snap")
                self.staged[txid] = (effects, keys,
                                     None if snap is None else int(snap))
                self.staged_at[txid] = 0.0  # older than any sweep grace
                for dk in keys:
                    self.prepared[dk] = txid
            elif ev == "commit":
                prev = {int(k): int(v) for k, v in rec["prev"].items()}
                self.committed_txns[txid] = (rec["vc"], prev)
                pending.append((txid, rec["vc"], prev))
            elif ev == "abort":
                self._drop_staged(txid)
                self.aborted_txns[txid] = True
            elif ev == "seq" and self.seq is not None:
                self.seq.restore_issue(rec["ts"], txid, rec["shards"],
                                       rec["prev"])
            elif ev == "boot_layout":
                # authoritative starting ownership (own events below
                # adjust it); overrides the modular guess from the
                # recover-time member count.  Records lacking the
                # explicit set predate it — fall back to modular(n).
                n0 = int(rec["n"])
                booted = rec.get("shards")
                self.shards = (set(int(s) for s in booted)
                               if booted is not None
                               else set(owned_shards(self.cfg,
                                                     self.member_id, n0)))
                self.shard_map = {
                    s: s % n0 for s in range(self.cfg.n_shards)
                }
                for s in self.shards:
                    self.shard_map[s] = self.member_id
                self.shard_epoch = {
                    s: 0 for s in range(self.cfg.n_shards)
                }
                self.applied_ts = {s: 0 for s in self.shards}
                self.chain_wait = {s: {} for s in self.shards}
            elif ev == "own":
                # live-membership ownership change (durable: a member
                # crashing mid-join must rejoin with the moved layout)
                s, owner = int(rec["shard"]), int(rec["owner"])
                self.shard_map[s] = owner
                self.shard_epoch[s] = int(rec.get(
                    "epoch", self.shard_epoch.get(s, 0) + 1))
                if owner == self.member_id:
                    self.shards.add(s)
                    self.applied_ts.setdefault(s, 0)
                    self.chain_wait.setdefault(s, {})
                else:
                    self.shards.discard(s)
                    self.applied_ts.pop(s, None)
                    self.chain_wait.pop(s, None)
            elif ev == "members":
                # monotone on replay too: pre-fix logs may hold a
                # shrunken value from an old leave driver
                self.n_members = max(self.n_members, int(rec["n"]))
            elif ev == "departed":
                self.departed.add(int(rec["member"]))
        self._trim_ledgers()
        return pending

    def _replay_recovered_commits(self, pending: list) -> None:
        """Finish commits whose durable decision preceded the crash but
        whose effects never reached the store (still staged + frontier
        below their ts).  Shards already at/past the ts are skipped —
        their effects were applied and WAL-replayed."""
        for txid, vc, prev in pending:
            if txid not in self.staged:
                continue  # applied pre-crash (or compacted as decided)
            ts = int(np.asarray(vc)[self.dc_id])
            effects, keys, snap_own = self.staged.pop(txid)
            # snap_own None = legacy record predating overlay stamping:
            # its effects carry no tentative dots, nothing to rewrite
            if snap_own is not None and snap_own + 1 != ts:
                for eff in effects:
                    ty_e = get_type(eff.type_name)
                    eff.eff_a, eff.eff_b = ty_e.restamp_own_dots(
                        self.cfg, eff.eff_a, eff.eff_b, self.dc_id,
                        snap_own + 1, ts)
            by_shard: Dict[int, list] = {}
            for eff in effects:
                _, shard, _ = self.node.store.locate(
                    eff.key, eff.type_name, eff.bucket
                )
                if shard in self.shards and self.applied_ts[shard] < ts:
                    by_shard.setdefault(shard, []).append(eff)
            cvc = np.asarray(vc, np.int32)
            for shard, effs in by_shard.items():
                self._chain_apply(shard, int(prev.get(shard, 0)), ts, effs,
                                  cvc)
            for dk in keys:
                if self.prepared.get(dk) == txid:
                    del self.prepared[dk]
                self.last_commit[dk] = max(self.last_commit.get(dk, 0), ts)
            self.staged_at.pop(txid, None)

    def _drop_staged(self, txid: int) -> None:
        self.staged_at.pop(txid, None)
        effects_keys = self.staged.pop(txid, None)
        if effects_keys is not None:
            for dk in effects_keys[1]:
                if self.prepared.get(dk) == txid:
                    del self.prepared[dk]

    def _trim_ledgers(self) -> None:
        while len(self.committed_txns) > _LEDGER_CAP:
            self.committed_txns.popitem(last=False)
        while len(self.aborted_txns) > _LEDGER_CAP:
            self.aborted_txns.popitem(last=False)

    # ------------------------------------------------------------------
    def connect(self, member_id: int, host: str, port: int) -> None:
        self.peers[member_id] = RpcClient(host, port)

    @property
    def address(self) -> Tuple[str, int]:
        return (self.rpc.host, self.rpc.port)

    # ------------------------------------------------------------------
    # owner-side handlers (all run on RPC server threads; the node lock
    # serializes against other mutations)
    # ------------------------------------------------------------------
    def m_ready(self) -> bool:
        return True

    def prepared_on_shard(self, shard: int) -> bool:
        """Any prepared-but-uncommitted txn touching one of my keys on
        ``shard`` (gates the heartbeat safe time).  Snapshots the key set
        under the lock — RPC threads mutate ``prepared`` concurrently."""
        with self._lock:
            keys = list(self.prepared)
        for (key, bucket) in keys:
            if key_to_shard(key, bucket, self.cfg.n_shards) == shard:
                return True
        return False

    def m_seq(self, shards, txid: int = 0) -> Tuple[int, Dict[int, int]]:
        return self.seq_ts(shards, txid)

    def seq_ts(self, shards, txid: int = 0) -> Tuple[int, Dict[int, int]]:
        """Issue a commit ts + per-shard prev chain, durably ledgered —
        every coordinator (local or remote) must come through here so
        takeover can find the txn behind any issued ts.  The member lock
        serializes the ledger append with the other prepare-log writers
        (the WAL is single-writer) and keeps 'seq' records in ts order."""
        assert self.seq is not None, "not the sequencer"
        with self._lock:
            if txid and txid in self.seq.resolutions:
                # the stale-prepare sweep already decided this txn's fate
                # (coordinator stalled pre-seq, then woke up): refuse the
                # ts — issuing one would open a chain hole that the sticky
                # ts=0 resolution could never close
                raise RuntimeError(
                    f"abort: txn {txid} was resolved by takeover before "
                    "sequencing")
            ts, prev = self.seq.next_ts(shards, txid)
            prev_wire = {int(k): int(v) for k, v in prev.items()}
            self._prep_append({"ev": "seq", "ts": ts, "txid": int(txid),
                               "shards": [int(s) for s in shards],
                               "prev": prev_wire})
        return ts, prev_wire

    def m_seq_counter(self) -> int:
        assert self.seq is not None, "not the sequencer"
        return self.seq.counter

    def m_clocks(self) -> list:
        """My owned shards' applied clock rows: [(shard, [D])]."""
        self.advance_idle_shards()
        vc = self.node.store.applied_vc
        return [(s, [int(x) for x in vc[s]]) for s in sorted(self.shards)]

    def invalidate_seq_cache(self) -> None:
        """Force the next ``_seq_counter`` to refresh from the sequencer
        (called after a certification abort: the conflict proves the
        frontier moved past our cached view)."""
        self._seq_cache_at = 0.0

    def _seq_counter(self) -> int:
        """The DC timestamp frontier (locally for the sequencer, cached
        RPC otherwise)."""
        if self.seq is not None:
            return self.seq.counter
        import time as _t

        now = _t.monotonic()
        if now - self._seq_cache_at > 0.2 and 0 in self.peers:
            try:
                self._seq_cache = int(self.peers[0].call("m_seq_counter"))
                self._seq_cache_at = now
            except Exception:
                pass
        return self._seq_cache

    def advance_idle_shards(self) -> None:
        """Own-lane safe-time advance for idle owned shards: with no
        prepared or chain-buffered txn touching a shard, every issued ts
        is already applied there (prepare precedes sequencing), so its
        own-lane clock may claim the sequencer frontier — the intra-DC
        analogue of the single-node heartbeat self-advance, and what lets
        the aggregated stable snapshot progress past untouched shards."""
        ctr = self._seq_counter()
        if ctr == 0:
            return
        vc = self.node.store.applied_vc
        own = self.dc_id
        for s in self.shards:
            # lock-free walk racing a live shard move: a popped entry
            # means the shard just left this member — skip it
            if self.chain_wait.get(s) or self.prepared_on_shard(s):
                continue
            if s in self.shards and vc[s, own] < ctr:
                vc[s, own] = ctr

    def m_read_values(self, objects, read_vc, overlays=None) -> list:
        """Owner read: values at ``read_vc`` for my keys (the serving
        path: store.read_values -> read_resolved).

        ``overlays`` (aligned with ``objects``; None entries = plain)
        carries a coordinator txn's own pending effects for each object —
        read-your-writes in open cluster transactions: the owner reads
        the base state at the snapshot, folds the txn's effects eagerly
        (materialize_eager), and returns the overlaid value.

        Before reading, each involved shard waits until its own-lane
        clock can safely claim ``read_vc[own]`` — an in-flight commit
        (prepared here, ts possibly already issued) below that ts would
        otherwise make the snapshot observe a txn partially, the exact
        hazard clocksi_readitem_server's check_prepared_list blocks on
        (/root/reference/src/clocksi_readitem_server.erl:254-264)."""
        objs = [(freeze_key(k), t, b) for k, t, b in objects]
        read_vc = np.asarray(read_vc, np.int32)
        want = int(read_vc[self.dc_id])
        shards = {
            key_to_shard(k, b, self.cfg.n_shards) for k, _, b in objs
        }
        for s in shards:
            self._check_owner(s)
            self._wait_read_safe(s, want)
        with self._lock:
            if not overlays or not any(overlays):
                vals = self.node.store.read_values(objs, read_vc)
            else:
                vals = self._read_values_overlaid(objs, read_vc, overlays)
        return [_wire_value(v) for v in vals]

    def _overlay_state(self, key, type_name, bucket, state, read_vc,
                       overlay) -> dict:
        """Fold a txn's pending effect wires onto a host state copy
        (materialize_eager at the owner).  The tentative own-lane stamp
        is read_vc[own]+1 = snapshot+1 — the same value m_commit's
        restamp rewrites to the real commit ts.

        ``overlay`` is the incremental form ``{"n": prefix_len,
        "d": prefix_digest, "effs": [new wires], "nd": digest after}`` —
        the coordinator ships only the effects the owner has not folded
        yet (O(N) wire bytes AND folds over a txn's life, not O(N^2)).
        An owner that lost its cached prefix (restart, eviction) raises
        ``overlay-resync`` and the coordinator re-sends in full.  The
        digest is a process-independent rolling CRC (python ``hash`` is
        per-process-seeded)."""
        import jax
        import jax.numpy as jnp

        from antidote_tpu.store.kv import _pad_lane
        from antidote_tpu.txn.manager import _jitted_apply

        store = self.node.store
        ty = get_type(type_name)
        ent = store.locate(key, type_name, bucket, create=False)
        cfg_k = store.table(ent[0]).cfg if ent else self.cfg
        apply_host = getattr(ty, "apply_host", None)
        apply_fn = None if apply_host else _jitted_apply(ty.name, cfg_k)
        tvc = np.asarray(read_vc, np.int32).copy()
        tvc[self.dc_id] += 1
        if apply_host is None:
            tvc_j = jnp.asarray(tvc, jnp.int32)
            origin = jnp.int32(self.dc_id)
        if not isinstance(overlay, dict):
            raise TypeError(
                "overlay must be the incremental dict form "
                "{'n', 'd', 'effs', 'nd'}")
        # the txid in the key means two txns sharing a (key, bucket,
        # snapshot) can never alias each other's fold prefix, whatever
        # the 32-bit digest says (r4 advisor); overlays from pre-txid
        # coordinators fall into a shared 0 lane, where the digest still
        # gates as before
        ck = (key, bucket, tvc.tobytes(), int(overlay.get("txid", 0)))
        cached = self._overlay_fold_cache.get(ck)
        n0, d0 = int(overlay["n"]), int(overlay["d"])
        wires, nd = overlay["effs"], int(overlay["nd"])
        n_total = n0 + len(wires)
        if (cached is not None and cached[1] == n_total
                and cached[2] == nd):
            # idempotent re-send (e.g. the same object twice in one read
            # batch): the suffix is already folded
            return jax.tree.map(np.asarray, cached[0])
        if n0 == 0:
            if apply_host is None:
                state = {f: jnp.asarray(x) for f, x in state.items()}
        elif (cached is not None and cached[1] == n0
                and cached[2] == d0):
            state = cached[0]
        else:
            raise RuntimeError(
                "overlay-resync: owner has no matching overlay "
                f"prefix for {key!r} (have "
                f"{None if cached is None else cached[1:3]}, "
                f"want ({n0}, {d0}))")
        for w in wires:
            eff = eff_from_wire(w)
            # the txn's blob payloads travel with its effects; the
            # owner must intern them before value decode resolves
            for h, data in eff.blob_refs:
                store.blobs.intern_bytes(h, data)
            ea = _pad_lane(eff.eff_a, ty.eff_a_width(cfg_k), np.int64)
            eb = _pad_lane(eff.eff_b, ty.eff_b_width(cfg_k), np.int32)
            if apply_host is not None:
                # host twin (rga): numpy ops beat per-effect dispatch
                state = apply_host(cfg_k, state, ea, eb, tvc, self.dc_id)
            else:
                state = apply_fn(state, jnp.asarray(ea), jnp.asarray(eb),
                                 tvc_j, origin)
        self._overlay_fold_cache[ck] = (state, n_total, nd)
        while len(self._overlay_fold_cache) > 512:
            self._overlay_fold_cache.popitem(last=False)
        return jax.tree.map(np.asarray, state)

    def _read_values_overlaid(self, objs, read_vc, overlays) -> list:
        store = self.node.store
        plain = [i for i, ov in enumerate(overlays) if not ov]
        laid = [i for i, ov in enumerate(overlays) if ov]
        vals: list = [None] * len(objs)
        if plain:
            pv = store.read_values([objs[i] for i in plain], read_vc)
            for i, v in zip(plain, pv):
                vals[i] = v
        states = store.read_states([objs[i] for i in laid], read_vc)
        for i, state in zip(laid, states):
            key, type_name, bucket = objs[i]
            ty = get_type(type_name)
            state = self._overlay_state(key, type_name, bucket, state,
                                        read_vc, overlays[i])
            ent = store.locate(key, type_name, bucket, create=False)
            cfg_k = store.table(ent[0]).cfg if ent else self.cfg
            vals[i] = ty.value(state, store.blobs, cfg_k)
        return vals

    def _wait_read_safe(self, shard: int, want_ts: int,
                        timeout: float = 30.0) -> None:
        import time as _t

        # the requested own-lane ts was derived from the sequencer
        # (stable/session/frontier), so it IS a frontier lower bound:
        # adopt it instead of stalling up to the cache-refresh window
        # waiting for idle-advance to learn the same number
        if self.seq is None and want_ts > self._seq_cache:
            self._seq_cache = want_ts
        deadline = _t.monotonic() + timeout
        while True:
            self.advance_idle_shards()
            if shard not in self.shards:
                # a live move took the shard mid-wait: its frozen local
                # clock would never reach want_ts — surface the
                # RETRYABLE ownership error, not a 30s timeout
                self._check_owner(shard)
            if int(self.node.store.applied_vc[shard, self.dc_id]) >= want_ts:
                return
            if _t.monotonic() > deadline:
                raise TimeoutError(
                    f"shard {shard} own-lane stuck below {want_ts} "
                    "(in-flight commit never arrived?)"
                )
            _t.sleep(0.001)

    def m_downstream(self, key, type_name, bucket, op, read_vc,
                     overlay=None) -> list:
        """Generate downstream effects for a state-dependent op at my
        replica of the key (clocksi_downstream:generate_downstream_op,
        /root/reference/src/clocksi_downstream.erl:38-68).  counter_b
        decrements/transfers run the escrow guard HERE at the key's
        owner (bcounter_mgr parity): the rights check uses the owner's
        replica state, and first-committer-wins certification closes
        the check-to-commit race between concurrent coordinators."""
        from antidote_tpu.cluster.rpc import eff_to_wire
        from antidote_tpu.store.kv import Effect, scaled_cfg, split_tier
        from antidote_tpu.txn.bcounter import NoPermissionsError

        key = freeze_key(key)
        op = _freeze_op(op)
        ty = get_type(type_name)
        read_vc = np.asarray(read_vc, np.int32)
        # same in-flight-commit gate as m_read_values: a downstream
        # generated from a snapshot missing a committed-but-unapplied op
        # would break observed-remove semantics
        shard = key_to_shard(key, bucket, self.cfg.n_shards)
        self._check_owner(shard)
        self._wait_read_safe(shard, int(read_vc[self.dc_id]))
        with self._lock:
            store = self.node.store
            state = store.read_states(
                [(key, type_name, bucket)], read_vc
            )[0]
            if overlay:
                # the coordinator's txn already holds pending effects for
                # this key: overlay them so the generated downstream
                # observes them (same-txn add-then-remove)
                state = self._overlay_state(key, type_name, bucket, state,
                                            read_vc, overlay)
            if type_name == "counter_b" and op[0] in ("decrement",
                                                      "transfer"):
                if op[0] == "decrement":
                    amount, src_lane = op[1]
                else:
                    amount, _to_dc, src_lane = op[1]
                if src_lane != self.dc_id:
                    raise RuntimeError(
                        f"abort: counter_b {op[0]} must spend this DC's "
                        f"lane {self.dc_id}, not {src_lane}")
                bcm = self.node.txm.bcounters
                try:
                    bcm.check_decrement(ty, state, key, bucket, amount)
                except NoPermissionsError as e:
                    if op[0] == "transfer":
                        bcm.satisfied(key, bucket)
                    raise RuntimeError(f"abort: {e}") from e
                bcm.satisfied(key, bucket)
            ent = store.locate(key, type_name, bucket, create=False)
            cfg_k = store.table(ent[0]).cfg if ent else self.cfg
            effs = ty.downstream(op, state, store.blobs, cfg_k)
        return [
            eff_to_wire(Effect(key, type_name, bucket, a, b, refs))
            for a, b, refs in effs
        ]

    def m_process_transfer(self, key, bucket, amount: int, to_dc: int
                           ) -> int:
        """Grant up to ``amount`` bcounter rights to ``to_dc`` from this
        DC's lane — the clustered bcounter_mgr:process_transfer: runs at
        the key's owner member and commits the transfer through the DC
        sequencer (this member's coordinator), so the grant is certified
        like any other txn."""
        from antidote_tpu.txn.manager import AbortError

        key = freeze_key(key)
        ty = get_type("counter_b")
        state = self.node.store.read_states(
            [(key, "counter_b", bucket)], self.node.store.dc_max_vc()
        )[0]
        held = ty.local_rights(state, self.dc_id)
        grant = min(int(amount), held)
        if grant <= 0:
            return 0
        try:
            self.coordinator().update_objects([
                (key, "counter_b", bucket,
                 ("transfer", (grant, int(to_dc), self.dc_id))),
            ])
        except AbortError:
            return 0  # lost a race for the rights; requester retries
        return grant

    # ------------------------------------------------------------------
    # live membership (the riak_core staged join/leave + ownership
    # handoff analogue, /root/reference/src/antidote_dc_manager.erl:53-81
    # + /root/reference/src/materializer_vnode.erl:221-246): shards move
    # one at a time between members WHILE THE CLUSTER SERVES — a move
    # briefly refuses new work on that one shard ("busy"/"not_owner"
    # retryable errors), never the cluster
    # ------------------------------------------------------------------
    def _check_owner(self, shard: int) -> None:
        if shard not in self.shards:
            raise RuntimeError(
                f"not_owner: shard {shard} owner "
                f"{self.shard_map.get(shard, -1)} "
                f"(asked member {self.member_id})"
            )
        if shard in self.moving:
            # exported but not yet relinquished: new work would make the
            # in-flight package stale — retryable, the window is the
            # import RPC's round trip
            raise RuntimeError(f"busy: shard {shard} mid-move")

    def m_shard_map(self) -> dict:
        """{shard: [owner, epoch]} — epochs let pullers reject stale
        entries (a refresh must never clobber newer knowledge)."""
        return {int(s): [int(m), int(self.shard_epoch.get(int(s), 0))]
                for s, m in self.shard_map.items()}

    def m_membership(self) -> dict:
        """Membership introspection for drivers: the id-space bound
        (monotone), the live member ids this member knows (self + wired
        peers), and the DURABLE departed-id set — the authoritative
        never-reuse list (a wired peer entry cannot distinguish an
        interrupted-join re-run from a reused id; this set can)."""
        with self._lock:
            return {"n_members": int(self.n_members),
                    "members": sorted({self.member_id, *self.peers}),
                    "departed": sorted(int(m) for m in self.departed)}

    def m_join_begin(self, new_id: int, new_addr, n_members_new: int) -> bool:
        """Learn a joining member: wire its RPC, grow the id-space bound
        (``n_members`` is a BOUND on assigned member ids, not a live
        count — mid-id leaves open gaps).  Ownership is untouched —
        shards move one by one afterwards."""
        with self._lock:
            self.n_members = max(self.n_members, int(n_members_new))
            if new_id != self.member_id and new_id not in self.peers:
                self.connect(int(new_id), new_addr[0], int(new_addr[1]))
            self._prep_append({"ev": "members", "txid": 0,
                               "n": int(self.n_members)})
        return True

    def m_seed_map(self, entries, n_members: Optional[int] = None) -> bool:
        """Adopt an authoritative ownership-map snapshot ``{shard:
        [owner, epoch]}`` (live-join driver seeding).  A joiner boots
        with a modular GUESS of the current layout; if earlier
        joins/leaves reshaped the map, same-epoch entries of that guess
        would survive epoch-guarded refreshes forever — so the driver
        seeds the real map, adopting entries at or above the local epoch
        for shards not owned here (equal-epoch entries from a live
        member are at least as correct as any guess; genuinely moved
        shards always carry a strictly higher epoch).  Adopted changes
        are durable own events: a joiner crashing mid-join recovers the
        seeded layout, not the guess."""
        with self._lock:
            if n_members is not None:
                self.n_members = max(self.n_members, int(n_members))
            for s, ent in entries.items():
                s = int(s)
                owner, epoch = int(ent[0]), int(ent[1])
                if s in self.shards or epoch < self.shard_epoch.get(s, 0):
                    continue
                if (self.shard_map.get(s) == owner
                        and self.shard_epoch.get(s, 0) == epoch):
                    continue
                self.shard_map[s] = owner
                self.shard_epoch[s] = epoch
                self._prep_append({"ev": "own", "txid": 0, "shard": s,
                                   "owner": owner, "epoch": epoch})
        return True

    def m_set_owner(self, shard: int, owner: int,
                    n_members: Optional[int] = None,
                    epoch: Optional[int] = None) -> bool:
        """Record a completed shard move (driver broadcast).  The source
        and destination already updated themselves durably in the
        import/relinquish phases; everyone else updates the map here.
        A broadcast older than what we already know (epoch) is a no-op —
        replays and races must not resurrect a previous owner."""
        with self._lock:
            shard, owner = int(shard), int(owner)
            if n_members is not None:
                # monotone like m_forget_member: a leave driver computes
                # its bound from the CURRENT rpcs map, which undercounts
                # whenever a higher id departed earlier — taking the max
                # keeps departed ids unreusable on every member
                self.n_members = max(self.n_members, int(n_members))
            if epoch is not None and int(epoch) < self.shard_epoch.get(
                    shard, 0):
                return True  # stale replay of an older move
            self.shard_map[shard] = owner
            if epoch is not None:
                self.shard_epoch[shard] = int(epoch)
            if owner != self.member_id:
                self.shards = self.shards - {shard}
            self._prep_append({"ev": "own", "txid": 0, "shard": shard,
                               "owner": owner,
                               "epoch": int(self.shard_epoch.get(shard, 0))})
        return True

    def m_export_shard(self, shard: int, target: int) -> bytes:
        """Phase 1 of a live shard move: package a COPY of the shard.

        Refuses (retryably) while any staged txn or chain hole touches
        the shard — the prepare→commit window pins ownership, so a
        coordinator never has to chase a staged txn across members.

        The move is TWO-PHASE (riak_core handoff keeps the source vnode
        until the receiver acks the fold for the same reason): export
        copies without dropping and marks the shard mid-move — new
        prepares get retryable "busy" refusals so the package cannot go
        stale — and only the separate :meth:`m_relinquish_shard` (called
        by the driver AFTER the target confirmed the import) drops the
        source copy and durably flips ownership.  A driver crash between
        export and import therefore destroys nothing: the source still
        owns the only live copy, and :meth:`m_cancel_export` (or a
        member restart — the mid-move mark is deliberately volatile)
        reopens the shard for writes."""
        from antidote_tpu.store import handoff as _handoff

        shard, target = int(shard), int(target)
        with self._xlock, self._lock:
            if shard not in self.shards:
                # NOT _check_owner: a shard mid-move is still owned here,
                # and a driver retry may legitimately re-export it (the
                # mid-move write block keeps the package contents stable)
                raise RuntimeError(
                    f"not_owner: shard {shard} owner "
                    f"{self.shard_map.get(shard, -1)}"
                )
            for txid, st in self.staged.items():
                effects = st[0]
                for eff in effects:
                    if key_to_shard(eff.key, eff.bucket,
                                    self.cfg.n_shards) == shard:
                        raise RuntimeError(
                            f"busy: txn {txid} staged on shard {shard}")
            if self.chain_wait.get(shard):
                raise RuntimeError(f"busy: chain holes on shard {shard}")
            pkg = _handoff.export_shard(self.node.store, shard)
            pkg["applied_ts"] = int(self.applied_ts.get(shard, 0))
            # the epoch this move WILL have once it completes: importers
            # adopt it, and the relinquish/broadcast carry it so stale
            # pre-move map entries can never clobber the new owner
            pkg["owner_epoch"] = int(self.shard_epoch.get(shard, 0)) + 1
            # plane extras (inter-DC egress/ingress chain state): taken
            # under both locks, so they are exactly consistent with the
            # package — no commit or remote apply can land in between
            for fn in self.export_extras:
                pkg.setdefault("x", {}).update(fn(shard))
            data = _handoff.pack(pkg)
            self.moving.add(shard)
        return data

    def m_relinquish_shard(self, shard: int, target: int) -> int:
        """Phase 2 of a live shard move: the driver confirmed the import
        landed at ``target`` — drop the source copy and durably record
        the new owner.  Idempotent: a repeat for an already-relinquished
        shard is a no-op (driver retries after transient RPC errors).
        Returns the move's ownership epoch for the driver's broadcast."""
        from antidote_tpu.store import handoff as _handoff

        shard, target = int(shard), int(target)
        with self._xlock:
            with self._lock:
                self.moving.discard(shard)
                if shard not in self.shards:
                    # duplicate relinquish after a driver retry — the
                    # hooks below still re-run: the retry may exist
                    # because a hook failed after the durable flip, and
                    # release_shard is idempotent
                    dup = True
                    epoch = int(self.shard_epoch.get(shard, 0))
                else:
                    dup = False
                    _handoff.drop_shard(self.node.store, shard)
                    # copy-on-write: lock-free readers iterate the old set
                    self.shards = self.shards - {shard}
                    self.shard_map[shard] = target
                    epoch = int(self.shard_epoch.get(shard, 0)) + 1
                    self.shard_epoch[shard] = epoch
                    self.applied_ts.pop(shard, None)
                    self.chain_wait.pop(shard, None)
                    self._prep_append({"ev": "own", "txid": 0,
                                       "shard": shard, "owner": target,
                                       "epoch": epoch})
            # still under the cross-plane lock (serialized vs the ingress
            # drain), out of the member lock: clear the inter-DC chain
            # state — queued remote txns for a shard we no longer hold
            # must never apply to the dropped slice
            for fn in self.on_shard_relinquish:
                fn(shard)
            if not dup:
                _count_shard_move("relinquish")
        return epoch

    def m_cancel_export(self, shard: int) -> bool:
        """Abort phase 1: the import failed for good (or the driver is
        cleaning up after a predecessor's crash) — reopen the shard for
        writes.  The exported package is simply forgotten; nothing was
        dropped."""
        with self._lock:
            self.moving.discard(int(shard))
        return True

    def m_import_shard(self, data: bytes) -> bool:
        """Install a moved shard and take ownership (idempotent: a
        re-sent package for a shard I already own is a no-op)."""
        from antidote_tpu.store import handoff as _handoff

        pkg = _handoff.unpack(bytes(data))
        shard = int(pkg["shard"])
        with self._xlock:
            dup = False
            with self._lock:
                if shard in self.shards:
                    # duplicate delivery after a driver retry: the data
                    # is installed, but the plane hooks below must still
                    # re-run — the retry may exist precisely BECAUSE a
                    # hook failed mid-way on the first delivery, and
                    # skipping them would strand the egress chain at its
                    # partial state (adopt_shard is idempotent/monotone)
                    dup = True
            if not dup:
                self._import_pkg_locked(shard, pkg)
            extras = pkg.get("x", {})
            for fn in self.on_shard_import:
                fn(shard, extras)
            if not dup:
                _count_shard_move("import")
        return True

    def _import_pkg_locked(self, shard: int, pkg: dict) -> None:
        """Install a handoff package's data + ownership (fresh import
        leg of :meth:`m_import_shard`; caller holds the cross-plane
        lock).  The inter-DC chain-state hooks run in the caller, for
        duplicates too."""
        with self._lock:
            self.node.receive_handoff(pkg)
            self.shards = self.shards | {shard}
            self.shard_map[shard] = self.member_id
            self.shard_epoch[shard] = int(pkg.get(
                "owner_epoch", self.shard_epoch.get(shard, 0) + 1))
            self.applied_ts[shard] = int(pkg.get("applied_ts", 0))
            self.chain_wait[shard] = {}
            # certification continuity for the moved keys (the member
            # cert table, not just the node's): their last own-lane
            # commit rides in each head clock
            for key, bucket, tname, row in pkg["directory"]:
                lane = int(np.asarray(
                    pkg["tables"][tname]["head_vc"])[row][self.dc_id])
                if lane:
                    dk = (freeze_key(key), bucket)
                    self.last_commit[dk] = max(
                        self.last_commit.get(dk, 0), lane)
            self._prep_append({"ev": "own", "txid": 0, "shard": shard,
                               "owner": self.member_id,
                               "epoch": int(self.shard_epoch[shard])})

    def m_prepare(self, txid: int, effs_wire: list, snap_own: int) -> bool:
        """Certify + lock this txn's keys on my shards
        (certification_with_check, /root/reference/src/clocksi_vnode.erl:599-624).
        Raises on conflict (the RPC surfaces it as an error reply)."""
        effects = [eff_from_wire(w) for w in effs_wire]
        with self._lock:
            keys = []
            for eff in effects:
                self._check_owner(
                    key_to_shard(eff.key, eff.bucket, self.cfg.n_shards)
                )
                dk = (eff.key, eff.bucket)
                holder = self.prepared.get(dk)
                if holder is not None and holder != txid:
                    raise RuntimeError(
                        f"abort: key {eff.key!r} prepared by txn {holder}"
                    )
                if self.last_commit.get(dk, 0) > snap_own:
                    raise RuntimeError(
                        f"abort: certification conflict on {eff.key!r}"
                    )
                # type-binding check HERE, not at apply: a key bound to a
                # different CRDT type must fail as a clean prepare abort —
                # discovered at commit it would poison the ts chain (the
                # decision is durable before the apply).  The prepare lock
                # then pins the binding until commit.
                try:
                    self.node.store.locate(eff.key, eff.type_name,
                                           eff.bucket, create=False)
                except TypeError as e:
                    raise RuntimeError(f"abort: {e}") from e
            for eff in effects:
                dk = (eff.key, eff.bucket)
                self.prepared[dk] = txid
                keys.append(dk)
            self.staged[txid] = (effects, keys, int(snap_own))
            self.staged_at[txid] = time.monotonic()
            self._prep_append({"ev": "prep", "txid": int(txid),
                               "effs": effs_wire,
                               "snap": int(snap_own)})
        return True

    def m_abort(self, txid: int) -> bool:
        with self._lock:
            if txid in self.staged:
                self._prep_append({"ev": "abort", "txid": int(txid)})
            self._drop_staged(txid)
        return True

    def m_commit(self, txid: int, commit_vc, prev_by_shard,
                 resolved: bool = False) -> bool:
        """Apply a staged txn at ts = commit_vc[own]; my shards' slices
        apply in ts order via the sequencer's per-shard chain.

        ``resolved`` marks a takeover-driven apply: it may pass a block
        barrier.  A normal commit for a blocked or resolved-aborted txid
        is refused — the zombie-coordinator door the takeover shut."""
        commit_vc = np.asarray(commit_vc, np.int32)
        ts = int(commit_vc[self.dc_id])
        # an applied commit proves the sequencer reached ts: advance the
        # cached frontier so idle-shard self-advance (and the reads
        # waiting on it) need not wait out the 0.2 s cache refresh
        if self.seq is None and ts > self._seq_cache:
            self._seq_cache = ts
        with self._xlock, self._lock:
            if txid in self.aborted_txns:
                raise RuntimeError(
                    f"abort: txn {txid} was resolved-aborted by takeover")
            if not resolved and txid in self.blocked_txns:
                raise RuntimeError(
                    f"abort: txn {txid} is blocked pending takeover")
            effects, keys, snap_own = self.staged.pop(
                txid, (None, None, 0))
            if effects is None:
                return True  # duplicate commit
            self.staged_at.pop(txid, None)
            self.blocked_txns.discard(txid)
            # rewrite tentative own dots (overlay stamp = snapshot+1) to
            # the real commit ts (restamp_own_dots; see txn/manager.py);
            # snap_own None = legacy prep record, no tentative dots
            if snap_own is not None and snap_own + 1 != ts:
                for eff in effects:
                    ty_e = get_type(eff.type_name)
                    eff.eff_a, eff.eff_b = ty_e.restamp_own_dots(
                        self.cfg, eff.eff_a, eff.eff_b, self.dc_id,
                        snap_own + 1, ts)
            self._prep_append({
                "ev": "commit", "txid": int(txid),
                "vc": [int(x) for x in commit_vc],
                "prev": {int(k): int(v) for k, v in prev_by_shard.items()},
            })
            self.committed_txns[txid] = (
                [int(x) for x in commit_vc],
                {int(k): int(v) for k, v in prev_by_shard.items()},
            )
            self._trim_ledgers()
            by_shard: Dict[int, list] = {}
            for eff in effects:
                _, shard, _ = self.node.store.locate(
                    eff.key, eff.type_name, eff.bucket
                )
                by_shard.setdefault(shard, []).append(eff)
            for shard, effs in by_shard.items():
                prev = int(prev_by_shard.get(str(shard),
                                             prev_by_shard.get(shard, 0)))
                self._chain_apply(shard, prev, ts, effs, commit_vc)
            for dk in keys:
                if self.prepared.get(dk) == txid:
                    del self.prepared[dk]
                self.last_commit[dk] = ts
        return True

    # ------------------------------------------------------------------
    # coordinator-crash takeover
    # ------------------------------------------------------------------
    def m_txn_status(self, txid: int) -> list:
        """What this member knows about a txn (takeover poll)."""
        with self._lock:
            ent = self.committed_txns.get(txid)
            if ent is not None:
                return ["committed", ent[0],
                        {int(k): int(v) for k, v in ent[1].items()}]
            if txid in self.aborted_txns:
                return ["aborted"]
            if txid in self.staged:
                return ["staged"]
            return ["unknown"]

    def m_block_txn(self, txid: int) -> list:
        """Block barrier: unless already committed here, bar the txid
        from committing until the takeover decision lands.  Returns the
        pre-block status so the resolver can detect a commit that raced
        in."""
        with self._lock:
            st = self.m_txn_status(txid)
            if st[0] != "committed":
                self.blocked_txns.add(txid)
            return st

    def m_forget_txn(self, txid: int, ts: int, shards, prev_by_shard
                     ) -> bool:
        """Apply a takeover ABORT decision: release the txn's staged
        write-set + locks and close its hole in my owned shards' ts
        chains (a no-op link, so successors drain)."""
        with self._xlock, self._lock:
            self.blocked_txns.discard(txid)
            if txid not in self.aborted_txns:
                self.aborted_txns[txid] = True
                self._trim_ledgers()
                if txid in self.staged:
                    self._prep_append({"ev": "abort", "txid": int(txid)})
                self._drop_staged(txid)
            for s in shards:
                s = int(s)
                if s in self.shards and self.applied_ts[s] < int(ts):
                    prev = int(prev_by_shard.get(str(s),
                                                 prev_by_shard.get(s, 0)))
                    self._chain_apply(s, prev, int(ts), [], None)
        return True

    def m_resolve_chain(self, shard: int, after_ts: int,
                        grace_s: float = 0.0) -> Optional[list]:
        """Takeover driver (sequencer member only): decide the fate of
        the txn holding the earliest unapplied ts on ``shard``.

        Decision rule: if ANY member applied it, the txn is committed —
        return its commit VC + chains so stuck members can finish the
        fan-out (atomicity).  Otherwise, after ``grace_s`` since issue,
        block the txid at every reachable member (a late coordinator's
        commit now refuses), re-check for a commit that raced in, and
        failing that abort it everywhere.  Decisions are sticky."""
        assert self.seq is not None, "m_resolve_chain runs on the sequencer"
        ent = self.seq.entry_after(int(shard), int(after_ts))
        if ent is None:
            return None
        ts, txid = ent
        prior = self.seq.resolutions.get(txid)
        if prior is not None:
            if prior[0] == "abort" and int(prior[2]) != ts:
                # the txn was stale-aborted pre-seq but a racing
                # coordinator still got a ts in (defense in depth beside
                # the seq_ts refusal): close the hole at the REAL ts
                issued = self.seq.issued.get(ts)
                if issued is not None:
                    _, tx_shards, prev, _ = issued
                    pw = {int(k): int(v) for k, v in prev.items()}
                    self.m_forget_txn(txid, ts, tx_shards, pw)
                    for mid, cli in list(self.peers.items()):
                        try:
                            cli.call("m_forget_txn", txid, ts, tx_shards,
                                     pw)
                        except Exception as e:
                            log.warning("takeover: hole-close of txn %d "
                                        "at member %d failed: %s",
                                        txid, mid, e)
                return ["abort", int(txid), int(ts)]
            return list(prior)
        issued = self.seq.issued.get(ts)
        if issued is None:
            # ledger GC'd beneath a very old hole: nothing left to learn;
            # treat as abort with an empty shard set is unsafe — refuse
            raise RuntimeError(
                f"ts {ts} missing from sequencer ledger (GC'd); manual "
                "intervention required")
        _, tx_shards, prev, t_issued = issued
        dec = self._decide(txid, ts, tx_shards, prev, t_issued, grace_s)
        if dec is not None and dec[0] != "wait":
            self.seq.resolutions[txid] = tuple(dec)
            self.seq.trim_resolutions()
            if dec[0] == "commit":
                # complete the dead coordinator's fan-out: every member
                # holding the staged write-set applies it now
                _, _, vc, prevw = dec
                pw = {int(k): int(v) for k, v in prevw.items()}
                try:
                    self.m_commit(txid, vc, pw, resolved=True)
                except Exception:
                    log.warning("takeover: local completion of txn %d "
                                "failed", txid, exc_info=True)
                for mid, cli in list(self.peers.items()):
                    try:
                        cli.call("m_commit", txid, vc, pw, True)
                    except Exception as e:
                        log.warning("takeover: completion of txn %d at "
                                    "member %d failed: %s", txid, mid, e)
        return dec

    def _poll(self, method: str, txid: int) -> Dict[int, list]:
        out = {self.member_id: getattr(self, method)(txid)}
        for mid, cli in list(self.peers.items()):
            try:
                out[mid] = cli.call(method, txid)
            except Exception:
                out[mid] = ["unreachable"]
        return out

    def _decide(self, txid, ts, tx_shards, prev, t_issued,
                grace_s) -> Optional[list]:
        """Takeover decision.  SAFETY RULE: a prepared participant may
        only be aborted when every owner of the txn's shards is
        reachable and reports not-committed — an unreachable owner may
        have applied + WAL-logged the commit just before dying, and
        aborting behind its back would diverge on rejoin (the classic
        2PC blocking window; the reference rides it out the same way by
        restarting the node, multiple_dcs_node_failure_SUITE).  The
        block barrier shuts the door on a zombie coordinator racing the
        abort."""
        involved = {self.shard_map.get(int(s), int(s) % self.n_members)
                    for s in tx_shards}
        statuses = self._poll("m_txn_status", txid)
        for st in statuses.values():
            if st[0] == "committed":
                return ["commit", int(txid), st[1], st[2]]
        if any(statuses.get(mid, ["unreachable"])[0] == "unreachable"
               for mid in involved):
            return ["wait", int(txid)]  # blocking: owner may rejoin
        if time.monotonic() - t_issued < grace_s:
            return ["wait", int(txid)]
        # block barrier everywhere, then re-check for a raced-in commit
        blocked = self._poll("m_block_txn", txid)
        for st in blocked.values():
            if st[0] == "committed":
                return ["commit", int(txid), st[1], st[2]]
        if any(blocked.get(mid, ["unreachable"])[0] == "unreachable"
               for mid in involved):
            return ["wait", int(txid)]  # an owner died mid-barrier
        prev_wire = {int(k): int(v) for k, v in prev.items()}
        self.m_forget_txn(txid, ts, tx_shards, prev_wire)
        for mid, cli in list(self.peers.items()):
            try:
                cli.call("m_forget_txn", txid, ts, tx_shards, prev_wire)
            except Exception as e:
                log.warning("takeover: abort of txn %d at member %d "
                            "failed: %s", txid, mid, e)
        return ["abort", int(txid), int(ts)]

    def m_txn_sequenced(self, txid: int) -> bool:
        assert self.seq is not None
        return int(txid) in self.seq.txid_index

    def m_resolve_stale_txn(self, txid: int) -> list:
        """Takeover for a txn whose coordinator died BEFORE sequencing:
        its prepared locks would otherwise be held forever (no ts, so no
        chain hole for m_resolve_chain to find).  Runs on the sequencer:
        if the txid was never issued a ts — checked again after the
        block barrier, so a racing coordinator that sequences late finds
        its commit refused — abort it everywhere."""
        assert self.seq is not None, "m_resolve_stale_txn runs on sequencer"
        txid = int(txid)
        prior = self.seq.resolutions.get(txid)
        if prior is not None:
            return list(prior)
        if txid in self.seq.txid_index:
            return ["sequenced", self.seq.txid_index[txid]]
        blocked = self._poll("m_block_txn", txid)
        for st in blocked.values():
            if st[0] == "committed":
                return ["commit", txid, st[1], st[2]]
        if txid in self.seq.txid_index:
            return ["sequenced", self.seq.txid_index[txid]]
        self.m_forget_txn(txid, 0, [], {})
        for cli in list(self.peers.values()):
            try:
                cli.call("m_forget_txn", txid, 0, [], {})
            except Exception:
                pass
        dec = ["abort", txid, 0]
        self.seq.resolutions[txid] = tuple(dec)
        self.seq.trim_resolutions()
        return dec

    def sweep_stale_prepared(self, grace_s: float = 30.0) -> int:
        """Release prepared locks of txns staged longer than ``grace_s``
        whose coordinator never reached the sequencer.  Sequenced txns
        are left to :meth:`resolve_wedged` (the chain protocol owns
        them).  Returns the number of txns resolved away."""
        now = time.monotonic()
        with self._lock:
            stale = [txid for txid, t in self.staged_at.items()
                     if now - t >= grace_s]
        n = 0
        for txid in stale:
            if self.seq is not None:
                dec = self.m_resolve_stale_txn(txid)
            else:
                dec = self.peers[0].call("m_resolve_stale_txn", txid)
            if dec[0] == "abort":
                n += 1
        return n

    def resolve_wedged(self, grace_s: float = 0.0, max_rounds: int = 64
                       ) -> int:
        """Settle every unapplied issued ts on my owned shards via the
        sequencer's takeover protocol.  Returns the number of decisions
        applied.  Any member may call this (on a timer, on a stuck-read
        timeout, or after a rejoin)."""
        applied = 0
        for _ in range(max_rounds):
            progress = False
            for s in sorted(self.shards):
                frontier_v = self.applied_ts.get(s)
                if frontier_v is None:
                    continue  # shard moved away mid-walk (live join)
                frontier = int(frontier_v)
                if self.seq is not None:
                    dec = self.m_resolve_chain(s, frontier, grace_s)
                else:
                    dec = self.peers[0].call(
                        "m_resolve_chain", s, frontier, grace_s)
                if dec is None or dec[0] == "wait":
                    continue
                if dec[0] == "commit":
                    _, txid, vc, prevw = dec
                    self.m_commit(int(txid), vc, {
                        int(k): int(v) for k, v in prevw.items()
                    }, resolved=True)
                elif dec[0] == "abort":
                    _, txid, ts = dec
                    # m_forget_txn already ran here via the broadcast;
                    # re-apply locally in case we were unreachable then
                    issued = None
                    if self.seq is not None:
                        issued = self.seq.issued.get(int(ts))
                    if self.applied_ts[s] < int(ts):
                        shards_ = issued[1] if issued else [s]
                        prev_ = (issued[2] if issued
                                 else {s: self.applied_ts[s]})
                        self.m_forget_txn(int(txid), int(ts), shards_, {
                            int(k): int(v) for k, v in prev_.items()
                        })
                if int(self.applied_ts[s]) > frontier:
                    applied += 1
                    progress = True
            if not progress:
                break
        return applied

    def _chain_apply(self, shard: int, prev: int, ts: int, effects,
                     commit_vc) -> None:
        """Apply when the shard's own-lane chain reaches ``prev``; buffer
        otherwise (commits may arrive out of ts order from concurrent
        coordinators)."""
        if shard not in self.chain_wait:
            raise RuntimeError(
                f"commit ts {ts} for unowned shard {shard} at member "
                f"{self.member_id} (owned {sorted(self.shards)}, map "
                f"{self.shard_map.get(shard)}) — protocol violation")
        if self.applied_ts[shard] < prev:
            self.chain_wait[shard][prev] = (ts, effects, commit_vc)
            return
        self._apply_now(shard, ts, effects, commit_vc)
        # drain successors whose prev just became current
        waits = self.chain_wait[shard]
        while self.applied_ts[shard] in waits:
            nts, neffs, nvc = waits.pop(self.applied_ts[shard])
            self._apply_now(shard, nts, neffs, nvc)

    def _apply_now(self, shard: int, ts: int, effects, commit_vc) -> None:
        if effects:  # a takeover no-op link just advances the frontier
            self.node.store.apply_effects(
                effects, [commit_vc] * len(effects),
                [self.dc_id] * len(effects)
            )
        self.applied_ts[shard] = ts
        if effects:
            for listener in self.on_commit:
                listener(effects, commit_vc, self.dc_id)

    # ------------------------------------------------------------------
    # stable-time aggregation (meta_data_sender stable-time gossip)
    # ------------------------------------------------------------------
    def refresh_peer_clocks(self) -> None:
        for mid, cli in list(self.peers.items()):
            try:
                rows = cli.call("m_clocks")
            except Exception:
                # unreachable peer (crashed, or departed via live leave):
                # keep its last gossiped rows; staleness is safe (mins
                # only lag) and takeover/rewire handles the rest
                continue
            with self._lock:
                # insert under the member lock: clock_matrix iterates
                # this dict on every snapshot, and a lock-free insert
                # (first gossip from a joiner) racing that iteration
                # raises "dictionary changed size during iteration".
                # Re-check liveness: a leave's m_forget_member may have
                # dropped this peer while our m_clocks call was in
                # flight, and re-inserting would permanently resurrect
                # the departed member's rows (undoing the cleanup)
                if mid not in self.peers:
                    continue
                mat = self.peer_clocks.get(mid)
                if mat is None:
                    mat = np.zeros((self.cfg.n_shards, self.cfg.max_dcs),
                                   np.int32)
                    self.peer_clocks[mid] = mat
            for s, row in rows:
                np.maximum(mat[s], np.asarray(row, np.int32), out=mat[s])

    def m_forget_member(self, member_id: int, n_members_new: int) -> bool:
        """Drop a departed member (live leave): close + remove its peer
        client and gossip rows.  The id-space bound is MONOTONE — the
        driver passes it unchanged, so a departed id (highest or not)
        is never handed out again: its durable log dir and the routes
        remote DCs learned for it must never alias a new member."""
        with self._lock:
            member_id = int(member_id)
            # monotone: never shrink (a smaller value from an old driver
            # would reopen a departed id for reuse)
            self.n_members = max(self.n_members, int(n_members_new))
            cli = self.peers.pop(member_id, None)
            if cli is not None:
                try:
                    cli.close()
                except Exception:
                    pass
            self.peer_clocks.pop(member_id, None)
            self.departed.add(member_id)
            self._prep_append({"ev": "members", "txid": 0,
                               "n": int(self.n_members)})
            self._prep_append({"ev": "departed", "txid": 0,
                               "member": member_id})
        return True

    def clock_matrix(self) -> np.ndarray:
        """The DC's full (shards x D) applied matrix: my owned rows live,
        peer rows from gossip."""
        mat = self.node.store.applied_vc.copy()
        # list(): the gossip loop inserts / m_forget_member pops rows
        # concurrently; a stale snapshot of the dict is safe (mins lag)
        for mid, peer in list(self.peer_clocks.items()):
            for s in range(self.cfg.n_shards):
                if s not in self.shards:
                    np.maximum(mat[s], peer[s], out=mat[s])
        return mat

    def stable_vc(self) -> np.ndarray:
        """DC stable snapshot = entry-wise min over every member's shard
        rows (stable_time_functions:get_min_time aggregated across nodes,
        /root/reference/src/meta_data_sender.erl:224-255)."""
        self.advance_idle_shards()
        return stable_min_of(self.clock_matrix())

    def close(self) -> None:
        self.rpc.close()
        for cli in list(self.peers.values()):
            cli.close()
        if self._prep_wal is not None:
            self._prep_wal.close()


def _wire_value(v):
    """Client values over msgpack: map dicts have tuple keys."""
    if isinstance(v, dict):
        return {"__map__": [[list(k), _wire_value(x)] for k, x in v.items()]}
    if isinstance(v, (list, tuple)):
        return [_wire_value(x) for x in v]
    return v


def unwire_value(v):
    if isinstance(v, dict) and "__map__" in v:
        return {
            (freeze_key(k[0]), k[1]): unwire_value(x) for k, x in v["__map__"]
        }
    if isinstance(v, list):
        return [unwire_value(x) for x in v]
    return v


def _freeze_op(op):
    """Ops over msgpack come back as lists; freeze to the tuple shapes the
    type layer expects."""
    if isinstance(op, list):
        return tuple(_freeze_op(x) for x in op)
    return op
