"""AntidoteNode — the public API facade.

The surface of ``antidote.erl`` (/root/reference/src/antidote.erl:36-54):
static & interactive transactions, typed bound objects, hook registration —
over one replica's TransactionManager + KVStore.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Optional, Sequence

import numpy as np

from antidote_tpu import native_build
from antidote_tpu.config import AntidoteConfig
from antidote_tpu.crdt import is_type
from antidote_tpu.store.kv import KVStore
from antidote_tpu.txn.manager import (
    AbortError,
    Transaction,
    TransactionManager,
    Update,
)

BoundObject = Any


def device_report() -> dict:
    """The JAX devices this process runs on, as JAX reports them, with
    each device's allocator figures where the backend keeps them (the
    CPU backend does not)."""
    import jax

    devs = jax.devices()
    mem = [d.memory_stats() or {} for d in devs]
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "bytes_in_use": [m.get("bytes_in_use") for m in mem],
        "peak_bytes_in_use": [m.get("peak_bytes_in_use") for m in mem],
        "bytes_limit": [m.get("bytes_limit") for m in mem],
    }


class AntidoteNode:
    """One replica ("DC") of the store.

    ``dc_id`` is the dense clock lane of this replica (the dcid→lane
    registry replacing Antidote's dict VCs keyed by dcid).
    """

    def __init__(
        self,
        cfg: Optional[AntidoteConfig] = None,
        dc_id: int = 0,
        sharding=None,
        cert: bool = True,
        log_dir: Optional[str] = None,
        recover: bool = False,
        meta=None,
        store: Optional[KVStore] = None,
        resident_rows: int = 0,
        cold_fault_rate_cap: float = 0.0,
    ):
        """``store`` adopts an existing KVStore (e.g. the output of
        ``handoff.reshard``) instead of building one; ``log_dir`` must be
        None then — the adopted store keeps its own log."""
        if store is not None and cfg is None:
            cfg = store.cfg
        self.cfg = cfg or AntidoteConfig()
        self.dc_id = dc_id
        # durable, DC-replicated metadata/flag store (stable_meta_data_server)
        if meta is None:
            from antidote_tpu.meta import MetaDataStore

            meta = MetaDataStore()
        self.meta = meta
        log = None
        if store is not None:
            assert log_dir is None, "store= and log_dir= are exclusive"
            if recover:
                raise RuntimeError(
                    "store= adopts already-populated tables; recover=True "
                    "would replay its log on top of them (double-apply)"
                )
            log = store.log
        elif log_dir is not None:
            import glob
            import os

            from antidote_tpu.log import LogManager
            from antidote_tpu.log.checkpoint import has_checkpoints

            # a published checkpoint carries committed data even when
            # every WAL file below its floor was reclaimed — such a dir
            # must recover, never boot fresh over the image
            has_data = any(
                os.path.getsize(p) > 0
                for p in glob.glob(os.path.join(log_dir, "shard_*.wal"))
            ) or has_checkpoints(log_dir)
            if has_data and not recover:
                # appending to an existing log with fresh counters would
                # mint duplicate (commit counter, origin) dots — corruption
                raise RuntimeError(
                    f"log_dir {log_dir!r} contains existing WAL data; pass "
                    "recover=True (or point at an empty directory)"
                )
            log = LogManager(
                self.cfg, log_dir,
                sync_on_commit=self.meta.get_env("sync_log",
                                                 self.cfg.sync_log),
            )
        elif recover:
            raise RuntimeError(
                "recover=True requires log_dir"
            )
        self.store = store if store is not None else KVStore(
            self.cfg, sharding=sharding, log=log
        )
        self.txm = TransactionManager(
            self.store, my_dc=dc_id,
            cert=self.meta.get_env("txn_cert", cert),
            protocol=self.meta.get_env("txn_prot", "clocksi"),
        )
        from antidote_tpu.obs import NodeMetrics, install_error_monitor

        #: prometheus-parity metric set (antidote_stats_collector, SURVEY §2.7)
        self.metrics = NodeMetrics()
        self.txm.metrics = self.metrics
        # snapshot-cache / serving-epoch counters land in the same registry
        self.store.metrics = self.metrics
        if self.store.log is not None:
            # group-fsync coordinator -> antidote_wal_fsync_batch
            self.store.log.on_fsync_batch = (
                self.metrics.wal_fsync_batch.observe)
        # count this package's ERROR-level log records (antidote_error_monitor)
        self._error_handler = install_error_monitor(
            self.metrics, logging.getLogger("antidote_tpu")
        )
        self._metrics_server = None
        if store is not None:
            # adopted (already-populated) store: continue the commit
            # counter above every applied clock so new commits never mint
            # duplicate (counter, origin) dots
            self.txm.commit_counter = int(self.store.dc_max_vc()[dc_id])
        #: background checkpoint writer (ISSUE 8); started by
        #: start_checkpointer (console serve) or lazily by checkpoint_now
        self.checkpointer = None
        import threading as _threading

        self._ckpt_init_lock = _threading.Lock()
        #: extras blobs restored from the checkpoint image (membership
        #: state etc.) for attached subsystems to consult
        self.checkpoint_extras: dict = {}
        #: name -> provider of extra state to embed in checkpoint images
        #: (cluster members register their membership snapshot here);
        #: shared with the Checkpointer so late registrations are seen
        self.checkpoint_extras_providers: dict = {}
        # --- cold tier (ISSUE 13): attach BEFORE recovery so a chain
        # image's cold_directory can register fault-in refs and the tail
        # replay stays under the resident budget
        if resident_rows > 0 and self.store.cold is None:
            # enable_cold_tier raises without a durable log — the
            # explicitly-requested residency bound must never be a
            # silent no-op
            self.enable_cold_tier(resident_rows, cold_fault_rate_cap)
        if recover and log is not None:
            # node restart (check_node_restart,
            # /root/reference/src/inter_dc_manager.erl:156-206).  Fast
            # path (ISSUE 8/13): compose the newest verifiable FULL
            # checkpoint image with its parent-linked delta chain, then
            # replay only the WAL tail above the last good link's floor;
            # the full-log replay remains the no-checkpoint fallback and
            # the semantics oracle (both rebuild certification +
            # counters).  A corrupt mid-chain link truncates the
            # composition — the tail above the surviving prefix is still
            # on disk (reclaim never passes the retained fulls' floors).
            from antidote_tpu.log import checkpoint as _ckpt

            rlog = logging.getLogger("antidote_tpu.recovery")
            t0 = time.monotonic()
            loaded = _ckpt.load_chain(log_dir)
            if loaded is not None:
                image, manifest, deltas = loaded
                summary = _ckpt.install_image(self.store, self.txm, image)
                self.checkpoint_extras = image.get("extras", {}) or {}
                if summary["cold_directory"]:
                    # beyond-RAM image: the cold keys get NO device row —
                    # reads fault them in against this image's sidecar
                    if self.store.cold is None:
                        self.enable_cold_tier(0, cold_fault_rate_cap)
                    self.store.cold.seed(summary["cold_directory"],
                                         int(manifest["id"]))
                if self.store.cold is not None \
                        and (manifest.get("cold") is not None):
                    # resident keys' image coords double as evict hints
                    # (their rows ARE the sidecar rows) — the budget
                    # pass below and the commit path both need them
                    self.store.cold.seed_hints(int(manifest["id"]))
                for delta, dman in deltas:
                    ds = _ckpt.install_delta(self.store, self.txm, delta)
                    self.checkpoint_extras.update(
                        delta.get("extras", {}) or {})
                    rlog.info(
                        "recovery chain link %d: %d rows, %d keys, "
                        "%d evicted", ds["id"], ds["rows"], ds["keys"],
                        ds["evicted"])
                ckpt_s = time.monotonic() - t0
                self.metrics.recovery_seconds.set(ckpt_s,
                                                  phase="checkpoint")
                rlog.info(
                    "recovery phase checkpoint: image %d + %d chain "
                    "link(s) (%d keys, %d rows, %d tables%s, %d cold) "
                    "installed in %.2f s",
                    summary["id"], len(deltas), summary["keys"],
                    summary["rows"], summary["tables"],
                    (f", dropped shards {summary['dropped_shards']}"
                     if summary["dropped_shards"] else ""),
                    len(summary["cold_directory"]), ckpt_s,
                )
            t1 = time.monotonic()
            last = self.store.recover(track_origin=dc_id)
            self.txm.committed_keys.update(last)
            self.txm.commit_counter = int(self.store.dc_max_vc()[dc_id])
            tail_s = time.monotonic() - t1
            n_tail = int(getattr(self.store, "last_recovery_records", 0))
            self.metrics.recovery_seconds.set(tail_s, phase="tail")
            self.metrics.recovery_records.inc(n_tail)
            rlog.info(
                "recovery phase tail: %d record(s) replayed in %.2f s "
                "(total %.2f s, %s)",
                n_tail, tail_s, time.monotonic() - t0,
                "checkpoint + tail" if loaded is not None
                else "full replay — no checkpoint found",
            )
            if self.store.cold is not None \
                    and self.store.cold.budget > 0:
                # a beyond-RAM restart re-enforces the resident budget
                # BEFORE serving: rows the installed image covers (and
                # the tail left untouched) go straight back cold
                n_ev = self.store.cold.enforce_budget()
                if n_ev:
                    rlog.info("recovery cold tier: %d row(s) re-evicted "
                              "to the resident budget (%d)", n_ev,
                              self.store.cold.budget)
        # react to replicated flag flips from ANY node in the DC
        # (registered last: construction-time get_env seeds fire watchers)
        self.meta.watch(self._on_meta_change)

    # --- cold tier (ISSUE 13) -------------------------------------------
    def enable_cold_tier(self, resident_rows: int = 0,
                         fault_rate_cap: float = 0.0):
        """Attach the cold tier: device residency bounded by
        ``resident_rows`` (0 = unbounded; fault-in only), fault-ins past
        ``fault_rate_cap``/s refused with a typed ColdMiss.  Requires a
        durable log (the cold state lives in checkpoint sidecars)."""
        if self.store.log is None:
            raise RuntimeError("the cold tier requires log_dir (cold "
                               "rows live in checkpoint sidecars)")
        if self.store.cold is None:
            from antidote_tpu.store.coldtier import ColdTier

            self.store.cold = ColdTier(
                self.store, budget=resident_rows,
                fault_rate_cap=fault_rate_cap, lock=self.txm.commit_lock,
            )
            cp = self.checkpointer
            if cp is not None:
                self.store.cold.on_pressure = cp.request
                self.store.cold.on_corrupt = cp._on_cold_corrupt
        else:
            self.store.cold.budget = int(resident_rows)
            self.store.cold.fault_rate_cap = float(fault_rate_cap)
        return self.store.cold

    # --- checkpointing (ISSUE 8) ----------------------------------------
    def start_checkpointer(self, interval_s: float = 300.0,
                           retain: int = 2, rebase_every: int = 8,
                           scrub_every_s: float = 0.0):
        """Attach (and, for ``interval_s`` > 0, start) the background
        checkpoint writer.  Requires a durable log.  Idempotent and
        race-safe: CHECKPOINT_NOW is served outside the wire dispatch
        lock, so two concurrent admin calls must not construct two
        checkpointers racing over the same image ids."""
        if self.store.log is None:
            raise RuntimeError("checkpointing requires log_dir (a durable "
                               "WAL to stamp floors into)")
        with self._ckpt_init_lock:
            if self.checkpointer is None:
                from antidote_tpu.log.checkpoint import Checkpointer

                cp = Checkpointer(
                    self.store, self.txm, metrics=self.metrics,
                    interval_s=interval_s, retain=retain,
                    rebase_every=rebase_every,
                    scrub_every_s=scrub_every_s,
                )
                cp.extras_providers = self.checkpoint_extras_providers
                cp.start()
                self.checkpointer = cp
        return self.checkpointer

    def checkpoint_now(self, full: Optional[bool] = None) -> dict:
        """Run one synchronous checkpoint cycle (stamp, stream, publish,
        reclaim); returns the published manifest summary.  ``full``
        forces a rebase (True) or a delta link (False); None lets the
        chain cadence decide."""
        if self.checkpointer is None:
            self.start_checkpointer(interval_s=0.0)
        return self.checkpointer.checkpoint_now(full=full)

    # --- readiness (wait_init, /root/reference/src/wait_init.erl:50-88) --
    def check_ready(self) -> dict:
        """Probe every subsystem; returns {probe: bool}.  All-true means
        the node can serve traffic (the reference's check_ready polls
        clocksi tables + read servers + materializer + stable meta)."""
        probes = {}
        probes["types"] = bool(is_type("counter_pn"))
        try:
            probes["meta"] = self.meta.get_env("txn_prot", "clocksi") in (
                "clocksi", "gr")
        except Exception:
            probes["meta"] = False
        try:
            self.store.stable_vc()
            probes["clocks"] = True
        except Exception:
            probes["clocks"] = False
        if self.store.log is not None:
            try:
                self.store.log.commit_barrier([0])
                probes["log"] = True
            except Exception:
                probes["log"] = False
        else:
            probes["log"] = True  # ephemeral mode: nothing to probe
        metrics, self.txm.metrics = self.txm.metrics, None
        try:
            # full txn machinery + device round trip, then rolled back —
            # also warms the jit caches (first TPU compile is ~20-40 s,
            # better here than on the first client request).  Metrics are
            # detached so health polling never skews op/abort dashboards;
            # the aborted probe txn binds no rows (reads of never-written
            # keys allocate nothing, commits never happen).
            txn = self.start_transaction()
            self.update_objects(
                [("__ready__", "counter_pn", "__ready__", ("increment", 1))],
                txn)
            self.read_objects([("__ready__", "counter_pn", "__ready__")], txn)
            self.abort_transaction(txn)
            probes["txn"] = True
        except Exception:
            logging.getLogger("antidote_tpu").exception("readiness probe")
            probes["txn"] = False
        finally:
            self.txm.metrics = metrics
        return probes

    def is_ready(self) -> bool:
        return all(self.check_ready().values())

    def status(self, include_ready: bool = False) -> dict:
        """Operator-facing snapshot (the console's `status` command).

        Passive by default — ``include_ready=True`` additionally runs the
        full readiness probe (a device round trip + WAL barrier), which is
        too heavy for high-frequency monitoring polls."""
        stable = self.store.stable_vc()
        out = {
            "dc_id": self.dc_id,
            "n_shards": self.cfg.n_shards,
            "max_dcs": self.cfg.max_dcs,
            "protocol": self.txm.protocol,
            "certification": self.txm.cert,
            "stable_vc": [int(x) for x in stable],
            "commit_counter": int(self.txm.commit_counter),
            "keys": len(self.store.directory),
            "tables": {
                t: {"rows_used": int(tab.used_rows.sum()),
                    "n_rows": tab.n_rows,
                    "device_bytes": tab.device_bytes()}
                for t, tab in self.store.tables.items()
            },
            "durable": self.store.log is not None,
            # the device this node really runs on, and which native
            # planes loaded (None) or why one fell back to Python
            "device": device_report(),
            "native": dict(native_build.LOAD_STATE),
        }
        if self.store.mesh is not None:
            # mesh serving plane (ISSUE 10): device count, per-shard
            # publish rows, stable-collective latency
            out["mesh"] = self.store.mesh.status()
        # fabric/RPC resilience counters (process-wide; see NetMetrics):
        # operators watch these to see partitions heal and retries drain
        from antidote_tpu.obs.metrics import net_metrics

        out["net"] = {k: v for k, v in net_metrics().snapshot().items()
                      if v}
        # overload/degradation view (PR 4): every bound and shed is
        # visible here and on /metrics — a wedged-looking node should
        # explain itself from one status call
        shed = {
            plane[0]: v
            for plane, v in sorted(self.metrics.shed.snapshot().items())
            if v
        }
        out["overload"] = {
            "read_only": self.txm.read_only_reason,
            "commit_backlog": self.txm._commit_backlog,
            "max_commit_backlog": self.txm.max_commit_backlog,
            "shed": shed,
        }
        # escrow economy (ISSUE 18): typed bounded-counter refusals,
        # queued shortfall, and the rights-transfer traffic this node
        # has driven/served — the zero-oversell plane's one-call view
        out["escrow"] = dict(
            self.txm.bcounters.status(),
            grants={
                role[0]: int(v) for role, v in sorted(
                    self.metrics.escrow_grants.snapshot().items()) if v
            },
        )
        # write plane (ISSUE 6): merge width, group-fsync batching,
        # per-segment durability debt, bypass counts — the knobs table
        # in docs/operations.md explains how to read these
        def _hist(h):
            s = h.summary()
            return {"count": s["count"], "mean": round(s["mean"], 2),
                    "p50": s["p50"], "p99": s["p99"]}

        def _hist_ms(h):
            s = h.summary()
            return {"count": s["count"],
                    "sum_ms": s["count"] * s["mean"] * 1e3,
                    "p50_ms": s["p50"] * 1e3, "p99_ms": s["p99"] * 1e3}

        wlog = self.store.log
        out["write_plane"] = {
            "merge_width": _hist(self.metrics.commit_merge_width),
            "fsync_batch": _hist(self.metrics.wal_fsync_batch),
            "cert_bypass_total": int(self.metrics.cert_bypass.value()),
            "sync_log": (bool(wlog.wals[0].sync_on_commit)
                         if wlog is not None else None),
            "wal_segments": wlog.n_segments if wlog is not None else 0,
            "segment_depth_bytes": (wlog.segment_depths()
                                    if wlog is not None else []),
            # commit-path split (ISSUE 24): `group` is the lock-held
            # time of a write-bearing commit round
            # (antidote_commit_seconds); `phases` certify / wal_append /
            # scatter / fsync_wait / listeners / publish sum to it
            # (`freeze` lies inside publish, `ack` follows the lock)
            "group": _hist_ms(self.metrics.commit_seconds),
            "phases": self.txm.phases.status(),
            # what the scatter phase sent to the device: commit groups,
            # host arrays transferred, device programs launched
            "scatter": self.store.scatter_status(),
            # ring overflow and slot-tier promotion, both inside the
            # scatter phase: `gc` (launches, rows, sum_ms) and `tiers`
            # (promotions by destination tier, promote_sum_ms, tables
            # built ahead of need, grows, rows a shard)
            **self.store.tier_status(),
        }
        # checkpoint / fast-restart view (ISSUE 8): last published image
        # stamp, size, age, and how much tail a crash-now restart would
        # replay; reads from disk when no checkpointer is attached so a
        # passive status poll still sees the inherited image
        if wlog is not None:
            if self.checkpointer is not None:
                out["checkpoint"] = self.checkpointer.status()
            else:
                from antidote_tpu.log import checkpoint as _ckpt

                cks = _ckpt.list_checkpoints(
                    _ckpt.checkpoint_root(wlog.dir))
                blk = {
                    "interval_s": 0,
                    "tail_records": int(
                        (wlog.seqs - wlog.floor_seqs).sum()),
                }
                if cks:
                    m = _ckpt.load_manifest(cks[-1][1]) or {}
                    blk.update({
                        "last_id": m.get("id"),
                        "stamp_vc_max": m.get("stamp_vc_max"),
                        "image_bytes": m.get("image_bytes"),
                        "age_s": round(
                            time.time() - m.get("created_at", 0), 1),
                    })
                out["checkpoint"] = blk
        if self.store.cold is not None:
            # cold tier (ISSUE 13): residency vs budget, fault/evict
            # counters, anchor image — the beyond-RAM health view
            out["cold_tier"] = self.store.cold.status()
        if include_ready:
            out["ready"] = self.check_ready()
        return out

    # --- shard handoff (riak_core handoff receiver) ---------------------
    def receive_handoff(self, pkg, shard: Optional[int] = None) -> None:
        """Install an exported shard package (see store/handoff.py) and
        re-sync the commit counter above every imported clock, so this
        node's own-lane snapshots cover the moved commits."""
        from antidote_tpu.store import handoff as _handoff

        _handoff.import_shard(self.store, pkg, shard)
        if pkg.get("compacted"):
            # SYNCHRONOUS import-then-checkpoint barrier (ISSUE 9
            # satellite, closing the PR-7 residual): the source's WAL was
            # checkpoint-truncated, so the package's ride-along log holds
            # only the tail — this node's WAL cannot rebuild the imported
            # rows' pre-checkpoint history, and the in-memory chain floor
            # installed above is not durable either.  The old
            # nudge-the-checkpointer left a window where a crash lost the
            # moved rows' pre-checkpoint state silently; now the import
            # does not RETURN (and therefore the two-phase move's confirm
            # and the source's relinquish cannot proceed) until a local
            # image covers the moved rows.  A failed checkpoint fails the
            # import loudly — the source keeps the shard.
            if self.store.log is not None:
                summary = self.checkpoint_now()
                logging.getLogger("antidote_tpu").info(
                    "compacted-source shard import sealed by local "
                    "checkpoint %s (import-then-checkpoint barrier)",
                    summary.get("id"),
                )
            else:
                logging.getLogger("antidote_tpu").warning(
                    "imported a shard from a checkpoint-compacted source "
                    "into a LOG-LESS node: there is no durable history "
                    "for the moved rows at all (ephemeral mode)"
                )
        self.txm.commit_counter = max(
            self.txm.commit_counter,
            int(self.store.dc_max_vc()[self.dc_id]),
        )
        # rebuild the certification table for the moved keys: their last
        # own-lane commit is the head clock's own lane (same role as the
        # recover path's track_origin scan) — without this, a txn whose
        # snapshot predates the import could overwrite a moved commit
        # unchecked (first-committer-wins violation)
        from antidote_tpu.store.kv import freeze_key

        for key, bucket, tname, row in pkg["directory"]:
            lane = int(pkg["tables"][tname]["head_vc"][row][self.dc_id])
            if lane:
                dk = (freeze_key(key), bucket)
                self.txm.committed_keys[dk] = max(
                    self.txm.committed_keys.get(dk, 0), lane
                )

    # --- transactions (antidote.erl:36-54) -----------------------------
    def start_transaction(self, clock=None, props=None) -> Transaction:
        return self.txm.start_transaction(clock, props)

    def read_objects(self, objects: Sequence, txn: Optional[Transaction] = None,
                     clock=None):
        if txn is not None:
            return self.txm.read_objects(objects, txn)
        return self.txm.read_objects_static(objects, clock)

    def update_objects(self, updates: Sequence[Update],
                       txn: Optional[Transaction] = None, clock=None):
        if txn is not None:
            self.txm.update_objects(updates, txn)
            return None
        return self.txm.update_objects_static(updates, clock)

    def commit_transaction(self, txn: Transaction) -> np.ndarray:
        return self.txm.commit_transaction(txn)

    def abort_transaction(self, txn: Transaction) -> None:
        self.txm.abort_transaction(txn)

    def get_log_operations(self, object_clock_pairs: Sequence) -> list:
        """Logged update operations newer than a snapshot time, per object
        (``antidote:get_log_operations``,
        /root/reference/src/antidote.erl:69-90).

        ``object_clock_pairs`` is ``[((key, type, bucket), clock), ...]``;
        ``clock`` is a dense VC (``None`` = all ops).  Returns one list per
        object of ``(opid, op)`` dicts where ``op`` carries the origin
        lane, commit VC, and decoded effect — an op is included iff its
        commit VC is NOT dominated by the given clock (the reference's
        ``get_from_time`` newer-than filter,
        /root/reference/src/logging_vnode.erl:194-200).
        """
        from antidote_tpu.store.kv import effect_from_rec, freeze_key
        from antidote_tpu.store.kv import key_to_shard

        log = self.store.log
        if log is None:
            raise RuntimeError("get_log_operations requires a durable log "
                               "(node started with log_dir)")
        wanted: dict = {}  # (shard) -> [(out_idx, key, type, bucket, vc)]
        for i, ((key, type_name, bucket), clock) in enumerate(
                object_clock_pairs):
            key = freeze_key(key)
            shard = key_to_shard(key, bucket, self.cfg.n_shards)
            vc = None
            if clock is not None:
                vc = np.zeros(self.cfg.max_dcs, np.int64)
                clock = np.asarray(clock, np.int64)
                vc[: len(clock)] = clock[: self.cfg.max_dcs]
            wanted.setdefault(shard, []).append(
                (i, key, type_name, bucket, vc))
        out: list = [[] for _ in object_clock_pairs]
        for shard, items in wanted.items():
            by_obj: dict = {}  # an object may be asked at several clocks
            for i, k, t, b, vc in items:
                by_obj.setdefault((k, t, b), []).append((i, vc))
            for rec in log.replay_shard(shard):  # one scan per shard
                hits = by_obj.get((freeze_key(rec["k"]), rec["t"], rec["b"]))
                if hits is None:
                    continue
                rec_vc = np.zeros(self.cfg.max_dcs, np.int64)
                rv = np.asarray(rec["vc"], np.int64)
                rec_vc[: len(rv)] = rv[: self.cfg.max_dcs]
                for i, vc in hits:
                    if vc is not None and (rec_vc <= vc).all():
                        continue  # op already in the given snapshot
                    out[i].append((int(rec["id"]), {
                        "origin": int(rec["o"]),
                        "commit_vc": rec_vc,
                        "effect": effect_from_rec(rec),
                    }))
        return out

    # --- hooks (antidote.erl register_pre/post_hook) -------------------
    def register_pre_hook(self, bucket: str, fn) -> None:
        self.txm.hooks.register_pre_hook(bucket, fn)

    def register_post_hook(self, bucket: str, fn) -> None:
        self.txm.hooks.register_post_hook(bucket, fn)

    def unregister_hook(self, kind: str, bucket: str) -> None:
        self.txm.hooks.unregister_hook(kind, bucket)

    # --- introspection -------------------------------------------------
    @staticmethod
    def is_type(type_name: str) -> bool:
        return is_type(type_name)

    def stable_vc(self) -> np.ndarray:
        return self.store.stable_vc()

    def set_sync_log(self, sync: bool) -> None:
        """Flip fsync-on-commit DC-wide (replicated runtime flag;
        /root/reference/src/logging_vnode.erl:256-258).  The broadcast
        reaches every member node's watcher, which applies it to its
        running log."""
        self.meta.set_env("sync_log", sync)

    def _on_meta_change(self, key: str, value) -> None:
        if key == "env:sync_log" and self.store.log is not None:
            self.store.log.set_sync(bool(value))
        elif key == "env:txn_cert":
            self.txm.cert = bool(value)

    # --- observability (elli /metrics on :3001 in the reference,
    #     /root/reference/src/antidote_sup.erl:118-128) ------------------
    def serve_metrics(self, port: Optional[int] = None):
        from antidote_tpu.obs import MetricsServer
        from antidote_tpu.obs.server import DEFAULT_METRICS_PORT

        if port is None:
            port = DEFAULT_METRICS_PORT
        if self._metrics_server is not None:
            if port not in (0, self._metrics_server.port):
                raise RuntimeError(
                    f"metrics already served on port "
                    f"{self._metrics_server.port}, not {port}"
                )
            return self._metrics_server
        self._metrics_server = MetricsServer(self.metrics.registry, port=port)
        return self._metrics_server


__all__ = ["AntidoteNode", "AbortError"]
