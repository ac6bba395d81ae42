"""Mesh serving plane — the serving-epoch store sharded over devices.

The reference runs one ``materializer_vnode`` per ring partition and
aggregates the DC-wide stable snapshot with 1 s ``meta_data_sender``
gossip + entry-wise min (/root/reference/src/meta_data_sender.erl:224-255,
/root/reference/src/stable_time_functions.erl:51-85).  PRs 5-9 rebuilt
the serving structures — serving-epoch double buffers, the snapshot
cache, the staged wire pipeline — but all of it single-chip.  This
module is the multi-chip rendering (ROADMAP item 3 / SURVEY §7 step 6):

  * every table's arrays (and therefore the frozen serving-epoch double
    buffers cut from them) carry a ``NamedSharding`` over a one-axis
    ``jax.sharding.Mesh`` — contiguous shard blocks: device ``d`` owns
    shards ``[d*spd, (d+1)*spd)`` where ``spd = n_shards // n_devices``,
    permanently;
  * epoch-eligible wire reads launch as ROUTED per-shard gathers
    (``[P, M']`` row blocks through an explicit ``shard_map``), so each
    device gathers only its own shards' rows over ICI-free local HBM —
    the LAUNCH stage ships one program, not per-device work lists, and
    nothing is concatenated on the host until the writeback stage
    materializes the (already assembled) global array;
  * the stable/safe vector clock is a ``lax.pmin`` COLLECTIVE over the
    per-device applied clocks — the gossip rounds collapse into one ICI
    all-reduce (``stable_vc``), replacing the host-side min reduction
    for mesh-resident stores;
  * epoch publication is PER-SHARD INCREMENTAL: the freeze scatters
    each dirty shard's rows into that shard's device slice only
    (``TypedTable.freeze_serving``'s routed path), so one hot shard's
    write burst republishes its own slice, not the whole table —
    observable per shard via ``antidote_mesh_publish_total{shard}``.

GC folds and head folds were already per-shard vmapped bodies
(store/typed_table.py); with the arrays mesh-placed, XLA partitions
them across devices with no cross-device traffic on the data plane, and
the Pallas fold kernels dispatch with SHARD-LOCAL extents inside the
sharded step (``spmd.sharded_step_fn`` + ``pallas_kernels.
counter_fold_deltas``).

On this CPU container the mesh is the 8 virtual devices the test
harness forces (tests/conftest.py); on real TPU hardware the same code
places shards over ICI-connected chips — the pmin becomes a real
cross-chip collective and the per-shard gathers stay HBM-local.
"""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from antidote_tpu.materializer import longlog
from antidote_tpu.obs.trace import device_program
from antidote_tpu.parallel.spmd import SHARD_AXIS
from antidote_tpu.store.typed_table import _shard_read_latest_body


class MeshServingPlane:
    """Placement + collectives for one store's serving plane.

    Build with the deployment config, then :meth:`attach` a
    :class:`~antidote_tpu.store.kv.KVStore` (or pass ``sharding`` into
    ``AntidoteNode`` so recovery-built tables are placed at creation,
    then attach).  ``n_shards`` must be divisible by ``n_devices`` so
    every device owns a whole number of shards — the routed [P, M']
    layouts and the pmin blocks both split on that boundary.
    """

    def __init__(self, cfg, n_devices: int | None = None, metrics=None):
        devices = jax.devices()
        n = int(n_devices) if n_devices else len(devices)
        if not 1 <= n <= len(devices):
            raise ValueError(
                f"mesh wants {n} devices; jax sees {len(devices)}"
            )
        if cfg.n_shards % n:
            raise ValueError(
                f"n_shards={cfg.n_shards} is not divisible by "
                f"{n} mesh devices: every device must own a whole "
                "number of shards"
            )
        self.cfg = cfg
        self.n_devices = n
        self.mesh = Mesh(np.array(devices[:n]), (SHARD_AXIS,))
        self.sharding = NamedSharding(self.mesh, P(SHARD_AXIS))
        #: NodeMetrics (attached with the store; may arrive later)
        self.metrics = metrics
        self.store = None
        self._pmin_fn = None
        #: last computed stable VC keyed by the applied-clock snapshot it
        #: was computed from — txn starts call stable_vc() per request,
        #: and the collective only relaunches when a commit actually
        #: advanced a clock
        self._stable_cache: "tuple | None" = None
        self._stable_lock = threading.Lock()
        #: pmin collectives actually launched (cache misses)
        self.stable_collectives = 0
        #: compiled sequence-parallel giant-key folds, keyed by
        #: (type name, cfg) — cfg is a frozen (hashable) dataclass
        self._giant_fold_fns: dict = {}
        #: giant-key folds dispatched through the mesh (node status)
        self.giant_folds = 0
        #: routed epoch-read launches (node status ``pipeline.mesh``):
        #: how many, their [P, M'] slots, the objects really gathered,
        #: those by owning device, and the host seconds the routing took
        #: — written by the dispatcher thread only (``note_routed``)
        self.routed_launches = 0
        self.routed_slots = 0
        self.routed_rows = 0
        self.routed_rows_by_device = np.zeros((n,), np.int64)
        self.route_seconds = 0.0

    # ------------------------------------------------------------------
    # placement
    # ------------------------------------------------------------------
    def attach(self, store) -> "MeshServingPlane":
        """Adopt ``store``: place every existing table over the mesh,
        point new-table creation at the mesh sharding, and route the
        store's stable-time computation through the pmin collective."""
        if self.metrics is None:
            self.metrics = store.metrics
        store.sharding = self.sharding
        for t in store.tables.values():
            self.place_table(t)
        store.mesh = self
        self.store = store
        if self.metrics is not None:
            self.metrics.mesh_devices.set(self.n_devices)
        return self

    def place_table(self, t) -> None:
        """Move one table's device arrays onto the mesh (idempotent).
        Frozen epoch copies cut from the old placement die with it —
        readers fall back to the locked path until the next publish."""
        if t.sharding is self.sharding:
            return
        t.set_sharding(self.sharding)
        t._set_tree(jax.device_put(t._tree(), self.sharding))
        t.invalidate_epochs()

    # ------------------------------------------------------------------
    # routed epoch gathers (the LAUNCH stage's SPMD read)
    # ------------------------------------------------------------------
    def epoch_gather(self, t, head, head_vc, row_mat, vc_mat):
        """One merged frozen-head gather for a routed ``[P, M']`` batch,
        executed SPMD via an explicit ``shard_map``: each device gathers
        its own shards' rows from its local slice of the frozen epoch
        buffers and resolves them in place — no cross-device traffic,
        no host-side concat.  Returns (resolved fields [P, M', ...],
        fresh [P, M']) as device handles (the writeback stage owns the
        materialize)."""
        fn = getattr(t, "_mesh_gather_fn", None)
        if fn is None or getattr(t, "_mesh_gather_plane", None) is not self:
            fn = self._build_gather(t)
            t._mesh_gather_fn = fn
            t._mesh_gather_plane = self
        return fn(head, head_vc, row_mat, vc_mat)

    def note_routed(self, shards: np.ndarray, slots: int,
                    seconds: float) -> None:
        """Tally one routed launch: ``shards`` i64[M] are its objects'
        shards, ``slots`` the P × M' it padded them to, ``seconds`` what
        the routing (host span ``serve.route``) took.  Per launch, never
        per request; the launch stage's one thread is the only caller."""
        self.routed_launches += 1
        self.routed_slots += slots
        self.routed_rows += len(shards)
        self.routed_rows_by_device += np.bincount(
            shards // (self.cfg.n_shards // self.n_devices),
            minlength=self.n_devices)
        self.route_seconds += seconds

    def _build_gather(self, t):
        ty, cfg = t.ty, t.cfg
        latest = _shard_read_latest_body(ty, cfg)
        spec = P(SHARD_AXIS)

        def body(head, head_vc, rows, read_vcs):
            # per-device block: [P_local, ...] — vmap the per-shard
            # gather body over the local shards, resolve in place
            state, fresh = jax.vmap(latest)(head, head_vc, rows, read_vcs)
            resolved = (
                ty.resolve(cfg, state)
                if ty.resolve_spec(cfg) is not None
                else state
            )
            return resolved, fresh

        return device_program("mesh_gather", jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(spec, spec, spec, spec),
            out_specs=(spec, spec), check_vma=False,
        ))

    # ------------------------------------------------------------------
    # giant-key sequence sharding (ROADMAP item 6 / SURVEY §5)
    # ------------------------------------------------------------------
    def fold_giant_key(self, ty, cfg, state0, ops_a, ops_b, ops_vc,
                       ops_origin, n_ops, base_vc, read_vc):
        """Fold ONE key's over-ring op log with the op axis sharded over
        the device mesh: every device reduces its contiguous chunk of the
        sequence to a partial delta, one ``all_gather`` exchanges the
        (tiny) deltas, and the monoid tree merges them in sequence order
        — ring attention's partial-softmax exchange, rendered for the
        celebrity-key materialization (``longlog.sharded_assoc_fold_fn``).

        Host-assembled operands on the leading op axis L (e.g. from WAL
        replay): ops_a i64[L, A], ops_b i32[L, B], ops_vc i32[L, D],
        ops_origin i32[L]; ``n_ops`` = the true op count ≤ L; base_vc /
        read_vc i32[D].  Requires ``ty.supports_assoc``.  L is padded to
        a power-of-two device multiple here — padded slots sit at global
        index ≥ n_ops, so the inclusion mask drops them; the bucketing
        keeps one XLA compile family per doubling, not per log length.

        Returns (state pytree, applied) as DEVICE arrays — callers own
        the materialize (no sync here).
        """
        fn = self._giant_fold_fns.get((ty.name, cfg))
        if fn is None:
            fn = longlog.sharded_assoc_fold_fn(ty, cfg, self.mesh)
            self._giant_fold_fns[(ty.name, cfg)] = fn
        l = int(ops_vc.shape[0])
        padded = self.n_devices
        while padded < l:
            padded *= 2
        pad = padded - l

        def padl(x, dtype):
            x = np.asarray(x, dtype)  # sync-ok: host-assembled replay log
            if pad:
                x = np.concatenate(
                    [x, np.zeros((pad,) + x.shape[1:], dtype)]
                )
            return x

        self.giant_folds += 1
        return fn(
            state0,
            padl(ops_a, np.int64), padl(ops_b, np.int32),
            padl(ops_vc, np.int32), padl(ops_origin, np.int32),
            # sync-ok: host scalars/clocks from the replay cut, not
            # device arrays
            np.int32(n_ops), np.asarray(base_vc, np.int32),
            np.asarray(read_vc, np.int32),
        )

    # ------------------------------------------------------------------
    # stable time: the pmin collective
    # ------------------------------------------------------------------
    def _pmin(self):
        if self._pmin_fn is None:
            spec = P(SHARD_AXIS)

            def body(clocks):
                # local entry-wise min over this device's shards, then
                # one pmin all-reduce over the mesh axis — the ICI
                # rendering of stable_time_functions:get_min_time
                return lax.pmin(jnp.min(clocks, axis=0), SHARD_AXIS)

            self._pmin_fn = device_program("mesh_pmin", jax.shard_map(
                body, mesh=self.mesh, in_specs=(spec,), out_specs=P(),
                check_vma=False,
            ))
        return self._pmin_fn

    def stable_vc(self, applied: np.ndarray | None = None) -> np.ndarray:
        """DC-wide stable snapshot as a device collective: entry-wise
        pmin over the per-device applied clocks.  Identical to the host
        reduction by construction (min is min); cached per applied-clock
        version so only clock ADVANCES pay the launch.

        ``applied`` is the caller's clock matrix — KVStore.stable_vc
        passes its OWN ``applied_vc`` so that, across a follower
        reinstall (the plane re-attaches to the fresh store before the
        txn manager swaps over), a concurrent lock-free txn start on
        the old store still computes from the old store's intact
        clocks, never the new store's zeroed ones."""
        if applied is None:
            applied = self.store.applied_vc
        with self._stable_lock:
            c = self._stable_cache
            if c is not None and np.array_equal(c[0], applied):
                return c[1].copy()
            snap = applied.copy()
        t0 = time.monotonic()
        # applied_vc is host i32 already; device_put shards it directly
        dev = jax.device_put(snap, self.sharding)
        # sync-ok: the stable-time collective's readback — a [D]-entry
        # clock vector, launched only when a commit advanced a clock
        # (cached otherwise); never on the lock-free read path
        out = np.asarray(self._pmin()(dev))
        if self.metrics is not None:
            self.metrics.mesh_stable_seconds.observe(time.monotonic() - t0)
        with self._stable_lock:
            self.stable_collectives += 1
            self._stable_cache = (snap, out)
        return out.copy()

    # ------------------------------------------------------------------
    def status(self) -> dict:
        """The node-status ``mesh`` block."""
        out = {
            "devices": self.n_devices,
            "axis": SHARD_AXIS,
            "shards_per_device": self.cfg.n_shards // self.n_devices,
            "stable_collectives": self.stable_collectives,
            "giant_folds": self.giant_folds,
            # routed epoch-read launches: padding share = 1 - rows/slots,
            # skew = the busiest device's rows over the mean
            "launches": self.routed_launches,
            "slots": self.routed_slots,
            "rows": self.routed_rows,
            "rows_by_device": {
                str(d): int(v)
                for d, v in enumerate(self.routed_rows_by_device)},
            "route": {"count": self.routed_launches,
                      "sum_ms": round(self.route_seconds * 1e3, 3)},
        }
        m = self.metrics
        if m is not None:
            # int keys, numeric order (labels are strings internally)
            out["publish_by_shard"] = dict(sorted(
                (int(k[0]), int(v))
                for k, v in m.mesh_publish.snapshot().items()
            ))
            s = m.mesh_stable_seconds.summary()
            out["stable_pmin_us"] = {
                "count": s["count"],
                "mean_us": round(s["mean"] * 1e6, 1),
                "p99_us": round(s["p99"] * 1e6, 1),
            }
        return out
