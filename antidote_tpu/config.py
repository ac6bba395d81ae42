"""Global configuration for an antidote_tpu deployment.

Mirrors the reference's compile-time knobs (/root/reference/include/antidote.hrl:10-79)
and app-env flags (/root/reference/src/antidote.app.src:29-62), re-expressed for a
fixed-shape tensor store.
"""

from __future__ import annotations

import dataclasses
import os


@dataclasses.dataclass(frozen=True)
class AntidoteConfig:
    """Deployment-wide sizing and semantics knobs.

    The reference sizes (16-partition ring, 20 read servers, GC thresholds
    10/3/50/5 — include/antidote.hrl:28,36-47) inform the defaults, but
    here shapes must be static for XLA so they are explicit.
    """

    # --- cluster shape -------------------------------------------------
    #: number of shards ("partitions"); reference default ring size = 16
    #: (/root/reference/config/vars.config:5)
    n_shards: int = 8
    #: dense vector-clock width: max number of DCs (replicas). Reference VCs
    #: are dicts keyed by dcid; we use a stable dcid->lane registry.
    max_dcs: int = 4

    # --- per-type table sizing ----------------------------------------
    #: op-ring slots per key before a GC fold is forced. Analogue of
    #: ?OPS_THRESHOLD=50 (include/antidote.hrl:44) — ours is a hard ring size.
    ops_per_key: int = 16
    #: materialized snapshot versions retained per key. Analogue of
    #: ?SNAPSHOT_THRESHOLD=10 / ?SNAPSHOT_MIN=3 (include/antidote.hrl:36-41).
    snap_versions: int = 2
    #: element slots per set/map key (set_aw/set_rw/set_go/map membership)
    set_slots: int = 16
    #: concurrent-value slots for register_mv
    mv_slots: int = 4
    #: element slots per rga sequence key
    rga_slots: int = 64
    #: number of key slots per (shard, type) table; grows by doubling
    keys_per_table: int = 1024

    # --- read batching -------------------------------------------------
    #: read/commit batches are padded up to one of these sizes to bound
    #: the number of compiled kernel variants
    batch_buckets: tuple = (64, 512, 4096)

    # --- durability (reference: antidote.app.src:44-48) ---------------
    sync_log: bool = False
    #: parallel append segments per shard WAL (ISSUE 6): a commit group's
    #: records land on one segment while the group-fsync coordinator
    #: syncs the previous one in the background, so the serial
    #: append+fsync floor splits across segments.  1 = the classic
    #: single-file-per-shard layout (and byte-identical file contents);
    #: recovery merges segments by the per-shard append sequence either
    #: way.  Serving entrypoints (console serve) default higher.
    wal_segments: int = 1

    # --- kernels --------------------------------------------------------
    #: dispatch the materializer hot loops to the hand-tiled Pallas TPU
    #: kernels (materializer/pallas_kernels.py) where a type-specific fused
    #: kernel exists (counter fold, OR-set fold and presence); the
    #: generic XLA scan fold remains the fallback and the semantics oracle
    use_pallas: bool = False
    #: over-ring fold routing threshold (store/kv.py::_replay_read_many):
    #: a replayed key whose op-log extent exceeds this folds with the
    #: chunked ``fold_long`` (or, assoc types on a mesh, the op-axis-
    #: sharded ``sharded_assoc_fold``) instead of one giant serial scan —
    #: and each strategy's pad-to-multiple keeps XLA compile families
    #: bounded instead of one fresh compile per log length
    fold_chunk: int = 4096

    def __post_init__(self):
        assert self.n_shards >= 1
        assert self.max_dcs >= 1
        assert self.snap_versions >= 1
        assert self.ops_per_key >= 2


DEFAULT_CONFIG = AntidoteConfig()


#: the persistent XLA compile cache when ``JAX_COMPILATION_CACHE_DIR``
#: does not place it: one fixed, git-ignored directory at the root of the
#: checkout.  The path is part of the cache key, so it never varies.
XLA_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".xla_cache"
)


def enable_compilation_cache(path: str = XLA_CACHE_DIR) -> None:
    """Turn on JAX's persistent XLA compile cache for this process.

    The serving fns compile per (type, batch-bucket, fold-window) shape;
    a cold server pays seconds of compile debt as traffic discovers the
    shape family, which is exactly the latency-tail profile a database
    must not have (the BEAM reference has no such debt — its hot paths
    are interpreted).  With the on-disk cache, every antidote process of
    the checkout (server restarts, cluster members, test subprocesses)
    warms from the first process's compiles.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself keeps the
    cache there and this sets no directory; otherwise the cache lives at
    ``path`` (default :data:`XLA_CACHE_DIR`)."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
