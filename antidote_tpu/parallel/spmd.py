"""SPMD execution over a device mesh — the riak_core ring on ICI.

The reference distributes state over a 16-partition consistent-hash ring of
Erlang vnode processes (/root/reference/src/antidote_app.erl:42-59) and
computes the DC-wide stable snapshot by 1 s metadata gossip + entry-wise
min (/root/reference/src/meta_data_sender.erl:224-255,
/root/reference/src/stable_time_functions.erl:51-85).

Here the ring is a ``jax.sharding.Mesh`` with one axis, ``"shard"``: every
table array carries a leading shard axis laid out over the mesh, the data
plane (scatter-append, materializer fold) is embarrassingly parallel per
shard, and the stable snapshot is a single ``lax.pmin`` collective over ICI
per step — replacing the gossip rounds entirely.

``sharded_step_fn`` builds the full replica step as ONE jitted program:
  1. scatter a routed commit batch into the op rings (per shard)
  2. materialize a routed read batch (per shard)
  3. advance per-shard applied clocks and pmin them into the stable VC
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from antidote_tpu.obs.trace import device_program
from antidote_tpu.store.typed_table import _shard_base_select_body, _shard_read_body

SHARD_AXIS = "shard"


def make_mesh(n_devices: int | None = None) -> Mesh:
    devices = jax.devices()
    n = n_devices or len(devices)
    return Mesh(devices[:n], (SHARD_AXIS,))


def shard_axis_sharding(mesh: Mesh) -> NamedSharding:
    """Leading-axis sharding for table arrays: [P, ...] over the mesh."""
    return NamedSharding(mesh, P(SHARD_AXIS))


def sharded_step_fn(ty, cfg, mesh: Mesh):
    """One jitted replica step over the mesh (see module docstring).

    All batch operands are per-shard routed/padded:
      app_rows/app_slots i64[P, Ma], app_a i64[P, Ma, A], app_b i32[P, Ma, B],
      app_vc i32[P, Ma, D], app_origin i32[P, Ma];
      read_rows i64[P, Mr], read_n_ops i32[P, Mr], read_vcs i32[P, Mr, D];
      applied_vc i32[P, D].
    Returns (new ops arrays, read state pytree [P, Mr, ...], applied [P, Mr],
    complete [P, Mr], new_applied_vc [P, D], stable_vc [P, D] — the pmin,
    identical on every shard row).

    With ``cfg.use_pallas`` and a counter table, the ring fold inside
    the step dispatches to the fused Pallas kernel with SHARD-LOCAL
    extents (``pallas_kernels.counter_fold_deltas``): each shard's block
    runs its own kernel grid inside the shard_map body, so the fold
    stays device-local on a mesh (interpret mode off-TPU).  CALLER
    CONTRACT: the kernel sums lane-0 deltas in i32, and a static step
    fn cannot host-gate per batch — only enable ``use_pallas`` when
    every |delta| ≤ INT32_MAX // ops_per_key (the bound typed_table
    enforces dynamically via its host-tracked ``max_abs_delta`` before
    choosing ITS pallas dispatch; here the check is yours).
    """
    from antidote_tpu.materializer import pallas_kernels as pk

    read_body = _shard_read_body(ty, cfg)
    # platform-gated like the store's strategy picker: interpret-mode
    # kernels on CPU regress the step, they don't accelerate it
    use_pallas = (bool(getattr(cfg, "use_pallas", False))
                  and pk.in_path_ok())
    pallas_counter = use_pallas and ty.name == "counter_pn"
    pallas_set_aw = use_pallas and ty.name == "set_aw"
    select_body = (
        _shard_base_select_body(ty, cfg)
        if (pallas_counter or pallas_set_aw)
        else None
    )

    def per_shard(snap, snap_vc, snap_seq, ops_a, ops_b, ops_vc, ops_origin,
                  app_rows, app_slots, app_a, app_b, app_vc, app_origin,
                  read_rows, read_n_ops, read_vcs, applied_vc):
        # shard_map hands each shard its block with the leading axis of
        # size 1 kept; drop it for the body.
        sq = lambda t: jax.tree.map(lambda x: x[0], t)
        (snap, snap_vc, snap_seq, ops_a, ops_b, ops_vc, ops_origin,
         app_rows, app_slots, app_a, app_b, app_vc, app_origin,
         read_rows, read_n_ops, read_vcs, applied_vc) = map(
            sq,
            (snap, snap_vc, snap_seq, ops_a, ops_b, ops_vc, ops_origin,
             app_rows, app_slots, app_a, app_b, app_vc, app_origin,
             read_rows, read_n_ops, read_vcs, applied_vc),
        )
        # 1. commit scatter (padding rows are out-of-range → dropped)
        ops_a = ops_a.at[app_rows, app_slots].set(app_a, mode="drop")
        ops_b = ops_b.at[app_rows, app_slots].set(app_b, mode="drop")
        ops_vc = ops_vc.at[app_rows, app_slots].set(app_vc, mode="drop")
        ops_origin = ops_origin.at[app_rows, app_slots].set(
            app_origin, mode="drop"
        )
        # 2. advance this shard's applied clock
        n = ops_a.shape[0]
        valid = (app_rows < n)[:, None]
        new_applied = jnp.maximum(
            applied_vc, jnp.max(jnp.where(valid, app_vc, 0), axis=0)
        )
        # 3. stable snapshot: entry-wise min across shards, over ICI
        stable = lax.pmin(new_applied, SHARD_AXIS)
        # 4. batched materializer read
        rows_clip = jnp.minimum(read_rows, n - 1)
        if pallas_counter:
            # Pallas fold with shard-local extents, inside the sharded
            # step: version-select the base on this shard's block, then
            # one fused masked-sum kernel over the local ring slice —
            # the kernel grid never crosses the shard axis
            from antidote_tpu.materializer import pallas_kernels as pk

            base_state, base_vc, complete = select_body(
                snap, snap_vc, snap_seq, rows_clip, read_vcs
            )
            dcnt, applied = pk.counter_fold_deltas(
                ops_a[rows_clip][..., 0].astype(jnp.int32),
                ops_vc[rows_clip], read_n_ops, base_vc, read_vcs,
            )
            state = {"cnt": base_state["cnt"] + dcnt.astype(jnp.int64)}
        elif pallas_set_aw:
            # same shape: base select on this shard's block, then the
            # fused add-wins fold kernel over the local ring slice — the
            # BASELINE workload's own fold, shard-local on the mesh
            from antidote_tpu.materializer import pallas_kernels as pk

            base_state, base_vc, complete = select_body(
                snap, snap_vc, snap_seq, rows_clip, read_vcs
            )
            state, applied = pk.set_aw_fold(
                base_state, ops_a[rows_clip], ops_b[rows_clip],
                ops_vc[rows_clip], ops_origin[rows_clip],
                read_n_ops, base_vc, read_vcs,
            )
        else:
            state, applied, complete = read_body(
                snap, snap_vc, snap_seq, ops_a, ops_b, ops_vc, ops_origin,
                rows_clip, read_n_ops, read_vcs,
            )
        ex = lambda t: jax.tree.map(lambda x: x[None], t)
        return (
            ex(ops_a), ex(ops_b), ex(ops_vc), ex(ops_origin),
            ex(state), ex(applied), ex(complete),
            ex(new_applied), ex(stable),
        )

    spec = P(SHARD_AXIS)
    n_in = 17
    step = device_program(
        "spmd_step",
        jax.shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(spec,) * n_in,
            out_specs=(spec,) * 9,
            check_vma=False,
        )
    )
    return step
