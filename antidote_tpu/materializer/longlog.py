"""Long op-log materialization — the sequence-parallel analogue.

The reference keeps unbounded per-key op chains readable with cached
resume-point snapshots and incremental folds
(/root/reference/src/materializer_vnode.erl:37-39,
/root/reference/src/vector_orddict.erl:74-87); there is no parallelism
within one chain.  Here the op log IS the sequence axis (SURVEY §5
long-context), and three strategies scale it:

  * ``assoc_fold`` — for monoid CRDTs (counter_pn, flag_ew, flag_dw) the
    masked fold is a reduction: O(log L) depth on device instead of a
    length-L serial scan.
  * ``fold_long`` — for order-dependent types, a chunked ``lax.scan`` over
    [C, chunk] keeps memory bounded and compile time flat for huge L.
  * ``sharded_assoc_fold`` — ring-style sequence parallelism: the op axis
    is sharded over the device mesh, every device reduces its chunk, and
    the partial deltas merge with one ``all_gather`` + monoid tree — the
    database analogue of ring attention's partial-softmax exchange.

Inclusion semantics are identical to ``fold.fold_key``
(clocksi_materializer:is_op_in_snapshot,
/root/reference/src/clocksi_materializer.erl:214-268).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from antidote_tpu.clock import vector as vc
from antidote_tpu.obs.trace import device_program


def include_mask(ops_vc, n_ops, base_vc, read_vc):
    """Per-op inclusion: ¬(op ≤ base) ∧ op ≤ read ∧ slot < n_ops."""
    k = ops_vc.shape[0]
    slots = jnp.arange(k, dtype=jnp.int32)
    in_base = jnp.all(ops_vc <= base_vc[None, :], axis=-1)
    visible = jnp.all(ops_vc <= read_vc[None, :], axis=-1)
    return ~in_base & visible & (slots < n_ops)


def assoc_fold(ty, cfg, state0, ops_a, ops_b, ops_vc, ops_origin, n_ops,
               base_vc, read_vc):
    """Monoid reduction fold for one key (requires ty.supports_assoc)."""
    assert ty.supports_assoc, ty.name
    mask = include_mask(ops_vc, n_ops, base_vc, read_vc)
    delta = ty.delta_of_ops(cfg, ops_a, ops_b, ops_vc, ops_origin, mask)
    return ty.delta_apply(state0, delta), jnp.sum(mask.astype(jnp.int32))


def fold_long(ty, cfg, state0, ops_a, ops_b, ops_vc, ops_origin, n_ops,
              base_vc, read_vc, chunk: int = 1024):
    """Serial chunked fold for one key's arbitrarily long op log.

    Operands carry the full log on the leading axis L (host-assembled,
    e.g. from a WAL replay); L is padded up to a multiple of ``chunk`` by
    the caller via n_ops masking.  Works for every CRDT type.
    """
    l = ops_vc.shape[0]
    assert l % chunk == 0, (l, chunk)
    c = l // chunk

    def rs(x):
        return x.reshape((c, chunk) + x.shape[1:])

    slots0 = jnp.arange(l, dtype=jnp.int32).reshape(c, chunk)

    def chunk_step(carry, xs):
        state, applied = carry
        a, b, v, o, slots = xs

        def op_step(carry2, ys):
            st, ap = carry2
            ea, eb, op_vc, origin, slot = ys
            inc = (
                ~vc.le(op_vc, base_vc)
                & vc.le(op_vc, read_vc)
                & (slot < n_ops)
            )
            new = ty.apply(cfg, st, ea, eb, op_vc, origin)
            merged = jax.tree.map(lambda n_, o_: jnp.where(inc, n_, o_), new, st)
            return (merged, ap + inc.astype(jnp.int32)), None

        (state, applied), _ = lax.scan(
            op_step, (state, applied), (a, b, v, o, slots)
        )
        return (state, applied), None

    (state, applied), _ = lax.scan(
        chunk_step, (state0, jnp.int32(0)),
        (rs(ops_a), rs(ops_b), rs(ops_vc), rs(ops_origin), slots0),
    )
    return state, applied


def sharded_assoc_fold_fn(ty, cfg, mesh, axis: str = "shard"):
    """Build the jitted sequence-parallel fold: op arrays sharded on the
    leading (op) axis over ``mesh``; one all_gather merges the per-device
    partial deltas (ICI traffic = one delta per device, not the log)."""
    n_dev = mesh.devices.size

    def per_device(ops_a, ops_b, ops_vc, ops_origin, n_ops, base_vc, read_vc,
                   offset):
        # local block: global slot = offset + local index
        k = ops_vc.shape[0]
        slots = offset + jnp.arange(k, dtype=jnp.int32)
        in_base = jnp.all(ops_vc <= base_vc[None, :], axis=-1)
        visible = jnp.all(ops_vc <= read_vc[None, :], axis=-1)
        mask = ~in_base & visible & (slots < n_ops)
        delta = ty.delta_of_ops(cfg, ops_a, ops_b, ops_vc, ops_origin, mask)
        applied = jnp.sum(mask.astype(jnp.int32))
        # exchange partial deltas; tree-merge the small gathered pytree
        gathered = jax.tree.map(
            lambda x: lax.all_gather(x, axis), delta
        )
        total = jax.tree.map(lambda x: x[0], gathered)
        for i in range(1, n_dev):
            total = ty.delta_merge(
                total, jax.tree.map(lambda x: x[i], gathered)
            )
        return total, lax.psum(applied, axis)

    op_spec = P(axis)
    rep = P()

    def fn(state0, ops_a, ops_b, ops_vc, ops_origin, n_ops, base_vc, read_vc):
        l = ops_vc.shape[0]
        assert l % n_dev == 0, (l, n_dev)
        per = l // n_dev
        offsets = jnp.arange(n_dev, dtype=jnp.int32) * per

        mapped = jax.shard_map(
            per_device,
            mesh=mesh,
            in_specs=(op_spec, op_spec, op_spec, op_spec, rep, rep, rep,
                      op_spec),
            out_specs=(rep, rep),
            check_vma=False,
        )
        delta, applied = mapped(
            ops_a, ops_b, ops_vc, ops_origin,
            jnp.int32(n_ops), base_vc, read_vc, offsets,
        )
        return ty.delta_apply(state0, delta), applied

    return device_program("giant_fold", fn)
