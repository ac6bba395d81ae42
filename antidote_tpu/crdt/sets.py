"""Set CRDTs: set_aw (add-wins / OR-set), set_rw (remove-wins), set_go.

Dense layouts for the antidote_crdt set types (SURVEY §2.8).  Each key has
``E = cfg.set_slots`` element slots; a slot holds the element's blob handle
plus two per-DC clock rows whose comparison decides presence:

  * set_aw: present ⟺ ∃dc: add_vc[dc] > rm_vc[dc] — the optimized OR-set
    (per-element add dots vs observed-remove dots).  A remove's downstream
    observes the current add_vc (require_state_downstream, reference
    /root/reference/src/clocksi_downstream.erl:43), so concurrent adds —
    whose dot the remove could not have observed — survive.
  * set_rw: present ⟺ element exists ∧ add_vc ≥ rm_vc pointwise; an add's
    downstream observes current rm_vc and covers it, so causally-past
    removes are overridden but concurrent removes win.
  * set_go: grow-only: a slot, once taken, never clears.

Because effects are applied in causal order (the dep gate,
/root/reference/src/inter_dc_dep_vnode.erl:128-154), an absent aw-element's
slot can be reclaimed: any later add is either causally after the remove
(fresh dot ⇒ present) or concurrent (unobserved dot ⇒ present) — no
tombstone needed.  rw-set slots are only reclaimed when fully empty, since
a remove must out-survive concurrent adds.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from antidote_tpu.crdt.base import (CRDTType, Effect, TopCountResolved,
                                    compact_top, warn_overflow_state)
from antidote_tpu.crdt.blob import EMPTY_HANDLE


def _elem_effects(op, blobs, make):
    kind, arg = op
    if kind.endswith("_all"):
        return [make(v) for v in arg]
    return [make(arg)]



def _dedup_window(w, hs, counts, rows=None):
    """Compress a (possibly duplicated) handle sequence into a W-entry
    delta window: first-occurrence-ordered distinct handles, per-handle
    summed ``counts``, optional per-handle lane-maxed clock ``rows``, and
    the op count that overflowed the window (``tail``) — the set types'
    associative-delta core.

    W static passes, each claiming the sequence-FIRST unclaimed handle
    (argmax over a shrinking bool mask finds the first True) and tagging
    every occurrence with its window slot: O(W·L) work at O(W) depth.
    With W = set_slots (≤ tens) this beats the sort-based dedup by an
    order of magnitude on million-op celebrity logs — a stable i64
    argsort alone costs more than the whole serial scan budget.  Ops
    whose handle never wins a slot keep the ``w`` sentinel and fall into
    ``tail``.
    """
    valid = hs != EMPTY_HANDLE
    l = hs.shape[0]
    entry = jnp.full((l,), w, jnp.int32)
    remaining = valid
    elems_slots = []
    for slot in range(w):
        idx = jnp.argmax(remaining)  # first unclaimed position (or 0)
        h = jnp.where(remaining[idx], hs[idx], EMPTY_HANDLE)
        # remaining ⊆ valid and valid excludes EMPTY, so an exhausted
        # mask (h == EMPTY) matches nothing and the slot stays empty
        match = remaining & (hs == h)
        entry = jnp.where(match, jnp.int32(slot), entry)
        remaining = remaining & ~match
        elems_slots.append(h)
    elems = jnp.stack(elems_slots)
    ent_idx = jnp.where(valid, entry, jnp.int32(w))
    cnt = jnp.zeros((w,), jnp.int32).at[ent_idx].add(counts, mode="drop")
    tail = jnp.sum(jnp.where(valid & (entry >= w), counts, 0),
                   dtype=jnp.int32)
    if rows is None:
        return elems, cnt, tail
    vcs = jnp.zeros((w, rows.shape[-1]), jnp.int32).at[ent_idx].max(
        rows, mode="drop"
    )
    return elems, cnt, tail, vcs


def _restamp_obs_row(eff_a, eff_b, my_dc, tentative_own, commit_own):
    """Rewrite the observed-VC row at eff_b[1:1+d] when its own lane
    carries the txn's tentative stamp (shared by the observed-remove and
    remove-wins sets)."""
    if int(eff_b[1 + my_dc]) == tentative_own:
        eff_b = np.array(eff_b, copy=True)
        eff_b[1 + my_dc] = commit_own
    return eff_a, eff_b


class SetAW(TopCountResolved, CRDTType):
    """Add-wins OR-set.

    Effect lanes: eff_a = [handle]; eff_b = [kind(0=add,1=rm),
    observed_add_vc[0..D)] (observed row zero for adds).
    """

    name = "set_aw"
    commutative_blind = True
    type_id = 6
    # the ADD lane is a monoid: from a bottom base, an all-adds window
    # reduces to (first-occurrence handles, per-handle dot maxes) and
    # partial windows merge associatively.  Removes and warm bases are
    # order-sensitive (slot steals), so dispatchers gate on both flags.
    supports_assoc = True
    assoc_bottom_only = True
    assoc_add_only = True

    def eff_b_width(self, cfg):
        return 1 + cfg.max_dcs

    # -- associative add-lane fold (materializer/longlog.py) ------------
    # Exactness preconditions (checked by dispatchers, see
    # store/kv.py::_replay_read_many): bottom base state, no removes in
    # the window, distinct handles ≤ set_slots (the slot-promotion
    # invariant keeps live keys under capacity), and positive own commit
    # dots (always true for committed ops).
    def delta_of_ops(self, cfg, ops_a, ops_b, ops_vc, ops_origin, mask):
        w, d = cfg.set_slots, cfg.max_dcs
        ok = mask & (ops_b[:, 0] == 0)  # defensive: adds only
        hs = jnp.where(ok, ops_a[:, 0], jnp.int64(EMPTY_HANDLE))
        own = jnp.take_along_axis(ops_vc, ops_origin[:, None], axis=1)[:, 0]
        rows = jax.nn.one_hot(ops_origin, d, dtype=jnp.int32) * jnp.where(
            ok, own, 0
        )[:, None].astype(jnp.int32)
        counts = ok.astype(jnp.int32)
        elems, cnt, tail, addvc = _dedup_window(w, hs, counts, rows)
        return {"elems": elems, "counts": cnt, "addvc": addvc, "tail": tail}

    def delta_merge(self, a, b):
        w = a["elems"].shape[0]
        hs = jnp.concatenate([a["elems"], b["elems"]])
        counts = jnp.concatenate([a["counts"], b["counts"]])
        rows = jnp.concatenate([a["addvc"], b["addvc"]])
        elems, cnt, tail, addvc = _dedup_window(w, hs, counts, rows)
        return {"elems": elems, "counts": cnt, "addvc": addvc,
                "tail": a["tail"] + b["tail"] + tail}

    def delta_apply(self, state, d):
        nd = state["addvc"].shape[-1]

        def body(j, carry):
            elems, addvc, rmvc, ovf = carry
            h, cnt, row = d["elems"][j], d["counts"][j], d["addvc"][j]
            valid = h != EMPTY_HANDLE
            match = (elems == h) & (elems != EMPTY_HANDLE)
            has_match = jnp.any(match)
            present = jnp.any(addvc > rmvc, axis=-1) & (elems != EMPTY_HANDLE)
            free = ~present
            idx = jnp.where(has_match, jnp.argmax(match), jnp.argmax(free))
            base_add = jnp.where(
                has_match, addvc[idx], jnp.zeros((nd,), jnp.int32)
            )
            base_rm = jnp.where(
                has_match, rmvc[idx], jnp.zeros((nd,), jnp.int32)
            )
            can = valid & (has_match | jnp.any(free))
            elems = jnp.where(can, elems.at[idx].set(h), elems)
            addvc = jnp.where(
                can, addvc.at[idx].set(jnp.maximum(base_add, row)), addvc
            )
            rmvc = jnp.where(can, rmvc.at[idx].set(base_rm), rmvc)
            ovf = ovf + jnp.where(valid & ~can, cnt, 0)
            return (elems, addvc, rmvc, ovf)

        elems, addvc, rmvc, ovf = jax.lax.fori_loop(
            0, d["elems"].shape[0], body,
            (state["elems"], state["addvc"], state["rmvc"],
             state["ovf"] + d["tail"]),
        )
        return {"elems": elems, "addvc": addvc, "rmvc": rmvc, "ovf": ovf}

    def state_spec(self, cfg):
        e, d = cfg.set_slots, cfg.max_dcs
        return {
            "elems": ((e,), jnp.int64),
            "addvc": ((e, d), jnp.int32),
            "rmvc": ((e, d), jnp.int32),
            "ovf": ((), jnp.int32),  # adds dropped for lack of a free slot
        }

    def is_operation(self, op):
        return op[0] in ("add", "remove", "add_all", "remove_all")

    def require_state_downstream(self, op):
        return op[0] in ("remove", "remove_all", "reset")

    def downstream(self, op, state, blobs, cfg) -> List[Effect]:
        d = cfg.max_dcs
        bw = self.eff_b_width(cfg)
        kind = op[0]

        def make(value):
            h = blobs.intern(value)
            a = np.asarray([h], dtype=np.int64)
            b = np.zeros((bw,), dtype=np.int32)
            if kind.startswith("remove"):
                b[0] = 1
                elems = np.asarray(state["elems"])
                hit = np.nonzero(elems == h)[0]
                if hit.size:
                    b[1 : 1 + d] = np.asarray(state["addvc"])[hit[0]]
            return (a, b, [(h, blobs.bytes_of(h))])

        return _elem_effects(op, blobs, make)


    def restamp_own_dots(self, cfg, eff_a, eff_b, my_dc, tentative_own,
                         commit_own):
        return _restamp_obs_row(eff_a, eff_b, my_dc, tentative_own,
                                commit_own)

    def value(self, state, blobs, cfg):
        warn_overflow_state(self.name, state)
        elems = np.asarray(state["elems"])
        present = np.any(
            np.asarray(state["addvc"]) > np.asarray(state["rmvc"]), axis=-1
        ) & (elems != EMPTY_HANDLE)
        return sorted((blobs.resolve(int(h)) for h in elems[present]), key=repr)

    def resolve_spec(self, cfg):
        t = self.resolve_top
        return {"top": ((t,), jnp.int64), "count": ((), jnp.int32),
                "ovf": ((), jnp.int32)}

    def resolve(self, cfg, state):
        """Device OR-set presence + compaction.  With ``cfg.use_pallas`` the
        presence comparison runs as the fused Pallas kernel
        (materializer/pallas_kernels.py::orset_presence) wherever
        ``pallas_kernels.in_path_ok`` routes the serving path to the
        kernels (a TPU); otherwise it is the plain-XLA comparison."""
        elems = state["elems"]
        use_kernel = False
        if getattr(cfg, "use_pallas", False):
            from antidote_tpu.materializer import pallas_kernels as pk

            use_kernel = pk.in_path_ok()
        if use_kernel:
            lead = elems.shape[:-1]
            e = elems.shape[-1]
            # occupancy in i32 lanes: fold the high word in so a handle
            # whose low 32 bits happen to be zero still reads occupied
            occ = (elems | (elems >> 32)).reshape((-1, e)).astype(jnp.int32)
            pres_i = pk.orset_presence(
                state["addvc"].reshape((-1, e, cfg.max_dcs)),
                state["rmvc"].reshape((-1, e, cfg.max_dcs)),
                occ,
            )
            present = pres_i.reshape(lead + (e,)) > 0
        else:
            present = jnp.any(state["addvc"] > state["rmvc"], axis=-1)
            present = present & (elems != EMPTY_HANDLE)
        top, count = compact_top(elems, present, self.resolve_top)
        return {"top": top, "count": count, "ovf": state["ovf"]}

    def slot_capacity(self, cfg):
        return cfg.set_slots

    def slot_demand(self, eff_a, eff_b):
        return 1 if int(eff_b[0]) == 0 else 0  # adds may claim a slot

    def used_slots(self, state):
        # an add can reclaim any non-present slot (apply's free mask)
        present = np.any(
            np.asarray(state["addvc"]) > np.asarray(state["rmvc"]), axis=-1
        ) & (np.asarray(state["elems"]) != EMPTY_HANDLE)
        return int(present.sum())

    def apply(self, cfg, state, eff_a, eff_b, commit_vc, origin_dc):
        d = cfg.max_dcs
        elems, addvc, rmvc = state["elems"], state["addvc"], state["rmvc"]
        h = eff_a[0]
        is_rm = eff_b[0] == 1
        obs = eff_b[1 : 1 + d]

        match = (elems == h) & (elems != EMPTY_HANDLE)
        has_match = jnp.any(match)
        idx_match = jnp.argmax(match)

        present = jnp.any(addvc > rmvc, axis=-1) & (elems != EMPTY_HANDLE)
        free = ~present
        idx_free = jnp.argmax(free)
        has_free = jnp.any(free)

        # --- add path: take matching slot, else a free slot (reset its rows)
        idx_add = jnp.where(has_match, idx_match, idx_free)
        fresh = ~has_match
        add_row_add = jnp.where(fresh, jnp.zeros((d,), jnp.int32), addvc[idx_add])
        add_row_rm = jnp.where(fresh, jnp.zeros((d,), jnp.int32), rmvc[idx_add])
        add_row_add = add_row_add.at[origin_dc].max(commit_vc[origin_dc])
        can_add = has_match | has_free
        elems_a = jnp.where(can_add, elems.at[idx_add].set(h), elems)
        addvc_a = jnp.where(can_add, addvc.at[idx_add].set(add_row_add), addvc)
        rmvc_a = jnp.where(can_add, rmvc.at[idx_add].set(add_row_rm), rmvc)

        # --- remove path: raise rm_vc to the observed add dots
        rm_row = jnp.maximum(rmvc[idx_match], obs)
        rmvc_r = jnp.where(has_match, rmvc.at[idx_match].set(rm_row), rmvc)

        dropped = ~is_rm & ~can_add
        return {
            "elems": jnp.where(is_rm, elems, elems_a),
            "addvc": jnp.where(is_rm, addvc, addvc_a),
            "rmvc": jnp.where(is_rm, rmvc_r, rmvc_a),
            "ovf": state["ovf"] + dropped.astype(jnp.int32),
        }


class SetRW(TopCountResolved, CRDTType):
    """Remove-wins set.

    Effect lanes: eff_a = [handle]; eff_b = [kind(0=add,1=rm),
    observed_rm_vc[0..D)] (observed row zero for removes).
    """

    name = "set_rw"
    commutative_blind = True
    type_id = 7

    def eff_b_width(self, cfg):
        return 1 + cfg.max_dcs

    def state_spec(self, cfg):
        e, d = cfg.set_slots, cfg.max_dcs
        return {
            "elems": ((e,), jnp.int64),
            "addvc": ((e, d), jnp.int32),
            "rmvc": ((e, d), jnp.int32),
            "ovf": ((), jnp.int32),
        }

    def is_operation(self, op):
        return op[0] in ("add", "remove", "add_all", "remove_all")

    def require_state_downstream(self, op):
        return op[0] in ("add", "add_all")

    def downstream(self, op, state, blobs, cfg) -> List[Effect]:
        d = cfg.max_dcs
        bw = self.eff_b_width(cfg)
        kind = op[0]

        def make(value):
            h = blobs.intern(value)
            a = np.asarray([h], dtype=np.int64)
            b = np.zeros((bw,), dtype=np.int32)
            if kind.startswith("remove"):
                b[0] = 1
            else:
                elems = np.asarray(state["elems"])
                hit = np.nonzero(elems == h)[0]
                if hit.size:
                    b[1 : 1 + d] = np.asarray(state["rmvc"])[hit[0]]
            return (a, b, [(h, blobs.bytes_of(h))])

        return _elem_effects(op, blobs, make)


    def restamp_own_dots(self, cfg, eff_a, eff_b, my_dc, tentative_own,
                         commit_own):
        return _restamp_obs_row(eff_a, eff_b, my_dc, tentative_own,
                                commit_own)

    def _present(self, elems, addvc, rmvc):
        has_add = np.any(np.asarray(addvc) > 0, axis=-1)
        covered = np.all(np.asarray(addvc) >= np.asarray(rmvc), axis=-1)
        return (np.asarray(elems) != EMPTY_HANDLE) & has_add & covered

    def value(self, state, blobs, cfg):
        warn_overflow_state(self.name, state)
        elems = np.asarray(state["elems"])
        present = self._present(elems, state["addvc"], state["rmvc"])
        return sorted((blobs.resolve(int(h)) for h in elems[present]), key=repr)

    def resolve_spec(self, cfg):
        t = self.resolve_top
        return {"top": ((t,), jnp.int64), "count": ((), jnp.int32),
                "ovf": ((), jnp.int32)}

    def resolve(self, cfg, state):
        elems, addvc, rmvc = state["elems"], state["addvc"], state["rmvc"]
        has_add = jnp.any(addvc > 0, axis=-1)
        covered = jnp.all(addvc >= rmvc, axis=-1)
        present = (elems != EMPTY_HANDLE) & has_add & covered
        top, count = compact_top(elems, present, self.resolve_top)
        return {"top": top, "count": count, "ovf": state["ovf"]}

    def slot_capacity(self, cfg):
        return cfg.set_slots

    def slot_demand(self, eff_a, eff_b):
        return 1  # adds and removes may both claim a slot (rw tombstones)

    def used_slots(self, state):
        # rw slots are reclaimed only when fully empty (apply's free mask)
        return int((np.asarray(state["elems"]) != EMPTY_HANDLE).sum())

    def apply(self, cfg, state, eff_a, eff_b, commit_vc, origin_dc):
        d = cfg.max_dcs
        elems, addvc, rmvc = state["elems"], state["addvc"], state["rmvc"]
        h = eff_a[0]
        is_rm = eff_b[0] == 1
        obs_rm = eff_b[1 : 1 + d]

        match = (elems == h) & (elems != EMPTY_HANDLE)
        has_match = jnp.any(match)
        idx_match = jnp.argmax(match)
        free = elems == EMPTY_HANDLE
        idx_free = jnp.argmax(free)
        has_free = jnp.any(free)

        # --- add: cover observed removes, stamp own dot
        idx_add = jnp.where(has_match, idx_match, idx_free)
        row_add = jnp.where(has_match, addvc[idx_add], jnp.zeros((d,), jnp.int32))
        row_add = jnp.maximum(row_add, obs_rm).at[origin_dc].max(commit_vc[origin_dc])
        can_add = has_match | has_free
        elems_a = jnp.where(can_add, elems.at[idx_add].set(h), elems)
        addvc_a = jnp.where(can_add, addvc.at[idx_add].set(row_add), addvc)

        # --- remove: stamp own dot on the rm row (create slot if needed so
        # the remove out-survives concurrent adds)
        idx_rm = jnp.where(has_match, idx_match, idx_free)
        can_rm = has_match | has_free
        row_rm_base = jnp.where(has_match, rmvc[idx_rm], jnp.zeros((d,), jnp.int32))
        row_rm = row_rm_base.at[origin_dc].max(commit_vc[origin_dc])
        elems_r = jnp.where(can_rm, elems.at[idx_rm].set(h), elems)
        rmvc_r = jnp.where(can_rm, rmvc.at[idx_rm].set(row_rm), rmvc)

        dropped = jnp.where(is_rm, ~can_rm, ~can_add)
        return {
            "elems": jnp.where(is_rm, elems_r, elems_a),
            "addvc": jnp.where(is_rm, addvc, addvc_a),
            "rmvc": jnp.where(is_rm, rmvc_r, rmvc),
            "ovf": state["ovf"] + dropped.astype(jnp.int32),
        }


class SetGO(TopCountResolved, CRDTType):
    """Grow-only set: slots fill monotonically."""

    name = "set_go"
    commutative_blind = True
    type_id = 8
    # grow-only inserts from a bottom base are first-occurrence order —
    # the same delta-window monoid as set_aw's add lane, minus clocks
    supports_assoc = True
    assoc_bottom_only = True

    def state_spec(self, cfg):
        e = cfg.set_slots
        return {"elems": ((e,), jnp.int64), "ovf": ((), jnp.int32)}

    # -- associative fold (materializer/longlog.py); exact from a bottom
    # base with distinct handles ≤ set_slots (see SetAW.delta_of_ops) ----
    def delta_of_ops(self, cfg, ops_a, ops_b, ops_vc, ops_origin, mask):
        w = cfg.set_slots
        hs = jnp.where(mask, ops_a[:, 0], jnp.int64(EMPTY_HANDLE))
        elems, cnt, tail = _dedup_window(w, hs, mask.astype(jnp.int32))
        return {"elems": elems, "counts": cnt, "tail": tail}

    def delta_merge(self, a, b):
        w = a["elems"].shape[0]
        elems, cnt, tail = _dedup_window(
            w,
            jnp.concatenate([a["elems"], b["elems"]]),
            jnp.concatenate([a["counts"], b["counts"]]),
        )
        return {"elems": elems, "counts": cnt,
                "tail": a["tail"] + b["tail"] + tail}

    def delta_apply(self, state, d):
        def body(j, carry):
            elems, ovf = carry
            h, cnt = d["elems"][j], d["counts"][j]
            valid = h != EMPTY_HANDLE
            has_match = jnp.any(elems == h)
            free = elems == EMPTY_HANDLE
            do_insert = valid & ~has_match & jnp.any(free)
            elems = jnp.where(
                do_insert, elems.at[jnp.argmax(free)].set(h), elems
            )
            ovf = ovf + jnp.where(valid & ~has_match & ~jnp.any(free), cnt, 0)
            return (elems, ovf)

        elems, ovf = jax.lax.fori_loop(
            0, d["elems"].shape[0], body,
            (state["elems"], state["ovf"] + d["tail"]),
        )
        return {"elems": elems, "ovf": ovf}

    def is_operation(self, op):
        return op[0] in ("add", "add_all")

    def downstream(self, op, state, blobs, cfg) -> List[Effect]:
        bw = self.eff_b_width(cfg)

        def make(value):
            h = blobs.intern(value)
            return (
                np.asarray([h], dtype=np.int64),
                np.zeros((bw,), dtype=np.int32),
                [(h, blobs.bytes_of(h))],
            )

        return _elem_effects(op, blobs, make)

    def value(self, state, blobs, cfg):
        warn_overflow_state(self.name, state)
        elems = np.asarray(state["elems"])
        return sorted(
            (blobs.resolve(int(h)) for h in elems[elems != EMPTY_HANDLE]), key=repr
        )

    def resolve_spec(self, cfg):
        t = self.resolve_top
        return {"top": ((t,), jnp.int64), "count": ((), jnp.int32),
                "ovf": ((), jnp.int32)}

    def resolve(self, cfg, state):
        elems = state["elems"]
        top, count = compact_top(elems, elems != EMPTY_HANDLE, self.resolve_top)
        return {"top": top, "count": count, "ovf": state["ovf"]}

    def slot_capacity(self, cfg):
        return cfg.set_slots

    def slot_demand(self, eff_a, eff_b):
        return 1

    def used_slots(self, state):
        return int((np.asarray(state["elems"]) != EMPTY_HANDLE).sum())

    def apply(self, cfg, state, eff_a, eff_b, commit_vc, origin_dc):
        elems = state["elems"]
        h = eff_a[0]
        match = elems == h
        has_match = jnp.any(match)
        free = elems == EMPTY_HANDLE
        idx = jnp.argmax(free)
        do_insert = ~has_match & jnp.any(free)
        dropped = ~has_match & ~jnp.any(free)
        return {
            "elems": jnp.where(do_insert, elems.at[idx].set(h), elems),
            "ovf": state["ovf"] + dropped.astype(jnp.int32),
        }
