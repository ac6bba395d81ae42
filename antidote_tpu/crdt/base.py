"""The CRDT type behaviour — the plugin boundary of the framework.

Reproduces the ``antidote_crdt`` behaviour visible at the reference's call
sites (SURVEY §2.8; /root/reference/src/materializer.erl:45-58,
/root/reference/src/clocksi_downstream.erl:38-68,
/root/reference/src/antidote.erl:183-200), re-shaped for a tensor store:

  * per-key state is a dict of fixed-shape arrays (``state_spec``)
  * a *downstream effect* is a pair of fixed-width lanes
    ``(eff_a: i64[A], eff_b: i32[B])`` produced on host from the client op
    (and, for observed-remove semantics, the current state snapshot)
  * ``apply`` is a pure JAX function folding one effect into one key's
    state; the materializer vmaps/scans it across keys and op rings
  * ``value`` decodes a host copy of the state into the client-visible value

Effects, not ops, are what the log stores and replication ships — exactly
the reference's op-based CRDT model (Type:downstream then Type:update).
"""

from __future__ import annotations

import abc
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from antidote_tpu.config import AntidoteConfig
from antidote_tpu.crdt.blob import BlobStore

# One downstream effect, host-side: (eff_a int64 lanes, eff_b int32 lanes,
# list of (handle, payload-bytes) the effect references).
Effect = Tuple[np.ndarray, np.ndarray, List[Tuple[int, bytes]]]


class CRDTType(abc.ABC):
    """Behaviour implemented by every CRDT type."""

    #: wire/type-registry name, e.g. "counter_pn"
    name: str
    #: stable small integer id (used in logs and wire format)
    type_id: int
    #: True when the fold is an associative+commutative monoid: the type
    #: also provides delta_of_ops/delta_merge/delta_apply, letting long op
    #: logs reduce in O(log L) depth and partial folds merge across
    #: devices (materializer/longlog.py; SURVEY §2.10 last row)
    supports_assoc: bool = False
    #: assoc fold is exact only from a BOTTOM base state: the delta window
    #: replays slot claims in sequence order, which matches ``apply`` only
    #: when every slot starts empty (sets).  Ring fold sites serve from an
    #: arbitrary GC'd base and must not route these through assoc_fold;
    #: replay/GC paths that build from bottom may.
    assoc_bottom_only: bool = False
    #: assoc fold additionally requires an all-adds window (set_aw: an
    #: observed-remove is order-sensitive against the adds around it)
    assoc_add_only: bool = False
    #: True for op-based types whose BLIND effects commute (counters,
    #: sets, flags): an update with no state-dependent downstream from a
    #: txn that read nothing needs no first-committer-wins round at all
    #: — concurrent blind updates all apply and converge by CRDT
    #: construction (the write-plane certification bypass, ISSUE 6; the
    #: reference's ``certify=false`` analogue made automatic).  Types
    #: where certification is the SEMANTICS — registers (assign races),
    #: escrow counters, rga positions, composite maps — stay False.
    commutative_blind: bool = False

    # ---- host side ----------------------------------------------------

    def eff_a_width(self, cfg: AntidoteConfig) -> int:
        """i64 lanes per effect."""
        return 1

    def eff_b_width(self, cfg: AntidoteConfig) -> int:
        """i32 lanes per effect (may depend on max_dcs)."""
        return 1

    @abc.abstractmethod
    def state_spec(self, cfg: AntidoteConfig) -> Dict[str, Tuple[tuple, Any]]:
        """name -> (per-key shape suffix, dtype) of the device state arrays."""

    @abc.abstractmethod
    def is_operation(self, op: Tuple[str, Any]) -> bool:
        """Type-check a client update (antidote:type_check/1,
        /root/reference/src/antidote.erl:183-200)."""

    def require_state_downstream(self, op: Tuple[str, Any]) -> bool:
        """Whether downstream generation needs the current snapshot
        (Type:require_state_downstream/1,
        /root/reference/src/clocksi_downstream.erl:43)."""
        return False

    @abc.abstractmethod
    def downstream(
        self,
        op: Tuple[str, Any],
        state: Dict[str, np.ndarray] | None,
        blobs: BlobStore,
        cfg: AntidoteConfig,
    ) -> List[Effect]:
        """Turn a client op into downstream effect(s).

        ``state`` is a host copy of the key's *materialized* per-key state
        (present iff require_state_downstream), used for observed-remove
        semantics.  May return several effects (e.g. add_all).
        """

    @abc.abstractmethod
    def value(
        self, state: Dict[str, np.ndarray], blobs: BlobStore, cfg: AntidoteConfig
    ) -> Any:
        """Client-visible value of a host state copy (Type:value/1)."""

    def stamp_op_seq(self, eff_a, eff_b, seq: int):
        """Number an effect within its transaction (per key).  Types
        whose apply derives identity from the commit clock alone (rga
        uids) carry the sequence in an effect lane so same-commit ops
        stay distinguishable.  Default: identity."""
        return eff_a, eff_b

    def restamp_own_dots(self, cfg: AntidoteConfig, eff_a, eff_b,
                         my_dc: int, tentative_own: int, commit_own: int):
        """Rewrite dots an effect observed from the txn's OWN uncommitted
        writes: overlay applies stamp pending effects with a tentative
        own-lane ts (snapshot+1); the real commit ts may differ when
        other txns committed in between, so observed-VC lanes / packed
        ids equal to the tentative value are rewritten to the commit ts
        at commit time.  No collision with real observations is possible:
        anything observed from the snapshot has own-lane ts ≤ snapshot <
        tentative.  Default: the effect observes no dots — unchanged."""
        return eff_a, eff_b

    # ---- device side ---------------------------------------------------

    @abc.abstractmethod
    def apply(
        self,
        cfg: AntidoteConfig,
        state: Dict[str, Any],
        eff_a,
        eff_b,
        commit_vc,
        origin_dc,
    ) -> Dict[str, Any]:
        """Fold one effect into one key's state.  Pure JAX; traced inside the
        materializer scan (Type:update/2,
        /root/reference/src/materializer.erl:51-58)."""

    # ---- device-side value resolution (serving fast path) --------------
    #: how many value lanes ``resolve`` compacts multi-element values into;
    #: keys with more present elements than this report the true count and
    #: the caller re-fetches the full state (rare — Antidote sets/maps are
    #: small per key)
    resolve_top = 4

    def resolve_spec(self, cfg: AntidoteConfig):
        """Layout of the compact device-resolved value view:
        name -> (per-key shape suffix, dtype), or ``None`` when the type has
        no device resolution (callers fall back to the host ``value``).

        This is the device analogue of ``Type:value`` in the batched read
        path (cure:transform_reads, /root/reference/src/cure.erl:186-192):
        instead of shipping full per-key state host-side and decoding in
        Python, the resolution runs on device and only the compact view
        crosses the host/device boundary."""
        return None

    def resolve(self, cfg: AntidoteConfig, state: Dict[str, Any]) -> Dict[str, Any]:
        """Batched device value resolution: ``state`` fields carry arbitrary
        leading batch dims; returns arrays per ``resolve_spec``.  Pure JAX,
        traced inside the serving read kernel."""
        raise NotImplementedError(f"{self.name} has no device resolution")

    # ---- slot accounting (the overflow escape hatch) -------------------
    # The reference's slotted analogues (sets, maps, mv-register, rga)
    # grow without bound; fixed device layouts cannot.  Instead of
    # dropping ops on slot exhaustion, the store PROMOTES a key to a
    # wider-slot sibling table before appending (KVStore._promote_key),
    # driven by a host-side conservative bound: ``slot_demand`` ops may
    # each claim a fresh slot, so bound_after = bound + demand; when that
    # exceeds ``slot_capacity`` the key migrates and the bound resets to
    # ``used_slots`` (exact, from the head state).  The bound only ever
    # over-counts, so no op is ever dropped.

    def slot_capacity(self, cfg: AntidoteConfig):
        """Max element slots a key of this type holds at ``cfg``'s widths,
        or ``None`` for unslotted types (counters, flags, lww)."""
        return None

    def slot_demand(self, eff_a, eff_b) -> int:
        """How many fresh slots this one effect may claim (host, 0/1)."""
        return 0

    def used_slots(self, state: Dict[str, np.ndarray]) -> int:
        """Exact count of slots an incoming add cannot claim, from a host
        copy of the key's head state."""
        return 0

    def value_from_resolved(
        self, resolved: Dict[str, np.ndarray], blobs: BlobStore,
        cfg: AntidoteConfig,
    ) -> Any:
        """Client-visible value reconstructed from ONE key's compact
        device-resolved view (``resolve_spec`` layout) — the host half of
        the serving read path (cure:transform_reads,
        /root/reference/src/cure.erl:186-192): the device ran ``resolve``,
        only the compact view crossed to the host, and this turns it into
        the same value ``value`` would return from the full state.

        Returns :data:`RESOLVE_OVERFLOW` when the compact view is
        truncated (count > ``resolve_top``) and the caller must re-fetch
        the full state.  Only called for types with a ``resolve_spec``."""
        raise NotImplementedError(f"{self.name} has no resolved decoding")


#: sentinel: the compact resolved view was truncated; re-fetch full state
RESOLVE_OVERFLOW = object()


def warn_overflow(type_name: str, ovf: int, stacklevel: int = 3) -> None:
    """Surface element-slot exhaustion (the device apply dropped ``ovf``
    ops).  Raising would make the key unreadable; warn loudly instead —
    growth + WAL replay is the recovery path."""
    if ovf > 0:
        import warnings

        warnings.warn(
            f"{type_name}: {ovf} op(s) dropped — cfg slots exhausted "
            "for this key; increase the slot budget (data until then is "
            "truncated)",
            RuntimeWarning,
            stacklevel=stacklevel,
        )


def warn_overflow_state(type_name: str, state) -> None:
    """Slot-exhaustion warning from a full host state copy (the
    resolved-view twin lives in :class:`TopCountResolved`)."""
    warn_overflow(type_name, int(np.asarray(state.get("ovf", 0))),
                  stacklevel=4)


def value_from_top(resolved, blobs: BlobStore, top: int):
    """Shared ``value_from_resolved`` body for top-k/count multi-element
    types (sets, mv-register): resolve the packed handles, or signal
    overflow when the true count exceeds the compacted lanes."""
    count = int(resolved["count"])
    if count > top:
        return RESOLVE_OVERFLOW
    handles = np.asarray(resolved["top"]).reshape(-1)
    return sorted(
        (blobs.resolve(int(h)) for h in handles if h != 0), key=repr
    )


class TopCountResolved:
    """Mixin for slotted multi-element types whose compact device view is
    ``{top, count, ovf}``: decode via :func:`value_from_top`, preserving
    the slot-exhaustion warning the full-state ``value`` path emits."""

    def value_from_resolved(self, resolved, blobs, cfg):
        v = value_from_top(resolved, blobs, self.resolve_top)
        if v is not RESOLVE_OVERFLOW:
            # truncated views re-fetch full state and warn in value();
            # warning here too would double-fire for one read
            warn_overflow(self.name, int(np.asarray(resolved.get("ovf", 0))))
        return v


def compact_top(elems, present, top: int):
    """Compact a slotted multi-element value view on device.

    ``elems`` i64[..., E], ``present`` bool[..., E] → (``top_elems``
    i64[..., top] — the first ``top`` present elements, zero-padded —
    and ``count`` i32[...], the true presence count).  Callers re-fetch
    the full state for keys whose count exceeds ``top``."""
    import jax.numpy as jnp

    # the k-th present slot is the one whose running count of present
    # slots reads k + 1: ``top`` masked sums, no sort (a stable argsort
    # over a tier's 1,024 slots is a sorting network that takes the TPU's
    # compiler 11 s, and over 4,096 slots 18)
    rank = jnp.cumsum(present, axis=-1, dtype=jnp.int32)
    top_elems = jnp.stack(
        [jnp.sum(jnp.where(present & (rank == k + 1), elems, 0), axis=-1)
         for k in range(top)], axis=-1)
    return top_elems, rank[..., -1]


def pack_a(*vals: int, width: int) -> np.ndarray:
    out = np.zeros((width,), dtype=np.int64)
    for i, v in enumerate(vals):
        out[i] = v
    return out


def pack_b(vals: Sequence[int], width: int) -> np.ndarray:
    out = np.zeros((width,), dtype=np.int32)
    for i, v in enumerate(vals):
        out[i] = v
    return out
