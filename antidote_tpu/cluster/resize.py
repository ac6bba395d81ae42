"""Offline DC membership resize: N member log-dirs -> M member log-dirs.

The reference changes membership live through riak_core's staged
join/leave + ownership handoff (/root/reference/src/antidote_console.erl:34-50,
riak_core handoff).  Here ownership is the modular layout (shard s owned
by member s % n_members — the takeover protocol's involved-owner
computation depends on it, cluster/member.py), so membership changes are
a RING-WIDE remap performed OFFLINE on quiesced logs:

    python -m antidote_tpu.cluster.resize \
        --old-dirs /data/m0,/data/m1 --new-dirs /data/n0,/data/n1,/data/n2

1. every old member's store recovers from its WAL; prepare logs are
   checked for staged-but-undecided txns (resize refuses until takeover
   settles them — run `console cluster-resolve` / `cluster-sweep` first);
2. each shard's table slice + WAL chain moves to its new owner via the
   handoff package machinery (store/handoff.py);
3. the sequencer ledger carries over to the new member 0 (per-shard
   last-ts chains + a counter floor at the global max applied ts);
4. members then boot with ``cluster.boot --members M --recover``.
"""

from __future__ import annotations

import argparse
import sys
from typing import List


def resize_dc(old_dirs: List[str], new_dirs: List[str], dc_id: int = 0
              ) -> None:
    import os

    from antidote_tpu.api.node import AntidoteNode
    from antidote_tpu.config import AntidoteConfig
    from antidote_tpu.log import load_dir_meta
    from antidote_tpu.log.wal import replay
    from antidote_tpu.store import handoff

    n_old, n_new = len(old_dirs), len(new_dirs)
    if set(old_dirs) & set(new_dirs):
        raise ValueError("new dirs must be disjoint from old dirs")
    for d in new_dirs:
        if os.path.isdir(d) and os.listdir(d):
            raise ValueError(f"new dir {d!r} is not empty")
    meta = load_dir_meta(old_dirs[0])
    if meta is None:
        raise RuntimeError(f"{old_dirs[0]!r} has no log-dir metadata")
    cfg = AntidoteConfig(n_shards=meta["n_shards"], max_dcs=meta["max_dcs"])

    # ---- quiescence gate: no staged-but-undecided txns anywhere
    for d in old_dirs:
        prep = os.path.join(d, "prepare.wal")
        if not os.path.exists(prep):
            continue
        staged = {}
        for rec in replay(prep):
            ev = rec.get("ev")
            txid = int(rec.get("txid", 0))
            if ev == "prep":
                staged[txid] = True
            elif ev in ("commit", "abort"):
                staged.pop(txid, None)
        if staged:
            raise RuntimeError(
                f"{d!r} holds staged-but-undecided txns {sorted(staged)}; "
                "settle them first (console cluster-resolve / "
                "cluster-sweep on the live cluster)")

    # ---- recover old members through the FULL member machinery: a crash
    # between the durable commit record and the store apply leaves the
    # effects only in prepare.wal, and _replay_recovered_commits is what
    # re-applies them — a bare store-WAL replay would silently drop an
    # acknowledged commit
    from antidote_tpu.cluster.member import ClusterMember

    old_members = [
        ClusterMember(cfg, dc_id=dc_id, member_id=i, n_members=n_old,
                      log_dir=d, recover=True)
        for i, d in enumerate(old_dirs)
    ]
    new_nodes = [AntidoteNode(cfg, dc_id=dc_id, log_dir=d)
                 for d in new_dirs]

    # ---- move every shard to its new owner
    for s in range(cfg.n_shards):
        src = old_members[s % n_old].node
        dst = new_nodes[s % n_new]
        pkg = handoff.export_shard(src.store, s)
        handoff.import_shard(dst.store, pkg)

    # ---- sequencer floor for the new member 0: per-shard last-ts =
    # the old OWNER's applied frontier (NOT the old ledger's last issued
    # ts: a takeover-aborted hole is closed only by an in-memory no-op
    # link, so carrying the issued ts would wedge the first post-resize
    # commit behind a prev no one can reach)
    from antidote_tpu.log.wal import ShardWAL

    w = ShardWAL(os.path.join(new_dirs[0], "prepare.wal"))
    max_ts = 0
    for s in range(cfg.n_shards):
        owner = old_members[s % n_old]
        ts_s = int(owner.applied_ts.get(s, 0))
        max_ts = max(max_ts, ts_s)
        if ts_s > 0:
            w.append({"ev": "seq", "ts": ts_s, "txid": 0,
                      "shards": [int(s)], "prev": {}})
    # counter floor covers lanes with no per-shard record
    w.append({"ev": "seq", "ts": int(max_ts), "txid": 0, "shards": [],
              "prev": {}})
    w.commit()
    w.sync()
    w.close()

    for m in old_members:
        m.close()
        if m.node.store.log is not None:
            m.node.store.log.close()
    for n in new_nodes:
        if n.store.log is not None:
            n.store.log.close()

    # ---- layout-epoch guard (r4 VERDICT item 7): stamp the new layout's
    # epoch into the new dirs and RETIRE the old ones — an old-dir member
    # booted after the resize would serve (and extend) a stale copy of
    # shards that now live elsewhere
    from antidote_tpu.log import mark_dir_retired, stamp_layout_epoch

    old_epoch = int((meta or {}).get("layout_epoch", 0))
    new_epoch = old_epoch + 1
    for d in new_dirs:
        stamp_layout_epoch(d, new_epoch)
    for d in old_dirs:
        mark_dir_retired(d, new_epoch)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="antidote_tpu.cluster.resize")
    ap.add_argument("--old-dirs", required=True,
                    help="comma-separated member log dirs (current layout)")
    ap.add_argument("--new-dirs", required=True,
                    help="comma-separated member log dirs (new layout; "
                         "must be empty)")
    ap.add_argument("--dc-id", type=int, default=0)
    args = ap.parse_args(argv)

    resize_dc(args.old_dirs.split(","), args.new_dirs.split(","),
              args.dc_id)
    print("resized; boot the new members with "
          "`python -m antidote_tpu.cluster.boot --members "
          f"{len(args.new_dirs.split(','))} --recover ...`")
    return 0


if __name__ == "__main__":
    sys.exit(main())
