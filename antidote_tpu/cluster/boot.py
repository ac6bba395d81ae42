"""Cluster member process entrypoint.

    python -m antidote_tpu.cluster.boot --dc-id 0 --member 1 --members 2 \
        --shards 4 --max-dcs 3 [--log-dir DIR]

Prints one JSON line with the process' ports:
    {"rpc": [h, p], "client": [h, p], "fabric": [h, p], "fabric_id": N}

then serves until killed.  A controller (the CT-style test harness, or an
operator script) wires the topology afterwards through the control RPC:

    ctl_wire(peers, remotes, members_by_dc)
        peers          {member_id: [host, port]}      intra-DC RPC
        remotes        {fabric_id: [host, port]}      inter-DC endpoints
        members_by_dc  {dc_id: n_members}             catch-up routing

— the two-phase bring-up of the reference's CT utilities (boot nodes,
then exchange descriptors and observe_dcs_sync,
/root/reference/test/utils/test_utils.erl:110-165,426-451).
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="antidote_tpu.cluster.boot")
    ap.add_argument("--dc-id", type=int, required=True)
    ap.add_argument("--member", type=int, default=0)
    ap.add_argument("--members", type=int, default=1)
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--max-dcs", type=int, default=4)
    ap.add_argument("--log-dir", default=None)
    ap.add_argument("--recover", action="store_true",
                    help="rejoin: replay the WAL + prepare log")
    ap.add_argument("--joining", action="store_true",
                    help="boot OWNING NOTHING: the live-join protocol "
                         "(cluster.join.live_join) streams this member's "
                         "shard share over while the cluster serves")
    args = ap.parse_args(argv)

    from antidote_tpu.config import enable_compilation_cache

    enable_compilation_cache()

    from antidote_tpu.cluster import (ClusterMember, ClusterNode,
                                      attach_interdc, cluster_query_router)
    from antidote_tpu.config import AntidoteConfig
    from antidote_tpu.interdc.tcp import TcpFabric
    from antidote_tpu.proto.server import ProtocolServer

    cfg = AntidoteConfig(n_shards=args.shards, max_dcs=args.max_dcs)
    member = ClusterMember(cfg, dc_id=args.dc_id, member_id=args.member,
                           n_members=args.members, log_dir=args.log_dir,
                           recover=args.recover,
                           shards=[] if args.joining else None)
    fabric = TcpFabric()
    replica = attach_interdc(member, fabric)
    node = ClusterNode(member)
    # interdc=replica: this member's wire server answers
    # GET_CONNECTION_DESCRIPTOR (and replica-status), so followers can
    # learn the fleet's endpoints member by member (ISSUE 11)
    server = ProtocolServer(node, port=0, interdc=replica)

    subscribed = set()

    def ctl_wire(peers, remotes, members_by_dc) -> bool:
        for mid, (h, p) in peers.items():
            mid = int(mid)
            if mid != member.member_id:
                member.connect(mid, h, int(p))
        for fid, (h, p) in remotes.items():
            fabric.connect_remote(int(fid), h, int(p))
        replica.route_query = cluster_query_router(
            {int(k): int(v) for k, v in members_by_dc.items()}, cfg.n_shards
        )
        for fid in remotes:
            fid = int(fid)
            if (fid != replica.fabric_id and (fid & 0xFFFF) != member.dc_id
                    and fid not in subscribed):
                # incremental re-wires (a joiner appearing mid-life) must
                # not stack duplicate subscription streams
                fabric.subscribe(replica.fabric_id, fid, replica._on_message)
                subscribed.add(fid)
        # background pump: deliver the inter-DC stream + flush
        # heartbeats.  Supervised (5-in-10s, like console serve): a
        # crashed drain loop restarts loudly instead of silently
        # freezing geo-replication for this member
        from antidote_tpu.supervise import Supervisor, ThreadLoop

        old = getattr(fabric, "_pump_sup", None)
        if old is not None:  # re-wire: replace, don't stack pump loops
            old.shutdown()
        sup = Supervisor()
        sup.add(
            "interdc-pump",
            start=lambda: ThreadLoop(
                lambda: fabric.pump(timeout=0.2), interval_s=0.01,
                name="interdc-pump").start(),
            alive=lambda lp: lp.is_alive(),
            stop=lambda lp: lp.stop(),
        )
        # stable-time gossip on a timer (the meta_data_sender role,
        # /root/reference/src/meta_data_sender.erl:224-255 — its cadence
        # is 1 s; ours is 100 ms so read snapshots lag peers less on
        # small clusters): without it,
        # the aggregated stable snapshot stalls after a live shard move
        # — the relinquished source's rows zero out and only a FRESH
        # peer-row pull covers the shard from its new owner, but plain
        # (unpinned) reads never spin on the clock and so never pulled
        sup.add(
            "clock-gossip",
            start=lambda: ThreadLoop(
                member.refresh_peer_clocks, interval_s=0.1,
                name="clock-gossip").start(),
            alive=lambda lp: lp.is_alive(),
            stop=lambda lp: lp.stop(),
        )
        sup.start()
        fabric._pump_sup = sup
        return True

    member.rpc.register("ctl_wire", ctl_wire)
    # takeover/test controls (the CT suite's fault-injection seams)
    member.rpc.register("ctl_failpoint",
                        lambda name: setattr(node, "failpoint", name) or True)
    member.rpc.register("ctl_resolve",
                        lambda grace=0.0: member.resolve_wedged(grace))
    # membership/ops surface for console.py (ringready/cluster-status/
    # cluster-sweep — antidote_console.erl parity)
    member.rpc.register("ctl_sweep",
                        lambda grace=30.0: member.sweep_stale_prepared(grace))
    member.rpc.register("ctl_ready_all",
                        lambda: {str(k): bool(v)
                                 for k, v in node.check_ready().items()})
    member.rpc.register("ctl_status", lambda: node.status(include_ready=True))

    def ctl_repl_status():
        """Geo-replication introspection: per-chain positions, learned
        ownership routes, and the raw shard clock matrix — what an
        operator (or a membership test) reads to see WHERE a stalled
        chain is stuck."""
        vc = member.node.store.applied_vc
        # snapshot under the ingress-state lock: the pump thread inserts
        # into these dicts under it, and even a bare dict() copy can
        # raise on a concurrent resize
        with member.node.txm.commit_lock:
            last_seen = dict(replica.last_seen)
            shard_route = dict(replica.shard_route)
        return {
            "owned": sorted(int(s) for s in member.shards),
            "pub_opid": [int(x) for x in replica.pub_opid],
            "last_seen": {f"{o}:{s}": int(v)
                          for (o, s), v in last_seen.items()},
            "shard_route": {f"{o}:{s}": [int(mm), int(e)]
                            for (o, s), (mm, e) in shard_route.items()},
            "applied_vc": [[int(x) for x in row] for row in vc],
            "stable_vc": [int(x) for x in member.stable_vc()],
        }

    member.rpc.register("ctl_repl_status", ctl_repl_status)

    print(json.dumps({
        "rpc": list(member.address),
        "client": [server.host, server.port],
        "fabric": list(fabric.address_of(replica.fabric_id)),
        "fabric_id": replica.fabric_id,
    }), flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
