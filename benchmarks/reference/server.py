"""The reference put in the program's place: `Model` behind a TCP port.

This is what a control run drives instead of `console serve`: the same
generators, fill, window and comparison, against a server that is plainly
right -- or, with `--break`, plainly breaks ONE guarantee that the
configurations state:

  lose_acked     every Nth acknowledged update transaction is not applied
                 (durability: "an acknowledged write is read back")
  txn_reads_head reads inside a transaction are served at the head
                 (snapshot isolation)
  stale_reads    static reads are served N commits behind the head
                 (a read sees every acknowledged commit)

With `--dcs N` it serves N data centres, one port each, as Cure
replicates them: each DC holds a `Model` of its own, applies its own
commits at once and every other DC's commits in that DC's commit order,
each after a delay of 5-50 ms drawn from `--seed`, once it has been told
of that DC (`connect_to_dcs`).  The geo breaks, which only this mode has:

  remote_lost     the last DC never applies every Nth remote commit of
                  one update (every DC converges to one end state)
  causal_reorder  the last DC holds every Nth remote commit of one update
                  back for a second while the origin's later commits
                  apply (causal delivery)
  home_stale      every Nth static read of one object is served before
                  its DC's own last commit to that key (a DC shows its
                  own acknowledged commits at once)

They strike the traffic's one-object requests only, so that the fill and
the harness's wide reads (its convergence barrier) are left whole.

The protocol is its own (length-prefixed JSON); `RefClient` has the call
surface of the program's `AntidoteClient` that the generators use.  Nothing
here imports antidote_tpu.
"""

from __future__ import annotations

import argparse
import json
import random
import socket
import socketserver
import struct
import sys
import threading
import time
from collections import deque

from benchmarks.reference.model import Model

BREAKS = ("lose_acked", "txn_reads_head", "stale_reads")
GEO_BREAKS = ("remote_lost", "causal_reorder", "home_stale")
HOLD_S = 1.0          # how long causal_reorder holds a commit back


def _send(sock, obj) -> None:
    data = json.dumps(obj, separators=(",", ":")).encode()
    sock.sendall(struct.pack(">I", len(data)) + data)


def _recv(rfile):
    head = rfile.read(4)
    if len(head) < 4:
        raise ConnectionError("closed")
    (n,) = struct.unpack(">I", head)
    return json.loads(rfile.read(n))


class _State:
    def __init__(self, broken: str | None, every: int):
        self.model = Model()
        self.lock = threading.Lock()
        self.broken, self.every = broken, every
        self.n_updates = 0
        self.txns: dict = {}
        self.next_txid = 1

    def handle(self, req):
        op = req["op"]
        with self.lock:
            m = self.model
            if op == "update":
                self.n_updates += 1
                if (self.broken == "lose_acked"
                        and self.n_updates % self.every == 0):
                    return {"clock": m.commit_no}      # acked, not applied
                ups = [(k, t, b, (o, a)) for k, t, b, (o, a) in req["updates"]]
                return {"clock": m.apply(ups)}
            if op == "read":
                at = None
                if self.broken == "stale_reads":
                    at = max(0, m.commit_no - self.every)
                return {"values": [m.value(tuple(o), at)
                                   for o in req["objects"]],
                        "clock": m.commit_no}
            if op == "start":
                txid = self.next_txid
                self.next_txid += 1
                self.txns[txid] = m.commit_no
                return {"txid": txid}
            if op == "txn_read":
                at = self.txns[req["txid"]]
                if self.broken == "txn_reads_head":
                    at = None
                return {"values": [m.value(tuple(o), at)
                                   for o in req["objects"]]}
            if op == "commit":
                self.txns.pop(req["txid"], None)
                return {"clock": m.commit_no}
            if op == "status":
                return {"status": {"reference": True, "broken": self.broken,
                                   "commit_no": m.commit_no}}
        raise ValueError(f"unknown request {op!r}")


class _Geo:
    """N DCs, one `Model` each; remote commits apply in origin order after
    a seeded delay.  `handle(dc, req)` answers a request sent to DC dc."""

    def __init__(self, n: int, broken: str | None, every: int, seed: int):
        self.n, self.broken, self.every = n, broken, every
        self.models = [Model() for _ in range(n)]
        # dc -> key -> commit numbers (in that DC's model) of its own commits
        self.own = [dict() for _ in range(n)]
        self.subs = [set() for _ in range(n)]   # origin -> DCs told of it
        self.queues: dict = {}                  # (origin, dc) -> deque
        self.held: list = []                    # causal_reorder's
        self.n_struck = 0      # remote one-update commits the last DC took
        self.n_reads = [0] * n
        self.rng = random.Random(seed)
        self.cv = threading.Condition()
        self.txns: dict = {}
        self.next_txid = 1
        threading.Thread(target=self._deliver, daemon=True).start()

    def _apply(self, dc, ups):
        return self.models[dc].apply(
            [(k, t, b, (o, a)) for k, t, b, (o, a) in ups])

    def _deliver(self):
        """Apply every remote commit that is due, in origin order."""
        with self.cv:
            while True:
                now = time.monotonic()
                due = [q for q in self.queues.values() if q and q[0][0] <= now]
                for q in due:
                    while q and q[0][0] <= now:
                        _t, dc, ups = q.popleft()
                        self._remote(dc, ups)
                for h in [h for h in self.held if h[0] <= now]:
                    self.held.remove(h)
                    self._apply(h[1], h[2])
                heads = [q[0][0] for q in self.queues.values() if q]
                heads += [h[0] for h in self.held]
                self.cv.wait(max(0.0, min(heads) - now) if heads else None)

    def _remote(self, dc, ups):
        if (self.broken in ("remote_lost", "causal_reorder")
                and dc == self.n - 1 and len(ups) == 1):
            self.n_struck += 1
            if self.n_struck % self.every == 0:
                if self.broken == "causal_reorder":
                    self.held.append((time.monotonic() + HOLD_S, dc, ups))
                return
        self._apply(dc, ups)

    def _commit(self, dc, ups):
        no = self._apply(dc, ups)
        for k, *_ in ups:
            self.own[dc].setdefault(k, []).append(no)
        now = time.monotonic()
        for to in self.subs[dc]:
            q = self.queues.setdefault((dc, to), deque())
            at = now + self.rng.uniform(0.005, 0.05)
            q.append((max(at, q[-1][0]) if q else at, to, ups))
        self.cv.notify()
        return no

    def _read_at(self, dc, objects):
        """The snapshot a static read is served at (None: the head)."""
        if self.broken != "home_stale" or len(objects) != 1:
            return None
        self.n_reads[dc] += 1
        own = self.own[dc].get(objects[0][0])
        if own and self.n_reads[dc] % self.every == 0:
            return own[-1] - 1
        return None

    def handle(self, dc, req):
        op = req["op"]
        with self.cv:
            m = self.models[dc]
            if op == "update":
                return {"clock": self._commit(dc, req["updates"])}
            if op == "read":
                at = self._read_at(dc, req["objects"])
                return {"values": [m.value(tuple(o), at)
                                   for o in req["objects"]],
                        "clock": m.commit_no}
            if op == "start":
                txid = self.next_txid
                self.next_txid += 1
                self.txns[txid] = (dc, m.commit_no)
                return {"txid": txid}
            if op == "txn_read":
                tdc, at = self.txns[req["txid"]]
                return {"values": [self.models[tdc].value(tuple(o), at)
                                   for o in req["objects"]]}
            if op == "commit":
                self.txns.pop(req["txid"], None)
                return {"clock": m.commit_no}
            if op == "descriptor":
                return {"descriptor": {"dc_id": dc}}
            if op == "connect":
                for d in req["descriptors"]:
                    self.subs[int(d["dc_id"])].add(dc)
                return {"ok": True}
            if op == "status":
                return {"status": {"reference": True, "broken": self.broken,
                                   "dc_id": dc, "commit_no": m.commit_no}}
        raise ValueError(f"unknown request {op!r}")


class _Handler(socketserver.StreamRequestHandler):
    def handle(self):
        self.request.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        srv = self.server
        try:
            while True:
                req = _recv(self.rfile)
                _send(self.request, srv.state.handle(req) if srv.dc is None
                      else srv.state.handle(srv.dc, req))
        except (ConnectionError, OSError):
            pass


class _Server(socketserver.ThreadingTCPServer):
    daemon_threads = True
    allow_reuse_address = True
    request_queue_size = 256
    dc = None                  # the DC a port serves under --dcs


class RefTxn:
    def __init__(self, client, txid):
        self._c, self._txid = client, txid

    def read_objects(self, objects):
        return self._c._call({"op": "txn_read", "txid": self._txid,
                              "objects": list(objects)})["values"]

    def commit(self):
        return self._c._call({"op": "commit", "txid": self._txid})["clock"]


class RefClient:
    def __init__(self, host, port, timeout=30.0):
        self._sock = socket.create_connection((host, port), timeout=timeout)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._rfile = self._sock.makefile("rb")

    def _call(self, req):
        _send(self._sock, req)
        return _recv(self._rfile)

    def update_objects(self, updates):
        return self._call({"op": "update", "updates": list(updates)})["clock"]

    def read_objects(self, objects):
        r = self._call({"op": "read", "objects": list(objects)})
        return r["values"], r["clock"]

    def start_transaction(self):
        return RefTxn(self, self._call({"op": "start"})["txid"])

    def node_status(self):
        return self._call({"op": "status"})["status"]

    def get_connection_descriptor(self):
        return self._call({"op": "descriptor"})["descriptor"]

    def connect_to_dcs(self, descriptors):
        self._call({"op": "connect", "descriptors": list(descriptors)})

    def close(self):
        self._rfile.close()
        self._sock.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--break", dest="broken", choices=BREAKS + GEO_BREAKS,
                    default=None)
    ap.add_argument("--every", type=int, default=50)
    ap.add_argument("--dcs", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if (args.broken in GEO_BREAKS and args.dcs == 1
            or args.broken in BREAKS and args.dcs > 1):
        ap.error(f"--break {args.broken} with --dcs {args.dcs}: the geo "
                 "breaks need --dcs > 1, the others one DC")
    if args.dcs == 1:
        srv = _Server(("127.0.0.1", 0), _Handler)
        srv.state = _State(args.broken, args.every)
        servers = [srv]
        extra = {}
    else:
        geo = _Geo(args.dcs, args.broken, args.every, args.seed)
        servers = []
        for dc in range(args.dcs):
            srv = _Server(("127.0.0.1", 0), _Handler)
            srv.state, srv.dc = geo, dc
            servers.append(srv)
        extra = {"dcs": [{"dc_id": dc, "port": s.server_address[1],
                          "device": {}} for dc, s in enumerate(servers)]}
    for srv in servers[1:]:
        threading.Thread(target=srv.serve_forever, daemon=True).start()
    print(json.dumps({"host": "127.0.0.1",
                      "port": servers[0].server_address[1],
                      "ready": True, "reference": True,
                      "broken": args.broken, **extra}), flush=True)
    try:
        servers[0].serve_forever()
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
