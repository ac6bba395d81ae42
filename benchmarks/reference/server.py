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

The protocol is its own (length-prefixed JSON); `RefClient` has the call
surface of the program's `AntidoteClient` that the generators use.  Nothing
here imports antidote_tpu.
"""

from __future__ import annotations

import argparse
import json
import socket
import socketserver
import struct
import sys
import threading

from benchmarks.reference.model import Model

BREAKS = ("lose_acked", "txn_reads_head", "stale_reads")


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


class _Handler(socketserver.StreamRequestHandler):
    def handle(self):
        self.request.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        try:
            while True:
                _send(self.request, self.server.state.handle(
                    _recv(self.rfile)))
        except (ConnectionError, OSError):
            pass


class _Server(socketserver.ThreadingTCPServer):
    daemon_threads = True
    allow_reuse_address = True
    request_queue_size = 256


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

    def close(self):
        self._rfile.close()
        self._sock.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--break", dest="broken", choices=BREAKS, default=None)
    ap.add_argument("--every", type=int, default=50)
    args = ap.parse_args(argv)
    srv = _Server(("127.0.0.1", 0), _Handler)
    srv.state = _State(args.broken, args.every)
    print(json.dumps({"host": "127.0.0.1", "port": srv.server_address[1],
                      "ready": True, "reference": True,
                      "broken": args.broken}), flush=True)
    try:
        srv.serve_forever()
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
