"""One generator process: some of the cell's clients, each a closed loop on
its own connection, driven by one traffic-mix file.

    python -m benchmarks.loadgen          (spec as one JSON line on stdin)

It connects, prints READY, and reads two more JSON lines, each [ramp,
start, end] in time.monotonic() seconds (which all processes of a machine
share): the first starts the ramp with start and end still null, the
second fixes the window.  Then it writes its log as a pickle and exits.  Every update is logged
from the first one -- the comparison needs the whole history -- and every
read that is sent inside the window is logged with its answer.

Under geo-replication the spec has `ports`, one a DC: client c is homed at
DC c mod N, sends every request there, and the log tags each of its
updates and reads with that DC.

The process never touches JAX.
"""

from __future__ import annotations

import bisect
import json
import os
import pickle
import random
import sys
import threading
import time

from benchmarks import data
from benchmarks.clients import connect, refused

BLOCK = 100   # requests per reshuffled block: every seed sends the same mix


def atoms(mix: dict) -> list:
    """The mix as BLOCK request atoms (kind, op), by largest remainder."""
    weighted = []
    for k in mix["kinds"]:
        ops = k.get("ops") or [{"op": None, "share": 1.0}]
        for o in ops:
            weighted.append(((k["kind"], o["op"]),
                             k["share"] * o["share"] * BLOCK))
    counts = [int(w) for _, w in weighted]
    by_rest = sorted(range(len(weighted)),
                     key=lambda i: weighted[i][1] - counts[i], reverse=True)
    for i in by_rest[:BLOCK - sum(counts)]:
        counts[i] += 1
    out = []
    for (atom, _), n in zip(weighted, counts):
        out.extend([atom] * n)
    return out


class KeyDraw:
    def __init__(self, spec: dict, n: int, order):
        self.n, self.order = n, order
        self.cdf = None
        if spec["distribution"] == "zipf":
            s, acc, cdf = spec["s"], 0.0, []
            for r in range(1, n + 1):
                acc += 1.0 / r ** s
                cdf.append(acc)
            self.cdf = [c / acc for c in cdf]
        elif spec["distribution"] != "uniform":
            raise ValueError(f"unknown distribution {spec['distribution']!r}")

    def __call__(self, rng) -> int:
        if self.cdf is None:
            return self.order(rng.randrange(self.n))
        return self.order(min(bisect.bisect_left(self.cdf, rng.random()),
                              self.n - 1))


class Client(threading.Thread):
    def __init__(self, spec, cid, draw, times):
        super().__init__(daemon=True)
        self.spec, self.cid, self.draw, self.times = spec, cid, draw, times
        self.rng = random.Random(data.mix(spec["seed"], cid, 7))
        self.fill = spec["fill"]
        self.updates, self.reads = [], []
        self.never = 0
        self.error = None
        ports = spec.get("ports") or [spec["port"]]
        self.dc = cid % len(ports)             # the client's home DC
        self.conn = connect(spec["client"], spec["host"], ports[self.dc],
                            spec["timeout"], spec.get("fault"),
                            spec.get("fault_every", 40))
        self.txn = None
        self.txn_at = (0.0, 0.0)
        self.own = []          # (key index, element) added here, acked
        self.seq = 0
        kinds = {k["kind"]: k for k in spec["mix"]["kinds"]}
        self.renew_s = kinds.get("txn_read", {}).get("renew_seconds", 2.0)

    # -- one request of each kind -----------------------------------------
    def _update(self, atom_op, rec):
        f = self.fill
        if atom_op == "remove_own" and self.own:
            i, elem = self.own.pop(self.rng.randrange(len(self.own)))
            op = ("remove", elem)
        elif atom_op == "increment":
            i, op = self.draw(self.rng), ("increment", 1)
        else:
            i = self.draw(self.rng)
            self.seq += 1
            op = ("add", f"c{self.cid}:{self.seq}")
        t0 = time.monotonic()
        try:
            self.conn.update_objects([(data.key_name(f, i), f["type"],
                                       f["bucket"], op)])
            t1 = time.monotonic()
        except Exception as e:
            self.updates.append((i, op[0], op[1], t0, None, rec))
            self._failed(e)
            return
        self.updates.append((i, op[0], op[1], t0, t1, rec))
        if op[0] == "add":
            self.own.append((i, op[1]))

    def _read(self, in_txn, rec):
        i = self.draw(self.rng)
        o = [data.obj(self.fill, i)]
        if in_txn and (self.txn is None or time.monotonic()
                       - self.txn_at[1] >= self.renew_s):
            self._renew()
        t0 = time.monotonic()
        try:
            val = (self.txn.read_objects(o)[0] if in_txn
                   else self.conn.read_objects(o)[0][0])
            t1 = time.monotonic()
        except Exception as e:
            if in_txn:
                self.txn = None
            if rec:
                self.reads.append((i, in_txn, t0, None, 0.0, 0.0, None))
            self._failed(e)
            return
        if rec:
            # the snapshot's instants: the transaction's start, or the read's
            snap = self.txn_at if in_txn else (t0, t1)
            if isinstance(val, list):
                val = tuple(val)
            self.reads.append((i, in_txn, t0, t1, snap[0], snap[1], val))

    def _renew(self):
        if self.txn is not None:
            self.txn.commit()
        t0 = time.monotonic()
        self.txn = self.conn.start_transaction()
        self.txn_at = (t0, time.monotonic())

    def _failed(self, e):
        if refused(e):
            time.sleep(min(getattr(e, "retry_after_ms", 25), 100) / 1e3)
            return
        self.never += 1        # no answer: the connection is of no use now
        raise e

    def run(self):
        times, block = self.times, []
        try:
            while True:
                now = time.monotonic()
                t_ramp, t_start, t_end = times
                if t_end is not None and now >= t_end:
                    break
                if now < t_ramp:
                    time.sleep(min(t_ramp - now, 0.05))
                    continue
                if not block:
                    block = list(self.spec["atoms"])
                    self.rng.shuffle(block)
                kind, op = block.pop()
                rec = t_start is not None and now >= t_start
                if kind == "static_update":
                    self._update(op, rec)
                else:
                    self._read(kind == "txn_read", rec)
            if self.txn is not None:
                self.txn.commit()
        except Exception as e:  # noqa: BLE001 - reported to the parent
            self.error = f"client {self.cid}: {type(e).__name__}: {e}"
        finally:
            try:
                self.conn.close()
            except OSError:
                pass


def main() -> int:
    # one process per chip: a generator can never initialise a backend
    os.environ["JAX_PLATFORMS"] = "benchmark_generators_never_initialise_jax"
    spec = json.loads(sys.stdin.readline())
    spec["atoms"] = [tuple(a) for a in atoms(spec["mix"])]
    fill = spec["fill"]
    order = data.KeyOrder(spec["seed"], fill["fill_keys"])
    draw = KeyDraw(spec["mix"]["keys"], fill["fill_keys"], order)
    times = [float("inf"), None, None]
    clients = [Client(spec, cid, draw, times) for cid in spec["client_ids"]]
    print("READY", flush=True)
    times[:] = json.loads(sys.stdin.readline())      # the ramp begins
    cpu0 = time.process_time()
    for c in clients:
        c.start()
    times[:] = json.loads(sys.stdin.readline())      # the window is fixed
    while time.monotonic() < times[1]:
        time.sleep(0.01)
    cpu1 = time.process_time()
    while time.monotonic() < times[2]:
        time.sleep(0.01)
    cpu2 = time.process_time()
    for c in clients:
        c.join(timeout=spec["timeout"] + 30)
    hung = sum(c.is_alive() for c in clients)
    out = {
        "updates": [u + (c.cid, c.dc) for c in clients for u in c.updates],
        "reads": [r + (c.dc,) for c in clients for r in c.reads],
        "never": sum(c.never for c in clients) + hung,
        "errors": [c.error for c in clients if c.error],
        "cpu_busy_window": (cpu2 - cpu1) / (times[2] - times[1]),
        "cpu_busy_ramp": (cpu1 - cpu0) / max(times[1] - times[0], 1e-9),
    }
    with open(spec["out"], "wb") as f:
        pickle.dump(out, f, protocol=pickle.HIGHEST_PROTOCOL)
    print("DONE", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
