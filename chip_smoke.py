#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the serving path starts, answers
correctly and survives a kill on the TPU.

    python chip_smoke.py             # one chip (what the driver runs)
    python chip_smoke.py --chips 4   # only the mesh serving plane, 4 chips

It boots a real `console serve --pallas --sync-log` with tables allocated
for 16 x 65,536 = 1,048,576 set_aw rows (BASELINE.json config 3 at `console
serve`'s default widths), fills them over the wire with `AntidoteClient`
batches made from --seed for as long as --fill-seconds allows, and compares
a few queries with a plain host model (dicts of Python sets and ints fed
the same acknowledged ops; no code shared with antidote_tpu):

  (i)   static reads at the head,
  (ii)  the same keys inside a transaction whose snapshot predates later
        writes, so the versioned ring fold runs on the device,
  (iii) read-your-writes inside a transaction,
  (iv)  SIGKILL, respawn on the same --log-dir, read everything back.

It also checks that the Pallas kernels took the folds (dispatch tallies),
that nothing fell back to the serial fold, and — with --chips 4 — that the
tables really lie over four devices.

One process per chip: only the server child initialises a JAX backend.
This process and its client never do (JAX_PLATFORMS is poisoned here so
that an accidental jnp op fails loudly instead of taking the chip from
the server); the device is learned from the server's ready line.

The last stdout line is one JSON object, {"ok": ..., "device": {...}};
everything else is on earlier lines.  Any failed phase, error reply or
mismatch, or a device that is not a TPU, exits non-zero with "ok": false.
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import random
import shutil
import signal
import subprocess
import sys
import threading
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")
BUCKET = "smoke"
SHARDS = 16
N_QUERY_SETS, N_QUERY_COUNTERS, N_COUNTERS = 3072, 1024, 4096
FILL_BATCH = 1024          # new set keys per fill request
COUNTERS_PER_BATCH = 16

# the server child gets the environment as it was handed to this script
SERVER_ENV = dict(os.environ)
# ... and this process can never initialise a backend (see module doc)
os.environ["JAX_PLATFORMS"] = "chip_smoke_parent_never_initialises_jax"

_T0 = time.monotonic()


def say(msg: str) -> None:
    print(f"[{time.monotonic() - _T0:7.1f}s] {msg}", flush=True)


class Failed(Exception):
    """A phase did not hold; the message says which and why."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Failed(what)


# ---------------------------------------------------------------------------
# the server child
# ---------------------------------------------------------------------------
class Server:
    def __init__(self, args, boot_no: int):
        self.stderr_path = os.path.join(OUT, f"server-{boot_no}.stderr.log")
        cmd = [
            sys.executable, "-m", "antidote_tpu.console", "serve",
            "--pallas", "--sync-log", "--log-dir", os.path.join(OUT, "wal"),
            "--shards", str(SHARDS),
            "--keys-per-table", str(args.keys_per_table), "--port", "0",
        ]
        if args.chips == 4:
            cmd += ["--mesh-devices", "4"]
        env = dict(SERVER_ENV)
        if args.rehearse:
            # off-TPU rehearsal only: the kernels under the interpreter
            env["ANTIDOTE_PALLAS_INTERPRET"] = "1"
        say("spawn: " + " ".join(cmd[1:]))
        t0 = time.monotonic()
        self._stderr = open(self.stderr_path, "wb")
        self.proc = subprocess.Popen(cmd, cwd=ROOT, env=env,
                                     stdout=subprocess.PIPE,
                                     stderr=self._stderr)
        lines: "queue.Queue[bytes]" = queue.Queue()
        threading.Thread(
            target=lambda: [lines.put(l) for l in self.proc.stdout],
            daemon=True).start()
        deadline = t0 + args.boot_timeout
        self.ready = None
        while self.ready is None:
            try:
                line = lines.get(timeout=1.0)
            except queue.Empty:
                check(self.proc.poll() is None,
                      f"server exited with {self.proc.returncode} before "
                      "its ready line")
                check(time.monotonic() < deadline,
                      f"no ready line within {args.boot_timeout}s")
                continue
            if line.lstrip().startswith(b"{"):
                self.ready = json.loads(line)
        self.boot_s = time.monotonic() - t0
        self.port = int(self.ready["port"])

    def stderr_tail(self, n: int = 4000) -> str:
        with open(self.stderr_path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")

    def kill(self, sig=signal.SIGKILL) -> None:
        """Signal the child and wait until it has really exited — the chip
        is free for the next process only then."""
        if self.proc.poll() is None:
            self.proc.send_signal(sig)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._stderr.close()


# ---------------------------------------------------------------------------
# data from the seed, and the plain host model
# ---------------------------------------------------------------------------
def set_key(i: int) -> str:
    return f"k{i:07d}"


def counter_key(i: int) -> str:
    return f"c{i:05d}"


class Model:
    """Add-wins sets and integer counters, fed the acknowledged ops."""

    def __init__(self):
        self.sets: dict = {}
        self.counters: dict = {}

    def apply(self, updates) -> None:
        for key, ty, _bucket, (op, arg) in updates:
            if ty == "counter_pn":
                self.counters[key] = self.counters.get(key, 0) + (
                    arg if op == "increment" else -arg)
            elif op == "add_all":
                self.sets.setdefault(key, set()).update(arg)
            elif op == "add":
                self.sets.setdefault(key, set()).add(arg)
            elif op == "remove":
                self.sets.setdefault(key, set()).discard(arg)
            else:
                raise AssertionError(op)

    def value(self, obj):
        key, ty, _bucket = obj
        if ty == "counter_pn":
            return self.counters.get(key, 0)
        return sorted(self.sets.get(key, ()))

    def values(self, objs):
        return [self.value(o) for o in objs]


def same(got, want) -> bool:
    # the wire returns a set as a list in the server's own order
    return sorted(got) == want if isinstance(want, list) else got == want


def compare(phase: str, objs, got, want) -> None:
    bad = [(o[0], g, w) for o, g, w in zip(objs, got, want)
           if not same(g, w)]
    check(len(got) == len(want) and not bad,
          f"{phase}: {len(bad)} of {len(objs)} answers differ from the host "
          f"model, first {bad[:3]}")
    say(f"{phase}: {len(objs)} answers equal the host model")


# ---------------------------------------------------------------------------
def run(args, result: dict) -> None:
    from antidote_tpu.proto.client import AntidoteClient

    rng = random.Random(args.seed)
    capacity = SHARDS * args.keys_per_table
    # key -> shard is a hash: stop short of capacity so that no shard's
    # table has to grow (a doubling reallocates it and recompiles)
    target = capacity * 15 // 16
    model = Model()

    def connect(srv):
        return AntidoteClient("127.0.0.1", srv.port, timeout=args.rpc_timeout)

    def device_of(srv):
        check("device" in srv.ready, "the ready line names no device")
        dev = srv.ready["device"]
        say(f"server ready in {srv.boot_s:.1f}s on {dev}")
        return dev

    def write(c, updates):
        clock = c.update_objects(updates)   # raises on any error reply
        model.apply(updates)                # acknowledged: now it counts
        return clock

    # ---- cold boot ------------------------------------------------------
    srv = Server(args, 1)
    result["servers"].append(srv)
    dev = result["device"] = device_of(srv)
    if dev["platform"] != "tpu":
        check(args.rehearse, f"the server runs on {dev['platform']}, not a "
                             "TPU: nothing to prove here")
        say("REHEARSAL off-TPU: phases run, the result stays ok=false")
    check(dev["count"] == args.chips,
          f"{dev['count']} devices where --chips {args.chips} was asked")
    cold_boot_s = srv.boot_s
    c = connect(srv)

    # ---- fill over the wire ----------------------------------------------
    t_fill = time.monotonic()
    n_keys, n_ops, clock = 0, 0, None
    while n_keys < target and time.monotonic() - t_fill < args.fill_seconds:
        hi = min(n_keys + FILL_BATCH, target)
        adds = [
            (set_key(i), "set_aw", BUCKET,
             ("add_all", [f"{i}:{rng.randrange(1 << 30)}" for _ in range(3)]))
            for i in range(n_keys, hi)
        ]
        incs = [
            (counter_key(rng.randrange(N_COUNTERS)), "counter_pn", BUCKET,
             ("increment", rng.randrange(1, 100)))
            for _ in range(COUNTERS_PER_BATCH)
        ]
        write(c, adds + incs)
        # removes on a tenth of the keys, as their own later transaction
        rms = [
            (k, "set_aw", BUCKET, ("remove", min(model.sets[k])))
            for k, _, _, _ in adds[::10]
        ]
        clock = write(c, rms)
        n_ops += 3 * len(adds) + len(incs) + len(rms)
        if n_keys == 0:
            say(f"first fill batch acknowledged after "
                f"{time.monotonic() - t_fill:.1f}s (compiles the write path)")
        n_keys = hi
    fill_s = time.monotonic() - t_fill
    why = ("the target was reached" if n_keys >= target else
           f"--fill-seconds {args.fill_seconds:g} ran out")
    say(f"filled {n_keys} set_aw keys of {capacity} allocated rows "
        f"(target {target}) + {len(model.counters)} counter_pn keys, "
        f"{n_ops} ops in {fill_s:.1f}s = {n_keys / fill_s:.0f} keys/s over "
        f"the wire; stopped because {why}")
    check(n_keys >= N_QUERY_SETS, "the fill did not reach the query size")

    st = c.node_status()
    tab = st["tables"]
    say("tables: " + ", ".join(
        f"{t} {SHARDS}x{v['n_rows']} rows ({v['rows_used']} used, "
        f"{sum(v['device_bytes'].values()) / 2**30:.2f} GiB on device)"
        for t, v in tab.items()))
    check(tab["set_aw"]["n_rows"] * SHARDS == capacity,
          f"set_aw is not allocated for {capacity} rows: {tab['set_aw']}")
    say(f"device memory: in use {st['device']['bytes_in_use']}, peak "
        f"{st['device']['peak_bytes_in_use']}, limit "
        f"{st['device']['bytes_limit']} bytes")
    say(f"native planes (None = loaded): {st['native']}")
    for plane, why_not in st["native"].items():
        if why_not is not None:
            say(f"NATIVE PLANE NOT LOADED, its Python plane serves: "
                f"{plane}: {why_not}")

    # ---- the query set, and a snapshot that will become history ----------
    q_sets = rng.sample(range(n_keys), N_QUERY_SETS)
    q_cnts = rng.sample(sorted(model.counters),
                        min(N_QUERY_COUNTERS, len(model.counters)))
    objs = ([(set_key(i), "set_aw", BUCKET) for i in q_sets]
            + [(k, "counter_pn", BUCKET) for k in q_cnts])
    c_hist = connect(srv)
    t_hist = c_hist.start_transaction(clock=clock)
    at_snapshot = model.values(objs)
    # later writes to every queried key: the snapshot is now history
    late = []
    for n, (key, ty, _b) in enumerate(objs):
        if ty == "counter_pn":
            late.append((key, ty, BUCKET, ("increment", 1000 + n)))
        elif n % 2 and model.sets[key]:
            late.append((key, ty, BUCKET, ("remove", max(model.sets[key]))))
        else:
            late.append((key, ty, BUCKET, ("add", f"late:{n}")))
    for lo in range(0, len(late), FILL_BATCH):
        clock = write(c, late[lo:lo + FILL_BATCH])
    check(model.values(objs) != at_snapshot, "the late writes changed nothing")

    # ---- (i) head reads ----------------------------------------------------
    t = time.monotonic()
    got, _ = c.read_objects(objs, clock=clock)
    say(f"(i) first head read took {time.monotonic() - t:.1f}s "
        "(compiles the gather)")
    compare("(i) static reads at the head", objs, got, model.values(objs))

    # ---- (ii) reads at the earlier snapshot: the ring fold -----------------
    before = st["pipeline"]["materializer"]
    t = time.monotonic()
    got = t_hist.read_objects(objs)
    say(f"(ii) first read at the earlier snapshot took "
        f"{time.monotonic() - t:.1f}s (compiles the fold kernels)")
    compare("(ii) reads at the earlier snapshot", objs, got, at_snapshot)
    t_hist.commit()
    c_hist.close()
    mat = c.node_status()["pipeline"]["materializer"]
    say(f"fold dispatch before (ii): {before['serving_folds']}, after: "
        f"{mat['serving_folds']}; replay folds {mat['replay_folds']}")
    check(mat["use_pallas"], "the server does not run with use_pallas")
    for strategy in ("pallas_set_aw", "pallas_counter"):
        check(mat["serving_folds"].get(strategy, 0)
              > before["serving_folds"].get(strategy, 0),
              f"no {strategy} fold was dispatched by (ii): {mat}")
    check(not mat["serving_folds"].get("serial")
          and not mat["serving_folds"].get("assoc")
          and not any(mat["replay_folds"].values()),
          f"a fold left the kernels: {mat}")

    # ---- (iii) read-your-writes inside a transaction -----------------------
    ryw_objs = objs[:32] + objs[-32:]
    ryw = [
        (k, ty, BUCKET,
         ("increment", 7) if ty == "counter_pn" else ("add", f"ryw:{k}"))
        for k, ty, _b in ryw_objs
    ]
    txn = c.start_transaction(clock=clock)
    txn.update_objects(ryw)
    got = txn.read_objects(ryw_objs)
    pending = Model()
    pending.sets = {k: set(model.sets.get(k, ())) for k, _, _ in ryw_objs}
    pending.counters = {k: model.counters.get(k, 0) for k, _, _ in ryw_objs}
    pending.apply(ryw)
    compare("(iii) read-your-writes inside the transaction", ryw_objs, got,
            pending.values(ryw_objs))
    clock = txn.commit()
    model.apply(ryw)
    got, _ = c.read_objects(objs, clock=clock)
    compare("(iii) static reads after its commit", objs, got,
            model.values(objs))

    # a wider sample for the read-back: any filled key, not only queried
    wide = [(set_key(i), "set_aw", BUCKET)
            for i in rng.sample(range(n_keys), min(n_keys, 4096))]
    t = time.monotonic()
    got, _ = c.read_objects(wide, clock=clock)
    cold_wide_s = time.monotonic() - t
    compare("head reads of a wide sample", wide, got, model.values(wide))
    st = c.node_status()
    if args.chips == 4:
        mesh = st.get("mesh") or {}
        say(f"mesh: {mesh}")
        check(mesh.get("devices") == 4
              and mesh.get("shards_per_device") == SHARDS // 4,
              f"the mesh is not 4 devices x 4 shards: {mesh}")
        for t_name, v in st["tables"].items():
            per_dev = v["device_bytes"]
            say(f"placement: {t_name} bytes by device {per_dev}")
            check(len(per_dev) == 4 and min(per_dev.values()) > 0
                  and max(per_dev.values()) <= 1.05 * min(per_dev.values()),
                  f"{t_name} does not lie evenly over four devices: "
                  f"{per_dev}")
        say(f"device memory by device: in use {st['device']['bytes_in_use']}")
    n_stored = st["keys"]
    c.close()

    # ---- (iv) SIGKILL, respawn on the same log, read back ------------------
    srv.kill(signal.SIGKILL)
    say(f"server killed with SIGKILL (exit {srv.proc.returncode}); "
        "respawning on the same --log-dir")
    srv = Server(args, 2)
    result["servers"].append(srv)
    check(device_of(srv) == dev, "the respawn reports another device")
    c = connect(srv)
    t = time.monotonic()
    got, _ = c.read_objects(objs)
    got_wide, _ = c.read_objects(wide)
    warm_s = time.monotonic() - t
    compare("(iv) queried keys after SIGKILL + recovery", objs, got,
            model.values(objs))
    compare("(iv) wide sample after SIGKILL + recovery", wide, got_wide,
            model.values(wide))
    st = c.node_status()
    check(st["keys"] == n_stored == len(model.sets) + len(model.counters),
          f"recovered {st['keys']} keys, stored {n_stored}, model "
          f"{len(model.sets) + len(model.counters)}")
    say(f"boot: cold {cold_boot_s:.1f}s, respawn with recovery of "
        f"{st['keys']} keys {srv.boot_s:.1f}s "
        f"({st['keys'] / srv.boot_s:.0f} keys/s); first head reads: cold "
        f"{cold_wide_s:.1f}s, after the respawn (compile cache warm) "
        f"{warm_s:.1f}s for both batches")
    c.close()
    srv.kill(signal.SIGTERM)
    check(dev["platform"] == "tpu", "rehearsal off-TPU: never ok")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh serving plane "
                         "(--mesh-devices 4) and its comparison")
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--fill-seconds", type=float, default=400.0,
                    help="stop filling after this long (the run has to "
                         "end within 1200 s, compiles and recovery "
                         "included); the count reached is printed")
    ap.add_argument("--keys-per-table", type=int, default=65536,
                    help="rows allocated per (type, shard); the default is "
                         "the deployment's, smaller only to rehearse")
    ap.add_argument("--boot-timeout", type=float, default=600.0)
    ap.add_argument("--rpc-timeout", type=float, default=600.0)
    ap.add_argument("--rehearse", action="store_true",
                    help="off-TPU: run the phases anyway, kernels under "
                         "the Pallas interpreter; the result is still "
                         "ok=false and the exit code non-zero")
    args = ap.parse_args()

    shutil.rmtree(OUT, ignore_errors=True)  # reclaim-ok: last run's output
    os.makedirs(OUT)
    result: dict = {"device": None, "servers": []}
    error = None
    try:
        run(args, result)
    except BaseException as e:  # noqa: BLE001 - reported below, never passed
        error = f"{type(e).__name__}: {e}"
        traceback.print_exc()
        for srv in result["servers"]:
            print(f"--- tail of {srv.stderr_path}\n{srv.stderr_tail()}",
                  file=sys.stderr, flush=True)
    finally:
        for srv in result["servers"]:
            srv.kill()
        # reclaim-ok: this run's own scratch log, every server stopped;
        # it can be gigabytes, and the servers' stderr stays
        shutil.rmtree(os.path.join(OUT, "wal"), ignore_errors=True)
    dev = result["device"] or {}
    out = {"ok": error is None,
           "device": {k: dev.get(k) for k in ("platform", "kind", "count")}}
    if error is not None:
        out["error"] = error
    print(json.dumps(out), flush=True)
    return 0 if error is None else 1


if __name__ == "__main__":
    sys.exit(main())
