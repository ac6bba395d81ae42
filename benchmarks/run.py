#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It starts the server as a child (the only process that touches JAX), fills
it over the wire, walks the cell's shapes, ramps, measures for --seconds
with the cell's traffic mix from generator processes, reads a sample of the
keys back, stops the server, compares every answer with the plain reference
(benchmarks/check.py) and prints one JSON object as its last line.

There is no cell-, configuration- or mix-specific branch here: a cell is
`workloads[i]` of BENCHMARK.json, its configuration is
benchmarks/configs/<config>.json, its mix benchmarks/traffic/<traffic>.json,
and each per-layer metric benchmarks/layer_metrics/<name>.json (read by
benchmarks/readers/<reader>.py) or benchmarks/layer_metrics/<name>.py.

A configuration with `dcs` is a geo-replicated deployment: one `console
serve --interdc` a DC (each with the DC's own arguments and environment),
wired to each other as `antidote_dc_manager` wires DCs, filled by shares,
held at a barrier until every DC holds the whole fill, warmed at every DC,
loaded by clients homed at every DC, and read back at every DC until all
hold one end state (benchmarks/README.md).  Without `dcs` a run is one
server, as it always was.

Not for the driver: --rehearse runs off-TPU at the configuration's
`rehearse` size (the result is never `correct`); --control <break> puts the
reference server with one guarantee broken in the program's place;
--fault <fault> plants a fault under the comparison; --config-file tries a
configuration that has no file under benchmarks/configs yet.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()

import argparse            # noqa: E402
import importlib           # noqa: E402
import importlib.util      # noqa: E402
import json                # noqa: E402
import os                  # noqa: E402
import pickle              # noqa: E402
import queue               # noqa: E402
import random              # noqa: E402
import shutil              # noqa: E402
import signal              # noqa: E402
import subprocess          # noqa: E402
import sys                 # noqa: E402
import threading           # noqa: E402
import traceback           # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the children get the environment as it was handed to this process ...
CHILD_ENV = dict(os.environ)
# ... and this process can never initialise a backend: one process per chip
os.environ["JAX_PLATFORMS"] = "benchmark_parent_never_initialises_jax"

from benchmarks import check, data, stats            # noqa: E402
from benchmarks.clients import FAULTS, connect, refused  # noqa: E402
from benchmarks.reference.server import BREAKS, GEO_BREAKS  # noqa: E402

EXIT_INCORRECT, EXIT_NO_CHIP, EXIT_USAGE = 1, 3, 2


def say(msg: str) -> None:
    print(f"[{time.monotonic() - _T0:7.1f}s] {msg}", flush=True)


class NoResult(Exception):
    """The run cannot give a result line (no chip, a child died, ...)."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, unlisted: bool, config_file: str | None = None):
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells and unlisted:
        # a cell that is tried before it gets its entry: <config>.<traffic>
        config, _, traffic = name.partition(".")
        cells[name] = {"name": name, "config": config, "traffic": traffic}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(there are: {', '.join(cells)})")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    if unlisted:
        files.setdefault(cell["config"], os.path.join(
            "benchmarks", "configs", cell["config"] + ".json"))
    config = load_json(ROOT, config_file or files[cell["config"]])
    cell.setdefault("chips", config["chips"])
    mix = load_json(HERE, "traffic", cell["traffic"] + ".json")
    here = lambda m: unlisted or name in m.get("workloads", [name])  # noqa: E731
    e2e = [m for m in bench["end_to_end"] if here(m)]
    layer = [m for m in bench["per_layer"] if here(m)]
    return cell, config, mix, e2e, layer


# ---------------------------------------------------------------------------
# the server child (after chip_smoke.py's `Server`, PR 21)
# ---------------------------------------------------------------------------
class Server:
    def __init__(self, cmd, env, run_dir, name="server"):
        self.stderr_path = os.path.join(run_dir, name + ".stderr.log")
        say("spawn: " + " ".join(cmd[1:]))
        self.t0 = time.monotonic()
        self._stderr = open(self.stderr_path, "wb")
        self.proc = subprocess.Popen(cmd, cwd=ROOT, env=env,
                                     stdout=subprocess.PIPE,
                                     stderr=self._stderr)
        self.lines: "queue.Queue[bytes]" = queue.Queue()
        threading.Thread(
            target=lambda: [self.lines.put(ln) for ln in self.proc.stdout],
            daemon=True).start()
        self.ready = None

    def wait_ready(self, boot_timeout=900.0):
        while self.ready is None:
            try:
                line = self.lines.get(timeout=1.0)
            except queue.Empty:
                if self.proc.poll() is not None:
                    raise NoResult(f"the server exited with "
                                   f"{self.proc.returncode} before its ready "
                                   f"line:\n{self.stderr_tail()}")
                if time.monotonic() - self.t0 > boot_timeout:
                    raise NoResult(f"no ready line in {boot_timeout:.0f}s")
                continue
            if line.lstrip().startswith(b"{"):
                self.ready = json.loads(line)
        self.boot_s = time.monotonic() - self.t0
        self.port = int(self.ready["port"])
        self._seen = 0
        return self

    def endpoints(self, dc_id=0):
        """The DCs this process serves: the ready line's `dcs` list
        ({"dc_id", "port", "device"}), or the one DC on its `port`."""
        if "dcs" in self.ready:
            return [SimpleNamespace(dc_id=int(e["dc_id"]), port=int(e["port"]),
                                    device=e.get("device") or {}, srv=self)
                    for e in self.ready["dcs"]]
        return [SimpleNamespace(dc_id=dc_id, port=self.port,
                                device=self.ready.get("device") or {},
                                srv=self)]

    def stderr_tail(self, n: int = 3000) -> str:
        with open(self.stderr_path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")

    def new_compiles(self) -> int:
        """Compilations the server logged (JAX_LOG_COMPILES=1) since the
        last call."""
        with open(self.stderr_path, "rb") as f:
            f.seek(self._seen)
            new = f.read()
        self._seen += len(new)
        return new.count(b"Compiling ")

    def stop(self, sig=signal.SIGTERM) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(sig)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._stderr.close()


# ---------------------------------------------------------------------------
# the phases of set-up
# ---------------------------------------------------------------------------
def fill(kind, port, fill_spec, seed, fault, every, share=None):
    """Load keys 0..fill_keys-1 (or the share [lo, hi) of them) over
    `connections` connections, in batches made from the seed.  Every batch
    must be acknowledged.  -> (seconds, each connection's last batch)."""
    n, batch = fill_spec["fill_keys"], fill_spec["batch_keys"]
    first, end = share or (0, n)
    spans = [(lo, min(lo + batch, end)) for lo in range(first, end, batch)]
    nxt, lock, errors, lasts = iter(spans), threading.Lock(), [], []

    def worker():
        c = connect(kind, "127.0.0.1", port, 600.0, fault, every)
        last = None
        try:
            while not errors:
                with lock:
                    span = next(nxt, None)
                if span is None:
                    return
                for txn in data.fill_batch(fill_spec, seed, *span):
                    c.update_objects(txn)
                last = span
        except Exception as e:  # noqa: BLE001 - re-raised by the caller
            errors.append(e)
        finally:
            c.close()
            if last is not None:
                lasts.append(last)

    t0 = time.monotonic()
    ts = [threading.Thread(target=worker, daemon=True)
          for _ in range(fill_spec["connections"])]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    if errors:
        raise NoResult(f"the fill failed: {errors[0]!r}")
    return time.monotonic() - t0, lasts


def in_parallel(fn, items):
    """fn(item) for every item, each on a thread of its own; the results in
    order, or the first exception raised again."""
    out, errors = [None] * len(items), []

    def one(k, item):
        try:
            out[k] = fn(item)
        except BaseException as e:  # noqa: BLE001 - raised again below
            errors.append(e)

    ts = [threading.Thread(target=one, args=(k, x), daemon=True)
          for k, x in enumerate(items)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    if errors:
        raise errors[0]
    return out


def wire(kind, dcs):
    """Join the DCs as antidote_dc_manager does: every DC's connection
    descriptor, then at every DC connect_to_dcs with the others'."""
    conns = [connect(kind, "127.0.0.1", e.port, 600.0) for e in dcs]
    try:
        descs = [c.get_connection_descriptor() for c in conns]
        for k, c in enumerate(conns):
            c.connect_to_dcs([d for j, d in enumerate(descs) if j != k])
    finally:
        for c in conns:
            c.close()


def read_keys(conn, fill_spec, keys):
    """[(key index, answer)] of `keys`, read 1,024 keys a request."""
    out = []
    for lo in range(0, len(keys), 1024):
        part = keys[lo:lo + 1024]
        vals, _ = conn.read_objects([data.obj(fill_spec, i) for i in part])
        out.extend(zip(part, vals))
    return out


def fill_barrier(conns, fill_spec, seed, shares, lasts, limit=600.0):
    """Wait until every DC holds the whole fill: a sample of 1,024 keys,
    drawn from every share, and each fill connection's last batch, read at
    every DC until each answer is the key's fill value."""
    rng = random.Random(data.mix(seed, 5))
    keys = set()
    for lo, hi in shares:
        keys.update(rng.sample(range(lo, hi), min(hi - lo,
                                                   1024 // len(shares))))
    for lo, hi in lasts:
        keys.add(lo)
        if fill_spec["type"] == "set_aw":      # the batch's removes too
            keys.update(i for i in range(lo, hi)
                        if i % fill_spec["remove_every"] == 0)
    keys = sorted(keys)
    want = [data.fill_value(fill_spec, seed, i) for i in keys]
    t0, pending = time.monotonic(), set(range(len(conns)))
    while True:
        for d in sorted(pending):
            got = read_keys(conns[d], fill_spec, keys)
            if all((set(g) if isinstance(w, set) else g) == w
                   for (_i, g), w in zip(got, want)):
                pending.discard(d)
        if not pending:
            return time.monotonic() - t0, len(keys)
        if time.monotonic() - t0 > limit:
            raise NoResult(f"DCs {sorted(pending)} did not hold the whole "
                           f"fill {limit:.0f}s after it was acknowledged")
        time.sleep(0.5)


def converge(conns, fill_spec, back, hist, t_end, limit=120.0):
    """Read the keys `back` at every DC until each DC's answers equal the
    end state, for `limit` seconds at most.  -> (the last answers of each
    DC, seconds from the window's end until the last DC agreed or None)."""
    answers, agreed = [None] * len(conns), [None] * len(conns)
    deadline = time.monotonic() + limit
    while True:
        for d, c in enumerate(conns):
            if agreed[d] is not None:
                continue
            answers[d] = read_keys(c, fill_spec, back)
            if not check.readback_diff(hist, answers[d])[1]:
                agreed[d] = time.monotonic()
        if all(a is not None for a in agreed):
            return answers, max(agreed) - t_end
        if time.monotonic() > deadline:
            return answers, None
        time.sleep(0.5)


def node_status(conn, tries=50):
    """The status call shares the admission gate with the load: a busy
    reply is retried, it is not part of any metric."""
    for n in range(tries):
        try:
            return conn.node_status()
        except Exception as e:  # noqa: BLE001 - only typed refusals retried
            if not refused(e) or n == tries - 1:
                raise
            time.sleep(0.02)


class WarmLog:
    """The warm-up's own operations, logged as a generator logs its: the
    warm-up at DC d is a client of its own (-1 - d) with elements of its
    own."""

    def __init__(self, conn, fill_spec, dc=0):
        self.c, self.f, self.dc = conn, fill_spec, dc
        self.updates, self.n = [], 0

    def update(self, i, op, arg):
        f = self.f
        t0 = time.monotonic()
        self.c.update_objects([(data.key_name(f, i), f["type"], f["bucket"],
                                (op, arg))])
        self.updates.append((i, op, arg, t0, time.monotonic(), False,
                             -1 - self.dc, self.dc))

    def element(self, n):
        return f"warm:{n}" if self.dc == 0 else f"warm{self.dc}:{n}"

    def fresh(self):
        self.n += 1
        return ("increment", 1) if self.f["type"] == "counter_pn" \
            else ("add", self.element(self.n))

    def log(self):
        return {"updates": self.updates, "reads": [], "never": 0}


def warm_walk(conn, fill_spec, mix, order, has, dc=0):
    """Walk, one request at a time, every shape the window can meet: each
    request waits for its answer, so each compile ends before the next."""
    w, wl = mix["warm"], WarmLog(conn, fill_spec, dc)
    hot = order(0)
    conn.read_objects([data.obj(fill_spec, hot)])
    if has["static_update"]:
        # one key past ring overflow (GC fold) and a slot-tier promotion
        for n in range(w["hammer_updates"]):
            wl.update(hot, *wl.fresh())
            if n % 16 == 15:
                conn.read_objects([data.obj(fill_spec, hot)])
    if has["txn_read"]:
        # a snapshot that later writes make history: the versioned fold
        txn = conn.start_transaction()
        keys = [order(r) for r in range(4)]
        for n in range(w["stale_updates"]):
            wl.update(keys[n % len(keys)], *wl.fresh())
        for i in keys:
            txn.read_objects([data.obj(fill_spec, i)])
        txn.commit()
    if has["static_update"] and fill_spec["type"] == "set_aw":
        wl.update(hot, "remove", wl.element(1))
    wide = [data.obj(fill_spec, order(r)) for r in range(
        min(w["wide_read"], fill_spec["fill_keys"]))]
    conn.read_objects(wide)
    return wl.log()


class Generators:
    def __init__(self, spec_base, mix, run_dir):
        n_proc = mix["generator_processes"]
        ids = list(range(mix["clients"]))
        self.procs, self.outs = [], []
        for p in range(n_proc):
            out = os.path.join(run_dir, f"gen-{p}.pickle")
            spec = dict(spec_base, client_ids=ids[p::n_proc], out=out)
            proc = subprocess.Popen(
                [sys.executable, "-m", "benchmarks.loadgen"], cwd=ROOT,
                env=CHILD_ENV, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                text=True)
            proc.stdin.write(json.dumps(spec) + "\n")
            proc.stdin.flush()
            self.procs.append(proc)
            self.outs.append(out)
        for proc in self.procs:
            line = proc.stdout.readline()
            if line.strip() != "READY":
                raise NoResult(f"a generator did not start: {line!r}")

    def tell(self, obj):
        for proc in self.procs:
            proc.stdin.write(json.dumps(obj) + "\n")
            proc.stdin.flush()

    def collect(self, timeout):
        logs = []
        for proc, out in zip(self.procs, self.outs):
            try:
                proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
                raise NoResult("a generator did not finish")
            if proc.returncode != 0:
                raise NoResult(f"a generator exited with {proc.returncode}")
            with open(out, "rb") as f:
                logs.append(pickle.load(f))   # written by our own child
        return logs

    def kill(self):
        for proc in self.procs:
            if proc.poll() is None:
                proc.kill()
            proc.wait()


# ---------------------------------------------------------------------------
def layer_value(metric_name, ctx):
    """A per-layer metric is data for a generic reader, or code of its own,
    found by the metric's name."""
    base = os.path.join(HERE, "layer_metrics", metric_name)
    if os.path.exists(base + ".py"):
        spec = importlib.util.spec_from_file_location(
            "layer_metric_" + metric_name.replace(".", "_"), base + ".py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read(ctx)
    m = load_json(base + ".json")
    reader = importlib.import_module("benchmarks.readers." + m["reader"])
    return reader.read(m, ctx)


def window_metrics(logs, t_start, t_end):
    """The end-to-end numbers, over all requests and the whole window."""
    reads = [r for g in logs for r in g["reads"]]
    ups = [u for g in logs for u in g["updates"] if u[5]]
    attempted = len(reads) + len(ups)
    r_lat = [(r[3] - r[2]) * 1e3 for r in reads if r[3] is not None]
    u_lat = [(u[4] - u[3]) * 1e3 for u in ups if u[4] is not None]
    failed = attempted - len(r_lat) - len(u_lat)
    done = (sum(1 for r in reads if r[3] is not None and r[3] <= t_end)
            + sum(1 for u in ups if u[4] is not None and u[4] <= t_end))
    out = {"ops_per_s": stats.rate(done, t_end - t_start)}
    if r_lat:
        out["read_p95_ms"] = stats.percentile(r_lat, 95)
    if u_lat:
        out["update_p95_ms"] = stats.percentile(u_lat, 95)
    p50 = {}
    for what, lat in (("read", r_lat), ("update", u_lat)):
        if lat:
            p50[what] = {f"p{q}_ms": round(stats.percentile(lat, q), 3)
                         for q in (50, 90, 95, 99, 100)}
            p50[what]["n"] = len(lat)
            p50[what]["over_5ms_share"] = round(
                sum(1 for x in lat if x > 5.0) / len(lat), 4)
    n_txn = sum(1 for r in reads if r[1])
    say(f"window: {attempted} requests attempted, {failed} failed, {done} "
        f"completed inside; reads {len(r_lat)} ({n_txn} in a transaction), "
        f"updates {len(u_lat)}; percentiles {p50}")
    return out, attempted, failed


def fold_tallies(status):
    """Serving-fold dispatch tallies, flattened to {name: count}."""
    mat = (status.get("pipeline") or {}).get("materializer") or {}
    out = {}

    def walk(prefix, node):
        for k, v in node.items():
            name = f"{prefix}.{k}" if prefix else str(k)
            if isinstance(v, dict):
                walk(name, v)
            else:
                out[name] = v

    walk("", mat.get("serving_folds") or {})
    return out


def dc_servers(args, config, run_dir, control):
    """[(name, command, environment)] of the server processes: one for a
    configuration without `dcs` (or the reference, which serves every DC
    itself), else one `console serve --interdc` a DC."""
    env = dict(CHILD_ENV, JAX_LOG_COMPILES="1")
    geo = config.get("dcs")
    if control:
        cmd = [sys.executable, "-m", "benchmarks.reference.server"]
        if args.control != "none":
            cmd += ["--break", args.control, "--every", str(args.every)]
        if geo:
            cmd += ["--dcs", str(len(geo)), "--seed", str(args.seed)]
        return [("server", cmd, env)]
    serve_args = list(config["rehearse"]["serve_args"] if args.rehearse
                      else config["serve_args"])
    if args.rehearse:
        env["ANTIDOTE_PALLAS_INTERPRET"] = "1"
    out = []
    for k, d in enumerate(geo or [None]):
        tail = list(serve_args)
        denv = dict(env)
        wal = "wal"
        if d is not None:
            own = dict(d, **d.get("rehearse", {})) if args.rehearse else d
            tail += list(own.get("serve_args", [])) + [
                "--interdc", "--dc-id", str(d["dc_id"])]
            for key, v in (own.get("env") or {}).items():
                # libtpu's flags are added to, never replaced
                denv[key] = (f"{denv[key]} {v}" if key == "LIBTPU_INIT_ARGS"
                             and denv.get(key) else str(v))
            wal = f"wal{d['dc_id']}"
        tail += ["--log-dir", os.path.join(run_dir, wal), "--port", "0"]
        if args.trace and k == 0:          # a traced run traces DC 0 only
            cmd = [sys.executable, os.path.join(HERE, "serve_traced.py"),
                   run_dir] + tail
        else:
            cmd = [sys.executable, "-m", "antidote_tpu.console",
                   "serve"] + tail
        out.append(("server" if d is None else f"server-dc{d['dc_id']}",
                    cmd, denv))
    return out


def run(args, res):
    cell, config, mix, e2e, layer = load_cell(args.workload, args.unlisted,
                                              args.config_file)
    control = args.control is not None
    kind = "reference" if control else "wire"
    fill_spec = dict(config["fill"])
    if args.rehearse:
        fill_spec.update({k: v for k, v in config["rehearse"].items()
                          if k != "serve_args"})
        mix = dict(mix, **mix["rehearse"])
    has = {k: any(x["kind"] == k for x in mix["kinds"])
           for k in ("static_update", "static_read", "txn_read")}
    peaks = load_json(HERE, "peaks.json")

    run_dir = os.path.join(ROOT, ".bench_run", args.workload)
    shutil.rmtree(run_dir, ignore_errors=True)   # last run's own scratch
    os.makedirs(run_dir)
    res["run_dir"] = run_dir

    # ---- the servers: one, or one a DC, booting side by side ----------------
    servers = res["servers"] = []
    for name, cmd, env in dc_servers(args, config, run_dir, control):
        servers.append(Server(cmd, env, run_dir, name))
    for srv in servers:
        srv.wait_ready()
    geo = config.get("dcs")
    dcs = [e for k, srv in enumerate(servers)
           for e in srv.endpoints(geo[k]["dc_id"] if geo and not control
                                  else 0)]
    dcs.sort(key=lambda e: e.dc_id)
    n_dcs = len(dcs)
    dev = dict(dcs[0].device)
    for e in dcs:
        say(f"{'server' if n_dcs == 1 else f'DC {e.dc_id}'} ready in "
            f"{e.srv.boot_s:.1f}s on port {e.port}, "
            f"{e.device or 'no device (control)'}")
    wrong_device = 0
    if not control:
        total = sum(e.device.get("count") or 0 for e in dcs)
        dev["count"] = total
        chips_of = {d["dc_id"]: d["chips"] for d in geo or []}
        if (any(e.device.get("platform") != "tpu" for e in dcs)
                or total != cell["chips"]
                or any(e.device.get("count") != chips_of.get(e.dc_id, total)
                       for e in dcs)):
            if not args.rehearse:
                raise NoResult(
                    f"the server runs on {dev.get('platform')} x "
                    f"{total}, the cell asks for tpu x "
                    f"{cell['chips']}: no result")
            wrong_device = 1
            say("REHEARSAL off-TPU: the result is never `correct`")
        elif any(e.device.get("kind") not in peaks for e in dcs):
            raise NoResult(f"device_kind {dev.get('kind')!r} is not in "
                           "benchmarks/peaks.json: no result")
    if n_dcs > 1:
        t = time.monotonic()
        wire(kind, dcs)
        say(f"{n_dcs} DCs wired (get_connection_descriptor, connect_to_dcs) "
            f"in {time.monotonic() - t:.1f}s")

    # ---- generators connect while the fill runs ----------------------------
    spec = {"host": "127.0.0.1", "port": dcs[0].port, "client": kind,
            "seed": args.seed, "mix": mix, "fill": fill_spec,
            "timeout": 60.0, "fault": args.fault, "fault_every": args.every}
    if n_dcs > 1:
        spec["ports"] = [e.port for e in dcs]   # client c homed at c mod N
    gens = res["gens"] = Generators(spec, mix, run_dir)

    n_fill = fill_spec["fill_keys"]
    if n_dcs == 1:
        fill_s, _ = fill(kind, dcs[0].port, fill_spec, args.seed, args.fault,
                         args.every)
    else:
        shares = [(d * n_fill // n_dcs, (d + 1) * n_fill // n_dcs)
                  for d in range(n_dcs)]
        t = time.monotonic()
        done = in_parallel(lambda d: fill(
            kind, dcs[d].port, fill_spec, args.seed, args.fault, args.every,
            shares[d]), list(range(n_dcs)))
        fill_s = time.monotonic() - t
        say("each DC filled its share: " + ", ".join(
            f"DC {e.dc_id} keys {lo}..{hi - 1} in {s:.1f}s"
            for e, (lo, hi), (s, _l) in zip(dcs, shares, done)))
    say(f"filled {n_fill} {fill_spec['type']} keys in "
        f"{fill_s:.1f}s = {n_fill / fill_s:.0f} keys/s over "
        f"{fill_spec['connections']} connections"
        + (" a DC" if n_dcs > 1 else ""))

    conns = res["conns"] = [connect(kind, "127.0.0.1", e.port, 600.0)
                            for e in dcs]
    conn = conns[0]
    if n_dcs > 1:
        barrier_s, n_keys = fill_barrier(
            conns, fill_spec, args.seed, shares,
            [span for _s, lasts in done for span in lasts])
        say(f"convergence barrier: every DC held the fill ({n_keys} keys "
            f"read at each) {barrier_s:.1f}s after the fill")
    sts = [node_status(c) for c in conns]
    if not control:
        for e, st in zip(dcs, sts):
            where = "" if n_dcs == 1 else f"DC {e.dc_id}: "
            tab = st["tables"]
            say(where + "tables: " + ", ".join(
                f"{t} {v['n_rows']} rows/shard ({v['rows_used']} used, "
                f"{sum(v['device_bytes'].values()) / 2**30:.2f} GiB on "
                f"device)" for t, v in tab.items()))
            say(f"{where}device {st['device']['kind']} x "
                f"{st['device']['count']}: HBM in use "
                f"{st['device']['bytes_in_use']}, peak "
                f"{st['device']['peak_bytes_in_use']}, limit "
                f"{st['device']['bytes_limit']} bytes")
            say(f"{where}native planes (None = loaded): {st['native']}")
    order = data.KeyOrder(args.seed, fill_spec["fill_keys"])
    t = time.monotonic()
    if n_dcs == 1:
        warm_logs = [warm_walk(conn, fill_spec, mix, order, has)]
    else:
        # at every DC: each compiles its own shapes, and those of the
        # remote ingress that the other DCs' warm-up writes bring
        warm_logs = in_parallel(lambda d: warm_walk(
            conns[d], fill_spec, mix, order, has, dcs[d].dc_id),
            list(range(n_dcs)))
    say(f"warm walk {time.monotonic() - t:.1f}s "
        f"({sum(s.new_compiles() for s in servers)} compilations logged so "
        f"far)")

    # ---- ramp: the real mix, until it runs and nothing compiles any more ---
    t_ramp = time.monotonic() + 0.2
    gens.tell([t_ramp, None, None])
    last_compile = t_ramp
    while True:
        time.sleep(0.25)
        now = time.monotonic()
        if sum(s.new_compiles() for s in servers):
            last_compile = now
        if now - t_ramp >= mix["ramp_seconds"] and now - last_compile >= 2.0:
            break
        if now - t_ramp > 240:
            raise NoResult("the ramp never stopped compiling")
    t_start = time.monotonic() + 0.3
    t_end = t_start + args.seconds
    gens.tell([t_ramp, t_start, t_end])
    time.sleep(max(0.0, t_start - time.monotonic()))
    by_dc = [{"window": [node_status(c), None]} for c in conns]
    status = by_dc[0]
    for s in servers:
        s.new_compiles()
    setup_s = t_start - _T0
    say(f"window opens: set-up took {setup_s:.1f}s (boot "
        f"{max(s.boot_s for s in servers):.1f}, fill {fill_s:.1f}, ramp "
        f"{t_start - t_ramp:.1f})")

    # ---- the window --------------------------------------------------------
    trace_span = None
    if args.trace and not control:
        trace_span = max(1.0, min(3.0, args.seconds / 3.0))
        time.sleep(max(0.0, t_start + args.seconds / 4.0 - time.monotonic()))
        tmp = os.path.join(run_dir, "trace.start.tmp")
        with open(tmp, "w") as f:
            json.dump({"port": dcs[0].port, "seconds": trace_span}, f)
        os.replace(tmp, os.path.join(run_dir, "trace.start"))
    time.sleep(max(0.0, t_end - time.monotonic()))
    for c, st in zip(conns, by_dc):
        st["window"][1] = node_status(c)
    compiles_in_window = sum(s.new_compiles() for s in servers)
    logs = gens.collect(timeout=150.0)
    for s in servers:
        if s.proc.poll() is not None:
            raise NoResult(f"the server died in the window:\n"
                           f"{s.stderr_tail()}")

    # ---- after the window: memory, read-back, stop the servers --------------
    st_afters = [node_status(c) for c in conns]
    device = {"platform": dev.get("platform"), "kind": dev.get("kind"),
              "count": dev.get("count"), "memory_peak_bytes": None}
    if not control:
        peak = [b for st in st_afters
                for b in st["device"]["peak_bytes_in_use"] if b is not None]
        device["memory_peak_bytes"] = max(peak) if peak else None
        for e, st in zip(dcs, st_afters):
            say(f"{'' if n_dcs == 1 else f'DC {e.dc_id}: '}HBM after the "
                f"window: in use {st['device']['bytes_in_use']}, peak "
                f"{st['device']['peak_bytes_in_use']}")
    folds = [(fold_tallies(st["window"][0]), fold_tallies(st["window"][1]))
             for st in by_dc]
    for e, (f0, f1) in zip(dcs, folds):
        say(f"{'' if n_dcs == 1 else f'DC {e.dc_id}: '}serving_folds before "
            f"the window {f0}, after {f1}")
    say(f"compilations inside the window: {compiles_in_window}")
    for p, g in enumerate(logs):
        say(f"generator {p}: busy {100 * g['cpu_busy_window']:.0f}% of one "
            f"core in the window ({100 * g['cpu_busy_ramp']:.0f}% in the "
            f"ramp){'; errors: ' + str(g['errors'][:2]) if g['errors'] else ''}")

    touched = sorted({u[0] for g in logs + warm_logs for u in g["updates"]})
    rng = random.Random(data.mix(args.seed, 3))
    n_back = min(mix["readback_keys"], fill_spec["fill_keys"])
    back = set(touched[:n_back // 2]) | {order(0)}
    while len(back) < n_back:
        back.add(rng.randrange(fill_spec["fill_keys"]))
    back = sorted(back)
    hist = None
    t = time.monotonic()
    if n_dcs == 1:
        readback = read_keys(conn, fill_spec, back)
        say(f"read {len(back)} keys back in {time.monotonic() - t:.1f}s "
            f"({len(touched)} keys were updated)")
    else:
        hist = check.History(fill_spec, args.seed,
                             [u for g in logs + warm_logs
                              for u in g["updates"]])
        readback, converge_s = converge(conns, fill_spec, back, hist, t_end)
        say(f"read {len(back)} keys back at each of {n_dcs} DCs until all "
            f"held the end state: converge_s "
            + (f"{converge_s:.3f}" if converge_s is not None
               else "none: not every DC agreed within 120 s")
            + f" ({len(touched)} keys were updated)")
    trace_done = None
    if trace_span is not None:
        done_path = os.path.join(run_dir, "trace.done")
        t = time.monotonic()
        while not os.path.exists(done_path) and time.monotonic() - t < 120:
            time.sleep(0.1)
        if os.path.exists(done_path):
            trace_done = load_json(done_path)
    for c in conns:
        c.close()
    for s in servers:
        s.stop()

    # ---- metrics -------------------------------------------------------------
    metrics, attempted, failed = window_metrics(logs, t_start, t_end)
    metrics["setup_s"] = setup_s
    ctx = SimpleNamespace(status=status, trace=None, config=config,
                          peaks=peaks.get(dev.get("kind"), {}), cell=cell,
                          status_by_dc=by_dc)
    breakdown = None
    if trace_done is not None:
        if trace_done.get("error"):
            raise NoResult(f"the profiler failed: {trace_done['error']}")
        from benchmarks import trace_reduce
        status["trace"] = [trace_done["pre"], trace_done["post"]]
        path = trace_reduce.find_xplane(os.path.join(run_dir, "trace"))
        if path is None:
            raise NoResult("the profiler left no xplane file")
        t = time.monotonic()
        tr = ctx.trace = trace_reduce.load(path)
        say(f"trace {os.path.getsize(path) / 2**20:.1f} MiB read in "
            f"{time.monotonic() - t:.1f}s: {len(tr.events)} device "
            f"operations on {tr.n_devices} device(s), busy {tr.busy_s:.4f}s "
            f"of {tr.window_s:.4f}s; lines {tr.lines_seen}")
        device["busy_s"], device["window_s"] = tr.busy_s, tr.window_s
        breakdown = {"device_ops": tr.top_ops(10),
                     "idle_gaps": tr.idle_gaps(10)}

    # ---- the comparison ----------------------------------------------------
    t = time.monotonic()
    checks, counts = check.compare(fill_spec, args.seed, logs + warm_logs,
                                   readback, n_dcs, hist)
    if mix.get("expect", {}).get("folds_rise") and not control:
        # at any DC: the mix reached the versioned fold
        rose = [k for f0, f1 in folds for k in f1 if f1[k] > f0.get(k, 0)]
        say(f"fold tallies that rose in the window: {rose}")
        checks["no_fold_rose"] = {"value": int(not rose), "limit": 0}
    if not control:
        checks["wrong_device"] = {"value": wrong_device, "limit": 0}
    say(f"comparison took {time.monotonic() - t:.1f}s: {counts}")

    names = [m["name"] for m in (layer if args.trace else e2e)]
    units = {m["name"]: m["unit"] for m in e2e + layer}
    values = {}
    for name in names:
        v = layer_value(name, ctx) if args.trace else metrics.get(name)
        if v is not None:
            values[name] = {"value": v, "unit": units[name]}
    res["line"] = {
        "correct": check.verdict(checks), "attempted": attempted,
        "failed": failed, "metrics": values, "device": device,
    }
    if breakdown:
        res["line"]["breakdown"] = breakdown
    if n_dcs > 1:
        res["line"]["geo"] = {"dcs": n_dcs, "converge_s": converge_s,
                              "traced_dc": 0 if trace_done else None}
    res["line"]["checks"] = checks


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", choices=("none",) + BREAKS + GEO_BREAKS,
                    default=None)
    ap.add_argument("--fault", choices=FAULTS, default=None)
    ap.add_argument("--unlisted", action="store_true",
                    help="try <config>.<traffic> before BENCHMARK.json "
                         "lists it as a cell")
    ap.add_argument("--config-file", default=None,
                    help="with --unlisted: the configuration's file, where "
                         "it is not benchmarks/configs/<config>.json")
    ap.add_argument("--every", type=int, default=40,
                    help="the control or fault strikes every Nth request "
                         "of a connection (of the server, for a control)")
    args = ap.parse_args()
    if args.config_file and not args.unlisted:
        ap.error("--config-file is for an --unlisted cell")
    res: dict = {}
    code = 0
    try:
        # the program itself must be there: no result without it
        importlib.import_module("antidote_tpu.proto.client")
        run(args, res)
    except NoResult as e:
        print(f"NO RESULT: {e}", file=sys.stderr, flush=True)
        code = EXIT_NO_CHIP
    except BaseException:  # noqa: BLE001 - reported, then the exit code says so
        traceback.print_exc()
        for srv in res.get("servers") or []:
            print("--- tail of the server's stderr\n"
                  + srv.stderr_tail(), file=sys.stderr, flush=True)
        code = EXIT_USAGE
    finally:
        if res.get("gens") is not None:
            res["gens"].kill()
        for srv in res.get("servers") or []:
            srv.stop()
        if res.get("run_dir") and not os.environ.get("BENCH_KEEP_RUN_DIR"):
            # this run's own scratch: WAL, generator logs, trace
            shutil.rmtree(res["run_dir"], ignore_errors=True)
    line = res.get("line")
    if code or line is None:
        return code or EXIT_USAGE
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0 if line["correct"] else EXIT_INCORRECT


if __name__ == "__main__":
    sys.exit(main())
