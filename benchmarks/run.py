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

Not for the driver: --rehearse runs off-TPU at the configuration's
`rehearse` size (the result is never `correct`); --control <break> puts the
reference server with one guarantee broken in the program's place;
--fault <fault> plants a fault under the comparison.
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
from benchmarks.reference.server import BREAKS       # noqa: E402

EXIT_INCORRECT, EXIT_NO_CHIP, EXIT_USAGE = 1, 3, 2


def say(msg: str) -> None:
    print(f"[{time.monotonic() - _T0:7.1f}s] {msg}", flush=True)


class NoResult(Exception):
    """The run cannot give a result line (no chip, a child died, ...)."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, unlisted: bool):
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
    config = load_json(ROOT, files[cell["config"]])
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
    def __init__(self, cmd, env, run_dir, boot_timeout=900.0):
        self.stderr_path = os.path.join(run_dir, "server.stderr.log")
        say("spawn: " + " ".join(cmd[1:]))
        t0 = time.monotonic()
        self._stderr = open(self.stderr_path, "wb")
        self.proc = subprocess.Popen(cmd, cwd=ROOT, env=env,
                                     stdout=subprocess.PIPE,
                                     stderr=self._stderr)
        lines: "queue.Queue[bytes]" = queue.Queue()
        threading.Thread(
            target=lambda: [lines.put(ln) for ln in self.proc.stdout],
            daemon=True).start()
        self.ready = None
        while self.ready is None:
            try:
                line = lines.get(timeout=1.0)
            except queue.Empty:
                if self.proc.poll() is not None:
                    raise NoResult(f"the server exited with "
                                   f"{self.proc.returncode} before its ready "
                                   f"line:\n{self.stderr_tail()}")
                if time.monotonic() - t0 > boot_timeout:
                    raise NoResult(f"no ready line in {boot_timeout:.0f}s")
                continue
            if line.lstrip().startswith(b"{"):
                self.ready = json.loads(line)
        self.boot_s = time.monotonic() - t0
        self.port = int(self.ready["port"])
        self._seen = 0

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
def fill(kind, port, fill_spec, seed, fault, every):
    """Load keys 0..fill_keys-1 over `connections` connections, in batches
    made from the seed.  Every batch must be acknowledged."""
    n, batch = fill_spec["fill_keys"], fill_spec["batch_keys"]
    spans = [(lo, min(lo + batch, n)) for lo in range(0, n, batch)]
    nxt, lock, errors = iter(spans), threading.Lock(), []

    def worker():
        c = connect(kind, "127.0.0.1", port, 600.0, fault, every)
        try:
            while not errors:
                with lock:
                    span = next(nxt, None)
                if span is None:
                    return
                for txn in data.fill_batch(fill_spec, seed, *span):
                    c.update_objects(txn)
        except Exception as e:  # noqa: BLE001 - re-raised by the caller
            errors.append(e)
        finally:
            c.close()

    t0 = time.monotonic()
    ts = [threading.Thread(target=worker, daemon=True)
          for _ in range(fill_spec["connections"])]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    if errors:
        raise NoResult(f"the fill failed: {errors[0]!r}")
    return time.monotonic() - t0


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
    """The warm-up's own operations, logged as a generator logs its."""

    def __init__(self, conn, fill_spec):
        self.c, self.f = conn, fill_spec
        self.updates, self.n = [], 0

    def update(self, i, op, arg):
        f = self.f
        t0 = time.monotonic()
        self.c.update_objects([(data.key_name(f, i), f["type"], f["bucket"],
                                (op, arg))])
        self.updates.append((i, op, arg, t0, time.monotonic(), False, -1))

    def fresh(self):
        self.n += 1
        return ("increment", 1) if self.f["type"] == "counter_pn" \
            else ("add", f"warm:{self.n}")

    def log(self):
        return {"updates": self.updates, "reads": [], "never": 0}


def warm_walk(conn, fill_spec, mix, order, has):
    """Walk, one request at a time, every shape the window can meet: each
    request waits for its answer, so each compile ends before the next."""
    w, wl = mix["warm"], WarmLog(conn, fill_spec)
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
        wl.update(hot, "remove", "warm:1")
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


def run(args, res):
    cell, config, mix, e2e, layer = load_cell(args.workload, args.unlisted)
    control = args.control is not None
    kind = "reference" if control else "wire"
    fill_spec = dict(config["fill"])
    serve_args = list(config["serve_args"])
    if args.rehearse:
        serve_args = list(config["rehearse"]["serve_args"])
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

    # ---- the server ------------------------------------------------------
    env = dict(CHILD_ENV, JAX_LOG_COMPILES="1")
    if control:
        cmd = [sys.executable, "-m", "benchmarks.reference.server"]
        if args.control != "none":
            cmd += ["--break", args.control, "--every", str(args.every)]
    else:
        tail = serve_args + ["--log-dir", os.path.join(run_dir, "wal"),
                             "--port", "0"]
        if args.trace:
            cmd = [sys.executable, os.path.join(HERE, "serve_traced.py"),
                   run_dir] + tail
        else:
            cmd = [sys.executable, "-m", "antidote_tpu.console", "serve"] + tail
        if args.rehearse:
            env["ANTIDOTE_PALLAS_INTERPRET"] = "1"
    srv = res["server"] = Server(cmd, env, run_dir)
    dev = srv.ready.get("device") or {}
    say(f"server ready in {srv.boot_s:.1f}s on {dev or 'no device (control)'}")
    wrong_device = 0
    if not control:
        if dev.get("platform") != "tpu" or dev.get("count") != cell["chips"]:
            if not args.rehearse:
                raise NoResult(
                    f"the server runs on {dev.get('platform')} x "
                    f"{dev.get('count')}, the cell asks for tpu x "
                    f"{cell['chips']}: no result")
            wrong_device = 1
            say("REHEARSAL off-TPU: the result is never `correct`")
        elif dev.get("kind") not in peaks:
            raise NoResult(f"device_kind {dev.get('kind')!r} is not in "
                           "benchmarks/peaks.json: no result")

    # ---- generators connect while the fill runs ----------------------------
    spec = {"host": "127.0.0.1", "port": srv.port, "client": kind,
            "seed": args.seed, "mix": mix, "fill": fill_spec,
            "timeout": 60.0, "fault": args.fault, "fault_every": args.every}
    gens = res["gens"] = Generators(spec, mix, run_dir)

    fill_s = fill(kind, srv.port, fill_spec, args.seed, args.fault,
                  args.every)
    say(f"filled {fill_spec['fill_keys']} {fill_spec['type']} keys in "
        f"{fill_s:.1f}s = {fill_spec['fill_keys'] / fill_s:.0f} keys/s over "
        f"{fill_spec['connections']} connections")

    conn = connect(kind, "127.0.0.1", srv.port, 600.0)
    st = node_status(conn)
    if not control:
        tab = st["tables"]
        say("tables: " + ", ".join(
            f"{t} {v['n_rows']} rows/shard ({v['rows_used']} used, "
            f"{sum(v['device_bytes'].values()) / 2**30:.2f} GiB on device)"
            for t, v in tab.items()))
        say(f"device {st['device']['kind']} x {st['device']['count']}: HBM "
            f"in use {st['device']['bytes_in_use']}, peak "
            f"{st['device']['peak_bytes_in_use']}, limit "
            f"{st['device']['bytes_limit']} bytes")
        say(f"native planes (None = loaded): {st['native']}")
    order = data.KeyOrder(args.seed, fill_spec["fill_keys"])
    t = time.monotonic()
    warm_log = warm_walk(conn, fill_spec, mix, order, has)
    say(f"warm walk {time.monotonic() - t:.1f}s "
        f"({srv.new_compiles()} compilations logged so far)")

    # ---- ramp: the real mix, until it runs and nothing compiles any more ---
    t_ramp = time.monotonic() + 0.2
    gens.tell([t_ramp, None, None])
    last_compile = t_ramp
    while True:
        time.sleep(0.25)
        now = time.monotonic()
        if srv.new_compiles():
            last_compile = now
        if now - t_ramp >= mix["ramp_seconds"] and now - last_compile >= 2.0:
            break
        if now - t_ramp > 240:
            raise NoResult("the ramp never stopped compiling")
    t_start = time.monotonic() + 0.3
    t_end = t_start + args.seconds
    gens.tell([t_ramp, t_start, t_end])
    time.sleep(max(0.0, t_start - time.monotonic()))
    status = {"window": [node_status(conn), None]}
    srv.new_compiles()
    setup_s = t_start - _T0
    say(f"window opens: set-up took {setup_s:.1f}s (boot {srv.boot_s:.1f}, "
        f"fill {fill_s:.1f}, ramp {t_start - t_ramp:.1f})")

    # ---- the window --------------------------------------------------------
    trace_span = None
    if args.trace and not control:
        trace_span = max(1.0, min(3.0, args.seconds / 3.0))
        time.sleep(max(0.0, t_start + args.seconds / 4.0 - time.monotonic()))
        tmp = os.path.join(run_dir, "trace.start.tmp")
        with open(tmp, "w") as f:
            json.dump({"port": srv.port, "seconds": trace_span}, f)
        os.replace(tmp, os.path.join(run_dir, "trace.start"))
    time.sleep(max(0.0, t_end - time.monotonic()))
    status["window"][1] = st_end = node_status(conn)
    compiles_in_window = srv.new_compiles()
    logs = gens.collect(timeout=150.0)
    if srv.proc.poll() is not None:
        raise NoResult(f"the server died in the window:\n{srv.stderr_tail()}")

    # ---- after the window: memory, read-back, stop the server ---------------
    st_after = node_status(conn)
    device = {"platform": dev.get("platform"), "kind": dev.get("kind"),
              "count": dev.get("count"), "memory_peak_bytes": None}
    if not control:
        peak = [b for b in st_after["device"]["peak_bytes_in_use"]
                if b is not None]
        device["memory_peak_bytes"] = max(peak) if peak else None
        say(f"HBM after the window: in use "
            f"{st_after['device']['bytes_in_use']}, peak "
            f"{st_after['device']['peak_bytes_in_use']}")
    f0 = fold_tallies(status["window"][0])
    f1 = fold_tallies(st_end)
    say(f"serving_folds before the window {f0}, after {f1}; compilations "
        f"inside the window: {compiles_in_window}")
    for p, g in enumerate(logs):
        say(f"generator {p}: busy {100 * g['cpu_busy_window']:.0f}% of one "
            f"core in the window ({100 * g['cpu_busy_ramp']:.0f}% in the "
            f"ramp){'; errors: ' + str(g['errors'][:2]) if g['errors'] else ''}")

    touched = sorted({u[0] for g in logs + [warm_log] for u in g["updates"]})
    rng = random.Random(data.mix(args.seed, 3))
    n_back = min(mix["readback_keys"], fill_spec["fill_keys"])
    back = set(touched[:n_back // 2]) | {order(0)}
    while len(back) < n_back:
        back.add(rng.randrange(fill_spec["fill_keys"]))
    back = sorted(back)
    readback = []
    t = time.monotonic()
    for lo in range(0, len(back), 1024):
        part = back[lo:lo + 1024]
        vals, _ = conn.read_objects([data.obj(fill_spec, i) for i in part])
        readback.extend(zip(part, vals))
    say(f"read {len(back)} keys back in {time.monotonic() - t:.1f}s "
        f"({len(touched)} keys were updated)")
    trace_done = None
    if trace_span is not None:
        done_path = os.path.join(run_dir, "trace.done")
        t = time.monotonic()
        while not os.path.exists(done_path) and time.monotonic() - t < 120:
            time.sleep(0.1)
        if os.path.exists(done_path):
            trace_done = load_json(done_path)
    conn.close()
    srv.stop()

    # ---- metrics -------------------------------------------------------------
    metrics, attempted, failed = window_metrics(logs, t_start, t_end)
    metrics["setup_s"] = setup_s
    ctx = SimpleNamespace(status=status, trace=None, config=config,
                          peaks=peaks.get(dev.get("kind"), {}), cell=cell)
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
    checks, counts = check.compare(fill_spec, args.seed, logs + [warm_log],
                                   readback)
    if mix.get("expect", {}).get("folds_rise") and not control:
        rose = [k for k in f1 if f1[k] > f0.get(k, 0)]
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
    res["line"]["checks"] = checks


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", choices=("none",) + BREAKS, default=None)
    ap.add_argument("--fault", choices=FAULTS, default=None)
    ap.add_argument("--unlisted", action="store_true",
                    help="try <config>.<traffic> before BENCHMARK.json "
                         "lists it as a cell")
    ap.add_argument("--every", type=int, default=40,
                    help="the control or fault strikes every Nth request "
                         "of a connection (of the server, for a control)")
    args = ap.parse_args()
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
        if res.get("server") is not None:
            print("--- tail of the server's stderr\n"
                  + res["server"].stderr_tail(), file=sys.stderr, flush=True)
        code = EXIT_USAGE
    finally:
        if res.get("gens") is not None:
            res["gens"].kill()
        if res.get("server") is not None:
            res["server"].stop()
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
