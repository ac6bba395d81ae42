"""The server child of a `--trace 1` run: `console serve`, unchanged, in a
process of its own, plus one thread that profiles a few seconds of it when
the benchmark asks.  Only the process that holds the chip can trace it.

    python benchmarks/serve_traced.py <run dir> <console serve arguments>

The thread waits for `<run dir>/trace.start` ({"port", "seconds"}), starts
`jax.profiler` into `<run dir>/trace`, samples node_status() over the wire
just inside both ends of the traced span, stops the profiler and writes
`<run dir>/trace.done` with the two samples.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _tracer(run_dir: str) -> None:
    start = os.path.join(run_dir, "trace.start")
    while not os.path.exists(start):
        time.sleep(0.02)
    time.sleep(0.05)                       # let the writer finish the file
    with open(start) as f:
        ask = json.load(f)
    out: dict = {"error": None}
    try:
        import jax
        from antidote_tpu.proto.client import AntidoteClient

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # device and runtime lines only
        opts.host_tracer_level = 1
        c = AntidoteClient("127.0.0.1", ask["port"], timeout=60)
        jax.profiler.start_trace(os.path.join(run_dir, "trace"),
                                 profiler_options=opts)
        t0 = time.monotonic()
        out["pre"] = c.node_status()
        time.sleep(ask["seconds"])
        out["post"] = c.node_status()
        t1 = time.monotonic()
        jax.profiler.stop_trace()
        c.close()
        out["span_s"] = t1 - t0
    except Exception as e:  # noqa: BLE001 - the parent reports it
        out["error"] = f"{type(e).__name__}: {e}"
    tmp = os.path.join(run_dir, "trace.done.tmp")
    with open(tmp, "w") as f:
        json.dump(out, f, default=str)
    os.replace(tmp, os.path.join(run_dir, "trace.done"))


def main(argv) -> int:
    run_dir, serve_args = argv[0], argv[1:]
    threading.Thread(target=_tracer, args=(run_dir,), daemon=True).start()
    from antidote_tpu.console import main as console_main
    return console_main(["serve", *serve_args]) or 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
