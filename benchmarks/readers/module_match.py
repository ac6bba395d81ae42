"""Per-layer reader: device time of the operations that ran inside the
launches of one device program, found by the program's stable name.

Metric file:
  {"reader": "module_match", "module": "<regex>", "value": <kind>, ...}
`module` is searched in the names of the "XLA Modules" events (one per
launch of a jitted program: `jit_antidote_<what>(...)`); the operations
counted are the "XLA Ops" events that start inside a matching event, on the
same device.
kinds:
  "time_ms"        their summed device time, mean over devices
  "ms_per_launch"  their summed device time / matching launches
  "roofline"       % : least seconds the chip needs for the work / seconds
                   in which one of them ran (union, mean over devices);
                   "work" as in trace_match: rows over the traced span x
                   bytes per row / the device's peak bytes per second
Nothing is returned when no module matched (a program without the name, as
before PR 24) or no row was served: never a 0 share.
"""

from __future__ import annotations

import bisect
import re

from benchmarks import work_model
from benchmarks.readers import status_delta, xplane_spans


def ops_inside(ops, modules, rx):
    """(ops of one device that start inside a module whose name matches,
    number of matching modules)."""
    hit = sorted((t0, t0 + dur) for name, t0, dur in modules
                 if rx.search(name))
    if not hit:
        return [], 0
    starts = [a for a, _ in hit]
    inside = []
    for name, t0, dur in ops:
        i = bisect.bisect_right(starts, t0) - 1
        if i >= 0 and t0 < hit[i][1]:
            inside.append((name, t0, dur))
    return inside, len(hit)


def read(spec: dict, ctx) -> float | None:
    planes = xplane_spans.planes(ctx)
    devices = xplane_spans.device_planes(planes) if planes else []
    if not devices:
        return None
    rx = re.compile(spec["module"])
    launches, dev_ns, busy_ns = 0, 0, 0
    for ops, modules in devices:
        inside, n = ops_inside(ops, modules, rx)
        launches += n
        dev_ns += sum(d for _n, _t, d in inside)
        busy_ns += xplane_spans.total(xplane_spans.merge(
            (t, t + d) for _n, t, d in inside))
    if not launches or dev_ns <= 0:
        return None
    kind = spec["value"]
    if kind == "time_ms":
        return dev_ns / len(devices) / 1e6
    if kind == "ms_per_launch":
        return dev_ns / launches / 1e6
    if kind == "roofline":
        w = spec["work"]
        pair = ctx.status.get("trace")
        rows = status_delta.total(w["rows"], *pair) if pair else None
        if not rows or rows <= 0:
            return None
        bpr = work_model.MODELS[w["bytes_per_row"]](w["type"],
                                                    ctx.config["widths"])
        least_s = rows * bpr / ctx.peaks["hbm_bytes_per_s"]
        return 100.0 * least_s / (busy_ns / len(devices) / 1e9)
    raise ValueError(f"unknown module_match value {kind!r}")
