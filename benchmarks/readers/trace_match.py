"""Generic per-layer reader: a regex over device-operation names in the
profiler trace.

Metric file:
  {"reader": "trace_match", "match": "<regex>", "value": <kind>, ...}
kinds:
  "time_ms"     summed device time of the matching operations
  "busy_share"  % of the traced window in which a matching operation ran
  "idle_share"  100 - busy_share
  "roofline"    % : least seconds the chip needs for the work / seconds in
                which a matching operation ran.  The work is
                "work": {"rows": [status_delta terms over the traced span],
                         "bytes_per_row": "<name in work_model.MODELS>",
                         "type": "<crdt type>"}
                bytes = rows * bytes_per_row(config widths); least seconds =
                bytes / peak bytes per second of this device_kind
                (memory-bound: these are gathers, no arithmetic to speak of)
Nothing is returned when nothing matched, or no row was served: never a 0
share.
"""

from __future__ import annotations

import re

from benchmarks import work_model
from benchmarks.readers import status_delta


def read(spec: dict, ctx) -> float | None:
    tr = ctx.trace
    if tr is None or not tr.events or tr.window_s <= 0:
        return None
    rx = re.compile(spec["match"])
    busy = tr.union_s(lambda name: rx.search(name) is not None)
    kind = spec["value"]
    if kind == "idle_share":
        return 100.0 * (1.0 - busy / tr.window_s)
    if busy <= 0:
        return None
    if kind == "busy_share":
        return 100.0 * busy / tr.window_s
    if kind == "time_ms":
        return 1e3 * sum(d for _dev, n, _t, d in tr.events
                         if rx.search(n)) / tr.n_devices / 1e9
    if kind == "roofline":
        w = spec["work"]
        pair = ctx.status.get("trace")
        rows = status_delta.total(w["rows"], *pair) if pair else None
        if not rows or rows <= 0:
            return None
        bpr = work_model.MODELS[w["bytes_per_row"]](w["type"],
                                                    ctx.config["widths"])
        least_s = rows * bpr / ctx.peaks["hbm_bytes_per_s"]
        return 100.0 * least_s / busy
    raise ValueError(f"unknown trace_match value {kind!r}")
