"""Per-layer reader: what the host was doing while the device was idle.

The first device's idle time (the gaps between its "XLA Ops" events, first
event to last) is split EXCLUSIVELY over an ordered list of host-span
classes: an idle instant belongs to the first class of the list that has a
span open on any host thread then, and to `uncovered` when none has.  So
the classes' shares and `uncovered` sum to 100.

Metric file:
  {"reader": "host_cover", "cover": "<name>", "value": "<class>"}
`cover` names `readers/<name>.json`: {"classes": [[<class>, <regex over
host-span names>], ...]} in order of precedence; `value` is one of its
classes, or "uncovered".  The metric is that class's share of the idle
time, in %.  Nothing is returned when the trace holds no span of the class
(a program without the spans, as before PR 24); `uncovered` needs a span of
any class.

    python -m benchmarks.readers.host_cover <file.xplane.pb | trace dir> [cover]

prints every class's share of the first device's idle time, and `uncovered`.
"""

from __future__ import annotations

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.readers import xplane_spans  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def split(ops, host, classes) -> dict | None:
    """{class: idle ns, ..., "uncovered": ns, "idle": ns, "spans": {class:
    n}} for one device's op events and the host's span events."""
    busy = xplane_spans.merge((t, t + d) for _n, t, d in ops)
    if not busy:
        return None
    idle = xplane_spans.subtract([(busy[0][0], busy[-1][1])], busy)
    out = {"idle": xplane_spans.total(idle), "spans": {}}
    left = idle
    for name, pattern in classes:
        rx = re.compile(pattern)
        spans = [(t, t + d) for n, t, d in host if rx.search(n)]
        out["spans"][name] = len(spans)
        rest = xplane_spans.subtract(left, xplane_spans.merge(spans))
        out[name] = xplane_spans.total(left) - xplane_spans.total(rest)
        left = rest
    out["uncovered"] = xplane_spans.total(left)
    return out


def split_planes(planes, cover: str) -> dict | None:
    devices = xplane_spans.device_planes(planes) if planes else []
    if not devices:
        return None
    with open(os.path.join(HERE, cover + ".json")) as f:
        classes = json.load(f)["classes"]
    return split(devices[0][0], xplane_spans.host_events(planes), classes)


def read(spec: dict, ctx) -> float | None:
    cached = getattr(ctx, "_host_cover", None)
    if cached is None:
        cached = ctx._host_cover = {}
    cover = spec["cover"]
    if cover not in cached:
        cached[cover] = split_planes(xplane_spans.planes(ctx), cover)
    res = cached[cover]
    if not res or res["idle"] <= 0:
        return None
    what = spec["value"]
    seen = res["spans"].get(what) if what != "uncovered" \
        else sum(res["spans"].values())
    if not seen:
        return None
    return 100.0 * res[what] / res["idle"]


def main(argv) -> int:
    from benchmarks import trace_reduce
    path = argv[0]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    res = split_planes(xplane_spans.load(path),
                       argv[1] if len(argv) > 1 else "idle_cover")
    if not res or res["idle"] <= 0:
        print("no device operations in this trace")
        return 1
    print(f"idle {res['idle'] / 1e9:.4f} s of the first device")
    for name in [*res["spans"], "uncovered"]:
        print(f"  {name}: {100.0 * res[name] / res['idle']:.3f}% "
              f"({res['spans'].get(name, '-')} spans)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
