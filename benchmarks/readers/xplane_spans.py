"""The profiler's xplane as plain tuples, host planes included, parsed once
per run: what `module_match` and `host_cover` read.

    planes(ctx) -> [(plane name, [(line name, [(event name, t0_ns, dur_ns)])])]

the shape `trace_reduce.reduce_planes` takes, with every line of every plane
kept (trace_reduce.load keeps the device planes' lines only).  The file is
the one `run.py` reduced: `.bench_run/<cell name>/trace/**/*.xplane.pb`.
Host spans (`antidote_tpu.obs.trace.span`, TraceMe level 1) are events of
the "/host:CPU" plane, one line per thread, on the clock of the device
planes' "XLA Ops" and "XLA Modules" lines.

    python -m benchmarks.readers.xplane_spans <file.xplane.pb | trace dir>

prints every plane and line with its event count, its extent and its most
frequent names (count, summed duration): the check by hand that the spans
and program names are in a trace, and how long each span was open.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import trace_reduce  # noqa: E402

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def load(path: str) -> list:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return [(plane.name,
             [(line.name, [(ev.name, ev.start_ns, ev.duration_ns)
                           for ev in line.events])
              for line in plane.lines])
            for plane in pd.planes]


def planes(ctx) -> list | None:
    """The traced run's planes, or None when the run left no trace."""
    cached = getattr(ctx, "_xplane_spans", None)
    if cached is None:
        if getattr(ctx, "trace", None) is None:
            return None
        path = trace_reduce.find_xplane(os.path.join(
            ROOT, ".bench_run", ctx.cell["name"], "trace"))
        cached = ctx._xplane_spans = load(path) if path else []
    return cached or None


def device_planes(all_planes) -> list:
    """[(ops, modules)] per device plane that has an "XLA Ops" line, in
    plane order (the order trace_reduce numbers devices in); each a list of
    (name, t0_ns, dur_ns) with dur > 0."""
    out = []
    for pname, lines in all_planes:
        if not pname.startswith("/device:") or "CUSTOM" in pname.upper():
            continue
        by = {}
        for ln, evs in lines:
            by.setdefault(ln, []).extend(e for e in evs if e[2] > 0)
        if by.get(OPS_LINE):
            out.append((by[OPS_LINE], by.get(MODULES_LINE, [])))
    return out


def host_events(all_planes) -> list:
    """Every event of every host plane's lines: (name, t0_ns, dur_ns)."""
    return [e for pname, lines in all_planes if pname.startswith("/host:")
            for _ln, evs in lines for e in evs if e[2] > 0]


def merge(intervals) -> list:
    """Sorted disjoint union of (a, b) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def subtract(a, b) -> list:
    """a minus b, both sorted disjoint."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while lo < hi and k < len(b) and b[k][0] < hi:
            if b[k][0] > lo:
                out.append((lo, b[k][0]))
            lo = max(lo, b[k][1])
            k += 1
        if lo < hi:
            out.append((lo, hi))
    return out


def total(intervals) -> int:
    return sum(b - a for a, b in intervals)


def main(argv) -> int:
    from collections import Counter
    path = argv[0]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    for pname, lines in load(path):
        print(f"plane {pname!r}: {len(lines)} lines")
        for ln, evs in lines:
            if not evs:
                continue
            t0 = min(e[1] for e in evs)
            t1 = max(e[1] + e[2] for e in evs)
            ns = Counter()
            for e in evs:
                ns[e[0]] += e[2]
            top = Counter(e[0] for e in evs).most_common(12)
            print(f"  line {ln!r}: {len(evs)} events, {t0} .. {t1} ns "
                  f"({(t1 - t0) / 1e9:.4f} s); " + ", ".join(
                      f"{n[:60]} x{c} ({ns[n] / 1e6:.2f} ms)"
                      for n, c in top))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
