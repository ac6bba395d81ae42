"""From the profiler's xplane file to device busy time, time per operation
name and the longest idle gaps.  Read after the server has exited, with
nothing but `jax.profiler.ProfileData` (no backend is initialised).

What counts as "an operation ran on the device": the events of each device
plane's "XLA Ops" line (the line of the executed HLO operations and
kernels).  Lines that only frame them ("Steps", "XLA Modules", launch and
framework-name lines) are left out, so that a module's frame does not hide
the gaps between its operations.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

OP_LINES = ("XLA Ops",)


@dataclass
class Trace:
    n_devices: int
    window_s: float                 # first to last device event, or given
    busy_s: float                   # union of op intervals, mean over devices
    events: list = field(default_factory=list)  # (device, name, t0_ns, dur_ns)
    lines_seen: dict = field(default_factory=dict)

    def union_s(self, pick=lambda name: True) -> float:
        """Seconds in which a picked operation ran, averaged over devices."""
        per = {}
        for dev, name, t0, dur in self.events:
            if pick(name):
                per.setdefault(dev, []).append((t0, t0 + dur))
        if not per:
            return 0.0
        return sum(_union_ns(iv) for iv in per.values()) / self.n_devices / 1e9

    def top_ops(self, n=10) -> list:
        tot = {}
        for _dev, name, _t0, dur in self.events:
            tot[name] = tot.get(name, 0) + dur
        top = sorted(tot.items(), key=lambda kv: kv[1], reverse=True)[:n]
        return [[name, ns / self.n_devices / 1e9] for name, ns in top]

    def idle_gaps(self, n=10) -> list:
        """The longest gaps on the first device, each named by the operation
        that ended it (what the host got round to launching next)."""
        dev0 = sorted((t0, t0 + dur, name) for dev, name, t0, dur
                      in self.events if dev == 0)
        gaps, end = [], None
        for t0, t1, name in dev0:
            if end is not None and t0 > end:
                gaps.append((t0 - end, name))
            end = t1 if end is None else max(end, t1)
        gaps.sort(reverse=True)
        return [[f"before:{name}", ns / 1e9] for ns, name in gaps[:n]]


def _union_ns(intervals) -> int:
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def find_xplane(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    return hits[-1] if hits else None


def reduce_planes(planes, window_s: float | None = None) -> Trace:
    """`planes`: [(plane name, [(line name, [(event name, t0_ns, dur_ns)])])]
    -- the shape `load` makes from the xplane, and what selfcheck feeds."""
    events, seen, dev = [], {}, 0
    for pname, lines in planes:
        if not pname.startswith("/device:") or "CUSTOM" in pname.upper():
            continue
        seen[pname] = [ln for ln, _ in lines]
        ops = [evs for ln, evs in lines if ln in OP_LINES]
        if not ops:
            continue
        for evs in ops:
            events.extend((dev, name, int(t0), int(dur))
                          for name, t0, dur in evs if dur > 0)
        dev += 1
    n_dev = max(dev, 1)
    tr = Trace(n_dev, 0.0, 0.0, events, seen)
    if events:
        span = (max(t0 + d for _, _, t0, d in events)
                - min(t0 for _, _, t0, _ in events)) / 1e9
        tr.window_s = window_s if window_s else span
        tr.busy_s = tr.union_s()
    elif window_s:
        tr.window_s = window_s
    return tr


def load(path: str, window_s: float | None = None) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = []
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                lines.append((line.name,
                              [(ev.name, ev.start_ns, ev.duration_ns)
                               for ev in line.events]))
        planes.append((plane.name, lines))
    return reduce_planes(planes, window_s)
