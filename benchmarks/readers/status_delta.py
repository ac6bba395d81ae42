"""Generic per-layer reader: paths into node_status(), sampled at the start
and the end of a span, reduced to a ratio (or a difference) of deltas.

Metric file:
  {"reader": "status_delta", "span": "window" | "trace",
   "num": [term, ...], "den": [term, ...] (optional), "scale": 1.0}
term: {"path": "pipeline.stages.parked.sum_ms"}
      {"path": "write_plane.merge_width.count",
       "times": "write_plane.merge_width.mean"}     (product, then delta)
      "optional": true -- a path the status lacks counts as 0
value = scale * sum(delta num) / sum(delta den); nothing when a required
path is missing or the denominator did not advance.
"""

from __future__ import annotations


def lookup(status: dict, path: str):
    cur = status
    for part in path.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur if isinstance(cur, (int, float)) else None


def term_delta(term: dict, pre: dict, post: dict):
    out = []
    for st in (pre, post):
        v = lookup(st, term["path"])
        if v is not None and "times" in term:
            w = lookup(st, term["times"])
            v = None if w is None else v * w
        out.append(v)
    if out[1] is None:
        return 0.0 if term.get("optional") else None
    return out[1] - (out[0] or 0.0)


def total(terms, pre, post):
    acc = 0.0
    for t in terms:
        d = term_delta(t, pre, post)
        if d is None:
            return None
        acc += d
    return acc


def read(spec: dict, ctx) -> float | None:
    pair = ctx.status.get(spec.get("span", "window"))
    if not pair:
        return None
    num = total(spec["num"], *pair)
    if num is None:
        return None
    if "den" not in spec:
        return spec.get("scale", 1.0) * num
    den = total(spec["den"], *pair)
    if not den or den <= 0:
        return None
    return spec.get("scale", 1.0) * num / den
