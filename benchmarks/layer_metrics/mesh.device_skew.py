"""mesh.device_skew (ratio; layer mesh plane; moves ops_per_s; lower is
better): the rows the busiest device's shards served in routed launches
over the window, over the mean of all devices' (1.0 = even) — what a Zipf
batch puts on the mesh.  From node status `pipeline.mesh.rows_by_device`;
a maximum, which the generic `status_delta` reader cannot express;
nothing where the program has no such counters."""

from benchmarks.readers import status_delta


def read(ctx) -> float | None:
    pair = ctx.status.get("window")
    if not pair:
        return None
    by = ((pair[1].get("pipeline") or {}).get("mesh") or {}).get(
        "rows_by_device")
    if not by:
        return None
    rows = [status_delta.total(
        [{"path": f"pipeline.mesh.rows_by_device.{d}"}], *pair) for d in by]
    if None in rows or sum(rows) <= 0:
        return None
    return max(rows) / (sum(rows) / len(rows))
