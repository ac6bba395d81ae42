"""mesh.pad_share (%; layer mesh plane; moves ops_per_s; lower is better):
the share of the routed launches' [P, M'] slots that held no object, over
the window: 100 x (1 - rows / slots) from node status `pipeline.mesh`.
A difference of two counters, which the generic `status_delta` reader
cannot express; nothing where the program has no such counters."""

from benchmarks.readers import status_delta

ROWS = [{"path": "pipeline.mesh.rows"}]
SLOTS = [{"path": "pipeline.mesh.slots"}]


def read(ctx) -> float | None:
    pair = ctx.status.get("window")
    if not pair:
        return None
    rows = status_delta.total(ROWS, *pair)
    slots = status_delta.total(SLOTS, *pair)
    if rows is None or not slots or slots <= 0:
        return None
    return 100.0 * (1.0 - rows / slots)
