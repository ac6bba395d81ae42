"""kernels.mesh_gather_roofline (%; layer kernels; device_trace; moves
ops_per_s): the least time the MESH needs to move the head rows its routed
gathers served in the traced span — rows x `work_model.head_row_bytes` /
(one chip's peak bytes per second x the number of devices) — over the
seconds in which an operation of `jit_antidote_mesh_gather` ran (union on
each device, mean over the devices).  Memory-bound: a gather.

The generic `module_match` roofline divides by ONE chip's peak, which
would overstate a four-device program fourfold: this is its reading over
the number of device planes in the trace (the rows are
`pipeline.reads.gather` over the traced span: in this cell every gather is
a routed one).  Nothing — never 0 — where no launch of the program is in
the trace or no row was gathered."""

from benchmarks.readers import module_match, xplane_spans

ONE_CHIP = {"module": "antidote_mesh_gather", "value": "roofline",
            "work": {"rows": [{"path": "pipeline.reads.gather"}],
                     "bytes_per_row": "head_row", "type": "set_aw"}}


def read(ctx) -> float | None:
    share = module_match.read(ONE_CHIP, ctx)
    if share is None:
        return None
    return share / len(xplane_spans.device_planes(xplane_spans.planes(ctx)))
