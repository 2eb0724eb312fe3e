"""Model FLOP/s utilisation of the ``lfm2`` step: the step's model
operations from its shapes (``flops_lfm2.train_flops``: the short
convolutions' projections and tap sums, grouped-query attention over the
allowed causal pairs, the dense SwiGLU, the router, the routed experts
over the pairs held at the uniform-routing expectation and the tied head,
nothing recomputed counted) over device step time x chips x the bf16
peak."""

from chipbench.lfm2_trace import shape_of


def read(trace, spans, counters, cell):
    shape = shape_of(cell)
    if (not trace or not trace.get("step_device_s") or not cell.get("peaks")
            or shape is None):
        return None
    from chipbench.flops_lfm2 import train_flops

    shape = {k: v for k, v in shape.items() if k != "dtype_bytes"}
    least = train_flops(**shape) / (
        counters["chips"] * cell["peaks"]["flops_bf16"])
    return 100.0 * least / trace["step_device_s"]
