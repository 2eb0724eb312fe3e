"""Model FLOP/s utilisation of the ``solar`` step: the step's model
operations from its shapes (``flops_kda.train_flops``: the delta rule's
projections, convolutions and chunked scan, gated grouped-query attention
over the allowed causal pairs, the router, the shared experts, the routed
experts over the pairs held at the uniform-routing expectation and the
untied head, nothing recomputed counted) over device step time x chips x
the bf16 peak."""


def read(trace, spans, counters, cell):
    shape = cell.get("shape") or {}
    if (not trace or not trace.get("step_device_s") or not cell.get("peaks")
            or "kda_layers" not in shape):
        return None
    from chipbench.flops_kda import train_flops

    shape = {k: v for k, v in shape.items() if k != "dtype_bytes"}
    least = train_flops(**shape) / (
        counters["chips"] * cell["peaks"]["flops_bf16"])
    return 100.0 * least / trace["step_device_s"]
