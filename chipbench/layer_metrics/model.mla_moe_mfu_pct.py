"""Model FLOP/s utilisation of the ``xing`` step: the step's model
operations from its shapes (``flops_xing.train_flops``: latent attention
over the allowed causal pairs, the hyper-connections' products, the
shared expert, the routed experts over the pairs held at the
uniform-routing expectation, the prediction module and both heads,
nothing recomputed counted) over device step time x chips x the bf16
peak."""

from chipbench.xing_trace import shape_of


def read(trace, spans, counters, cell):
    shape = shape_of(cell)
    if (not trace or not trace.get("step_device_s") or not cell.get("peaks")
            or shape is None):
        return None
    from chipbench.flops_xing import train_flops

    shape = {k: v for k, v in shape.items()
             if k not in ("dtype_bytes", "head_dim")}
    least = train_flops(**shape) / (
        counters["chips"] * cell["peaks"]["flops_bf16"])
    return 100.0 * least / trace["step_device_s"]
