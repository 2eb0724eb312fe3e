"""Model FLOP/s utilisation of the ``sambay`` step: the step's model
operations from its shapes (``flops_sambay.train_flops``: differential
attention over the allowed pairs, the recurrence as vector operations,
the head at every position, nothing recomputed counted) over device
step time x chips x the bf16 peak."""


def read(trace, spans, counters, cell):
    shape = cell.get("shape") or {}
    if (not trace or not trace.get("step_device_s") or not cell.get("peaks")
            or "mamba_layers" not in shape):
        return None
    from chipbench.flops_sambay import train_flops

    shape = {k: v for k, v in shape.items() if k != "dtype_bytes"}
    least = train_flops(**shape) / (
        counters["chips"] * cell["peaks"]["flops_bf16"])
    return 100.0 * least / trace["step_device_s"]
