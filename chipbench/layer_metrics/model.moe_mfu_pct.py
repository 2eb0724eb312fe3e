"""Model FLOP/s utilisation of the ``sdar_moe`` step: the step's model
operations from its shapes (``flops_sdar.train_flops``: attention over the
allowed pairs, experts over the pairs held at the uniform-routing
expectation, the head at the noised positions, nothing recomputed
counted) over device step time x chips x the bf16 peak."""

from chipbench.flops_sdar import train_flops


def read(trace, spans, counters, cell):
    if (not trace or not trace.get("step_device_s") or not cell.get("peaks")
            or "experts_held" not in (cell.get("shape") or {})):
        return None
    shape = {k: v for k, v in cell["shape"].items() if k != "dtype_bytes"}
    least = train_flops(**shape) / (
        counters["chips"] * cell["peaks"]["flops_bf16"])
    return 100.0 * least / trace["step_device_s"]
