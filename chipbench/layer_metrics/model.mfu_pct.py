"""Model FLOP/s utilisation: the step's model operations from its shapes
(``flops.py``; causal attention at half, nothing recomputed counted)
over device step time x chips x the bf16 peak."""

from chipbench.flops import transformer_train_flops


def read(trace, spans, counters, cell):
    if not trace or not trace["step_device_s"] or not cell.get("shape"):
        return None
    shape = {k: v for k, v in cell["shape"].items()
             if k not in ("head_dim", "dtype_bytes")}
    least = transformer_train_flops(**shape) / (
        counters["chips"] * cell["peaks"]["flops_bf16"])
    return 100.0 * least / trace["step_device_s"]
