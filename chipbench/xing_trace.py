"""What the ``xing`` family's readers share: the names of its flash
kernels, the scopes of its hyper-connections, and how a reader knows the
cell is this family's.

The latent-attention kernels are the causal flash kernels with keys wider
than the value (192 over 128): ``ops/attention_pallas.py`` names every
kernel whose value is of another width ``flash_wide_*``, which the
``sambay`` family's differential attention carries too, so a reader here
also asks the cell's ``shape`` for ``nope_dim``. Scopes are read
through ``sambay_trace.seconds_per_step``, which leaves out a loop's own
event beside its body's.
"""

from __future__ import annotations

MLA_KERNELS = (r"^%\S*flash_wide_(fwd|dq|dkv)\S* = "
               r".*\[tpu_custom_call\]$")
# inside the prediction module every scope of the model rides behind "mtp."
HC = r"^(mtp\.)?hc\.(mix|sinkhorn)$"


def shape_of(cell: dict) -> dict | None:
    """The cell's FLOP shape where it is a ``xing`` cell's, else None."""
    shape = cell.get("shape") or {}
    return shape if "nope_dim" in shape and "streams" in shape else None


def kernel_seconds(trace: dict | None, cell: dict) -> float | None:
    """Seconds a step of the latent-attention kernels' events; None where
    none ran or the cell is another family's."""
    from chipbench.trace_reduce import seconds_per_step

    if not trace or "by_name" not in trace or shape_of(cell) is None:
        return None
    return seconds_per_step(trace, MLA_KERNELS)


def per_chip(shape: dict, counters: dict) -> dict:
    return dict(shape, rows=shape["rows"] // counters["chips"])
