"""Of the sub-tiles of one head's scores under the window mask, the
share the flash kernels skip (dead / (dead + cut + full)), by the
program's own plan for the cell's sequence length and window
(``ops/attention_pallas.flash_tiles``: the dictionary of the window
layer's ``attn.flash_tiles`` row). A count, the same on any backend.
Absent where the program has no such plan or the configuration no
window layer."""


def read(trace, spans, counters, cell):
    shape, config = cell.get("shape") or {}, cell.get("config") or {}
    if not shape.get("window_layers") or not config.get("sliding_window"):
        return None
    try:
        from pytorch_ps_mpi_tpu.ops.attention_pallas import flash_tiles
    except ImportError:
        return None
    plan = flash_tiles(("window", int(config["sliding_window"])),
                       shape["seq"], shape["seq"], config["dtype"])
    if plan is None:
        return None
    return 100.0 * plan["dead"] / (plan["dead"] + plan["cut"] + plan["full"])
