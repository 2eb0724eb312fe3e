"""Share of the window the worker spent inside ``worker.grad`` (batch,
gradient, ``block_until_ready``): the most the chip can have been busy."""


def read(trace, spans, counters, cell):
    grads = spans.get("worker.grad")
    if not grads:
        return None
    return 100.0 * sum(e["dur"] for e in grads) / counters["window_s"]
