"""Median device time of the fused step program (its events on the
trace's ``XLA Modules`` line), mean over the devices."""


def read(trace, spans, counters, cell):
    if not trace or not trace["step_device_s"]:
        return None
    return 1e3 * trace["step_device_s"]
