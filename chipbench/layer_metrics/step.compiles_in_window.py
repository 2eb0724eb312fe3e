"""Programs the backend was asked for inside the window
(``jax.monitoring``); anything but 0 makes the run incorrect."""


def read(trace, spans, counters, cell):
    return counters.get("compiles_in_window")
