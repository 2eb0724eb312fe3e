"""The fullest held expert's pairs over the mean of the held experts,
from the program's router on the batches of steps 1-3 (mean over steps
and layers; outside the window, by the job's comparison): 1.0 is a
perfectly even load."""


def read(trace, spans, counters, cell):
    return counters.get("moe_load_max_over_mean")
