"""Seconds of ``setup.serve`` that are the server's own: entry of
``serve`` to the first published version, less the wait inside
``setup.serve.first_update`` for the first gradient to arrive (the
worker's set-up, not the server's)."""

from chipbench.setup_phases import phase_s, summary


def read(trace, spans, counters, cell):
    whole = phase_s(spans, cell, "setup.serve")
    return whole and whole - summary(spans, cell)["serve_wait_s"]
