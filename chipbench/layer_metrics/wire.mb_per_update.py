"""Exact payload one chip or worker contributes to one update: the
codec's ``payload_bits`` (or 4 bytes a parameter) on the mesh, the
server's received bytes over consumed pushes in the async job."""


def read(trace, spans, counters, cell):
    wire = counters.get("wire_bytes_per_update")
    return wire / 1e6 if wire else None
