"""Share of the traced cold-start window in which no operation ran on the
device: 1 - (union of device op intervals) / window."""


def read(r):
    return r.trace.idle_pct if r.trace is not None else None
