"""Device: 1 - (union of the device's operation intervals) / traced window."""
from benchmark import trace_reader


def read(trace, stats, record):
    return trace_reader.idle_share(trace) if trace is not None else None
