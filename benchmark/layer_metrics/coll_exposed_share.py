"""Collectives: time inside all-gather, all-reduce, reduce-scatter (and the
other collectives) during which no other operation ran on that chip, over the
traced window. Nothing to read on one chip."""
from benchmark import trace_reader


def read(trace, stats, record):
    if trace is None or len(trace.devices) < 2 or trace.window_s <= 0:
        return None
    return 100.0 * trace_reader.exposed_collective_seconds(trace) / trace.window_s
