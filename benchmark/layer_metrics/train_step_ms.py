"""Trainer: median time of the window's steps, each ended by a device barrier."""


def read(trace, stats, record):
    return record.get("step_ms_median")
