"""Engine tick: mean, over the window's requests, of the ``queue`` part of the
time to first token: enqueue to the row assigned (``t0`` to ``t_row``),
by the engine's own record (``benchmark/first_tokens.py``)."""
from benchmark import first_tokens


def read(trace, stats, record):
    return first_tokens.part_mean(stats, record, "queue")
