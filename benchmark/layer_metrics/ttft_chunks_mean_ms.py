"""Engine tick: mean, over the window's requests, of the ``chunks`` part of the
time to first token: the dispatch of the request's first prefill program to that of its final one (``prefill_t0`` to ``t_final``),
by the engine's own record (``benchmark/first_tokens.py``)."""
from benchmark import first_tokens


def read(trace, stats, record):
    return first_tokens.part_mean(stats, record, "chunks")
