"""Engine tick: mean, over the window's requests, of the ``backlog`` part of the
time to first token: the row assigned to the dispatch of the request's first prefill program (``t_row`` to ``prefill_t0``),
by the engine's own record (``benchmark/first_tokens.py``)."""
from benchmark import first_tokens


def read(trace, stats, record):
    return first_tokens.part_mean(stats, record, "backlog")
