"""Engine tick: mean, over the window's requests, of the ``first`` part of the
time to first token: the dispatch of the request's final prefill program to its first token on the host (``t_final`` to the stamp of ``ttft_ms``),
by the engine's own record (``benchmark/first_tokens.py``)."""
from benchmark import first_tokens


def read(trace, stats, record):
    return first_tokens.part_mean(stats, record, "first")
