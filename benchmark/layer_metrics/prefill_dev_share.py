"""Jitted steps: the share of the traced window in which a prefill program
(``jit_engine_prefill``, ``jit_engine_prefill_from``) ran on chip 0. An earlier
output line of the traced run has every program's executions and seconds."""
from benchmark import span_reader


def read(trace, stats, record):
    spans = span_reader.load(trace)
    if spans is None or record.get("kind") != "serve":
        return None
    return span_reader.program_share(spans, "jit_engine_prefill")
