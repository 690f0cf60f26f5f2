"""Jitted steps: the device's time for one decode step, in milliseconds. Over
the traced window's executions of ``jit_engine_decode_seg<k>`` (the ``XLA
Modules`` line of chip 0; ``k`` steps an execution), their device time over
their steps. The client's gap between tokens (``tpot_p50_ms``) holds this and
every stall a prefill puts between two segments."""
from benchmark import span_reader


def read(trace, stats, record):
    spans = span_reader.load(trace)
    if spans is None or record.get("kind") != "serve":
        return None
    seconds = span_reader.decode_step_seconds(spans)
    return None if seconds is None else 1e3 * seconds
