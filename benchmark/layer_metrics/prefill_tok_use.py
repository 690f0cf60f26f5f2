"""Engine tick: of the positions the window's prefill dispatches computed
(``slots`` rows of ``bucket`` positions each), the share that held a real
prompt token (``tokens``), from the attributes of the
``engine.prefill_dispatch`` spans."""
from benchmark import span_reader


def read(trace, stats, record):
    spans = span_reader.load(trace)
    if spans is None or record.get("kind") != "serve":
        return None
    return span_reader.use_share(spans, "engine.prefill_dispatch", "tokens", "bucket")
