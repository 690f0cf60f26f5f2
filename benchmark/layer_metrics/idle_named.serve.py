"""Device: of the traced window's idle time on chip 0 (gaps of 50 us and more
between two operations), the share that lies under one of the engine's leaf
phase spans (any ``engine.*`` but ``engine.tick``): how much of the idle time
the trace can name. None where the window has no such gap, as in ``chat-open``,
whose device never waits that long (so the manifest lists
``longprompt-closed`` alone)."""
from benchmark import span_reader


def read(trace, stats, record):
    spans = span_reader.load(trace)
    if spans is None or record.get("kind") != "serve":
        return None
    return span_reader.idle_named(trace, spans, "engine.")
