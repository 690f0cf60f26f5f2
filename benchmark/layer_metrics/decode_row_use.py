"""Engine tick: of the row-steps the window's decode segments computed
(``slots`` rows for ``k`` steps each, whatever is active), the share that
delivered a token (``take``: a scheduled row's steps up to its budget), from
the attributes of the ``engine.decode_dispatch`` spans."""
from benchmark import span_reader


def read(trace, stats, record):
    spans = span_reader.load(trace)
    if spans is None or record.get("kind") != "serve":
        return None
    return span_reader.use_share(spans, "engine.decode_dispatch", "take", "k")
