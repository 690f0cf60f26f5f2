"""Trainer: the host's own turn a step, in milliseconds: median over the traced
window's ``train.step`` spans of their ``train.data`` (the loader and the
batch's transfer) plus ``train.dispatch`` (the compiled step's call). It hides
behind the device while the device has a step queued; it is what a faster
device would expose."""
from benchmark import span_reader


def read(trace, stats, record):
    spans = span_reader.load(trace)
    if spans is None or record.get("kind") != "train":
        return None
    return span_reader.train_host_ms(spans)
