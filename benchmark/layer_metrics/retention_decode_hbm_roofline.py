"""Jitted steps: the least time the chip's memory bandwidth allows the traced
window's decode segments of a model whose rows are recurrent state and nothing
else, over the device time they took: the share of the whole step. Least bytes
by ``retention_costs.decode_segment_bytes`` from each ``engine.decode_dispatch``
span (``k``, ``rows``, ``take``), the HBM peak from ``peaks.json``, the time
from the ``jit_engine_decode_seg<k>`` execution the span started, over the
executions the capture holds whole (``retention_costs.whole_segments``).
Another family's configuration, a program without the state kernel (a CPU, the
parent), or spans that lack these, read None."""
from benchmark import kernel_costs, retention_costs, span_reader


def read(trace, stats, record):
    spans = span_reader.load(trace)
    peak = kernel_costs.load_peaks().get(record.get("device_kind"))
    config = record.get("config", {})
    if spans is None or peak is None or config.get("family") != "retention":
        return None
    least = seconds = 0.0
    for s, m, _kernels in retention_costs.whole_segments(trace, spans, config):
        if not all(k in s.stats for k in ("rows", "take")):
            return None
        least += retention_costs.decode_segment_bytes(
            config, int(s.stats["k"]), int(s.stats["rows"]), int(s.stats["take"]),
        ) / peak["hbm_bytes_per_s"]
        seconds += m.end - m.start
    if not seconds:
        return None
    print(f"retention decode segments the capture holds whole: {seconds:.4f} s on chip 0, "
          f"{least:.4f} s least by bytes", flush=True)
    return 100.0 * least / seconds
