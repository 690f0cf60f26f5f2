"""Jitted steps: the least time the chip's memory bandwidth allows the traced
window's decode segments, over the device time they took. Least bytes by
``hybrid_costs.decode_segment_bytes`` from each ``engine.decode_dispatch`` span
(``k``, ``rows``, ``take``, ``keys``), the HBM peak from ``peaks.json``, the
time from the ``jit_engine_decode_seg<k>`` execution the span started. A
family without such costs, or a program whose spans lack ``keys``, reads None."""
from benchmark import hybrid_costs, kernel_costs, span_reader


def read(trace, stats, record):
    spans = span_reader.load(trace)
    peak = kernel_costs.load_peaks().get(record.get("device_kind"))
    config = record.get("config", {})
    if spans is None or peak is None or "layer_types" not in config:
        return None
    least = seconds = 0.0
    for s, m in hybrid_costs.paired(spans, "engine.decode_dispatch", "jit_engine_decode_seg"):
        if not all(k in s.stats for k in ("k", "rows", "take", "keys")):
            return None
        least += hybrid_costs.decode_segment_bytes(
            config, int(s.stats["k"]), int(s.stats["rows"]), int(s.stats["take"]),
            int(s.stats["keys"])) / peak["hbm_bytes_per_s"]
        seconds += m.end - m.start
    if not seconds:
        return None
    print(f"decode segments paired with their dispatch: {seconds:.4f} s on chip 0, "
          f"{least:.4f} s least by bytes", flush=True)
    return 100.0 * least / seconds
