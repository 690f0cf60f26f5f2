"""Jitted steps: the least time the chip's memory bandwidth allows the traced
window's decode segments of a parallel-block model that holds a share of its
experts, over the device time they took. Least bytes by
``parallel_sparse_costs.decode_segment_bytes`` from each ``engine.decode_dispatch``
span (``k``, ``rows``, ``take``, ``keys``, ``wkeys``) and the ``experts_touched``
of the ``engine.harvest_host`` span with the same ``seq`` (held experts count by
what the kept tokens touched), the HBM peak from ``peaks.json``, the time from
the ``jit_engine_decode_seg<k>`` execution the span started. Another family's
configuration, or a program whose spans lack these, reads None."""
from benchmark import kernel_costs, parallel_sparse_costs, span_reader
from benchmark.hybrid_costs import paired


def read(trace, stats, record):
    spans = span_reader.load(trace)
    peak = kernel_costs.load_peaks().get(record.get("device_kind"))
    config = record.get("config", {})
    if spans is None or peak is None or config.get("family") != "parallel_sparse":
        return None
    touched = {int(s.stats["seq"]): int(s.stats["experts_touched"]) for s in spans.spans
               if s.name == "engine.harvest_host" and "experts_touched" in s.stats}
    least = seconds = 0.0
    for s, m in paired(spans, "engine.decode_dispatch", "jit_engine_decode_seg"):
        if not all(k in s.stats for k in ("k", "rows", "take", "keys", "wkeys", "seq")):
            return None
        if int(s.stats["seq"]) not in touched:
            continue  # harvested after the trace stopped
        least += parallel_sparse_costs.decode_segment_bytes(
            config, int(s.stats["k"]), int(s.stats["rows"]), int(s.stats["take"]),
            int(s.stats["keys"]), int(s.stats["wkeys"]), touched[int(s.stats["seq"])],
        ) / peak["hbm_bytes_per_s"]
        seconds += m.end - m.start
    if not seconds:
        return None
    print(f"decode segments paired with their dispatch and harvest: {seconds:.4f} s on chip 0, "
          f"{least:.4f} s least by bytes", flush=True)
    return 100.0 * least / seconds
