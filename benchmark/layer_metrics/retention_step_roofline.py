"""Pallas kernels: the least time the chip's memory bandwidth allows the state
kernel of the traced window's decode segments, over the device time its calls
took. Least bytes: over the decode segments the capture holds whole
(``retention_costs.whole_segments``: an ``engine.decode_dispatch`` span, its
``jit_engine_decode_seg<k>`` execution and the ``retention_step_rows`` custom
calls inside it on chip 0), ``rows`` x ``k`` steps of a scheduled row, each
reading and writing the row's packed slab once in every layer
(``retention_costs.slab_bytes``). Time: those calls'. A program without that
kernel (a CPU, the parent) reads None."""
from benchmark import kernel_costs, retention_costs, span_reader


def read(trace, stats, record):
    spans = span_reader.load(trace)
    peak = kernel_costs.load_peaks().get(record.get("device_kind"))
    config = record.get("config", {})
    if spans is None or peak is None or config.get("family") != "retention":
        return None
    layers = retention_costs.sizes_of(config)["L"]
    slab = retention_costs.slab_bytes(config)
    least = seconds = 0.0
    calls = 0
    for s, _m, kernels in retention_costs.whole_segments(trace, spans, config):
        if "rows" not in s.stats:
            return None
        least += int(s.stats["rows"]) * int(s.stats["k"]) * layers * 2.0 * slab \
            / peak["hbm_bytes_per_s"]
        seconds += sum(o.end - o.start for o in kernels)
        calls += len(kernels)
    if not seconds:
        return None
    print(f"{retention_costs.STEP_KERNEL}: {calls} calls inside whole decode segments, "
          f"{seconds:.4f} s on chip 0, {least:.4f} s least by bytes", flush=True)
    return 100.0 * least / seconds
