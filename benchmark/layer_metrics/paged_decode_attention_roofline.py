"""Pallas kernels: the least time the chip's memory bandwidth allows the decode
kernel (``paged_decode_attention``) of the traced window's decode segments of a
parallel-block model, over the device time its calls took: one call a layer a
step, a full layer's over each scheduled row's own blocks, a window layer's over
the run of blocks that ends at the row's position. Least bytes by
``parallel_sparse_costs.kernel_segment_bytes`` over the decode segments the
capture holds whole (``parallel_sparse_costs.whole_segments``: an
``engine.decode_dispatch`` span with ``k``, ``rows``, ``take``, ``read`` and
``wkeys``, its ``jit_engine_decode_seg<k>`` execution and the kernel's custom
calls inside it on chip 0). A program without that kernel on these pools (a CPU,
the parent), or another family's configuration, reads None."""
from benchmark import kernel_costs, parallel_sparse_costs, span_reader


def read(trace, stats, record):
    spans = span_reader.load(trace)
    peak = kernel_costs.load_peaks().get(record.get("device_kind"))
    config = record.get("config", {})
    if spans is None or peak is None or config.get("family") != "parallel_sparse":
        return None
    least = seconds = 0.0
    calls = 0
    for s, _m, kernels in parallel_sparse_costs.whole_segments(trace, spans, config):
        if not all(k in s.stats for k in ("rows", "take", "read", "wkeys")):
            return None
        least += parallel_sparse_costs.kernel_segment_bytes(
            config, int(s.stats["k"]), int(s.stats["rows"]), int(s.stats["take"]),
            int(s.stats["read"]), int(s.stats["wkeys"])) / peak["hbm_bytes_per_s"]
        seconds += sum(o.end - o.start for o in kernels)
        calls += len(kernels)
    if not seconds:
        return None
    print(f"{parallel_sparse_costs.DECODE_KERNEL}: {calls} calls inside whole decode segments, "
          f"{seconds:.4f} s on chip 0, {least:.4f} s least by bytes", flush=True)
    return 100.0 * least / seconds
