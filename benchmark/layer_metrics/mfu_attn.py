"""Trainer: FLOPs the forward and backward passes require per token
(``kernel_costs.train_flops_per_token``: matmuls and causal attention, nothing
recomputed, no embedding lookup) times tokens per second, over chips times the
peak of ``peaks.json``."""
from benchmark import kernel_costs


def read(trace, stats, record):
    if record.get("kind") != "train" or not record.get("window_s"):
        return None
    peaks = kernel_costs.load_peaks()
    if record["device_kind"] not in peaks:
        return None
    flops = kernel_costs.train_flops_per_token(record["config"], stats["seq_len"])
    rate = record["tokens_in_window"] / record["window_s"]
    return 100.0 * flops * rate / (record["chips"] * peaks[record["device_kind"]]["bf16_flops_per_s"])
