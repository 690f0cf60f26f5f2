"""KV-block manager: the window's increase of the engine's ``kv_preemptions``."""


def read(trace, stats, record):
    return stats.get("kv_preemptions")
