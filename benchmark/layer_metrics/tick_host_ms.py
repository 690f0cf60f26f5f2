"""Engine tick: median host time per scheduler tick, as
``engine.pipeline_stats()`` keeps it over its recent ticks."""


def read(trace, stats, record):
    return (stats.get("pipeline") or {}).get("host_ms_p50")
