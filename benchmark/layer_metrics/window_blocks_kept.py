"""KV-block manager: window blocks the live rows hold over the blocks their
positions span (the engine's ``kv_blocks.window`` ``held`` and ``spanned``),
sampled with the occupancy every 50 ms of the traced window. 100 means nothing
is released. A program with one kind of block reports no samples."""


def read(trace, stats, record):
    samples = stats.get("window_blocks_samples") or []
    spanned = sum(s for _h, s in samples)
    if not spanned:
        return None
    return 100.0 * sum(h for h, _s in samples) / spanned
