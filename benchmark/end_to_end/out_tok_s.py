"""Output tokens delivered inside the window, over the window.

The server does not stream, so a request still decoding when the window closes
counts the share of its tokens that a steady decode from its first token to its
end had reached by then; counting only whole requests would move the rate by a
request's worth (2% here) with the millisecond at which one of them ends."""


def read(trace, stats, record):
    w = record["window_s"]
    total = 0.0
    for r in record["requests"]:
        if not r["ok"]:
            continue
        first, done, n = r["due_s"] + r["ttft_ms"] / 1e3, r["done_s"], r["n_out"]
        if done <= w:
            total += n
        elif first < w:
            total += 1 + (n - 1) * (w - first) / max(done - first, 1e-9)
    return total / w
