"""99th percentile (nearest rank), over every request of the window, of due
time -> first token: the reply's ``ttft_ms`` plus the generator's own delay
from due time to hand-over. A failed or shed request misses: it counts as the
whole window. The tail of all requests, which a cell below its knee holds end
to end. Every seed replays one schedule, so the tail is the same requests in
every run, the end of the schedule's worst burst of long prompts, and what
varies is the engine's time on them; lower percentiles stand at the edge of
that burst, where one request more or less moves them by 3% (the 95th, recorded
per layer), and the median is decided by the phase of a decode segment
(PERF.md section 2)."""
from benchmark.stats import percentile


def read(trace, stats, record):
    miss = record["window_s"] * 1e3
    return percentile([r["ttft_ms"] if r["ok"] else miss for r in record["requests"]], 99)
