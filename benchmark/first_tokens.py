"""Where the window's requests' time to first token went, by the engine's own
record, and how long each prefill program waited on the device.

Since PR 39 the engine stamps every request's way to its first token and keeps
the newest 1,024 records in ``pipeline_stats()["first_tokens"]``, oldest first:
``(seq, queue, backlog, chunks, first, n_chunks, segments, steps)``, the four
parts in ms summing to the reply's ``ttft_ms`` (``docs/observability.md``,
"Where a request's time to first token goes"). ``ServeProgram.stats()`` hands
``pipeline`` through whole. The generator returns only after every request it
sent has come back, so the window's records are the last ``n``, ``n`` the
requests that were ``ok``; the warm-up's lie before them. A window of more
than 1,024 finished requests outgrows the record and reads None (the largest
cell finishes 230). A program from before PR 39 keeps no such record and
every reader here returns None.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark import hybrid_costs
from benchmark.stats import percentile

PARTS = ("queue", "backlog", "chunks", "first")
#: the parts' sum may differ from the replies' by this much a request: both
#: are rounded to the microsecond
SUM_TOLERANCE_MS = 0.01


def window_records(stats: Dict[str, Any], record: Dict[str, Any]) -> Optional[List[Sequence[float]]]:
    """The records of the window's ``ok`` requests, or None with the reason
    printed: no record kept, fewer records than requests, or parts that do
    not sum to the replies' time to first token (another request's records
    among the last ``n``)."""
    kept = (stats.get("pipeline") or {}).get("first_tokens")
    ok = [r for r in record.get("requests") or [] if r["ok"]]
    if kept is None or not ok:
        return None
    if len(kept) < len(ok):
        print(f"first_tokens: {len(kept)} records for {len(ok)} finished requests", flush=True)
        return None
    mine = kept[len(kept) - len(ok):]
    parts = sum(sum(r[1:5]) for r in mine)
    replies = sum(r["ttft_ms"] - r["late_ms"] for r in ok)
    if abs(parts - replies) > SUM_TOLERANCE_MS * len(ok):
        print(f"first_tokens: the last {len(ok)} records' parts sum to {parts:.3f} ms, the "
              f"replies' time to first token to {replies:.3f} ms", flush=True)
        return None
    return mine


def part_mean(stats: Dict[str, Any], record: Dict[str, Any], part: str) -> Optional[float]:
    """Mean of one part over the window's requests, in ms; prints its median
    and 95th percentile and, beside the first part, what the tick put ahead."""
    mine = window_records(stats, record)
    if mine is None:
        return None
    at = 1 + PARTS.index(part)
    values = [float(r[at]) for r in mine]
    mean = statistics.fmean(values)
    print(f"time to first token, {part}: mean {mean:.3f} ms, median "
          f"{statistics.median(values):.3f}, p95 {percentile(values, 95):.3f} (n={len(values)})",
          flush=True)
    if at == 1:
        print("before a first token, medians: "
              + ", ".join(f"{name} {statistics.median(r[n] for r in mine)}"
                          for n, name in ((5, "n_chunks"), (6, "segments"), (7, "steps")))
              + f"; the four parts' means sum to "
              f"{sum(statistics.fmean(r[n] for r in mine) for n in range(1, 5)):.3f} ms",
              flush=True)
    return mean


def prefill_waits(spans: Any) -> List[Tuple[Any, Any, float]]:
    """``(dispatch span, execution, wait in ms)`` for each prefill program of
    the traced window on chip 0, paired in order as ``hybrid_costs.paired``
    pairs decode segments: the execution's start minus the end of the
    ``engine.prefill_dispatch`` span that started it, or 0 where the program
    began before its span closed."""
    return [(s, m, max(0.0, 1e3 * (m.start - s.end)))
            for s, m in hybrid_costs.paired(spans, "engine.prefill_dispatch", "jit_engine_prefill")]


def describe_waits(waits: List[Tuple[Any, Any, float]]) -> str:
    """One line: the programs paired, their wait's median and largest, and
    the median of those that end a prompt (``final`` 1: the wait inside a
    request's ``first`` part)."""
    final = [w for s, _m, w in waits if int(s.stats.get("final", 0))]
    return (f"prefill programs paired with their dispatch {len(waits)}; wait on the device, ms: "
            f"median {statistics.median(w for _s, _m, w in waits):.3f} max "
            f"{max(w for _s, _m, w in waits):.3f}; of the {len(final)} that end a prompt: median "
            f"{statistics.median(final) if final else float('nan'):.3f}")
