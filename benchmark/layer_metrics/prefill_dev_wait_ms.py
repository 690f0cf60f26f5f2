"""Engine tick: median, over the traced window's prefill programs, of how long
a dispatched program sat on the device behind work queued before it: the
execution's start on chip 0 (``XLA Modules``, ``jit_engine_prefill*``) minus
the end of the ``engine.prefill_dispatch`` span that started it. Prints the
same for the programs that end a prompt (``final`` 1: the wait inside a
request's ``first`` part) and how many executions were paired."""
import statistics

from benchmark import first_tokens, span_reader


def read(trace, stats, record):
    spans = span_reader.load(trace)
    if spans is None or record.get("kind") != "serve":
        return None
    waits = first_tokens.prefill_waits(spans)
    if not waits:
        return None
    lo, hi = spans.window
    inside = sum(1 for m in (spans.modules[0] if spans.modules else [])
                 if m.name.startswith("jit_engine_prefill") and m.start >= lo and m.end <= hi)
    print(f"prefill programs wholly inside the traced window: {inside}; "
          + first_tokens.describe_waits(waits), flush=True)
    return statistics.median(w for _s, _m, w in waits)
