"""What a decode segment costs on the device, from a traced benchmark run:

    python3 benchmark/run.py --workload chat-open --seed 7 --seconds 51 --trace 1
    JAX_PLATFORMS=cpu python3 scripts/segment_costs.py chat-open [checkout]

Reads the capture the traced run left under ``.cache/bench_trace/<cell>/`` of
this checkout, or of the one named (a copy of the parent commit, say), through
``benchmark/span_reader.py``; pairs every ``jit_engine_decode_seg<k>``
execution with the ``engine.decode_dispatch`` span that dispatched it (in
order, as ``benchmark/hybrid_costs.paired`` does), and prints the device time
of a segment by its length ``k`` and its view's ``span``. Where a span was run
at two lengths it fits ``time = k * s + F``: ``s`` a step, ``F`` what a
segment costs whatever its length (PERF.md section 5). Where the dispatch says
what its attention ``read`` (the decoder's kernel on a chip: the keys fetched,
all steps together) it prints every segment's ``read`` beside its ``span`` and
device time, and fits a step's time to the keys a step reads. Beside it: how many
segments were short, by the reason the tick gave (``short``), the backlog it
saw, the prefill programs by bucket and then one by one (``req``, ``base``,
``final`` of the ``engine.prefill_dispatch`` span that dispatched each, its
device time and how long it waited on the device behind work queued before it:
the pairing ``prefill_dev_wait_ms`` reads, ``benchmark/first_tokens.py``), and
what a segment runs outside its step loop (the operations of an execution that
lie in no ``while``), a segment. Reads files only; needs no chip.
"""

from __future__ import annotations

import glob
import os
import statistics
import sys
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main(argv) -> int:
    from benchmark import first_tokens, hybrid_costs, span_reader

    cell = argv[1] if len(argv) > 1 else "*"
    root = Path(argv[2]).resolve() if len(argv) > 2 else ROOT
    paths = glob.glob(str(root / ".cache" / "bench_trace" / cell / "plugins" / "profile"
                          / "*" / "*.xplane.pb"))
    if not paths:
        print(f"no capture under .cache/bench_trace/{cell}/")
        return 1
    path = max(paths, key=os.path.getmtime)
    spans = span_reader.parse(path)
    print(span_reader.program_table(spans))

    by = defaultdict(list)  # (k, span) -> [(ms, rows)]
    reads = []  # (keys read a step, ms a step, k, span, rows)
    why = defaultdict(int)
    backlog = defaultdict(list)
    for s, m in hybrid_costs.paired(spans, "engine.decode_dispatch", "jit_engine_decode_seg"):
        k, span = int(s.stats["k"]), int(s.stats.get("span", 0))
        by[(k, span)].append((1e3 * (m.end - m.start), int(s.stats["rows"])))
        if "read" in s.stats:
            reads.append((int(s.stats["read"]) / k, 1e3 * (m.end - m.start) / k, k,
                          span, int(s.stats["rows"])))
        reason = s.stats.get("short", "no")
        why[(k, reason)] += 1
        if "backlog" in s.stats:
            backlog[reason].append(int(s.stats["backlog"]))
    for (k, span), got in sorted(by.items()):
        ms = [t for t, _r in got]
        rows = [r for _t, r in got]
        print(f"decode seg{k} span {span}: n={len(ms)} device ms median "
              f"{statistics.median(ms):.3f} min {min(ms):.3f} max {max(ms):.3f} "
              f"(a step {statistics.median(ms) / k:.3f}); rows scheduled {min(rows)}-{max(rows)}")
    for span in sorted({sp for _k, sp in by}):
        ks = sorted(k for k, sp in by if sp == span)
        med = {k: statistics.median(t for t, _r in by[(k, span)]) for k in ks}
        for a, b in zip(ks, ks[1:]):
            s_ms = (med[b] - med[a]) / (b - a)
            print(f"span {span}: seg{a} {med[a]:.3f} ms, seg{b} {med[b]:.3f} ms -> "
                  f"s = {s_ms:.3f} ms a step, F = {med[a] - a * s_ms:.3f} ms a segment")
    print_by_keys_read(reads)
    print("segments paired, by length and reason: "
          + ", ".join(f"k={k} short={r}: {n}" for (k, r), n in sorted(why.items())))
    for reason, seen in sorted(backlog.items()):
        print(f"backlog seen where short={reason}: median {statistics.median(seen)} "
              f"max {max(seen)} (n={len(seen)})")

    chunks = defaultdict(list)
    waits = first_tokens.prefill_waits(spans)
    for s, m, _wait in waits:
        chunks[(int(s.stats.get("bucket", 0)), int(s.stats.get("span", 0)))].append(
            (1e3 * (m.end - m.start), int(s.stats.get("tokens", 0))))
    for (bucket, span), got in sorted(chunks.items()):
        ms = [t for t, _n in got]
        print(f"prefill bucket {bucket} span {span}: n={len(ms)} device ms median "
              f"{statistics.median(ms):.3f} min {min(ms):.3f} max {max(ms):.3f}; "
              f"real tokens median {statistics.median(n for _t, n in got)}")
    for s, m, wait in waits:
        print(f"prefill req {s.stats.get('req', '?')} base {s.stats.get('base', '?')} "
              f"final {s.stats.get('final', '?')} bucket {s.stats.get('bucket', 0)}: "
              f"dispatched at {s.start - spans.window[0]:.4f} s of the window, device "
              f"{1e3 * (m.end - m.start):.3f} ms, waited {wait:.3f} ms on the device")
    if waits:
        print(first_tokens.describe_waits(waits))
    print_outside_the_loop(path, spans)
    return 0


def print_by_keys_read(reads) -> None:
    """Every segment whose dispatch named its ``read``, by the keys a step
    reads, and the line ``ms a step = a + b x thousand keys`` through the
    segments of each length (least squares)."""
    for keys, ms, k, span, rows in sorted(reads):
        print(f"decode seg{k} span {span} rows {rows}: read {keys:.0f} keys a step, "
              f"device {ms * k:.3f} ms, a step {ms:.3f}")
    for k in sorted({r[2] for r in reads}):
        pts = [(keys / 1e3, ms) for keys, ms, kk, _s, _r in reads if kk == k]
        if len({x for x, _y in pts}) < 2:
            continue
        mx, my = statistics.fmean(x for x, _ in pts), statistics.fmean(y for _, y in pts)
        b = sum((x - mx) * (y - my) for x, y in pts) / sum((x - mx) ** 2 for x, _ in pts)
        print(f"seg{k}: a step = {my - b * mx:.3f} ms + {b:.4f} ms a thousand keys read "
              f"(n={len(pts)}, {min(x for x, _ in pts) * 1e3:.0f}-"
              f"{max(x for x, _ in pts) * 1e3:.0f} keys a step)")


def print_outside_the_loop(path, spans) -> None:
    """By segment length: the operations of a ``jit_engine_decode_seg<k>``
    execution that lie inside none of its ``while`` loops, so run once a
    segment whatever ``k`` is (for the 1-step program, whose step is not a
    loop, they include the step's own head and sampling), in ms a segment."""
    from benchmark import trace_reader

    ops = trace_reader.load(path).devices
    if not ops:
        return
    lo, hi = spans.window
    for k in (32, 4, 1):
        runs = [m for m in spans.modules[0] if m.name.startswith(f"jit_engine_decode_seg{k}")
                and m.start >= lo and m.end <= hi]
        if not runs:
            continue
        sums = defaultdict(float)
        for m in runs:
            inside = [o for o in ops[0] if o.start >= m.start and o.end <= m.end]
            loops = [(o.start, o.end) for o in inside if o.opcode == "while"]
            for o in inside:
                if o.opcode in trace_reader.CONTAINERS:
                    continue
                if not any(a <= o.start and o.end <= b for a, b in loops):
                    sums[o.label] += o.self_s
        total = 1e3 * sum(sums.values()) / len(runs)
        top = sorted(sums.items(), key=lambda kv: -kv[1])[:6]
        print(f"seg{k} outside its loops: {total:.3f} ms a segment over {len(runs)} executions: "
              + ", ".join(f"{name} {1e3 * t / len(runs):.3f}" for name, t in top))


if __name__ == "__main__":
    sys.exit(main(sys.argv))
