"""Find a serving cell's knee, once, by hand, on the chip:
``python3 benchmark/sweep.py --workload chat-open --rates 4,5,6,7,8,10 --seconds 51``.

One process and one set-up; each rate is offered for ``--seconds`` through the
cell's own open-loop generator, then the engine drains. Give it the benchmark's
own window: at 20 s a rate holds some tens of requests, and PR 33's first sweep
read a knee of 5.0 where whole windows then sustained 6.0. The knee is the
highest rate at which the backlog does not grow over the window: requests still
in the engine when the window closes are no more than the batch (the
configuration's ``max_batch``), nothing is shed, and the second half's time to
first token is not far above the first half's. The rate written into the
traffic file is 0.75 of it, rounded down to a quarter request a second, beside
the knee itself (``knee_per_s``) and the sweep's lines (``notes``);
``tests/test_manifest.py`` holds the two numbers to each other. Not part of a
benchmark run.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=11)
    args = ap.parse_args()
    from benchmark import families
    from benchmark import run as harness
    from benchmark.generators import _serve, open_loop
    from benchmark.stats import percentile

    manifest = harness.load_json(ROOT / "BENCHMARK.json")
    ctx = harness.Context(ROOT, manifest, args.workload, args.seed, args.seconds, False)
    print("device:", harness.device_info(ctx.chips, True), flush=True)
    family = families.load(ctx.config)
    family.enable_cache(ROOT)
    program = family.serve_program(ctx.config_name, ctx.config, family.weights(args.seed, ctx.config))
    vocab = int(ctx.config["vocab_size"])
    _serve.warm_up(program, ctx.mix, vocab)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(ctx.mix, rate_per_s=rate)
        t0 = time.perf_counter()
        reqs = open_loop.drive(program, mix, args.seed + i, args.seconds, vocab, t0)
        ok = [r for r in reqs if r["ok"]]
        half = args.seconds / 2
        first = [r["ttft_ms"] for r in ok if r["due_s"] < half]
        second = [r["ttft_ms"] for r in ok if r["due_s"] >= half]
        open_at_end = sum(1 for r in reqs if r["done_s"] > args.seconds)
        print(json.dumps({
            "rate_per_s": rate, "sent": len(reqs), "failed": len(reqs) - len(ok),
            "open_at_window_end": open_at_end, "max_batch": ctx.config["engine"]["max_batch"],
            "shed_so_far": program.stats().get("shed"),
            "gen_late_p95_ms": percentile([r["late_ms"] for r in reqs], 95),
            "drain_s": max(r["done_s"] for r in reqs) - args.seconds,
            "ttft_ms_median_first_half": statistics.median(first) if first else None,
            "ttft_ms_median_second_half": statistics.median(second) if second else None,
            "ttft_p95_ms": percentile([r["ttft_ms"] for r in ok], 95) if ok else None,
            "tpot_ms_median": statistics.median(
                [(r["latency_ms"] - r["ttft_ms"]) / (r["n_out"] - 1) for r in ok if r["n_out"] > 1]),
            "out_tok_s": sum(r["n_out"] for r in ok if r["done_s"] <= args.seconds) / args.seconds,
        }), flush=True)
    program.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
