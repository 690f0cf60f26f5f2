"""Read the numbers a cell's limits are set from, by hand, on the chip:
``python3 benchmark/control.py --workload chat-open --seeds 101,102,... --seconds 12``.

For each seed, in one process: a sound run of the cell's program at the cell's
own load (a short window, long enough to finish the mix's longest requests),
the numbers its comparison reads, and the same numbers for the control: the
reference in int8, put in the program's place, reading at each position of the
same prompts and tokens the gap of the token it puts first. A limit belongs
above the sound runs' largest and below the control's smallest, with room on
both sides (``PERF.md`` section 2). Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="how many of the seeds also read the control")
    ap.add_argument("--manifest", default=str(ROOT / "BENCHMARK.json"))
    ap.add_argument("--allow-cpu", action="store_true", help="for the tiny test manifest only")
    args = ap.parse_args()
    import importlib

    import numpy as np

    from benchmark import correctness, families
    from benchmark.generators import _serve
    from benchmark import run as harness

    manifest = harness.load_json(Path(args.manifest))
    seeds = [int(s) for s in args.seeds.split(",")]
    ctx = harness.Context(ROOT, manifest, args.workload, seeds[0], args.seconds, False)
    print("device:", harness.device_info(ctx.chips, not args.allow_cpu), flush=True)
    family = families.load(ctx.config)
    family.enable_cache(ROOT)
    generator = importlib.import_module(f"benchmark.generators.{ctx.mix['generator']}")
    vocab = int(ctx.config["vocab_size"])
    if ctx.mix["generator"] == "train_job":
        # a training cell's sound numbers are printed by its runs; here, the control
        for seed in seeds[: args.control_seeds]:
            path = ROOT / ".cache" / "bench_data" / f"control-{args.workload}.bin"
            generator.write_token_file(path, ctx.mix, seed, vocab)
            tokens = np.fromfile(path, dtype=np.int32)
            rng = np.random.default_rng([seed, 3])
            rows, seq = int(ctx.mix["global_batch"]), int(ctx.mix["seq_len"])
            batches = [np.stack([tokens[o: o + seq] for o in rng.integers(0, len(tokens) - seq, rows)])
                       for _ in range(int(ctx.mix["check_steps"]))]
            for precision in ("int8", "bfloat16"):
                print(json.dumps({"seed": seed, f"control_{precision}":
                                  correctness.control_trained(seed, ctx.config, batches, precision)}),
                      flush=True)
        return 0
    for n, seed in enumerate(seeds):
        program = family.serve_program(ctx.config_name, ctx.config, family.weights(seed, ctx.config))
        _serve.warm_up(program, ctx.mix, vocab)
        requests = generator.drive(program, ctx.mix, seed, args.seconds, vocab, time.perf_counter())
        program.close()
        del program
        gc.collect()
        sample = correctness.pick_sample(requests, seed, **ctx.limits.get("sample", {}))
        tree = family.weights(seed, ctx.config)
        out = {"seed": seed, "requests": len(requests),
               "failed": sum(1 for r in requests if not r["ok"]),
               "sampled_tokens": sum(r["n_out"] for r in sample),
               "sound": correctness.gap_numbers(correctness.served_gaps(tree, ctx.config, sample))}
        if n < args.control_seeds:
            out["control_int8"] = correctness.gap_numbers(
                correctness.control_gaps(tree, ctx.config, sample))
            out["control_int8_every_position"] = correctness.gap_numbers(
                correctness.control_gaps(tree, ctx.config, sample, every_position=True))
            out["control_bfloat16"] = correctness.gap_numbers(
                correctness.control_gaps(tree, ctx.config, sample, "bfloat16"))
        del tree
        gc.collect()
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
