"""Read what a serve cell's comparison reads of a planted fault, by hand, on the chip:
``python3 benchmark/planted.py --workload codechat-open --fault token --seeds 201,202 --seconds 12``.

``served_gap_max`` is held against a fault, not against a precision (``PERF.md``
section 2), so its limit needs a reading above it as the mean's has the int8
control's. For each seed, in one process: the cell's program at the cell's own
load with one fault planted in it, then the comparison a benchmark run makes
(``correctness.check_served``'s sample, gaps and ``compare``) with the cell's
limits as they stand: each number beside its limit, ``ok`` or not. The faults,
each planted on the runner the engine calls and nowhere in the engine:

- ``token``: the sampler of a request's first token hands out the next token
  id (another token than the best, as good as a random one). Every request
  holds ONE altered token and everything after it is decoded from it, so the
  reference sees a sound continuation of an altered token. ``altered`` lists
  that token's own gap for each sampled request: what the comparison reads
  when its sample holds a single altered token.
- ``block`` / ``wblock``: in every upload of the block tables, one entry of
  each live row's table points at another live row's block: in the full pool
  the block of the row's first keys, in the windowed pool the block two behind
  the row's newest. The row then
  reads, and where it is still writing there writes, another row's keys.
- ``int8``: no fault in the program; the reference in int8 is put in its place
  at the sampled positions (``control.py``'s first arm alone, which is the
  one a limit is set from, through ``compare``), the sound run's own numbers
  beside it under ``sound``. ``none``: a sound run and its numbers.

A limit of ``served_gap_max`` belongs under the smallest reading of a planted
fault and above the sound runs' largest. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def plant_token(runner, vocab: int) -> None:
    real = runner.sample_first
    runner.sample_first = lambda logits, temps, key: (real(logits, temps, key) + 1) % vocab


def plant_block(runner, windowed: bool) -> None:
    import numpy as np

    real = runner.upload_mirrors

    def upload(bt, pos=None, wbt=None):
        sound = np.asarray(wbt if windowed else bt)
        table = sound.copy()
        live = [r for r in range(len(sound)) if sound[r].any()]
        for r, other in zip(live, live[1:] + live[:1]):
            # the entry: the full pool's block of a row's first keys, the
            # windowed pool's two behind the newest the row holds
            mine, theirs = (max(0, int(np.flatnonzero(sound[x])[-1]) - 2) if windowed else 0
                            for x in (r, other))
            if other != r and sound[other, theirs]:
                table[r, mine] = sound[other, theirs]
        return real(*((bt, pos, table) if windowed else (table, pos, wbt)))

    runner.upload_mirrors = upload


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True, choices=("token", "block", "wblock", "int8", "none"))
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--manifest", default=str(ROOT / "BENCHMARK.json"))
    ap.add_argument("--allow-cpu", action="store_true", help="for the tiny test manifest only")
    args = ap.parse_args()

    import numpy as np

    from benchmark import correctness, families
    from benchmark import run as harness
    from benchmark.generators import _serve

    manifest = harness.load_json(Path(args.manifest))
    seeds = [int(s) for s in args.seeds.split(",")]
    ctx = harness.Context(ROOT, manifest, args.workload, seeds[0], args.seconds, False)
    print("device:", harness.device_info(ctx.chips, not args.allow_cpu), flush=True)
    family = families.load(ctx.config)
    family.enable_cache(ROOT)
    generator = importlib.import_module(f"benchmark.generators.{ctx.mix['generator']}")
    vocab = int(ctx.config["vocab_size"])
    for seed in seeds:
        program = family.serve_program(ctx.config_name, ctx.config, family.weights(seed, ctx.config))
        _serve.warm_up(program, ctx.mix, vocab)  # sound: the fault is in the window alone
        runner = program.engine._runner
        if args.fault == "token":
            plant_token(runner, vocab)
        elif args.fault in ("block", "wblock"):
            plant_block(runner, args.fault == "wblock")
        requests = generator.drive(program, ctx.mix, seed, args.seconds, vocab, time.perf_counter())
        program.close()
        del program, runner
        gc.collect()
        sample = correctness.pick_sample(requests, seed, **ctx.limits.get("sample", {}))
        tree = family.weights(seed, ctx.config)
        gaps = [correctness.served_gaps(tree, ctx.config, [r]) for r in sample]
        numbers = correctness.gap_numbers(np.concatenate(gaps))
        out = {"seed": seed, "fault": args.fault, "requests": len(requests),
               "failed": sum(1 for r in requests if not r["ok"]),
               "sampled_tokens": sum(r["n_out"] for r in sample)}
        if args.fault == "int8":
            out["sound"] = numbers
            numbers = correctness.gap_numbers(correctness.control_gaps(tree, ctx.config, sample))
        out["compared"] = correctness.compare(numbers, ctx.limits)
        if args.fault == "token":
            out["altered"] = [float(g[0]) for g in gaps]
        del tree
        gc.collect()
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
