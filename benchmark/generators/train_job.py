"""A training job through ``Trainer.fit``: sequences from a seeded token file
through the repo's loader, a fixed batch, as many steps as fill the window.

Set-up builds one object, the compiled step with its state. It drives it from
the seed through its first steps through the window's own call and feed (the
comparison with the reference reads those), takes a few more for the step time,
and hands the same object to the window. ``fit`` takes a step count, not a
deadline, so the measured call runs the number of steps that fills the window;
every step ends in a device barrier, and the rate is taken over the steps that
ended inside the window and the time up to the last of them. The mix's
``barrier_lag`` (0 or 1) says whether a step's barrier is taken before or after
the next step is dispatched.
"""

from __future__ import annotations

import gc
import math
import statistics
import time
from pathlib import Path
from typing import Any, Dict, Iterator, List

import numpy as np

from benchmark import correctness, families


def write_token_file(path: Path, mix: Dict[str, Any], seed: int, vocab: int) -> None:
    """``file_tokens`` int32 ids: ``distinct_ids`` ids of the vocabulary, drawn
    from the seed, in a uniformly random order. Rows all differ; what there is
    to learn is which ids occur, so the loss falls within a window."""
    rng = np.random.default_rng(int(seed))
    ids = rng.choice(vocab, size=int(mix["distinct_ids"]), replace=False)
    tokens = ids[rng.integers(0, len(ids), int(mix["file_tokens"]))].astype(np.int32)
    path.parent.mkdir(parents=True, exist_ok=True)
    tokens.tofile(path)


def recording(data: Iterator, keep: List[np.ndarray], first: int) -> Iterator:
    """The feed, with its first ``first`` batches copied for the reference."""
    for batch in data:
        if len(keep) < first:
            keep.append(np.array(batch))
        yield batch


def run_cell(ctx: Any) -> Dict[str, Any]:
    config, mix = ctx.config, ctx.mix
    family = families.load(config, needs=("enable_cache", "weights", "train_program", "train_follow"))
    check_steps = int(mix["check_steps"])
    marks = [("imports", time.perf_counter())]
    family.enable_cache(ctx.root)
    program = family.train_program(config, family.weights(ctx.seed, config), mix, ctx.chips)
    marks.append(("weights_and_trainer", time.perf_counter()))
    path = ctx.root / ".cache" / "bench_data" / f"{ctx.cell['name']}.bin"
    write_token_file(path, mix, ctx.seed, int(config["vocab_size"]))
    batches: List[np.ndarray] = []
    loader = program.batches(str(path), ctx.seed)
    feed = recording(iter(loader), batches, check_steps)

    # the first steps, through the window's own call and feed: what is compared
    lag = int(mix.get("barrier_lag", 0))
    losses: List[float] = []
    program.run_steps(feed, 1, losses.append, lag=lag)
    decay = program.first_moment_decay
    first_grad = {k: v / (1 - decay) for k, v in program.first_moment_norms().items()}
    first_moment = program.first_moment_host()
    if check_steps > 1:
        program.run_steps(feed, check_steps - 1, losses.append, lag=lag)
    change = program.change_norms(family.weights(ctx.seed, config))
    marks.append(("first_steps_and_readings", time.perf_counter()))
    seen = {"losses": list(losses), "first_grad_norms": first_grad, "change_norms": change,
            # after one step the first moment is (1 - b1) times the gradient the optimizer got
            "first_grad": first_moment, "first_grad_scale": 1.0 / (1 - decay)}

    ends: List[float] = []
    program.run_steps(feed, int(mix["timing_steps"]), lambda _l: ends.append(time.perf_counter()), lag=lag)
    step_s = statistics.median(np.diff(ends)) if len(ends) > 1 else 1.0
    n_steps = math.ceil(ctx.seconds / step_s * 1.03) + 1

    window_losses: List[float] = []
    ends = []

    def on_end(loss: float) -> None:
        window_losses.append(loss)
        ends.append(time.perf_counter())

    marks.append(("timing_steps", time.perf_counter()))
    t0 = ctx.begin_window()
    print("setup phases (s since the one before; the first since process start): " + ", ".join(
        f"{name} {t - prev:.2f}" for (name, t), prev in zip(marks, [ctx.process_t0] + [m[1] for m in marks])),
        flush=True)
    del program.host_ms[:]
    try:
        program.run_steps(feed, n_steps, on_end, lag=lag)
    finally:
        ctx.end_window()
    inside = [e for e in ends if e - t0 <= ctx.seconds]
    tokens_per_step = program.global_batch * program.seq_len
    ctx.note_memory_peak()
    host_ms = list(program.host_ms)
    stats = {"attn_impl": program.attn_impl, "tokens_per_step": tokens_per_step,
             "seq_len": program.seq_len, "global_batch": program.global_batch}
    program.close()
    if hasattr(loader, "close"):
        loader.close()  # the native loader's prefetch threads end here
    del program, feed, loader
    gc.collect()
    step_ms = [1e3 * d for d in np.diff([t0] + inside)]
    if len(step_ms) > 3:
        half = len(step_ms) // 2
        print(f"step_ms median by half of the window: {statistics.median(step_ms[:half]):.2f} "
              f"{statistics.median(step_ms[half:]):.2f}; quartiles "
              + " ".join(f"{q:.2f}" for q in statistics.quantiles(step_ms, n=4)), flush=True)
    finite = [math.isfinite(x) for x in window_losses[: len(inside)]]
    record = {
        "kind": "train",
        # the window ends with the last step that finished inside --seconds
        "window_s": (inside[-1] - t0) if inside else float(ctx.seconds),
        "steps": len(inside),
        "tokens_in_window": len(inside) * tokens_per_step,
        "step_ms": step_ms,
        "step_ms_median": statistics.median(step_ms) if step_ms else float("nan"),
        # the host's own time a step, from one step's callback to the next's
        "host_ms_median": statistics.median(host_ms) if host_ms else float("nan"),
        "loss_first": window_losses[0] if window_losses else float("nan"),
        "loss_last": window_losses[len(inside) - 1] if inside else float("nan"),
        "attempted": len(inside),
        "failed": finite.count(False),
    }
    t_check = time.perf_counter()
    record["compared"] = correctness.check_trained(
        ctx.seed, config, batches, seen, record, ctx.limits)
    record["check_s"] = time.perf_counter() - t_check
    return {"record": record, "stats": stats}
