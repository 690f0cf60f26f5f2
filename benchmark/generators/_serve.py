"""What the serving generators share: lengths, requests, the call, the cell's flow.

A traffic mix is a data file (``benchmark/traffic/<mix>.json``). Its sizes are
drawn once from the mix's own ``shape_seed``; ``--seed`` gives the weights and
the token ids. Every seed offers the same prompts, outputs and gaps in the same
order, so runs differ by what the program computes on and not by how much work
they hold or by which request meets which.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Any, Callable, Dict, List

import jax
import numpy as np

from benchmark import correctness, families


def draw_lengths(spec: Dict[str, Any], n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` whole lengths from ``{"dist": "lognormal"|"uniform", ...}``."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(float(spec["median"])), float(spec["sigma"]), n)
    elif spec["dist"] == "uniform":
        x = rng.uniform(lo, hi + 1, n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def draw_requests(mix: Dict[str, Any], n: int, seed: int, vocab: int) -> List[Dict[str, Any]]:
    """``n`` requests: the mix's fixed sequence of sizes, with token ids drawn
    from ``seed``.

    Every seed replays the same sequence of sizes (and, in an open loop, of
    arrival times): a tail over some tens of requests is set by which request
    meets which, and an order drawn afresh, or the same order started at
    another point, moved ``ttft_p95_ms`` by a factor of two between seeds on
    the chip (PERF.md, PR 23). What the seed changes is what the program
    computes on: the weights and every token."""
    shape = np.random.default_rng(int(mix["shape_seed"]))
    prompts = draw_lengths(mix["prompt_tokens"], n, shape)
    outputs = draw_lengths(mix["output_tokens"], n, shape)
    rng = np.random.default_rng(int(seed))
    return [
        {
            "prompt": rng.integers(0, vocab, int(prompts[i])).tolist(),
            "max_tokens": int(outputs[i]),
        }
        for i in range(n)
    ]


def call(program: Any, req: Dict[str, Any], due: float, t0: float) -> Dict[str, Any]:
    """Hand one request to the program and record what a client would see.

    Times are seconds from the window's start ``t0`` (``time.perf_counter``)."""
    with jax.profiler.TraceAnnotation("bench.generate_handover"):
        handed = time.perf_counter()
    reply = program.generate(req["prompt"], req["max_tokens"])
    done = time.perf_counter()
    tokens = reply.get("token_ids") or []
    ok = "error" not in reply and len(tokens) > 0 and reply.get("ttft_ms") is not None
    return {
        "due_s": due - t0,
        "handed_s": handed - t0,
        "done_s": done - t0,
        "late_ms": (handed - due) * 1e3,
        "ok": ok,
        "error": reply.get("error", ""),
        # the reply's ttft is stamped from the engine's arrival, so the
        # generator adds its own delay from due time to hand-over
        "ttft_ms": (reply.get("ttft_ms", 0.0) + (handed - due) * 1e3) if ok else None,
        "latency_ms": (done - due) * 1e3,
        "n_out": len(tokens),
        "prompt": req["prompt"],
        "tokens": list(tokens),
    }


def warm_up(program: Any, mix: Dict[str, Any], vocab: int) -> None:
    """One request for every shape the mix can make the engine compile."""
    rng = np.random.default_rng(0)
    for w in mix["warmup"]:
        reply = program.generate(
            rng.integers(0, vocab, int(w["prompt_tokens"])).tolist(), int(w["max_tokens"]))
        if "error" in reply:
            raise RuntimeError(f"warm-up request failed: {reply['error']}")


def sample_occupancy(program: Any, stop: threading.Event, every_s: float = 0.05) -> List[int]:
    """Active rows, sampled until ``stop``; the traced run's ``batch_occupancy``."""
    rows: List[int] = []
    while not stop.wait(every_s):
        with jax.profiler.TraceAnnotation("bench.stats_sample"):
            rows.append(program.active_rows())
    return rows


def run_cell(ctx: Any, drive: Callable[..., List[Dict[str, Any]]]) -> Dict[str, Any]:
    """Set-up, window, drain, then the comparison with the reference.

    ``drive(program, mix, seed, seconds, vocab, t0)`` offers the mix's load
    for ``seconds`` and returns one record for every request it handed over,
    after the last of them has come back."""
    config, mix = ctx.config, ctx.mix
    family = families.load(config, needs=("enable_cache", "weights", "serve_program", "logits_at"))
    vocab = int(config["vocab_size"])
    family.enable_cache(ctx.root)
    tree = family.weights(ctx.seed, config)
    program = family.serve_program(ctx.config_name, config, tree)
    del tree
    warm_up(program, mix, vocab)
    program.mark_window()
    stop = threading.Event()
    occupancy: List[int] = []
    sampler = None
    if ctx.trace:
        sampler = threading.Thread(
            target=lambda: occupancy.extend(sample_occupancy(program, stop)),
            name="bench-sampler",
        )
    t0 = ctx.begin_window()
    if sampler is not None:
        sampler.start()
    try:
        requests = drive(program, mix, ctx.seed, ctx.seconds, vocab, t0)
    finally:
        stop.set()
        if sampler is not None:
            sampler.join()
        ctx.end_window()
    stats = program.stats()
    stats["occupancy_samples"] = occupancy
    ctx.note_memory_peak()
    program.close()
    del program
    gc.collect()
    record = {
        "kind": "serve",
        "window_s": float(ctx.seconds),
        "requests": requests,
        "attempted": len(requests),
        "failed": sum(1 for r in requests if not r["ok"]),
    }
    t_check = time.perf_counter()
    record["compared"] = correctness.check_served(ctx.seed, config, requests, ctx.limits)
    record["check_s"] = time.perf_counter() - t_check
    return {"record": record, "stats": stats}
