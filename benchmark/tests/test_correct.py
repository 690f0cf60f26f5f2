"""`correct` can fail: the control (the reference in int8, put in the
program's place) and a timed path broken underneath both come out as not
correct, at a size a test run can hold."""
import json
from pathlib import Path

import numpy as np
import pytest

from benchmark import correctness, weights
from benchmark import run as harness

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"
TINY = json.loads((DATA / "tiny.json").read_text())
MANIFEST = json.loads((DATA / "manifest.json").read_text())


def _run(workload, seed, seconds=2.0):
    return harness.run(ROOT, MANIFEST, workload, seed, seconds, False, require_tpu=False)


@pytest.fixture(scope="module")
def sound():
    """One sound run of the tiny open-loop cell, with the requests it served."""
    served = []
    from benchmark.generators import _serve

    real = _serve.call

    def recording(*a, **kw):
        rec = real(*a, **kw)
        served.append(rec)
        return rec

    _serve.call = recording
    try:
        result = _run("tiny-open", 2**31 + 77)
    finally:
        _serve.call = real
    return result, served


def test_sound_run_is_correct(sound):
    result, served = sound
    assert result["correct"] and result["failed"] == 0 and result["attempted"] == len(served)
    assert set(result["metrics"]) == {"ttft_p50_ms", "tpot_p50_ms", "out_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_control_in_int8_is_not_correct(sound):
    """The reference in int8, put in the program's place: the token it puts
    first lies further below the float32 reference's best, on average, than the
    limit that the sound run above stays under. Three seeds; the gaps are read
    at every position of the served prompts and tokens (a tiny run serves only
    some hundreds of tokens)."""
    _result, served = sound
    limits = json.loads((DATA / "limits" / "tiny-open.json").read_text())
    sample = correctness.pick_sample(served, 1, **limits["sample"])
    assert sum(r["n_out"] for r in sample) >= 400
    for seed in (1, 2, 3):
        tree = weights.decoder_weights(seed, TINY)
        gaps = correctness.control_gaps(tree, TINY, sample, "int8", every_position=True)
        compared = correctness.compare(correctness.gap_numbers(gaps), limits)
        assert not all(c["ok"] for c in compared), (seed, compared)
        # and the same arithmetic in bfloat16, the configuration's own, passes
        gaps = correctness.control_gaps(tree, TINY, sample, "bfloat16", every_position=True)
        assert all(c["ok"] for c in correctness.compare(correctness.gap_numbers(gaps), limits))


def test_an_altered_token_is_not_correct(monkeypatch):
    """The timed path broken underneath: every served token shifted by one
    where the program hands it over."""
    from benchmark import program

    real = program.ServeProgram.generate

    def altered(self, prompt, max_tokens):
        reply = real(self, prompt, max_tokens)
        if "token_ids" in reply:
            reply = dict(reply, token_ids=[(t + 1) % TINY["vocab_size"] for t in reply["token_ids"]])
        return reply

    monkeypatch.setattr(program.ServeProgram, "generate", altered)
    result = _run("tiny-closed", 5)
    assert result["failed"] == 0 and not result["correct"]
    # the line says whether the closed loop stayed loaded, and ends with each
    # number compared beside its limit
    assert "ran_dry_s" in result and list(result)[-1] == "compared"
    assert not result["compared"]["served_gap_mean"]["ok"]
    assert set(result["compared"]["served_gap_mean"]) == {"value", "limit", "must_be", "ok"}


def test_the_sample_holds_the_longest_request():
    reqs = [{"ok": True, "n_out": n, "prompt": [0] * p, "tokens": [0] * n}
            for p, n in ((10, 5), (90, 9), (20, 300), (5, 5), (7, 7), (8, 8))]
    reqs.append({"ok": False, "n_out": 0, "prompt": [0] * 500, "tokens": []})
    sample = correctness.pick_sample(reqs, 3)
    assert sample[0] is reqs[2] and 4 <= len(sample) <= 8
    assert sample == correctness.pick_sample(reqs, 3)
    assert np.isinf(correctness.gap_numbers(np.zeros((0,)))["served_gap_max"])


# ---- training ---------------------------------------------------------------

TRAIN_LIMITS = json.loads((DATA / "limits" / "tiny-train.json").read_text())


def test_sound_training_run_is_correct():
    result = _run("tiny-train", 2**31 + 11, seconds=1.0)
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 3
    assert set(result["metrics"]) == {"train_tok_s_chip", "setup_s"}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_training_control_in_int8_is_not_correct(seed):
    """The reference with int8 weights, put in the trainer's place: its first
    gradient differs from the float32 reference's by more than the limit, though
    the gap between the norms (and the loss) hardly moves."""
    batches = [np.random.default_rng(seed).integers(0, 16, (2, 64)) for _ in range(2)]
    numbers = correctness.control_trained(seed, TINY, batches)
    compared = {c["name"]: c for c in correctness.compare(numbers, TRAIN_LIMITS)}
    assert not compared["first_grad_diff"]["ok"], compared
    assert compared["first_grad_norm_gap"]["ok"] and compared["param_change_gap"]["ok"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    """The timed path broken underneath: every call trains, then puts the
    parameters back as they were."""
    import jax
    import jax.numpy as jnp

    from benchmark import program

    real = program.TrainProgram.run_steps

    def unchanged(self, data, n_steps, on_step_end=None, **kw):
        before = jax.tree_util.tree_map(jnp.copy, self.state["params"])
        summary = real(self, data, n_steps, on_step_end, **kw)
        self.state["params"] = before
        return summary

    monkeypatch.setattr(program.TrainProgram, "run_steps", unchanged)
    result = _run("tiny-train", 6, seconds=1.0)
    assert not result["correct"]


def test_a_lagged_barrier_hands_over_every_step_in_order():
    """``barrier_lag`` moves when a step's barrier is taken, never which steps
    are counted: both ways give one loss a step, the same losses in the same
    order."""
    from benchmark import program

    mix = json.loads((DATA / "traffic" / "tiny-train.json").read_text())
    seen = {}
    for lag in (0, 1):
        prog = program.TrainProgram(TINY, weights.decoder_weights(5, TINY), mix, 1)
        rng = np.random.default_rng(5)
        data = (rng.integers(0, 16, (2, 64)).astype(np.int32) for _ in range(8))
        seen[lag] = []
        prog.run_steps(data, 1, seen[lag].append, lag=lag)
        prog.run_steps(data, 4, seen[lag].append, lag=lag)
        prog.close()
    assert len(seen[0]) == len(seen[1]) == 5
    assert seen[0] == seen[1] and seen[0][-1] < seen[0][0]
