"""The hybrid state-space family: its file gives what ``families/__init__.py``
asks, its weights come whole from a seed, its costs count what the model's
shapes say, and a tiny cell of it runs through the whole harness on the CPU
and can come out not correct."""
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correctness, families, hybrid_costs
from benchmark import run as harness

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"
TINY = json.loads((DATA / "tiny-hybrid.json").read_text())
MANIFEST = json.loads((DATA / "hybrid_manifest.json").read_text())
GRANITE = json.loads((ROOT / "benchmark" / "configs" / "granite-4.0-h-micro.json").read_text())


def test_the_family_file_gives_what_a_served_family_gives():
    family = families.load(TINY, needs=("enable_cache", "weights", "serve_program", "logits_at"))
    assert family is families.load(GRANITE)
    assert not hasattr(family, "train_program")  # served, not trained
    for name in ("hybrid_weights.py", "hybrid_program.py", "reference/hybrid_ref.py",
                 "hybrid_costs.py"):
        assert name in family.__doc__ and (ROOT / "benchmark" / name).is_file()


def test_weights_are_one_tree_from_the_seed():
    family = families.load(TINY)
    seed = 2**31 + 17
    a, b, c = family.weights(seed, TINY), family.weights(seed, TINY), family.weights(seed + 1, TINY)
    leaves = jax.tree_util.tree_flatten_with_path(a)[0]
    assert len(leaves) == 21 and "lm_head" not in a  # the head is the embedding
    for (path, x), y, z in zip(leaves, jax.tree_util.tree_leaves(b), jax.tree_util.tree_leaves(c)):
        assert np.array_equal(np.asarray(x), np.asarray(y)), path
        name = path[-1].key
        if name not in ("mixer_norm", "mlp_norm", "gate_norm", "final_norm", "D"):
            assert not np.array_equal(np.asarray(x), np.asarray(z)), path
        assert x.dtype == (jnp.float32 if name in ("dt_bias", "A_log", "D") else jnp.bfloat16)
    m = a["mamba"]
    assert m["in_proj_z"].shape == (6, 64, 128) and m["in_proj_xbc"].shape == (6, 64, 160)
    assert m["conv_w"].shape == (6, 4, 160) and a["mlp"]["input_linear"].shape == (8, 64, 256)
    # Mamba-2's own initialisation: A in [1, 16], dt in [1e-3, 1e-1]
    assert 1.0 <= float(jnp.exp(m["A_log"]).min()) and float(jnp.exp(m["A_log"]).max()) <= 16.0
    dt = jax.nn.softplus(m["dt_bias"])
    assert 0.99e-3 <= float(dt.min()) and float(dt.max()) <= 1.01e-1
    # a row times the multiplier has unit norm
    rows = jnp.linalg.norm(a["embed"].astype(jnp.float32), axis=1) * TINY["embedding_multiplier"]
    assert 0.6 < float(rows.min()) and float(rows.max()) < 1.5


def test_the_configuration_is_the_published_one_whole():
    assert GRANITE["reduced"] == {} and GRANITE["family"] == "hybrid_ssm"
    assert (GRANITE["num_hidden_layers"], GRANITE["hidden_size"], GRANITE["vocab_size"],
            GRANITE["shared_intermediate_size"], GRANITE["mamba_d_state"]) == (
                40, 2048, 100352, 8192, 128)
    assert [i for i, k in enumerate(GRANITE["layer_types"]) if k == "attention"] == [5, 15, 25, 35]
    from benchmark import hybrid_program

    cfg = hybrid_program.hybrid_config(GRANITE)
    assert (cfg.periods, cfg.mamba_before, cfg.mamba_after, cfg.ssm_chunk) == (4, 5, 4, 256)
    assert cfg.num_params() == hybrid_costs.matmul_params(GRANITE) + 100352 * 2048 + sum(
        (36 * (4352 * 5 + 3 * 64 + 4096), 80 * 2048, 2048))
    with pytest.raises(ValueError, match="mamba_n_groups"):
        hybrid_program.hybrid_config({**GRANITE, "mamba_n_groups": 8})


def test_costs_are_the_shapes():
    assert hybrid_costs.matmul_params(GRANITE) == 36 * (2048 * 8512 + 4096 * 2048) + 4 * (
        2 * 2048 * 2048 + 2 * 2048 * 512) + 40 * 3 * 2048 * 8192
    assert abs(hybrid_costs.weight_bytes(GRANITE) - 6.38e9) < 0.01e9
    assert hybrid_costs.state_bytes_per_row(GRANITE) == 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    assert hybrid_costs.kv_bytes_per_token(GRANITE) == 8192
    w, s = hybrid_costs.weight_bytes(GRANITE), hybrid_costs.state_bytes_per_row(GRANITE)
    # 32 rows that all keep 4 tokens: the weights four times, every kept token's state twice
    assert hybrid_costs.decode_segment_bytes(GRANITE, 4, 32, 128, 0) == 4 * w + 128 * 2 * s
    # one row of 32 keeps 2 of a 32-step segment: the weights for the steps it needed at least
    assert hybrid_costs.decode_segment_bytes(GRANITE, 32, 2, 3, 100) == (
        2 * w + 3 * 2 * s + 8192 * 100 * 3 / 2)
    assert hybrid_costs.decode_segment_bytes(GRANITE, 4, 0, 0, 0) == 0.0
    per_token = 2 * hybrid_costs.matmul_params(GRANITE) + 36 * (5 * 64 * 64 * 128 + 2 * 4 * 4352)
    assert hybrid_costs.prefill_flops(GRANITE, 1024, 0) == 1024 * per_token
    assert hybrid_costs.prefill_flops(GRANITE, 0, 1000) == 4 * 4.0 * 32 * 64 * 1000


def test_an_execution_is_paired_with_the_span_that_dispatched_it():
    span = lambda name, start, **stats: SimpleNamespace(name=name, start=start, stats=stats)  # noqa: E731
    module = lambda name, start, end: SimpleNamespace(name=name, start=start, end=end)  # noqa: E731
    spans = SimpleNamespace(
        window=(1.0, 9.0),
        spans=[span("engine.decode_dispatch", 1.5, k=4), span("engine.prefill_dispatch", 1.6),
               span("engine.decode_dispatch", 2.5), span("engine.decode_dispatch", 3.0, k=32),
               span("engine.decode_dispatch", 8.0, k=4)],
        modules=[[module("jit_engine_decode_seg32", 0.5, 1.4),   # dispatched before the trace
                  module("jit_engine_decode_seg4", 1.55, 2.0), module("jit_engine_prefill_from", 2.0, 2.2),
                  module("jit_engine_decode_seg32", 3.1, 5.0),
                  module("jit_engine_decode_seg4", 8.5, 9.5)]])  # ends past the window
    got = [(s.start, m.start) for s, m in hybrid_costs.paired(
        spans, "engine.decode_dispatch", "jit_engine_decode_seg")]
    assert got == [(1.5, 1.55), (3.0, 3.1)]
    got = [(s.start, m.start) for s, m in hybrid_costs.paired(
        spans, "engine.prefill_dispatch", "jit_engine_prefill")]
    assert got == [(1.6, 2.0)]


def _run(seed, trace=False):
    return harness.run(ROOT, MANIFEST, "tiny-hybrid-open", seed, 2.0, trace, require_tpu=False)


@pytest.fixture(scope="module")
def sound():
    served = []
    from benchmark.generators import _serve

    real = _serve.call

    def recording(*a, **kw):
        rec = real(*a, **kw)
        served.append(rec)
        return rec

    _serve.call = recording
    try:
        result = _run(2**31 + 91)
    finally:
        _serve.call = real
    return result, served


def test_a_tiny_hybrid_cell_runs_through_the_harness_and_is_correct(sound):
    result, served = sound
    assert result["correct"] and result["failed"] == 0 and result["attempted"] == len(served) > 0
    assert set(result["metrics"]) == {"tpot_mean_ms", "out_tok_s", "setup_s"}
    assert result["compared"]["served_gap_mean"]["value"] <= 1e-5


def test_the_traced_run_reports_what_the_state_did():
    result = _run(2**31 + 92, trace=True)
    assert result["correct"]
    # the CPU has no device plane: the trace's readers find nothing and stay out
    assert {"state_row_use", "batch_occupancy", "decode_row_use"} <= set(result["metrics"])
    assert not {"decode_hbm_roofline", "prefill_mfu"} & set(result["metrics"])
    assert 0 < result["metrics"]["state_row_use"]["value"] <= result["metrics"]["batch_occupancy"]["value"]


def test_the_control_in_int8_is_not_correct(sound):
    _result, served = sound
    limits = json.loads((DATA / "limits" / "tiny-hybrid-open.json").read_text())
    sample = correctness.pick_sample(served, 1, **limits["sample"])
    family = families.load(TINY)
    for seed in (2**31 + 91, 2**31 + 93, 5):
        tree = family.weights(seed, TINY)
        low = correctness.gap_numbers(correctness.control_gaps(tree, TINY, sample, "int8",
                                                              every_position=True))
        assert low["served_gap_mean"] > limits["served_gap_mean"]["limit"], (seed, low)


def test_the_reference_needs_one_group_and_no_experts():
    from benchmark.reference import hybrid_ref

    with pytest.raises(ValueError, match="one group"):
        hybrid_ref.sizes_of({**TINY, "mamba_n_groups": 2})
    with pytest.raises(ValueError, match="no expert branch"):
        hybrid_ref.sizes_of({**TINY, "num_local_experts": 8})
    with pytest.raises(ValueError, match="precision"):
        hybrid_ref.hidden({}, jnp.zeros((4,), jnp.int32), TINY, "float16")
