"""The retention family: its file gives what ``families/__init__.py`` asks, its
weights come whole from a seed with the slow gate, its costs count what the
model's shapes say, its configuration is the catalog row but for the depth, and
a tiny closed-loop cell of it runs through the whole harness on the CPU and can
come out not correct."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correctness, families, retention_costs
from benchmark import run as harness

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"
TINY = json.loads((DATA / "tiny-retention.json").read_text())
MANIFEST = json.loads((DATA / "retention_manifest.json").read_text())
BRUMBY = json.loads((ROOT / "benchmark" / "configs" / "brumby-14b-base-l8.json").read_text())
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl, Brumby-14B-Base)
CATALOG = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 5120,
    "intermediate_size": 17408, "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}


def test_the_family_file_gives_what_a_served_family_gives():
    family = families.load(TINY, needs=("enable_cache", "weights", "serve_program", "logits_at"))
    assert family is families.load(BRUMBY)
    assert not hasattr(family, "train_program")  # served, not trained
    for name in ("retention_weights.py", "retention_program.py", "reference/retention_ref.py",
                 "retention_costs.py"):
        assert name in family.__doc__ and (ROOT / "benchmark" / name).is_file()
    # the reference takes nothing of the program under test
    source = (ROOT / "benchmark" / "reference" / "retention_ref.py").read_text()
    assert "import kubedl_tpu" not in source and "from kubedl_tpu" not in source


def test_weights_are_one_tree_from_the_seed_with_the_slow_gate():
    family = families.load(TINY)
    seed = 2**31 + 17
    a, b, c = family.weights(seed, TINY), family.weights(seed, TINY), family.weights(seed + 1, TINY)
    leaves = jax.tree_util.tree_flatten_with_path(a)[0]
    assert len(leaves) == 16 and a["lm_head"].shape == (64, 512)  # the head is its own leaf
    for (path, x), y, z in zip(leaves, jax.tree_util.tree_leaves(b), jax.tree_util.tree_leaves(c)):
        assert np.array_equal(np.asarray(x), np.asarray(y)), path
        name = path[-1].key
        if not name.endswith("norm"):
            assert not np.array_equal(np.asarray(x), np.asarray(z)), path
        assert x.dtype == (jnp.float32 if name == "g_bias" else jnp.bfloat16)
    layers = a["layers"]
    assert layers["q_proj"].shape == (2, 64, 64) and layers["k_proj"].shape == (2, 64, 32)
    assert layers["g_proj"].shape == (2, 2, 64) and layers["q_norm"].shape == (2, 16)
    # gamma at a zero input lies where a trained retention layer's does
    wide = family.weights(3, {**TINY, "num_key_value_heads": 4, "num_hidden_layers": 64})
    gamma = jax.nn.sigmoid(wide["layers"]["g_bias"])
    assert 0.98 <= float(gamma.min()) < 0.985 and 0.999 < float(gamma.max()) <= 0.9995


def test_the_configuration_is_the_catalog_row_but_for_its_depth():
    assert BRUMBY["family"] == "retention" and set(BRUMBY["reduced"]) == {"num_hidden_layers"}
    assert BRUMBY["published"] == {"num_hidden_layers": 40} and BRUMBY["num_hidden_layers"] == 8
    for key, value in CATALOG.items():
        if key != "num_hidden_layers":
            assert key in BRUMBY and BRUMBY[key] == value, key
    for unstated in ("torch_dtype", "qk_norm", "rope", "retention_degree", "gate", "normaliser",
                     "state_dtype", "weights", "not_given"):
        assert unstated in BRUMBY["assumed"], unstated
    assert BRUMBY["engine"] == {"kv_layout": "paged", "kv_attention": "gather", "kv_block_size": 16,
                                "max_batch": 16, "max_seq": 8192, "prefill_chunk_tokens": 1024}
    from benchmark import retention_program

    cfg = retention_program.retention_config(BRUMBY)
    assert (cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.ffn_dim,
            cfg.vocab_size, cfg.chunk) == (8, 5120, 40, 8, 128, 17408, 151936, 1024)
    # the program's parameter count is the benchmark's: matrices, the embedding, and the small leaves
    assert cfg.num_params() == retention_costs.matmul_params(BRUMBY) + 2 * 151936 * 5120 + sum(
        (8 * (2 * 5120 + 2 * 128 + 8), 5120))
    for change, message in (({"tie_word_embeddings": True}, "tie_word_embeddings"),
                            ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
                            ({"retention_degree": 4}, "degree 2")):
        with pytest.raises(ValueError, match=message):
            retention_program.retention_config({**BRUMBY, **change})


def test_costs_are_the_shapes():
    mixer, gate, mlp = 2 * 5120 * 5120 + 2 * 5120 * 1024, 8 * 5120, 3 * 5120 * 17408
    assert (mixer, gate + 8, mlp) == (62_914_560, 40_968, 267_386_880)  # the issue's hand count
    assert retention_costs.matmul_params(BRUMBY) == 8 * (mixer + gate + mlp)
    # a decode step reads 8 layers and the head: 6.84 GB; the embedding is a row a token
    assert abs(retention_costs.weight_bytes(BRUMBY) - 6.84e9) < 0.01e9
    # the state packed: 8 key groups x 8,256 products x (128 values + 1) x 4 bytes a layer
    assert retention_costs.slab_bytes(BRUMBY) == 8 * 8256 * 129 * 4 == 34_080_768
    assert retention_costs.state_bytes_per_row(BRUMBY) == 8 * 34_080_768
    w, s = retention_costs.weight_bytes(BRUMBY), retention_costs.state_bytes_per_row(BRUMBY)
    # 16 rows that all keep 4 tokens: the weights four times, every kept token's state twice
    assert retention_costs.decode_segment_bytes(BRUMBY, 4, 16, 64) == 4 * w + 64 * 2 * s
    # two rows keep 3 tokens of a 32-step segment between them: the weights for two steps at least
    assert retention_costs.decode_segment_bytes(BRUMBY, 32, 2, 3) == 2 * w + 3 * 2 * s
    assert retention_costs.decode_segment_bytes(BRUMBY, 4, 0, 0) == 0.0
    # at 16 live rows the state is most of a step's bytes: 8.7 GB beside 6.8
    assert 16 * 2 * s > w
    readout, update = 2 * 40 * 8256 * 129, 2 * 8 * 8256 * 129
    pairs = 1024 * 1025 // 2
    fresh = 1024 * 2 * retention_costs.matmul_params(BRUMBY) + 8 * (4 * 40 * 128 * pairs + 1024 * update)
    assert retention_costs.prefill_flops(BRUMBY, 1024, 0, pairs) == fresh
    assert retention_costs.prefill_flops(BRUMBY, 1024, 1024, pairs) == fresh + 8 * 1024 * readout
    # the pairs are the lesser form at a chunk's length, the recurrence from some 8,000 tokens
    assert 4 * 40 * 128 * pairs < 1024 * readout
    assert retention_costs.prefill_flops(BRUMBY, 16384, 0, 16384 * 16385 // 2) == (
        16384 * 2 * retention_costs.matmul_params(BRUMBY) + 8 * 16384 * (readout + update))


def _run(seed, trace=False):
    return harness.run(ROOT, MANIFEST, "tiny-retention-closed", seed, 2.0, trace, require_tpu=False)


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


def test_a_tiny_closed_loop_cell_runs_through_the_harness_and_is_correct(sound):
    result, served = sound
    assert result["correct"] and result["failed"] == 0 and result["attempted"] == len(served) > 0
    assert set(result["metrics"]) == {"out_tok_s", "setup_s"}
    assert result["ran_dry_s"] is None  # the loop stayed loaded to the window's end
    assert result["compared"]["served_gap_mean"]["value"] <= 1e-5


def test_the_traced_run_reports_what_a_row_without_blocks_did():
    result = _run(2**31 + 92, trace=True)
    assert result["correct"]
    # the CPU has no device plane: the trace's readers find nothing and stay out
    assert {"state_row_use", "batch_occupancy", "decode_row_use", "preempted"} <= set(result["metrics"])
    assert not {"retention_decode_hbm_roofline", "retention_prefill_mfu",
                "retention_step_roofline"} & set(result["metrics"])
    assert result["metrics"]["preempted"]["value"] == 0  # there is no block to run out of
    assert 0 < result["metrics"]["state_row_use"]["value"] <= result["metrics"]["batch_occupancy"]["value"]


@pytest.mark.parametrize("precision", ["int8", "bfloat16"])
def test_the_control_in_a_lower_precision_is_not_correct(sound, precision):
    _result, served = sound
    limits = json.loads((DATA / "limits" / "tiny-retention-closed.json").read_text())
    sample = correctness.pick_sample(served, 1, **limits["sample"])[:24]
    family = families.load(TINY)
    for seed in (2**31 + 91, 5):
        tree = family.weights(seed, TINY)
        low = correctness.gap_numbers(correctness.control_gaps(tree, TINY, sample, precision,
                                                              every_position=True))
        assert low["served_gap_mean"] > limits["served_gap_mean"]["limit"], (seed, low)


def test_the_readers_stay_out_of_another_familys_cell():
    from types import SimpleNamespace

    record = {"device_kind": "TPU v5 lite", "config": {"family": "hybrid_ssm"}}
    for name in ("retention_decode_hbm_roofline", "retention_prefill_mfu", "retention_step_roofline"):
        read = harness.load_reader("layer_metrics", name)
        assert read(None, {}, record) is None
        assert read(SimpleNamespace(devices=[], window=(0.0, 0.0), window_s=0.0), {}, record) is None


def test_the_reference_refuses_what_it_does_not_describe():
    from benchmark.reference import retention_ref

    with pytest.raises(ValueError, match="use_sliding_window"):
        retention_ref.sizes_of({**TINY, "use_sliding_window": True})
    with pytest.raises(ValueError, match="multiple of num_key_value_heads"):
        retention_ref.sizes_of({**TINY, "num_key_value_heads": 3})
    with pytest.raises(ValueError, match="precision"):
        retention_ref.hidden({}, jnp.zeros((4,), jnp.int32), TINY, "float16")


def test_only_the_segments_a_capture_holds_whole_are_counted():
    """A 4-step segment of the 2-layer tiny model runs the state kernel 8
    times; an execution the capture caught in the middle holds fewer and is
    left out (on the chip one read 102 ms for 32 steps)."""
    from types import SimpleNamespace

    def kernel(start):
        return SimpleNamespace(
            text='%retention_step_rows.6 = f32[3,2,16,128] custom-call(), custom_call_target="tpu_custom_call"',
            name="retention_step_rows.6", opcode="custom-call", start=start, end=start + 0.001)

    def other(start):
        return SimpleNamespace(text="%fusion.1 = f32[3] fusion()", name="fusion.1",
                               opcode="fusion", start=start, end=start + 0.001)

    import benchmark.trace_reader as trace_reader

    span = lambda start, **stats: SimpleNamespace(  # noqa: E731
        name="engine.decode_dispatch", start=start, stats=stats)
    module = lambda start, end: SimpleNamespace(  # noqa: E731
        name="jit_engine_decode_seg4", start=start, end=end)
    spans = SimpleNamespace(
        window=(1.0, 9.0),
        spans=[span(1.0, k=4, rows=2, take=8), span(2.0, k=4, rows=3, take=12),
               span(3.0, k=4, rows=1, take=4)],
        modules=[[module(1.1, 1.5), module(2.1, 2.9), module(3.1, 3.9)]])
    ops = ([kernel(1.2 + 0.01 * n) for n in range(3)]      # cut short: 3 of 8 calls
           + [kernel(2.2 + 0.01 * n) for n in range(8)] + [other(2.5)]
           + [kernel(3.2 + 0.01 * n) for n in range(8)])
    trace = SimpleNamespace(devices=[ops])
    assert all(trace_reader.is_kernel(o) for o in ops if o.opcode == "custom-call")
    got = list(retention_costs.whole_segments(trace, spans, TINY))
    assert [(s.stats["rows"], m.start, len(inside)) for s, m, inside in got] == [(3, 2.1, 8), (1, 3.1, 8)]
    assert list(retention_costs.whole_segments(SimpleNamespace(devices=[]), spans, TINY)) == []
