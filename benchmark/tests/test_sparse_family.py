"""The sparse-window family: its file gives what ``families/__init__.py`` asks,
its weights come whole from a seed, its configuration is the published one cut in
depth alone, its costs count what the model's shapes say, and a tiny cell of it
runs through the whole harness on the CPU and can come out not correct."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correctness, families, sparse_costs
from benchmark import run as harness

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"
TINY = json.loads((DATA / "tiny-sparse.json").read_text())
MANIFEST = json.loads((DATA / "sparse_manifest.json").read_text())
MELLUM = json.loads((ROOT / "benchmark" / "configs" / "mellum2-12b-a2.5b-l12.json").read_text())


def test_the_family_file_gives_what_a_served_family_gives():
    family = families.load(TINY, needs=("enable_cache", "weights", "serve_program", "logits_at"))
    assert family is families.load(MELLUM)
    assert not hasattr(family, "train_program")  # served, not trained
    for name in ("sparse_weights.py", "sparse_program.py", "reference/sparse_window_ref.py",
                 "sparse_costs.py"):
        assert name in family.__doc__ and (ROOT / "benchmark" / name).is_file()
    source = (ROOT / "benchmark" / "reference" / "sparse_window_ref.py").read_text()
    assert "kubedl_tpu" not in source.split('"""', 2)[2]  # the reference stands alone


def test_weights_are_one_tree_from_the_seed():
    family = families.load(TINY)
    seed = 2**31 + 17
    a, b, c = family.weights(seed, TINY), family.weights(seed, TINY), family.weights(seed + 1, TINY)
    leaves = jax.tree_util.tree_flatten_with_path(a)[0]
    assert len(leaves) == 17 and a["lm_head"].shape == (64, 512)  # an untied head
    for (path, x), y, z in zip(leaves, jax.tree_util.tree_leaves(b), jax.tree_util.tree_leaves(c)):
        assert np.array_equal(np.asarray(x), np.asarray(y)), path
        if not path[-1].key.endswith("norm"):
            assert not np.array_equal(np.asarray(x), np.asarray(z)), path
        assert x.dtype == jnp.bfloat16
    assert a["sliding_attention"]["q_proj"].shape == (4, 64, 64)
    assert a["full_attention"]["k_proj"].shape == (2, 64, 32)
    m = a["moe"]
    assert m["router"].shape == (6, 64, 8) and m["gate_up_proj"].shape == (6, 8, 64, 64)
    assert m["down_proj"].shape == (6, 8, 32, 64)
    # the router's logits over a normed row have the deviation the file asks for
    wide = family.weights(seed, {**TINY, "init": {"router_logit_deviation": 3.0}})
    ratio = float(jnp.std(wide["moe"]["router"].astype(jnp.float32))
                  / jnp.std(m["router"].astype(jnp.float32)))
    assert abs(ratio - 3.0) < 0.05
    assert abs(float(jnp.std(m["router"].astype(jnp.float32))) * 8 - 1.0) < 0.1  # 1/sqrt(64)
    # the embedding is a table of rows, fan-in 1: its entries are the normed stream's size
    assert abs(float(jnp.std(a["embed"].astype(jnp.float32))) - 1.0) < 0.05


def test_the_configuration_is_the_published_one_cut_in_depth_alone():
    assert MELLUM["family"] == "sparse_window"
    assert list(MELLUM["reduced"]) == ["num_hidden_layers"] and MELLUM["published"] == {
        "num_hidden_layers": 28}
    assert (MELLUM["num_hidden_layers"], MELLUM["hidden_size"], MELLUM["vocab_size"],
            MELLUM["num_experts"], MELLUM["num_experts_per_tok"], MELLUM["moe_intermediate_size"],
            MELLUM["intermediate_size"], MELLUM["sliding_window"], MELLUM["head_dim"],
            MELLUM["num_attention_heads"], MELLUM["num_key_value_heads"]) == (
                12, 2304, 98304, 64, 8, 896, 7168, 1024, 128, 32, 4)
    # the two lists are the published ones whole; the program reads the first 12
    assert len(MELLUM["layer_types"]) == len(MELLUM["mlp_layer_types"]) == 28
    assert [i for i, k in enumerate(MELLUM["layer_types"]) if k == "full_attention"] == [
        3, 7, 11, 15, 19, 23, 27]
    yarn = MELLUM["rope_parameters"]["full_attention"]
    assert (yarn["rope_type"], yarn["factor"], yarn["original_max_position_embeddings"],
            yarn["attention_factor"]) == ("yarn", 16, 8192, 1.2772588722239782)
    for key in ("torch_dtype", "yarn_truncate", "qk_norm", "attention_sinks", "mtp_head", "weights",
                "embedding", "router", "router_softmax", "activations"):
        assert key in MELLUM["assumed"], key
    from benchmark import sparse_program

    cfg = sparse_program.sparse_config(MELLUM)
    assert (cfg.periods, cfg.period, cfg.n_window, cfg.n_full, cfg.n_layers) == (
        3, ("window", "window", "window", "full"), 9, 3, 12)
    assert cfg.rope_full.attention_factor == 1.2772588722239782 and cfg.rope_window.factor == 1.0
    assert cfg.num_params() == 12 * sparse_costs.layer_params(MELLUM) + 2 * 98304 * 2304 + 2304
    assert cfg.num_params() == 5_465_956_608  # 10.93 GB in bfloat16
    with pytest.raises(ValueError, match="norm_topk_prob"):
        sparse_program.sparse_config({**MELLUM, "norm_topk_prob": False})
    with pytest.raises(ValueError, match="rope_type"):
        sparse_program.sparse_config({**MELLUM, "rope_parameters": {
            **MELLUM["rope_parameters"], "sliding_attention": {"rope_type": "llama3", "rope_theta": 1}}})


def test_costs_are_the_shapes():
    assert sparse_costs.attention_params(MELLUM) == 21_233_664
    assert sparse_costs.router_params(MELLUM) == 147_456
    assert sparse_costs.expert_params(MELLUM) == 6_193_152
    assert sparse_costs.layer_params(MELLUM) == 417_747_456
    assert sparse_costs.expert_bytes(MELLUM) == 12_386_304
    assert sparse_costs.kv_bytes_per_key(MELLUM) == 2048
    step = sparse_costs.step_bytes(MELLUM)
    assert step == 2 * (12 * (21_233_664 + 147_456 + 4608) + 98304 * 2304 + 2304)
    # 16 rows that all keep 4 tokens, every expert touched in every layer and step
    all_touched = 4 * 12 * 64
    assert sparse_costs.decode_segment_bytes(MELLUM, 4, 16, 64, 0, 0, all_touched) == (
        4 * step + all_touched * 12_386_304)
    # one row of two keeps 3 of a 32-step segment: the steps it needed at least, the
    # experts its tokens touched, its keys in 3 full layers and 9 window layers
    assert sparse_costs.decode_segment_bytes(MELLUM, 32, 2, 3, 5000, 2048, 100) == (
        2 * step + 100 * 12_386_304 + 2048 * (3 * 5000 + 9 * 2048) * 3 / 2)
    assert sparse_costs.decode_segment_bytes(MELLUM, 4, 0, 0, 0, 0, 0) == 0.0
    # a chunk of 1024 from position 2048: every query sees a whole window
    keys = 1024 * 2048 + 1024 * 1025 // 2
    assert sparse_costs.window_pairs(1024, keys, 1024) == 1024 * 1024
    # a prompt's first 1500 tokens: positions 0..1022 see p + 1 keys, the rest 1024
    assert sparse_costs.window_pairs(1500, 1500 * 1501 // 2, 1024) == (
        1023 * 1024 // 2 + (1500 - 1023) * 1024)
    assert sparse_costs.window_pairs(10, 55, 1024) == 55 and sparse_costs.window_pairs(0, 0, 8) == 0
    per_token = 2 * 12 * (21_233_664 + 147_456 + 8 * 6_193_152)
    assert sparse_costs.prefill_flops(MELLUM, 1024, keys) == (
        1024 * per_token + 4.0 * 32 * 128 * (3 * keys + 9 * 1024 * 1024))
    with pytest.raises(ValueError, match="sparse expert layer"):
        sparse_costs.sizes_of({**MELLUM, "mlp_layer_types": ["dense"] * 28})


def _run(seed, trace=False):
    return harness.run(ROOT, MANIFEST, "tiny-sparse-open", seed, 2.0, trace, require_tpu=False)


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


def test_a_tiny_sparse_cell_runs_through_the_harness_and_is_correct(sound):
    result, served = sound
    assert result["correct"] and result["failed"] == 0 and result["attempted"] == len(served) > 0
    assert set(result["metrics"]) == {"tpot_mean_ms", "out_tok_s", "setup_s"}
    assert result["compared"]["served_gap_mean"]["value"] <= 1e-3
    assert max(len(r["prompt"]) for r in served) > 2 * TINY["sliding_window"]  # past the window


def test_the_traced_run_reports_what_the_experts_and_the_window_did():
    result = _run(2**31 + 92, trace=True)
    assert result["correct"]
    # the CPU has no device plane: the trace's readers find nothing and stay out
    assert {"expert_load_peak", "window_blocks_kept", "batch_occupancy", "decode_row_use"} <= set(
        result["metrics"])
    assert not {"sparse_decode_hbm_roofline", "sparse_prefill_mfu"} & set(result["metrics"])
    assert 1.0 <= result["metrics"]["expert_load_peak"]["value"] <= TINY["num_experts"]
    assert 0 < result["metrics"]["window_blocks_kept"]["value"] < 100  # blocks were released


def test_the_new_readers_find_nothing_in_a_program_without_experts_or_a_window():
    """What the parent's programs, and the other families', give them."""
    for name in ("expert_load_peak", "window_blocks_kept", "sparse_decode_hbm_roofline",
                 "sparse_prefill_mfu"):
        read = harness.load_reader("layer_metrics", name)
        assert read(None, {"kv_blocks": {}, "pipeline": {}}, {"kind": "serve", "config": {}}) is None


def test_the_control_in_int8_is_not_correct(sound):
    _result, served = sound
    limits = json.loads((DATA / "limits" / "tiny-sparse-open.json").read_text())
    sample = correctness.pick_sample(served, 1, **limits["sample"])
    family = families.load(TINY)
    for seed in (2**31 + 91, 2**31 + 93, 5):
        tree = family.weights(seed, TINY)
        low = correctness.gap_numbers(correctness.control_gaps(tree, TINY, sample, "int8",
                                                              every_position=True))
        assert low["served_gap_mean"] > limits["served_gap_mean"]["limit"], (seed, low)


@pytest.mark.parametrize("fault", ["token", "wblock", "block", "int8"])
def test_a_planted_fault_is_not_correct(fault, capsys, monkeypatch):
    """``planted.py`` on the tiny cell: each fault it can plant comes out not ok
    through the comparison a run makes, by at least one of the cell's limits."""
    from benchmark import planted

    monkeypatch.setattr("sys.argv", [
        "planted.py", "--workload", "tiny-sparse-open", "--fault", fault, "--seeds", str(2**31 + 95),
        "--seconds", "2", "--manifest", str(DATA / "sparse_manifest.json"), "--allow-cpu"])
    assert planted.main() == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["failed"] == 0 and not all(c["ok"] for c in out["compared"]), out
    if fault == "token":  # one altered token a sampled request, each read on its own
        assert len(out["altered"]) >= 4 and min(out["altered"]) > 0
    if fault == "int8":
        assert all(v == 0 for v in out["sound"].values())  # the float32 tiny engine


def test_the_reference_describes_this_family_and_no_other():
    from benchmark.reference import sparse_window_ref

    with pytest.raises(ValueError, match="sliding and full attention only"):
        sparse_window_ref.sizes_of({**TINY, "layer_types": ["mamba"] * 6})
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        sparse_window_ref.sizes_of({**TINY, "tie_word_embeddings": True})
    with pytest.raises(ValueError, match="precision"):
        sparse_window_ref.hidden({}, jnp.zeros((4,), jnp.int32), TINY, "float16")
    # a depth cut keeps the published lists whole: the first num_hidden_layers count
    cut = {**TINY, "num_hidden_layers": 3}
    assert sparse_window_ref.sizes_of(cut)["kinds"] == TINY["layer_types"][:3]
    # the routed sets of the bfloat16 arm may differ from the float32 arm's, and are counted
    tree = families.load(TINY).weights(3, TINY)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 512, 64), jnp.int32)
    sets = {}
    for precision in ("float32", "bfloat16"):
        routed = []
        sparse_window_ref.hidden(tree, tokens, TINY, precision, routed=routed)
        sets[precision] = np.sort(np.stack([np.asarray(r) for r in routed]), axis=-1)
    assert sets["float32"].shape == (6, 64, 2)
    share = float((sets["float32"] != sets["bfloat16"]).any(-1).mean())
    assert 0.0 <= share < 0.5
