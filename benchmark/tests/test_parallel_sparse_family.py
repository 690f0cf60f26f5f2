"""The parallel-sparse family: its file gives what ``families/__init__.py`` asks,
its weights come whole from a seed, its configuration is the catalog row's cut to
one chip's share, its readers find nothing in another family's program, and a
tiny cell of it runs through the whole harness on the CPU and can come out not
correct."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correctness, families
from benchmark import parallel_sparse_costs as costs
from benchmark import run as harness

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"
TINY = json.loads((DATA / "tiny-parallel.json").read_text())
MANIFEST = json.loads((DATA / "parallel_manifest.json").read_text())
COMMAND = json.loads((ROOT / "benchmark" / "configs" / "command-a-plus-05-2026-l4.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def test_the_family_file_gives_what_a_served_family_gives():
    family = families.load(TINY, needs=("enable_cache", "weights", "serve_program", "logits_at"))
    assert family is families.load(COMMAND)
    assert not hasattr(family, "train_program")  # served, not trained
    for name in ("parallel_sparse_weights.py", "parallel_sparse_program.py",
                 "reference/parallel_sparse_ref.py", "parallel_sparse_costs.py"):
        assert name in family.__doc__ and (ROOT / "benchmark" / name).is_file()
    source = (ROOT / "benchmark" / "reference" / "parallel_sparse_ref.py").read_text()
    assert "kubedl_tpu" not in source.split('"""', 2)[2]  # the reference stands alone


def test_weights_are_one_tree_from_the_seed():
    family = families.load(TINY)
    seed = 2**31 + 17
    a, b, c = family.weights(seed, TINY), family.weights(seed, TINY), family.weights(seed + 1, TINY)
    leaves = jax.tree_util.tree_flatten_with_path(a)[0]
    assert len(leaves) == 17 and "lm_head" not in a  # a tied head
    for (path, x), y, z in zip(leaves, jax.tree_util.tree_leaves(b), jax.tree_util.tree_leaves(c)):
        assert np.array_equal(np.asarray(x), np.asarray(y)), path
        if path[-1].key != "input_norm":
            assert not np.array_equal(np.asarray(x), np.asarray(z)), path
        assert x.dtype == jnp.bfloat16
    m = a["moe"]
    # the router scores all 8 published experts, the stacks hold this chip's 2
    assert m["router"].shape == (6, 64, 8) and m["gate_up_proj"].shape == (6, 2, 64, 64)
    assert m["down_proj"].shape == (6, 2, 32, 64)
    assert m["shared_gate_up_proj"].shape == (6, 64, 128) and m["shared_down_proj"].shape == (6, 64, 64)
    assert a["sliding_attention"]["q_proj"].shape == (4, 64, 64)
    std = lambda w: float(jnp.std(w.astype(jnp.float32)))  # noqa: E731
    s = TINY["init"]["stream_deviation"]
    assert abs(std(a["embed"]) / s - 1.0) < 0.05  # rows read whole: fan-in 1
    assert abs(std(m["shared_down_proj"]) * np.sqrt(32) / s - 1.0) < 0.05
    assert abs(std(m["down_proj"]) * np.sqrt(32) / (4.0 * s) - 1.0) < 0.05
    assert abs(std(a["full_attention"]["q_proj"]) * 8 / 2.0 - 1.0) < 0.05
    assert set(np.unique(np.asarray(a["final_norm"], np.float32))) == {-1.0, 1.0}
    with pytest.raises(ValueError, match="this family's weights know"):
        family.weights(seed, {**TINY, "init": {"embedding": 1.0}})


def test_the_configuration_is_the_catalog_rows_cut_to_one_chips_share():
    """Key by key against the catalog (ROADMAP Reach A.0 (i)): every key of the
    row's ``config`` under its own name, unchanged but the three counts under
    ``reduced``, each with its published value; no width among them."""
    assert COMMAND["family"] == "parallel_sparse"
    assert set(COMMAND["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert COMMAND["published"] == {"num_hidden_layers": 32, "num_experts": 128, "vocab_size": 262144}
    assert (COMMAND["num_hidden_layers"], COMMAND["num_experts"], COMMAND["vocab_size"]) == (4, 16, 32768)
    assert "this chip's experts, 0-15; the router keeps 128 outputs" in COMMAND["reduced"]["num_experts"]
    assert "8 chips share each layer" in COMMAND["deployment"]
    for key in ("expert_width", "shared_experts", "prefix_dense", "vision_tower"):
        assert key in COMMAND["assumed"], key
    if CATALOG.is_file():
        row = next(json.loads(line) for line in CATALOG.read_text().splitlines()
                   if '"command-a-plus-05-2026"' in line)
        assert COMMAND["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in COMMAND["reduced"]:
                assert COMMAND["published"][key] == value
            else:
                assert COMMAND[key] == value, key
    assert COMMAND["engine"] == {"kv_layout": "paged", "kv_attention": "blocked", "kv_block_size": 16,
                                 "max_batch": 16, "max_seq": 32768, "prefill_chunk_tokens": 1024}
    from benchmark import parallel_sparse_program
    from kubedl_tpu.models import sparse_window

    cfg = parallel_sparse_program.parallel_config(COMMAND)
    assert cfg == sparse_window.preset("command-a-plus-05-2026-l4")
    assert cfg.num_params() == costs.model_params(COMMAND) == 4_733_292_544  # 9.47 GB in bfloat16
    with pytest.raises(ValueError, match="expert_selection_fn"):
        parallel_sparse_program.parallel_config({**COMMAND, "expert_selection_fn": "softmax"})
    with pytest.raises(ValueError, match="sliding and full attention only"):
        parallel_sparse_program.parallel_config({**COMMAND, "layer_types": ["mamba"] * 4})


def _run(seed, trace=False):
    return harness.run(ROOT, MANIFEST, "tiny-parallel-open", seed, 2.0, trace, require_tpu=False)


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


def test_a_tiny_cell_runs_through_the_harness_and_is_correct(sound):
    result, served = sound
    assert result["correct"] and result["failed"] == 0 and result["attempted"] == len(served) > 0
    assert set(result["metrics"]) == {"tpot_mean_ms", "out_tok_s", "setup_s"}
    assert result["compared"]["served_gap_mean"]["value"] <= 1e-3
    assert max(len(r["prompt"]) for r in served) > 2 * TINY["sliding_window"]  # past the window
    # the signs on the final norm: a request is not served one token for ever (with ones
    # a tied head puts the token standing at a position first, and most requests are)
    long = [r for r in served if r["n_out"] >= 6]
    assert sum(len(set(r["tokens"])) > 2 for r in long) >= 0.7 * len(long) > 0


def test_the_traced_run_reports_the_share_the_experts_and_the_window():
    result = _run(2**31 + 92, trace=True)
    assert result["correct"]
    # the CPU has no device plane: the trace's readers find nothing and stay out
    assert {"expert_held_share", "expert_load_peak", "window_blocks_kept", "batch_occupancy",
            "decode_row_use"} <= set(result["metrics"])
    assert not {"parallel_sparse_decode_hbm_roofline", "parallel_sparse_prefill_mfu",
                "paged_decode_attention_roofline"} & set(result["metrics"])
    assert 5.0 < result["metrics"]["expert_held_share"]["value"] < 60.0  # 2 of 8 held: 25 if even
    assert 0 < result["metrics"]["window_blocks_kept"]["value"] < 100  # blocks were released


def test_the_new_readers_find_nothing_in_another_familys_program():
    """What the parent's programs, and the other families', give them."""
    mellum = json.loads((ROOT / "benchmark" / "configs" / "mellum2-12b-a2.5b-l12.json").read_text())
    for name in ("parallel_sparse_decode_hbm_roofline", "parallel_sparse_prefill_mfu",
                 "expert_held_share", "paged_decode_attention_roofline"):
        read = harness.load_reader("layer_metrics", name)
        for config in ({}, mellum):
            assert read(None, {"kv_blocks": {}, "pipeline": {}, "expert_tokens": [[1, 2]]},
                        {"kind": "serve", "config": config}) is None


def test_the_control_in_int8_is_not_correct(sound):
    _result, served = sound
    limits = json.loads((DATA / "limits" / "tiny-parallel-open.json").read_text())
    sample = correctness.pick_sample(served, 1, **limits["sample"])
    family = families.load(TINY)
    for seed in (2**31 + 91, 5):
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
        "planted.py", "--workload", "tiny-parallel-open", "--fault", fault, "--seeds", str(2**31 + 95),
        "--seconds", "2", "--manifest", str(DATA / "parallel_manifest.json"), "--allow-cpu"])
    assert planted.main() == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["failed"] == 0 and not all(c["ok"] for c in out["compared"]), out


def test_the_reference_computes_the_share_it_is_given():
    from benchmark.reference import parallel_sparse_ref as ref

    with pytest.raises(ValueError, match="tie_word_embeddings"):
        ref.sizes_of({**TINY, "tie_word_embeddings": False})
    with pytest.raises(ValueError, match="precision"):
        ref.hidden({}, jnp.zeros((128,), jnp.int32), TINY, "float16")
    with pytest.raises(ValueError, match="whole blocks"):
        ref.hidden({}, jnp.zeros((100,), jnp.int32), TINY)
    s = ref.sizes_of(TINY)
    assert (s["E"], s["held"], s["held_first"]) == (8, 2, 2)
    # one of the two held experts alone, then the other: their parts add up to the share's
    tree = families.load(TINY).weights(3, TINY)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 512, 128), jnp.int32)
    one = {**TINY, "layer_types": ["full_attention"], "num_hidden_layers": 1}
    both, a, b, none = (np.asarray(ref.hidden(tree, tokens, one, first=f, count=c))
                        for f, c in ((2, 2), (2, 1), (3, 1), (2, 0)))
    assert np.abs(a + b - none - both).max() <= 1e-5 and np.abs(both - none).max() > 1e-4
