"""BENCHMARK.json resolves to files, keeps to the characters and limits of the
benchmark's contract, and every configuration states its cut as section 4 of
the ``model-configs`` guide asks.

Every test runs on two roots: the repo's own, and a synthetic one built in
``tmp_path`` that adds to it, as new files and entries only, what a PR bringing
a second model family would bring: a family file, a configuration of a
sparse-expert decoder with latent attention (no ``num_key_value_heads``) cut to
one chip's share of a four-chip deployment, a mix, limits and a serve cell."""
import copy
import importlib
import json
import re
import shutil
from pathlib import Path

import pytest

from benchmark import families
from benchmark import run as harness

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

MISTRAL = "https://huggingface.co/mistralai/Mistral-7B-v0.3/blob/main/config.json"
#: what may stand under ``reduced``, by the published key names: counts of
#: layers, of experts held, and of vocabulary rows. Never a width.
LAYER_KEYS = {"num_hidden_layers"}
EXPERT_KEYS = {"num_experts", "n_routed_experts", "num_local_experts"}
VOCAB_KEYS = {"vocab_size"}
#: published names of "the first k layers are dense"
LEADING_DENSE_KEYS = ("first_k_dense_replace", "num_dense_layers", "n_dense_first_layers")

SPARSE_CELL = "sparse-closed"
SPARSE_CONFIG = {
    "source": "https://example.org/sparse-latent-105b/blob/main/config.json",
    "family": "sparse",
    "hidden_size": 4096, "intermediate_size": 16384, "moe_intermediate_size": 2048,
    "num_attention_heads": 64, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "first_k_dense_replace": 1,
    "num_experts": 32, "num_experts_per_tok": 8, "num_shared_experts": 1,
    "num_hidden_layers": 5, "vocab_size": 65536, "torch_dtype": "bfloat16",
    "reduced": {"num_hidden_layers": "32 -> 5: one dense layer and four expert layers",
                "num_experts": "128 -> 32: this chip's experts; the router keeps 128 outputs",
                "vocab_size": "262144 -> 65536: this chip's slice of embedding and head"},
    "published": {"num_hidden_layers": 32, "num_experts": 128, "vocab_size": 262144},
    "deployment": "4 chips share each layer by experts; attention and the shared expert whole on each",
    "engine": {"max_seq": 4096, "max_batch": 4},
}
SPARSE_FAMILY = '''"""What a second family's file gives (resolved here, never run)."""
def enable_cache(root): raise NotImplementedError
def weights(seed, config): raise NotImplementedError
def serve_program(name, config, tree): raise NotImplementedError
def logits_at(tree, tokens, positions, config, precision): raise NotImplementedError
'''


def synthetic_root(tmp_path, **config_changes):
    """The repo's benchmark with a second family added: new files, new entries,
    and the new cell's name in the lists of the metrics it reports."""
    root = tmp_path / "root"
    for part in ("configs", "traffic", "limits", "families"):
        shutil.copytree(ROOT / "benchmark" / part, root / "benchmark" / part,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "benchmark" / "peaks.json", root / "benchmark" / "peaks.json")
    bench = root / "benchmark"
    (bench / "families" / "sparse.py").write_text(SPARSE_FAMILY)
    config = {**copy.deepcopy(SPARSE_CONFIG), **config_changes}
    (bench / "configs" / "sparse-l5.json").write_text(json.dumps(config))
    for part in ("traffic", "limits"):
        shutil.copy(bench / part / "longprompt-closed.json", bench / part / f"{SPARSE_CELL}.json")
    manifest = copy.deepcopy(MANIFEST)
    manifest["configs"].append({
        "name": "sparse-l5", "source": config["source"], "file": "benchmark/configs/sparse-l5.json",
        "reduced": sorted(config["reduced"]), "why": "sparse experts, a shared expert, latent attention"})
    manifest["workloads"].append({
        "name": SPARSE_CELL, "config": "sparse-l5", "traffic": SPARSE_CELL, "chips": 1,
        "why": "closed loop, 8 clients, prompts 2048-3584: latent-cache prefill and the expert layer"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "longprompt-closed" in m.get("workloads", []):
            m["workloads"].append(SPARSE_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest, indent=1))
    return root, manifest


@pytest.fixture(params=["repo", "synthetic"])
def world(request, tmp_path, monkeypatch):
    """``(root, manifest)`` of the repo, then of the synthetic root."""
    if request.param == "repo":
        return ROOT, MANIFEST
    root, manifest = synthetic_root(tmp_path)
    monkeypatch.setattr(families, "DIRECTORY", root / "benchmark" / "families")
    return root, manifest


def test_keys_and_limits(world):
    root, manifest = world
    assert set(manifest) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert manifest["command"][-1] == "benchmark/run.py" and manifest["paths"] == ["benchmark"]
    assert 1 <= manifest["run_seconds"] <= 51
    assert len((root / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= len(manifest["configs"]) <= 24 and 1 <= len(manifest["workloads"]) <= 24
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_names_units_and_lines(world):
    _root, manifest = world
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    assert "setup_s" in names
    cells = [w["name"] for w in manifest["workloads"]]
    assert len(cells) == len(set(cells))
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert len(c["reduced"]) <= 16
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200


def check_cut(entry, body):
    """Section 4 of the ``model-configs`` guide, for one configuration: what
    may be reduced, what the file states beside it, and the floors."""
    assert body["source"] == entry["source"]
    reduced = set(body["reduced"])
    assert reduced == set(entry["reduced"])
    if entry["source"] == MISTRAL:  # nothing is loosened for what is there
        assert (body["hidden_size"], body["intermediate_size"], body["num_attention_heads"],
                body["num_key_value_heads"], body["head_dim"], body["vocab_size"]) == (
                    4096, 14336, 32, 8, 128, 32768)
    widths = reduced - LAYER_KEYS - EXPERT_KEYS - VOCAB_KEYS
    assert not widths, (f"{sorted(widths)} may not stand under reduced: only a count of layers, "
                        "of experts held or of vocabulary rows is ever cut, never a width")
    published = body.get("published", {})
    assert set(published) == reduced, "the file states the published value beside each reduced key"
    for key in reduced:
        assert 0 < body[key] < published[key], f"{key}: {body[key]} is no cut of {published[key]}"
    if reduced & (EXPERT_KEYS | VOCAB_KEYS):
        assert isinstance(body.get("deployment"), str) and body["deployment"].strip(), (
            "a file that reduces experts or vocabulary states the deployment it is a share of")
    experts = [k for k in EXPERT_KEYS if k in body]
    for key in experts:
        assert body[key] >= 8, f"{key}: {body[key]} held, at least 8 experts stay in a layer"
    if "vocab_size" in reduced:
        assert 8 * body["vocab_size"] >= published["vocab_size"], (
            f"vocab_size: {body['vocab_size']} is under an eighth of the vocabulary")
    if experts:
        dense = sum(int(body.get(k, 0)) for k in LEADING_DENSE_KEYS)
        assert body["num_hidden_layers"] - dense >= 4, (
            "at least four layers stay after the leading dense ones")


def test_every_configuration_is_used_and_states_its_cut(world):
    root, manifest = world
    used = {w["config"] for w in manifest["workloads"]}
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for c in manifest["configs"]:
        assert c["name"] in used
        check_cut(c, json.loads((root / c["file"]).read_text()))


@pytest.mark.parametrize("change, message", [
    ({"num_experts": 4}, "at least 8 experts"),
    ({"vocab_size": 16384}, "an eighth of the vocabulary"),
    ({"reduced": dict(SPARSE_CONFIG["reduced"], hidden_size="8192 -> 4096"),
      "published": dict(SPARSE_CONFIG["published"], hidden_size=8192)}, "may not stand under reduced"),
    ({"deployment": ""}, "states the deployment"),
    ({"published": {"num_hidden_layers": 32}}, "published value beside each"),
    ({"num_hidden_layers": 4}, "four layers stay after the leading dense"),
])
def test_a_cut_that_breaks_the_rule_fails_on_its_own_assertion(tmp_path, change, message):
    root, manifest = synthetic_root(tmp_path, **change)
    entry = manifest["configs"][-1]
    with pytest.raises(AssertionError, match=message):
        check_cut(entry, json.loads((root / entry["file"]).read_text()))
    for c in manifest["configs"][:-1]:  # and nothing that was there is touched by it
        check_cut(c, json.loads((root / c["file"]).read_text()))


CELLS = [w["name"] for w in MANIFEST["workloads"]]


@pytest.mark.parametrize("world, cell", [("repo", c) for c in CELLS]
                         + [("synthetic", c) for c in CELLS + [SPARSE_CELL]], indirect=["world"])
def test_cell_resolves_to_files(world, cell):
    root, manifest = world
    ctx = harness.Context(root, manifest, cell, 1, 1.0, False)
    generator = importlib.import_module(f"benchmark.generators.{ctx.mix['generator']}")
    assert callable(generator.run_cell)
    family = families.load(ctx.config)
    assert callable(family.weights) and callable(family.enable_cache)
    assert callable(getattr(family, "serve_program", None)) or callable(
        getattr(family, "train_program", None))
    reported = []
    for section, kind in (("end_to_end", "end_to_end"), ("per_layer", "layer_metrics")):
        for m in harness.metrics_of(manifest, section, cell):
            reported.append(m["name"])
            if m["name"] != "setup_s":
                assert callable(harness.load_reader(kind, m["name"]))
    assert "setup_s" in reported and len(reported) >= 3
    e2e = {m["name"] for m in harness.metrics_of(manifest, "end_to_end", cell)}
    for m in harness.metrics_of(manifest, "per_layer", cell):
        assert m["moves"] in e2e, f"{m['name']} moves {m['moves']}, which {cell} does not report"
    assert set(ctx.limits), "a cell states the limits its comparison uses"
    for shape in ctx.mix.get("warmup", []):
        assert shape["prompt_tokens"] + shape["max_tokens"] < ctx.config["engine"]["max_seq"]


@pytest.mark.parametrize("world, cell", [("repo", c) for c in CELLS]
                         + [("synthetic", c) for c in CELLS + [SPARSE_CELL]], indirect=["world"])
def test_a_serve_mix_states_what_its_load_was_set_from(world, cell):
    """No generator reads these two keys; they hold the file to the runs it was
    sized by. A closed loop must not run dry: it draws at least twice the most
    requests any run of the two sets finished. An open loop below the knee
    states the knee as swept, offers at most 0.75 of it, and its cell lists an
    end-to-end metric beside the rate it was offered."""
    root, manifest = world
    mix = harness.Context(root, manifest, cell, 1, 1.0, False).mix
    if mix["generator"] == "closed_loop":
        assert mix["finished_most"] >= 1
        assert mix["requests_drawn"] >= 2 * mix["finished_most"], (
            "a window can finish more than half of the sequence: draw more")
        assert mix["requests_drawn"] % mix["clients"] == 0
    elif mix["generator"] == "open_loop":
        assert mix["knee_per_s"] > 0
        assert mix["rate_per_s"] <= 0.75 * mix["knee_per_s"]
        # below the knee the tokens delivered are the tokens offered, a constant
        # of the file: such a cell holds a latency end to end beside it
        e2e = {m["name"] for m in harness.metrics_of(manifest, "end_to_end", cell)}
        assert e2e - {"setup_s", "out_tok_s"}, f"{cell} bounds nothing that can move"


def test_files_under_paths_use_plain_names(world):
    root, _manifest = world
    for path in (root / "benchmark").rglob("*"):
        if "__pycache__" in path.parts:
            continue
        rel = path.relative_to(root).as_posix()
        assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_peaks_name_their_source(world):
    root, _manifest = world
    peaks = json.loads((root / "benchmark" / "peaks.json").read_text())
    assert "TPU v5 lite" in peaks["device_kinds"] and "cloud.google.com" in peaks["source"]
    assert peaks["device_kinds"]["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
