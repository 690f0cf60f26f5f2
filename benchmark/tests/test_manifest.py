"""BENCHMARK.json resolves to files, and keeps to the characters and limits of
the benchmark's contract."""
import importlib
import json
import re
from pathlib import Path

import pytest

from benchmark import run as harness

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert MANIFEST["command"][-1] == "benchmark/run.py" and MANIFEST["paths"] == ["benchmark"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    four = sum(1 for w in MANIFEST["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(MANIFEST["workloads"]) // 4)


def test_names_units_and_lines():
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    assert "setup_s" in names
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200


def test_every_configuration_is_used_and_states_its_cut():
    used = {w["config"] for w in MANIFEST["workloads"]}
    for c in MANIFEST["configs"]:
        assert c["name"] in used
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["source"] == c["source"]
        assert set(c["reduced"]) == set(body["reduced"])
        # no width is ever cut: Mistral-7B-v0.3's published widths
        assert (body["hidden_size"], body["intermediate_size"], body["num_attention_heads"],
                body["num_key_value_heads"], body["head_dim"], body["vocab_size"]) == (
                    4096, 14336, 32, 8, 128, 32768)


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_cell_resolves_to_files(cell):
    ctx = harness.Context(ROOT, MANIFEST, cell, 1, 1.0, False)
    generator = importlib.import_module(f"benchmark.generators.{ctx.mix['generator']}")
    assert callable(generator.run_cell)
    reported = []
    for section, kind in (("end_to_end", "end_to_end"), ("per_layer", "layer_metrics")):
        for m in harness.metrics_of(MANIFEST, section, cell):
            reported.append(m["name"])
            if m["name"] != "setup_s":
                assert callable(harness.load_reader(kind, m["name"]))
    assert "setup_s" in reported and len(reported) >= 3
    e2e = {m["name"] for m in harness.metrics_of(MANIFEST, "end_to_end", cell)}
    for m in harness.metrics_of(MANIFEST, "per_layer", cell):
        assert m["moves"] in e2e, f"{m['name']} moves {m['moves']}, which {cell} does not report"
    assert set(ctx.limits), "a cell states the limits its comparison uses"
    for shape in ctx.mix.get("warmup", []):
        assert shape["prompt_tokens"] + shape["max_tokens"] < ctx.config["engine"]["max_seq"]


def test_files_under_paths_use_plain_names():
    for path in (ROOT / "benchmark").rglob("*"):
        if "__pycache__" in path.parts:
            continue
        rel = path.relative_to(ROOT).as_posix()
        assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_peaks_name_their_source():
    peaks = json.loads((ROOT / "benchmark" / "peaks.json").read_text())
    assert "TPU v5 lite" in peaks["device_kinds"] and "cloud.google.com" in peaks["source"]
    assert peaks["device_kinds"]["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
