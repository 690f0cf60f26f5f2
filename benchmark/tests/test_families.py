"""The family seam: the lookup goes by the configuration's ``family`` key to a
file, not by an import that happens to be there."""
import json
import shutil
from pathlib import Path

import jax
import numpy as np
import pytest

from benchmark import families, weights
from benchmark import run as harness

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"
TINY = json.loads((DATA / "tiny.json").read_text())
MANIFEST = json.loads((DATA / "manifest.json").read_text())

#: a second family: its configuration has no ``num_key_value_heads`` (the
#: decoder family cannot read it), and everything the harness asks of it is
#: its own function, which notes that it was asked
OTHER_FAMILY = '''
from benchmark import program
from benchmark import weights as _weights
from benchmark.reference import decoder_ref, train_ref

ASKED = []


def _own(config):
    return {**config, "num_key_value_heads": config["kv_groups"]}


def enable_cache(root):
    ASKED.append("enable_cache")
    return program.enable_cache(root)


def weights(seed, config):
    ASKED.append("weights")
    return _weights.decoder_weights(seed, _own(config))


def serve_program(name, config, tree):
    ASKED.append("serve_program")
    return program.ServeProgram(name, _own(config), tree)


def train_program(config, tree, job, n_chips):
    ASKED.append("train_program")
    return program.TrainProgram(_own(config), tree, job, n_chips)


def logits_at(tree, tokens, positions, config, precision):
    ASKED.append("logits_at")
    return decoder_ref.logits_at(tree, tokens, positions, _own(config), precision)


def train_follow(make_weights, batches, config, *args, **kwargs):
    ASKED.append("train_follow")
    return train_ref.follow(make_weights, batches, _own(config), *args, **kwargs)
'''


@pytest.fixture
def other_root(tmp_path, monkeypatch):
    """A root that holds the tiny manifest's files, its configuration given to
    a family ``other`` whose file is in a directory the loader is pointed at."""
    data = tmp_path / "benchmark" / "tests" / "data"
    for part in ("traffic", "limits"):
        shutil.copytree(DATA / part, data / part)
    config = {k: v for k, v in TINY.items() if k != "num_key_value_heads"}
    config.update(family="other", kv_groups=TINY["num_key_value_heads"])
    (data / "tiny.json").write_text(json.dumps(config))
    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "other.py").write_text(OTHER_FAMILY)
    monkeypatch.setattr(families, "DIRECTORY", tmp_path / "families")
    # where this variable is set the program leaves JAX's cache directory as it
    # is: the tests after this one keep the repo's, and not one that is gone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".cache" / "jax"))
    return tmp_path, families.load(config)


@pytest.mark.parametrize("cell, asked", [
    ("tiny-closed", {"enable_cache", "weights", "serve_program", "logits_at"}),
    ("tiny-train", {"enable_cache", "weights", "train_program", "train_follow"}),
])
def test_a_cell_runs_through_the_family_its_configuration_names(other_root, cell, asked):
    root, other = other_root
    del other.ASKED[:]
    result = harness.run(root, MANIFEST, cell, 2**31 + 3, 1.0, False, require_tpu=False)
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    assert set(other.ASKED) == asked
    with pytest.raises(FileNotFoundError, match="decoder.py is missing"):
        families.load(TINY)  # the directory holds no decoder: nothing fell back to it


@pytest.mark.parametrize("name", ["mistral-7b-v0.3-l16", "mistral-7b-v0.3-l4"])
def test_the_decoder_family_makes_the_weights_it_made(name):
    """Both Mistral files name no family and get ``weights.decoder_weights``,
    leaf for leaf (widths cut to the tiny size, so that the CPU holds them)."""
    body = json.loads((ROOT / "benchmark" / "configs" / f"{name}.json").read_text())
    assert "family" not in body
    config = {**body, **{k: TINY[k] for k in (
        "hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "vocab_size")}}
    seed = 2**31 + 41
    got = families.load(config).weights(seed, config)
    want = weights.decoder_weights(seed, config)
    paths = jax.tree_util.tree_flatten_with_path(want)[0]
    assert len(paths) == 12 and jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    for (path, a), b in zip(paths, jax.tree_util.tree_leaves(got)):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b)), path


def test_a_family_that_is_not_there_fails_with_the_missing_path():
    with pytest.raises(FileNotFoundError, match=r"families/absent\.py is missing"):
        families.load({"family": "absent"})
    with pytest.raises(ValueError, match="plain name"):
        families.load({"family": "../program"})
    with pytest.raises(AttributeError, match="does not give train_the_other_way"):
        families.load({}, needs=("weights", "train_the_other_way"))
