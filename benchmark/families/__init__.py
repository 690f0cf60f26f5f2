"""The family seam: whatever belongs to one model family is found by a file.

A configuration file names its family under ``"family"`` (absent means
``"decoder"``); :func:`load` resolves ``benchmark/families/<family>.py`` by that
name, as ``run.load_reader`` resolves a metric's reader. No generator, comparison
or hand tool names a model: each asks the family of the configuration it was
given. Adding a family is one file here, its reference under ``reference/``, its
weights and its bridge to the program beside them, and no edit to a file that is
there.

A family file gives, under these names:

``enable_cache(root) -> str``
    The program's own switch for JAX's persistent compilation cache, given the
    checkout's root; called before the first compile.
``weights(seed, config) -> tree``
    The whole parameter tree, made on the device in one jitted call from
    ``seed``, in the type it is served or trained in. The program and the
    reference are each given a tree made by this function from the same seed.
``serve_program(name, config, tree) -> program`` (a family that is served)
    The system under test in this process. The generators and readers call
    ``generate(prompt, max_tokens) -> {"token_ids", "ttft_ms"} | {"error"}``,
    ``mark_window()``, ``active_rows()``, ``stats()`` and ``close()``.
``logits_at(tree, tokens, positions, config, precision) -> [len(positions), V]``
    The plain reference's logits of one sequence ``tokens [S]`` at
    ``positions``; ``precision`` is ``"float32"`` (the reference), ``"bfloat16"``
    or ``"int8"`` (the control, which has to come out as not correct).
``train_program(config, tree, job, n_chips) -> program`` (a family that trains)
    The compiled step with its state. ``generators/train_job.py`` calls
    ``batches(path, seed)``, ``run_steps(data, n, on_step_end, lag=)``,
    ``first_moment_norms()``, ``first_moment_host()``, ``change_norms(start)``
    and ``close()``, and reads ``first_moment_decay``, ``host_ms``,
    ``attn_impl``, ``global_batch`` and ``seq_len``.
``train_follow(make_weights, batches, config, precision="float32",
first_grad_seen=None, seen_scale=1.0, keep_first=False) -> dict``
    The plain reference's first steps on ``batches`` from ``make_weights()``:
    ``losses``, ``first_grad_norms``, ``first_grad_diff_norms``, ``first_grad``
    and ``change_norms``, by leaf.
"""

from __future__ import annotations

import functools
import importlib.util
import re
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, Sequence

#: where family files are looked for
DIRECTORY = Path(__file__).resolve().parent
DEFAULT = "decoder"
_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")


@functools.lru_cache(maxsize=None)
def _load_file(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"benchmark.families.{path.stem.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load(config: Dict[str, Any], needs: Sequence[str] = ()) -> ModuleType:
    """The family of ``config``: ``<DIRECTORY>/<config["family"]>.py``, loaded
    once a process. ``needs`` names what the caller will ask of it."""
    name = config.get("family", DEFAULT)
    if not isinstance(name, str) or not _NAME.fullmatch(name):
        raise ValueError(f"a configuration's family is a plain name, not {name!r}")
    path = DIRECTORY / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no model family {name!r}: {path} is missing")
    family = _load_file(path)
    missing = [n for n in needs if not callable(getattr(family, n, None))]
    if missing:
        raise AttributeError(f"model family {name!r} ({path}) does not give {', '.join(missing)}")
    return family
