"""The sparse-window family's bridge into ``kubedl_tpu``: a ``LlamaEngine`` that
serves a ``models/sparse_window.py`` model, built from a configuration file.

As ``hybrid_program.py`` does: ``sparse_window.preset`` is a closed table and the
engine makes its own weights (``sparse_window.sparse_init``), so both are wrapped
while the engine is built: the configuration's name returns the
``SparseWindowConfig`` made from its file, and the init hands over the tree made
from ``--seed``, its leaves under the program's names (no copy: the program
stacks a kind's layers as ``sparse_weights.py`` does). Everything the generators
and readers call is ``program.ServeProgram``'s; beside it, the expert layer's and
the window pool's counters.
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, Iterator

import jax.numpy as jnp
import numpy as np

from benchmark import program
from kubedl_tpu.models import sparse_window


def _rope(entry: Dict[str, Any]) -> sparse_window.Rope:
    if entry["rope_type"] == "default":
        return sparse_window.Rope(theta=float(entry["rope_theta"]))
    if entry["rope_type"] != "yarn":
        raise ValueError(f"rope_type {entry['rope_type']!r}: models/sparse_window.py runs default and yarn")
    return sparse_window.Rope(
        theta=float(entry["rope_theta"]), factor=float(entry["factor"]),
        original_max=int(entry["original_max_position_embeddings"]),
        beta_fast=float(entry["beta_fast"]), beta_slow=float(entry["beta_slow"]),
        attention_factor=float(entry["attention_factor"]))


def sparse_config(config: Dict[str, Any]) -> sparse_window.SparseWindowConfig:
    """The program's ``SparseWindowConfig`` from a configuration file's published keys."""
    L = int(config["num_hidden_layers"])
    checks = {"attention_bias": False, "hidden_act": "silu", "norm_topk_prob": True,
              "tie_word_embeddings": False, "use_sliding_window": True}
    for key, want in checks.items():
        if config[key] != want:
            raise ValueError(f"{key} = {config[key]!r}: models/sparse_window.py runs {want!r} only")
    if set(config["mlp_layer_types"][:L]) != {"sparse"}:
        raise ValueError("mlp_layer_types: every layer of models/sparse_window.py is sparse")
    periods, period = sparse_window.pattern_of(config["layer_types"][:L])
    return sparse_window.SparseWindowConfig(
        vocab_size=int(config["vocab_size"]), dim=int(config["hidden_size"]),
        periods=periods, period=period, n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]), head_dim=int(config["head_dim"]),
        window=int(config["sliding_window"]), n_experts=int(config["num_experts"]),
        top_k=int(config["num_experts_per_tok"]), expert_ffn=int(config["moe_intermediate_size"]),
        rope_window=_rope(config["rope_parameters"]["sliding_attention"]),
        rope_full=_rope(config["rope_parameters"]["full_attention"]),
        norm_eps=float(config["rms_norm_eps"]), max_seq=int(config["max_position_embeddings"]),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config["torch_dtype"]],
    )


def program_tree(tree: Dict[str, Any], dtype: Any) -> Dict[str, Any]:
    """``sparse_weights``' tree under the names ``sparse_window`` reads."""
    def attention(a):
        return {"norm": a["input_norm"], "wq": a["q_proj"], "wk": a["k_proj"],
                "wv": a["v_proj"], "wo": a["o_proj"]}

    m = tree["moe"]
    out = {
        "embed": tree["embed"], "lm_head": tree["lm_head"], "final_norm": tree["final_norm"],
        "window": attention(tree["sliding_attention"]), "full": attention(tree["full_attention"]),
        "moe": {"norm": m["post_attention_norm"], "router": m["router"],
                "w_in": m["gate_up_proj"], "w_out": m["down_proj"]},
    }
    if dtype != jnp.bfloat16:  # the tiny test configuration serves float32
        out = {k: ({n: w.astype(dtype) for n, w in v.items()} if isinstance(v, dict)
                   else v.astype(dtype)) for k, v in out.items()}
    return out


@contextlib.contextmanager
def _bridged(name: str, cfg: sparse_window.SparseWindowConfig, params: Any) -> Iterator[None]:
    real_preset, real_init = sparse_window.preset, sparse_window.sparse_init

    def preset(asked: str) -> sparse_window.SparseWindowConfig:
        return cfg if asked == name else real_preset(asked)

    def init(_key: Any, asked: sparse_window.SparseWindowConfig) -> Any:
        return params if asked is cfg else real_init(_key, asked)

    sparse_window.preset, sparse_window.sparse_init = preset, init
    try:
        yield
    finally:
        sparse_window.preset, sparse_window.sparse_init = real_preset, real_init


class ServeProgram(program.ServeProgram):
    """A ``LlamaEngine`` on a sparse-window configuration, in this process."""

    def __init__(self, name: str, config: Dict[str, Any], weights: Any) -> None:
        from kubedl_tpu.serving.server import EngineOverloaded, LlamaEngine

        self._overloaded = EngineOverloaded
        self.cfg = sparse_config(config)
        settings = dict(config["engine"])
        self.max_batch = int(settings["max_batch"])
        with _bridged(name, self.cfg, program_tree(weights, self.cfg.dtype)):
            self.engine = LlamaEngine(preset=name, **settings)
        self._kv_preempt0 = self._queue_wait0 = 0
        self._counters0: Dict[str, Any] = {}
        self._window_blocks: list = []

    def _counters(self) -> Dict[str, Any]:
        st = self.engine.stats()
        return {"expert_tokens": np.asarray(st.get("expert_tokens", 0)),
                "experts_touched": int(st.get("experts_touched", 0)),
                "expert_steps": int(st.get("expert_steps", 0)),
                "window_blocks_released": int(
                    st.get("kv_blocks", {}).get("window", {}).get("released", 0))}

    def mark_window(self) -> None:
        super().mark_window()
        self._counters0 = self._counters()
        del self._window_blocks[:]

    def active_rows(self) -> int:
        """The sampler's call: what the live rows hold of the window pool, and
        what their positions span, are noted beside."""
        st = self.engine.stats()
        w = st.get("kv_blocks", {}).get("window", {})
        self._window_blocks.append((int(w.get("held", 0)), int(w.get("spanned", 0))))
        return int(st["active_slots"])

    def stats(self) -> Dict[str, Any]:
        out = super().stats()
        now = self._counters()
        for name, value in now.items():
            before = self._counters0.get(name, 0)
            out[name] = (value - before).tolist() if isinstance(value, np.ndarray) else value - before
        out["window_blocks_samples"] = list(self._window_blocks)
        return out
