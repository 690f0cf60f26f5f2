"""The parallel-sparse family's bridge into ``kubedl_tpu``: a ``LlamaEngine``
that serves ``models/sparse_window.py``'s model under the settings of a parallel
attention-and-experts block, built from a configuration file.

As ``sparse_program.py`` does: ``sparse_window.preset`` is a closed table and the
engine makes its own weights (``sparse_window.sparse_init``), so both are wrapped
while the engine is built: the configuration's name returns the
``SparseWindowConfig`` made from its file, and the init hands over the tree made
from ``--seed``, its leaves under the program's names (no copy: the program
stacks a kind's layers, an expert's gate and up, and the shared experts side by
side as ``parallel_sparse_weights.py`` does). Everything the generators and
readers call is ``sparse_program.ServeProgram``'s; beside it, the two counters of
a held share (``assign_held``, ``assign_all``).
"""

from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp

from benchmark import sparse_program
from benchmark.reference import parallel_sparse_ref
from kubedl_tpu.models import sparse_window


def parallel_config(config: Dict[str, Any]) -> sparse_window.SparseWindowConfig:
    """The program's ``SparseWindowConfig`` from a configuration file's published
    keys (``parallel_sparse_ref.sizes_of`` refuses what this family is not)."""
    s = parallel_sparse_ref.sizes_of(config)
    periods, period = sparse_window.pattern_of(s["kinds"])
    return sparse_window.SparseWindowConfig(
        vocab_size=s["V"], dim=s["D"], periods=periods, period=period, n_heads=s["heads"],
        n_kv_heads=s["KV"], head_dim=s["hd"], window=s["window"], n_experts=s["E"],
        top_k=s["top_k"], expert_ffn=s["F"],
        rope_window=sparse_window.Rope(theta=float(config["rope_theta"]), form="interleaved"),
        rope_full=sparse_window.Rope(form="none"), norm_eps=float(config["layer_norm_eps"]),
        max_seq=int(config["max_position_embeddings"]),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config["torch_dtype"]],
        norm="layer", parallel_block=True, router_score="sigmoid", n_shared=s["n_shared"],
        shared_ffn=s["F"], shared_average=True, tied_head=True,
        logit_scale=float(config["logit_scale"]), expert_first=s["held_first"],
        experts_held=s["held"],
    )


def program_tree(tree: Dict[str, Any], dtype: Any) -> Dict[str, Any]:
    """``parallel_sparse_weights``' tree under the names ``sparse_window`` reads."""
    def attention(a):
        return {"norm": a["input_norm"], "wq": a["q_proj"], "wk": a["k_proj"],
                "wv": a["v_proj"], "wo": a["o_proj"]}

    m = tree["moe"]
    out = {
        "embed": tree["embed"], "final_norm": tree["final_norm"],
        "window": attention(tree["sliding_attention"]), "full": attention(tree["full_attention"]),
        "moe": {"router": m["router"], "w_in": m["gate_up_proj"], "w_out": m["down_proj"],
                "shared_in": m["shared_gate_up_proj"], "shared_out": m["shared_down_proj"]},
    }
    if dtype != jnp.bfloat16:  # the tiny test configuration serves float32
        out = {k: ({n: w.astype(dtype) for n, w in v.items()} if isinstance(v, dict)
                   else v.astype(dtype)) for k, v in out.items()}
    return out


class ServeProgram(sparse_program.ServeProgram):
    """A ``LlamaEngine`` on a parallel-sparse configuration, in this process."""

    def __init__(self, name: str, config: Dict[str, Any], weights: Any) -> None:
        from kubedl_tpu.serving.server import EngineOverloaded, LlamaEngine

        self._overloaded = EngineOverloaded
        self.cfg = parallel_config(config)
        settings = dict(config["engine"])
        self.max_batch = int(settings["max_batch"])
        with sparse_program._bridged(name, self.cfg, program_tree(weights, self.cfg.dtype)):
            self.engine = LlamaEngine(preset=name, **settings)
        self._kv_preempt0 = self._queue_wait0 = 0
        self._counters0: Dict[str, Any] = {}
        self._window_blocks: list = []

    def _counters(self) -> Dict[str, Any]:
        st = self.engine.stats()
        return {**super()._counters(), "assign_held": int(st.get("assign_held", 0)),
                "assign_all": int(st.get("assign_all", 0))}
