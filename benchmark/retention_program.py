"""The retention family's bridge into ``kubedl_tpu``: a ``LlamaEngine`` that
serves a ``models/retention.py`` model, built from a configuration file.

As ``hybrid_program.py`` does: ``retention.preset`` is a closed table and the
engine makes its own weights (``retention.retention_init``), so both are
wrapped while the engine is built: the configuration's name returns the
``RetentionConfig`` made from its file, and the init hands over the tree made
from ``--seed``, its leaves under the program's names (no copy). Everything the
generators and readers call is ``hybrid_program.ServeProgram``'s: the state's
counters are the same two.
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, Iterator

import jax.numpy as jnp

from benchmark import hybrid_program, program
from benchmark.reference import retention_ref
from kubedl_tpu.models import retention

#: the program's name of each leaf of ``retention_weights``' ``layers``
LEAVES = {
    "input_layernorm": "mixer_norm", "q_proj": "wq", "k_proj": "wk", "v_proj": "wv",
    "o_proj": "wo", "q_norm": "q_norm", "k_norm": "k_norm", "g_proj": "w_g",
    "g_bias": "b_g", "post_attention_layernorm": "mlp_norm", "gate_proj": "w_gate",
    "up_proj": "w_up", "down_proj": "w_down",
}


def retention_config(config: Dict[str, Any]) -> retention.RetentionConfig:
    """The program's ``RetentionConfig`` from a configuration file's published keys."""
    s = retention_ref.sizes_of(config)  # refuses what the family does not describe
    return retention.RetentionConfig(
        vocab_size=s["V"], dim=s["D"], n_layers=s["L"], n_heads=s["H"], n_kv_heads=s["KV"],
        head_dim=s["hd"], ffn_dim=s["F"], rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        max_seq=int(config["max_position_embeddings"]),
        **({"chunk": int(config["retention_chunk"])} if "retention_chunk" in config else {}),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config["torch_dtype"]],
    )


def program_tree(tree: Dict[str, Any], dtype: Any) -> Dict[str, Any]:
    """``retention_weights``' tree under the names ``models/retention.py`` reads."""
    cast = (lambda n, w: w) if dtype == jnp.bfloat16 else (  # the tiny test size serves float32
        lambda n, w: w if n == "g_bias" else w.astype(dtype))
    out = {n: cast(n, tree[n]) for n in ("embed", "lm_head", "final_norm")}
    out["layers"] = {LEAVES[n]: cast(n, w) for n, w in tree["layers"].items()}
    return out


@contextlib.contextmanager
def _bridged(name: str, cfg: retention.RetentionConfig, params: Any) -> Iterator[None]:
    real_preset, real_init = retention.preset, retention.retention_init

    def preset(asked: str) -> retention.RetentionConfig:
        return cfg if asked == name else real_preset(asked)

    def init(_key: Any, asked: retention.RetentionConfig) -> Any:
        return params if asked is cfg else real_init(_key, asked)

    retention.preset, retention.retention_init = preset, init
    try:
        yield
    finally:
        retention.preset, retention.retention_init = real_preset, real_init


class ServeProgram(hybrid_program.ServeProgram):
    """A ``LlamaEngine`` on a retention configuration, in this process."""

    def __init__(self, name: str, config: Dict[str, Any], weights: Any) -> None:
        from kubedl_tpu.serving.server import EngineOverloaded, LlamaEngine

        self._overloaded = EngineOverloaded
        self.cfg = retention_config(config)
        settings = dict(config["engine"])
        self.max_batch = int(settings["max_batch"])
        with _bridged(name, self.cfg, program_tree(weights, self.cfg.dtype)):
            self.engine = LlamaEngine(preset=name, **settings)
        self._kv_preempt0 = self._queue_wait0 = self._state_resets0 = 0
        self._state_rows: list = []
