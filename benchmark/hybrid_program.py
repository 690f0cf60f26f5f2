"""The hybrid family's bridge into ``kubedl_tpu``: a ``LlamaEngine`` that
serves a ``models/hybrid_ssm.py`` model, built from a configuration file.

As ``program.py`` does for the decoder, two bridges stand in for what the
program lacks: ``hybrid_ssm.preset`` is a closed table and the engine makes its
own weights (``hybrid_ssm.hybrid_init``), so both are wrapped while the engine
is built: the configuration's name returns the ``HybridConfig`` made from its
file, and the init hands over the tree made from ``--seed``, its leaves under
the program's names (no copy: the program stacks a kind's layers as
``hybrid_weights.py`` does). Everything the generators and readers call is
``program.ServeProgram``'s; beside it, the state's counters.
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, Iterator

import jax.numpy as jnp

from benchmark import program
from kubedl_tpu.models import hybrid_ssm


def hybrid_config(config: Dict[str, Any]) -> hybrid_ssm.HybridConfig:
    """The program's ``HybridConfig`` from a configuration file's published keys."""
    periods, before, after = hybrid_ssm.pattern_of(config["layer_types"])
    heads = int(config["num_attention_heads"])
    checks = {
        "mamba_n_groups": 1, "num_local_experts": 0, "position_embedding_type": "nope",
        "mamba_conv_bias": True, "mamba_proj_bias": False, "attention_bias": False,
        "hidden_act": "silu", "normalization_function": "rmsnorm", "tie_word_embeddings": True,
    }
    for key, want in checks.items():
        if config[key] != want:
            raise ValueError(f"{key} = {config[key]!r}: models/hybrid_ssm.py runs {want!r} only")
    if int(config["mamba_n_heads"]) * int(config["mamba_d_head"]) != \
            int(config["mamba_expand"]) * int(config["hidden_size"]):
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand x hidden_size")
    return hybrid_ssm.HybridConfig(
        vocab_size=int(config["vocab_size"]), dim=int(config["hidden_size"]),
        periods=periods, mamba_before=before, mamba_after=after,
        n_heads=heads, n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config.get("head_dim") or config["hidden_size"] // heads),
        ffn_dim=int(config["shared_intermediate_size"]),
        ssm_heads=int(config["mamba_n_heads"]), ssm_head_dim=int(config["mamba_d_head"]),
        ssm_state=int(config["mamba_d_state"]), conv_kernel=int(config["mamba_d_conv"]),
        ssm_chunk=int(config["mamba_chunk_size"]),
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        attention_multiplier=float(config["attention_multiplier"]),
        norm_eps=float(config["rms_norm_eps"]),
        max_seq=int(config["max_position_embeddings"]),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config["torch_dtype"]],
    )


def program_tree(tree: Dict[str, Any], dtype: Any) -> Dict[str, Any]:
    """``hybrid_weights``' tree under the names ``hybrid_ssm`` reads."""
    m, a, f = tree["mamba"], tree["attention"], tree["mlp"]
    out = {
        "embed": tree["embed"], "final_norm": tree["final_norm"],
        "mamba": {"norm": m["mixer_norm"], "in_z": m["in_proj_z"], "in_xbc": m["in_proj_xbc"],
                  "in_dt": m["in_proj_dt"], **{k: m[k] for k in (
                      "conv_w", "conv_b", "dt_bias", "A_log", "D", "gate_norm", "out_proj")}},
        "attn": {"norm": a["mixer_norm"], "wq": a["q_proj"], "wk": a["k_proj"],
                 "wv": a["v_proj"], "wo": a["o_proj"]},
        "mlp": {"norm": f["mlp_norm"], "w_in": f["input_linear"], "w_out": f["output_linear"]},
    }
    if dtype != jnp.bfloat16:  # the tiny test configuration serves float32
        keep = ("dt_bias", "A_log", "D")
        out = {k: ({n: (w if n in keep else w.astype(dtype)) for n, w in v.items()}
                   if isinstance(v, dict) else v.astype(dtype)) for k, v in out.items()}
    return out


@contextlib.contextmanager
def _bridged(name: str, cfg: hybrid_ssm.HybridConfig, params: Any) -> Iterator[None]:
    real_preset, real_init = hybrid_ssm.preset, hybrid_ssm.hybrid_init

    def preset(asked: str) -> hybrid_ssm.HybridConfig:
        return cfg if asked == name else real_preset(asked)

    def init(_key: Any, asked: hybrid_ssm.HybridConfig) -> Any:
        return params if asked is cfg else real_init(_key, asked)

    hybrid_ssm.preset, hybrid_ssm.hybrid_init = preset, init
    try:
        yield
    finally:
        hybrid_ssm.preset, hybrid_ssm.hybrid_init = real_preset, real_init


class ServeProgram(program.ServeProgram):
    """A ``LlamaEngine`` on a hybrid configuration, in this process."""

    def __init__(self, name: str, config: Dict[str, Any], weights: Any) -> None:
        from kubedl_tpu.serving.server import EngineOverloaded, LlamaEngine

        self._overloaded = EngineOverloaded
        self.cfg = hybrid_config(config)
        settings = dict(config["engine"])
        self.max_batch = int(settings["max_batch"])
        with _bridged(name, self.cfg, program_tree(weights, self.cfg.dtype)):
            self.engine = LlamaEngine(preset=name, **settings)
        self._kv_preempt0 = self._queue_wait0 = self._state_resets0 = 0
        self._state_rows: list = []

    def mark_window(self) -> None:
        super().mark_window()
        self._state_resets0 = int(self.engine.stats().get("state_resets", 0))
        del self._state_rows[:]

    def active_rows(self) -> int:
        """The sampler's call: the rows holding live state are noted beside."""
        st = self.engine.stats()
        self._state_rows.append(int(st.get("state_rows", 0)))
        return int(st["active_slots"])

    def stats(self) -> Dict[str, Any]:
        out = super().stats()
        out["state_rows_samples"] = list(self._state_rows)
        out["state_resets"] = int(self.engine.stats().get("state_resets", 0)) - self._state_resets0
        return out
