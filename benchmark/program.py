"""The one file of the benchmark that calls into ``kubedl_tpu``.

Everything else under ``benchmark/`` sees the program only through the two
classes here: :class:`ServeProgram` (a ``LlamaEngine`` in this process) and
:class:`TrainProgram` (a ``Trainer`` with its state). A later PR that renames
something in the program changes the program; the benchmark's yardstick (the
generators, the readers, the reference, the comparison) does not move.

Two bridges stand in for what the program lacks, both listed in ``PERF.md``
under what only the program can fix:

- ``LlamaEngine`` resolves its model by ``llama.preset(name)`` from a closed
  table. While the engine is built, ``preset`` is wrapped so that the
  configuration's name returns the ``LlamaConfig`` made from its file.
- The engine makes its weights itself (``llama_init`` from ``PRNGKey(0)``,
  op by op). The benchmark's weights come from ``--seed`` through
  ``benchmark/weights.py``; while the engine is built, ``llama_init`` is
  wrapped to hand it that tree.
"""

from __future__ import annotations

import contextlib
import time
from pathlib import Path
from typing import Any, Dict, Iterator, List

import jax
import jax.numpy as jnp
import numpy as np

from kubedl_tpu.models import llama
from kubedl_tpu.utils.compile_cache import enable_compilation_cache


def enable_cache(root: Path) -> str:
    """The program's own switch: ``JAX_COMPILATION_CACHE_DIR`` where set, else
    the fixed ``<checkout>/.cache/jax``."""
    return enable_compilation_cache(str(Path(root) / ".cache" / "jax"))


def llama_config(config: Dict[str, Any], **overrides: Any) -> llama.LlamaConfig:
    """The program's ``LlamaConfig`` from a configuration file's published keys."""
    heads = int(config["num_attention_heads"])
    head_dim = int(config.get("head_dim") or config["hidden_size"] // heads)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config["torch_dtype"]]
    return llama.LlamaConfig(
        vocab_size=int(config["vocab_size"]),
        dim=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]),
        n_heads=heads,
        n_kv_heads=int(config["num_key_value_heads"]),
        ffn_dim=int(config["intermediate_size"]),
        max_seq=int(config["max_position_embeddings"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=dtype,
        tie_embeddings=bool(config["tie_word_embeddings"]),
        head_dim_fixed=0 if head_dim * heads == int(config["hidden_size"]) else head_dim,
        **overrides,
    )


@contextlib.contextmanager
def _bridged(name: str, cfg: llama.LlamaConfig, weights: Any) -> Iterator[None]:
    real_preset, real_init = llama.preset, llama.llama_init

    def preset(asked: str) -> llama.LlamaConfig:
        return cfg if asked == name else real_preset(asked)

    def init(_key: Any, asked: llama.LlamaConfig) -> Any:
        if asked is not cfg:
            return real_init(_key, asked)
        return weights

    llama.preset, llama.llama_init = preset, init
    try:
        yield
    finally:
        llama.preset, llama.llama_init = real_preset, real_init


class ServeProgram:
    """A ``LlamaEngine`` built from a configuration file, in this process."""

    def __init__(self, name: str, config: Dict[str, Any], weights: Any) -> None:
        from kubedl_tpu.serving.server import EngineOverloaded, LlamaEngine

        self._overloaded = EngineOverloaded
        self.cfg = llama_config(config)
        settings = dict(config["engine"])
        self.max_batch = int(settings["max_batch"])
        with _bridged(name, self.cfg, weights):
            self.engine = LlamaEngine(preset=name, **settings)
        self._kv_preempt0 = 0
        self._queue_wait0 = 0

    def generate(self, prompt: List[int], max_tokens: int) -> Dict[str, Any]:
        """One greedy request; a shed or failed one returns ``{"error": ...}``."""
        try:
            return self.engine.generate(prompt, max_tokens=max_tokens, temperature=0.0)
        except self._overloaded as e:
            return {"error": f"shed: {e}"}

    def mark_window(self) -> None:
        """Counters and records before the window, so readers see its increase."""
        st = self.engine.stats()
        self._kv_preempt0 = int(st.get("kv_preemptions", 0))
        self._queue_wait0 = len(self._queue_waits())

    def _queue_waits(self) -> List[float]:
        # the engine's record is a 4096-deep deque shared with the warm-up;
        # stats() gives only percentiles of all of it
        rec = getattr(self.engine, "_queue_wait_recent", None)
        return list(rec) if rec is not None else []

    def active_rows(self) -> int:
        return int(self.engine.stats()["active_slots"])

    def stats(self) -> Dict[str, Any]:
        """What the per-layer readers may look at after the window."""
        st = self.engine.stats()
        return {
            "pipeline": st.get("pipeline", {}),
            "kv_preemptions": int(st.get("kv_preemptions", 0)) - self._kv_preempt0,
            "queue_wait_ms": self._queue_waits()[self._queue_wait0:],
            "shed": int(st.get("shed", 0)),
            "kv_blocks": st.get("kv_blocks", {}),
            "max_batch": self.max_batch,
        }

    def close(self) -> None:
        """Stop the scheduler thread and drop every device buffer the engine holds."""
        self.engine.close()
        self.engine = None


class TrainProgram:
    """A ``Trainer`` with its compiled step and state, in this process."""

    def __init__(self, config: Dict[str, Any], weights: Any, job: Dict[str, Any],
                 n_chips: int) -> None:
        from kubedl_tpu.api.topology import MeshSpec
        from kubedl_tpu.parallel import mesh as meshlib
        from kubedl_tpu.training.trainer import TrainConfig, Trainer

        t = dict(config["trainer"])
        model = llama_config(
            config, remat_policy=t.pop("remat_policy"), loss_chunk=int(t.pop("loss_chunk")),
        )
        self.seq_len = int(job["seq_len"])
        self.global_batch = int(job["global_batch"])
        self.cfg = TrainConfig(
            model=model, global_batch=self.global_batch, seq_len=self.seq_len,
            # the schedule's length is fixed, whatever a run's window: the
            # same learning rate at the same step in every run
            steps=int(t.pop("schedule_steps")), **t,
        )
        axes = {k: int(v) for k, v in job["mesh"].items()}
        if int(np.prod(list(axes.values()))) != n_chips:
            raise ValueError(f"mesh {axes} does not cover {n_chips} chips")
        mesh = meshlib.build_mesh(MeshSpec(axes), jax.devices()[:n_chips])
        self.trainer = Trainer(self.cfg, mesh=mesh)
        with self.trainer.mesh:
            # a copy: the step donates its state, the caller keeps its tree
            params = jax.tree_util.tree_map(jnp.copy, weights)
            params = jax.device_put(params, self.trainer.state_shardings["params"])
            self.state = {"params": params}
            self.state.update(self.trainer.init_opt_fn(params))
        self.attn_impl = self.trainer.attn_impl
        self.first_moment_decay = 0.9  # make_optimizer's b1
        #: the host's time from one step's callback to the next's: the loader, the
        #: batch's transfer and the dispatch
        self.host_ms: List[float] = []

    def batches(self, path: str, seed: int) -> Any:
        """The repo's loader over a token file."""
        from kubedl_tpu.data import TokenFileDataset

        return TokenFileDataset(path, self.global_batch, self.seq_len, seed=seed % (2 ** 31))

    def run_steps(self, data: Iterator, n_steps: int, on_step_end: Any = None,
                  lag: int = 0) -> Dict[str, Any]:
        """``n_steps`` more steps of the same state through ``Trainer.fit``;
        ``on_step_end(loss)`` runs once for each step, in order, after that
        step's device barrier. With ``lag`` 1 the barrier of a step is taken
        after the next step is dispatched, as ``fit``'s own loop runs ahead of
        the device between its logged steps: the host's work for a step then
        hides behind the device's, and does not stand in every step's time."""
        start = int(jax.device_get(self.state["step"]))
        pending: List[Any] = []
        left = 0.0  # when the last callback returned

        def barrier() -> None:
            with jax.profiler.TraceAnnotation("bench.fit_step_callback"):
                loss = float(jax.device_get(pending.pop(0)))
                if on_step_end is not None:
                    on_step_end(loss)

        def on_step(_i: int, metrics: Dict[str, Any]) -> None:
            nonlocal left
            if left:
                self.host_ms.append(1e3 * (time.perf_counter() - left))
            pending.append(metrics["loss"])
            if len(pending) > lag:
                barrier()
            left = time.perf_counter()

        self.state, summary = self.trainer.fit(
            data, state=self.state, steps=start + n_steps, on_step=on_step,
        )
        while pending:
            barrier()
        return summary

    @staticmethod
    def _named(tree: Any) -> Dict[str, Any]:
        """A parameter-shaped tree by the reference's leaf names: a stacked
        layer leaf counts as one."""
        out = {"embed": tree["embed"], "final_norm": tree["final_norm"], "lm_head": tree["lm_head"]}
        out.update({f"layers.{k}": v for k, v in tree["layers"].items()})
        return out

    @staticmethod
    @jax.jit
    def _norms(tree: Any, minus: Any) -> Any:
        """Norm by leaf of ``tree - minus``, reduced in one program so that the
        float32 difference never exists as a tree."""
        return jax.tree_util.tree_map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32)))),
            tree, minus)

    def _first_moment(self) -> Any:
        found = [p for p in jax.tree_util.tree_leaves(
            self.state["opt_state"], is_leaf=lambda x: hasattr(x, "mu")) if hasattr(p, "mu")]
        if not found:
            raise RuntimeError("no Adam state in the optimizer state")
        return found[0].mu

    def first_moment_norms(self) -> Dict[str, float]:
        """Norm by leaf of Adam's first moment, as the optimizer keeps it."""
        mu = self._first_moment()
        zeros = jax.tree_util.tree_map(lambda a: jnp.zeros((), a.dtype), mu)
        return {k: float(v) for k, v in self._named(self._norms(mu, zeros)).items()}

    def first_moment_host(self) -> Dict[str, np.ndarray]:
        """Adam's first moment copied to the host, by the reference's leaf names."""
        return {k: np.asarray(v) for k, v in self._named(jax.device_get(self._first_moment())).items()}

    def change_norms(self, start: Any) -> Dict[str, float]:
        """Norm by leaf of parameters minus ``start``."""
        with self.trainer.mesh:
            start = jax.device_put(start, self.trainer.state_shardings["params"])
            return {k: float(v) for k, v in self._named(self._norms(self.state["params"], start)).items()}

    def close(self) -> None:
        self.state = None
        self.trainer = None
