"""Seed regression fixture (the PR 8 mirror-borrow bug in the runner's
shape, FIXED form): what reaches the donated cache is an XLA-owned copy of
a private snapshot of the engine's mirror (serving/model_runner.py
``_upload_mirror``).
"""

import jax
import jax.numpy as jnp


def _decode_step(params, cache):
    return cache["k"].sum(), cache


class Runner:
    def __init__(self):
        self.cache = {"k": jnp.zeros((4, 4)), "bt": jnp.zeros((4, 4), jnp.int32)}
        self._decode = jax.jit(_decode_step, donate_argnums=(1,))

    def upload_mirrors(self, bt):
        self.cache["bt"] = jnp.asarray(bt.copy()) + 0

    def step(self, params):
        logits, self.cache = self._decode(params, self.cache)
        return logits
