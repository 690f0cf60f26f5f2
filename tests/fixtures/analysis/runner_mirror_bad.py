"""Seed regression fixture (the PR 8 mirror-borrow bug in the runner's
shape, BAD form): the engine hands its numpy block-table mirror to the
runner as an argument, and the runner stores a ``jnp.asarray`` borrow of
it into the cache it donates to every program — XLA may then alias a
program's outputs onto the engine's live mirror. Canonical fix lives in
serving/model_runner.py ``_upload_mirror``.
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
        self.cache["bt"] = jnp.asarray(bt)

    def step(self, params):
        logits, self.cache = self._decode(params, self.cache)
        return logits
