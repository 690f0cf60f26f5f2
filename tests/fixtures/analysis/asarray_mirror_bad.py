"""Seed regression fixture (the PR 8 mirror-borrow bug, BAD form):
``jnp.asarray`` of a persistent numpy host mirror (``self._bt_host``)
passed into a call whose donated cache lets XLA alias segment outputs
onto the borrowed mirror memory. Canonical fix lives in
serving/model_runner.py ``_upload_mirror``.
"""

import jax
import jax.numpy as jnp
import numpy as np


def _decode_step(cache, block_table):
    return cache


class Decoder:
    def __init__(self):
        self._bt_host = np.zeros((4, 4), dtype=np.int32)
        self._decode = jax.jit(_decode_step, donate_argnums=(0,))

    def step(self, cache):
        bt = jnp.asarray(self._bt_host)
        return self._decode(cache, bt)
