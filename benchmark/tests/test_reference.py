"""The plain reference agrees with the program's own forward and loss on a
tiny configuration (float32 on both sides, same weights)."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights
from benchmark.reference import decoder_ref

TINY = json.loads((Path(__file__).parent / "data" / "tiny.json").read_text())


def _both():
    from benchmark import program
    from kubedl_tpu.models import llama

    config = dict(TINY, torch_dtype="float32")
    tree = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                  weights.decoder_weights(5, config))
    cfg = program.llama_config(config, remat=False)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, TINY["vocab_size"], (2, 48)), jnp.int32)
    return llama, cfg, config, tree, tokens


def test_forward_agrees_with_llama_forward():
    llama, cfg, config, tree, tokens = _both()
    got = decoder_ref.forward(tree, tokens, config)
    want = llama.llama_forward(tree, tokens, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_loss_agrees_with_llama_loss():
    llama, cfg, config, tree, tokens = _both()
    got = float(decoder_ref.loss(tree, tokens, config))
    assert abs(got - float(llama.llama_loss(tree, tokens, cfg))) < 1e-4
    assert abs(got - np.log(TINY["vocab_size"])) < 1.0  # random weights: near ln(V)


def test_weights_are_a_function_of_the_seed():
    a = weights.decoder_weights(2**31 + 9, TINY)
    b = weights.decoder_weights(2**31 + 9, TINY)
    c = weights.decoder_weights(3, TINY)
    assert jnp.array_equal(a["layers"]["wq"], b["layers"]["wq"])
    assert not jnp.array_equal(a["layers"]["wq"], c["layers"]["wq"])
    assert a["layers"]["wq"].dtype == jnp.bfloat16 and a["layers"]["wq"].shape == (2, 256, 256)
    assert not jnp.array_equal(a["layers"]["wq"][0], a["layers"]["wq"][1])


def test_blocked_attention_equals_whole(monkeypatch):
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 4, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 40, 2, 16))
    whole = decoder_ref.attention(q, k, k, "float32")
    monkeypatch.setattr(decoder_ref, "QUERY_BLOCK", 16)
    np.testing.assert_allclose(np.asarray(decoder_ref.attention(q, k, k, "float32")),
                               np.asarray(whole), atol=1e-5)
