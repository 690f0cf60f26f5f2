"""Plain reference for training: the first steps of AdamW on the decoder's loss.

Float32 throughout (``precision="highest"`` on a TPU), no kernels, no
rematerialisation policy, no sharding: the loss of ``decoder_ref``, its
gradients by reverse mode one layer at a time (``jax.vjp`` of one layer's
function, one batch row at a time, so that a 4096-token layer's float32
scores fit beside the parameters), global-norm clipping, and AdamW with the
warm-up-then-cosine schedule, written out. The optimizer's moments live on the
host between steps and visit the device one leaf at a time.

It imports nothing of the program under test. The hyper-parameters are the
configuration file's ``trainer`` group; the defaults below are the ones the
file does not state (b1 0.9, b2 0.95, eps 1e-8, the schedule ending at a tenth
of its peak).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import decoder_ref

B1, B2, EPS, END_FRACTION = 0.9, 0.95, 1e-8, 0.1


def learning_rate(count: int, hp: Dict[str, Any]) -> float:
    """Linear warm-up from 0 over ``warmup_steps``, then a cosine from the peak
    to a tenth of it at ``schedule_steps``; ``count`` is the steps already taken."""
    peak, warm = float(hp["learning_rate"]), int(hp["warmup_steps"])
    total = max(int(hp["schedule_steps"]), warm + 1)
    if count < warm:
        return peak * count / warm
    t = min(count - warm, total - warm) / (total - warm)
    return peak * ((1 - END_FRACTION) * 0.5 * (1 + math.cos(math.pi * t)) + END_FRACTION)


def _head_loss(x, final_norm, lm_head, targets, *, eps, precision, scale):
    """Sum of next-token cross entropies of one row, times ``scale``."""
    logits = decoder_ref._matmul(decoder_ref.rmsnorm(x, final_norm, eps), lm_head, precision)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, targets[:, 1:, None], axis=-1)[..., 0]
    return -picked.sum() * scale


_head_grad = jax.jit(
    jax.value_and_grad(_head_loss, argnums=(0, 1, 2)),
    static_argnames=("eps", "precision", "scale"),
)


@partial(jax.jit, static_argnames=("H", "KV", "hd", "theta", "eps", "precision"))
def _layer_vjp(x, lw, dy, *, H, KV, hd, theta, eps, precision):
    fn = partial(decoder_ref._layer.__wrapped__, H=H, KV=KV, hd=hd, theta=theta, eps=eps,
                 precision=precision)
    _, pull = jax.vjp(fn, x, lw)
    return pull(dy)


def loss_and_grads(params: Dict[str, Any], tokens: np.ndarray, config: Dict[str, Any],
                   precision: str = "float32") -> Tuple[float, Dict[str, Any]]:
    """Mean next-token loss of ``tokens [B, S]`` and its gradient, float32."""
    st = decoder_ref._statics(config)
    eps = st["eps"]
    n_layers = int(config["num_hidden_layers"])
    rows, seq = tokens.shape
    scale = 1.0 / (rows * (seq - 1))
    grads = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    total = 0.0
    for r in range(rows):
        row = jnp.asarray(tokens[r: r + 1], jnp.int32)
        xs = [params["embed"][row].astype(jnp.float32)]
        for i in range(n_layers):
            lw = jax.tree_util.tree_map(lambda leaf: leaf[i], params["layers"])
            xs.append(decoder_ref._layer(xs[-1], lw, precision=precision, **st))
        loss, (dx, d_norm, d_head) = _head_grad(
            xs[-1], params["final_norm"], params["lm_head"], row,
            eps=eps, precision=precision, scale=scale)
        total += float(loss)
        grads["final_norm"] = grads["final_norm"] + d_norm
        grads["lm_head"] = grads["lm_head"] + d_head
        for i in reversed(range(n_layers)):
            lw = jax.tree_util.tree_map(lambda leaf: leaf[i], params["layers"])
            dx, d_lw = _layer_vjp(xs[i], lw, dx, precision=precision, **st)
            xs.pop()
            for name, g in d_lw.items():
                grads["layers"][name] = grads["layers"][name].at[i].add(g.astype(jnp.float32))
        grads["embed"] = grads["embed"].at[row[0]].add(dx[0])
    return total, grads


def _leaves(tree: Dict[str, Any]) -> List[Tuple[str, Any]]:
    """(name, leaf) in a fixed order; a stacked layer leaf counts as one."""
    out = [("embed", tree["embed"])]
    out += [(f"layers.{k}", tree["layers"][k]) for k in sorted(tree["layers"])]
    out += [("final_norm", tree["final_norm"]), ("lm_head", tree["lm_head"])]
    return out


def leaf_norms(tree: Dict[str, Any]) -> Dict[str, float]:
    return {name: float(jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32)))))
            for name, leaf in _leaves(tree)}


@partial(jax.jit, static_argnames=("first",), donate_argnums=(0,))
def _adamw_leaf(p, g, mu, nu, lr, clip, c1, c2, decay, *, first):
    g = g * clip
    mu = (1 - B1) * g if first else B1 * mu + (1 - B1) * g
    nu = (1 - B2) * g * g if first else B2 * nu + (1 - B2) * g * g
    update = (mu / c1) / (jnp.sqrt(nu / c2) + EPS) + decay * p
    return p - lr * update, mu, nu


class Adam:
    """AdamW over a float32 tree, its moments kept on the host between steps."""

    def __init__(self, hp: Dict[str, Any], first_grad_seen: Optional[Dict[str, np.ndarray]] = None,
                 seen_scale: float = 1.0, keep_first: bool = False) -> None:
        self.hp = hp
        self.count = 0
        self.mu: Optional[Dict[str, np.ndarray]] = None
        self.nu: Optional[Dict[str, np.ndarray]] = None
        self.first_clipped_norms: Dict[str, float] = {}
        #: a trainer's first gradient by leaf (host arrays, times ``seen_scale``),
        #: to take the norm of its difference from this one's
        self.first_grad_seen, self.seen_scale = first_grad_seen, seen_scale
        self.first_diff_norms: Dict[str, float] = {}
        #: keep this one's first clipped gradient on the host (for a control)
        self.keep_first = keep_first
        self.first_kept: Dict[str, np.ndarray] = {}

    def step(self, params: Dict[str, Any], grads: Dict[str, Any]) -> Dict[str, Any]:
        norms = leaf_norms(grads)
        total = math.sqrt(sum(v * v for v in norms.values()))
        clip = min(1.0, float(self.hp["grad_clip"]) / max(total, 1e-30))
        if self.count == 0:
            # the first gradient as the optimizer gets it: after clipping
            self.first_clipped_norms = {k: v * clip for k, v in norms.items()}
            for name, g in _leaves(grads):
                if self.first_grad_seen is not None:
                    seen = jnp.asarray(self.first_grad_seen[name]).astype(jnp.float32)
                    self.first_diff_norms[name] = float(
                        jnp.sqrt(jnp.sum(jnp.square(seen * self.seen_scale - g * clip))))
                if self.keep_first:
                    self.first_kept[name] = np.asarray(g * clip)
        lr = learning_rate(self.count, self.hp)
        self.count += 1
        c1, c2 = 1 - B1 ** self.count, 1 - B2 ** self.count
        first = self.mu is None
        mu_host, nu_host = {}, {}
        flat_p, flat_g = dict(_leaves(params)), dict(_leaves(grads))
        new = {}
        for name in flat_p:
            mu = jnp.zeros((), jnp.float32) if first else jnp.asarray(self.mu[name])
            nu = jnp.zeros((), jnp.float32) if first else jnp.asarray(self.nu[name])
            new[name], mu, nu = _adamw_leaf(flat_p[name], flat_g[name], mu, nu, lr, clip, c1, c2,
                                            float(self.hp["weight_decay"]), first=first)
            mu_host[name], nu_host[name] = np.asarray(mu), np.asarray(nu)
            flat_g[name] = None
        self.mu, self.nu = mu_host, nu_host
        return {
            "embed": new["embed"],
            "layers": {k[len("layers."):]: v for k, v in new.items() if k.startswith("layers.")},
            "final_norm": new["final_norm"],
            "lm_head": new["lm_head"],
        }


def follow(make_weights: Callable[[], Dict[str, Any]], batches: List[np.ndarray],
           config: Dict[str, Any], precision: str = "float32",
           first_grad_seen: Optional[Dict[str, np.ndarray]] = None, seen_scale: float = 1.0,
           keep_first: bool = False) -> Dict[str, Any]:
    """Take ``len(batches)`` steps from ``make_weights()`` and report what a
    trainer is compared on: each step's loss, the first clipped gradient's norm
    by leaf (and, given a trainer's first gradient, the norm by leaf of the
    difference), and the norm by leaf of the parameters' change after the last
    step.

    The starting weights are made a second time at the end rather than kept:
    parameters and gradients in float32 leave no room for a third tree."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), make_weights())
    opt = Adam(config["trainer"], first_grad_seen, seen_scale, keep_first)
    losses = []
    for batch in batches:
        loss, grads = loss_and_grads(params, np.asarray(batch), config, precision)
        losses.append(loss)
        params = opt.step(params, grads)
        del grads
    start = dict(_leaves(make_weights()))
    change = {name: float(jnp.sqrt(jnp.sum(jnp.square(leaf - start[name].astype(jnp.float32)))))
              for name, leaf in _leaves(params)}
    return {"losses": losses, "first_grad_norms": opt.first_clipped_norms,
            "first_grad_diff_norms": opt.first_diff_norms, "first_grad": opt.first_kept,
            "change_norms": change}
