"""The comparison that decides ``correct``.

Serving: once the window has closed, a sample of the requests it finished
(drawn from the seed, the longest always in it) is run once through the plain
reference, prompt and served tokens together. For every served token the
reference's logit of that token is compared with the reference's best at that
position. Greedy decoding in bfloat16 may pick a near-tie's other side, so
tokens are not compared one for one: the number compared is the gap by which a
served token lies below the reference's best, its widest and its mean over the
sample. A token altered where it is produced, a cache read from the wrong
block, or weights rounded to 8 bits all widen it.

The control (``control_gaps``) puts the reference in the program's place in the
nearest precision below the configuration's bfloat16: at each of the same
positions it reads the gap of the token that int8 arithmetic puts first.

Limits live in ``benchmark/limits/<cell>.json``, each with the readings it
was set from (``PERF.md`` section 2).
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import jax.numpy as jnp
import numpy as np

from benchmark import families

#: sequences are padded to a multiple of this for the reference, so that a
#: handful of compiled shapes serves every sample (padding sits after the
#: last real position, where a causal model cannot see it)
PAD_TO = 256


def pick_sample(requests: Sequence[Dict[str, Any]], seed: int,
                min_requests: int = 4, min_tokens: int = 256,
                max_requests: int = 8) -> List[Dict[str, Any]]:
    """Finished requests to check: the longest, then others in the seed's
    order until there are ``min_requests`` and ``min_tokens`` served tokens."""
    done = [r for r in requests if r["ok"] and r["n_out"] > 0]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["prompt"]) + r["n_out"])
    sample = [longest]
    rest = [r for r in done if r is not longest]
    for i in np.random.default_rng([int(seed), 7]).permutation(len(rest)):
        if len(sample) >= max_requests or (
            len(sample) >= min_requests and sum(r["n_out"] for r in sample) >= min_tokens
        ):
            break
        sample.append(rest[int(i)])
    return sample


def _reference_logits(tree: Any, config: Dict[str, Any], req: Dict[str, Any],
                      precision: str, every_position: bool = False) -> np.ndarray:
    """Reference logits at the positions that predict each served token (or,
    for the control, at every position of the prompt and the served tokens)."""
    p, n = len(req["prompt"]), req["n_out"]
    seq = list(req["prompt"]) + list(req["tokens"][: n - 1])
    padded = -(-len(seq) // PAD_TO) * PAD_TO
    tokens = jnp.asarray(seq + [0] * (padded - len(seq)), jnp.int32)
    first, count = (0, len(seq)) if every_position else (p - 1, n)
    # positions too are padded (by repeats of the last), to bound the shapes
    positions = np.minimum(np.arange(first, first + -(-count // 64) * 64), first + count - 1)
    logits = families.load(config).logits_at(tree, tokens, jnp.asarray(positions), config, precision)
    return np.asarray(logits)[:count]


def served_gaps(tree: Any, config: Dict[str, Any], sample: Sequence[Dict[str, Any]]) -> np.ndarray:
    """For every served token of the sample: reference's best logit at its
    position minus the reference's logit of the served token (>= 0)."""
    gaps = []
    for req in sample:
        logits = _reference_logits(tree, config, req, "float32")
        served = np.asarray(req["tokens"][: req["n_out"]])
        gaps.append(logits.max(axis=-1) - logits[np.arange(len(served)), served])
    return np.concatenate(gaps) if gaps else np.zeros((0,))


def control_gaps(tree: Any, config: Dict[str, Any], sample: Sequence[Dict[str, Any]],
                 precision: str = "int8", every_position: bool = False) -> np.ndarray:
    """The same gaps for the tokens that ``precision`` arithmetic puts first
    at the same positions of the same prompts and tokens (``every_position``:
    at the prompts' positions too, where a sample's served tokens are few)."""
    gaps = []
    for req in sample:
        ref = _reference_logits(tree, config, req, "float32", every_position)
        low = _reference_logits(tree, config, req, precision, every_position).argmax(axis=-1)
        gaps.append(ref.max(axis=-1) - ref[np.arange(len(low)), low])
    return np.concatenate(gaps) if gaps else np.zeros((0,))


def gap_numbers(gaps: np.ndarray) -> Dict[str, float]:
    if gaps.size == 0:
        return {"served_gap_max": float("inf"), "served_gap_mean": float("inf")}
    return {"served_gap_max": float(gaps.max()), "served_gap_mean": float(gaps.mean())}


def compare(numbers: Dict[str, float], limits: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Each number beside its limit; a number with no limit is refused."""
    out = []
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for compared number {name!r}")
        limit = float(limits[name]["limit"])
        out.append({"name": name, "value": value, "limit": limit,
                    "ok": bool(np.isfinite(value) and value <= limit)})
    return out


def check_served(seed: int, config: Dict[str, Any], requests: Sequence[Dict[str, Any]],
                 limits: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Run after the program's state is freed: the reference's weights are a
    second tree from the same seed and would not fit beside the first."""
    sample = pick_sample(requests, seed, **limits.get("sample", {}))
    tree = families.load(config).weights(seed, config)
    numbers = gap_numbers(served_gaps(tree, config, sample))
    compared = compare(numbers, limits)
    compared.append({"name": "sampled_tokens", "value": float(sum(r["n_out"] for r in sample)),
                     "limit": 1.0, "ok": bool(sample), "at_least": True})
    return compared


# ---- training ---------------------------------------------------------------

def worst_leaf_gap(seen: Dict[str, float], ref: Dict[str, float]) -> float:
    """The largest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some gradients are all but zero)."""
    median = float(np.median(list(ref.values())))
    return max(abs(seen[k] - ref[k]) / max(ref[k], median, 1e-30) for k in ref)


def trained_numbers(seen: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """``ref`` is the reference's ``follow`` given ``seen``'s first gradient."""
    numbers = {f"loss_gap_step{i + 1}": abs(a - b)
               for i, (a, b) in enumerate(zip(seen["losses"], ref["losses"]))}
    numbers["first_grad_norm_gap"] = worst_leaf_gap(seen["first_grad_norms"], ref["first_grad_norms"])
    # the norm of the difference, which a change of precision moves where the
    # gap between the norms hardly does (PERF.md section 2)
    norms = ref["first_grad_norms"]
    median = float(np.median(list(norms.values())))
    numbers["first_grad_diff"] = max(
        d / max(norms[k], median, 1e-30) for k, d in ref["first_grad_diff_norms"].items())
    numbers["param_change_gap"] = worst_leaf_gap(seen["change_norms"], ref["change_norms"])
    return numbers


def check_trained(seed: int, config: Dict[str, Any], batches: Sequence[np.ndarray],
                  seen: Dict[str, Any], record: Dict[str, Any],
                  limits: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The reference follows the trainer's first steps on the same batches
    from the same seeded weights; run after the trainer's state is freed."""
    family = families.load(config)
    ref = family.train_follow(lambda: family.weights(seed, config), list(batches), config,
                              first_grad_seen=seen["first_grad"], seen_scale=seen["first_grad_scale"])
    compared = compare(trained_numbers(seen, ref), limits)
    fell = record["loss_first"] - record["loss_last"]
    compared.append({"name": "loss_fell_by", "value": float(fell), "limit": 0.0,
                     "ok": bool(np.isfinite(fell) and fell > 0), "at_least": True})
    return compared


def control_trained(seed: int, config: Dict[str, Any], batches: Sequence[np.ndarray],
                    precision: str = "int8") -> Dict[str, float]:
    """The control: the reference in ``precision`` put in the trainer's place,
    compared with the float32 reference exactly as a trainer is."""
    family = families.load(config)
    make = lambda: family.weights(seed, config)  # noqa: E731
    low = family.train_follow(make, list(batches), config, precision, keep_first=True)
    ref = family.train_follow(make, list(batches), config, first_grad_seen=low["first_grad"])
    return trained_numbers(low, ref)
