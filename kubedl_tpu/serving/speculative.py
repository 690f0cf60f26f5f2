"""Draft-k / verify-1 speculative decoding for the paged serving engine.

Decode is memory-bandwidth bound: every generated token re-reads the
whole weight set for ONE row of matmul work. Speculative decoding buys
back that bandwidth by making each target-model forward score ``k+1``
positions at once: a cheap *draft* proposes k tokens, the target scores
the whole proposal in one batched forward over the paged cache
(`llama.paged_verify`), the longest prefix where the draft agrees with
the target's own greedy choice is accepted, and one "bonus" token — the
target's argmax after the last accepted position — is emitted for free.
Every emitted token is the target's argmax given only accepted history,
so GREEDY outputs are bit-identical to plain decode by construction (the
tier-1 gate); the only thing speculation changes is how many sequential
forwards it takes to produce them. Rejected-suffix KV lands beyond the
rolled-back position and its blocks are freed in place by the engine
(docs/serving.md "Speculative decoding").

Drafts are PLUGGABLE: anything with ``propose(context, k) -> list[int]``
works. Shipped drafts:

- :class:`NgramDraft` ("ngram", the default): self-speculative prompt-
  lookup — match the tail n-gram of the context against its own earlier
  tokens and propose whatever followed the most recent match. Zero
  model cost; strong on the repetitive traffic (templated output,
  retried generations, code) where speculation pays most.
- :class:`RepeatDraft` ("repeat"): propose the last token k times — the
  degenerate baseline that still wins on run-length-heavy output.
- :class:`ScriptedDraft`: tests force exact proposal streams to pin the
  acceptance-length distribution.
- :class:`ModelDraft` ("model"): a real small-model draft — greedy
  decode k tokens from its own (smaller) weights in its own contiguous
  cache, batched across verifying rows in one prefill + one decode
  segment. :meth:`ModelDraft.from_target` carves an early-exit draft out
  of the target's own stacked layer weights (first n layers + shared
  embed/norm/head) — with the `tiny-deep` preset's zero-init deep
  residuals that pairing agrees with the target at init, the CPU-scale
  proxy for a trained draft/target pair.

Multi-candidate verification rides on :meth:`DraftModel
.propose_candidates`: N candidate continuations per row, scored by the
target in ONE read-only forward (`llama.paged_verify_multi`); the engine
re-verifies only the winner through the standard write path, so emitted
tokens stay the target's own argmax. The default implementation returns
the single `propose()` list; ModelDraft branches candidates at the first
token (top-N draft logits, greedy continuations), with candidate 0
always the pure-greedy proposal — which is why multi-candidate accepts
at least as much as single-candidate on the same seeds.

Tree speculation (:class:`DraftTree`) folds those N chains into a
prefix TRIE before verification: chains sharing a prefix share trie
nodes, so the verify window is the trie size (≤ 1 + N*k, typically far
smaller) instead of the flat N*(k+1) multi-verify rows. The target
scores every node in one read-only forward (`llama.paged_verify_tree`,
per-node ancestor mask), the host walks the deepest accepted root path
(:meth:`DraftTree.walk` — `accept_length` generalized to trees), and
the engine re-verifies that winning path through the standard write
path. Emission always comes from the write-path verify, so greedy
output stays bit-identical to plain decode at every tree shape.

A wrong draft can never corrupt output — it only wastes the verify
forward — so draft quality is purely a throughput knob, measured by the
acceptance rate the engine exports (`stats()["speculative"]` and the
``kubedl_tpu_serving_spec_*`` metrics).
"""

from __future__ import annotations

import os
import threading
from collections import deque
from typing import Dict, List, Optional, Sequence


class DraftModel:
    """Protocol for draft proposers (duck-typed; subclassing optional)."""

    name = "draft"

    def propose(self, context: Sequence[int], k: int) -> List[int]:
        """Return up to ``k`` proposed continuation tokens for
        ``context`` (prompt + generated so far). Shorter lists are
        allowed — the engine pads the verify window with repeats of the
        last proposal and simply accepts less."""
        raise NotImplementedError

    def propose_batch(
        self, contexts: Sequence[Sequence[int]], k: int
    ) -> List[List[int]]:
        """Batched :meth:`propose` (one call per verify tick). Model
        drafts override this to amortize their forward across rows."""
        return [self.propose(ctx, k) for ctx in contexts]

    def propose_candidates(
        self, context: Sequence[int], k: int, n: int
    ) -> List[List[int]]:
        """Up to ``n`` candidate continuations for multi-candidate
        verify. Candidate 0 MUST be the plain :meth:`propose` output —
        the engine relies on that to guarantee multi-candidate never
        accepts fewer tokens than the single-candidate path."""
        return [self.propose(context, k)]


class RepeatDraft(DraftModel):
    """Propose the last context token k times: the zero-knowledge
    baseline. Wins exactly on run-length repetition."""

    name = "repeat"

    def propose(self, context: Sequence[int], k: int) -> List[int]:
        if not context:
            return []
        return [int(context[-1])] * k


class NgramDraft(DraftModel):
    """Self-speculative prompt-lookup decoding: find the most recent
    earlier occurrence of the context's tail ``n``-gram (longest match
    first, down to 1) and propose the tokens that followed it. The
    context IS the draft model — no weights, no device time."""

    name = "ngram"

    def __init__(self, max_ngram: int = 3, window: int = 1024) -> None:
        self.max_ngram = max(1, int(max_ngram))
        self.window = max(8, int(window))

    def propose(self, context: Sequence[int], k: int) -> List[int]:
        ctx = [int(t) for t in context[-self.window:]]
        n_ctx = len(ctx)
        if n_ctx < 2:
            return []
        for n in range(min(self.max_ngram, n_ctx - 1), 0, -1):
            tail = ctx[n_ctx - n:]
            # scan for the most recent PRIOR occurrence of the tail
            for i in range(n_ctx - n - 1, -1, -1):
                if ctx[i:i + n] == tail:
                    out = ctx[i + n:i + n + k]
                    if out:
                        return out
                    break
        # no lookup hit: fall back to run-length repetition
        return [ctx[-1]] * k


class ScriptedDraft(DraftModel):
    """Deterministic proposal stream for tests: pops pre-seeded
    proposals in order, then falls back to repeats."""

    name = "scripted"

    def __init__(self, proposals: Sequence[Sequence[int]]) -> None:
        self._q = deque([list(p) for p in proposals])

    def propose(self, context: Sequence[int], k: int) -> List[int]:
        if self._q:
            return [int(t) for t in self._q.popleft()][:k]
        return RepeatDraft().propose(context, k)


class ModelDraft(DraftModel):
    """Small-model draft: greedy-decode ``k`` tokens from its own
    weights. Each proposal round is one batched prefill over the rows'
    recent context windows plus one greedy decode segment in a FRESH
    contiguous cache (the draft is small enough that re-prefilling a
    bounded window every round beats keeping per-row draft caches
    coherent with the target's accept/rewind churn). All jitted closures
    cache by shape; context lengths are padded to ``pad_to`` multiples
    and batch to powers of two so the engine's varying row counts reuse
    a handful of compiles."""

    name = "model"

    def __init__(self, params, cfg, max_context: int = 512,
                 pad_to: int = 32) -> None:
        import jax

        from kubedl_tpu.models import llama

        self.params = params
        self.cfg = cfg
        self.max_context = min(int(max_context), cfg.max_seq)
        self.pad_to = max(8, int(pad_to))
        self._llama = llama
        self._jnp = jax.numpy
        # named functions, not lambdas: a device profile shows a jitted
        # program as jit_<__name__> (docs/observability.md)
        def draft_prefill(p, c, t, l):
            return llama.prefill_batched(p, c, t, l, cfg)

        self._prefill = jax.jit(draft_prefill)
        self._segments: Dict[int, object] = {}
        self._key = jax.random.PRNGKey(0)  # greedy: never consumed

    @classmethod
    def from_target(cls, params, cfg, n_layers: int,
                    **kwargs) -> "ModelDraft":
        """Early-exit draft: the target's first ``n_layers`` decoder
        layers (sliced off the stacked [L, ...] arrays — views, no
        copies) with the shared embedding / final norm / lm head. With
        `zero_init_deep_from <= n_layers` the deep layers are identity
        residuals and the slice IS the target; in general it is the
        standard early-exit approximation."""
        import dataclasses

        import jax

        n = max(1, min(int(n_layers), cfg.n_layers))
        draft_params = {k: v for k, v in params.items() if k != "layers"}
        # tree_map, not a dict comprehension: quantized layer leaves are
        # nested {"w", "scale"} dicts, all stacked [L, ...] on axis 0
        draft_params["layers"] = jax.tree_util.tree_map(
            lambda a: a[:n], params["layers"]
        )
        draft_cfg = dataclasses.replace(cfg, n_layers=n)
        return cls(draft_params, draft_cfg, **kwargs)

    @classmethod
    def from_zoo(cls, name: str, target_cfg, seed: int = 0,
                 ckpt_path: Optional[str] = None, **kwargs) -> "ModelDraft":
        """A *trainable* small draft shaped by the planner MODEL_ZOO
        entry ``name`` — its own weights, not a slice of the target's.
        Vocab / max_seq / dtype come from the target (the draft proposes
        target tokens); depth and widths from the zoo descriptor. Fresh
        weights propose noise — ``ckpt_path`` restores a checkpoint
        saved by :meth:`save` (e.g. after :func:`distill_draft`), which
        is what makes this the trained-draft arm of the decode bench."""
        import jax

        from kubedl_tpu.models import llama
        from kubedl_tpu.planner.costmodel import MODEL_ZOO

        try:
            desc = MODEL_ZOO[name]
        except KeyError:
            raise ValueError(
                f"unknown zoo draft {name!r} (have: {sorted(MODEL_ZOO)})"
            ) from None
        heads = max(1, desc.hidden // 64)
        cfg = llama.LlamaConfig(
            vocab_size=target_cfg.vocab_size, dim=desc.hidden,
            n_layers=desc.layers, n_heads=heads, n_kv_heads=heads,
            ffn_dim=desc.ffn, max_seq=target_cfg.max_seq,
            dtype=target_cfg.dtype, remat=False,
        )
        params = llama.llama_init(jax.random.PRNGKey(seed), cfg)
        draft = cls(params, cfg, **kwargs)
        draft.name = f"zoo:{name}"
        if ckpt_path:
            draft.load(ckpt_path)
        return draft

    def save(self, path: str) -> None:
        """Flat-npz draft checkpoint (leaves in tree order). The draft
        is one process's worth of small arrays — the sharded trainer
        checkpoint machinery would be pure overhead here."""
        import numpy as np

        import jax

        leaves = jax.tree_util.tree_leaves(self.params)
        np.savez(path, **{
            f"leaf_{i}": np.asarray(jax.device_get(l))
            for i, l in enumerate(leaves)
        })

    def load(self, path: str) -> None:
        """Restore :meth:`save` output into the existing param tree
        (shapes must match — the zoo descriptor pins them)."""
        import numpy as np

        import jax
        import jax.numpy as jnp

        leaves, treedef = jax.tree_util.tree_flatten(self.params)
        with np.load(path) as z:
            new = []
            for i, old in enumerate(leaves):
                arr = z[f"leaf_{i}"]
                if tuple(arr.shape) != tuple(old.shape):
                    raise ValueError(
                        f"draft checkpoint leaf {i} shape {arr.shape} != "
                        f"model shape {tuple(old.shape)}"
                    )
                new.append(jnp.asarray(arr, old.dtype))
        self.params = jax.tree_util.tree_unflatten(treedef, new)

    def _segment_fn(self, n_steps: int):
        import jax

        llama, cfg = self._llama, self.cfg
        fn = self._segments.get(n_steps)
        if fn is None:
            def draft_decode_seg(p, c, t, z, key):
                return llama.decode_segment(
                    p, c, t, z, key, cfg, n_steps, greedy=True
                )

            draft_decode_seg.__name__ = f"draft_decode_seg{n_steps}"
            fn = jax.jit(draft_decode_seg)
            self._segments[n_steps] = fn
        return fn

    def _prefill_padded(self, contexts: Sequence[Sequence[int]], k: int):
        """Left-truncate each context to the draft window, right-pad to
        a shape bucket, run one batched prefill. Returns (last-token
        logits [Bp, V], cache, B)."""
        jnp, llama = self._jnp, self._llama
        B = len(contexts)
        Bp = 1
        while Bp < B:
            Bp *= 2
        win = max(1, self.max_context - k - 1)
        ctxs = [list(map(int, c))[-win:] for c in contexts]
        P = max(max((len(c) for c in ctxs), default=1), 1)
        P = ((P + self.pad_to - 1) // self.pad_to) * self.pad_to
        toks = [c + [0] * (P - len(c)) for c in ctxs]
        toks += [[0] * P] * (Bp - B)
        lens = [len(c) for c in ctxs] + [0] * (Bp - B)
        cache = llama.init_batched_cache(self.cfg, Bp, self.max_context)
        logits, cache = self._prefill(
            self.params, cache,
            jnp.asarray(toks, jnp.int32), jnp.asarray(lens, jnp.int32),
        )
        return logits, cache, B

    def propose(self, context: Sequence[int], k: int) -> List[int]:
        return self.propose_batch([context], k)[0]

    def propose_batch(
        self, contexts: Sequence[Sequence[int]], k: int
    ) -> List[List[int]]:
        if k <= 0 or not contexts:
            return [[] for _ in contexts]
        jnp = self._jnp
        logits, cache, B = self._prefill_padded(contexts, k)
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [Bp]
        if k == 1:
            import numpy as np

            return [[int(t)] for t in np.asarray(first)[:B]]
        Bp = first.shape[0]
        toks, _, _, _ = self._segment_fn(k - 1)(
            self.params, cache, first[:, None],
            jnp.zeros((Bp,), jnp.float32), self._key,
        )
        import numpy as np

        first_h = np.asarray(first)
        toks_h = np.asarray(toks)
        return [
            [int(first_h[b])] + [int(t) for t in toks_h[b]]
            for b in range(B)
        ]

    def propose_candidates(
        self, context: Sequence[int], k: int, n: int
    ) -> List[List[int]]:
        """Branch at the first token: the draft's top-``n`` first tokens
        (descending — candidate 0 is the greedy proposal), each continued
        greedily. One prefill over n identical rows, one segment."""
        if n <= 1 or k <= 0:
            return [self.propose(context, k)]
        import numpy as np

        import jax

        jnp = self._jnp
        logits, cache, _ = self._prefill_padded([context] * n, k)
        _, top = jax.lax.top_k(logits[0], n)
        firsts = top.astype(jnp.int32)  # [n], descending score
        Bp = logits.shape[0]
        firsts_full = jnp.concatenate(
            [firsts, jnp.zeros((Bp - n,), jnp.int32)]
        )
        if k == 1:
            return [[int(t)] for t in np.asarray(firsts)]
        toks, _, _, _ = self._segment_fn(k - 1)(
            self.params, cache, firsts_full[:, None],
            jnp.zeros((Bp,), jnp.float32), self._key,
        )
        firsts_h, toks_h = np.asarray(firsts), np.asarray(toks)
        return [
            [int(firsts_h[i])] + [int(t) for t in toks_h[i]]
            for i in range(n)
        ]


class DraftTree:
    """Prefix trie over candidate draft chains for tree speculation.

    Node 0 is the ROOT: the row's next verify input (its last accepted
    token), depth 0. Every other node is one proposed draft token; a
    node's root path spells one draft prefix, and chains that share a
    prefix share nodes — the whole reason the trie beats the flat
    multi-candidate layout. :meth:`arrays` emits the fixed-size
    (tokens, depth, ancestor-mask) layout `llama.paged_verify_tree`
    consumes; :meth:`walk` follows the target's greedy ids down the
    trie to the deepest accepted path."""

    __slots__ = ("tokens", "parents", "depth", "children")

    def __init__(self, root_token: int) -> None:
        self.tokens: List[int] = [int(root_token)]
        self.parents: List[int] = [-1]
        self.depth: List[int] = [0]
        self.children: List[Dict[int, int]] = [{}]

    @property
    def size(self) -> int:
        return len(self.tokens)

    def insert(self, chain: Sequence[int], m_max: int) -> None:
        """Merge one candidate chain into the trie, capped at ``m_max``
        total nodes (excess suffix tokens are dropped — never verified,
        never emitted, so the cap only costs acceptance length)."""
        cur = 0
        for t in chain:
            t = int(t)
            nxt = self.children[cur].get(t)
            if nxt is None:
                if len(self.tokens) >= m_max:
                    return
                nxt = len(self.tokens)
                self.tokens.append(t)
                self.parents.append(cur)
                self.depth.append(self.depth[cur] + 1)
                self.children.append({})
                self.children[cur][t] = nxt
            cur = nxt

    def arrays(self, m_max: int):
        """Fixed-shape verify inputs: ``(tokens [m_max], depth [m_max],
        mask [m_max, m_max])`` numpy arrays. ``mask[m, t]`` is True iff
        t is m or an ancestor of m. Pad nodes repeat the root token as
        depth-1 children of the root: well-formed rows whose outputs the
        walk never reads, and — the masks being per-node — invisible to
        every live node's attention."""
        import numpy as np

        M = len(self.tokens)
        if M > m_max:
            raise ValueError(f"trie size {M} exceeds m_max {m_max}")
        toks = np.full((m_max,), self.tokens[0], np.int32)
        dep = np.ones((m_max,), np.int32)
        mask = np.zeros((m_max, m_max), bool)
        toks[:M] = self.tokens
        dep[:M] = self.depth
        for m in range(M):
            a = m
            while a != -1:
                mask[m, a] = True
                a = self.parents[a]
        for m in range(M, m_max):
            mask[m, m] = True
            mask[m, 0] = True
        return toks, dep, mask

    def walk(self, ids: Sequence[int]) -> List[int]:
        """Deepest accepted path: starting at the root, repeatedly step
        to the child whose token equals the target's greedy continuation
        ``ids[cur]`` at the current node; stop when no child matches.
        Returns the accepted DRAFT tokens along that path (root
        excluded) — `accept_length` over a chain trie, exactly."""
        path: List[int] = []
        cur = 0
        while True:
            nxt = self.children[cur].get(int(ids[cur]))
            if nxt is None:
                return path
            path.append(self.tokens[nxt])
            cur = nxt


def build_tree(
    root_token: int, chains: Sequence[Sequence[int]], k: int, m_max: int
) -> DraftTree:
    """Fold candidate ``chains`` (each ≤ k draft tokens) into one
    :class:`DraftTree`, inserting in order so candidate 0 — the greedy
    proposal — is never the one truncated by the node cap."""
    tree = DraftTree(root_token)
    for c in chains:
        tree.insert([int(t) for t in c][:k], m_max)
    return tree


def distill_draft(
    draft: "ModelDraft",
    target_params,
    target_cfg,
    prompts: Sequence[Sequence[int]],
    gen_len: int = 16,
    steps: int = 40,
    lr: float = 1e-2,
) -> List[float]:
    """Train ``draft`` to imitate the target's GREEDY rollouts: generate
    continuations with the target from each prompt, then fit the draft
    with the standard next-token loss on the concatenated sequences
    (hard-label distillation — exactly the objective that maximizes
    greedy acceptance, which is all a draft is scored on). Mutates
    ``draft.params`` in place and returns the per-step losses. CPU-scale
    by design: the zoo drafts this trains are tiny."""
    import jax
    import jax.numpy as jnp
    import optax

    from kubedl_tpu.models import llama

    # the teacher IS a ModelDraft over the target weights: one batched
    # prefill + greedy segment gives every rollout
    teacher = ModelDraft(target_params, target_cfg,
                         max_context=target_cfg.max_seq)
    conts = teacher.propose_batch(prompts, gen_len)
    seqs = [list(map(int, p)) + c for p, c in zip(prompts, conts)]
    L = min(len(s) for s in seqs)
    toks = jnp.asarray([s[:L] for s in seqs], jnp.int32)

    opt = optax.adam(lr)
    params = draft.params
    opt_state = opt.init(params)
    cfg = draft.cfg

    @jax.jit
    def step(params, opt_state):
        loss, grads = jax.value_and_grad(
            lambda p: llama.llama_loss(p, toks, cfg)
        )(params)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(max(1, int(steps))):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    draft.params = params
    return losses


_DRAFTS = {
    "ngram": NgramDraft,
    "repeat": RepeatDraft,
}


def make_draft(name: str, params=None, cfg=None, max_context: int = 0,
               n_layers: int = 0) -> DraftModel:
    """Draft factory for the engine's ``spec_draft`` knob. The model
    drafts take the target's ``params``/``cfg`` and the engine's context
    length: "model" is an early-exit draft carved out of the target's own
    stacked weights (views, no copies; ``n_layers`` deep, half the target
    by default), "zoo:<name>" a trained small model shaped by the planner
    MODEL_ZOO (KUBEDL_SPEC_DRAFT_CKPT restores weights saved after
    distillation; fresh weights propose noise — harmless, just zero
    acceptance)."""
    if name == "model":
        return ModelDraft.from_target(
            params, cfg, n_layers=n_layers or max(1, cfg.n_layers // 2),
            max_context=max_context,
        )
    if name.startswith("zoo:"):
        ckpt = os.environ.get("KUBEDL_SPEC_DRAFT_CKPT", "")
        return ModelDraft.from_zoo(
            name.split(":", 1)[1], cfg, ckpt_path=ckpt or None,
            max_context=max_context,
        )
    try:
        return _DRAFTS[name]()
    except KeyError:
        raise ValueError(
            f"unknown draft {name!r} (have: {sorted(_DRAFTS)})"
        ) from None


def accept_length(drafts: Sequence[int], greedy_ids: Sequence[int]) -> int:
    """Longest agreeing prefix: number of draft tokens ``a`` such that
    ``drafts[j] == greedy_ids[j]`` for all ``j < a`` (greedy_ids[j] is
    the target's argmax after consuming the j-th verify input). The
    engine emits ``greedy_ids[:a+1]`` — a accepted drafts plus the bonus
    token, every one of them the target's own greedy choice."""
    a = 0
    for d, g in zip(drafts, greedy_ids):
        if int(d) != int(g):
            break
        a += 1
    return a


class SpecStats:
    """Acceptance accounting shared by the engine, stats(), and
    /metrics. ``accepted``/``proposed`` count DRAFT tokens (the bonus
    token is not a draft — a 0-acceptance verify still emits one token);
    ``window`` keeps recent per-verify acceptance lengths for the
    distribution tests and the p50 the autoscaler reads."""

    def __init__(self, maxlen: int = 4096) -> None:
        self._lock = threading.Lock()
        self.proposed = 0
        self.accepted = 0
        self.verifies = 0
        self.emitted = 0
        self.candidates_scored = 0
        self.candidate_switches = 0
        self.draft_ms_total = 0.0
        self.window: "deque[int]" = deque(maxlen=maxlen)
        self.draft_ms_window: "deque[float]" = deque(maxlen=maxlen)

    def record(self, proposed: int, accepted: int, emitted: int) -> None:
        with self._lock:
            self.proposed += int(proposed)
            self.accepted += int(accepted)
            self.verifies += 1
            self.emitted += int(emitted)
            self.window.append(int(accepted))

    def record_draft_ms(self, ms: float) -> None:
        """Wall time of one draft proposal round (all rows)."""
        with self._lock:
            self.draft_ms_total += float(ms)
            self.draft_ms_window.append(float(ms))

    def record_candidates(self, scored: int, switched: bool) -> None:
        """One multi-candidate verify: ``scored`` candidates ranked,
        ``switched`` = the winner was NOT the greedy candidate 0."""
        with self._lock:
            self.candidates_scored += int(scored)
            self.candidate_switches += 1 if switched else 0

    def acceptance_rate(self) -> float:
        with self._lock:
            return self.accepted / self.proposed if self.proposed else 0.0

    def snapshot(self) -> Dict:
        with self._lock:
            win = list(self.window)
            out = {
                "proposed": self.proposed,
                "accepted": self.accepted,
                "verifies": self.verifies,
                "emitted": self.emitted,
                "candidates_scored": self.candidates_scored,
                "candidate_switches": self.candidate_switches,
                "draft_ms_total": round(self.draft_ms_total, 3),
            }
            dwin = list(self.draft_ms_window)
        if dwin:
            out["draft_ms_p50"] = sorted(dwin)[len(dwin) // 2]
        out["acceptance_rate"] = round(
            out["accepted"] / out["proposed"], 4
        ) if out["proposed"] else 0.0
        out["tokens_per_verify"] = round(
            out["emitted"] / out["verifies"], 4
        ) if out["verifies"] else 0.0
        if win:
            srt = sorted(win)
            out["accept_len_p50"] = srt[len(srt) // 2]
            out["accept_len_mean"] = round(sum(win) / len(win), 4)
        return out


__all__ = [
    "DraftModel", "NgramDraft", "RepeatDraft", "ScriptedDraft",
    "ModelDraft", "make_draft", "accept_length", "SpecStats",
    "DraftTree", "build_tree", "distill_draft",
]
