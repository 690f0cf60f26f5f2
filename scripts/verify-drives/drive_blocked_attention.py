"""Verify drive: blocked paged attention + model-based drafts (PR 11).

Drives the blocked-attention decode kernel and model-draft speculation
through the PUBLIC surface — real LlamaEngines behind the real HTTP
handler — and checks the contracts docs/serving.md "Blocked paged
attention" / "Model drafts" promise:

  1. greedy outputs over HTTP with kv_attention="blocked" are
     bit-identical to the gather-oracle engine (the exactness gate,
     end to end, ragged prompts included);
  2. /v1/stats carries kv_blocks.attention_kernel and /metrics serves
     the kv gauges with the attention_kernel label;
  3. spec_draft="model" (early-exit slice of the target) stays
     bit-identical over HTTP on the blocked kernel, with acceptance
     > 0.5 on the tiny-deep proxy pair and draft wall time on the
     books (draft_ms_p50 + the spec_draft_ms metric, draft label);
  4. multi-candidate verification (spec_candidates=2) accepts >= the
     single-candidate run on the same requests, with candidates
     actually scored;
  5. KUBEDL_SERVE_CONFIG plumbing (kv_attention/spec_draft/
     spec_candidates/spec_draft_layers reach engine_kwargs; gather
     stays the default) and Predictor field plumbing through
     framework._jax_setter;
  6. raw-kernel parity: the lax blocked kernel matches a float64
     dense reference on a ragged hand-built pool (trash-block row
     included);
  7. blocked-attention host overhead stays under the tier-1 budget.

Run: python scripts/verify-drives/drive_blocked_attention.py
(CPU-forced, ~2 min)
"""

import json
import os
import sys
import threading
import urllib.request
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

CHECKS = []


def check(name, ok, detail=""):
    CHECKS.append((name, bool(ok), detail))
    print(f"  [{'ok' if ok else 'FAIL'}] {name}" + (f" — {detail}" if detail else ""))


def post(port, payload, timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/{path.lstrip('/')}", timeout=30
    ) as resp:
        return resp.read()


def serve(eng, name):
    import http.server

    from kubedl_tpu.serving.server import make_handler

    srv = http.server.ThreadingHTTPServer(
        ("127.0.0.1", 0), make_handler(eng, name)
    )
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, srv.server_address[1]


PROMPTS = [[5, 9, 13], [7, 3, 3, 11, 2, 6, 1], [1], [4, 4, 4, 4]]


def run_engine(prompts, max_tokens=16, **kw):
    """Spin an engine behind real HTTP, run prompts, return (outs, stats,
    metrics body)."""
    from kubedl_tpu.serving.server import LlamaEngine

    base = dict(preset="tiny", max_batch=2, max_seq=64, kv_layout="paged",
                kv_block_size=4, kv_blocks=48, prefix_cache_mb=0)
    eng = LlamaEngine(**{**base, **kw})
    srv, port = serve(eng, "drive11")
    try:
        outs = [
            post(port, {"token_ids": p, "max_tokens": max_tokens})["token_ids"]
            for p in prompts
        ]
        stats = json.loads(get(port, "/v1/stats"))
        body = get(port, "/metrics").decode()
        return outs, stats, body
    finally:
        srv.shutdown()
        eng.close()


def main():
    from kubedl_tpu.serving.server import engine_kwargs

    print("== 1-2: blocked kernel bit-identity over HTTP + accounting ==")
    g_outs, g_stats, _ = run_engine(PROMPTS)
    b_outs, b_stats, b_body = run_engine(PROMPTS, kv_attention="blocked")
    check("greedy outputs blocked == gather over HTTP", b_outs == g_outs,
          f"{len(PROMPTS)} ragged prompts x 16 tokens")
    check("stats attention_kernel",
          g_stats["kv_blocks"].get("attention_kernel") == "gather"
          and b_stats["kv_blocks"].get("attention_kernel") == "blocked")
    check("metrics attention_kernel label",
          'attention_kernel="blocked"' in b_body
          and "kubedl_tpu_serving_kv_blocks_total" in b_body)

    print("== 3-4: model drafts on the blocked kernel ==")
    # tiny-deep zero-inits the deep residual branches, so the 2-of-4
    # layer slice is bit-identical to the target at init.
    deep = dict(preset="tiny-deep", kv_attention="blocked")
    ref_outs, _, _ = run_engine(PROMPTS, **deep)
    m_outs, m_stats, m_body = run_engine(
        PROMPTS, spec_k=3, spec_draft="model", spec_draft_layers=2, **deep)
    sp = m_stats["speculative"]
    check("model-draft outputs bit-identical", m_outs == ref_outs)
    check("model-draft acceptance > 0.5",
          sp["acceptance_rate"] > 0.5, f"rate={sp['acceptance_rate']:.2f}")
    check("draft wall time recorded",
          sp.get("draft_ms_p50", 0) > 0
          and "kubedl_tpu_serving_spec_draft_ms" in m_body
          and 'draft="model"' in m_body,
          f"draft_ms_p50={sp.get('draft_ms_p50', 0):.2f}")
    mc_outs, mc_stats, _ = run_engine(
        PROMPTS, spec_k=3, spec_draft="model", spec_draft_layers=2,
        spec_candidates=2, **deep)
    mcsp = mc_stats["speculative"]
    check("multi-candidate outputs bit-identical", mc_outs == ref_outs)
    check("multi accepted >= single, candidates scored",
          mcsp["accepted"] >= sp["accepted"]
          and mcsp.get("candidates_scored", 0) > 0,
          f"multi={mcsp['accepted']} single={sp['accepted']} "
          f"scored={mcsp.get('candidates_scored', 0)}")

    print("== 5: config plumbing ==")
    kw = engine_kwargs(
        {"kv_attention": "blocked", "spec_draft": "model",
         "spec_candidates": 2, "spec_draft_layers": 2}, "/x")
    dflt = engine_kwargs({}, "/x")
    check("engine_kwargs plumbing",
          kw["kv_attention"] == "blocked" and kw["spec_draft"] == "model"
          and kw["spec_candidates"] == 2 and kw["spec_draft_layers"] == 2
          and dflt["kv_attention"] == "gather"
          and dflt["spec_candidates"] == 1)
    from kubedl_tpu.serving.types import Predictor
    pred = Predictor(model_name="m", attention_kernel="blocked", spec_k=3,
                     spec_draft="model", spec_candidates=2)
    check("Predictor carries kernel/draft fields",
          pred.attention_kernel == "blocked" and pred.spec_draft == "model"
          and pred.spec_candidates == 2)

    print("== 6: raw-kernel parity vs float64 dense reference ==")
    import numpy as np
    import jax.numpy as jnp
    from kubedl_tpu.models.paged_attention import paged_attention

    rng = np.random.default_rng(11)
    H, KV, hd, BS, NB, MB, B = 4, 2, 8, 4, 10, 4, 3
    kp = rng.standard_normal((NB, BS, KV, hd)).astype(np.float32)
    vp = rng.standard_normal((NB, BS, KV, hd)).astype(np.float32)
    kp[0], vp[0] = 37.0, -29.0  # poisoned trash block
    q = rng.standard_normal((B, 1, H, hd)).astype(np.float32)
    bt = np.array([[1, 2, 3, 4], [5, 6, 0, 0], [0, 0, 0, 0]], np.int32)
    starts = np.array([13, 6, 0], np.int32)  # partial tail, mid, trash row
    out = np.asarray(paged_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), jnp.asarray(starts), kernel="lax"))
    ok = np.isfinite(out).all()
    scale = 1.0 / np.sqrt(hd)
    for b in range(B):
        # the query at position starts[b] attends to pool slots
        # t <= starts[b] (its own KV is already written there)
        n = min(int(starts[b]) + 1, MB * BS)
        keys = kp[bt[b]].reshape(-1, KV, hd)[:n].astype(np.float64)
        vals = vp[bt[b]].reshape(-1, KV, hd)[:n].astype(np.float64)
        for h in range(H):
            g = h * KV // H
            s = keys[:, g] @ q[b, 0, h].astype(np.float64) * scale
            w = np.exp(s - s.max())
            w /= w.sum()
            ref = w @ vals[:, g]
            ok = ok and np.allclose(out[b, 0, h], ref, atol=1e-5)
    check("lax blocked kernel matches float64 dense reference", ok,
          "ragged rows + poisoned trash block, finite everywhere")

    print("== 7: host-overhead budget ==")
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from scheduler_microbench import run_blocked_attention_microbench

    mb = run_blocked_attention_microbench(iters=50)
    check("blocked host overhead within budget", mb["within_budget"],
          f"tick_p50={mb['tick_ms_p50']:.2f}ms "
          f"dispatch={mb['kernel_dispatch_ms']:.2f}ms")

    failed = [c for c in CHECKS if not c[1]]
    print(f"\n{len(CHECKS) - len(failed)}/{len(CHECKS)} checks passed")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
