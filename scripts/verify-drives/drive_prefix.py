"""Verify drive: prefix-aware KV cache reuse (serving PR, 2026-08-06).

Drives the prefix cache through the PUBLIC serving surface — a real
LlamaEngine behind the real HTTP handler — and checks the contracts
docs/serving.md "Prefix cache" promises:

  1. a shared-system-prompt fleet over HTTP auto-populates the cache
     (observation trie: no tagging) and later requests hit;
  2. greedy outputs are bit-identical to a cache-off engine;
  3. per-request ttft_ms rides the response, p50/p95 ride /v1/stats;
  4. /v1/stats carries the prefix_cache section (hits/tokens_saved...);
  5. /metrics serves the kubedl_tpu_serving_prefix_cache_* family;
  6. "cache_prefix": true in the body inserts on FIRST sight;
  7. prefix_cache_mb=0 disables the cache (no stats section, no hits);
  8. a tiny byte budget evicts LRU entries instead of growing;
  9. KUBEDL_SERVE_CONFIG plumbing (engine_kwargs carries prefix_cache_mb);
 10. host-side match+graft overhead stays under the tier-1 budget.

Run: python scripts/verify-drives/drive_prefix.py  (CPU-forced, ~60s)
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


def post(port, payload):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
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


def main():
    from kubedl_tpu.serving.server import LlamaEngine, engine_kwargs

    shared = list(range(3, 51))  # 48-token shared system prompt
    prompts = [shared + [500 + j, 600 + j] for j in range(6)]

    print("== cache-off reference ==")
    ref = LlamaEngine(preset="tiny", max_seq=128, max_batch=4,
                      prefix_cache_mb=0)
    try:
        want = [ref.generate(p, max_tokens=6)["token_ids"] for p in prompts]
        st_off = ref.stats()
        check("cache-off stats has no prefix_cache section",
              "prefix_cache" not in st_off)
    finally:
        ref.close()

    print("== shared-prompt fleet over HTTP (auto-detection) ==")
    eng = LlamaEngine(preset="tiny", max_seq=128, max_batch=4,
                      prefix_cache_mb=8, prefix_min_len=8)
    srv, port = serve(eng, "tiny")
    try:
        got = [post(port, {"prompt_ids": p, "max_tokens": 6})
               for p in prompts]
        check("greedy outputs bit-identical to cache-off over HTTP",
              [r["token_ids"] for r in got] == want)
        check("later requests rode a grafted prefix",
              any(r.get("cached_prefix_len", 0) >= len(shared)
                  for r in got[2:]),
              f"cached_prefix_len={[r.get('cached_prefix_len') for r in got]}")
        check("per-request ttft_ms in the HTTP response",
              all(isinstance(r.get("ttft_ms"), (int, float)) for r in got))
        stats = json.loads(get(port, "/v1/stats"))
        pc = stats.get("prefix_cache") or {}
        check("/v1/stats prefix_cache: hits>0 and tokens_saved>0",
              pc.get("hits", 0) > 0 and pc.get("tokens_saved", 0) > 0,
              f"hits={pc.get('hits')} saved={pc.get('tokens_saved')} "
              f"hit_rate={pc.get('hit_rate')}")
        check("no pins leaked after all requests finished",
              pc.get("pinned", -1) == 0)
        check("/v1/stats carries ttft_ms_p50/p95",
              "ttft_ms_p50" in stats and "ttft_ms_p95" in stats,
              f"p50={stats.get('ttft_ms_p50')} p95={stats.get('ttft_ms_p95')}")
        metrics = get(port, "/metrics").decode()
        check("/metrics serves kubedl_tpu_serving_prefix_cache_* family",
              "kubedl_tpu_serving_prefix_cache_hits" in metrics
              and "kubedl_tpu_serving_prefix_cache_tokens_saved" in metrics)

        print("== tagged first-sight insertion ==")
        tag_prompt = list(range(60, 80))
        post(port, {"prompt_ids": tag_prompt, "max_tokens": 2,
                    "cache_prefix": True})
        r2 = post(port, {"prompt_ids": tag_prompt + [99], "max_tokens": 2})
        check("cache_prefix=true in body inserts on first sight",
              r2.get("cached_prefix_len", 0) >= 8,
              f"cached_prefix_len={r2.get('cached_prefix_len')}")
    finally:
        srv.shutdown()
        eng.close()

    print("== tiny budget evicts LRU ==")
    # one tiny-model 16-bucket entry is 8KB (fp32 k+v); 0.01MB holds one
    small = LlamaEngine(preset="tiny", max_seq=64, max_batch=2,
                        prefix_cache_mb=0.01, prefix_min_len=4)
    try:
        for base in (100, 300):
            p = [base + t for t in range(10)]
            small.generate(p, max_tokens=2, cache_prefix=True)
        st = small.stats()["prefix_cache"]
        check("byte budget enforced via LRU eviction",
              st["evictions"] >= 1 and st["bytes"] <= st["budget_bytes"],
              f"evictions={st['evictions']} bytes={st['bytes']}"
              f"/{st['budget_bytes']}")
    finally:
        small.close()

    print("== config plumbing + host-overhead budget ==")
    kw = engine_kwargs({"prefix_cache_mb": 2.5}, "")
    check("KUBEDL_SERVE_CONFIG prefix_cache_mb reaches engine_kwargs",
          kw.get("prefix_cache_mb") == 2.5
          and engine_kwargs({}, "").get("prefix_cache_mb") == 64.0)
    from scripts.scheduler_microbench import run_prefix_microbench

    mb = run_prefix_microbench(requests=8, max_tokens=8)
    check("match+graft host overhead within tier-1 budget",
          mb["within_budget"] and mb["hits"] == 8,
          f"tick_p50={mb['tick_ms_p50']}ms match_graft={mb['match_graft_ms']}ms")

    failed = [c for c in CHECKS if not c[1]]
    print(f"\n{len(CHECKS) - len(failed)}/{len(CHECKS)} checks passed")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
