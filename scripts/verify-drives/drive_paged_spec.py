"""Verify drive: paged KV-block allocator + speculative decoding (PR 8).

Drives the paged serving subsystem through the PUBLIC surface — real
LlamaEngines behind the real HTTP handler — and checks the contracts
docs/serving.md "Paged KV" / "Speculative decoding" promise:

  1. paged greedy outputs over HTTP are bit-identical to the contiguous
     engine (the exactness gate, end to end);
  2. /v1/stats carries kv_blocks accounting and the pool drains back to
     empty once every request finishes (no block leaks);
  3. /metrics serves the kubedl_tpu_serving_kv_* gauge family;
  4. speculative decoding (ngram draft-k/verify-1) stays bit-identical
     over HTTP and reports acceptance in /v1/stats + /metrics;
  5. block exhaustion below the low watermark sheds with a REAL HTTP
     503 + Retry-After, and admission recovers once blocks free;
  6. the serving.kv_alloc chaos site forces the preempt-and-requeue
     path with outputs still exact and kv_preemptions counted;
  7. prefix-cache entries share row blocks by reference (shared>0 while
     cached, refs returned on reclaim);
  8. KUBEDL_SERVE_CONFIG plumbing (kv_layout/kv_blocks/spec_k reach
     engine_kwargs, paged is the serve default);
  9. block-table host overhead stays under the tier-1 budget.

Run: python scripts/verify-drives/drive_paged_spec.py  (CPU-forced, ~90s)
"""

import json
import os
import sys
import threading
import urllib.error
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


def main():
    from kubedl_tpu.serving.server import LlamaEngine, engine_kwargs

    prompts = [[5, 9, 13], [1, 2, 3, 4, 5, 6, 7], [7, 7, 7], [42]]
    n_tok = 8

    print("== contiguous reference ==")
    ref = LlamaEngine(preset="tiny", max_seq=64, max_batch=2,
                      kv_layout="contiguous", prefix_cache_mb=0)
    try:
        want = [ref.generate(p, max_tokens=n_tok)["token_ids"]
                for p in prompts]
    finally:
        ref.close()

    print("== paged engine over HTTP ==")
    eng = LlamaEngine(preset="tiny", max_seq=64, max_batch=2,
                      kv_layout="paged", kv_block_size=8, prefix_cache_mb=0)
    srv, port = serve(eng, "tiny")
    try:
        got = [post(port, {"prompt_ids": p, "max_tokens": n_tok})
               for p in prompts]
        check("paged greedy outputs bit-identical to contiguous over HTTP",
              [r["token_ids"] for r in got] == want)
        stats = json.loads(get(port, "/v1/stats"))
        kv = stats.get("kv_blocks") or {}
        check("/v1/stats kv_blocks: pool drained, allocs counted",
              kv.get("used") == 0 and kv.get("allocs", 0) > 0
              and kv.get("free") == kv.get("total"),
              f"used={kv.get('used')} free={kv.get('free')}"
              f"/{kv.get('total')} allocs={kv.get('allocs')}")
        metrics = get(port, "/metrics").decode()
        check("/metrics serves kubedl_tpu_serving_kv_* family",
              all(f"kubedl_tpu_serving_kv_{m}" in metrics
                  for m in ("blocks_total", "blocks_free", "blocks_shared")))
    finally:
        srv.shutdown()
        eng.close()

    print("== speculative engine over HTTP ==")
    spec = LlamaEngine(preset="tiny", max_seq=64, max_batch=2,
                       kv_layout="paged", spec_k=4, spec_draft="ngram",
                       prefix_cache_mb=0)
    srv, port = serve(spec, "tiny")
    try:
        got = [post(port, {"prompt_ids": p, "max_tokens": n_tok})
               for p in prompts]
        check("speculative greedy outputs bit-identical over HTTP",
              [r["token_ids"] for r in got] == want)
        stats = json.loads(get(port, "/v1/stats"))
        sp = stats.get("speculative") or {}
        check("/v1/stats speculative: verifies>0, acceptance reported",
              sp.get("verifies", 0) > 0 and "acceptance_rate" in sp,
              f"verifies={sp.get('verifies')} "
              f"acc={sp.get('acceptance_rate')} "
              f"tok/verify={sp.get('tokens_per_verify')}")
        metrics = get(port, "/metrics").decode()
        check("/metrics serves kubedl_tpu_serving_spec_* family",
              all(f"kubedl_tpu_serving_spec_{m}" in metrics
                  for m in ("tokens_proposed", "tokens_accepted",
                            "acceptance_rate")))
    finally:
        srv.shutdown()
        spec.close()

    print("== block exhaustion: 503 + Retry-After, then recovery ==")
    # 11 usable blocks, watermarks 0.2/0.5: draining the pool closes
    # admission; freeing past the high watermark reopens it
    small = LlamaEngine(preset="tiny", max_seq=64, max_batch=2,
                        kv_layout="paged", kv_block_size=8, kv_blocks=12,
                        kv_low_watermark=0.2, kv_high_watermark=0.5,
                        prefix_cache_mb=0)
    srv, port = serve(small, "tiny")
    try:
        held = small._alloc.alloc(small._alloc.free_count)
        code, retry_after = 0, None
        try:
            post(port, {"prompt_ids": [5, 9], "max_tokens": 2}, timeout=30)
        except urllib.error.HTTPError as e:
            code = e.code
            retry_after = e.headers.get("Retry-After")
            e.read()
        check("pool below low watermark sheds with HTTP 503 + Retry-After",
              code == 503 and retry_after is not None,
              f"code={code} Retry-After={retry_after}")
        small._alloc.free(held)
        r = post(port, {"prompt_ids": [5, 9], "max_tokens": 2})
        check("admission recovers once blocks free past the high watermark",
              len(r.get("token_ids", [])) == 2
              and json.loads(get(port, "/v1/stats"))["kv_sheds"] >= 1)
    finally:
        srv.shutdown()
        small.close()

    print("== chaos serving.kv_alloc: preempt-and-requeue stays exact ==")
    from kubedl_tpu import chaos

    vict = LlamaEngine(preset="tiny", max_seq=64, max_batch=2,
                       kv_layout="paged", kv_block_size=8,
                       prefix_cache_mb=0)
    try:
        plan = chaos.FaultPlan(
            seed=3, sites={"serving.kv_alloc": [chaos.FaultSpec.nth(1)]}
        )
        outs = [None, None]

        def worker(i):
            outs[i] = vict.generate(prompts[i], max_tokens=n_tok)

        with plan:
            ts = [threading.Thread(target=worker, args=(i,))
                  for i in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=120)
        check("outputs exact through an injected reservation failure",
              [r["token_ids"] for r in outs] == want[:2]
              and plan.faults("serving.kv_alloc") == 1
              and vict.stats()["kv_blocks"]["used"] == 0,
              f"faults={plan.faults('serving.kv_alloc')}")
    finally:
        vict.close()

    # In the plain segment path the double-buffered pipeline keeps every
    # co-resident row at pending>0 when the reserve runs, so a failing
    # row finds no victim and DEFERS (the check above). The speculative
    # path harvests synchronously — co-resident rows sit at pending==0
    # and are eligible victims, so an injected reservation failure on
    # the first-processed row deterministically preempts the other.
    spec2 = LlamaEngine(preset="tiny", max_seq=64, max_batch=2,
                        kv_layout="paged", kv_block_size=8, spec_k=4,
                        spec_draft="ngram", prefix_cache_mb=0)
    try:
        sprompts = [[5, 9, 13], [1, 2, 3]]
        sw = [spec2.generate(p, max_tokens=24)["token_ids"]
              for p in sprompts]
        plan = chaos.FaultPlan(
            seed=5, sites={"serving.kv_alloc": [chaos.FaultSpec.nth(4)]}
        )
        outs = [None, None]

        def sworker(i):
            outs[i] = spec2.generate(sprompts[i], max_tokens=24,
                                     timeout_s=120)

        with plan:
            ts = [threading.Thread(target=sworker, args=(i,))
                  for i in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=180)
        st = spec2.stats()
        check("spec-path reserve failure preempts-and-requeues the "
              "youngest row with exact outputs",
              [r["token_ids"] for r in outs] == sw
              and plan.faults("serving.kv_alloc") == 1
              and st["kv_preemptions"] >= 1
              and st["kv_blocks"]["used"] == 0,
              f"preemptions={st['kv_preemptions']} "
              f"faults={plan.faults('serving.kv_alloc')}")
    finally:
        spec2.close()

    print("== prefix entries share blocks by reference ==")
    pfx = LlamaEngine(preset="tiny", max_seq=64, max_batch=2,
                      kv_layout="paged", kv_block_size=4,
                      prefix_cache_mb=8, prefix_min_len=4)
    try:
        head = [3, 4, 5, 6, 7, 8, 9, 10]
        pfx.generate(head + [99], max_tokens=2, cache_prefix=True)
        st = pfx.stats()["kv_blocks"]
        check("cached prefix holds block refs (used>0, shared after hit)",
              st["used"] > 0, f"used={st['used']} shared={st['shared']}")
        r = pfx.generate(head + [77], max_tokens=2)
        check("second request grafted the shared-prefix blocks",
              r.get("cached_prefix_len", 0) >= 4,
              f"cached_prefix_len={r.get('cached_prefix_len')}")
        pfx._pcache.reclaim(10 ** 9)
        check("reclaim returns entry refs to the allocator",
              pfx.stats()["kv_blocks"]["used"] == 0)
    finally:
        pfx.close()

    print("== config plumbing + host-overhead budget ==")
    kw = engine_kwargs({"kv_blocks": 40, "spec_k": 4}, "")
    check("KUBEDL_SERVE_CONFIG kv/spec knobs reach engine_kwargs "
          "(paged is the serve default)",
          kw.get("kv_layout") == "paged" and kw.get("kv_blocks") == 40
          and kw.get("spec_k") == 4
          and engine_kwargs({}, "").get("kv_block_size") == 16)
    from scripts.scheduler_microbench import run_paged_microbench

    mb = run_paged_microbench(requests=8, max_tokens=16)
    check("block-table host overhead within tier-1 budget, no leaks",
          mb["within_budget"] and mb["blocks_leaked"] == 0,
          f"tick_p50={mb['tick_ms_p50']}ms "
          f"mirror_upload={mb['mirror_upload_ms']}ms")

    failed = [c for c in CHECKS if not c[1]]
    print(f"\n{len(CHECKS) - len(failed)}/{len(CHECKS)} checks passed")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
