"""One-chip smoke run of the served RAG path.

    python chip_smoke.py [--seed N]

Runs in one process, in this order, and stops at the first failure:

  (a) requires JAX's default backend to be a TPU; anything else exits
      non-zero before any work;
  (b) kernel parity on the chip at llama3-8b widths: paged decode (bf16
      and int8 pools), flash prefill at batch 4, retrieval top-k, top-k
      merge and rmsnorm, each against its ``kernels/ref.py`` oracle;
  (c) the engine serves 8 RAG requests end to end on llama3-8b at its
      published widths with seeded bf16 weights, part of the layers
      streamed from host memory: submit, retrieve, paged prefill,
      streamed decode of 32 tokens each, harvest.

Earlier lines report setup and compile seconds, peak device memory, the
memory plan and which implementation each kernel dispatch took.  The
per-request latencies it prints come from a smoke run, not a benchmark.
The last line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

NUM_REQUESTS = 8
TOP_K = 5
# bf16 outputs of kernels that accumulate in fp32, against fp32 oracles
# run at "highest" matmul precision: the two sides may round to
# neighbouring bf16 values, one ulp = 2**-7 of the magnitude
BF16_TOL = dict(atol=1e-2, rtol=1e-2)
# fp32 retrieval scores of 768-wide dot products
SCORE_TOL = dict(atol=1e-3, rtol=1e-5)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def log(msg: str) -> None:
    print(msg, flush=True)


def kernel_parity(seed: int) -> None:
    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)
    h, kvh, d = 32, 8, 128            # llama3-8b attention widths

    def normal(*shape, dtype=jnp.bfloat16):
        return jnp.asarray(rng.standard_normal(shape, np.float32), dtype)

    def close(name, got, want, atol, rtol):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = np.abs(got - want)
        log(f"  parity {name}: max abs err {float(err.max()):.3e} "
            f"(atol {atol:g} + rtol {rtol:g} x |ref|)")
        check(bool((err <= atol + rtol * np.abs(want)).all()),
              f"{name} within atol {atol} + rtol {rtol}")

    # flash prefill, batch 4, ragged kv_len through scalar prefetch
    b, s = 4, 1024
    q, k, v = normal(b, s, h, d), normal(b, s, kvh, d), normal(b, s, kvh, d)
    kv_len = jnp.asarray([1024, 900, 700, 1024], jnp.int32)
    got = ops.flash_attention(q, k, v, causal=True, kv_len=kv_len)
    with jax.default_matmul_precision("highest"):
        want = ref.attention_reference(q, k, v, causal=True, kv_len=kv_len)
    close("flash_attention b=4 s=1024", got, want, **BF16_TOL)

    # paged decode over an 8192-page pool, bf16 then int8 + scales
    b, page, pages, nmax = 8, 16, 8192, 66
    tab = jnp.asarray(rng.permutation(pages - 1)[:b * nmax]
                      .reshape(b, nmax) + 1, jnp.int32)
    kv_len = jnp.asarray(rng.integers(1, nmax * page + 1, b), jnp.int32)
    q = normal(b, h, d)
    for fmt in ("bf16", "int8"):
        if fmt == "bf16":
            kp, vp = normal(pages, page, kvh, d), normal(pages, page, kvh, d)
            scales = {}
        else:
            kp, vp = (jnp.asarray(rng.integers(-127, 128,
                                               (pages, page, kvh, d)),
                                  jnp.int8) for _ in range(2))
            scales = {n: jnp.asarray(rng.uniform(0.002, 0.02,
                                                 (pages, kvh)), jnp.float32)
                      for n in ("k_scale", "v_scale")}
        got = ops.paged_decode_attention(q, kp, vp, tab, kv_len, **scales)
        with jax.default_matmul_precision("highest"):
            want = ref.paged_decode_attention_reference(
                q, kp, vp, tab, kv_len, **scales)
        close(f"paged_decode_attention {fmt} pages={pages}", got, want,
              **BF16_TOL)
        del kp, vp

    # retrieval top-k over one partition-sized database, then the merge
    qs = jnp.asarray(rng.standard_normal((16, 768), np.float32))
    db = jnp.asarray(rng.standard_normal((5000, 768), np.float32))
    gs, gi = ops.retrieval_topk(qs, db, 8)
    with jax.default_matmul_precision("highest"):
        ws, wi = ref.topk_reference(qs, db, 8)
    check(bool((np.asarray(gi) == np.asarray(wi)).all()), "top-k ids equal")
    close("retrieval_topk q=16 n=5000 k=8", gs, ws, **SCORE_TOL)

    ps = jnp.asarray(rng.standard_normal((16, 32, 8), np.float32))
    pi = jnp.asarray(rng.permutation(16 * 32 * 8).reshape(16, 32, 8),
                     jnp.int32)
    mask = jnp.asarray(rng.random((16, 32)) < 0.5)
    gs, gi = ops.retrieval_topk_merge(ps, pi, mask, 8)
    ws, wi = ref.topk_merge_reference(ps, pi, mask, 8)
    check(bool((np.asarray(gi) == np.asarray(wi)).all()), "merge ids equal")
    close("retrieval_topk_merge q=16 p=32 k=8", gs, ws, **SCORE_TOL)

    x, w = normal(1024, 4096), normal(4096)
    close("rmsnorm 1024x4096", ops.rmsnorm(x, w),
          ref.rmsnorm_reference(x, w), **BF16_TOL)


def serve_requests(served) -> None:
    from repro.serving.request import Request, latency_table

    eng, gen = served.engine, served.generator
    eng.start()
    try:
        t0 = time.perf_counter()
        for i, query in enumerate(served.queries[:NUM_REQUESTS]):
            eng.submit(Request(rid=i, query=query, top_k=TOP_K,
                               arrival=time.perf_counter()))
        reqs = eng.drain(NUM_REQUESTS, timeout=900)
        log(f"phase serve: {time.perf_counter() - t0:.1f} s for "
            f"{NUM_REQUESTS} requests")
    finally:
        eng.stop()

    budget = gen.gen_cfg.max_new_tokens
    check(sorted(r.rid for r in reqs) == list(range(NUM_REQUESTS)),
          "every request completed once")
    for r in sorted(reqs, key=lambda r: r.rid):
        ids = [int(c) for c in r.retrieved]
        check(len(ids) == TOP_K and all(0 <= i < served.corpus_size
                                        for i in ids),
              f"request {r.rid} retrieved {TOP_K} real chunk ids")
        check(len(r.output.split()) == budget,
              f"request {r.rid} emitted its {budget}-token budget")
        log(f"  request {r.rid}: latency {r.latency:.2f} s (retrieval "
            f"{r.retrieval:.2f} s, generation {r.generation:.2f} s) "
            f"ids {ids} [smoke, not a benchmark]")
    check(gen.nonfinite_batches == 0, "every logits batch finite")
    log(f"latency table [smoke, not a benchmark]: {latency_table(reqs)}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the kernel inputs, weights and corpus")
    args = ap.parse_args()
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke.py needs a TPU; JAX's default backend is "
              f"{backend!r}", file=sys.stderr)
        return 1
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch.serve import build_served

    compile_s = {}

    def on_event(event, seconds, **_):
        if event.startswith("/jax/core/compile/"):
            compile_s[event] = compile_s.get(event, 0.0) + seconds

    jax.monitoring.register_event_duration_secs_listener(on_event)
    dev = jax.devices()[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {cache_dir}")
    t_start = time.perf_counter()

    t0 = time.perf_counter()
    kernel_parity(args.seed)
    log(f"phase parity: {time.perf_counter() - t0:.1f} s; dispatch "
        f"{ops.dispatch_counts()}")

    ops.DISPATCH_COUNTS.clear()
    with tempfile.TemporaryDirectory() as root:
        t0 = time.perf_counter()
        served = build_served(get_config("llama3-8b"), spill_root=root,
                              seed=args.seed, num_queries=NUM_REQUESTS,
                              log=log)
        ex = served.generator.exec
        log(f"phase setup: {time.perf_counter() - t0:.1f} s "
            f"{ {k: round(v, 1) for k, v in served.seconds.items()} }")
        log(f"device bytes in use after setup: "
            f"{(dev.memory_stats() or {}).get('bytes_in_use')}")
        log(f"weights: {ex.resident}/{ex.n_layers} layers resident "
            f"({ex.resident_bytes} B incl. embed/unembed), "
            f"{ex.n_layers - ex.resident} streamed from host "
            f"({ex.streamed_bytes} B); KV pages {served.plan.kv_pages} x "
            f"{served.generator.page_size} tokens; profile {served.hw.name}")
        serve_requests(served)
    log(f"engine dispatch: {ops.dispatch_counts()}")
    stats = dev.memory_stats() or {}
    log(f"peak device bytes: {stats.get('peak_bytes_in_use')} of "
        f"{stats.get('bytes_limit')}")
    log(f"compile seconds: { {k.rsplit('/', 1)[-1]: round(v, 1) for k, v in compile_s.items()} }")
    log(f"total: {time.perf_counter() - t_start:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
