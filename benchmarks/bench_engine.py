"""Engine micro-benchmark: prefill latency and decode throughput of the
real JAX serving engine on the reduced CPU config — contiguous vs paged
KV layout, plus the paged engine's prefix cache (shared-prefix workload:
prefill-FLOP and pool-occupancy win), chunked prefill (mixed
prefill+decode steps bounding per-step latency while a long prompt
prefills), and the scheduler policies under an *overload* workload
(arrival burst beyond the endpoint's slot capacity, mixed
priorities/SLOs): per-policy TTFT-SLO attainment shows FCFS head-of-line
blocking starving tight-deadline requests while the priority and
SLO-deadline (EDF) policies reorder — and preempt background residents,
resuming them through the prefix cache — to hit their budgets. Writes
``BENCH_engine.json`` (path overridable via argv[1]) so the perf
trajectory is tracked across PRs.

    PYTHONPATH=src python benchmarks/bench_engine.py [out.json]
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, smoke_variant
from repro.core.types import SLO
from repro.kernels import ops as kops
from repro.models import build_model
from repro.models.attention import paged_kv_token_bytes
from repro.serving.api import SamplingParams
from repro.serving.endpoint import ServingEndpoint
from repro.serving.engine import Engine

BATCH = 4
PROMPT_LEN = 16
N_DECODE = 16
BLOCK = 8
SHARED_LEN = 32          # system-prompt prefix shared by every request
TAIL_LEN = 8
LONG_PROMPT = 64
CHUNK = 8
POLICIES = ("fcfs", "priority", "slo")
DECODE_MODES = ("gather", "fused", "fused_fp16", "fused_int8")


def bench_layout(cfg, params, paged: bool) -> dict:
    ep = ServingEndpoint(Engine(cfg, [params], max_batch=BATCH,
                                max_seq=96, paged=paged))
    # max_new keeps every request resident past the timed window, so the
    # measured steps are pure full-batch decode (no finish/clear_slot cost)
    for i in range(BATCH):
        ep.submit([1 + i] * PROMPT_LEN,
                  SamplingParams(max_new=N_DECODE + 4))
    # step 1 = BATCH prefills + the first batched decode, both cold (the
    # engine decodes newly admitted requests in the same step), so this
    # number includes prefill AND decode jit compiles
    t0 = time.perf_counter()
    ep.step()
    first_step_cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(N_DECODE):
        ep.step()
    decode_s = time.perf_counter() - t0
    return {
        "layout": "paged" if paged else "contiguous",
        "batch": BATCH,
        "prompt_len": PROMPT_LEN,
        "first_step_cold_s": first_step_cold_s,
        "decode_steps_per_s": N_DECODE / decode_s,
        "decode_step_ms": decode_s / N_DECODE * 1e3,
    }


def bench_prefix_sharing(cfg, params, prefix_cache: bool) -> dict:
    """BATCH requests sharing a SHARED_LEN-token system prompt. With the
    prefix cache, every request after the first prefills only its tail:
    the FLOP win is the cached-token count, the memory win the deduped
    pool occupancy."""
    eng = Engine(cfg, [params], max_batch=BATCH, max_seq=96,
                 block_size=BLOCK, paged=True, prefix_cache=prefix_cache)
    shared = list(range(2, 2 + SHARED_LEN))
    reqs = [eng.submit(shared + [100 + i] * TAIL_LEN,
                       SamplingParams(max_new=8)) for i in range(BATCH)]
    t0 = time.perf_counter()
    eng.step()                    # all BATCH prompts prefill here
    prefill_step_s = time.perf_counter() - t0
    bm = eng.block_mgr
    blocks_in_use = bm.n_blocks - bm.free_blocks   # referenced right now
    blocks_no_sharing = sum(len(bm.tables[r.rid].blocks) for r in reqs)
    eng.run()
    prompt_tokens = sum(r.prompt_total for r in reqs)
    cached = sum(r.metrics.cached_tokens for r in reqs)
    return {
        "workload": "shared-prefix",
        "prefix_cache": prefix_cache,
        "batch": BATCH,
        "shared_prefix_len": SHARED_LEN,
        "prompt_tokens_total": prompt_tokens,
        "cached_tokens_total": cached,
        "prefill_tokens_computed": prompt_tokens - cached,
        "pool_blocks_used": blocks_in_use,
        "pool_blocks_without_sharing": blocks_no_sharing,
        "prefill_step_s": prefill_step_s,
        "cache_hit_tokens": bm.cache_hit_tokens,
        "evictions": bm.evictions,
    }


def bench_chunked_prefill(cfg, params, chunk) -> dict:
    """BATCH-1 short requests decode while one LONG_PROMPT request
    arrives. Monolithic prefill stalls every decode for a full forward;
    chunked prefill bounds the per-step work (mixed steps)."""
    eng = Engine(cfg, [params], max_batch=BATCH, max_seq=96,
                 block_size=BLOCK, paged=True, prefill_chunk=chunk)
    shorts = [eng.submit([1 + i] * 4, SamplingParams(max_new=40))
              for i in range(BATCH - 1)]
    for _ in range(2):            # shorts are warm and decoding
        eng.step()
    long_req = eng.submit(list(range(3, 3 + LONG_PROMPT)),
                          SamplingParams(max_new=4))
    short_before = sum(len(r.generated) for r in shorts)
    step_ms, mixed_steps = [], 0
    while not long_req.prefill_done:
        t0 = time.perf_counter()
        out = eng.step()
        step_ms.append((time.perf_counter() - t0) * 1e3)
        if out.prefill_tokens and out.events:
            mixed_steps += 1
    short_during = sum(len(r.generated) for r in shorts) - short_before
    eng.run()
    return {
        "workload": "chunked-prefill",
        "prefill_chunk": chunk,
        "long_prompt_len": LONG_PROMPT,
        "decode_batch": BATCH - 1,
        "prefill_steps": len(step_ms),
        "mixed_steps": mixed_steps,
        "max_step_ms_during_prefill": max(step_ms),
        "mean_step_ms_during_prefill": sum(step_ms) / len(step_ms),
        "long_ttft_steps": long_req.metrics.ttft_steps,
        "short_tokens_during_prefill": short_during,
    }


def bench_overload(cfg, params, policy: str) -> dict:
    """Overload burst: two loose-SLO background requests saturate both
    slots, then three tight-TTFT interactive requests arrive at once —
    more work than the endpoint can hold. FCFS serves in arrival order
    (interactive TTFTs blow their budgets behind the long decodes);
    priority/EDF admit the urgent requests first, preempting background
    residents whose blocks are released but whose committed prefix stays
    cached, so the resumes re-prefill only their tails."""
    eng = Engine(cfg, [params], max_batch=2, max_seq=96, block_size=BLOCK,
                 paged=True, prefix_cache=True, policy=policy)
    background = [
        eng.submit([10 + i] * 24,
                   SamplingParams(max_new=24, priority=0,
                                  slo=SLO(ttft=200.0, tpot=60.0)))
        for i in range(2)]
    for _ in range(3):                    # background is warm and decoding
        eng.step()
    interactive = [
        eng.submit([50 + i] * 4,
                   SamplingParams(max_new=4, priority=2,
                                  slo=SLO(ttft=6.0, tpot=30.0)))
        for i in range(3)]
    eng.run()
    reqs = background + interactive
    attained = [r.metrics.ttft_steps is not None
                and r.metrics.ttft_steps <= r.params.slo.ttft for r in reqs]
    resumed_cached = sum(r.metrics.cached_tokens for r in reqs
                         if r.metrics.preemptions)
    return {
        "workload": "overload-burst",
        "policy": policy,
        "n_background": len(background),
        "n_interactive": len(interactive),
        "ttft_slo_attainment": sum(attained) / len(reqs),
        "interactive_ttft_steps": [r.metrics.ttft_steps
                                   for r in interactive],
        "preemptions": eng.scheduler.n_preemptions,
        "resumed_cached_tokens": resumed_cached,
    }


def _pool_bytes_per_token(eng) -> float:
    """Measured KV pool bytes per token slot, every leaf (int8 pages +
    their f32 scale/zero) across all stages and attention periods."""
    total = 0
    for w in eng.runner.workers:
        for sub in w.cache.values():
            if "k_pages" in sub:
                total += sum(int(a.nbytes) for a in sub.values())
    w0 = eng.runner.workers[0]
    return total / (w0.n_pages * w0.page_size)


def bench_decode_mode(cfg, params, mode: str) -> dict:
    """Steady-state decode throughput of one engine mode over a staggered
    mixed workload: ``gather`` is the legacy paged step (per-request
    prefill forwards + one batched paged-decode), the ``fused*`` modes run
    every step as fused ragged launches, at fp32/fp16/int8 KV storage.
    p50/p99 step latency over the timed decode window; KV bytes/token
    both analytic (attention.paged_kv_token_bytes) and measured off the
    live pools — the accounting satellite asserts they agree exactly."""
    kv_dtype = {"fused_fp16": "float16", "fused_int8": "int8"}.get(mode)
    eng = Engine(cfg, [params], max_batch=BATCH, max_seq=96,
                 block_size=BLOCK, paged=True, prefill_chunk=CHUNK,
                 kv_dtype=kv_dtype, fused=mode != "gather")
    for i in range(BATCH):     # staggered lengths: a genuinely ragged mix
        eng.submit([1 + i] * (10 + 3 * i), SamplingParams(max_new=48))
    while any(not r.prefill_done for r in eng.active()):
        eng.step()             # warmup: chunked prefills + early decodes
    for _ in range(3):
        eng.step()             # decode shapes compiled, caches warm
    times, toks = [], 0
    for _ in range(N_DECODE):
        t0 = time.perf_counter()
        out = eng.step()
        times.append(time.perf_counter() - t0)
        toks += len(out.events)
    ts = sorted(times)
    analytic = paged_kv_token_bytes(cfg, kv_dtype) * eng.n_attn_layers()
    return {
        "workload": "decode-throughput",
        "mode": mode,
        "kv_dtype": kv_dtype or str(cfg.dtype),
        "batch": BATCH,
        "decode_tokens_per_s": toks / sum(times),
        "p50_step_ms": ts[len(ts) // 2] * 1e3,
        "p99_step_ms": ts[min(len(ts) - 1, int(len(ts) * 0.99))] * 1e3,
        "kv_bytes_per_token_analytic": analytic,
        "kv_bytes_per_token_measured": _pool_bytes_per_token(eng),
    }


def bench_fused_launch(cfg, params) -> dict:
    """The tentpole claim at op level: ONE fused ragged launch serving a
    whole mixed batch vs the per-request gather baseline (one
    paged-decode launch per request over the same pools). Same math, same
    tokens — the fused row amortizes launch/dispatch across the batch."""
    rng = np.random.RandomState(0)
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    bs, nb = BLOCK, 96 // BLOCK + 1
    n_pages = BATCH * nb + 1
    k_pages = jnp.asarray(rng.randn(n_pages, bs, hkv, hd), jnp.float32)
    v_pages = jnp.asarray(rng.randn(n_pages, bs, hkv, hd), jnp.float32)
    tables = jnp.asarray(
        np.arange(BATCH * nb, dtype=np.int32).reshape(BATCH, nb))
    hist = [9 + 8 * i for i in range(BATCH)]      # ragged histories
    q = jnp.asarray(rng.randn(BATCH, hq, hd), jnp.float32)

    per_req = jax.jit(lambda qb, bt, kl: kops.paged_decode_attention(
        qb, k_pages, v_pages, bt, kl))
    tile = 8
    row = jnp.asarray(np.repeat(np.arange(BATCH, dtype=np.int32), tile))
    pos = np.full(BATCH * tile, -1, np.int32)
    pos[::tile] = hist
    pos = jnp.asarray(pos)
    qrag = jnp.zeros((BATCH * tile, hq, hd), jnp.float32)
    qrag = qrag.at[::tile].set(q)
    fused = jax.jit(lambda qf: kops.ragged_paged_attention(
        qf, k_pages, v_pages, tables, row, pos))

    for _ in range(2):        # compile + warm both
        for b in range(BATCH):
            per_req(q[b:b + 1, None], tables[b:b + 1],
                    jnp.asarray([hist[b] + 1])).block_until_ready()
        fused(qrag).block_until_ready()
    iters = 30
    t0 = time.perf_counter()
    for _ in range(iters):
        for b in range(BATCH):
            out = per_req(q[b:b + 1, None], tables[b:b + 1],
                          jnp.asarray([hist[b] + 1]))
        out.block_until_ready()
    gather_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fused(qrag)
    out.block_until_ready()
    fused_s = time.perf_counter() - t0
    return {
        "workload": "fused-launch-vs-per-request-gather",
        "batch": BATCH,
        "launches_per_step_gather": BATCH,
        "launches_per_step_fused": 1,
        "gather_tokens_per_s": BATCH * iters / gather_s,
        "fused_tokens_per_s": BATCH * iters / fused_s,
    }


def main(out_path: str = "BENCH_engine.json"):
    enable_compile_cache()
    cfg = smoke_variant(get_config("granite-3-8b"))
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    results = [bench_layout(cfg, params, paged) for paged in (False, True)]
    prefix = [bench_prefix_sharing(cfg, params, pc) for pc in (False, True)]
    chunked = [bench_chunked_prefill(cfg, params, c) for c in (None, CHUNK)]
    overload = [bench_overload(cfg, params, pol) for pol in POLICIES]
    decode = [bench_decode_mode(cfg, params, m) for m in DECODE_MODES]
    launch = bench_fused_launch(cfg, params)
    # quantized-KV byte quote at PRODUCTION geometry (head_dim=128): the
    # smoke config's head_dim=16 inflates the f32 scale/zero overhead, so
    # the "halves bytes/token" claim is stated where it holds
    full = get_config("granite-3-8b")
    kv_full = {
        "workload": "kv-bytes-per-token-full-config",
        "model": full.name,
        "head_dim": full.head_dim,
        "fp16_bytes": paged_kv_token_bytes(full, "float16"),
        "int8_bytes": paged_kv_token_bytes(full, "int8"),
    }
    kv_full["int8_over_fp16"] = kv_full["int8_bytes"] / kv_full["fp16_bytes"]
    report = {
        "bench": "engine-smoke",
        "model": cfg.name,
        "device": jax.devices()[0].platform,
        "results": (results + prefix + chunked + overload + decode
                    + [launch, kv_full]),
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    for r in results:
        print(f"{r['layout']:>10}: first step (cold, prefill+decode) "
              f"{r['first_step_cold_s']*1e3:.0f}ms"
              f"  decode {r['decode_steps_per_s']:.1f} steps/s"
              f" ({r['decode_step_ms']:.1f} ms/step, batch={r['batch']})")
    for r in prefix:
        on = "on " if r["prefix_cache"] else "off"
        print(f"prefix {on}: prefill {r['prefill_tokens_computed']}/"
              f"{r['prompt_tokens_total']} tokens computed, pool "
              f"{r['pool_blocks_used']} blocks "
              f"(vs {r['pool_blocks_without_sharing']} unshared)")
    for r in chunked:
        mode = f"chunk={r['prefill_chunk']}" if r["prefill_chunk"] \
            else "monolithic"
        print(f"{mode:>10}: long-prompt prefill over "
              f"{r['prefill_steps']} steps ({r['mixed_steps']} mixed), "
              f"max step {r['max_step_ms_during_prefill']:.1f}ms, "
              f"ttft {r['long_ttft_steps']} steps")
    for r in overload:
        print(f"{r['policy']:>10}: TTFT-SLO attainment "
              f"{r['ttft_slo_attainment']:.2f}, interactive ttft "
              f"{r['interactive_ttft_steps']} steps, "
              f"{r['preemptions']} preemptions "
              f"({r['resumed_cached_tokens']} resumed tokens from cache)")
    by_pol = {r["policy"]: r["ttft_slo_attainment"] for r in overload}
    assert by_pol["slo"] > by_pol["fcfs"], \
        "SLO-deadline policy must beat FCFS on the bursty workload"
    for r in decode:
        print(f"{r['mode']:>10}: decode {r['decode_tokens_per_s']:.0f} "
              f"tok/s, p50 {r['p50_step_ms']:.2f}ms p99 "
              f"{r['p99_step_ms']:.2f}ms, KV {r['kv_bytes_per_token_analytic']}"
              f" B/tok (measured {r['kv_bytes_per_token_measured']:.0f})")
    print(f"fused launch: {launch['fused_tokens_per_s']:.0f} tok/s (1 "
          f"launch) vs per-request gather "
          f"{launch['gather_tokens_per_s']:.0f} tok/s "
          f"({launch['launches_per_step_gather']} launches)")
    print(f"kv bytes/token @ {full.name} (hd={full.head_dim}): "
          f"int8 {kv_full['int8_bytes']} / fp16 {kv_full['fp16_bytes']} "
          f"= {kv_full['int8_over_fp16']:.3f}")
    assert launch["fused_tokens_per_s"] >= launch["gather_tokens_per_s"], \
        "one fused ragged launch must beat per-request gather launches"
    assert kv_full["int8_over_fp16"] <= 0.6, \
        "int8 pages must (at least) nearly halve KV bytes/token at " \
        "production head_dim"
    for r in decode:
        assert r["kv_bytes_per_token_measured"] == \
            r["kv_bytes_per_token_analytic"], \
            f"pool bytes diverge from the analytic quote in mode {r['mode']}"
    print(f"wrote {out_path}")


if __name__ == "__main__":
    main(*sys.argv[1:2])
