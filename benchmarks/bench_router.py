"""KV-aware routing benchmark: multi-turn chat sessions over a
replicated model — ``kv_affinity`` routing vs the ``round_robin``
baseline, plus the multi-tier spill/restore accounting cross-check.

Three parts, all written to ``BENCH_router.json``:

  * ``routing`` — one model, 3 replicas on a shared pool, a
    ``multi_turn_sessions`` trace (every turn re-sends the growing
    conversation). kv_affinity must strictly beat round_robin on the
    cached-token ratio *and* on TTFT p99 — sticking a session to the
    replica holding its KV blocks skips the re-prefill that round-robin
    pays on every replica switch.
  * ``exactness`` — the same trace on a single replica: outputs must be
    bit-exact with every multi-replica run, whatever the policy routed
    (routing moves *where* a prompt prefills, never *what* it decodes).
  * ``restore`` — spill a prefix cache through churn, restore it, and
    hold the measured restore-flow seconds against the analytic
    ``restore_estimate`` quote (same Eq. 3 bandwidth model): they must
    agree within 5%, and the restored bytes must round-trip bit-exact.

    PYTHONPATH=src python benchmarks/bench_router.py [out.json]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_REPLICAS = 3
N_SESSIONS = 6
TURNS = 4
MAX_NEW = 4
VOCAB = 256


def _cfg():
    from repro.configs.base import ModelConfig
    return ModelConfig(name="router-tiny", family="dense", n_layers=2,
                       d_model=32, n_heads=4, n_kv_heads=4, d_ff=64,
                       vocab=VOCAB, dtype="float32", max_pp=2)


def _trace():
    from repro.workloads.generator import ModelInstance, multi_turn_sessions
    inst = ModelInstance("m0", "chat", "router-tiny", 10.0, 0.5, 24, MAX_NEW)
    return multi_turn_sessions(inst, N_SESSIONS, TURNS, first_prompt=24,
                               turn_tokens=8, vocab=VOCAB,
                               session_rps=0.5, think_s=2.0, seed=0)


def _fleet(params, n_replicas, routing):
    import jax  # noqa: F401  (env already imported it)
    from repro.core.types import GB, Gbps, ModelProfile, ServerSpec, SLO, \
        TimingProfile
    from repro.fleet import FleetFrontend
    from repro.fleet.controller import FleetPolicy

    servers = [ServerSpec(f"s{i}", 10 * Gbps, 12e9, 2 * GB, 1)
               for i in range(2)]
    ff = FleetFrontend(servers, FleetPolicy.naive(keepalive_s=1e6))
    prof = ModelProfile("m0", 2 * 1024 * 1024,
                        TimingProfile(t_cc=0.2, t_l=0.2, t_cu=0.1),
                        SLO(10.0, 0.5), max_pp=2, kv_bytes_per_token=256)
    ff.register(_cfg(), prof, params=params, max_batch=2, max_seq=64,
                block_size=8, routing=routing)
    ff.scale_to("m0", n_replicas, now=0.0)
    return ff


def _drive(ff, trace):
    from repro.serving.api import SamplingParams
    mm = ff.models["m0"]
    t0 = max(s.ready_at for s in mm.slots) + 1.0
    out = []
    for r in trace:
        out.append(ff.submit("m0", r.prompt_ids,
                             SamplingParams(max_new=MAX_NEW),
                             now=t0 + r.arrival))
    ff.advance(t0 + trace[-1].arrival + 10.0)
    return out


def _pct(xs, q):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(len(xs) * q))] if xs else 0.0


def run_routing(params, trace) -> dict:
    out = {}
    for routing in ("round_robin", "kv_affinity"):
        ff = _fleet(params, N_REPLICAS, routing)
        reqs = _drive(ff, trace)
        mm = ff.metrics()["per_model"]["m0"]
        ttfts = [r.ttft for r in reqs]
        out[routing] = {
            "n": len(reqs),
            "replicas": N_REPLICAS,
            "cached_ratio": mm["cached_ratio"],
            "cached_tokens": mm["cached_tokens"],
            "restored_tokens": mm["restored_tokens"],
            "ttft_p50": _pct(ttfts, 0.50),
            "ttft_p99": _pct(ttfts, 0.99),
            "router": mm["router"],
            "kv_tier": mm["kv_tier"],
            "outputs": [r.output for r in reqs],
        }
    aff, rr = out["kv_affinity"], out["round_robin"]
    assert aff["cached_ratio"] > rr["cached_ratio"], (
        f'kv_affinity cached ratio {aff["cached_ratio"]:.3f} !> '
        f'round_robin {rr["cached_ratio"]:.3f}')
    assert aff["ttft_p99"] < rr["ttft_p99"], (
        f'kv_affinity ttft_p99 {aff["ttft_p99"]:.4f} !< '
        f'round_robin {rr["ttft_p99"]:.4f}')
    return out


def run_exactness(params, trace, routing_out) -> dict:
    """Single-replica reference: whatever the policy routed, the decoded
    tokens must match — routing is placement, not semantics."""
    ff = _fleet(params, 1, "kv_affinity")
    reqs = _drive(ff, trace)
    ref = [r.output for r in reqs]
    for routing, r in routing_out.items():
        assert r["outputs"] == ref, f"{routing} outputs diverged from the " \
            "single-replica reference"
        del r["outputs"]
    return {"n": len(ref), "bit_exact": True}


def run_restore(params) -> dict:
    """Standalone engine + KVBlockStore: churn evicts a committed prefix
    (spill), resubmitting restores it. The measured flow seconds must
    match the analytic restore_estimate quote within 5% and the decode
    must be bit-exact with a never-evicted run."""
    from repro.router import KVBlockStore, ResidencyIndex
    from repro.serving.api import SamplingParams
    from repro.serving.engine import Engine

    def fresh(kv_tier=None):
        return Engine(_cfg(), [params], max_batch=2, max_seq=64,
                      block_size=8, paged=True, prefix_cache=True,
                      kv_tier=kv_tier)

    P = list(range(1, 25))               # 3 full blocks at block_size=8
    eng_ref = fresh()
    r_ref = eng_ref.submit(P, SamplingParams(max_new=MAX_NEW))
    eng_ref.run()

    tier = KVBlockStore()                # single-server schedule, host bw
    eng = fresh(kv_tier=tier)
    res = ResidencyIndex(kv_tier=tier)
    res.attach("r0", eng.block_mgr)
    r1 = eng.submit(P, SamplingParams(max_new=MAX_NEW))
    eng.run()
    assert list(r1.generated) == list(r_ref.generated)

    i = 0
    while res.match("r0", P)[0] > 0:     # churn until P fully evicted
        q = [(97 + 13 * i + j) % VOCAB for j in range(24)]
        eng.submit(q, SamplingParams(max_new=2))
        eng.run()
        i += 1
        assert i < 200, "churn never evicted the prefix"
    warm, restorable = res.match("r0", P)
    assert warm == 0 and restorable >= 3

    hashes = res.chain_hashes("r0", P)[:restorable]
    analytic = tier.restore_estimate(hashes, now=0.0)
    flows0 = len(tier.restore_flows)
    r2 = eng.submit(P, SamplingParams(max_new=MAX_NEW))
    eng.run()
    assert list(r2.generated) == list(r_ref.generated), \
        "restored decode diverged"
    measured = sum(f.seconds for f in tier.restore_flows[flows0:])
    err = abs(measured - analytic) / max(analytic, 1e-12)
    assert err <= 0.05, (
        f"restore flow accounting drifted {err:.1%} from the analytic "
        f"quote (measured {measured:.3e}s vs {analytic:.3e}s)")
    return {
        "blocks_restored": tier.restores,
        "restored_bytes": tier.restored_bytes,
        "restored_tokens": r2.metrics.restored_tokens,
        "measured_s": measured,
        "analytic_s": analytic,
        "rel_err": err,
        "bit_exact": True,
        "tier": tier.stats(),
    }


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    out = sys.argv[1] if len(sys.argv) > 1 else "BENCH_router.json"
    import jax
    from repro.models import build_model
    t0 = time.time()
    params = build_model(_cfg()).init(jax.random.PRNGKey(0))
    trace = _trace()
    routing = run_routing(params, trace)
    exact = run_exactness(params, trace, routing)
    restore = run_restore(params)
    report = {
        "decode_mode": os.environ.get("REPRO_DECODE_MODE", "scatter"),
        "sessions": N_SESSIONS, "turns": TURNS,
        "routing": routing, "exactness": exact, "restore": restore,
        "wall_s": round(time.time() - t0, 2),
    }
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    aff, rr = routing["kv_affinity"], routing["round_robin"]
    print(f"router: cached ratio rr={rr['cached_ratio']:.3f} -> "
          f"affinity={aff['cached_ratio']:.3f}, "
          f"ttft_p99 {rr['ttft_p99']:.4f}s -> {aff['ttft_p99']:.4f}s, "
          f"outputs bit-exact across {N_REPLICAS} replicas")
    print(f"restore: {restore['blocks_restored']} blocks "
          f"({restore['restored_bytes']}B) measured {restore['measured_s']:.2e}s "
          f"vs analytic {restore['analytic_s']:.2e}s "
          f"({100 * restore['rel_err']:.2f}% err)")
    print(f"wrote {out} ({report['wall_s']}s)")


if __name__ == "__main__":
    main()
