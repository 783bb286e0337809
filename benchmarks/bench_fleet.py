"""Fleet control-plane benchmark: many models, one shared pool, bursty
arrivals — naive reactive scaling vs the HydraServe-style proactive
policy (Alg. 1 proactive model distribution + §6.1 predictive
prewarming + delayed downscale), all through the one shared
``FleetController``.

Two parts, both written to ``BENCH_fleet.json``:

  * ``sim``   — the discrete-event fleet: ≥8 model instances over
    testbed (i), a recurring-burst trace (every model reaped to zero
    between episodes), naive vs proactive. Reports fleet-wide
    request-experienced cold-start p50/p99 and TTFT SLO attainment;
    the proactive policy must strictly improve cold p99 and
    attainment.
  * ``real``  — the real-JAX ``FleetFrontend`` smoke: ≥4 tiny models
    on a shared server pool, concurrent cold starts through the shared
    ``FetchSchedule``, scale-to-zero and re-warm, measured cold-start
    timelines.

    PYTHONPATH=src python benchmarks/bench_fleet.py [out.json] [--sim-only]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import profiles, testbed_i
from repro.compile_cache import enable_compile_cache
from repro.fleet.controller import FleetPolicy
from repro.serving.simulation import ServerlessSim
from repro.workloads.applications import APPLICATIONS
from repro.workloads.generator import make_instances, periodic_bursts

# --------------------------------------------------------------------- sim
N_INSTANCES = 8          # distinct model instances sharing the pool
PERIOD = 120.0           # burst recurrence per instance
N_BURSTS = 10
BURST_SIZE = 3
KEEPALIVE = 30.0         # << PERIOD: every model reaps to zero between bursts


def fleet_sim(policy: FleetPolicy) -> dict:
    insts = make_instances(APPLICATIONS, 2)[:N_INSTANCES]
    assert len(insts) >= 8
    sim = ServerlessSim(testbed_i(), profiles(), insts, system="hydra",
                        policy=policy)
    reqs = periodic_bursts(insts, PERIOD, N_BURSTS, BURST_SIZE,
                           stagger=3.0, jitter=1.0, seed=0)
    sim.submit(reqs)
    sim.run(until=PERIOD * (N_BURSTS + 2))
    m = sim.metrics()
    assert m["n"] == len(reqs), "trace did not drain"
    return m


def run_sim() -> dict:
    naive = fleet_sim(FleetPolicy.naive(keepalive_s=KEEPALIVE))
    proactive = fleet_sim(FleetPolicy.proactive(
        keepalive_s=KEEPALIVE, downscale_extend_s=60.0,
        placement_interval_s=20.0, placement_top_k=N_INSTANCES,
        placement_fanout=2))
    assert proactive["prewarms"] > 0, "prewarming never fired"
    assert proactive["placements"] > 0, "proactive placement never fired"
    assert proactive["cold_p99"] < naive["cold_p99"], \
        f'cold p99 {proactive["cold_p99"]:.2f} !< {naive["cold_p99"]:.2f}'
    assert proactive["ttft_attainment"] > naive["ttft_attainment"], (
        f'attainment {proactive["ttft_attainment"]:.3f} !> '
        f'{naive["ttft_attainment"]:.3f}')
    return {
        "models": N_INSTANCES, "period_s": PERIOD, "bursts": N_BURSTS,
        "burst_size": BURST_SIZE, "keepalive_s": KEEPALIVE,
        "naive": naive, "proactive": proactive,
        "cold_p99_reduction": 1.0 - proactive["cold_p99"] / naive["cold_p99"],
    }


# -------------------------------------------------------------------- real
def run_real() -> dict:
    """≥4 real models on a shared pool: batched concurrent cold starts,
    queued-during-cold-start requests, scale-to-zero and bit-exact
    re-warm — through the same FleetController policy object."""
    import jax

    from repro.configs.base import ModelConfig
    from repro.core.types import (GB, Gbps, ModelProfile, ServerSpec, SLO,
                                  TimingProfile)
    from repro.fleet import FleetFrontend
    from repro.models import build_model

    cfg = ModelConfig(name="fleet-tiny", family="dense", n_layers=4,
                      d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                      vocab=256, dtype="float32", max_pp=2)
    servers = [ServerSpec(f"s{i}", 10 * Gbps, 12e9, 2 * GB, 1)
               for i in range(4)]
    params = build_model(cfg).init(jax.random.PRNGKey(0))

    ff = FleetFrontend(servers, FleetPolicy.proactive(
        keepalive_s=20.0, downscale_extend_s=20.0,
        placement_interval_s=5.0, placement_top_k=4))
    n_models = 4
    for i in range(n_models):
        prof = ModelProfile(f"m{i}", 8 * 1024 * 1024,
                            TimingProfile(t_cc=0.2, t_l=0.2, t_cu=0.1),
                            SLO(10.0, 0.5), max_pp=2,
                            kv_bytes_per_token=4 * 4 * 16 * 2 * 2)
        ff.register(cfg, prof, params=params, max_batch=2, max_seq=64)

    # burst 1: all four models cold-start concurrently (shared schedule)
    trace = [(f"m{i}", 0.0, [1 + i, 2 + i, 3 + i]) for i in range(n_models)]
    # burst 2 (after reap): every model cold again — outputs must repeat.
    # Drain past the reap window but short of t=120, where the controller
    # (correctly) prewarms for the learned 60 s burst period.
    trace += [(f"m{i}", 60.0, [1 + i, 2 + i, 3 + i]) for i in range(n_models)]
    reqs = ff.run_trace(trace, drain_to=110.0)

    first = {r.model: r.output for r in reqs if r.arrival == 0.0}
    for r in reqs:
        if r.arrival == 60.0:
            assert r.output == first[r.model], \
                f"{r.model}: re-warmed output diverged"
    assert all(not mm.slots for mm in ff.models.values()), \
        "scale-to-zero reap did not run"
    m = ff.metrics()
    assert m["cold_starts"] >= 2 * n_models
    return {"models": n_models, "bit_exact_rewarm": True, **m}


def main():
    enable_compile_cache()
    out = sys.argv[1] if len(sys.argv) > 1 and not sys.argv[1].startswith(
        "--") else "BENCH_fleet.json"
    t0 = time.time()
    report = {"sim": run_sim()}
    if "--sim-only" not in sys.argv:
        report["real"] = run_real()
    report["wall_s"] = round(time.time() - t0, 2)
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    s = report["sim"]
    print(f"fleet sim: cold_p99 naive={s['naive']['cold_p99']:.2f}s "
          f"proactive={s['proactive']['cold_p99']:.2f}s "
          f"(-{100 * s['cold_p99_reduction']:.0f}%), "
          f"attainment {s['naive']['ttft_attainment']:.3f} -> "
          f"{s['proactive']['ttft_attainment']:.3f}")
    if "real" in report:
        r = report["real"]
        print(f"fleet real: {r['models']} models, {r['cold_starts']} cold "
              f"starts, cold_p50={r['cold_p50']:.2f}s, bit-exact re-warm ok")
    print(f"wrote {out} ({report['wall_s']}s)")


if __name__ == "__main__":
    main()
