"""Pipeline-consolidation deep dive (paper §6, Fig. 13): serve one long
generation on a 4-stage pipeline, scale DOWN mid-flight, and show the
per-token latency profile before/after the KV migration. Uses the real JAX
endpoint API (reduced-config jamba — the hybrid arch migrates attention KV
*and* Mamba/conv recurrent state), and the swap happens behind the stable
ServingEndpoint handle.

    PYTHONPATH=src python examples/consolidation_demo.py
"""

import time

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, smoke_variant
from repro.models import build_model
from repro.serving.api import SamplingParams
from repro.serving.endpoint import ServingEndpoint
from repro.serving.engine import Engine
from repro.serving.migration import gather_stage_caches

enable_compile_cache()
cfg = smoke_variant(get_config("jamba-v0.1-52b"))
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))

n_stages = 4 if cfg.n_periods >= 4 else 2
stage_params = [model.slice_stage_params(params, n_stages, i)
                for i in range(n_stages)]
print(f"{cfg.name}: {cfg.n_layers} layers in {n_stages} stages; per-stage "
      f"fetch bytes: {[model.stage_bytes(n_stages, i) for i in range(n_stages)]}")

ep = ServingEndpoint(Engine(cfg, stage_params, max_batch=2, max_seq=96))
req = ep.submit(list(range(2, 18)), SamplingParams(max_new=24))

lat = []
for step in range(8):
    t0 = time.perf_counter()
    ep.step()
    lat.append(time.perf_counter() - t0)
print(f"pipeline tokens: {req.generated}")
print(f"pipeline per-step wall: {[f'{x*1e3:.0f}ms' for x in lat]}")

t0 = time.perf_counter()
gathered = gather_stage_caches([w.cache for w in ep.engine.workers])
mig_wall = time.perf_counter() - t0
n_bytes = sum(x.nbytes for x in jax.tree.leaves(gathered))
print(f"KV+state migration: {n_bytes/1e6:.2f} MB gathered in "
      f"{mig_wall*1e3:.1f} ms (host)")

ep.consolidate(params)                   # same handle, standalone engine
lat2 = []
while req.generated and not req.done:
    t0 = time.perf_counter()
    ep.step()
    lat2.append(time.perf_counter() - t0)
    if len(lat2) > 40:
        break
print(f"standalone tokens: {req.generated}")
print(f"standalone per-step wall: {[f'{x*1e3:.0f}ms' for x in lat2[:8]]}")

# correctness: the full run must equal a never-pipelined run
ref = ServingEndpoint(Engine(cfg, [params], max_batch=2, max_seq=96))
r2 = ref.submit(list(range(2, 18)), SamplingParams(max_new=24))
ref.run()
assert r2.generated == req.generated
print("OK: scale-down preserved the generation exactly "
      f"(ttft={req.metrics.ttft_steps} steps, "
      f"tpot-proxy={req.metrics.tpot_steps:.2f} steps/token)")
