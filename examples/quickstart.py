"""Quickstart: hydra cold start end-to-end on CPU, against one API.

The ServerlessFrontend runs Alg. 1 and hands back a ServingEndpoint; the
endpoint serves, then consolidates (§6.2) behind the same handle — the
client never sees the pipeline group dissolve.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, smoke_variant
from repro.core import GB, Gbps, ModelProfile, ServerSpec, SLO, TimingProfile
from repro.models import build_model
from repro.serving.api import SamplingParams
from repro.serving.endpoint import ServerlessFrontend, ServingEndpoint
from repro.serving.engine import Engine

enable_compile_cache()
cfg = smoke_variant(get_config("granite-3-8b"))
params = build_model(cfg).init(jax.random.PRNGKey(0))

front = ServerlessFrontend({f"srv{i}": ServerSpec(f"srv{i}", 16 * Gbps,
                                                  12e9, 24 * GB)
                            for i in range(4)})
front.deploy(cfg, params, ModelProfile(
    cfg.name, int(12.5 * GB),            # pretend it's the real Llama2-7B
    TimingProfile(), SLO(ttft=7.5, tpot=0.2)))

ep = front.cold_start(cfg.name, min_stages=2, max_batch=2, max_seq=64)
print(f"Alg.1 scheme: s={ep.scheme.s} w={ep.scheme.w} "
      f"servers={ep.scheme.servers} -> {ep.n_stages}-stage pipeline, "
      f"pred_ttft={ep.scheme.predicted_ttft:.2f}s slo_ok={ep.scheme.slo_ok}")

req = ep.submit([11, 42, 7, 13, 5], SamplingParams(max_new=12))
for _ in range(5):
    ep.step()
print(f"tokens before consolidation: {req.generated}")
ep.consolidate(front.full_params(cfg.name))   # §6.2, same handle
ep.run()
print(f"tokens after consolidation:  {req.generated} "
      f"({req.finish_reason.value}, ttft={req.metrics.ttft_steps} steps)")

ref = ServingEndpoint(Engine(cfg, [params], max_batch=2, max_seq=64))
tokens = [ev.token for ev in ref.generate([11, 42, 7, 13, 5],
                                          SamplingParams(max_new=12))]
assert tokens == req.generated, "consolidation changed the output!"
print("OK: endpoint output == single-worker reference across consolidation")
