"""Streaming + sampling demo of the request-lifecycle API.

Three concurrent requests with different decode policies — greedy,
seeded temperature/top-k sampling, and a stop-token request — stream
token events out of one engine step loop. Shows per-request finish
reasons and step-metrics at the end.

    PYTHONPATH=src python examples/streaming_demo.py
"""

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, smoke_variant
from repro.models import build_model
from repro.serving.api import SamplingParams
from repro.serving.endpoint import ServingEndpoint
from repro.serving.engine import Engine

enable_compile_cache()
cfg = smoke_variant(get_config("granite-3-8b"))
params = build_model(cfg).init(jax.random.PRNGKey(0))
ep = ServingEndpoint(Engine(cfg, [params], max_batch=4, max_seq=64))

# learn the greedy stream once so the stop-token demo is guaranteed to hit
probe = [ev.token for ev in ep.generate([5, 7, 9, 11],
                                        SamplingParams(max_new=8))]

reqs = {
    "greedy ": ep.submit([5, 7, 9, 11], SamplingParams(max_new=8)),
    "sampled": ep.submit([5, 7, 9, 11],
                         SamplingParams(max_new=8, temperature=0.8,
                                        top_k=8, seed=1234)),
    "stopped": ep.submit([5, 7, 9, 11],
                         SamplingParams(max_new=8,
                                        stop_tokens=(probe[3],))),
}
stop_at = probe.index(probe[3])          # stop fires at first occurrence
while ep.has_work():
    out = ep.step()
    for ev in out.events:
        fin = f"  <- {ev.finish_reason.value}" if ev.finish_reason else ""
        print(f"step {out.step}: rid={ev.rid} token={ev.token}{fin}")

for name, r in reqs.items():
    m = r.metrics
    print(f"{name}: {r.generated} finish={r.finish_reason.value} "
          f"ttft={m.ttft_steps} queue={m.queue_steps} "
          f"decode_steps={m.decode_steps}")

assert reqs["greedy "].generated == probe
assert reqs["stopped"].generated == probe[:stop_at + 1]
assert reqs["sampled"].generated != probe
print("OK: streaming order, stop-token truncation, and sampling diverge "
      "as expected")
