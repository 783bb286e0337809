"""CI smoke: cold-start a tiny model through the on-disk ModelStore.

Deploys a smoke model into a real chunked store on disk, cold-starts a
pipeline group whose stage weights are *streamed* out of it, serves a
few greedy tokens, and verifies bit-exactness against an in-memory
engine built from the same params. The measured per-stage timeline —
plus the measured-vs-analytic cross-check for every OverlapFlags
ablation step — is written to ``BENCH_coldstart_timeline.json`` (CI
uploads it next to ``BENCH_engine.json``).

    PYTHONPATH=src python examples/store_coldstart_smoke.py
"""

import json
import tempfile

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, smoke_variant
from repro.core import GB, ModelProfile, SLO, ServerSpec, TimingProfile
from repro.core.coldstart import OverlapFlags
from repro.models import build_model
from repro.serving.api import SamplingParams
from repro.serving.endpoint import ServerlessFrontend, ServingEndpoint
from repro.serving.engine import Engine
from repro.store import assert_within, crosscheck_stages

enable_compile_cache()
cfg = smoke_variant(get_config("granite-3-8b"))
params = build_model(cfg).init(jax.random.PRNGKey(0))

store_dir = tempfile.mkdtemp(prefix="store-smoke-")
front = ServerlessFrontend({f"srv{i}": ServerSpec(f"srv{i}", 2e9, 12e9,
                                                  24 * GB)
                            for i in range(4)})
store = front.deploy(cfg, params, ModelProfile(
    cfg.name, int(12.5 * GB), TimingProfile(), SLO(ttft=7.5, tpot=0.2)),
    store_dir=store_dir)
print(f"store: {store.total_bytes} bytes in "
      f"{len(store.manifest.chunks)} chunks at {store_dir}")

ep = front.cold_start(cfg.name, min_stages=2, max_batch=2, max_seq=64)
report = ep.cold_start_timeline
print(f"cold start: s={ep.n_stages}, streamed {report.total_bytes} bytes, "
      f"measured ready={report.ready:.3f}s")

prompt = [11, 42, 7, 13, 5]
tokens = [ev.token for ev in ep.generate(prompt, SamplingParams(max_new=8))]
ref = ServingEndpoint(Engine(cfg, [params], max_batch=2, max_seq=64))
want = [ev.token for ev in ref.generate(prompt, SamplingParams(max_new=8))]
assert tokens == want, f"store-streamed weights diverged: {tokens} != {want}"
print(f"OK: first {len(tokens)} greedy tokens bit-exact with the "
      f"in-memory engine: {tokens}")

# measured-vs-analytic cross-check over the Fig. 9 ablation axis
nic = store.total_bytes / 8.0
ablation = {}
for name, flags in [("none", OverlapFlags.none()),
                    ("+prefetch", OverlapFlags(True, False, False)),
                    ("+stream", OverlapFlags(True, True, False)),
                    ("+overlap", OverlapFlags.all())]:
    checks = crosscheck_stages(store, min(2, cfg.n_periods), flags=flags,
                               nic_bytes_per_s=nic, load_bytes_per_s=4 * nic)
    worst = assert_within(checks, 0.05)
    ablation[name] = {"worst_err": worst,
                      "stages": [c.to_json() for c in checks]}
    print(f"  {name:10s} measured==analytic within {worst:.2%}")

with open("BENCH_coldstart_timeline.json", "w") as f:
    json.dump({"model": cfg.name, "store_bytes": store.total_bytes,
               "cold_start": report.to_json(),
               "tokens_bit_exact": True,
               "ablation_crosscheck": ablation}, f, indent=2)
print("wrote BENCH_coldstart_timeline.json")
