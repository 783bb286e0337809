"""A whole run of the harness at a tiny size on the CPU, without the
look for a chip: sound, it comes out correct; with the timed path broken
underneath in each way a served cell can break, it comes out not
correct; and the float8 control, the reference one precision below the
configuration's, fails the limit that sound runs pass."""

import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from bench.lib import check

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"


def harness():
    spec = importlib.util.spec_from_file_location("bench_run_under_test",
                                                  ROOT / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_run(seed=7, trace=False):
    cfg = json.loads((DATA / "tiny.json").read_text())
    mix = json.loads((DATA / "tinyburst.json").read_text())
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return harness().execute(cfg, mix, seed=seed, seconds=0.01, trace=trace,
                             chips=1, end_to_end=spec["end_to_end"],
                             per_layer=spec["per_layer"],
                             require_chip=False)


def test_sound_run_is_correct():
    res = tiny_run()
    assert res["correct"], res["checks"]
    assert res["attempted"] == 4 and res["failed"] == 0
    assert set(res["metrics"]) == {"cold_ttft_s", "cold_e2e_s",
                                   "itl_p95_ms", "setup_s"}
    assert list(res)[-1] == "checks"


def _alter_token(monkeypatch):
    """A token altered where it is produced: every fifth emitted token
    of a request is replaced by its neighbour in the vocabulary."""
    from repro.serving.engine import Engine
    orig = Engine._emit

    def emit(self, req, token, events):
        if len(req.generated) % 5 == 4:
            token = (token + 1) % self.cfg.vocab
        return orig(self, req, token, events)

    monkeypatch.setattr(Engine, "_emit", emit)


def _state_unchanged(monkeypatch):
    """A decode step that returns its KV state unchanged."""
    from repro.serving.worker import StageWorker

    def decode(self, x_in, positions, block_tables=None):
        out, _ = self._decode_fn(self.params, x_in, positions, self.cache,
                                 block_tables)
        return out

    monkeypatch.setattr(StageWorker, "decode", decode)


def _half_batch(monkeypatch):
    """Half of the decode batch left out: odd slots are fed token 0 at
    position 0 in place of their own."""
    from repro.serving.runner import ModelRunner
    orig = ModelRunner.decode

    def decode(self, reqs, skip_slots=()):
        saved = [(r, r.generated[-1]) for r in reqs if r.slot % 2]
        for r, _ in saved:
            r.generated[-1] = 0
        try:
            return orig(self, reqs, skip_slots)
        finally:
            for r, tok in saved:
                r.generated[-1] = tok

    monkeypatch.setattr(ModelRunner, "decode", decode)


def _no_exchange(monkeypatch):
    """The exchange between the pipeline's workers left out: the second
    stage receives zeros in place of the first stage's hidden state."""
    import jax.numpy as jnp

    from repro.serving.worker import StageWorker
    dec, rag = StageWorker.decode, StageWorker.forward_ragged

    def decode(self, x_in, positions, block_tables=None):
        if not self.first:
            x_in = jnp.zeros_like(x_in)
        return dec(self, x_in, positions, block_tables)

    def forward_ragged(self, x_in, *a):
        if not self.first:
            x_in = jnp.zeros_like(x_in)
        return rag(self, x_in, *a)

    monkeypatch.setattr(StageWorker, "decode", decode)
    monkeypatch.setattr(StageWorker, "forward_ragged", forward_ragged)


@pytest.mark.parametrize("fault", [_alter_token, _state_unchanged,
                                   _half_batch, _no_exchange])
def test_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = tiny_run()
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_control_fails_the_limit(seed, monkeypatch):
    """The reference computed in float8 e4m3 in the program's place: the
    tokens it puts first lie further below the float32 reference's best
    than the limit allows, on the same prompts and served tokens."""
    seen = {}
    orig = check.readings

    def readings(cfg, mix, s, reqs, control=False):
        seen.update(orig(cfg, mix, s, reqs, control=True))
        return seen

    monkeypatch.setattr(check, "readings", readings)
    res = tiny_run(seed)
    limit = res["checks"]["max_logit_gap"]["limit"]
    assert res["correct"]
    assert seen["control_max_logit_gap"] > limit >= seen["max_logit_gap"]


def test_traced_run_reports_host_layers_without_device_trace():
    """On the CPU there is no device plane: the trace readers find
    nothing and their metrics are left out, never reported as 0. The
    host clocks and the readers of the program's span ring read."""
    res = tiny_run(trace=True)
    assert set(res["metrics"]) == {
        "load_s", "build_s", "consolidate_s", "store_read_s",
        "first_step_builds", "consolidate_fetch_s", "consolidated_build_s"}
    assert all(np.isfinite(m["value"]) and m["value"] > 0
               for m in res["metrics"].values())
