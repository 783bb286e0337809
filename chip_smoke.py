"""Smoke run of the HydraServe cold-start serving path on one TPU chip.

    python3 chip_smoke.py [--seed N]

The model is granite-3-8b at its published widths (d_model 4096, 32 query
and 8 KV heads of 128, d_ff 12800, vocab 49155, bf16) with random weights
from ``--seed``, cut in depth to 12 of its 40 layers so that the §6.2
consolidation, which briefly holds the two-stage weights and the full
copy, fits one 16 GB chip.

Phase A is the paper's path through the user-facing entry points:
``ServerlessFrontend.deploy``, a two-stage paged ``cold_start``, eight
requests of 64-512 prompt tokens and 32 new tokens each, and a
``consolidate`` part-way through. Phase B serves the same requests on one
fused ragged-step ``Engine``. A single-worker contiguous-cache ``Engine``
is the reference; its attention runs through the other two kernels
(flash prefill, contiguous decode), so every serving kernel runs here.

Each phase is checked against the reference on logits, at every position
whose history the two runs share. Every request must end on its length,
every logit must be finite, and every jitted serving step must contain a
Pallas kernel (``tpu_custom_call``). Any failed check raises. The last
line of output is one JSON object naming the device.

The script refuses to run anywhere but on a TPU with the Pallas kernels.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import statistics
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import GB, SLO, Gbps, ModelProfile, ServerSpec  # noqa: E402
from repro.core import TimingProfile  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serving.api import FinishReason, SamplingParams  # noqa: E402
from repro.serving.endpoint import ServerlessFrontend  # noqa: E402
from repro.serving.engine import Engine  # noqa: E402

N_LAYERS = 12            # of granite-3-8b's 40: see the module docstring
N_REQUESTS = 8
PROMPT_LEN = (64, 512)
MAX_NEW = 32
MAX_SEQ = 2048
CONSOLIDATE_AFTER = 12   # phase A steps served by the 2-stage pipeline

# Logit tolerances against the reference, over the 49155 real vocab
# entries. Logits are bf16 with a spread of about 1 (rmsnorm'd hidden
# times a 1/sqrt(d) head). The runs differ only in kernel, KV layout,
# batch shape and pipeline split: f32 summation order, and so which bf16
# roundings of the 12 layers' activations land one ulp (2**-8) apart. On
# a v5e that spread measured max |dlogit| 0.078 and relative L2 0.016 at
# seed 0; the limits are about three times that. One rounding to a format
# with 5 fewer mantissa bits (fp8 e4m3) moves each activation by up to
# 2**-4, which over 12 layers exceeds both limits many times over.
MAX_ABS = 0.25
REL_L2 = 0.05


def log(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


def ready(tree) -> None:
    jax.block_until_ready(jax.tree.leaves(tree))


class CompileClock:
    """What JAX reports through ``jax.monitoring`` about building
    executables: seconds tracing, lowering, and compiling or loading from
    the persistent cache, and that cache's lookups, hits and writes."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
              "/jax/core/compile/backend_compile_duration": "compile_s",
              "/jax/compilation_cache/compile_requests_use_cache":
                  "cache_lookups",
              "/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_writes"}

    def __init__(self):
        self.totals = dict.fromkeys(self.EVENTS.values(), 0)
        jax.monitoring.register_event_duration_secs_listener(self._add)
        jax.monitoring.register_event_listener(
            lambda event, **kw: self._add(event, 1))

    def _add(self, event, value, **kw):
        if event in self.EVENTS:
            self.totals[self.EVENTS[event]] += value

    def since(self, before: dict) -> dict:
        return {k: v - before[k] for k, v in self.totals.items()}


class StepWatch:
    """Observes every jitted serving step an engine runs: each distinct
    argument signature it was called with (to compile again and read its
    text), and the logits each runner forward produced, keyed by
    ``(rid, position)`` — the position of the last token fed."""

    STEPS = ("_prefill_fn", "_decode_fn", "_ragged_fn")

    def __init__(self):
        self.calls = {}          # (label, signature) -> (fn, args, kw)
        self.logits = {}         # (rid, pos) -> (array, index)

    def watch(self, label: str, engine: Engine) -> None:
        for w in engine.workers:
            for name in self.STEPS:
                fn = getattr(w, name)
                setattr(w, name, functools.partial(
                    self._step, f"{label}/s{w.stage}{name[:-3]}", fn))
        runner = engine.runner
        slots = engine.scheduler
        fwd, dec, pre = runner.forward_batch, runner.decode, runner.prefill

        def forward_batch(segments):
            h = fwd(segments)
            for i, (slot, tokens, pos0) in enumerate(segments):
                rid = slots.slots[slot].rid
                self.logits[(rid, pos0 + len(tokens) - 1)] = (h, (i,))
            return h

        def decode(reqs, skip_slots=()):
            h = dec(reqs, skip_slots)
            for r in reqs:
                self.logits[(r.rid, r.pos_next)] = (h, (r.slot, 0))
            return h

        def prefill(slot, tokens, start, n, prefix_embeds=None):
            h = pre(slot, tokens, start, n, prefix_embeds=prefix_embeds)
            self.logits[(slots.slots[slot].rid, start + n - 1)] = (h, (0, 0))
            return h

        runner.forward_batch = forward_batch
        runner.decode = decode
        runner.prefill = prefill

    def _step(self, label, fn, *args, **kw):
        abstract = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding), args)
        sig = str(jax.tree.map(lambda s: (s.shape, str(s.dtype)), abstract))
        self.calls.setdefault((label, sig + str(sorted(kw.items()))),
                              (fn, abstract, kw))
        return fn(*args, **kw)

    def kernel_report(self):
        """(label, input shape, contains tpu_custom_call) per compiled
        serving step; then forgets the steps, which hold their workers."""
        out = []
        for (label, _), (fn, args, kw) in sorted(self.calls.items(),
                                                  key=lambda x: x[0]):
            text = fn.lower(*args, **kw).compile().as_text()
            out.append((label, tuple(args[1].shape),
                        "tpu_custom_call" in text))
        self.calls.clear()
        return out

    def host_logits(self, vocab: int):
        host, cache = {}, {}
        for key, (arr, idx) in self.logits.items():
            if id(arr) not in cache:
                cache[id(arr)] = np.asarray(arr, np.float32)
            host[key] = cache[id(arr)][idx][:vocab]
        return host


def run_phase(label, ep, prompts, watch, clock, *, consolidate=None):
    """Submit every prompt to ``ep`` (an endpoint or an engine), then step
    to completion. The first step of each engine compiles and is timed
    apart from the steady ones, with what ``clock`` saw JAX build in it.
    ``consolidate(ep)`` runs after ``CONSOLIDATE_AFTER`` steps when
    given."""
    reqs = [ep.submit(p, SamplingParams(max_new=MAX_NEW)) for p in prompts]
    part, times, n = label, [], 0
    while ep.has_work():
        if consolidate is not None and n == CONSOLIDATE_AFTER:
            log_steady(part, times)
            t = time.perf_counter()
            consolidate(ep)
            ready([w.cache for w in ep.engine.workers])
            log(f"{label}.consolidate_s", time.perf_counter() - t)
            for i, w in enumerate(ep.engine.workers):
                log(f"{label}.consolidated.stage{i}_devices", devices_of(w))
            watch.watch(f"{label}-consolidated", ep.engine)
            part, times = f"{label}.consolidated", []
        built = dict(clock.totals)
        t = time.perf_counter()
        ep.step()
        ready([w.cache for w in getattr(ep, "engine", ep).workers])
        times.append(time.perf_counter() - t)
        if len(times) == 1:
            log(f"{part}.first_step_s", times[0])
            log(f"{part}.first_step_compile", clock.since(built))
        n += 1
    log_steady(part, times)
    log(f"{label}.steps", n)
    for r in reqs:
        if r.finish_reason is not FinishReason.LENGTH \
                or len(r.generated) != MAX_NEW:
            raise AssertionError(f"{label}: request {r.rid} ended "
                                 f"{r.finish_reason} after "
                                 f"{len(r.generated)} tokens")
    log(f"{label}.tokens_served", sum(len(r.generated) for r in reqs))
    return reqs


def log_steady(part, times):
    """The steps after an engine's first (which compiles)."""
    log(f"{part}.steady_steps", len(times) - 1)
    log(f"{part}.steady_step_s.median", statistics.median(times[1:]))
    log(f"{part}.steady_step_s.max", max(times[1:]))


def devices_of(worker) -> str:
    return ",".join(sorted({str(d) for leaf in jax.tree.leaves(worker.params)
                            for d in leaf.devices()}))


def compare(label, reqs, logits, ref_reqs, ref_logits):
    """Logits of ``reqs`` against the reference at every position whose
    history both runs share: from the last prompt token up to and
    including the first position where their tokens part. A parting is
    allowed only where the reference's two candidates lie within
    MAX_ABS of each other (a near tie that rounding may flip)."""
    n_cmp, worst_abs, worst_rel, parted = 0, 0.0, 0.0, 0
    for r, rr in zip(reqs, ref_reqs):
        p0 = len(r.prompt) - 1
        for j in range(MAX_NEW):
            key = (r.rid, p0 + j)
            got, want = logits[key], ref_logits[(rr.rid, p0 + j)]
            if not (np.isfinite(got).all() and np.isfinite(want).all()):
                raise AssertionError(f"{label}: non-finite logits at {key}")
            err = np.abs(got - want)
            rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
            worst_abs = max(worst_abs, float(err.max()))
            worst_rel = max(worst_rel, rel)
            n_cmp += 1
            if err.max() > MAX_ABS or rel > REL_L2:
                raise AssertionError(
                    f"{label}: request {r.rid} position {p0 + j}: max "
                    f"|dlogit| {err.max()} (limit {MAX_ABS}), rel L2 {rel} "
                    f"(limit {REL_L2})")
            a, b = r.generated[j], rr.generated[j]
            if a != b:
                gap = float(want[b] - want[a])
                if gap > MAX_ABS:
                    raise AssertionError(
                        f"{label}: request {r.rid} took token {a} at "
                        f"position {p0 + j}, reference {b}, whose logit "
                        f"is {gap} higher")
                parted += 1
                break
    log(f"{label}.logits_compared", f"{n_cmp} of {len(reqs) * MAX_NEW}")
    log(f"{label}.logits_max_abs_err", worst_abs)
    log(f"{label}.logits_max_rel_l2", worst_rel)
    log(f"{label}.requests_parted_on_near_tie", parted)


def serve(cfg, prompts, clock, *, seed: int, max_batch: int, max_seq: int):
    """Phases A and B and the reference, each passed to ``check_kernels``
    on its way out, while its engine is still alive."""
    model = build_model(cfg)
    t = time.perf_counter()
    params = model.init(jax.random.PRNGKey(seed))
    ready(params)
    log("init_params_s", time.perf_counter() - t)

    front = ServerlessFrontend({f"srv{i}": ServerSpec(f"srv{i}", 16 * Gbps,
                                                      12e9, 24 * GB)
                                for i in range(4)})
    t = time.perf_counter()
    front.deploy(cfg, params, ModelProfile(cfg.name, model.bytes(),
                                           TimingProfile(),
                                           SLO(ttft=7.5, tpot=0.2)))
    log("deploy_s", time.perf_counter() - t)
    del params                        # the store keeps the host copy

    watches = {}
    # ---- phase A: two-stage cold start, consolidated part-way
    t = time.perf_counter()
    ep = front.cold_start(cfg.name, min_stages=2, paged=True,
                          max_batch=max_batch, max_seq=max_seq)
    ready([(w.params, w.cache) for w in ep.engine.workers])
    log("A.cold_start_s", time.perf_counter() - t)
    if ep.n_stages != 2:
        raise AssertionError(f"cold start built {ep.n_stages} stages")
    for i, w in enumerate(ep.engine.workers):
        log(f"A.stage{i}_devices", devices_of(w))
    watches["A"] = StepWatch()
    watches["A"].watch("A", ep.engine)
    reqs_a = run_phase("A", ep, prompts, watches["A"], clock,
                       consolidate=lambda e: front.consolidate(e, cfg.name))
    if ep.n_stages != 1:
        raise AssertionError("phase A finished before it consolidated")
    log("A.migration_bytes", ep.last_migration_bytes)
    check_kernels(watches["A"])
    full = ep.engine.workers[0].params
    del ep
    gc.collect()

    # ---- phase B: one fused ragged-step engine
    eng = Engine(cfg, [full], max_batch=max_batch, max_seq=max_seq,
                 paged=True, fused=True)
    watches["B"] = StepWatch()
    watches["B"].watch("B", eng)
    reqs_b = run_phase("B", eng, prompts, watches["B"], clock)
    check_kernels(watches["B"])
    del eng
    gc.collect()

    # ---- reference: single worker, contiguous cache
    ref = Engine(cfg, [full], max_batch=max_batch, max_seq=max_seq,
                 paged=False)
    watches["ref"] = StepWatch()
    watches["ref"].watch("ref", ref)
    reqs_r = run_phase("ref", ref, prompts, watches["ref"], clock)
    check_kernels(watches["ref"])
    del ref
    gc.collect()

    ref_logits = watches["ref"].host_logits(cfg.vocab)
    for label, reqs in (("A", reqs_a), ("B", reqs_b)):
        compare(label, reqs, watches[label].host_logits(cfg.vocab),
                reqs_r, ref_logits)


def check_kernels(watch: StepWatch) -> None:
    """Every jitted serving step the phase compiled holds a Pallas
    kernel."""
    missing = []
    for label, shape, has_kernel in watch.kernel_report():
        log(f"compiled {label} {shape} tpu_custom_call", has_kernel)
        if not has_kernel:
            missing.append(label)
    if missing:
        raise AssertionError(f"steps without a Pallas kernel: {missing}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    seed = ap.parse_args().seed

    dev = jax.devices()[0]
    log("devices", jax.devices())
    log("device_kind", dev.device_kind)
    if dev.platform != "tpu":
        sys.exit(f"no TPU: JAX found {dev.platform}; this smoke runs only "
                 "on the chip")
    log("kernel_backend", ops.backend())
    if ops.backend() != "pallas":
        sys.exit(f"kernel backend {ops.backend()!r}: want 'pallas'")
    log("compile_cache_dir", enable_compile_cache())

    cfg = dataclasses.replace(get_config("granite-3-8b"), n_layers=N_LAYERS)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, int(n)).tolist()
               for n in rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1,
                                     N_REQUESTS)]
    log("prompt_lens", [len(p) for p in prompts])

    clock = CompileClock()
    t0 = time.perf_counter()
    serve(cfg, prompts, clock, seed=seed, max_batch=N_REQUESTS,
          max_seq=MAX_SEQ)
    log("total_s", time.perf_counter() - t0)
    log("compile_total", clock.totals)
    mem = dev.memory_stats()
    log("peak_bytes_in_use", mem["peak_bytes_in_use"])
    log("bytes_limit", mem["bytes_limit"])

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
