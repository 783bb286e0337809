"""Driver of the ``coldburst`` mixes: scale-to-zero, then a burst.

Each cycle: the model is at zero; a burst arrives at once (the arrival
is taken before ``cold_start``); the frontend cold-starts the model from
its host-resident store as a pipeline; the burst is submitted and
stepped; after a fixed number of steps the pipeline is consolidated onto
one worker; once every request has finished the endpoint is dropped,
which scales the model to zero. The next cycle begins at once. Set-up
deploys the model and runs one warm-up cycle; the window runs cycles
while it lasts and lets the last one finish.

After every scale-to-zero the live device bytes must be back where they
stood before that cycle's cold start; a cycle that leaves bytes behind
fails its requests, since the next "cold" start would find them.
"""

from __future__ import annotations

import gc
import time
from typing import Dict, List, Optional

import jax

from bench.lib import program, traffic
from bench.lib.clock import build_seconds, live_device_bytes, ready


class Cycle:
    """What one cycle measured, on the host clock."""

    def __init__(self, index: int, arrival: float):
        self.index = index
        self.arrival = arrival
        self.load_s: Optional[float] = None
        self.build: Optional[dict] = None
        self.consolidate_s: Optional[float] = None
        self.requests: List[dict] = []       # prompt, generated, times, ok
        self.leaked_bytes = 0
        self.n_stages = 0
        self.steps = 0

    @property
    def build_s(self) -> Optional[float]:
        return None if self.build is None else build_seconds(self.build)

    def summary(self) -> dict:
        return {"cycle": self.index, "stages": self.n_stages,
                "steps": self.steps, "load_s": self.load_s,
                "build_s": self.build_s, "build": self.build,
                "consolidate_s": self.consolidate_s,
                "requests": len(self.requests),
                "failed": sum(not r["ok"] for r in self.requests),
                "leaked_bytes": self.leaked_bytes}


class Driver:
    def __init__(self, cfg: dict, mix: dict, seed: int, clock, log):
        self.cfg = cfg
        self.mix = mix
        self.seed = seed
        self.clock = clock
        self.log = log
        self.front = None
        self.name = cfg["name"]
        self.tracer = None            # set by the harness for --trace 1
        self.traced_steps: List[List[int]] = []

    # ------------------------------------------------------------ set-up
    def setup(self, warmup: bool = True) -> None:
        from repro.core import GB, SLO, Gbps, ModelProfile, ServerSpec
        from repro.core import TimingProfile
        from repro.serving.endpoint import ServerlessFrontend

        t = time.perf_counter()
        model, params = program.served_params(self.cfg, self.seed)
        ready(params)
        self.log("init_params_s", time.perf_counter() - t)
        cl = self.mix["cluster"]
        self.front = ServerlessFrontend({
            f"srv{i}": ServerSpec(f"srv{i}", cl["nic_gbps"] * Gbps,
                                  cl["pcie_bytes_per_s"], cl["hbm_gb"] * GB)
            for i in range(cl["servers"])})
        slo = self.mix["slo"]
        t = time.perf_counter()
        self.front.deploy(program.model_config(self.cfg), params,
                          ModelProfile(self.name, model.bytes(),
                                       TimingProfile(),
                                       SLO(ttft=slo["ttft_s"],
                                           tpot=slo["tpot_s"])))
        del params
        gc.collect()
        self.log("deploy_s", time.perf_counter() - t)
        if not warmup:
            return
        warm = self.cycle(-1, traffic.warmup_burst(self.mix,
                                                  self.cfg["vocab_size"]))
        self.log("warmup_cycle", warm.summary())

    # ------------------------------------------------------------ window
    def window(self, seconds: float) -> List[Cycle]:
        cycles, t0, i = [], time.perf_counter(), 0
        while time.perf_counter() - t0 < seconds:
            reqs = traffic.burst(self.mix, self.cfg["vocab_size"],
                                 self.seed, i)
            c = self.cycle(i, reqs, trace=(i == 0))
            self.log(f"cycle{i}", c.summary())
            cycles.append(c)
            i += 1
        return cycles

    def cycle(self, index: int, reqs, trace: bool = False) -> Cycle:
        from repro.serving.api import FinishReason, SamplingParams
        ann = jax.profiler.TraceAnnotation
        base = live_device_bytes()
        c = Cycle(index, time.perf_counter())
        with ann("bench.cold_start"):
            ep = self.front.cold_start(self.name, **self.mix["cold_start"])
            ready([(w.params, w.cache) for w in ep.engine.workers])
        c.load_s = time.perf_counter() - c.arrival
        c.n_stages = ep.n_stages
        if ep.n_stages != self.mix["cold_start"]["min_stages"]:
            raise RuntimeError(f"cold start built {ep.n_stages} stages")
        handles = [ep.submit(r.prompt, SamplingParams(max_new=r.max_new))
                   for r in reqs]
        times: Dict[int, List[float]] = {h.rid: [] for h in handles}
        after = self.mix["consolidate_after_steps"]
        tracing = trace and self.tracer is not None
        t_on = after + self.mix["trace"]["skip_steps_after_consolidate"]
        t_off = t_on + self.mix["trace"]["steps"]
        n = 0
        while ep.has_work():
            if n == after:
                with ann("bench.consolidate"):
                    t = time.perf_counter()
                    self.front.consolidate(ep, self.name)
                    ready([(w.params, w.cache) for w in ep.engine.workers])
                    c.consolidate_s = time.perf_counter() - t
            if tracing and n == t_on:
                self.tracer.start()
            if tracing and t_on <= n < t_off:
                # context length of each request after this step
                self.traced_steps.append([r.pos_next + 1
                                          for r in ep.active()])
            before = self.clock.snapshot() if n == 0 else None
            with ann("bench.first_step" if n == 0 else "bench.step"):
                out = ep.step()
            now = time.perf_counter()
            if before is not None:
                c.build = self.clock.since(before)
            for ev in out.events:
                times[ev.rid].append(now)
            n += 1
            if tracing and n == t_off:
                self.tracer.stop()
        c.steps = n
        if c.consolidate_s is None:
            raise RuntimeError(f"cycle {index} ended after {n} steps, "
                               "before it consolidated")
        with ann("bench.scale_to_zero"):
            for h in handles:
                ok = (h.finish_reason is FinishReason.LENGTH
                      and len(h.generated) == h.params.max_new
                      and len(times[h.rid]) == h.params.max_new)
                c.requests.append({"prompt": list(h.prompt),
                                   "generated": list(h.generated),
                                   "times": times[h.rid], "ok": ok})
            del ep, handles, out
            gc.collect()
            c.leaked_bytes = live_device_bytes() - base
        if c.leaked_bytes > 0:
            for r in c.requests:
                r["ok"] = False
        return c

    def free(self) -> None:
        """Drop the deployment (the host store) once the window closed."""
        self.front = None
        gc.collect()
