"""The benchmark: one cell of ``BENCHMARK.json`` on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything is found by name: the cell's configuration file, its traffic
mix (``bench/traffic/<traffic>.json``), the mix's driver
(``bench/drivers/<driver>.py``), the configuration's plain reference
(``bench/references/<reference>.py``) and one reader per metric
(``bench/metrics/<metric>.py``). Set-up makes the weights from the seed,
deploys the model and warms every shape the window uses; the window
measures for ``--seconds``; then the program's state is freed and the
reference checks a sample of what the window served. The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or its per-layer
metrics with ``--trace 1``), ``device`` and, traced, ``breakdown``; the
numbers compared, each with its limit, come last under ``checks`` and
as the last lines of standard error.

It refuses to run, and prints no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
# The persistent compilation cache lives inside the checkout, at a fixed
# path, whatever the environment says: two checkouts share nothing.
CACHE = ROOT / ".jax_cache"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def log(key, value) -> None:
    print(f"{key}: {value}", file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(workload: str):
    """(cell, configuration dict, mix dict, metric specs) by name."""
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = load_json(ROOT / conf["file"])
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return (cell, cfg, mix, [m for m in spec["end_to_end"] if applies(m)],
            [m for m in spec["per_layer"] if applies(m)])


def enable_cache() -> str:
    """The program's persistent compilation cache, in the checkout; every
    executable the warm-up builds is kept, so a later run of the checkout
    loads what the first one compiled."""
    import jax

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()          # checkout-relative op locations
    os.makedirs(CACHE, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return str(CACHE)


class Tracer:
    """The JAX profiler over a bounded span the driver chooses."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.on = False

    def start(self):
        import jax
        jax.profiler.start_trace(self.dir)
        self.on = True

    def stop(self):
        import jax
        jax.profiler.stop_trace()
        self.on = False

    def close(self):
        if self.on:
            self.stop()
        shutil.rmtree(self.dir, ignore_errors=True)


def reduce_trace(tracer: Tracer, n_steps: int):
    """Device events, host events and the span of the traced steps."""
    from bench.lib import trace as tr
    t = tr.load(tr.find_xplane(tracer.dir))
    steps = tr.spans(t, "bench.step")
    events = tr.device_events(t)
    if not steps or not events or len(steps) != n_steps:
        log("trace", f"{len(steps)} step spans for {n_steps} traced steps, "
                     f"{len(events)} device events: nothing to read")
        return None
    lo, hi = steps[0].start, steps[-1].end
    return SimpleNamespace(events=events, host=t.host, lo=lo, hi=hi,
                           n_steps=n_steps,
                           busy_s=tr.busy_ns(events, lo, hi) * 1e-9,
                           window_s=(hi - lo) * 1e-9)


def breakdown(red) -> dict:
    from bench.lib import trace as tr
    ops = sorted(tr.time_by_name(tr.leaves(red.events), red.lo,
                                 red.hi).items(),
                 key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v * 1e-9] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in
                          tr.idle_gaps(red.events, red.host, red.lo,
                                       red.hi)]}


def execute(cfg: dict, mix: dict, *, seed: int, seconds: float,
            trace: bool, chips: int, end_to_end, per_layer,
            require_chip: bool = True) -> dict:
    """One run; returns the result object (the harness prints it).
    ``require_chip=False`` (the CPU tests) skips the look for a chip, the
    persistent compilation cache and the table of peaks."""
    import jax

    from bench.lib import check, peaks
    from bench.lib.clock import CompileClock

    devices = jax.devices()
    dev = devices[0]
    log("devices", devices)
    if require_chip and (dev.platform != "tpu" or len(devices) < chips):
        raise SystemExit(f"want {chips} TPU chip(s); JAX found "
                         f"{len(devices)} {dev.platform} device(s)")
    from repro.kernels import ops
    if require_chip:
        log("compile_cache_dir", enable_cache())
    if require_chip and ops.backend() != "pallas":
        raise SystemExit(f"kernel backend {ops.backend()!r}: want 'pallas'")
    peak = peaks.peaks(dev.device_kind) if require_chip else None

    clock = CompileClock()
    drv_mod = check.load_module(BENCH / "drivers" / f"{mix['driver']}.py",
                                f"bench_driver_{mix['driver']}")
    driver = drv_mod.Driver(cfg, mix, seed, clock, log)
    tracer = Tracer() if trace else None
    driver.tracer = tracer
    try:
        driver.setup()
        setup_s = time.perf_counter() - T0
        log("setup_s", setup_s)
        t = time.perf_counter()
        cycles = driver.window(seconds)
        log("window_s", time.perf_counter() - t)
        red = reduce_trace(tracer, len(driver.traced_steps)) \
            if trace else None
    finally:
        if tracer is not None:
            tracer.close()
    stats = dev.memory_stats() or {}
    memory_peak = stats.get("peak_bytes_in_use", 0)
    traced_steps = driver.traced_steps
    driver.free()
    del driver
    gc.collect()

    requests = [dict(r, arrival=c.arrival) for c in cycles
                for r in c.requests]
    picked = check.sample(requests, seed, mix["sample"]["requests"])
    t = time.perf_counter()
    read = check.readings(cfg, mix, seed, picked) if picked else {}
    log("reference_s", time.perf_counter() - t)
    checks = {name: {"value": read.get(name, float("inf")),
                     "limit": spec["limit"]}
              for name, spec in cfg["checks"].items()}
    correct = bool(picked) and all(
        c["value"] <= c["limit"] for c in checks.values())
    log("tokens_compared", read.get("tokens_compared", 0))

    run = SimpleNamespace(cfg=cfg, mix=mix, cycles=cycles,
                          requests=requests, setup_s=setup_s, peak=peak,
                          trace=red, traced_steps=traced_steps)
    metrics = {}
    for m in (per_layer if trace else end_to_end):
        if m["name"] == "setup_s":
            value = setup_s
        else:
            reader = check.load_module(BENCH / "metrics" / f"{m['name']}.py",
                                       f"bench_metric_{m['name']}")
            value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(requests),
              "failed": sum(not r["ok"] for r in requests),
              "metrics": metrics, "device": device}
    if trace and red is not None:
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["breakdown"] = breakdown(red)
    result["checks"] = checks
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args()
    cell, cfg, mix, e2e, layer = resolve(a.workload)
    log("cell", json.dumps(cell))
    result = execute(cfg, mix, seed=a.seed, seconds=a.seconds,
                     trace=bool(a.trace), chips=cell["chips"],
                     end_to_end=e2e, per_layer=layer)
    for name, c in result["checks"].items():
        log("check", f"{name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
