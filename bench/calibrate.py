"""Readings that the limit of a cell's comparison is set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ...

For each seed, in one process: the cell's weights and deployment, one
cycle of its traffic at the cell's own size and load (no warm-up, no
window: only what is compared), then the same comparison a run makes,
with the float8 control read beside it on the same prompts and served
tokens. Prints one JSON line per seed and a summary: the lower reading
(the largest ``max_logit_gap`` of the program) and the upper one (the
smallest ``control_max_logit_gap``). The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import run as harness  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args()
    cell, cfg, mix, _, _ = harness.resolve(a.workload)

    import jax

    from bench.lib import check, traffic
    from bench.lib.clock import CompileClock

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev.platform}")
    harness.enable_cache()
    drv_mod = check.load_module(
        harness.BENCH / "drivers" / f"{mix['driver']}.py", "bench_driver")
    clock = CompileClock()
    rows = []
    for seed in a.seeds:
        t = time.perf_counter()
        driver = drv_mod.Driver(cfg, mix, seed, clock, harness.log)
        driver.setup(warmup=False)
        c = driver.cycle(0, traffic.burst(mix, cfg["vocab_size"], seed, 0))
        driver.free()
        del driver
        gc.collect()
        picked = check.sample([dict(r, arrival=c.arrival)
                               for r in c.requests], seed,
                              mix["sample"]["requests"])
        r = check.readings(cfg, mix, seed, picked, control=True)
        r.update(seed=seed, failed=sum(not q["ok"] for q in c.requests),
                 leaked_bytes=c.leaked_bytes,
                 seconds=time.perf_counter() - t)
        rows.append(r)
        print(json.dumps(r), flush=True)
    print(json.dumps({
        "workload": a.workload,
        "lower": max(r["max_logit_gap"] for r in rows),
        "upper": min(r["control_max_logit_gap"] for r in rows),
        "program": [r["max_logit_gap"] for r in rows],
        "control": [r["control_max_logit_gap"] for r in rows]}),
        flush=True)


if __name__ == "__main__":
    main()
