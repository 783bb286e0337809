"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and dump roofline inputs.

    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-8b \
        --shape train_4k [--multi-pod] [--policy kvseq]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Results (memory analysis, cost analysis, collective bytes) are appended as
JSON lines under experiments/dryrun/. ``main`` asks XLA for 512 host
devices before JAX starts; importing this module changes nothing.
"""

import argparse
import json
import os
import time
import traceback

import jax

from repro.configs import SHAPES, applicable_shapes, get_config, list_configs
from repro.distributed.sharding import use_mesh
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import make_cell, rules_for
from repro.roofline import analysis

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             policy: str = "baseline", verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = 512 if multi_pod else 256
    from repro.kernels import ops as kops
    kops.set_attention_mode("causal_skip" if "skip" in policy
                            else "masked_full")
    kops.set_decode_mode("append" if "kvapp" in policy else "scatter")
    t0 = time.time()

    if "ppipe" in policy and shape.kind == "prefill":
        from repro.distributed import pp_spmd
        from repro.launch.mesh import make_pp_mesh
        assert pp_spmd.supports(cfg), f"{arch}: PP-SPMD unsupported"
        mesh = make_pp_mesh(4)
        mesh_name = "4x4x16(pp)"
        fn, args, in_sh, out_sh, donate = pp_spmd.make_pp_prefill(
            cfg, mesh, shape.global_batch, shape.seq_len)
    elif "manual" in policy and shape.kind == "prefill":
        from repro.distributed import manual_tp
        assert manual_tp.supports(cfg), f"{arch}: manual TP unsupported"
        fn, args, in_sh, out_sh, donate = manual_tp.make_manual_prefill(
            cfg, mesh, shape.global_batch, shape.seq_len)
    else:
        fn, args, in_sh, out_sh, donate = make_cell(cfg, shape, mesh,
                                                    policy=policy)
    with use_mesh(mesh, rules_for(shape, policy, cfg)):
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost_list = compiled.cost_analysis()
    cost = cost_list[0] if isinstance(cost_list, (list, tuple)) else cost_list
    hlo = compiled.as_text()
    roof = analysis.analyze(arch, shape, mesh_name, chips, cost, mem, hlo,
                            cfg, policy=policy)
    rec = roof.row()
    rec.update({
        "policy": policy,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "arg_bytes": getattr(mem, "argument_size_in_bytes", None),
        "out_bytes": getattr(mem, "output_size_in_bytes", None),
        "gen_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        "ok": True,
    })
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} on {mesh_name} "
              f"(policy={policy}): OK "
              f"compute={roof.compute_s*1e3:.2f}ms "
              f"memory={roof.memory_s*1e3:.2f}ms "
              f"collective={roof.collective_s*1e3:.2f}ms "
              f"dominant={roof.dominant} "
              f"mem/dev={rec['peak_mem_gb']:.2f}GiB "
              f"compile={t_compile:.1f}s")
        print(f"  memory_analysis: args={rec['arg_bytes']} "
              f"temps={rec['temp_bytes']} out={rec['out_bytes']}")
    return rec


def cells(multi_pod: bool):
    for arch, cfg in sorted(list_configs().items()):
        if arch in ("llama2-7b", "llama2-13b", "opt-6.7b"):
            continue                      # paper models: bench-only
        for shape in applicable_shapes(cfg):
            yield arch, shape.name


def main():
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--policy", default="baseline")
    ap.add_argument("--out", default=None)
    ap.add_argument("--resume", action="store_true",
                    help="skip cells already recorded")
    args = ap.parse_args()

    out_dir = args.out or os.path.abspath(OUT_DIR)
    os.makedirs(out_dir, exist_ok=True)
    mesh_name = "2x16x16" if args.multi_pod else "16x16"
    out_path = os.path.join(out_dir, f"{mesh_name}_{args.policy}.jsonl")

    done = set()
    if args.resume and os.path.exists(out_path):
        with open(out_path) as f:
            for line in f:
                r = json.loads(line)
                if r.get("ok"):
                    done.add((r["arch"], r["shape"]))

    todo = ([(args.arch, args.shape)] if not args.all
            else list(cells(args.multi_pod)))
    failures = []
    with open(out_path, "a") as f:
        for arch, shape in todo:
            if (arch, shape) in done:
                print(f"[dryrun] skip {arch} x {shape} (done)")
                continue
            try:
                rec = run_cell(arch, shape, args.multi_pod, args.policy)
            except (ValueError, TypeError, KeyError, NotImplementedError,
                    RuntimeError, MemoryError, OSError) as e:
                # a cell that fails to lower/compile is recorded and the
                # sweep continues; anything else (KeyboardInterrupt,
                # SystemExit, real bugs like NameError) propagates
                traceback.print_exc()
                rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                       "policy": args.policy, "ok": False, "error": str(e)}
                failures.append((arch, shape, str(e)))
            f.write(json.dumps(rec) + "\n")
            f.flush()
    if failures:
        print(f"[dryrun] FAILURES: {failures}")
        raise SystemExit(1)
    print("[dryrun] all cells compiled")


if __name__ == "__main__":
    main()
