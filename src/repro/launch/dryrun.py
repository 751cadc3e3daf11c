import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract the roofline inputs.

MUST be run as a script/module so the XLA_FLAGS above land before jax
initialises its backends:

    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Results (memory analysis, cost analysis, per-kind collective bytes,
roofline terms) are appended incrementally to results/dryrun.json so
interrupted sweeps resume where they left off.
"""
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.configs import ARCH_IDS, get_arch  # noqa: E402
from repro.launch import costs, jaxpr_cost  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import build_program, to_named  # noqa: E402

RESULTS = os.environ.get(
    "DRYRUN_RESULTS",
    os.path.join(os.path.dirname(__file__), "../../../results/dryrun.json"),
)


def run_cell(arch_id: str, shape_name: str, *, multi_pod: bool, opt: bool = False) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    prog = build_program(arch_id, shape_name, multi_pod=multi_pod, opt=opt)
    t0 = time.time()
    jitted = jax.jit(
        prog.fn,
        in_shardings=to_named(mesh, prog.in_specs),
        out_shardings=to_named(mesh, prog.out_specs)
        if prog.out_specs is not None
        else None,
        donate_argnums=prog.donate_argnums,
    )
    with jax.set_mesh(mesh):
        lowered = jitted.lower(*prog.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        # jaxpr-level global costs (trip-count-aware — compiled.cost_analysis
        # counts while bodies once and is per-device; see jaxpr_cost
        # docstring). Traced inside the mesh context: shard_map cells need it.
        jc = jaxpr_cost.analyze(prog.fn, *prog.args)

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # jax<=0.4: one properties dict per module
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    coll = costs.collective_bytes(hlo, prog.loop_trips)
    hlo_flops = jc["flops"]
    hlo_bytes = jc["bytes"]
    # cross-check numbers straight from the compiled artifact (per-device)
    xla_flops_pd = float(cost.get("flops", 0.0))
    xla_bytes_pd = float(cost.get("bytes accessed", 0.0))

    terms = costs.roofline_terms(hlo_flops, hlo_bytes, coll["total"], chips)
    result = {
        "arch": arch_id,
        "shape": shape_name,
        "mesh": "multipod_2x16x16" if multi_pod else "pod_16x16",
        "variant": "opt" if opt else "baseline",
        "chips": chips,
        "ok": True,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
        "hlo_flops": hlo_flops,
        "hlo_bytes_accessed": hlo_bytes,
        "xla_per_device_flops_scan_undercounted": xla_flops_pd,
        "xla_per_device_bytes_scan_undercounted": xla_bytes_pd,
        "collective_bytes": {
            k: v for k, v in coll.items() if k not in ("counts", "by_depth")
        },
        "collective_counts": coll["counts"],
        "collective_by_depth": coll["by_depth"],
        "loop_trips": list(prog.loop_trips),
        "model_flops": prog.model_flops,
        "useful_flops_ratio": (prog.model_flops / hlo_flops) if hlo_flops else None,
        "roofline": terms,
        "note": prog.note,
    }
    return result


def load_results() -> list:
    if os.path.exists(RESULTS):
        with open(RESULTS) as f:
            return json.load(f)
    return []


def save_results(rows: list) -> None:
    os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
    with open(RESULTS, "w") as f:
        json.dump(rows, f, indent=1)


def key_of(row) -> tuple:
    return (row["arch"], row["shape"], row["mesh"])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"], default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--verbose", action="store_true")
    ap.add_argument("--opt", action="store_true",
                    help="build the §Perf optimized variant of the cell")
    args = ap.parse_args()

    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]
    todo = []
    if args.all:
        for arch_id in ARCH_IDS:
            if arch_id == "fopo-paper":
                continue
            mod = get_arch(arch_id)
            for shape_name in mod.SHAPES:
                for mp in meshes:
                    todo.append((arch_id, shape_name, mp))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        for mp in meshes:
            todo.append((args.arch, args.shape, mp))

    rows = load_results()
    done = {key_of(r) for r in rows if r.get("ok") or r.get("skipped")}

    for arch_id, shape_name, mp in todo:
        mesh_name = "multipod_2x16x16" if mp else "pod_16x16"
        k = (arch_id, shape_name, mesh_name)
        if k in done and not args.force:
            print(f"[skip-cached] {k}")
            continue
        mod = get_arch(arch_id)
        reason = mod.SKIPPED_SHAPES.get(shape_name)
        if reason:
            print(f"[skipped] {k}: {reason}")
            rows = [r for r in rows if key_of(r) != k]
            rows.append(
                {"arch": arch_id, "shape": shape_name, "mesh": mesh_name,
                 "skipped": True, "reason": reason}
            )
            save_results(rows)
            continue
        print(f"[run] {k} opt={args.opt} ...", flush=True)
        try:
            res = run_cell(arch_id, shape_name, multi_pod=mp, opt=args.opt)
            rows = [r for r in rows if key_of(r) != k]
            rows.append(res)
            save_results(rows)
            r = res["roofline"]
            print(
                f"  ok: lower {res['lower_s']}s compile {res['compile_s']}s | "
                f"compute {r['compute_s']:.2e}s mem {r['memory_s']:.2e}s "
                f"coll {r['collective_s']:.2e}s -> {r['dominant']}",
                flush=True,
            )
        except Exception as e:  # noqa: BLE001 — record failures, keep sweeping
            print(f"  FAILED: {e}")
            if args.verbose:
                traceback.print_exc()
            rows = [r for r in rows if key_of(r) != k]
            rows.append(
                {"arch": arch_id, "shape": shape_name, "mesh": mesh_name,
                 "ok": False, "error": str(e)[:2000]}
            )
            save_results(rows)


if __name__ == "__main__":
    main()
