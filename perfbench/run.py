"""The benchmark's one command.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json, its configuration, traffic mix and
metric readers by name (`perfbench/harness/manifest.py`), runs the
configuration's runner on the chip JAX finds, and prints one JSON line
last on standard output:

    {"correct", "attempted", "failed", "metrics", "device",
     ["breakdown"], "checks"}

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a traced window that
follows the measured one. The numbers compared with the reference come
last, with their limits, on standard error and under "checks". Without
a TPU, with fewer chips than the cell asks for, or without the program,
it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from perfbench.harness import manifest as mf  # noqa: E402
from perfbench.harness.peaks import peaks_for  # noqa: E402

EXIT_NO_PROGRAM, EXIT_NO_CHIP = 2, 3


@dataclasses.dataclass
class Context:
    """What a metric reader sees."""

    cell: dict
    job: dict  # the configuration with the mix's overrides
    traffic: dict
    peaks: dict
    setup_s: float
    host: dict  # measurements of the untraced window
    traced: dict | None  # work done inside the traced window
    trace: object | None  # tracing.Reduction of the traced window


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_compile_cache(root: str) -> None:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    the environment names one; every program is cached, however fast it
    compiled, so a second run compiles nothing."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None, *, root: str = ROOT, require_chip: bool = True,
         config_override: dict | None = None, traffic_override: dict | None = None,
         peaks: dict | None = None, tamper=None, t_start: float | None = None) -> int:
    """Run one cell. The keyword arguments exist for the harness's own
    tests, which run a cell at a small size on the CPU."""
    args = parse(argv)
    manifest = mf.load_manifest(root)
    cell = mf.workload(manifest, args.workload)
    config, config_path = mf.config_file(manifest, cell, root)
    config.update(config_override or {})
    bench_dir = os.path.join(root, "perfbench")
    traffic = mf.traffic_file(cell, bench_dir)
    traffic.update(traffic_override or {})
    limits = mf.load_json(os.path.join(bench_dir, "limits", cell["name"] + ".json"))
    try:
        import jax

        import repro  # noqa: F401  (the program under test)
    except ImportError as e:
        print(f"perfbench: the program is not importable: {e}", file=sys.stderr)
        return EXIT_NO_PROGRAM
    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            print(f"perfbench: no TPU (JAX sees {devices[0].platform}); nothing run",
                  file=sys.stderr)
            return EXIT_NO_CHIP
        if len(devices) < cell["chips"]:
            print(f"perfbench: {cell['name']} needs {cell['chips']} chips, JAX sees "
                  f"{len(devices)}", file=sys.stderr)
            return EXIT_NO_CHIP
    kind = devices[0].device_kind
    peaks = peaks if peaks is not None else peaks_for(kind)
    enable_compile_cache(root)
    runner = mf.runner_module(config, bench_dir)
    ref = mf.reference_module(config, config_path)
    # the configuration's stated precision, for every program traced here
    with jax.default_matmul_precision(config["matmul_precision"]):
        out = runner.run(cell=cell, config=config, traffic=traffic, ref=ref,
                         limits=limits, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace),
                         t_start=T_START if t_start is None else t_start,
                         devices=devices[: cell["chips"]], tamper=tamper)
    ctx = Context(cell=cell, job=out["job"], traffic=traffic, peaks=peaks,
                  setup_s=out["setup_s"], host=out["host"], traced=out["traced"],
                  trace=out["reduction"])
    metrics = {}
    for m in mf.metrics_for(manifest, cell["name"], bool(args.trace)):
        value = mf.metric_reader(m["name"], bench_dir).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": out["checks"].correct, "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics, "device": device}
    if args.trace:
        red = out["reduction"]
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["breakdown"] = red.breakdown()
    result["correct"] = result["correct"] and result["failed"] == 0
    checks = dict(out["checks"].items)
    checks["failed"] = {"value": float(out["failed"]), "limit": 0.0}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
