"""Find a serving configuration's knee once, on the chip: one process
builds the engine, then offers open-loop load at each rate for a few
seconds and reports what it answered, the backlog left at the close,
and the latency tail. The knee is the highest rate whose backlog does
not grow. The benchmark's cells then fix their rates from it; no
benchmark run searches.

    python3 perfbench/tools/sweep.py --config sasrec --rates 400 600 800 ...
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from perfbench.harness import manifest as mf  # noqa: E402
from perfbench.harness import session  # noqa: E402
from perfbench.harness.traffic import p95  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="sasrec")
    ap.add_argument("--traffic", default="saturated")
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 3
    m = mf.load_manifest()
    entry = next(c for c in m["configs"] if c["name"] == args.config)
    path = os.path.join(ROOT, entry["file"])
    config = mf.load_json(path)
    traffic = mf.load_json(os.path.join(mf.BENCH_DIR, "traffic", args.traffic + ".json"))
    ref = mf.reference_module(config, path)
    runner = mf.runner_module(config)
    with jax.default_matmul_precision(config["matmul_precision"]):
        _, _, policy, engine = runner.build(config, ref, args.seed)
        engine.warmup()
        runner.OpenLoop(engine, policy, *runner.stream(
            config, traffic, args.seed, 0.0, "warmup", count=40 * policy.max_batch)
        ).run(60.0, complete=True)
        for rate in args.rates:
            mix = dict(traffic, rate=rate)
            loop = runner.OpenLoop(engine, policy, *runner.stream(
                config, mix, args.seed, args.seconds, f"sweep{rate}"))
            with session.quiet_gc():
                t0 = loop.run(args.seconds)
            host = runner.summarize(loop, t0, args.seconds)
            backlog = host["due_in_window"] - host["answered_in_window"]
            lat = host["latency_s"][~np.isnan(host["latency_s"])]
            print(json.dumps({
                "rate": rate, "answered_per_s": host["answered_in_window"] / args.seconds,
                "backlog_at_close": int(backlog),
                "mean_rows": float(np.mean(host["batch_size"])),
                "p95_ms": p95(lat) * 1e3 if lat.size else None,
                "batch_ms_mean": float(np.mean(host["batch_total_s"]) * 1e3),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
