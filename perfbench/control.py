"""Readings that set the upper end of each compared number's limit.

The control is the plain reference put in the program's place and
computed one precision below the configuration's (``high``, three
bfloat16 passes, for float32 at ``highest``). Beside it, the faults a
cell can have, planted in the reference put in the program's place:

  train  half_batch   every step on the first half of its batch
         (a step that returns its state unchanged reads update_gap 1
         by definition and needs no run)
  serve  altered      one returned id of every answer replaced
  both   centroids_rolled  the centroid table rolled by one list
         against the list table, as a relabelling that reaches one
         table and not the other would leave them

The control's partition puts each item in its nearest centroid's list
as scored at ``high``.

Each reading is compared with the reference at ``highest`` by the same
numbers the benchmark compares. The benchmark's runs never run this;
it runs on the chip at a cell's own size,

    python3 perfbench/control.py --workload <name> --seeds 1 2 3

and, at a small size on the CPU, as a test under perfbench/tests.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from perfbench.harness import manifest as mf  # noqa: E402
from perfbench.harness import partition  # noqa: E402
from perfbench.harness import traffic as gen  # noqa: E402


def train_readings(config: dict, traffic: dict, ref, runner, seed: int) -> dict:
    """{"control": gaps, "half_batch": gaps, "centroids_rolled": ...} for
    one seed; the control's gaps include its partition's."""
    import jax

    job = dict(config)
    job.update(traffic.get("overrides", {}))
    trainer, items, params0, pseed = runner.build(job, ref, seed)
    batches = [trainer.loader.next_batch() for _ in range(runner.CHECK_STEPS)]
    index = trainer.plan.initial_index_state
    lists, cents = jax.numpy.asarray(index.lists), jax.numpy.asarray(index.centroids)
    del trainer, index
    k = jax.random.PRNGKey(pseed + 17)
    keys = []
    for _ in range(runner.CHECK_STEPS):
        k, sub = jax.random.split(k)
        keys.append(sub)
    p0 = np.asarray(params0["w"])

    def steps(precision, fault=None):
        losses, g1, p3 = ref.reference_steps(job, items, params0, batches, keys, lists,
                                             cents, precision=precision, fault=fault)
        return losses, np.asarray(g1["w"]), np.asarray(p3["w"])

    want = steps("highest")
    out = {}
    for name, got in (("control", steps("high")),
                      ("half_batch", steps("highest", "half_batch"))):
        out[name] = runner.gaps(got[0], got[1], got[2], p0, *want)
    out["control"]["partition_gap"], rolled = partition_readings(items, lists, cents)
    out["centroids_rolled"] = {"partition_gap": rolled}
    return out


def partition_readings(items, lists, cents) -> tuple[float, float]:
    """partition_gap of the control's partition, and of rolled centroids."""
    control = partition.owner_gap(items, partition.assign(items, cents, "high"), cents)
    rolled = partition.partition_gap(items, np.asarray(lists), np.roll(np.asarray(cents), 1, 0))
    return control, rolled


def serve_readings(config: dict, traffic: dict, ref, runner, seed: int,
                   requests: int = 256) -> dict:
    """{"control": ..., "altered": ..., "centroids_rolled": ...}:
    answer_err of answers computed at ``high`` (and partition_gap of the
    partition scored there), of answers with one id replaced, and
    partition_gap of rolled centroids."""
    import jax.numpy as jnp

    params, route, _, _ = runner.build(config, ref, seed)
    state = route.planner.index_state
    lists = jnp.asarray(np.concatenate([np.asarray(state.lists),
                                        np.asarray(state.delta_lists)], axis=1))
    cents = jnp.asarray(state.centroids)
    del route, state
    _, hists = runner.stream(config, traffic, seed, 0.0, "control", count=requests)
    k, v = config["top_k"], config["item_vocab"]
    items = params["items"]
    readings = {"control": {"answer_err": 0.0}, "altered": {"answer_err": 0.0}}
    rng = gen.rng(seed, "alter")
    for lo in range(0, requests, runner.REF_BLOCK):
        x = jnp.asarray(hists[lo: lo + runner.REF_BLOCK])
        h = ref.user_vector(params, x, heads=config["num_heads"], precision="highest")
        _, top = ref.ivf_topk(h, items, lists, cents, k=k, n_probe=config["n_probe"],
                              precision="highest")
        top = np.asarray(top)
        h_c = ref.user_vector(params, x, heads=config["num_heads"], precision="high")
        ids_c, sc_c = ref.ivf_topk(h_c, items, lists, cents, k=k,
                                   n_probe=config["n_probe"], precision="high")
        ids_f, sc_f = ref.ivf_topk(h, items, lists, cents, k=k,
                                   n_probe=config["n_probe"], precision="highest")
        ids_f = np.array(ids_f)
        ids_f[:, 0] = rng.integers(0, v, ids_f.shape[0])
        for name, ids, sc in (("control", np.asarray(ids_c), sc_c),
                              ("altered", ids_f, sc_f)):
            r = readings[name]
            r["answer_err"] = max(r["answer_err"], runner.answer_err(
                ref, h, items, ids, np.asarray(sc), top, v))
    readings["control"]["partition_gap"], rolled = partition_readings(items, lists, cents)
    readings["centroids_rolled"] = {"partition_gap": rolled}
    return readings


def readings(workload: str, seed: int, root: str = ROOT,
             config_override: dict | None = None,
             traffic_override: dict | None = None) -> dict:
    import jax

    manifest = mf.load_manifest(root)
    cell = mf.workload(manifest, workload)
    config, path = mf.config_file(manifest, cell, root)
    config.update(config_override or {})
    bench_dir = os.path.join(root, "perfbench")
    traffic = mf.traffic_file(cell, bench_dir)
    traffic.update(traffic_override or {})
    ref = mf.reference_module(config, path)
    runner = mf.runner_module(config, bench_dir)
    with jax.default_matmul_precision(config["matmul_precision"]):
        if config["runner"] == "fopo_train":
            return train_readings(config, traffic, ref, runner, seed)
        return serve_readings(config, traffic, ref, runner, seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Control and fault readings of a cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU; the readings are made at the cell's size on the chip",
              file=sys.stderr)
        return 3
    for seed in args.seeds:
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "readings": readings(args.workload, seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
