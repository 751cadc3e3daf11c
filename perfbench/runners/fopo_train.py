"""Runner for FOPO training cells.

Set-up builds one trainer on the fused path (Pallas covgrad kernels, the
in-kernel sampler, `ivf_topk` retrieval over a `build_ivf` index) from
inputs the configuration's reference makes from the seed, and drives
that same trainer through its first three steps with
`FOPOTrainer.train`, the call the window makes. Those steps compile the
step and are the steps the reference follows. The window then calls
`train` in chunks until ``seconds`` have passed; the step time is the
window over every step it completed.

Compared with the reference, after the window (each a gap of norms,
the program's against the reference's, over the reference's own):
  loss_gap    the three steps' losses, over the largest |reference loss|
  grad_gap    the first gradient as Adam received it (m / (1 - b1))
  update_gap  the parameters' change over the three steps
  index_faults  catalog items that no inverted list holds, plus items
              that two slots hold
  partition_gap  how far an item's list lies from its nearest centroid
              (`harness/partition.py`), over the reference's catalog rows
"""
from __future__ import annotations

import math

import numpy as np

from perfbench.harness import partition, session, traffic as gen
from perfbench.harness.session import clock, log

CHECK_STEPS = 3
ADAM_B1 = 0.9


def _norm(tree_leaf) -> float:
    return float(np.linalg.norm(np.asarray(tree_leaf, np.float64)))


def build(job: dict, ref, seed: int):
    """The trainer under test, its inputs and the initial parameters."""
    import jax
    import jax.numpy as jnp

    from repro.core.fopo import FOPOConfig
    from repro.data.synthetic import SessionDataset
    from repro.mips.ivf import build_ivf
    from repro.train import FOPOTrainer, TrainerConfig

    pseed = gen.program_seed(seed)
    items, contexts, positives, params0 = ref.make_inputs(job, gen.jax_key(seed))
    dataset = SessionDataset(
        contexts=np.asarray(contexts), positives=np.asarray(positives),
        item_embeddings=items, num_items=job["num_items"])
    index = build_ivf(jax.random.PRNGKey(pseed), items,
                      num_clusters=job["num_clusters"], cap_tile=job["cap_tile"])
    fopo = FOPOConfig(
        num_items=job["num_items"], num_samples=job["num_samples"],
        top_k=job["top_k"], epsilon=float(job["epsilon"]),
        retriever=job["retriever"], fused=True, fused_sampler=True,
        sample_tile=job["sample_tile"])
    trainer = FOPOTrainer(
        TrainerConfig(estimator="fopo", fopo=fopo, batch_size=job["batch_size"],
                      learning_rate=float(job["learning_rate"]),
                      checkpoint_every=0, seed=pseed),
        dataset,
        retriever_kwargs={"index": index, "cap_tile": job["cap_tile"],
                          "n_probe": job["n_probe"]})
    trainer.params = jax.tree.map(jnp.asarray, params0)
    trainer.opt_state = trainer.optimizer.init(trainer.params)
    return trainer, items, params0, pseed


def run(*, cell, config, traffic, ref, limits, seed, seconds, trace, t_start,
        devices, tamper=None):
    import jax

    job = dict(config)
    job.update(traffic.get("overrides", {}))
    trainer, items, params0, pseed = build(job, ref, seed)
    on_tpu = devices[0].platform == "tpu"
    fc = trainer.cfg.fopo
    if on_tpu and trainer.plan.interpret:
        raise RuntimeError("the plan resolved interpret mode on the chip")
    if not (fc.fused and fc.fused_sampler and fc.retriever == job["retriever"]):
        raise RuntimeError(f"the trainer does not run the fused path: {fc}")

    # the loader's first batches are the rows the reference follows
    fed: list[dict] = []
    next_batch = trainer.loader.next_batch

    def recording_next_batch():
        batch = next_batch()
        if len(fed) < CHECK_STEPS:
            fed.append({k: np.array(v) for k, v in batch.items()})
        return batch

    trainer.loader.next_batch = recording_next_batch
    if tamper is not None:
        tamper(trainer)
    losses = [float(x) for x in trainer.train(1)["loss"]]
    m1 = np.asarray(trainer.opt_state["m"]["w"])
    losses += [float(x) for x in trainer.train(CHECK_STEPS - 1)["loss"]]
    p3 = np.asarray(trainer.params["w"])
    index = trainer.index_state  # what the timed steps probe
    lists, centroids = np.asarray(index.lists), np.asarray(index.centroids)
    chunk = int(traffic["chunk_steps"])
    setup_s = clock() - t_start

    bad = 0
    steps = 0
    watch = session.CompileWatch()
    with session.quiet_gc(), watch.watching():
        t0 = clock()
        while True:
            hist = trainer.train(chunk)
            steps += chunk
            bad += sum(not math.isfinite(x) for x in hist["loss"])
            t1 = clock()
            if t1 - t0 >= seconds:
                break
    window_s = t1 - t0
    host = {"window_s": window_s, "steps": steps, "compiles": watch.count}
    log(f"window: {steps} steps in {window_s:.6f} s "
        f"({window_s / steps * 1e3:.6f} ms a step); programs traced or compiled "
        f"in the window: {watch.count}")

    traced = None
    reduction = None
    if trace:
        n = int(traffic["trace_steps"])
        with session.traced_window() as tw:
            for _ in range(n // chunk):
                with session.span("train_chunk"):
                    trainer.train(chunk)
        reduction = tw["reduction"]
        traced = {"steps": n // chunk * chunk}
    mem = session.memory_peak_bytes(devices)

    # free the program's state before the reference runs
    del trainer, index
    import gc

    gc.collect()
    keys = []
    k = jax.random.PRNGKey(pseed + 17)  # the trainer's documented key schedule
    for _ in range(CHECK_STEPS):
        k, sub = jax.random.split(k)
        keys.append(sub)
    t_ref = clock()
    ref_losses, ref_g1, ref_p3 = ref.reference_steps(
        job, items, params0, fed, keys, jax.numpy.asarray(lists),
        jax.numpy.asarray(centroids), precision="highest")
    log(f"reference: {CHECK_STEPS} steps in {clock() - t_ref:.3f} s")
    checks = compare(job, losses, m1 / (1 - ADAM_B1), p3, np.asarray(params0["w"]),
                     ref_losses, np.asarray(ref_g1["w"]), np.asarray(ref_p3["w"]),
                     items, lists, centroids, limits)
    return {
        "setup_s": setup_s, "host": host, "traced": traced,
        "reduction": reduction, "memory_peak_bytes": mem, "job": job,
        "attempted": steps, "failed": bad, "checks": checks,
    }


def gaps(losses, g1, p3, p0, ref_losses, ref_g1, ref_p3) -> dict:
    """The compared numbers of one run (see the module docstring)."""
    scale = max(abs(x) for x in ref_losses)
    loss_gap = max(abs(a - b) for a, b in zip(losses, ref_losses)) / max(scale, 1e-30)
    if scale == 0.0 and max(abs(a) for a in losses) == 0.0:
        loss_gap = 0.0
    g_ref = _norm(ref_g1)
    u_ref = _norm(ref_p3 - p0)
    return {
        "loss_gap": loss_gap,
        "grad_gap": abs(_norm(g1) - g_ref) / max(g_ref, 1e-30),
        "update_gap": abs(_norm(p3 - p0) - u_ref) / max(u_ref, 1e-30),
    }


def compare(job, losses, g1, p3, p0, ref_losses, ref_g1, ref_p3, items, lists, centroids,
            limits):
    checks = session.Checks()
    for name, value in gaps(losses, g1, p3, p0, ref_losses, ref_g1, ref_p3).items():
        checks.add(name, value, limits[name])
    checks.add("index_faults", session.index_faults(lists, job["num_items"]), 0)
    checks.add("partition_gap", partition.partition_gap(items, lists, centroids),
               limits["partition_gap"])
    log(f"losses program {losses} reference {ref_losses}")
    return checks

