"""The whole training step's share of the chip's peak FLOP/s (%): the
operations the algorithm needs a step (tower forward and backward,
centroid and probed-list scores when the proposal retrieves, SNIS
forward and backward) times the steps a second of the measured window,
over the peak. Padding and recomputation do not count."""
from perfbench.harness import counts


def read(ctx):
    steps = ctx.host.get("steps")
    if not steps:
        return None
    j = ctx.job
    flops = counts.fopo_step_flops(
        j["batch_size"], j["embed_dim"], j["num_items"], j["num_samples"],
        j["num_clusters"], j["n_probe"], retrieval=float(j["epsilon"]) < 1.0)
    return 100.0 * flops * steps / ctx.host["window_s"] / ctx.peaks["flops_per_s"]
