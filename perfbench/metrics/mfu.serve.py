"""Serving's share of the chip's peak FLOP/s (%): the SASRec tower over
each padded batch plus its IVF query (centroid and mean-list scores),
times the batches a second of the measured window, over the peak."""
from perfbench.harness import counts


def read(ctx):
    n = ctx.host.get("batches_in_window")
    if not n:
        return None
    j = ctx.job
    b = j["max_batch"]
    flops = (counts.sasrec_tower_flops(b, j["seq_len"], j["embed_dim"], j["num_blocks"])
             + counts.ivf_probe_flops(b, j["embed_dim"], j["item_vocab"],
                                      j["num_clusters"], j["n_probe"]))
    return 100.0 * flops * n / ctx.host["window_s"] / ctx.peaks["flops_per_s"]
