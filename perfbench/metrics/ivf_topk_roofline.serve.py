"""The `ivf_topk` query's share of its roofline while serving (%): per
padded batch, the centroids and the mean inverted list of each probe of
each row, for every batch of the traced window, over the device time of
the query in the trace."""
from perfbench.harness import counts

OPS = r"ivf_topk"


def read(ctx):
    if ctx.trace is None:
        return None
    j = ctx.job
    n = ctx.traced["batches"]
    args = (ctx.traced["batch_rows"], j["embed_dim"], j["item_vocab"],
            j["num_clusters"], j["n_probe"])
    return counts.roofline_share(
        n * counts.ivf_probe_flops(*args),
        n * counts.ivf_probe_bytes(*args, j["top_k"]),
        ctx.trace.op_seconds(OPS), ctx.peaks)
