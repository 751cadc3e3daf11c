"""The `ivf_topk` retrieval's share of its roofline in the training step
(%): the bytes and operations one query of the batch needs (centroids,
and the mean inverted list of each probe, not the padded capacity) for
every step of the traced window, over the device time of the
retrieval in the trace. Silent where the proposal skips retrieval."""
from perfbench.harness import counts

OPS = r"ivf_topk"


def read(ctx):
    j = ctx.job
    if ctx.trace is None or float(j["epsilon"]) >= 1.0:
        return None
    args = (j["batch_size"], j["embed_dim"], j["num_items"], j["num_clusters"], j["n_probe"])
    n = ctx.traced["steps"]
    return counts.roofline_share(
        n * counts.ivf_probe_flops(*args),
        n * counts.ivf_probe_bytes(*args, j["top_k"]),
        ctx.trace.op_seconds(OPS), ctx.peaks)
