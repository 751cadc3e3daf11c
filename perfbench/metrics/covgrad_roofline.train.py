"""The fused SNIS covariance-gradient kernels' share of their roofline
(%): the B*S sampled rows they read in the forward and again in the
backward pass, with the per-sample vectors, for every step of the
traced window, over their device time in the trace."""
from perfbench.harness import counts

OPS = r"snis|covgrad"


def read(ctx):
    if ctx.trace is None:
        return None
    j = ctx.job
    n = ctx.traced["steps"]
    args = (j["batch_size"], j["num_samples"], j["embed_dim"])
    return counts.roofline_share(n * counts.covgrad_flops(*args),
                                 n * counts.covgrad_bytes(*args),
                                 ctx.trace.op_seconds(OPS), ctx.peaks)
