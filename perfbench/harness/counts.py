"""Operations and bytes that the algorithm needs, worked out from shapes.

These count the work the method asks for, not what today's
implementation happens to do: an IVF probe reads the *mean* inverted
list (P / C items), not the padded list capacity, and recomputation or
padding is never counted. A later change that removes padding then
shows as a higher roofline share. f32 is 4 bytes; ids are int32.
"""
from __future__ import annotations

F32 = 4
I32 = 4


def ivf_probe_bytes(b: int, l: int, p: int, c: int, n_probe: int, k: int) -> float:
    """HBM bytes of one IVF top-k query of ``b`` rows: the centroid
    table, the queries, the ids and embeddings of ``n_probe`` mean-size
    lists per row, and the (score, id) outputs."""
    mean_list = p / c
    return (c * l * F32 + b * l * F32
            + b * n_probe * mean_list * (l * F32 + I32)
            + b * k * (F32 + I32))


def ivf_probe_flops(b: int, l: int, p: int, c: int, n_probe: int) -> float:
    """Centroid scores plus the scores of the probed mean-size lists."""
    return 2.0 * b * c * l + 2.0 * b * n_probe * (p / c) * l


def covgrad_bytes(b: int, s: int, l: int) -> float:
    """The fused SNIS covariance-gradient kernels of one step. Forward:
    the B*S sampled catalog rows, the per-sample ids, log-q and rewards,
    the queries, and the scores out. Backward: the rows again, the ids
    and score gradients, and the [B, L] gradient out."""
    rows = b * s * l * F32
    fwd = rows + b * s * (I32 + F32 + F32) + b * l * F32 + b * s * F32
    bwd = rows + b * s * (I32 + F32) + b * l * F32
    return fwd + bwd


def covgrad_flops(b: int, s: int, l: int) -> float:
    """Sample scores forward, the weighted row sum backward."""
    return 2.0 * 2.0 * b * s * l


def linear_tower_flops(b: int, l: int) -> float:
    """h = x W forward and dL/dW backward (the loss needs no dL/dx)."""
    return 2.0 * 2.0 * b * l * l


def fopo_step_flops(b: int, l: int, p: int, s: int, c: int, n_probe: int,
                    retrieval: bool) -> float:
    """One FOPO training step: tower, retrieval (when the proposal uses
    it), SNIS forward and backward."""
    f = linear_tower_flops(b, l) + covgrad_flops(b, s, l)
    if retrieval:
        f += ivf_probe_flops(b, l, p, c, n_probe)
    return f


def sasrec_tower_flops(b: int, t: int, d: int, blocks: int) -> float:
    """SASRec forward over a padded [b, t] history: per block the q, k, v
    projections, the full t x t attention scores and weighted sum, and
    the two-layer feed-forward."""
    per_block = (3 * 2 * b * t * d * d          # q, k, v
                 + 2 * 2 * b * t * t * d        # scores and att @ v
                 + 2 * 2 * b * t * d * d)       # feed-forward
    return float(blocks * per_block)


def roofline_share(flops: float, nbytes: float, seconds: float, peaks: dict) -> float | None:
    """Percent of the roofline: the least time the chip could take for
    this work (operations at peak FLOP/s or bytes at peak bandwidth,
    whichever is longer) over the time it took. None without a time."""
    if not seconds or seconds <= 0.0:
        return None
    least = max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
