"""Plain reference of sasrec retrieval serving (SASRec, arXiv:1808.09781),
in jax.numpy and float32, importing nothing of the program.

The weights are made here from the seed, on the device, in one jitted
call, and handed to the program. One request:
  h = the final position's hidden state of the causal self-attention
      stack over the request's item history (right-padded with -1):
      items + positions, then per block RMSNorm -> one-head attention
      -> residual, RMSNorm -> ReLU feed-forward -> residual
  top-k = IVF probe of h over the index's partition: the n_probe lists
      with the best centroid scores, every item of them scored against
      this module's own item table
The index partition (list membership and centroids) is the one input
taken from the program's set-up, and the runner holds it to this
module's item table (`harness/partition.py`); every score is recomputed
here. The norm is RMSNorm with (1 + scale), as the program's tower has
it, where SASRec has LayerNorm (the configuration lists it as assumed).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.harness import precision as prec


@functools.partial(jax.jit, static_argnames=("v", "d", "t", "blocks"))
def _params(key, *, v, d, t, blocks):
    keys = jax.random.split(key, 2 + 6 * blocks)
    s = 1.0 / jnp.sqrt(float(d))
    params = {
        "items": jax.random.normal(keys[0], (v, d), jnp.float32) * s,
        "pos": jax.random.normal(keys[1], (t, d), jnp.float32) * 0.02,
        "blocks": [],
    }
    for i in range(blocks):
        k = keys[2 + 6 * i: 8 + 6 * i]
        params["blocks"].append({
            "wq": jax.random.normal(k[0], (d, d), jnp.float32) * s,
            "wk": jax.random.normal(k[1], (d, d), jnp.float32) * s,
            "wv": jax.random.normal(k[2], (d, d), jnp.float32) * s,
            "ffn": [
                {"w": jax.random.normal(k[3], (d, d), jnp.float32) * s,
                 "b": jnp.zeros((d,), jnp.float32)},
                {"w": jax.random.normal(k[4], (d, d), jnp.float32) * s,
                 "b": jnp.zeros((d,), jnp.float32)},
            ],
            "ln1": jnp.zeros((d,), jnp.float32),
            "ln2": jnp.zeros((d,), jnp.float32),
        })
    return params


def make_params(cfg: dict, key):
    return _params(key, v=cfg["item_vocab"], d=cfg["embed_dim"],
                   t=cfg["seq_len"], blocks=cfg["num_blocks"])


def _rms_norm(x, scale):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + 1e-6) * (1.0 + scale)


@functools.partial(jax.jit, static_argnames=("heads", "precision"))
def user_vector(params, hist, *, heads: int, precision: str):
    """hist [B, T] (-1 padded) -> h [B, D]."""
    mask = hist >= 0
    x = jnp.take(params["items"], jnp.maximum(hist, 0), axis=0) * mask[..., None]
    b, t, d = x.shape
    dh = d // heads
    h = x + params["pos"][None, :t]
    allowed = jnp.tril(jnp.ones((t, t), bool))[None, None] & mask[:, None, None, :]
    for blk in params["blocks"]:
        y = _rms_norm(h, blk["ln1"])
        q = prec.matmul(y, blk["wq"], precision).reshape(b, t, heads, dh)
        k = prec.matmul(y, blk["wk"], precision).reshape(b, t, heads, dh)
        v = prec.matmul(y, blk["wv"], precision).reshape(b, t, heads, dh)
        s = prec.einsum("bqhd,bkhd->bhqk", q, k, precision) / jnp.sqrt(float(dh))
        att = jax.nn.softmax(jnp.where(allowed, s, -1e30), axis=-1)
        h = h + prec.einsum("bhqk,bkhd->bqhd", att, v, precision).reshape(b, t, d)
        f1, f2 = blk["ffn"]
        y = _rms_norm(h, blk["ln2"])
        y = jax.nn.relu(prec.matmul(y, f1["w"], precision) + f1["b"])
        h = h + prec.matmul(y, f2["w"], precision) + f2["b"]
    last = jnp.maximum(jnp.sum(mask, axis=1) - 1, 0)
    return jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]


@functools.partial(jax.jit, static_argnames=("k", "n_probe", "precision"))
def ivf_topk(h, items, lists, centroids, *, k: int, n_probe: int, precision: str):
    """(ids [B, k], scores [B, k]) of h over the n_probe best lists."""
    cs = prec.matmul(h, centroids.T, precision)
    _, probe = jax.lax.top_k(cs, n_probe)
    ids = jnp.take(lists, probe, axis=0).reshape(h.shape[0], -1)
    rows = jnp.take(items, jnp.maximum(ids, 0), axis=0)
    scores = jnp.where(ids >= 0, prec.einsum("bd,bnd->bn", h, rows, precision),
                       -jnp.inf)
    top, pos = jax.lax.top_k(scores, k)
    return jnp.take_along_axis(ids, pos, axis=1), top


@functools.partial(jax.jit, static_argnames=("precision",))
def scores_of(h, items, ids, *, precision: str):
    """h . item for each id: [B, k]."""
    rows = jnp.take(items, jnp.maximum(ids, 0), axis=0)
    return prec.einsum("bd,bkd->bk", h, rows, precision)
