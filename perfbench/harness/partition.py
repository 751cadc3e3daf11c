"""The IVF partition, checked against the catalog's own rows.

IVF retrieval is defined by a partition: every catalog item sits in the
inverted list of the centroid nearest it under the L2 rule
argmax_c (x.c - |c|^2 / 2). The references probe the program's lists
through the program's centroids; this check holds the two to each other
with the reference's catalog rows, so centroids that do not match the
lists (stale, moved, wrong) fail the run instead of being followed.

  partition_gap  the widest margin, over the catalog, by which the
                 centroid of the list that holds an item scores below
                 the item's nearest centroid, over the largest |score|:
                 0 for the exact partition, rounding for a sound one
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.harness import precision as prec

BLOCK = 32768  # catalog rows a pass: [BLOCK, C] float32 scores


def owners(lists: np.ndarray, num_items: int) -> np.ndarray:
    """[num_items] int32: the list that holds each item, -1 where none
    does (`session.index_faults` counts those)."""
    lists = np.asarray(lists)
    owner = np.full(num_items, -1, np.int32)
    rows, cols = np.nonzero((lists >= 0) & (lists < num_items))
    owner[lists[rows, cols]] = rows
    return owner


def _blocks(items, *extra):
    p = items.shape[0]
    block = min(BLOCK, p)
    pad = -p % block
    valid = jnp.arange(p + pad) < p
    out = [jnp.pad(items, ((0, pad), (0, 0))).reshape(-1, block, items.shape[1]),
           valid.reshape(-1, block)]
    out += [jnp.pad(e, (0, pad)).reshape(-1, block) for e in extra]
    return out


def _scores(x, centroids, precision):
    half = 0.5 * jnp.sum(centroids * centroids, axis=-1)
    return prec.matmul(x, centroids.T, precision) - half[None, :]


@functools.partial(jax.jit, static_argnames=("precision",))
def _gap(items, owner, centroids, *, precision):
    xs, valid, own = _blocks(items, owner)

    def one(args):
        x, v, o = args
        s = _scores(x, centroids, precision)
        mine = jnp.take_along_axis(s, jnp.maximum(o, 0)[:, None], axis=1)[:, 0]
        gap = jnp.where(v & (o >= 0), jnp.max(s, axis=1) - mine, 0.0)
        scale = jnp.where(v, jnp.max(jnp.abs(s), axis=1), 0.0)
        return jnp.max(gap), jnp.max(scale)

    gaps, scales = jax.lax.map(one, (xs, valid, own))
    return jnp.max(gaps), jnp.max(scales)


@functools.partial(jax.jit, static_argnames=("precision",))
def _assign(items, centroids, *, precision):
    xs, _ = _blocks(items)
    nearest = jax.lax.map(
        lambda x: jnp.argmax(_scores(x, centroids, precision), axis=1).astype(jnp.int32), xs)
    return nearest.reshape(-1)[: items.shape[0]]


def partition_gap(items, lists, centroids, precision: str = "highest") -> float:
    """The compared number (module doc) of ``lists`` and ``centroids``
    over the catalog rows ``items``."""
    return owner_gap(items, owners(lists, np.shape(items)[0]), centroids, precision)


def owner_gap(items, owner, centroids, precision: str = "highest") -> float:
    """`partition_gap` of the partition that puts item i in list owner[i]."""
    gap, scale = _gap(jnp.asarray(items, jnp.float32), jnp.asarray(owner, jnp.int32),
                      jnp.asarray(centroids, jnp.float32), precision=precision)
    return float(gap) / max(float(scale), 1e-30)


def assign(items, centroids, precision: str) -> np.ndarray:
    """[P] the nearest centroid of each item, scored at ``precision``."""
    return np.asarray(_assign(jnp.asarray(items, jnp.float32),
                              jnp.asarray(centroids, jnp.float32), precision=precision))
