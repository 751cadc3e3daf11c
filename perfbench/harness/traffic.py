"""One general generator for every traffic mix, driven by the mix's file.

A mix is data (`perfbench/traffic/<name>.json`); this module turns it
and a seed into the run's inputs. The work a run offers is the same for
every seed: the seed only orders a fixed set of gaps and sizes and
draws the ids. So seeds change which requests come when, not how much
work arrives, and two seeds spread no wider than two runs of one seed.
"""
from __future__ import annotations

import math

import numpy as np

# --seed may exceed 32 bits; the program's own seeds take 31
PROGRAM_SEED_MOD = 2**31 - 1


def program_seed(seed: int) -> int:
    """The seed handed to the program's own constructors (31 bits)."""
    return int(seed) % PROGRAM_SEED_MOD


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent numpy stream per purpose, from the full seed."""
    return np.random.default_rng([int(seed) & (2**64 - 1),
                                  int.from_bytes(stream.encode(), "little") % 2**63])


def jax_key(seed: int):
    """A JAX key carrying every bit of ``seed`` (PRNGKey keeps 32)."""
    import jax

    s = int(seed) & (2**64 - 1)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def open_loop_arrivals(seed: int, rate: float, seconds: float) -> np.ndarray:
    """Due times (s, from 0) of an open-loop Poisson stream at ``rate``
    over ``seconds``: exactly round(rate * seconds) arrivals whose gaps
    are the exponential distribution's quantiles, in an order drawn from
    the seed. Sorted, float64."""
    n = int(round(rate * seconds))
    if n < 1:
        raise ValueError(f"rate {rate} over {seconds} s offers no request")
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps = gaps[rng(seed, "arrivals").permutation(n)]
    return np.cumsum(gaps) - gaps[0]


def history_lengths(seed: int, n: int, lo: int, mean: float, hi: int) -> np.ndarray:
    """``n`` history lengths, seed-ordered: ``lo`` plus a geometric count
    of mean ``mean - lo``, cut at ``hi`` (a longer history keeps its
    last ``hi`` items), taken at the distribution's evenly spaced
    quantiles so every seed gets the same set of lengths."""
    if not lo <= mean <= hi:
        raise ValueError(f"history mean {mean} outside [{lo}, {hi}]")
    q = (np.arange(n) + 0.5) / n
    extra = np.zeros(n) if mean == lo else np.floor(
        np.log1p(-q) / np.log1p(-1.0 / (mean - lo + 1.0)))
    lengths = np.minimum(lo + extra.astype(np.int64), hi)
    return lengths[rng(seed, "lengths").permutation(n)]


def histories(seed: int, n: int, seq_len: int, vocab: int, lo: int, mean: float,
              hi: int) -> np.ndarray:
    """[n, seq_len] int32 item histories, right-padded with -1 after
    each request's length; item ids uniform over the catalog."""
    lengths = history_lengths(seed, n, lo, mean, hi)
    ids = rng(seed, "items").integers(0, vocab, (n, seq_len), dtype=np.int64)
    live = np.arange(seq_len)[None, :] < lengths[:, None]
    return np.where(live, ids, -1).astype(np.int32)


def p95(values) -> float:
    """The 95th percentile by the nearest-rank rule: the smallest value
    with at least 95 % of the values at or below it."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if v.size == 0:
        raise ValueError("p95 of nothing")
    return float(v[max(0, math.ceil(0.95 * v.size) - 1)])
