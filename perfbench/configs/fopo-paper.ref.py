"""Plain reference of the fopo-paper training step (FOPO, arXiv:2208.05327,
Algorithm 1), in jax.numpy and float32, importing nothing of the program.

Inputs are made here from the seed, on the device, in one jitted call:
a catalog of C unit centers plus Gaussian noise (each item one center's
neighbour), user contexts drawn the same way, each user's completion
targets drawn from its own cluster, and the linear tower's initial
weights. The program receives these as its dataset and parameters.

One reference step, as the paper states it:
  h = x W                                  (linear tower, theta^T x)
  top-K = IVF probe of h over the index's partition: the n_probe lists
          with the best centroid scores, every item of them scored
          against this module's own catalog rows
  S draws from q = eps U(P) + (1 - eps) softmax(top-K scores) with the
          counter hash the fused sampler documents (a uniform draw
          instead when eps == 1)
  r = 1[a in the user's targets]
  wbar = softmax(h.beta_a - log q), c = wbar (r - sum wbar r)
  loss = -mean_b sum_s stop_grad(c) h.beta_a; Adam (0.9, 0.999, 1e-8)
The index partition (which item lies in which list, and the list
centroids) is the one input taken from the program's set-up: IVF
retrieval is defined relative to a partition, and the runner holds it
to this module's catalog (`harness/partition.py`). Every embedding and
score is recomputed here from this module's catalog.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.harness import precision as prec

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
# the fused sampler's documented counter hash (splitmix32 finaliser)
GOLDEN, MIX1, MIX2 = 0x9E3779B9, 0x21F0AAAD, 0x735A2D97


@functools.partial(jax.jit, static_argnames=("p", "l", "c", "n", "y"))
def _inputs(key, noise, *, p, l, c, n, y):
    k = jax.random.split(key, 7)
    scale = noise / jnp.sqrt(float(l))
    centers = jax.random.normal(k[0], (c, l), jnp.float32)
    centers = centers / jnp.linalg.norm(centers, axis=1, keepdims=True)
    assign = jax.random.randint(k[1], (p,), 0, c)
    items = centers[assign] + scale * jax.random.normal(k[2], (p, l), jnp.float32)
    user = jax.random.randint(k[3], (n,), 0, c)
    contexts = centers[user] + scale * jax.random.normal(k[4], (n, l), jnp.float32)
    order = jnp.argsort(assign, stable=True)
    counts = jnp.zeros((c,), jnp.int32).at[assign].add(1)
    starts = jnp.cumsum(counts) - counts
    size = counts[user][:, None]
    pick = starts[user][:, None] + jnp.floor(
        jax.random.uniform(k[5], (n, y)) * size).astype(jnp.int32)
    positives = jnp.where(size > 0, order[jnp.minimum(pick, p - 1)], -1)
    w0 = jax.random.normal(k[6], (l, l), jnp.float32) / jnp.sqrt(float(l))
    return items, contexts, positives.astype(jnp.int32), {"w": w0}


def make_inputs(job: dict, key):
    """(items [P, L], contexts [N, L], positives [N, Y], params) on the
    device, from ``key``."""
    return _inputs(key, float(job["cluster_noise"]), p=job["num_items"],
                   l=job["embed_dim"], c=job["num_clusters"],
                   n=job["num_users"], y=job["num_positives"])


def ivf_topk(h, items, lists, centroids, k, n_probe, precision):
    """Top-k of h over the n_probe best-scoring lists of the partition."""
    cs = prec.matmul(h, centroids.T, precision)
    _, probe = jax.lax.top_k(cs, n_probe)
    ids = jnp.take(lists, probe, axis=0).reshape(h.shape[0], -1)
    rows = jnp.take(items, jnp.maximum(ids, 0), axis=0)
    scores = prec.einsum("bl,bnl->bn", h, rows, precision)
    scores = jnp.where(ids >= 0, scores, -jnp.inf)
    top, pos = jax.lax.top_k(scores, k)
    return jnp.take_along_axis(ids, pos, axis=1), top


def _hash(seed, ctr):
    x = seed + ctr * jnp.uint32(GOLDEN)
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(MIX1)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(MIX2)
    return x ^ (x >> jnp.uint32(15))


def _unit(seed, ctr):
    bits = (_hash(seed, ctr) >> jnp.uint32(8)).astype(jnp.int32)
    return bits.astype(jnp.float32) * (1.0 / (1 << 24))


def mixture_sample(key, top_ids, top_scores, s, eps, p, sample_tile):
    """S draws per row from eps U(P) + (1 - eps) softmax(top scores):
    (actions [B, S], log q [B, S])."""
    b, k = top_ids.shape
    sp = -(-s // sample_tile) * sample_tile
    seed = jax.random.randint(key, (), 0, jnp.iinfo(jnp.int32).max,
                              dtype=jnp.int32).astype(jnp.uint32)
    pos = jnp.arange(s, dtype=jnp.int32)[None, :]
    row = jnp.arange(b, dtype=jnp.int32)[:, None]
    ctr0 = ((row * sp + pos) * (k + 2)).astype(jnp.uint32)
    u_arm = _unit(seed, ctr0)
    uniform = (_hash(seed, ctr0 + jnp.uint32(1)) % jnp.uint32(p)).astype(jnp.int32)
    ctr_g = ctr0[:, :, None] + jnp.uint32(2) + jnp.arange(k, dtype=jnp.uint32)
    gumbel = -jnp.log(-jnp.log(_unit(seed, ctr_g) + 1e-12) + 1e-12)
    slot = jnp.argmax(top_scores[:, None, :] + gumbel, axis=-1)
    kappa = jnp.take_along_axis(top_ids, slot, axis=1)
    actions = jnp.where(u_arm < eps, uniform, kappa)
    hit = actions[:, :, None] == top_ids[:, None, :]
    log_kappa = jnp.sum(jnp.where(hit, jax.nn.log_softmax(top_scores)[:, None, :],
                                  0.0), axis=-1)
    log_u = jnp.log(eps) - jnp.log(float(p))
    log_q = jnp.where(hit.any(-1),
                      jnp.logaddexp(log_u, jnp.log1p(-eps) + log_kappa), log_u)
    return actions, log_q


def _step_loss(w, x, items, actions, log_q, rewards, precision):
    h = prec.matmul(x, w, precision)
    rows = jnp.take(items, actions, axis=0)
    scores = prec.einsum("bl,bsl->bs", h, rows, precision)
    wbar = jax.nn.softmax(jax.lax.stop_gradient(scores) - log_q, axis=-1)
    rbar = jnp.sum(wbar * rewards, axis=-1, keepdims=True)
    coeff = jax.lax.stop_gradient(wbar * (rewards - rbar))
    return -jnp.mean(jnp.sum(coeff * scores, axis=-1))


def adam_step(w, g, m, v, t: int, lr: float):
    """One Adam step, in float32 throughout (the bias corrections too)."""
    b1, b2 = jnp.float32(ADAM_B1), jnp.float32(ADAM_B2)
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    tf = jnp.float32(t)
    mhat = m * (1.0 / (1.0 - b1 ** tf))
    vhat = v * (1.0 / (1.0 - b2 ** tf))
    return w - jnp.float32(lr) * mhat / (jnp.sqrt(vhat) + ADAM_EPS), m, v


def reference_steps(job: dict, items, params0, batches, keys, lists, centroids,
                    precision: str = "highest", fault: str | None = None):
    """Run len(batches) Adam steps from ``params0``. Returns (losses,
    first gradient, params after the last step). ``fault="half_batch"``
    drops the second half of every batch (a planted fault whose
    readings bound the check)."""
    eps = float(job["epsilon"])
    p, s, k = job["num_items"], job["num_samples"], job["top_k"]
    lr = float(job["learning_rate"])
    w = params0["w"]
    m = jnp.zeros_like(w)
    v = jnp.zeros_like(w)
    losses, g_first = [], None
    grad = jax.jit(jax.value_and_grad(_step_loss), static_argnums=(6,))
    for t, (batch, key) in enumerate(zip(batches, keys), start=1):
        x = jnp.asarray(batch["contexts"])
        targets = jnp.asarray(batch["positives"])
        if fault == "half_batch":
            x, targets = x[: len(x) // 2], targets[: len(targets) // 2]
        h = prec.matmul(x, w, precision)
        if eps >= 1.0:
            actions = jax.random.randint(key, (x.shape[0], s), 0, p, dtype=jnp.int32)
            log_q = jnp.full(actions.shape, -jnp.log(float(p)), jnp.float32)
        else:
            top_ids, top_scores = ivf_topk(h, items, lists, centroids, k,
                                           int(job["n_probe"]), precision)
            actions, log_q = mixture_sample(key, top_ids, top_scores, s, eps, p,
                                            int(job["sample_tile"]))
        rewards = (actions[:, :, None] == targets[:, None, :]).any(-1).astype(jnp.float32)
        loss, g = grad(w, x, items, actions, log_q, rewards, precision)
        losses.append(float(loss))
        if g_first is None:
            g_first = g
        w, m, v = adam_step(w, g, m, v, t, lr)
    return losses, {"w": g_first}, {"w": w}
