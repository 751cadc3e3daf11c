"""Training launcher: --arch <id> resolves a pool config and runs its
training step on the local device — at smoke scale by default
(``SMOKE_CONFIG``), or with ``--scale full`` at the arch's published
widths (``CONFIG``).

    PYTHONPATH=src python -m repro.launch.train --arch sasrec --steps 20
    PYTHONPATH=src python -m repro.launch.train --arch granite-8b --steps 5
    PYTHONPATH=src python -m repro.launch.train --arch fopo-paper --steps 200
    PYTHONPATH=src python -m repro.launch.train --arch fopo-paper --scale full --steps 5

fopo-paper trains on the fused main path at either scale — Pallas
covgrad kernels, the in-kernel sampler and `ivf_topk` retrieval over a
`build_ivf` index — with data made from ``--seed`` by
`clustered_sessions` (balanced catalog, seconds of host time).
``--scale full`` runs the paper's shapes (P=750,000, L=100, B=32,
S=1000, K=256); off a TPU the kernels run in interpret mode, which is
practical only at smoke widths.

The production path (256/512 chips) reuses the exact same step
functions through launch/specs.py — the dry-run proves those lower and
compile on the full meshes.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import adam

# users in the seeded fopo-paper dataset (contexts of the batches)
NUM_USERS = 2048


def arch_config(mod, scale: str):
    """The arch's ``CONFIG`` (published widths) or ``SMOKE_CONFIG``."""
    return mod.CONFIG if scale == "full" else mod.SMOKE_CONFIG


def _train_lm(cfg, steps: int) -> None:
    from repro.models import lm

    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    opt = adam(1e-3)
    step = jax.jit(lm.make_train_step(cfg, opt))
    st = opt.init(params)
    b, s = 4, 32
    rng = np.random.default_rng(0)
    for i in range(steps):
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s + 1)))
        t0 = time.perf_counter()
        params, st, loss = step(params, st, toks[:, :-1], toks[:, 1:])
        jax.block_until_ready(loss)
        print(f"step {i}: loss={float(loss):.4f} ({(time.perf_counter()-t0)*1e3:.0f} ms)")


def _train_gnn(cfg, steps: int) -> None:
    from repro.data import random_graph
    from repro.models import gnn

    g = random_graph(512, avg_degree=8, seed=0)
    d_feat = 16
    params = gnn.init_params(cfg, jax.random.PRNGKey(0), d_feat=d_feat)
    opt = adam(1e-3)
    step = jax.jit(gnn.make_train_step(cfg, opt))
    st = opt.init(params)
    rng = np.random.default_rng(0)
    feats = jnp.asarray(rng.normal(size=(512, d_feat)), jnp.float32)
    targets = jnp.asarray(rng.normal(size=(512, cfg.n_vars)), jnp.float32)
    src = jnp.asarray(g.indices % 512, jnp.int32)
    dst = jnp.asarray(np.repeat(np.arange(512), np.diff(g.indptr)), jnp.int32)
    mask = jnp.ones((512,))
    for i in range(steps):
        params, st, loss = step(params, st, feats, src, dst, targets, mask)
        print(f"step {i}: loss={float(loss):.4f}")


def _train_recsys(cfg, steps: int) -> None:
    from repro.models import recsys

    params = recsys.init_params(cfg, jax.random.PRNGKey(0))
    objective = "fopo" if cfg.kind == "sasrec" else "bce"
    opt = adam(1e-3)
    step = jax.jit(recsys.make_train_step(cfg, opt, objective=objective))
    st = opt.init(params)
    rng = np.random.default_rng(0)
    b = 64
    for i in range(steps):
        if cfg.kind == "wide_deep":
            batch = {
                "sparse": jnp.asarray(rng.integers(0, 10**6, (b, cfg.n_sparse))),
                "dense": jnp.asarray(rng.normal(size=(b, cfg.n_dense)), jnp.float32),
                "label": jnp.asarray(rng.random(b) < 0.3, jnp.float32),
            }
        elif objective == "fopo":
            batch = {
                "hist": jnp.asarray(rng.integers(-1, cfg.item_vocab, (b, cfg.seq_len))),
                "positives": jnp.asarray(rng.integers(0, cfg.item_vocab, (b, 4))),
            }
        else:
            batch = {
                "hist": jnp.asarray(rng.integers(-1, cfg.item_vocab, (b, cfg.seq_len))),
                "target": jnp.asarray(rng.integers(0, cfg.item_vocab, (b,))),
                "label": jnp.asarray(rng.random(b) < 0.3, jnp.float32),
            }
        params, st, loss = step(params, st, batch, jax.random.PRNGKey(i))
        print(f"step {i}: loss={float(loss):.5f} [{objective}]")


def fopo_paper_trainer(cfg, *, seed: int = 0):
    """The fopo-paper trainer on the fused main path: `clustered_sessions`
    data from ``seed``, a `build_ivf` index over the catalog, and
    ``cfg.fopo`` with retriever="ivf_pallas" plus the fused covgrad
    kernels and the in-kernel sampler. Returns (trainer, train_ds,
    test_ds)."""
    from repro.data import clustered_sessions
    from repro.mips.ivf import build_ivf
    from repro.train import FOPOTrainer, TrainerConfig

    index_key = jax.random.PRNGKey(seed)
    num_clusters = max(1, int(2 ** round(np.log2(np.sqrt(cfg.num_items)))))
    # 64 targets per session: at eps=0.8 the uniform arm alone lands ~0.07
    # of them per row, so every batch of 32 carries reward
    data = clustered_sessions(
        cfg.num_items, cfg.embed_dim, NUM_USERS,
        num_clusters=num_clusters, num_positives=64, seed=seed,
    )
    train_ds, test_ds = data.split(0.9, seed=seed)
    index = build_ivf(
        index_key, jnp.asarray(train_ds.item_embeddings),
        num_clusters=num_clusters, cap_tile=256,
    )
    fopo = dataclasses.replace(
        cfg.fopo, retriever="ivf_pallas", fused=True, fused_sampler=True
    )
    trainer = FOPOTrainer(
        TrainerConfig(
            estimator="fopo", fopo=fopo, batch_size=cfg.batch_size,
            learning_rate=cfg.learning_rate, checkpoint_every=0, seed=seed,
        ),
        train_ds,
        retriever_kwargs={"index": index, "cap_tile": 256},
    )
    return trainer, train_ds, test_ds


def _train_fopo_paper(cfg, steps: int, seed: int) -> None:
    tr, _, test_ds = fopo_paper_trainer(cfg, seed=seed)
    print(f"R_test before: {tr.evaluate(test_ds):.4f}")
    tr.train(steps, log_every=max(1, steps // 5))
    print(f"R_test after:  {tr.evaluate(test_ds):.4f}")


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--scale", choices=("smoke", "full"), default="smoke",
                    help="smoke: SMOKE_CONFIG; full: the published CONFIG")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main() -> None:
    args = make_parser().parse_args()
    enable_compile_cache()
    mod = get_arch(args.arch)
    cfg = arch_config(mod, args.scale)
    print(f"arch={args.arch} family={mod.FAMILY} ({args.scale} scale on "
          f"{jax.devices()[0].platform}; production mesh via launch/dryrun.py)")
    if mod.FAMILY == "lm":
        _train_lm(cfg, args.steps)
    elif mod.FAMILY == "gnn":
        _train_gnn(cfg, args.steps)
    elif mod.FAMILY == "recsys":
        _train_recsys(cfg, args.steps)
    else:
        _train_fopo_paper(cfg, args.steps, args.seed)


if __name__ == "__main__":
    main()
