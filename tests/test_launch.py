"""Entry-point plumbing: the compile-cache location, published-width
config selection, and the seeded balanced dataset the full-scale
launchers train on."""
from pathlib import Path

import numpy as np
import pytest

from repro.data import clustered_sessions
from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_compile_cache_dir_follows_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)


def test_compile_cache_dir_defaults_to_fixed_ignored_repo_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = Path(compile_cache.compile_cache_dir())
    assert path == REPO / ".jax_cache"
    assert path == Path(compile_cache.compile_cache_dir())  # stable, no pid/time
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


@pytest.mark.parametrize("scale", ["smoke", "full"])
def test_arch_config_scale_selects_published_or_smoke(scale):
    from repro.configs import get_arch
    from repro.launch.serve import make_parser as serve_parser
    from repro.launch.train import arch_config
    from repro.launch.train import make_parser as train_parser

    mod = get_arch("fopo-paper")
    want = mod.CONFIG if scale == "full" else mod.SMOKE_CONFIG
    assert arch_config(mod, scale) is want
    assert train_parser().parse_args(
        ["--arch", "fopo-paper", "--scale", scale]).scale == scale
    assert serve_parser().parse_args(
        ["--arch", "sasrec", "--scale", scale]).scale == scale


def test_clustered_sessions_is_balanced_seeded_and_rewarding():
    p, l, users, c = 4096, 16, 64, 16
    ds = clustered_sessions(p, l, users, num_clusters=c, num_positives=5, seed=3)
    again = clustered_sessions(p, l, users, num_clusters=c, num_positives=5, seed=3)
    np.testing.assert_array_equal(ds.item_embeddings, again.item_embeddings)
    np.testing.assert_array_equal(ds.positives, again.positives)
    assert ds.item_embeddings.shape == (p, l) and ds.contexts.shape == (users, l)
    assert ds.positives.shape == (users, 5) and ds.num_items == p
    assert ds.positives.min() >= 0 and ds.positives.max() < p
    # each user's targets sit in its own cluster: nearest to its context
    centers = ds.contexts / np.linalg.norm(ds.contexts, axis=1, keepdims=True)
    targets = ds.item_embeddings[ds.positives]  # [users, 5, L]
    cos = np.einsum("ul,ukl->uk", centers, targets) / np.linalg.norm(targets, axis=-1)
    assert np.median(cos) > 0.8
    # balanced: bucketing items by their nearest user context (each near
    # one cluster center) leaves no bucket far above P / C items
    nearest = np.argmax(ds.item_embeddings @ centers.T, axis=1)
    assert np.max(np.bincount(nearest)) < 3 * p / c


def test_fopo_paper_trainer_runs_the_fused_path_at_any_width():
    """Both launcher scales train through one path: fused covgrad
    kernels, the in-kernel sampler and ivf_pallas retrieval (interpret
    mode off a TPU), here at a toy width."""
    import dataclasses

    import jax

    from repro.configs import get_arch
    from repro.core.fopo import FOPOConfig
    from repro.launch.train import fopo_paper_trainer

    cfg = dataclasses.replace(
        get_arch("fopo-paper").SMOKE_CONFIG, num_items=512, embed_dim=8,
        batch_size=4,
        fopo=FOPOConfig(num_items=512, num_samples=16, top_k=8, epsilon=0.8),
    )
    trainer, train_ds, test_ds = fopo_paper_trainer(cfg, seed=1)
    fc = trainer.plan.cfg
    assert fc.fused and fc.fused_sampler and fc.retriever == "ivf_pallas"
    assert trainer.cfg.batch_size == 4 and trainer.beta.shape == (512, 8)
    before = jax.tree.map(np.asarray, trainer.params)
    losses = trainer.train(2)["loss"]
    assert np.all(np.isfinite(losses))
    moved = [np.max(np.abs(np.asarray(a) - b)) for a, b in
             zip(jax.tree.leaves(trainer.params), jax.tree.leaves(before))]
    assert max(moved) > 0.0
    assert np.isfinite(trainer.evaluate(test_ds))
