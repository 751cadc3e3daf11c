"""MIPS substrate: exact / streaming / IVF agreement and recall."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.mips import build_ivf, ivf_query, kmeans, topk_exact, topk_streaming


@pytest.mark.parametrize("p,l,b,k,block", [(500, 16, 8, 32, 128), (2048, 32, 4, 64, 512), (1000, 8, 3, 100, 64)])
def test_streaming_equals_exact(p, l, b, k, block):
    kq, ki = jax.random.split(jax.random.PRNGKey(p))
    q = jax.random.normal(kq, (b, l))
    items = jax.random.normal(ki, (p, l))
    e = topk_exact(q, items, k)
    s = topk_streaming(q, items, k, block_items=block)
    np.testing.assert_allclose(np.asarray(e.scores), np.asarray(s.scores), rtol=1e-5)
    assert (np.sort(e.indices, -1) == np.sort(np.asarray(s.indices), -1)).all()


def test_kmeans_partitions_points():
    pts = jax.random.normal(jax.random.PRNGKey(0), (512, 8))
    centroids, assign = kmeans(jax.random.PRNGKey(1), pts, 16, iters=8)
    assert centroids.shape == (16, 8)
    assert assign.shape == (512,)
    assert (np.asarray(assign) >= 0).all() and (np.asarray(assign) < 16).all()
    # every point is assigned to its nearest centroid (L2)
    d = np.linalg.norm(np.asarray(pts)[:, None] - np.asarray(centroids)[None], axis=-1)
    np.testing.assert_array_equal(np.asarray(assign), d.argmin(-1))


def test_ivf_recall_increases_with_probes():
    kq, ki = jax.random.split(jax.random.PRNGKey(0))
    items = jax.random.normal(ki, (2000, 16))
    q = jax.random.normal(kq, (16, 16))
    index = build_ivf(jax.random.PRNGKey(2), items, num_clusters=32)
    exact = topk_exact(q, items, 32)

    def recall(n_probe):
        approx = ivf_query(index, q, 32, n_probe=n_probe)
        hits = 0
        for i in range(q.shape[0]):
            hits += len(
                set(np.asarray(approx.indices[i]).tolist())
                & set(np.asarray(exact.indices[i]).tolist())
            )
        return hits / (q.shape[0] * 32)

    r2, r8, r32 = recall(2), recall(8), recall(32)
    assert r2 <= r8 + 0.05 and r8 <= r32 + 1e-9
    assert r32 > 0.999  # probing all clusters == exact
    assert r8 > 0.5


def test_kmeans_clamps_excess_clusters():
    """num_clusters > P used to crash inside jax.random.choice
    (replace=False past the population); now it warns and clamps."""
    pts = jax.random.normal(jax.random.PRNGKey(0), (12, 4))
    with pytest.warns(UserWarning, match="clamping"):
        centroids, assign = kmeans(jax.random.PRNGKey(1), pts, 50, iters=2)
    assert centroids.shape == (12, 4)
    assert (np.asarray(assign) < 12).all()
    with pytest.warns(UserWarning, match="clamping"):
        index = build_ivf(jax.random.PRNGKey(2), pts, num_clusters=50)
    ids = np.asarray(index.lists)
    assert sorted(ids[ids >= 0].tolist()) == list(range(12))


def test_build_ivf_cap_overflow_warns_not_misbuckets():
    """On the derive-from-data path (cap given, num_clusters derived), a
    cap smaller than the largest cluster is clamped UP with a warning —
    never silently dropping items from the list."""
    items = jax.random.normal(jax.random.PRNGKey(0), (200, 8))
    with pytest.warns(UserWarning, match="clamping cap"):
        index = build_ivf(jax.random.PRNGKey(1), items, cap=2)
    ids = np.asarray(index.lists)
    assert sorted(ids[ids >= 0].tolist()) == list(range(200))


def test_build_ivf_static_path_jits_without_host_sync():
    """With BOTH num_clusters and cap passed, the build is fully
    traceable (zero host syncs — the whole thing jits); a too-small cap
    drops overflow ranks instead of clamping, and every id that IS kept
    is bucketed correctly."""
    items = jax.random.normal(jax.random.PRNGKey(0), (200, 8))
    build = jax.jit(
        lambda k, it: build_ivf(k, it, num_clusters=4, cap=2, kmeans_iters=4)
    )
    index = build(jax.random.PRNGKey(1), items)  # traces => no .item()
    assert index.lists.shape == (4, 2)
    ids = np.asarray(index.lists)
    kept = ids[ids >= 0]
    assert len(set(kept.tolist())) == len(kept)  # no duplicate ids
    # generous static cap keeps everything — parity with the eager path
    full = build_ivf(
        jax.random.PRNGKey(1), items, num_clusters=4, cap=256, kmeans_iters=4
    )
    fids = np.asarray(full.lists)
    assert sorted(fids[fids >= 0].tolist()) == list(range(200))


def test_build_ivf_index_build_span_waits_only_when_traced(monkeypatch):
    """`build_ivf` records one `index_build` span; it waits for the
    index's arrays only under an installed tracer (the span then times
    the device work), never without one, and never while jit traces it."""
    from repro.mips.ivf import IVFIndex
    from repro.obs.trace import Tracer, tracing

    waited = []
    block = jax.block_until_ready

    def counting_block(x):
        if isinstance(x, IVFIndex):
            waited.append(x)
        return block(x)

    monkeypatch.setattr(jax, "block_until_ready", counting_block)
    items = jax.random.normal(jax.random.PRNGKey(0), (200, 8))
    build_ivf(jax.random.PRNGKey(1), items, num_clusters=4, kmeans_iters=2)
    assert waited == []
    tr = Tracer()
    with tracing(tr):
        index = build_ivf(jax.random.PRNGKey(1), items, num_clusters=4,
                          kmeans_iters=2)
        jax.jit(lambda k, it: build_ivf(k, it, num_clusters=4, cap=64,
                                        kmeans_iters=2))(jax.random.PRNGKey(1), items)
    assert len(waited) == 1 and waited[0] is index
    assert [e["name"] for e in tr.events] == ["index_build", "index_build"]


def test_build_ivf_cap_tile_alignment():
    items = jax.random.normal(jax.random.PRNGKey(0), (300, 8))
    index = build_ivf(jax.random.PRNGKey(1), items, num_clusters=8, cap_tile=48)
    assert index.lists.shape[1] % 48 == 0
    assert index.list_embs.shape[:2] == index.lists.shape


def test_kmeanspp_balances_clustered_catalog():
    """On a tightly clustered catalog, D^2 seeding must not let one
    centroid snowball the unclaimed mass (the random-init failure mode
    that blew the padded cap — and every probe's cost — up ~16x)."""
    c_true, per, l = 32, 32, 8
    kc, kn = jax.random.split(jax.random.PRNGKey(0))
    centers = jax.random.normal(kc, (c_true, l))
    items = (
        jnp.repeat(centers, per, axis=0)
        + 0.05 * jax.random.normal(kn, (c_true * per, l))
    )
    _, assign = kmeans(jax.random.PRNGKey(1), items, c_true, iters=6)
    counts = np.bincount(np.asarray(assign), minlength=c_true)
    assert counts.max() <= 4 * per, counts.max()


def test_ivf_index_covers_all_items():
    items = jax.random.normal(jax.random.PRNGKey(0), (777, 8))
    index = build_ivf(jax.random.PRNGKey(1), items, num_clusters=16)
    ids = np.asarray(index.lists)
    ids = ids[ids >= 0]
    assert sorted(ids.tolist()) == list(range(777))


def test_sharded_topk_multidevice():
    """Distributed top-K: per-shard streaming + global merge, on a real
    multi-device mesh (subprocess with forced host device count)."""
    import subprocess
    import sys

    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.mips import make_sharded_topk_fn, topk_exact

mesh = jax.make_mesh((2, 4), ("data", "model"))
kq, ki = jax.random.split(jax.random.PRNGKey(0))
q = jax.random.normal(kq, (6, 16))
items = jax.random.normal(ki, (1024, 16))
fn = make_sharded_topk_fn(mesh, 32, "model", block_items=64)
with mesh:
    out = fn(q, items)
ref = topk_exact(q, items, 32)
np.testing.assert_allclose(np.asarray(out.scores), np.asarray(ref.scores), rtol=1e-5)
assert (np.sort(out.indices, -1) == np.sort(np.asarray(ref.indices), -1)).all()
print("SHARDED_OK")
"""
    res = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
        cwd=__import__("os").path.dirname(__import__("os").path.dirname(__file__)),
        timeout=300,
    )
    assert "SHARDED_OK" in res.stdout, res.stderr[-3000:]
