"""Serving routes: how one padded micro-batch of payloads runs a model.

A route is the engine's model adapter — four duck-typed members:

    pad_payload          the dead-row payload short batches pad with
    prepare(payloads)    host list (len == max_batch) -> device arrays
    run(batch)           the jitted forward; returns device arrays
                         (the ENGINE times and blocks — routes never
                         block inside run, that would hide queue time)
    finalize(out, n)     device results -> the first n responses

Routes that retrieve through a `QueryPlanner` (the MIPS routes below)
additionally expose the degradation-ladder hooks the engine's health
monitor drives: probe / overflow / heal / degrade / degraded, and the
index's `live_tile_share`, which the engine emits as a gauge.

Three routes cover the arch pool:

  `RecsysMIPSRoute`     sasrec/dien — user tower -> `execute_query`
                        over the item table (the paper's Eq. 5 serve
                        path on the `ivf_topk` kernel).
  `LMGenerateRoute`     prefill + greedy decode where EVERY next-token
                        choice goes through the same `execute_query`
                        over the unembed rows (softcap is monotonic, so
                        MIPS argmax == logits argmax). Sampled tokens
                        accumulate ON DEVICE and materialise once after
                        the engine's block — no per-token host sync.
  `DenseCandidateRoute` din/wide_deep — no target-independent user
                        vector exists (DIN re-attends per candidate),
                        so these serve the per-request candidate-pool
                        shape (the Yahoo! front-page setting): dense
                        scoring of a fixed pool, batched across
                        requests by vmap.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve.planner import QueryPlanner

__all__ = ["DenseCandidateRoute", "LMGenerateRoute", "RecsysMIPSRoute"]


class RecsysMIPSRoute:
    """sasrec/dien retrieval: hist [T] -> top-k (ids, scores)."""

    def __init__(
        self, cfg, params, *, k: int = 10, num_clusters: int | None = None,
        n_probe: int | None = None, probe_hists=None, probe_k: int = 32,
        rebuild_iters: int = 4, seed: int = 0,
    ):
        from repro.core.policy import SoftmaxPolicy
        from repro.models import recsys

        if cfg.kind == "sasrec":
            tower = lambda p, hist: recsys.sasrec_user_vector(cfg, p, hist)
        elif cfg.kind == "dien":
            tower = lambda p, hist: recsys.dien_user_vector(cfg, p, hist)
        else:
            raise ValueError(
                f"{cfg.kind} has no target-independent user vector — "
                "serve it through DenseCandidateRoute"
            )
        self.cfg = cfg
        self.pad_payload = np.full((cfg.seq_len,), -1, np.int32)
        self.planner = QueryPlanner(
            SoftmaxPolicy(tower=tower, item_dim=cfg.embed_dim),
            params, params["items"], top_k=k, num_clusters=num_clusters,
            n_probe=n_probe, probe_k=probe_k, rebuild_iters=rebuild_iters,
            seed=seed,
            probe_x=None if probe_hists is None else jnp.asarray(probe_hists),
        )

    def prepare(self, payloads: list):
        return jnp.asarray(np.stack(payloads))

    def run(self, batch):
        return self.planner.query(batch)

    def warmup(self, max_batch: int) -> None:
        self.planner.warmup(jnp.asarray(
            np.stack([self.pad_payload] * max_batch)
        ))

    def finalize(self, out, n: int) -> list:
        ids = np.asarray(out.indices)[:n]
        scores = np.asarray(out.scores)[:n]
        return [(ids[i], scores[i]) for i in range(n)]

    # ladder hooks — delegate to the planner
    @property
    def degraded(self) -> bool:
        return self.planner.degraded

    def probe(self):
        return self.planner.probe()

    def overflow(self) -> int:
        return self.planner.overflow()

    def heal(self, action: str) -> None:
        self.planner.heal(action)

    @property
    def live_tile_share(self) -> float:
        return self.planner.live_tile_share

    def degrade(self) -> None:
        self.planner.degrade()


class LMGenerateRoute:
    """Batched prefill + greedy decode: prompt [prompt_len] ->
    gen_len generated token ids. The next-token head IS the query-only
    plan path: hidden state -> `execute_query` over the unembed rows."""

    def __init__(
        self, cfg, params, *, prompt_len: int, gen_len: int,
        max_batch: int, top_k: int = 4, num_clusters: int | None = None,
        n_probe: int | None = None, probe_hidden=None, probe_k: int = 32,
        seed: int = 0,
    ):
        from repro.core.policy import SoftmaxPolicy
        from repro.models import lm

        self.cfg, self.params = cfg, params
        self.prompt_len, self.gen_len = prompt_len, gen_len
        self.max_batch = max_batch
        self._lm = lm
        self.pad_payload = np.zeros((prompt_len,), np.int32)
        unembed = params.get("unembed", params["embed"])
        # identity tower: the "user embedding" of the LM serve path is
        # the transformer hidden state itself
        self.planner = QueryPlanner(
            SoftmaxPolicy(tower=lambda p, h: h, item_dim=cfg.d_model),
            params, unembed, top_k=top_k, num_clusters=num_clusters,
            n_probe=n_probe, probe_x=probe_hidden, probe_k=probe_k, seed=seed,
        )
        self._prefill = jax.jit(
            lambda p, t, c: lm.prefill(cfg, p, t, c, return_hidden=True)
        )
        self._decode = jax.jit(
            lambda p, t, c: lm.decode_step(cfg, p, t, c, return_hidden=True)
        )
        # greedy head over the retriever's slate: argmax by score (slot
        # order is not guaranteed sorted), dead -1 slots clamped
        self._greedy = jax.jit(lambda ind, sc: jnp.maximum(
            jnp.take_along_axis(
                ind, jnp.argmax(sc, axis=-1, keepdims=True), axis=-1
            )[:, 0], 0,
        ))

    def prepare(self, payloads: list):
        return jnp.asarray(np.stack(payloads))

    def run(self, tokens):
        """[B, prompt_len] -> [B, gen_len] generated ids — all device
        ops; the loop dispatches async and the token list materialises
        ONCE when the engine blocks on the stacked result."""
        cache = self._lm.init_cache(
            self.cfg, self.max_batch, self.prompt_len + self.gen_len
        )
        hidden, cache = self._prefill(self.params, tokens, cache)
        toks = []
        for _ in range(self.gen_len):
            slate = self.planner.query(hidden)
            tok = self._greedy(slate.indices, slate.scores)
            toks.append(tok)
            hidden, cache = self._decode(self.params, tok, cache)
        return jnp.stack(toks, axis=1)

    def warmup(self, max_batch: int) -> None:
        pads = jnp.asarray(np.stack([self.pad_payload] * max_batch))
        jax.block_until_ready(self.run(pads))
        cache = self._lm.init_cache(
            self.cfg, max_batch, self.prompt_len + self.gen_len
        )
        h, _ = self._prefill(self.params, pads, cache)
        self.planner.warmup(h)  # fallback path too

    def finalize(self, out, n: int) -> list:
        return [row.tolist() for row in np.asarray(out)[:n]]

    @property
    def degraded(self) -> bool:
        return self.planner.degraded

    def probe(self):
        return self.planner.probe()

    def overflow(self) -> int:
        return self.planner.overflow()

    def heal(self, action: str) -> None:
        self.planner.heal(action)

    @property
    def live_tile_share(self) -> float:
        return self.planner.live_tile_share

    def degrade(self) -> None:
        self.planner.degrade()


class DenseCandidateRoute:
    """din/wide_deep: score a fixed per-request candidate pool densely,
    vmapped across the micro-batch. payload: hist [T] (din) or
    (sparse [F], dense [Nd]) (wide_deep)."""

    def __init__(self, cfg, params, *, candidates, k: int = 10):
        from repro.models import recsys

        self.cfg = cfg
        cands = jnp.asarray(candidates, jnp.int32)
        if cfg.kind == "wide_deep":
            self.pad_payload = (
                np.zeros((cfg.n_sparse,), np.int32),
                np.zeros((cfg.n_dense,), np.float32),
            )

            def one(sparse, dense):
                vals, ids = recsys.retrieval_topk(
                    cfg, params,
                    {"sparse": sparse[None], "dense": dense[None],
                     "candidates": cands},
                    k=k,
                )
                return vals[0], ids[0]
        else:
            self.pad_payload = np.full((cfg.seq_len,), -1, np.int32)

            def one(hist):
                vals, ids = recsys.retrieval_topk(
                    cfg, params, {"hist": hist[None], "candidates": cands}, k=k
                )
                return vals[0], ids[0]

        self._fn = jax.jit(jax.vmap(one))

    def prepare(self, payloads: list):
        if self.cfg.kind == "wide_deep":
            sparse = jnp.asarray(np.stack([p[0] for p in payloads]))
            dense = jnp.asarray(np.stack([p[1] for p in payloads]))
            return sparse, dense
        return (jnp.asarray(np.stack(payloads)),)

    def run(self, batch):
        return self._fn(*batch)

    def warmup(self, max_batch: int) -> None:
        jax.block_until_ready(self.run(self.prepare(
            [self.pad_payload] * max_batch
        )))

    def finalize(self, out, n: int) -> list:
        vals, ids = np.asarray(out[0])[:n], np.asarray(out[1])[:n]
        return [(ids[i], vals[i]) for i in range(n)]
