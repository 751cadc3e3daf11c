"""QueryPlanner: the serve-side owner of one resolved ExecutionPlan.

The serving engine must not reimplement the training stack's retrieval
resolution — it resolves ONE `ExecutionPlan` at server start (same
compiled-vs-interpret rule, same IVF kwarg resolution, same exact
fallback) and queries it through the plan's query-only
`execute_query()` path for the rest of the process lifetime. This
module packages that ownership:

  * construction   — builds the IVF index over beta, resolves the plan
                     with an index_refresh route (every=0: maintenance
                     is event-driven in serving, not scheduled), so the
                     maintained-index machinery — `RefreshState` as a
                     jit operand, pre-resolved exact fallback — comes
                     from the plan, not from serve-side code;
  * the hot path   — `query(x)` is ONE jitted call
                     (params, x, beta, state) -> TopK, dispatched
                     without blocking (the engine owns the block);
  * the ladder     — `probe()`/`heal()`/`degrade()` are the hooks the
                     engine's `IndexHealthMonitor` drives: sampled
                     recall over a held probe set, jitted
                     compact/rebuild against the live state, and the
                     fallback swap. BOTH the primary and the fallback
                     paths are jitted and warmed at startup, so
                     degrading mid-traffic never pays a compile inside
                     a request's latency.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.ivf_topk.ops import live_tile_share

__all__ = ["QueryPlanner"]


class QueryPlanner:
    """One policy + one beta table + one resolved plan, serving queries.

    ``policy`` maps (params, x) -> h via `user_embedding` (the recsys
    user towers; the LM route passes an identity tower over hidden
    states). ``probe_x`` arms the degradation-ladder recall probe —
    without it `probe()` returns None and the ladder can only watch
    overflow (which serving never grows, so pass it when you want the
    ladder live)."""

    def __init__(
        self,
        policy,
        params,
        beta: jnp.ndarray,  # [P, L] item embeddings (LM: unembed rows)
        *,
        top_k: int,
        num_clusters: int | None = None,
        n_probe: int | None = None,
        delta_cap: int = 8,
        probe_x=None,
        probe_k: int = 32,
        rebuild_iters: int = 4,
        seed: int = 0,
    ):
        from repro.core.fopo import FOPOConfig
        from repro.core.plan import ExecutionPlan
        from repro.mips import refresh as refresh_mod
        from repro.mips.ivf import DEFAULT_N_PROBE, build_ivf

        self.policy = policy
        self.params = params
        self.beta = beta
        self.probe_k = min(probe_k, beta.shape[0])
        self.n_probe = n_probe or DEFAULT_N_PROBE
        index = build_ivf(
            jax.random.PRNGKey(seed), beta, num_clusters=num_clusters
        )
        fcfg = FOPOConfig(
            num_items=beta.shape[0],
            num_samples=1,  # unused on the query-only path
            top_k=top_k,
            retriever="ivf_pallas",
            # every=0 / compact_every=0: no scheduled maintenance — the
            # ladder's heal() actions are the only writers of the state
            index_refresh=refresh_mod.RefreshConfig(
                every=0, compact_every=0, delta_cap=delta_cap
            ),
        )
        self.plan = ExecutionPlan.resolve(
            fcfg, retriever_kwargs={"index": index, "n_probe": self.n_probe}
        )
        self.index_state = self.plan.initial_index_state
        # the share of the index's cap tiles that hold any item, read
        # from the lists where they are built or rebuilt (here and in
        # heal()); the engine emits it as the `ivf.live_tile_share` gauge
        self.live_tile_share = live_tile_share(self.index_state.lists)
        self.fallback_plan = self.plan.degrade_to_fallback()
        self._primary = self._jit(self.plan)
        self._fallback = self._jit(self.fallback_plan)
        self._fn = self._primary
        self._heal_fns = {
            "compact": jax.jit(refresh_mod.compact),
            "rebuild": jax.jit(partial(refresh_mod.rebuild, iters=rebuild_iters)),
        }
        k, n_probe = self.plan.cfg.top_k, self.n_probe
        self._reference = jax.jit(
            lambda params, x, state: refresh_mod.refresh_query(
                state, policy.user_embedding(params, x), k, n_probe
            )
        )
        self._embed = jax.jit(policy.user_embedding)
        self._probe_h = None if probe_x is None else self._embed(params, probe_x)

    def _jit(self, plan):
        policy = self.policy
        return jax.jit(
            lambda params, x, beta, state: plan.execute_query(
                policy, params, x, beta, index_state=state
            )
        )

    # -- the hot path ---------------------------------------------------
    def query(self, x: jnp.ndarray):
        """(x [B, Dx]) -> TopK, dispatched async — the caller blocks."""
        return self._fn(self.params, x, self.beta, self.index_state)

    def query_exact(self, x: jnp.ndarray):
        """(x [B, Dx]) -> TopK through the pre-resolved exact fallback
        plan, whatever the live plan is — the reference a recall check
        holds the live index to (same jitted trace `degrade()` swaps in)."""
        return self._fallback(self.params, x, self.beta, self.index_state)

    def query_reference(self, x: jnp.ndarray):
        """(x [B, Dx]) -> TopK through the pure-jnp IVF query over the
        live index (`refresh_query`: main lists and delta buffers, the
        same n_probe) — the reference the `ivf_topk` kernel route is
        held to: the same candidates, scored and merged without the
        kernel, so its ids must equal the primary plan's."""
        return self._reference(self.params, x, self.index_state)

    def warmup(self, x_example: jnp.ndarray) -> None:
        """Compile the primary AND fallback query paths before traffic:
        a mid-run degrade swaps to an already-warm trace."""
        jax.block_until_ready(
            self._primary(self.params, x_example, self.beta, self.index_state)
        )
        jax.block_until_ready(
            self._fallback(self.params, x_example, self.beta, self.index_state)
        )

    # -- degradation-ladder hooks (driven by the engine's monitor) ------
    @property
    def degraded(self) -> bool:
        return self.plan.degraded

    def probe(self) -> float | None:
        """Sampled recall@probe_k of the live index vs exact over the
        current beta — None when no probe set was armed. Host-blocking
        by design (why the engine probes periodically, not per batch)."""
        if self._probe_h is None:
            return None
        from repro.mips.refresh import sampled_recall

        return float(sampled_recall(
            self.index_state, self.beta, self._probe_h, self.probe_k,
            n_probe=self.n_probe,
        ))

    def overflow(self) -> int:
        return int(jnp.max(self.index_state.overflow))

    def heal(self, action: str) -> None:
        """Execute a compact/rebuild rung against the live state."""
        self.index_state = self._heal_fns[action](self.index_state, self.beta)
        self.live_tile_share = live_tile_share(self.index_state.lists)

    def degrade(self) -> None:
        """The ladder's last rung: swap to the pre-resolved (and
        pre-warmed) exact-fallback plan. Idempotent."""
        self.plan = self.fallback_plan
        self._fn = self._fallback
