"""The resolved ExecutionPlan of one FOPO training step.

`FOPOConfig` is a knob matrix — `fused` / `fused_interpret` /
`sample_tile` / `fused_sampler` / `retriever` / `dist` — and PRs 1-3
resolved it ad hoc wherever a knob happened to be consumed: interpret
mode in three places, the tile clamp in four, retriever construction in
the trainer, sampler selection in `fopo_loss`, and single-vs-dist
routing split between `fopo_loss` and `dist_fopo_loss`. This module
collapses all of that into ONE frozen object resolved ONCE from
(config, backend, mesh):

  * validation    — every invalid knob combination fails at
                    `ExecutionPlan.resolve`, before any tracing;
  * resolution    — interpret mode (compiled Pallas on TPU, interpret
                    fallback elsewhere), the `resolve_sample_tile`
                    clamp, and retriever construction happen here and
                    nowhere else;
  * routing       — the plan knows which sampler (jax.random
                    `MixtureProposal` vs the Pallas in-kernel
                    `fused_mixture_sample`) and which surrogate
                    (unfused jnp chain, fused custom_vjp kernels, or
                    the multi-device `dist_fused_covariance_loss`)
                    the step body runs;
  * the skeleton  — `execute()` is the single
                    retrieval -> sample -> weight -> reduce body shared
                    by the single-device and multi-device paths (they
                    differ only in which plan hooks fire, not in step
                    structure).

The previously forbidden `fused_sampler` x `dist` cell is closed: on
the multi-device path the in-kernel sampler runs per data shard with
its counter-hash PRNG folded by the shard's global batch-row offset
(`repro.dist.fopo.dist_fused_mixture_sample`), so per-shard draws
reproduce the single-device sampler stream exactly — independent
streams per shard, reproducible across mesh shapes.
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Any, Callable

import jax
import jax.numpy as jnp

from repro.backend import resolve_interpret
from repro.kernels.snis_covgrad.ops import resolve_sample_tile

if TYPE_CHECKING:
    from repro.core.fopo import FOPOConfig
    from repro.core.proposals import ProposalSample
    from repro.dist.fopo import DistConfig
    from repro.mips.exact import TopK
    from repro.mips.refresh import RefreshConfig, RefreshState

__all__ = ["ExecutionPlan", "RETRIEVERS", "make_retriever", "resolve_interpret"]

Retriever = Callable[[jnp.ndarray, jnp.ndarray], "TopK"]  # (h, beta) -> TopK

RETRIEVERS = ("exact", "streaming", "ivf", "ivf_pallas", "sharded", "pallas")

# retrievers whose query runs a Pallas kernel — the plan's resolved
# interpret mode is injected into their construction (same rule as the
# covgrad/sampler kernels: compiled on TPU, interpret elsewhere)
_PALLAS_RETRIEVERS = ("pallas", "ivf_pallas")


def make_retriever(cfg: FOPOConfig, **kw) -> Retriever:
    """Build the configured MIPS retriever (h, beta) -> TopK."""
    if cfg.retriever == "exact":
        from repro.mips.exact import topk_exact

        return lambda h, beta: topk_exact(h, beta, cfg.top_k)
    if cfg.retriever == "streaming":
        from repro.mips.streaming import topk_streaming

        block = kw.get("block_items", 4096)
        return lambda h, beta: topk_streaming(h, beta, cfg.top_k, block_items=block)
    if cfg.retriever == "pallas":
        from repro.kernels.mips_topk import ops as mips_ops

        interpret = kw.get("interpret")  # None -> the ops backend rule
        return lambda h, beta: mips_ops.mips_topk(
            h, beta, cfg.top_k, interpret=interpret
        )
    if cfg.retriever == "ivf":
        from repro.mips.ivf import DEFAULT_N_PROBE, ivf_query

        index = kw["index"]  # prebuilt IVFIndex (Assumption 1: beta fixed)
        n_probe = kw.get("n_probe", DEFAULT_N_PROBE)
        return lambda h, beta: ivf_query(index, h, cfg.top_k, n_probe=n_probe)
    if cfg.retriever == "ivf_pallas":
        from repro.kernels.ivf_topk import ops as ivf_ops

        index, n_probe, cap_tile = _resolve_ivf_pallas_kwargs(kw)
        interpret = kw.get("interpret")
        return lambda h, beta: ivf_ops.ivf_topk(
            h, index, cfg.top_k, n_probe=n_probe, cap_tile=cap_tile,
            interpret=interpret,
        )
    if cfg.retriever == "sharded":
        from repro.mips.sharded import make_sharded_topk_fn

        fn = make_sharded_topk_fn(kw["mesh"], cfg.top_k, kw.get("axis", "model"))
        return lambda h, beta: fn(h, beta)
    raise ValueError(f"unknown retriever {cfg.retriever!r}")


def _resolve_ivf_pallas_kwargs(kw: dict):
    """THE ivf_pallas kwarg resolution (single-device and dist routes
    alike): tile-align the prebuilt index ONCE — Assumption 1 fixes it,
    and leaving alignment to the kernel's in-trace pad fallback would
    re-copy the whole list table every step — and pin the n_probe
    default. Returns (aligned index, n_probe, cap_tile)."""
    from repro.kernels.ivf_topk import ops as ivf_ops
    from repro.mips.ivf import DEFAULT_N_PROBE

    index, cap_tile = ivf_ops.tile_align_index(kw["index"], kw.get("cap_tile"))
    return index, kw.get("n_probe", DEFAULT_N_PROBE), cap_tile


def _validate(cfg: FOPOConfig, *, injected_retriever: bool, retriever_kwargs: dict) -> None:
    """Construction-time knob validation — every invalid combination
    fails HERE, not deep inside a traced step body."""
    if cfg.num_items <= 0:
        raise ValueError(
            "FOPOConfig.num_items must be resolved (> 0) before planning; "
            "pass num_items= to ExecutionPlan.resolve or set it on the config"
        )
    if cfg.num_samples < 1:
        raise ValueError(f"num_samples must be >= 1, got {cfg.num_samples}")
    if cfg.top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {cfg.top_k}")
    if cfg.dist is not None:
        from repro.dist.fopo import DistConfig

        if not isinstance(cfg.dist, DistConfig):
            raise ValueError(
                f"FOPOConfig.dist must be a DistConfig (or None), got "
                f"{type(cfg.dist).__name__}"
            )
    if isinstance(cfg.epsilon, (int, float)) and not 0.0 <= cfg.epsilon <= 1.0:
        raise ValueError(f"epsilon must lie in [0, 1], got {cfg.epsilon}")
    if not injected_retriever and cfg.retriever not in RETRIEVERS:
        # typo guard fires under dist too — a misspelt retriever must
        # never silently fall back to the sharded exact scan
        raise ValueError(
            f"unknown retriever {cfg.retriever!r} (one of {RETRIEVERS})"
        )
    if not injected_retriever and cfg.dist is not None and cfg.retriever == "ivf":
        raise ValueError(
            'retriever="ivf" has no dist route (the jnp query would '
            "materialise the candidate tensor per shard); use "
            'retriever="ivf_pallas" with build_ivf_sharded, or drop the '
            "knob to take the sharded top-K merge"
        )
    if not injected_retriever and cfg.dist is None:
        if cfg.retriever in ("ivf", "ivf_pallas") and "index" not in retriever_kwargs:
            raise ValueError(
                f'retriever="{cfg.retriever}" needs a prebuilt index: pass '
                "retriever_kwargs={'index': build_ivf(...)}"
            )
        if cfg.retriever == "ivf_pallas":
            from repro.mips.ivf import IVFIndex

            if not isinstance(retriever_kwargs["index"], IVFIndex):
                raise ValueError(
                    'retriever="ivf_pallas" without dist= takes a single '
                    "IVFIndex (got "
                    f"{type(retriever_kwargs['index']).__name__}); under "
                    "dist= pass a ShardedIVFIndex from build_ivf_sharded"
                )
        if cfg.retriever == "sharded" and "mesh" not in retriever_kwargs:
            raise ValueError(
                'retriever="sharded" needs retriever_kwargs={"mesh": ...}'
            )
    if cfg.index_refresh is not None:
        from repro.mips.refresh import RefreshConfig

        if not isinstance(cfg.index_refresh, RefreshConfig):
            raise ValueError(
                "FOPOConfig.index_refresh must be a RefreshConfig (or "
                f"None), got {type(cfg.index_refresh).__name__}"
            )
        rc = cfg.index_refresh
        if injected_retriever:
            raise ValueError(
                "index_refresh= cannot combine with an injected retriever: "
                "the refresh path owns retriever construction (the index "
                "must ride as a RefreshState operand, not a closure)"
            )
        if cfg.retriever != "ivf_pallas":
            raise ValueError(
                "index_refresh= requires retriever='ivf_pallas' (the only "
                f"maintained index layout), got {cfg.retriever!r}"
            )
        if rc.every < 0 or rc.compact_every < 0:
            raise ValueError(
                "RefreshConfig.every / compact_every must be >= 0 "
                f"(0 disables), got {rc.every} / {rc.compact_every}"
            )
        if rc.every > 0 and rc.minibatch < 1:
            raise ValueError(
                f"RefreshConfig.minibatch must be >= 1, got {rc.minibatch}"
            )
        if rc.delta_cap < 1:
            raise ValueError(
                f"RefreshConfig.delta_cap must be >= 1, got {rc.delta_cap}"
            )
        if not 0.0 < rc.count_decay <= 1.0:
            raise ValueError(
                f"RefreshConfig.count_decay must lie in (0, 1], got "
                f"{rc.count_decay}"
            )
        if cfg.dist is not None and cfg.num_items % cfg.dist.n_model:
            raise ValueError(
                "index_refresh under dist= needs num_items divisible by "
                f"the mesh model axis (got {cfg.num_items} rows over "
                f"{cfg.dist.n_model} shards): the per-shard slot_of maps "
                "are sized by the uniform row slab"
            )
    if not injected_retriever and cfg.dist is not None and cfg.retriever == "ivf_pallas":
        # the one retriever the dist path resolves itself (every other
        # name falls back to the sharded exact top-K merge): each model
        # shard probes its LOCAL inverted lists, so the index must be
        # the per-shard stacked build
        from repro.mips.ivf import ShardedIVFIndex

        index = retriever_kwargs.get("index")
        if not isinstance(index, ShardedIVFIndex):
            raise ValueError(
                'retriever="ivf_pallas" under dist= needs retriever_kwargs='
                "{'index': build_ivf_sharded(...)} with n_shards == the "
                f"mesh model-axis size (got {type(index).__name__})"
            )
        if index.n_shards != cfg.dist.n_model:
            raise ValueError(
                f"ShardedIVFIndex has {index.n_shards} shards but the mesh "
                f"model axis is {cfg.dist.n_model}"
            )


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Everything `FOPOConfig` leaves implicit, resolved once.

    Resolved-knob table (config -> plan -> which code runs):

      cfg.fused_interpret  -> plan.interpret      compiled Pallas vs
                                                  interpret-mode kernels
      cfg.sample_tile      -> plan.sample_tile    clamped kernel tiling
      cfg.retriever        -> plan.retriever      built (h, beta)->TopK;
                                                  (h, beta, index)->TopK
                                                  for ivf_pallas, the
                                                  index riding as the
                                                  initial_index_state
                                                  operand (None under
                                                  dist: the
                                                  sharded top-K merge
                                                  owns retrieval —
                                                  except "ivf_pallas",
                                                  which probes local
                                                  inverted lists per
                                                  model shard)
      cfg.fused_sampler    -> plan.fused_sampler  Pallas in-kernel
                                                  sampler vs jax.random
                                                  MixtureProposal
      cfg.fused / cfg.dist -> plan.fused          custom_vjp kernel step
                                                  (dist implies fused)
      cfg.dist             -> plan.dist           shard_map multi-device
                                                  step vs single device
    """

    cfg: Any  # the normalized FOPOConfig (resolved knobs written back)
    backend: str
    interpret: bool
    sample_tile: int
    fused: bool
    fused_sampler: bool
    dist: DistConfig | None
    retriever: Retriever | None
    # cfg.index_refresh -> the maintenance schedule + the initial
    # RefreshState built from the caller's index. When set, `retriever`
    # takes the state as a third operand — (h, beta, state) -> TopK —
    # so the maintained index rides the step as data (no recompiles as
    # it updates; the trainer owns the state and its refresh cadence).
    refresh: RefreshConfig | None = None
    # the index OPERAND of the retriever — (h, beta, index) -> TopK — for
    # every ivf_pallas plan: the maintained RefreshState under a refresh
    # plan, else the tile-aligned IVFIndex / ShardedIVFIndex. A closure
    # capture would embed the whole [C, cap, L] table in the compiled
    # step as a constant (~GB at paper widths); as an operand it stays a
    # device buffer. None for retrievers without an index.
    initial_index_state: RefreshState | Any | None = None
    # the degradation ladder's last rung (repro.health.index_health):
    # a pre-resolved EXACT retriever with the refresh path's
    # (h, beta, state) signature — resolved at construction so the
    # decision to degrade never constructs anything new, it just swaps
    # which resolved retriever the step closes over. None when the plan
    # has no refresh path (the ladder only exists for maintained
    # indexes).
    fallback_retriever: Retriever | None = None
    degraded: bool = False  # True once degrade_to_fallback() was taken

    def degrade_to_fallback(self) -> "ExecutionPlan":
        """The ladder's terminal action: a new frozen plan whose
        retriever is the pre-resolved exact fallback (same operand
        signature — the trainer rebuilds its jitted step against the
        new plan, with every operand unchanged). Idempotent."""
        if self.degraded:
            return self
        if self.fallback_retriever is None:
            raise ValueError(
                "plan has no fallback retriever (only refresh plans "
                "resolve one — nothing to degrade to)"
            )
        return dataclasses.replace(
            self, retriever=self.fallback_retriever, degraded=True
        )

    # ------------------------------------------------------------------
    @classmethod
    def resolve(
        cls,
        cfg: FOPOConfig,
        *,
        num_items: int | None = None,
        backend: str | None = None,
        retriever: Retriever | None = None,
        retriever_kwargs: dict | None = None,
    ) -> "ExecutionPlan":
        """Resolve config + backend + mesh into a frozen plan.

        ``retriever`` injects a prebuilt retriever (tests; the recsys
        towers) and skips retriever construction/validation; otherwise
        the plan builds the configured one (``retriever_kwargs`` feeds
        it, e.g. the IVF index). In dist mode with no injection the
        sharded top-K merge owns retrieval (plan.retriever is None) —
        unless ``retriever="ivf_pallas"``, whose per-shard IVF probe
        replaces the exact merge (needs a ShardedIVFIndex).
        """
        kw = retriever_kwargs or {}
        backend = backend or jax.default_backend()
        if num_items is not None and cfg.num_items == 0:
            cfg = dataclasses.replace(cfg, num_items=num_items)
        _validate(cfg, injected_retriever=retriever is not None, retriever_kwargs=kw)
        tile = resolve_sample_tile(cfg.sample_tile, cfg.num_samples)
        interpret = resolve_interpret(cfg.fused_interpret, backend)
        uses_kernels = cfg.fused or cfg.fused_sampler or cfg.dist is not None
        # write the resolved knobs back so checkpoints/logs/downstream
        # consumers of plan.cfg see what actually runs
        if tile != cfg.sample_tile:
            cfg = dataclasses.replace(cfg, sample_tile=tile)
        if cfg.top_k > cfg.num_items:
            # same clamp-and-write-back rule as sample_tile: the default
            # top_k=256 on a tiny catalog must not reach the retriever as
            # an out-of-range K (lax.top_k would trace-fail; masked paths
            # would emit garbage ids)
            cfg = dataclasses.replace(cfg, top_k=cfg.num_items)
        if uses_kernels and cfg.fused_interpret is None:
            cfg = dataclasses.replace(cfg, fused_interpret=interpret)
        if retriever is None and cfg.retriever in _PALLAS_RETRIEVERS:
            # the retriever kernels follow the SAME resolved interpret
            # mode as the covgrad/sampler kernels (an explicit kwarg
            # still wins) — this is what lets them compile on TPU
            kw = dict(kw)
            kw.setdefault("interpret", interpret)
        refresh = cfg.index_refresh
        initial_state = None
        fallback = None
        if refresh is not None:
            # incremental maintenance: the index becomes a RefreshState
            # OPERAND of the retriever — (h, beta, state) — instead of a
            # closure capture, so refresh/append/compact never recompile
            # the step. The plan wraps the caller's (tile-aligned) index
            # into the initial state; the trainer owns it from there.
            from repro.kernels.ivf_topk import ops as ivf_ops
            from repro.mips import refresh as refresh_mod

            index, n_probe, cap_tile = _resolve_ivf_pallas_kwargs(kw)
            r_interp, top_k = kw["interpret"], cfg.top_k
            num_items = cfg.num_items
            if cfg.dist is None:
                from repro.mips.exact import topk_exact

                initial_state = refresh_mod.init_refresh_state(
                    index, cfg.num_items, refresh.delta_cap
                )
                retriever = lambda h, beta, state: ivf_ops.ivf_topk(  # noqa: E731
                    h, state.as_index(num_items), top_k,
                    n_probe=n_probe, cap_tile=cap_tile, interpret=r_interp,
                    delta=state.delta(),
                )
                # the ladder's exact fallback, with the refresh-route
                # signature (state rides along unused so the step body
                # never changes shape when degrading)
                fallback = lambda h, beta, state: topk_exact(  # noqa: E731
                    h, beta, top_k
                )
            else:
                from repro.dist.fopo import dist_ivf_topk, dist_sharded_topk

                dist_cfg = cfg.dist
                initial_state = refresh_mod.init_refresh_sharded(
                    index, refresh.delta_cap
                )
                retriever = lambda h, beta, state: dist_ivf_topk(  # noqa: E731
                    h, refresh_mod.sharded_as_index(state, cfg.num_items),
                    top_k, dist_cfg, n_probe=n_probe, cap_tile=cap_tile,
                    interpret=r_interp, delta=state.delta(),
                )
                fallback = lambda h, beta, state: dist_sharded_topk(  # noqa: E731
                    h, beta, top_k, dist_cfg, num_items=num_items
                )
        elif retriever is None and cfg.retriever == "ivf_pallas":
            # the static index rides as the retriever's operand (see
            # `initial_index_state`); under dist= retrieval joins the
            # plan as a per-shard IVF probe + K-merge instead of the
            # sharded exact top-K
            from repro.kernels.ivf_topk import ops as ivf_ops

            initial_state, n_probe, cap_tile = _resolve_ivf_pallas_kwargs(kw)
            r_interp, dist_cfg, top_k = kw["interpret"], cfg.dist, cfg.top_k
            if dist_cfg is None:
                retriever = lambda h, beta, index: ivf_ops.ivf_topk(  # noqa: E731
                    h, index, top_k, n_probe=n_probe, cap_tile=cap_tile,
                    interpret=r_interp,
                )
            else:
                from repro.dist.fopo import dist_ivf_topk

                retriever = lambda h, beta, index: dist_ivf_topk(  # noqa: E731
                    h, index, top_k, dist_cfg, n_probe=n_probe,
                    cap_tile=cap_tile, interpret=r_interp,
                )
        elif retriever is None and cfg.dist is None:
            retriever = make_retriever(cfg, **kw)
        return cls(
            cfg=cfg,
            backend=backend,
            interpret=interpret,
            sample_tile=tile,
            fused=bool(cfg.fused or cfg.dist is not None),
            fused_sampler=bool(cfg.fused_sampler),
            dist=cfg.dist,
            retriever=retriever,
            refresh=refresh,
            initial_index_state=initial_state,
            fallback_retriever=fallback,
        )

    # ------------------------------------------------------------------
    # the query-only serve path: user embedding -> retrieval, nothing else
    # ------------------------------------------------------------------
    def execute_query(
        self,
        policy,
        params,
        x: jnp.ndarray,  # [B, Dx] request contexts
        beta: jnp.ndarray,  # [P, L] item embeddings
        index_state: "RefreshState | None" = None,
    ) -> "TopK":
        """The inference half of `execute()`: h_theta(x) through the
        plan's resolved retriever — no sampling, no reward, no
        surrogate. This is the ONE serve path: the recsys MIPS route
        and the LM prefill/decode route both call it, so serving rides
        the same retriever resolution (interpret rule, IVF index
        operand, exact fallback) as training. Under a refresh plan the
        maintained index rides as ``index_state`` exactly as in
        `execute()`, which is what lets the serving engine reuse the
        degradation ladder unchanged."""
        h = self._user_embedding(policy, params, x)
        return self.retrieve(h, beta, index_state)

    def _user_embedding(self, policy, params, x) -> jnp.ndarray:
        """h_theta(x) under stop_gradient — shared by `execute()` and
        `execute_query()` so the training and serving paths embed
        identically by construction."""
        return jax.lax.stop_gradient(policy.user_embedding(params, x))

    # ------------------------------------------------------------------
    # the shared step skeleton: retrieval -> sample -> weight -> reduce
    # ------------------------------------------------------------------
    def execute(
        self,
        policy,
        params,
        key: jax.Array,
        x: jnp.ndarray,  # [B, Dx]
        beta: jnp.ndarray,  # [P, L] fixed item embeddings
        reward_fn,  # actions [B, S] -> [B, S]
        epsilon: float | jnp.ndarray | None = None,
        index_state: "RefreshState | None" = None,
    ) -> tuple[jnp.ndarray, dict]:
        """One Algorithm-1 step body — the SAME skeleton on one device
        and on the mesh; the plan hooks decide which retriever, sampler
        and surrogate fire. Returns (loss, aux). Under a refresh plan
        ``index_state`` is the maintained index (defaults to the plan's
        initial state) — pass the trainer's current state so retrieval
        sees appended/refreshed items."""
        eps = self.cfg.epsilon if epsilon is None else epsilon
        h_prop = self._user_embedding(policy, params, x)
        sample = self.draw(key, h_prop, beta, eps, index_state=index_state)
        # clamp keeps reward lookups in-bounds on pre-masked (padded)
        # slots; their reward is zeroed and their SNIS weight is 0
        valid = sample.actions >= 0
        rewards = jax.lax.stop_gradient(
            reward_fn(jnp.maximum(sample.actions, 0)) * valid
        )
        return self.surrogate(policy, params, x, beta, sample, rewards)

    # -- retrieval ------------------------------------------------------
    def retrieve(
        self,
        h_prop: jnp.ndarray,
        beta: jnp.ndarray,
        index_state: "RefreshState | None" = None,
    ) -> "TopK":
        if self.initial_index_state is not None:
            state = (
                index_state if index_state is not None
                else self.initial_index_state
            )
            return self.retriever(h_prop, beta, state)
        if self.retriever is not None:
            return self.retriever(h_prop, beta)
        from repro.dist.fopo import dist_sharded_topk

        return dist_sharded_topk(
            h_prop, beta, self.cfg.top_k, self.dist,
            num_items=self.cfg.num_items,
        )

    # -- sampling -------------------------------------------------------
    def draw(self, key, h_prop, beta, eps, index_state=None) -> "ProposalSample":
        """Step 4: S proposal draws per context. A static (python
        number) eps >= 1 short-circuits retrieval entirely (pure
        uniform proposal); a traced eps takes the mixture route, which
        reproduces the uniform pmf exactly at eps == 1."""
        if isinstance(eps, (int, float)) and eps >= 1.0:
            return self._draw_uniform(key, h_prop.shape[0])
        topk = self.retrieve(h_prop, beta, index_state)
        return self._draw_mixture(key, topk, eps)

    def _draw_uniform(self, key, batch: int) -> "ProposalSample":
        from repro.core.proposals import UniformProposal

        # one jax.random call on one device and on the mesh alike: the
        # partitionable threefry (jax's default) draws the same values
        # however the outer jit partitions the sampling ops
        return UniformProposal(self.cfg.num_items).sample(
            key, batch, self.cfg.num_samples
        )

    def _draw_mixture(self, key, topk: "TopK", eps) -> "ProposalSample":
        cfg = self.cfg
        if self.fused_sampler:
            if self.dist is None:
                from repro.core.proposals import ProposalSample
                from repro.kernels.fused_sampler import fused_mixture_sample

                actions, log_q, slots = fused_mixture_sample(
                    key, topk.indices, topk.scores,
                    num_samples=cfg.num_samples, epsilon=eps,
                    num_items=cfg.num_items, sample_tile=self.sample_tile,
                    interpret=self.interpret,
                )
                return ProposalSample(actions=actions, log_q=log_q, topk_slot=slots)
            from repro.dist.fopo import dist_fused_mixture_sample

            return dist_fused_mixture_sample(
                key, topk,
                num_samples=cfg.num_samples, epsilon=eps,
                num_items=cfg.num_items, sample_tile=self.sample_tile,
                interpret=self.interpret, dist=self.dist,
            )
        from repro.core.proposals import MixtureProposal

        # single shared implementation, float or traced epsilon alike, on
        # one device and on the mesh (partitionable threefry: the draws do
        # not depend on how the outer jit shards the sampling ops)
        return MixtureProposal(cfg.num_items, eps).sample(
            key, topk.indices, topk.scores, cfg.num_samples
        )

    # -- weighting + reduction ------------------------------------------
    def surrogate(
        self, policy, params, x, beta, sample: "ProposalSample", rewards
    ) -> tuple[jnp.ndarray, dict]:
        """Step 5: SNIS weights + covariance-gradient surrogate.
        `covariance_surrogate` owns the unfused/fused/dist dispatch —
        the plan just hands it the resolved knobs."""
        from repro.core.gradients import covariance_surrogate

        return covariance_surrogate(
            policy, params, x, beta, sample.actions, sample.log_q, rewards,
            fused=self.fused, fused_interpret=self.interpret,
            sample_tile=self.sample_tile, dist=self.dist,
        )
