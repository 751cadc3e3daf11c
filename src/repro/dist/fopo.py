"""The multi-device fused FOPO training step.

Single-device FOPO (repro.core) caps the catalog at one device's HBM:
beta [P, L] must be resident wherever the gather kernels run. This
module removes that cap by sharding beta's rows over the mesh `model`
axis and the batch over the `data` axis, while keeping the PR-2
sample-tiled Pallas kernels as the per-device compute:

  1. retrieval — `mips.sharded.sharded_topk` per beta shard + global
     K-merge (communication O(n * B * K), never O(P));
  2. sampling — the eps-mixture draws run on the merged top-K exactly
     as in the single-device path (same keys => same draws). With
     `fused_sampler` the draws instead come from the Pallas in-kernel
     sampler running PER DATA SHARD (`dist_fused_mixture_sample`): its
     counter-hash PRNG is keyed by the global batch row (the shard's
     `data`-axis index times its local batch), so each shard emits
     exactly the rows the single-device kernel would — no (B, S, K)
     Gumbel tensor anywhere, streams disjoint across shards and
     reproducible across mesh shapes;
  3. id routing — each device needs every sampled id to decide which
     rows it owns: an all-gather of the (B, S) id tensor along `model`
     (`collectives.gather_samples`), then local-id rebasing
     (`collectives.rebase_ids`) maps foreign ids to the kernels'
     dead-slot sentinel (-1);
  4. local kernels — the sample-tiled `snis_covgrad` forward scores
     ONLY owned slots (masked slots come back exactly zero after the
     ownership mask), and the backward regathers owned beta rows;
  5. reduction — ONE psum of the per-shard score partials along
     `model` (`collectives.psum_scores`). Each slot receives its
     owner's bitwise score plus hard zeros, so the reconstructed score
     matrix — and hence the per-row SNIS normaliser, weights and
     covariance coefficients — is bit-for-bit the single-device fused
     path's; the scalar loss then differs only by float-sum
     reassociation of the final batch reduction over the data-sharded
     rows (~1e-6 rel, inside the 1e-5 acceptance bar). The normaliser
     itself (softmax over S) is computed locally after that psum and
     never reduced again. The backward grad_h partials psum the same
     way (each slot contributes to exactly one shard).

Ragged catalogs (P % n_shards != 0) zero-pad beta; pad rows are
unaddressable (ids < P) and `sharded_topk(num_valid=P)` keeps them out
of retrieval. A device that owns none of the sampled ids contributes
an exact-zero partial everywhere — the all-foreign case is just "every
slot masked", which the kernels already handle exactly.

Gradients flow to the user tower only (`h`); beta is fixed
(Assumption 1), same contract as `fused_covariance_loss`.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.backend import resolve_interpret
from repro.core.policy import SoftmaxPolicy
from repro.core.proposals import ProposalSample
from repro.core.snis import snis_covariance_coefficients, snis_diagnostics
from repro.dist.collectives import (
    gather_samples,
    pad_rows,
    pad_samples,
    psum_scores,
    rebase_ids,
)
from repro.kernels.snis_covgrad.ops import (
    DEFAULT_SAMPLE_TILE,
    resolve_sample_tile,
    snis_covgrad_bwd,
    snis_scores_fused,
)
from repro.mips.exact import TopK
from repro.mips.ivf import DEFAULT_N_PROBE
from repro.mips.sharded import sharded_topk


@dataclasses.dataclass(frozen=True)
class DistConfig:
    """Wiring of the dist FOPO step onto a mesh.

    ``routing`` picks how sampled ids reach the beta shards:
      * "gather"    — actions/log_q/rewards enter shard_map sample-
                      sharded over `model` and are all-gathered
                      in-graph (explicit, costed collective; default);
      * "replicate" — they enter replicated over `model` (the gather
                      happens implicitly at the jit boundary).
    Both are exact; they trade an explicit (B, S) all-gather against
    resharding at dispatch. The remote-DMA in-kernel gather (no id
    movement at all) is the TPU follow-on tracked in ROADMAP.md.
    """

    mesh: jax.sharding.Mesh
    data_axis: str = "data"
    model_axis: str = "model"
    routing: str = "gather"

    def __post_init__(self):
        if self.routing not in ("gather", "replicate"):
            raise ValueError(f"unknown routing {self.routing!r}")
        for ax in (self.data_axis, self.model_axis):
            if ax not in self.mesh.shape:
                raise ValueError(f"axis {ax!r} not in mesh {self.mesh.shape}")

    @property
    def n_data(self) -> int:
        return self.mesh.shape[self.data_axis]

    @property
    def n_model(self) -> int:
        return self.mesh.shape[self.model_axis]

    def sample_spec(self) -> P:
        if self.routing == "gather":
            return P(self.data_axis, self.model_axis)
        return P(self.data_axis, None)


def make_debug_dist(data: int = 2, model: int = 2, **kw) -> DistConfig:
    """DistConfig on a small host-CPU mesh (tests / examples; needs
    >= data*model devices, e.g. XLA_FLAGS=--xla_force_host_platform_
    device_count=4)."""
    from repro.launch.mesh import make_debug_mesh

    return DistConfig(mesh=make_debug_mesh(data, model), **kw)


# ---------------------------------------------------------------------------
# the shard_map'd pieces
# ---------------------------------------------------------------------------

def _local_score_partial(dist, interpret, tile, h_, beta_sh, acts, lq, rw):
    """One device's score partial (inside shard_map): route ids, rebase
    to local rows, run the fused forward, and zero non-owned slots —
    masked slots score h . beta_shard[0] in-kernel (clamped DMA), so
    the ownership mask is what makes the psum reconstruct exactly the
    owner's value. Shared by the production path (`_dist_scores`) and
    the observability hook (`dist_score_partials`)."""
    if dist.routing == "gather":
        acts, lq, rw = gather_samples(dist.model_axis, acts, lq, rw)
    local_acts, owned = rebase_ids(acts, beta_sh.shape[0], dist.model_axis)
    part = snis_scores_fused(
        h_, beta_sh, local_acts, lq, rw,
        interpret=interpret, sample_tile=tile,
    )
    return jnp.where(owned, part, 0.0)


def _dist_scores(dist, interpret, tile, h, beta_p, actions, log_q, rewards):
    """Global sampled scores [B, Sp]: per-shard fused forward on owned
    slots, ownership-masked, psum'd once along `model`."""

    def local(h_, beta_sh, acts, lq, rw):
        part = _local_score_partial(
            dist, interpret, tile, h_, beta_sh, acts, lq, rw
        )
        return psum_scores(part, dist.model_axis)

    return jax.shard_map(
        local,
        mesh=dist.mesh,
        in_specs=(
            P(dist.data_axis, None),
            P(dist.model_axis, None),
            dist.sample_spec(),
            dist.sample_spec(),
            dist.sample_spec(),
        ),
        out_specs=P(dist.data_axis, None),
        check_vma=False,
    )(h, beta_p, actions, log_q, rewards)


def _dist_grad_h(dist, interpret, tile, g_scores, actions, beta_p):
    """grad_h [B, L] = sum_s g[b, s] beta[a_bs]: per-shard backward
    gather-reduce over owned slots, psum'd along `model`."""

    def local(g_, acts, beta_sh):
        if dist.routing == "gather":
            g_, acts = gather_samples(dist.model_axis, g_, acts)
        local_acts, _ = rebase_ids(acts, beta_sh.shape[0], dist.model_axis)
        part = snis_covgrad_bwd(
            g_, local_acts, beta_sh, interpret=interpret, sample_tile=tile
        )
        return jax.lax.psum(part, dist.model_axis)

    return jax.shard_map(
        local,
        mesh=dist.mesh,
        in_specs=(
            dist.sample_spec(),
            dist.sample_spec(),
            P(dist.model_axis, None),
        ),
        out_specs=P(dist.data_axis, None),
        check_vma=False,
    )(g_scores, actions, beta_p)


def dist_score_partials(
    h, beta, actions, log_q, rewards, *, dist: DistConfig,
    interpret: bool | None = None, sample_tile: int = DEFAULT_SAMPLE_TILE,
):
    """Per-shard score partials [n_model, B, S] BEFORE the psum —
    observability hook for tests (e.g. the all-foreign-ids shard must
    be exactly zero) and for debugging ownership masks."""
    interpret = resolve_interpret(interpret)
    tile = resolve_sample_tile(sample_tile, actions.shape[1])
    beta_p = pad_rows(beta, dist.n_model)
    actions, log_q, rewards = pad_samples(
        actions, log_q, rewards, dist.n_model
    )

    def local(h_, beta_sh, acts, lq, rw):
        return _local_score_partial(
            dist, interpret, tile, h_, beta_sh, acts, lq, rw
        )[None]

    return jax.shard_map(
        local,
        mesh=dist.mesh,
        in_specs=(
            P(dist.data_axis, None),
            P(dist.model_axis, None),
            dist.sample_spec(),
            dist.sample_spec(),
            dist.sample_spec(),
        ),
        out_specs=P(dist.model_axis, dist.data_axis, None),
        check_vma=False,
    )(h, beta_p, actions, log_q, rewards)


# ---------------------------------------------------------------------------
# custom_vjp loss — the dist twin of gradients.fused_covariance_loss
# ---------------------------------------------------------------------------

def _dist_loss_pieces(dist, interpret, tile, s_orig, h, beta_p, actions, log_q, rewards):
    scores = _dist_scores(
        dist, interpret, tile, h, beta_p, actions, log_q, rewards
    )
    # crop the routing-pad columns (dead slots appended by pad_samples)
    # BEFORE the SNIS chain: the psum'd scores equal the owner-kernel
    # values bitwise, and on equal shapes the softmax/reduction lowering
    # is identical to the single-device fused path — without the crop,
    # XLA's wider reduction tree reassociates the same sum to a
    # different last ulp (seed-dependent)
    scores = scores[:, :s_orig]
    actions_c, log_q_c, rewards_c = (
        actions[:, :s_orig], log_q[:, :s_orig], rewards[:, :s_orig]
    )
    wbar = jax.nn.softmax(scores - log_q_c, axis=-1) * (actions_c >= 0)
    coeff = snis_covariance_coefficients(wbar, rewards_c)
    loss = -jnp.mean(jnp.sum(coeff * scores, axis=-1))
    return loss, snis_diagnostics(wbar, rewards_c), coeff


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _dist_covariance_loss(dist, interpret, tile, s_orig, h, beta_p, actions, log_q, rewards):
    loss, aux, _ = _dist_loss_pieces(
        dist, interpret, tile, s_orig, h, beta_p, actions, log_q, rewards
    )
    return loss, aux


def _dist_covariance_loss_fwd(dist, interpret, tile, s_orig, h, beta_p, actions, log_q, rewards):
    loss, aux, coeff = _dist_loss_pieces(
        dist, interpret, tile, s_orig, h, beta_p, actions, log_q, rewards
    )
    return (loss, aux), (coeff, actions, beta_p)


def _dist_covariance_loss_bwd(dist, interpret, tile, s_orig, res, ct):
    coeff, actions, beta_p = res
    ct_loss = ct[0]  # aux cotangents are diagnostics — discarded
    batch, sp = actions.shape
    g_scores = (-ct_loss / batch) * coeff  # [B, s_orig]
    if sp != s_orig:  # re-pad to the routed width; pad slots are dead
        g_scores = jnp.concatenate(
            [g_scores, jnp.zeros((batch, sp - s_orig), g_scores.dtype)],
            axis=1,
        )
    grad_h = _dist_grad_h(dist, interpret, tile, g_scores, actions, beta_p)
    return (
        grad_h,
        jnp.zeros_like(beta_p),  # fixed embeddings (Assumption 1); DCE'd
        np.zeros(actions.shape, dtype=jax.dtypes.float0),
        jnp.zeros_like(g_scores),  # log_q: weights evaluated, not diff'd
        jnp.zeros_like(g_scores),  # rewards: logged feedback, constant
    )


_dist_covariance_loss.defvjp(_dist_covariance_loss_fwd, _dist_covariance_loss_bwd)


def dist_fused_covariance_loss(
    h: jnp.ndarray,  # [B, L] user embeddings (differentiable)
    beta: jnp.ndarray,  # [P, L] fixed item embeddings (any P — padded here)
    actions: jnp.ndarray,  # [B, S] int32 global ids; -1 marks masked slots
    log_q: jnp.ndarray,  # [B, S]; LOG_Q_PAD on masked slots
    rewards: jnp.ndarray,  # [B, S]
    *,
    dist: DistConfig,
    interpret: bool | None = None,
    sample_tile: int = DEFAULT_SAMPLE_TILE,
) -> tuple[jnp.ndarray, dict]:
    """The multi-device fused FOPO step: (loss, aux) with a custom VJP
    whose forward/backward run the sample-tiled Pallas kernels on each
    device's beta shard. Matches `fused_covariance_loss` (the
    single-device path) per slot bitwise on scores/weights; the scalar
    loss and grad_h differ only by float-sum reassociation of the
    batch/sample reductions over the sharded dims (~1e-6 rel).
    Requires B % n_data == 0; P and S are padded here as needed (zero
    rows / dead slots — exact no-ops)."""
    b, s = actions.shape
    if b % dist.n_data:
        raise ValueError(
            f"batch {b} must be a multiple of the data-axis size "
            f"({dist.n_data})"
        )
    tile = resolve_sample_tile(sample_tile, s)
    beta_p = pad_rows(beta, dist.n_model)
    if dist.routing == "gather":
        actions, log_q, rewards = pad_samples(
            actions, log_q, rewards, dist.n_model
        )
    return _dist_covariance_loss(
        dist, resolve_interpret(interpret), tile, s, h, beta_p, actions, log_q, rewards
    )


# ---------------------------------------------------------------------------
# the full dist Algorithm-1 loss — retrieval + sampling + fused step
# ---------------------------------------------------------------------------

def dist_ivf_topk(
    h: jnp.ndarray,  # [B, L] user embeddings — batch-sharded over `data`
    index,  # ShardedIVFIndex: one local IVF per model shard, global ids
    k: int,
    dist: DistConfig,
    *,
    n_probe: int = DEFAULT_N_PROBE,
    cap_tile: int | None = None,
    interpret: bool | None = None,
    delta=None,  # optional ([n, C, dcap] lists, [n, C, dcap, L] embs)
) -> TopK:
    """Sublinear proposal retrieval on the mesh: each `model` shard runs
    the tiled Pallas IVF query (`repro.kernels.ivf_topk`) over its OWN
    inverted lists — probing only local clusters, O(C_loc*L +
    n_probe*cap*L) per shard instead of the sharded exact top-K's full
    local scan O(P/n * L) — then the [n, B, K] local candidates merge
    along `model` exactly like `sharded_topk` (ids are already global:
    the slab offset is baked into the lists at build time, see
    `build_ivf_sharded`). Downstream id routing / psum machinery is
    untouched: `merge_topk_along_axis` is the SAME K-merge the exact
    route ends in (one home for the dead-slot convention — short local
    lists back-fill id -1 / NEG_INF and lose the merge).

    ``delta`` carries each shard's incremental-maintenance append
    buffers (`repro.mips.refresh`, stacked on the shard axis): every
    shard probes its own delta lists alongside its main lists, so
    not-yet-compacted updates are retrievable on the dist route too."""
    from repro.kernels.ivf_topk import ivf_topk
    from repro.mips.ivf import ShardedIVFIndex
    from repro.mips.sharded import merge_topk_along_axis

    def local(q, cent, lists, embs, *d):
        # the shard_map block is the [1, ...] leading-axis slice — view
        # it as this device's local IVFIndex (global ids baked in)
        local_index = ShardedIVFIndex(cent, lists, embs, index.num_items).shard(0)
        loc = ivf_topk(
            q, local_index, k,
            n_probe=n_probe, cap_tile=cap_tile, interpret=interpret,
            delta=(d[0][0], d[1][0]) if d else None,
        )
        return merge_topk_along_axis(loc.scores, loc.indices, k, dist.model_axis)

    in_specs = [
        P(dist.data_axis, None),
        P(dist.model_axis, None, None),
        P(dist.model_axis, None, None),
        P(dist.model_axis, None, None, None),
    ]
    operands = [h, index.centroids, index.lists, index.list_embs]
    if delta is not None:
        in_specs += [
            P(dist.model_axis, None, None),
            P(dist.model_axis, None, None, None),
        ]
        operands += [delta[0], delta[1]]
    return jax.shard_map(
        local,
        mesh=dist.mesh,
        in_specs=tuple(in_specs),
        out_specs=TopK(
            scores=P(dist.data_axis, None), indices=P(dist.data_axis, None)
        ),
        check_vma=False,
    )(*operands)


def dist_sharded_topk(
    h: jnp.ndarray,  # [B, L] user embeddings (proposal side)
    beta: jnp.ndarray,  # [P, L]
    k: int,
    dist: DistConfig,
    *,
    num_items: int | None = None,
    block_items: int = 4096,
) -> TopK:
    """Proposal retrieval over the row-sharded (and, if ragged, padded)
    catalog: per-shard streaming top-K + global K-merge, batch-sharded
    over `data`. Pad rows are masked out pre-merge (num_valid)."""
    p = beta.shape[0]
    beta_p = pad_rows(beta, dist.n_model)
    num_valid = num_items if num_items is not None else p

    def local(q, items_sh):
        return sharded_topk(
            q, items_sh, k, dist.model_axis, block_items, num_valid
        )

    return jax.shard_map(
        local,
        mesh=dist.mesh,
        in_specs=(P(dist.data_axis, None), P(dist.model_axis, None)),
        out_specs=TopK(
            scores=P(dist.data_axis, None), indices=P(dist.data_axis, None)
        ),
        check_vma=False,
    )(h, beta_p)


def dist_fused_mixture_sample(
    key: jax.Array,
    topk: TopK,  # indices/scores [B, K] — batch-sharded over `data`
    *,
    num_samples: int,
    epsilon,  # float or traced jnp scalar
    num_items: int,
    sample_tile: int,
    dist: DistConfig,
    interpret: bool | None = None,
) -> ProposalSample:
    """The Pallas in-kernel eps-mixture sampler on the mesh: one kernel
    launch per data shard, over that shard's local top-K rows.

    The kernel's counter-hash PRNG is keyed by the GLOBAL batch row —
    each shard passes ``row_offset = axis_index(data) * B_local`` — so
    shard d draws bit-exactly rows [d*B_local, (d+1)*B_local) of the
    single-device sampler stream at the same key: streams are disjoint
    across shards by construction and the assembled (B, Sp) draw is
    invariant to the mesh shape (hash-twin-tested against
    `fused_sampler_ref`). The int32 kernel seed is folded from the key
    ONCE outside shard_map (a scalar — nothing for the partitioner to
    reshard), then broadcast replicated.

    Outputs are tile-aligned [B, Sp] (Sp = ceil(S/TS)*TS, padded tail
    pre-masked) and flow straight into the existing id routing: the
    all-gather/rebase machinery of `dist_fused_covariance_loss` treats
    them exactly like jax.random draws.
    """
    interpret = resolve_interpret(interpret)
    b = topk.indices.shape[0]
    if b % dist.n_data:
        raise ValueError(
            f"batch {b} must be a multiple of the data-axis size "
            f"({dist.n_data})"
        )
    b_local = b // dist.n_data
    from repro.kernels.fused_sampler import fused_sampler_pallas, key_to_seed

    seed = key_to_seed(key)

    def local(seed_, eps_, idx, sc):
        off = jax.lax.axis_index(dist.data_axis) * b_local
        actions, log_q, slots = fused_sampler_pallas(
            seed_[0], eps_[0], idx, sc,
            num_samples=num_samples, num_items=num_items,
            sample_tile=sample_tile, interpret=interpret,
            row_offset=off,
        )
        return ProposalSample(actions=actions, log_q=log_q, topk_slot=slots)

    return jax.shard_map(
        local,
        mesh=dist.mesh,
        in_specs=(P(None), P(None), P(dist.data_axis, None), P(dist.data_axis, None)),
        out_specs=ProposalSample(
            actions=P(dist.data_axis, None),
            log_q=P(dist.data_axis, None),
            topk_slot=P(dist.data_axis, None),
        ),
        check_vma=False,
    )(
        seed.reshape(1),
        jnp.asarray(epsilon, jnp.float32).reshape(1),
        topk.indices,
        topk.scores,
    )


def dist_fopo_loss(
    policy: SoftmaxPolicy,
    params,
    key: jax.Array,
    x: jnp.ndarray,  # [B, Dx] — batch-sharded over `data`
    beta: jnp.ndarray,  # [P, L] — row-sharded over `model`
    reward_fn,
    cfg,  # FOPOConfig with cfg.dist set
    retriever=None,  # optional injected retriever (tests); None -> sharded
    epsilon: float | jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, dict]:
    """Algorithm 1 on the mesh — the `ExecutionPlan` skeleton with the
    dist hooks resolved (kept as the dist-level entry point; new code
    should resolve a plan once and call ``plan.execute``). jax.random
    sampling uses the same MixtureProposal / UniformProposal draws as
    the single-device path (identical keys => identical actions); with
    ``cfg.fused_sampler`` the per-data-shard in-kernel sampler draws
    the identical stream the single-device fused sampler does (see
    `dist_fused_mixture_sample`). Either way dist-vs-single parity is
    exact end to end."""
    from repro.core.plan import ExecutionPlan

    plan = ExecutionPlan.resolve(cfg, retriever=retriever)
    return plan.execute(policy, params, key, x, beta, reward_fn, epsilon=epsilon)


def dist_verdict_agree(verdict: jnp.ndarray, dist: DistConfig) -> jnp.ndarray:
    """Mesh agreement on a health verdict ([] int32 bitmask, replicated
    in): pmax over BOTH mesh axes, so if ANY shard saw a bad step every
    shard sees a nonzero verdict and takes the identical skip branch —
    sharded params can never diverge on a guarded step. pmax rather
    than the issue's psum: summing bitmasks aliases bits (2x ESS_COLLAPSE
    reads as GRAD_SPIKE|NONFINITE_*); pmax keeps a meaningful bitmask
    whenever the shards agree on WHICH check fired and guarantees
    any-bad -> all-bad always, which is the property the guard needs.
    Cheap enough to leave on: one scalar all-reduce per step."""

    def agree(v):
        v = jax.lax.pmax(v, dist.data_axis)
        return jax.lax.pmax(v, dist.model_axis)

    return jax.shard_map(
        agree,
        mesh=dist.mesh,
        in_specs=P(),
        out_specs=P(),
        check_vma=False,
    )(verdict)
