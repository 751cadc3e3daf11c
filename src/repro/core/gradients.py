"""Policy-gradient estimators.

Three estimators, in decreasing per-step cost:

  * `exact_objective`      — dense sum over the catalog, O(P). Ground truth.
  * `reinforce_surrogate`  — REINFORCE with exact sampling from pi_theta and
                             a leave-one-out baseline, O(P) (paper baseline).
  * `covariance_surrogate` — the paper's estimator: SNIS + covariance
                             gradient, O(S*K), catalog-size-free.

Each returns a scalar *surrogate loss* whose jax.grad equals (minus) the
desired policy-gradient estimate, so any optimizer / AD machinery
composes. Coefficients inside surrogates are stop_grad'ed — exactly
Algorithm 1's semantics (weights are evaluated, not differentiated).

`covariance_surrogate(fused=True)` swaps the jnp chain for the Pallas
custom_vjp path (`fused_covariance_loss`): forward kernel gathers beta
in-kernel and the backward kernel regathers for dL/dh, so the
(B, S, L) gathered-embedding tensor never exists in HBM. The
``sample_tile`` knob selects the kernel tiling — TS > 1 gathers TS
catalog rows per grid step and folds them with one online-softmax
rescale (the fast path); 1 is the legacy per-sample tiling. See
`repro.kernels.snis_covgrad` for the architecture.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.plan import resolve_interpret
from repro.core.policy import SoftmaxPolicy
from repro.core.snis import (
    snis_covariance_coefficients,
    snis_diagnostics,
    snis_weights,
)
from repro.kernels.snis_covgrad import snis_covgrad_bwd, snis_scores_fused
from repro.kernels.snis_covgrad.ops import DEFAULT_SAMPLE_TILE


# ---------------------------------------------------------------------------
# exact (dense) objective — O(P)
# ---------------------------------------------------------------------------

def exact_objective(
    policy: SoftmaxPolicy,
    params,
    x: jnp.ndarray,  # [B, Dx]
    beta: jnp.ndarray,  # [P, L]
    rewards_dense: jnp.ndarray,  # [B, P] r_hat(a, x_i) for every action
) -> jnp.ndarray:
    """R_hat = mean_i sum_a pi(a|x_i) r(a, x_i); loss = -R_hat."""
    log_pi = policy.log_probs(params, x, beta)  # [B, P]
    return -jnp.mean(jnp.sum(jnp.exp(log_pi) * rewards_dense, axis=-1))


# ---------------------------------------------------------------------------
# REINFORCE baseline — O(P) sampling + O(P) log-prob normalisation
# ---------------------------------------------------------------------------

def reinforce_surrogate(
    policy: SoftmaxPolicy,
    params,
    key: jax.Array,
    x: jnp.ndarray,  # [B, Dx]
    beta: jnp.ndarray,  # [P, L]
    reward_fn,  # actions [B,S] -> [B,S]
    num_samples: int,
) -> jnp.ndarray:
    """grad = E_{a~pi}[(r - b) grad log pi(a|x)], leave-one-out baseline b."""
    actions = policy.sample(key, params, x, beta, num_samples)  # [B, S]
    rewards = jax.lax.stop_gradient(reward_fn(actions))  # [B, S]
    s = num_samples
    if s > 1:  # leave-one-out control variate
        baseline = (jnp.sum(rewards, axis=-1, keepdims=True) - rewards) / (s - 1)
    else:
        baseline = jnp.zeros_like(rewards)
    advantage = jax.lax.stop_gradient(rewards - baseline)
    log_pi = policy.log_probs(params, x, beta)  # [B, P] — the O(P) cost
    log_pi_a = jnp.take_along_axis(log_pi, actions, axis=-1)  # [B, S]
    return -jnp.mean(jnp.sum(advantage * log_pi_a, axis=-1) / s)


# ---------------------------------------------------------------------------
# the paper's estimator — SNIS covariance gradient, O(S*K)
# ---------------------------------------------------------------------------

def covariance_surrogate(
    policy: SoftmaxPolicy,
    params,
    x: jnp.ndarray,  # [B, Dx]
    beta: jnp.ndarray,  # [P, L] (fixed — Assumption 1)
    actions: jnp.ndarray,  # [B, S] proposal draws
    log_q: jnp.ndarray,  # [B, S] proposal log-pmf at the draws
    rewards: jnp.ndarray,  # [B, S]
    *,
    fused: bool = False,
    fused_interpret: bool | None = None,
    sample_tile: int = DEFAULT_SAMPLE_TILE,
    dist=None,
) -> tuple[jnp.ndarray, dict]:
    """Surrogate whose gradient is the SNIS covariance gradient.

    grad_theta = sum_s c_s grad_theta f_theta(a_s, x),
    c_s = stop_grad(wbar_s (r_s - rbar)) — see snis.py. Returns aux
    diagnostics (ESS, rbar) for monitoring.

    ``fused=True`` routes through the Pallas custom_vjp path
    (`fused_covariance_loss`): the beta gather happens in-kernel and the
    (B, S, L) gathered-embedding tensor never reaches HBM. Requires the
    bilinear score form f = h . beta_a (SoftmaxPolicy's contract), and
    treats beta as *fixed* (Assumption 1): its cotangent is hard zero,
    whereas the unfused path lets jax.grad differentiate wrt beta too.
    ``fused_interpret=None`` auto-selects interpret mode off-TPU;
    ``sample_tile`` picks the kernel tiling (see module docstring).
    ``dist=DistConfig(...)`` selects the multi-device twin instead
    (`repro.dist.fopo`): same fused kernels per beta shard, SNIS score
    partials psum'd once — same contract, catalog sharded over the mesh.

    Masked slots (``action = -1`` / ``log_q = LOG_Q_PAD``) carry exactly
    zero weight in BOTH paths, including rows where every slot is masked
    (those contribute an exactly-zero loss term and gradient row).
    """
    if dist is not None:
        # multi-device twin: same contract as fused=True (beta fixed,
        # gradients to h only), kernels running per beta shard
        from repro.dist.fopo import dist_fused_covariance_loss

        h = policy.user_embedding(params, x)
        return dist_fused_covariance_loss(
            h, beta, actions, log_q, rewards,
            dist=dist, interpret=fused_interpret, sample_tile=sample_tile,
        )
    if fused:
        h = policy.user_embedding(params, x)  # [B, L] differentiable
        return fused_covariance_loss(
            h, beta, actions, log_q, rewards,
            interpret=fused_interpret, sample_tile=sample_tile,
        )
    valid = actions >= 0
    scores = policy.scores_at(
        params, x, beta, jnp.maximum(actions, 0)
    )  # [B, S] differentiable; clamp keeps masked gathers in-bounds
    w = snis_weights(jax.lax.stop_gradient(scores), log_q, valid=valid)
    coeff = snis_covariance_coefficients(w.wbar, rewards)  # [B, S]
    coeff = jax.lax.stop_gradient(coeff)
    # maximise covariance between reward and score direction => minimise -sum
    loss = -jnp.mean(jnp.sum(coeff * scores, axis=-1))
    return loss, snis_diagnostics(w.wbar, rewards)


# ---------------------------------------------------------------------------
# fused Pallas path — custom_vjp over the gather-fused kernels
# ---------------------------------------------------------------------------

def _fused_loss_pieces(interpret, sample_tile, h, beta, actions, log_q, rewards):
    scores = snis_scores_fused(
        h, beta, actions, log_q, rewards,
        interpret=interpret, sample_tile=sample_tile,
    )  # forward kernel: in-kernel gather, no (B, S, L) in HBM
    # exactly 0 on masked slots — the explicit mask also covers rows
    # where EVERY slot is masked (bare softmax would emit 1/S there)
    wbar = jax.nn.softmax(scores - log_q, axis=-1) * (actions >= 0)
    coeff = snis_covariance_coefficients(wbar, rewards)
    loss = -jnp.mean(jnp.sum(coeff * scores, axis=-1))
    return loss, snis_diagnostics(wbar, rewards), coeff


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _fused_covariance_loss(interpret, sample_tile, h, beta, actions, log_q, rewards):
    loss, aux, _ = _fused_loss_pieces(
        interpret, sample_tile, h, beta, actions, log_q, rewards
    )
    return loss, aux


def _fused_covariance_loss_fwd(interpret, sample_tile, h, beta, actions, log_q, rewards):
    loss, aux, coeff = _fused_loss_pieces(
        interpret, sample_tile, h, beta, actions, log_q, rewards
    )
    return (loss, aux), (coeff, actions, beta)


def _fused_covariance_loss_bwd(interpret, sample_tile, res, ct):
    coeff, actions, beta = res
    ct_loss = ct[0]  # aux cotangents are diagnostics — discarded
    batch = coeff.shape[0]
    # per-sample score gradients dL/df_{bs}; Algorithm 1 evaluates the
    # SNIS coefficients, it does not differentiate them
    g_scores = (-ct_loss / batch) * coeff
    grad_h = snis_covgrad_bwd(
        g_scores, actions, beta, interpret=interpret, sample_tile=sample_tile
    )
    return (
        grad_h,
        jnp.zeros_like(beta),  # fixed embeddings (Assumption 1); DCE'd
        np.zeros(actions.shape, dtype=jax.dtypes.float0),
        jnp.zeros_like(g_scores),  # log_q: weights are evaluated, not diff'd
        jnp.zeros_like(g_scores),  # rewards: logged feedback, constant
    )


_fused_covariance_loss.defvjp(_fused_covariance_loss_fwd, _fused_covariance_loss_bwd)


def fused_covariance_loss(
    h: jnp.ndarray,  # [B, L] user embeddings (differentiable)
    beta: jnp.ndarray,  # [P, L] fixed item embeddings
    actions: jnp.ndarray,  # [B, S] int32; -1 marks masked slots
    log_q: jnp.ndarray,  # [B, S]; LOG_Q_PAD on masked slots
    rewards: jnp.ndarray,  # [B, S]
    *,
    interpret: bool | None = None,
    sample_tile: int = DEFAULT_SAMPLE_TILE,
) -> tuple[jnp.ndarray, dict]:
    """The fused FOPO step: (loss, aux) with a custom VJP whose backward
    runs the Pallas gather-reduce kernel. Composes with jax.grad /
    optimizers; gradients flow to ``h`` only (the user-tower chain rule
    continues from there). ``sample_tile`` > 1 selects the tiled kernels
    (TS-row gather tiles per grid step — the fast path); 1 the
    per-sample kernels. Both tilings are numerically matched.

    CONTRACT (Assumption 1): ``beta`` is a *fixed* embedding table — its
    cotangent is hard zero here, unlike the unfused path where jax.grad
    wrt beta returns the true scatter gradient. Do not use ``fused=True``
    to fine-tune item embeddings."""
    return _fused_covariance_loss(
        resolve_interpret(interpret), sample_tile, h, beta, actions, log_q, rewards
    )


def covariance_gradient_dense_reference(
    policy: SoftmaxPolicy,
    params,
    x: jnp.ndarray,
    beta: jnp.ndarray,
    rewards_dense: jnp.ndarray,  # [B, P]
):
    """O(P) closed form of Cov_pi[r, grad f] for tests: must equal
    -grad exact_objective (the covariance identity, Eq. 8)."""

    def neg_obj(p):
        return exact_objective(policy, p, x, beta, rewards_dense)

    return jax.grad(neg_obj)(params)
