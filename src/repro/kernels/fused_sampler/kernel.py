"""Pallas kernel: in-kernel eps-mixture sampling (Algorithm 1, step 4).

Draws the S proposal actions and their log-pmf from

    q_{K,eps}(a|x) = eps/P + (1-eps) kappa(a|x)   if a in topK(x)
                   = eps/P                        otherwise

directly on-chip from the retrieved (indices, scores) top-K rows, tiled
to the same (B, Sp/TS) grid as the tiled `snis_covgrad` kernels — so
the sampled ids / log-q tiles are produced aligned for the covariance
kernel instead of round-tripping HBM as a separate jax.random chain
over (B, S, K) Gumbel tensors.

Per tile of TS samples (all shapes ≥ 2-D for TPU layout):

  1. counter-based randomness: uniforms u_arm (TS, 1) / u_gum (TS, K)
     and full-width uniform-arm bits (TS, 1), all from a
     splitmix32-style hash of (seed, global counter). The hash
     is written in plain jnp integer ops on purpose: it compiles on
     TPU *and* runs under interpret mode on CPU — `pltpu.prng_seed` /
     `prng_random_bits` have no CPU lowering in this jax, which would
     make the whole sampler untestable off-TPU. Draws therefore differ
     from `jax.random` bit-wise but match the mixture pmf in
     distribution (statistically tested against the shared ref).
  2. kappa arm: Gumbel-argmax over the K resident scores; the winning
     slot is turned into a one-hot to select the catalog id (no
     in-kernel dynamic gather needed).
  3. uniform arm: 32 hash bits mod P (full item coverage at any
     realistic catalog size), arm-selected against eps.
  4. log-q: O(TS*K) membership check of the drawn id against the top-K
     row (a uniform-arm draw can land in the top-K and must then get
     the full mixture pmf), logaddexp mixture combine — the same math
     as `MixtureProposal.log_prob`, parity <= 1e-6.

The padded tail (positions >= S when TS does not divide S) is emitted
pre-masked — action = -1, log_q = LOG_Q_PAD — exactly the dead-slot
convention the covgrad kernels consume.

eps arrives as a (1, 1) operand so adaptive (traced) epsilon schedules
work unchanged; only 0 <= eps < 1 reaches this kernel (the execution
plan short-circuits the float eps >= 1 uniform proposal before
retrieval — a *traced* eps may pass through at any value, which the
arm selection and logaddexp combine handle exactly).

The counter hash is keyed by the GLOBAL batch row: ``row_offset``
(a (1, 1) operand, 0 on one device) shifts the grid's batch index, so
a data shard running rows [off, off + B_local) draws the exact stream
the single-device kernel draws for those rows — per-shard streams are
disjoint by construction (disjoint counter blocks) and reproducible
across mesh shapes (the counter depends only on the global row, the
global sample position and K).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.constants import LOG_Q_PAD

# splitmix32 finalizer constants (Steele et al. mix, 32-bit variant)
_GOLDEN = 0x9E3779B9
_MIX1 = 0x21F0AAAD
_MIX2 = 0x735A2D97


def _hash_u32(seed: jnp.ndarray, ctr: jnp.ndarray) -> jnp.ndarray:
    """Counter-based uint32 hash: distinct (seed, ctr) -> iid-ish bits."""
    x = seed + ctr * jnp.uint32(_GOLDEN)
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(_MIX1)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(_MIX2)
    x = x ^ (x >> jnp.uint32(15))
    return x


def _uniform01(seed: jnp.ndarray, ctr: jnp.ndarray) -> jnp.ndarray:
    """float32 uniforms in [0, 1) with 24 mantissa bits (cast through
    int32, exact below 2^24: Mosaic has no uint32 -> float32 cast)."""
    bits = (_hash_u32(seed, ctr) >> jnp.uint32(8)).astype(jnp.int32)
    return bits.astype(jnp.float32) * (
        1.0 / (1 << 24)
    )


def _fused_sampler_kernel(
    seed_ref,  # (1, 1) int32 — per-call PRNG seed
    eps_ref,  # (1, 1) float32 — mixture epsilon (may be traced upstream)
    off_ref,  # (1, 1) int32 — global row offset of this batch shard
    idx_ref,  # (1, K) int32 — top-K ids for context b (resident)
    scores_ref,  # (1, K) float32 — top-K scores for context b (resident)
    actions_ref,  # (1, TS) int32 out
    logq_ref,  # (1, TS) float32 out
    slot_ref,  # (1, TS) int32 out — top-K slot of kappa draws, -1 otherwise
    *,
    sample_tile: int,
    num_samples: int,
    num_items: int,
    top_k: int,
):
    # GLOBAL batch row: local grid row + shard offset, so the counter
    # stream is mesh-shape-invariant (see module docstring)
    i = pl.program_id(0) + off_ref[0, 0]
    j = pl.program_id(1)
    num_j = pl.num_programs(1)
    ts, k = sample_tile, top_k

    seed = seed_ref[0, 0].astype(jnp.uint32)
    eps = eps_ref[0, 0]

    # global sample position of each lane, in sublane-major (TS, 1) space
    pos = j * ts + jax.lax.broadcasted_iota(jnp.int32, (ts, 1), 0)  # (TS, 1)
    live = pos < num_samples
    # disjoint counter blocks: K + 2 streams per (batch, sample) pair
    ctr0 = ((i * (num_j * ts) + pos) * (k + 2)).astype(jnp.uint32)

    u_arm = _uniform01(seed, ctr0)  # (TS, 1)
    pos2 = j * ts + jax.lax.broadcasted_iota(jnp.int32, (ts, k), 0)
    ctr_g = ((i * (num_j * ts) + pos2) * (k + 2)).astype(jnp.uint32) + (
        jnp.uint32(2) + jax.lax.broadcasted_iota(jnp.int32, (ts, k), 1).astype(jnp.uint32)
    )
    u_gum = _uniform01(seed, ctr_g)  # (TS, K)

    # kappa arm: Gumbel-argmax over the resident top-K scores
    tiny = 1e-12  # keeps both logs finite at u in {0, 1}
    gum = -jnp.log(-jnp.log(u_gum + tiny) + tiny)
    scores_row = scores_ref[...]  # (1, K)
    slot = jnp.argmax(scores_row + gum, axis=-1, keepdims=True)  # (TS, 1)
    onehot = jax.lax.broadcasted_iota(jnp.int32, (ts, k), 1) == slot
    kappa_draw = jnp.sum(
        jnp.where(onehot, idx_ref[...], 0), axis=-1, keepdims=True
    )  # (TS, 1)

    # uniform arm + eps arm-selection. The draw uses the full 32 hash
    # bits modulo P — floor(u24 * P) would leave items unreachable past
    # P = 2^24 and quantise per-item mass well before that. Residual
    # modulo bias is <= P / 2^32 relative (negligible at catalog sizes
    # this sampler targets; use the jax.random path near int32 range).
    bits_uni = _hash_u32(seed, ctr0 + jnp.uint32(1))  # (TS, 1)
    uniform_draw = (bits_uni % jnp.uint32(num_items)).astype(jnp.int32)
    take_uniform = u_arm < eps
    action = jnp.where(take_uniform, uniform_draw, kappa_draw)  # (TS, 1)

    # log q at the draw: membership against the top-K row — a uniform-arm
    # draw inside the top-K set still gets the full mixture pmf
    hit = action == idx_ref[...]  # (TS, K)
    in_topk = hit.sum(axis=-1, keepdims=True) > 0  # (TS, 1)
    m = jnp.max(scores_row)
    log_z = m + jnp.log(jnp.sum(jnp.exp(scores_row - m)))
    log_kappa_full = scores_row - log_z  # (1, K) log softmax
    log_kappa = jnp.sum(
        jnp.where(hit, log_kappa_full, 0.0), axis=-1, keepdims=True
    )
    log_u = jnp.log(eps) - jnp.log(float(num_items))
    log_mix = jnp.logaddexp(log_u, jnp.log1p(-eps) + log_kappa)
    log_q = jnp.where(in_topk, log_mix, log_u)  # (TS, 1)

    # padded tail (pos >= S): pre-masked dead slots for the covgrad kernels
    action = jnp.where(live, action, -1)
    log_q = jnp.where(live, log_q, LOG_Q_PAD)
    slot_out = jnp.where(live & ~take_uniform, slot, -1)

    # (TS, 1) -> (1, TS): row-major flatten preserves sample order
    actions_ref[...] = action.reshape(1, ts)
    logq_ref[...] = log_q.reshape(1, ts)
    slot_ref[...] = slot_out.astype(jnp.int32).reshape(1, ts)


def fused_sampler_pallas(
    seed: jnp.ndarray,  # int32 scalar
    epsilon: jnp.ndarray,  # float32 scalar (may be traced)
    topk_indices: jnp.ndarray,  # [B, K] int32
    topk_scores: jnp.ndarray,  # [B, K] float32
    *,
    num_samples: int,
    num_items: int,
    sample_tile: int,
    interpret: bool = False,
    row_offset: int | jnp.ndarray = 0,
):
    """Returns (actions [B, Sp], log_q [B, Sp], topk_slot [B, Sp]) with
    Sp = ceil(S / TS) * TS; positions >= S are pre-masked dead slots.
    ``row_offset`` keys the counter hash by global batch row (see the
    module docstring): with offset o this call draws exactly the rows
    [o, o + B) of the offset-0 stream — the dist path's per-shard
    sampler."""
    b, k = topk_indices.shape
    ts = sample_tile
    num_j = -(-num_samples // ts)
    sp = num_j * ts
    kernel = functools.partial(
        _fused_sampler_kernel,
        sample_tile=ts,
        num_samples=num_samples,
        num_items=num_items,
        top_k=k,
    )
    # Mosaic blocks must match (8, 128) or the whole trailing dims, so
    # the per-row operands carry a unit dim — top-K rows as [B, 1, K],
    # sample tiles as [B, S/TS, 1, TS] — and the kernel body still sees
    # (1, K) and (1, TS) blocks; the three scalars ride whole in SMEM.
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    row = pl.BlockSpec((None, 1, k), lambda i, j: (i, 0, 0))
    tile = pl.BlockSpec((None, None, 1, ts), lambda i, j: (i, j, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(b, num_j),
        in_specs=[
            smem,  # seed
            smem,  # eps
            smem,  # row offset
            row,  # top-K ids (resident)
            row,  # top-K scores
        ],
        out_specs=[tile, tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((b, num_j, 1, ts), jnp.int32),
            jax.ShapeDtypeStruct((b, num_j, 1, ts), jnp.float32),
            jax.ShapeDtypeStruct((b, num_j, 1, ts), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")  # no cross-step state
        ),
        interpret=interpret,
    )(
        seed.reshape(1, 1).astype(jnp.int32),
        jnp.asarray(epsilon, jnp.float32).reshape(1, 1),
        jnp.asarray(row_offset, jnp.int32).reshape(1, 1),
        topk_indices.astype(jnp.int32).reshape(b, 1, k),
        topk_scores.astype(jnp.float32).reshape(b, 1, k),
    )
    return [o.reshape(b, sp) for o in out]
