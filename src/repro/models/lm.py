"""Transformer LM family — one implementation covering all five assigned
LM architectures (dense GQA, MoE top-k, Gemma-2 local/global alternating
+ logit soft-caps, Arctic dense-residual MoE).

Design for scale:
  * layer params are stacked [n_layers, ...] and the forward is a
    lax.scan over layers (compact HLO — an 88-layer 123B model lowers in
    seconds) with optional jax.checkpoint (remat) per layer;
  * training uses microbatched gradient accumulation (scan) so the
    activation working set is bounded regardless of global batch;
  * everything is pure functions over a params pytree; sharding is
    applied externally (repro/dist) via PartitionSpec trees that mirror
    the params structure.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.models.attention import decode_attention, flash_attention
from repro.models.configs_base import LMConfig
from repro.models.layers import rms_norm, rope, softcap
from repro.models.moe import moe_ffn


class KVCache(NamedTuple):
    k: jnp.ndarray  # [n_layers, B, S, KV, Dh]
    v: jnp.ndarray  # [n_layers, B, S, KV, Dh]
    length: jnp.ndarray  # [] int32 — filled prefix


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg: LMConfig, key: jax.Array) -> Any:
    """Real initialisation (smoke tests / small configs)."""
    dtype = jnp.dtype(cfg.dtype)
    d, dh, h, kv = cfg.d_model, cfg.dh, cfg.num_heads, cfg.num_kv_heads
    n = cfg.num_layers
    keys = iter(jax.random.split(key, 32))

    def mat(k_, shape, fan_in):
        s = 1.0 / jnp.sqrt(jnp.asarray(fan_in, jnp.float32))
        return (jax.random.normal(k_, shape, jnp.float32) * s).astype(dtype)

    layers = {
        "attn_norm": jnp.zeros((n, d), dtype),
        "mlp_norm": jnp.zeros((n, d), dtype),
        "wq": mat(next(keys), (n, d, h * dh), d),
        "wk": mat(next(keys), (n, d, kv * dh), d),
        "wv": mat(next(keys), (n, d, kv * dh), d),
        "wo": mat(next(keys), (n, h * dh, d), h * dh),
    }
    if cfg.num_experts:
        eff = cfg.moe_d_ff or cfg.d_ff
        layers.update(
            router=mat(next(keys), (n, d, cfg.num_experts), d),
            we_gate=mat(next(keys), (n, cfg.num_experts, d, eff), d),
            we_up=mat(next(keys), (n, cfg.num_experts, d, eff), d),
            we_down=mat(next(keys), (n, cfg.num_experts, eff, d), eff),
        )
        if cfg.dense_residual:
            layers.update(
                w_gate=mat(next(keys), (n, d, cfg.d_ff), d),
                w_up=mat(next(keys), (n, d, cfg.d_ff), d),
                w_down=mat(next(keys), (n, cfg.d_ff, d), cfg.d_ff),
            )
    else:
        layers.update(
            w_gate=mat(next(keys), (n, d, cfg.d_ff), d),
            w_up=mat(next(keys), (n, d, cfg.d_ff), d),
            w_down=mat(next(keys), (n, cfg.d_ff, d), cfg.d_ff),
        )
    params = {
        "embed": mat(next(keys), (cfg.vocab_size, d), d),
        "final_norm": jnp.zeros((d,), dtype),
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        params["unembed"] = mat(next(keys), (cfg.vocab_size, d), d)
    return params


def abstract_params(cfg: LMConfig) -> Any:
    """ShapeDtypeStruct pytree — dry-run lowering without allocation."""
    return jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _self_attention(cfg: LMConfig, q, k_, v_, *, window):
    """Dispatch: scan-flash (baseline, pure XLA) vs the Pallas fused
    kernel under shard_map (§Perf variant — use_flash_kernel)."""
    if not cfg.use_flash_kernel:
        return flash_attention(
            q, k_, v_, causal=True, window=window,
            logit_cap=cfg.attn_logit_softcap,
        )
    from jax.sharding import PartitionSpec as P

    from repro.kernels.flash_attention import ops as fa_ops

    n_rep = cfg.num_heads // cfg.num_kv_heads
    if n_rep > 1:  # repeat BEFORE sharding so head mapping stays aligned
        k_ = jnp.repeat(k_, n_rep, axis=2)
        v_ = jnp.repeat(v_, n_rep, axis=2)

    # fold (batch, head) into ONE axis and shard it over the flattened
    # mesh: avoids model-axis redundancy when num_heads < mesh model size
    # (gemma-2's 8 heads vs 16 shards would replicate attention 16x)
    b, s, h_tot, dh = q.shape
    bh = b * h_tot
    from repro.dist.sharding import AXIS_SIZES

    # prefer the unfolded (B, S, H, dh) layout with heads sharded over
    # `model` (no data movement — q/k/v already arrive in that sharding);
    # fall back to the folded BH layout only when heads don't divide the
    # model axis (gemma-2's 8 heads vs 16 shards would otherwise REPLICATE
    # attention 16x — measured in §Perf D)
    if cfg.flash_axes and h_tot % AXIS_SIZES["model"] == 0:
        from jax.sharding import PartitionSpec as P2

        spec = P2(cfg.flash_axes, None, "model", None)

        def local_u(q_, k2, v2):
            return fa_ops.flash_attention(
                q_, k2, v2, causal=True, window=window,
                logit_cap=cfg.attn_logit_softcap,
            )

        return jax.shard_map(
            local_u, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )(q, k_, v_)

    axes = None
    if cfg.flash_axes:  # empty = single-device / no shard_map
        for cand in (cfg.flash_axes + ("model",), cfg.flash_axes):
            size = 1
            for a in cand:
                size *= AXIS_SIZES[a]
            if cand and bh % size == 0:
                axes = cand
                break

    def fold(x):  # [B, S, H, dh] -> [BH, S, 1, dh]
        return x.transpose(0, 2, 1, 3).reshape(bh, s, 1, dh)

    def unfold(x):  # [BH, S, 1, dh] -> [B, S, H, dh]
        return x.reshape(b, h_tot, s, dh).transpose(0, 2, 1, 3)

    def local(q_, k2, v2):
        return fa_ops.flash_attention(
            q_, k2, v2, causal=True, window=window,
            logit_cap=cfg.attn_logit_softcap,
        )

    if axes is None:
        return unfold(local(fold(q), fold(k_), fold(v_)))
    spec = P(axes, None, None, None)
    out = jax.shard_map(
        local, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(fold(q), fold(k_), fold(v_))
    return unfold(out)


def _layer_fwd(cfg: LMConfig, x, layer, is_local, positions, static_window="auto"):
    """One transformer block. x: [B, S, d]. static_window != "auto" pins
    the attention window at trace time (pair-scan §Perf variant — avoids
    the compute-both-and-select cost of alternating archs)."""
    b, s, d = x.shape
    h, kv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.dh
    y = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
    q = (y @ layer["wq"]).reshape(b, s, h, dh)
    k_ = (y @ layer["wk"]).reshape(b, s, kv, dh)
    v_ = (y @ layer["wv"]).reshape(b, s, kv, dh)
    q = rope(q, positions, cfg.rope_theta)
    k_ = rope(k_, positions, cfg.rope_theta)
    if static_window != "auto":
        att = _self_attention(cfg, q, k_, v_, window=static_window)
    elif cfg.local_global_alternating and cfg.sliding_window:
        # compute with the window mask and without; select by layer parity.
        # masks are applied inside the chunked kernel so this costs 2x attn
        # on alternating archs only when lowered naively; the dry-run
        # optimized variant specialises per-parity (see §Perf).
        att_local = _self_attention(cfg, q, k_, v_, window=cfg.sliding_window)
        att_global = _self_attention(cfg, q, k_, v_, window=None)
        att = jnp.where(is_local, att_local, att_global)
    else:
        att = _self_attention(cfg, q, k_, v_, window=cfg.sliding_window)
    x = x + att.reshape(b, s, h * dh) @ layer["wo"]

    y = rms_norm(x, layer["mlp_norm"], cfg.rms_eps)
    aux = {}
    if cfg.num_experts:
        flat = y.reshape(b * s, d)
        out, aux = moe_ffn(
            flat, layer["router"], layer["we_gate"], layer["we_up"],
            layer["we_down"], num_experts_per_tok=cfg.num_experts_per_tok,
            capacity_factor=cfg.capacity_factor, act=cfg.gated_act,
        )
        ffn_out = out.reshape(b, s, d)
        if cfg.dense_residual:
            from repro.models.layers import gated_mlp

            ffn_out = ffn_out + gated_mlp(
                y, layer["w_gate"], layer["w_up"], layer["w_down"], cfg.gated_act
            )
    else:
        from repro.models.layers import gated_mlp

        ffn_out = gated_mlp(
            y, layer["w_gate"], layer["w_up"], layer["w_down"], cfg.gated_act
        )
    x = x + ffn_out
    return x, aux.get("aux_loss", jnp.zeros((), jnp.float32))


def forward(cfg: LMConfig, params, tokens: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """tokens [B, S] -> (logits [B, S, V], moe_aux_loss)."""
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(jnp.sqrt(float(cfg.d_model)), x.dtype)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    is_local_flags = (
        (jnp.arange(cfg.num_layers) % 2 == 0)
        if cfg.local_global_alternating
        else jnp.zeros((cfg.num_layers,), bool)
    )

    def body(carry, inp):
        layer, is_local = inp
        fn = lambda c, lyr: _layer_fwd(cfg, c, lyr, is_local, positions)
        if cfg.remat:
            fn = jax.checkpoint(fn)
        x_new, aux = fn(carry, layer)
        return x_new, aux

    pair_ok = (
        cfg.pair_scan and cfg.local_global_alternating and cfg.scan_layers
        and cfg.num_layers % 2 == 0
    )
    if pair_ok:
        # §Perf: scan (local, global) layer PAIRS with static windows —
        # one attention per layer instead of compute-both-and-select
        pair_params = jax.tree.map(
            lambda p: p.reshape((cfg.num_layers // 2, 2) + p.shape[1:]),
            params["layers"],
        )

        def pair_body(carry, pair_layer):
            l0 = jax.tree.map(lambda p: p[0], pair_layer)
            l1 = jax.tree.map(lambda p: p[1], pair_layer)
            f0 = lambda c, lyr: _layer_fwd(
                cfg, c, lyr, False, positions, static_window=cfg.sliding_window
            )
            f1 = lambda c, lyr: _layer_fwd(
                cfg, c, lyr, False, positions, static_window=None
            )
            if cfg.remat:
                f0, f1 = jax.checkpoint(f0), jax.checkpoint(f1)
            x1, a0 = f0(carry, l0)
            x2, a1 = f1(x1, l1)
            return x2, a0 + a1

        x, auxes = jax.lax.scan(pair_body, x, pair_params)
        aux_loss = jnp.sum(auxes)
    elif cfg.scan_layers:
        x, auxes = jax.lax.scan(body, x, (params["layers"], is_local_flags))
        aux_loss = jnp.sum(auxes)
    else:
        aux_loss = jnp.zeros((), jnp.float32)
        for i in range(cfg.num_layers):
            layer_i = jax.tree.map(lambda p: p[i], params["layers"])
            x, a = body(x, (layer_i, is_local_flags[i]))
            aux_loss = aux_loss + a

    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    unembed = params.get("unembed", params["embed"])
    logits = x @ unembed.T
    logits = softcap(logits, cfg.final_logit_softcap)
    return logits, aux_loss


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def loss_fn(cfg: LMConfig, params, tokens, labels) -> jnp.ndarray:
    logits, aux_loss = forward(cfg, params, tokens)
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)  # [B, S]
    gold = jnp.take_along_axis(
        logits.astype(jnp.float32), labels[..., None], axis=-1
    )[..., 0]
    ce = jnp.mean(logz - gold)
    return ce + 0.01 * aux_loss


def make_train_step(cfg: LMConfig, optimizer):
    """(params, opt_state, tokens, labels) -> (params, opt_state, loss).
    Microbatched gradient accumulation when cfg.microbatch > 0."""

    def train_step(params, opt_state, tokens, labels):
        b = tokens.shape[0]
        mb = cfg.microbatch or b
        n_micro = max(1, b // mb)
        if n_micro == 1:
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(cfg, p, tokens, labels)
            )(params)
        else:
            # strided microbatch split: micro j takes rows {j, n_micro+j, ...}
            # so every microbatch spans all data shards (batch is sharded
            # contiguously over dp) — a plain reshape would give each
            # microbatch exactly one shard's rows and serialise DP.
            tk = tokens.reshape(mb, n_micro, -1).swapaxes(0, 1)
            lb = labels.reshape(mb, n_micro, -1).swapaxes(0, 1)

            def micro(carry, inp):
                g_acc, l_acc = carry
                t_, y_ = inp
                l, g = jax.value_and_grad(lambda p: loss_fn(cfg, p, t_, y_))(params)
                return (
                    jax.tree.map(jnp.add, g_acc, g),
                    l_acc + l,
                ), None

            g0 = jax.tree.map(jnp.zeros_like, params)
            (grads, loss), _ = jax.lax.scan(micro, (g0, jnp.zeros(())), (tk, lb))
            grads = jax.tree.map(lambda g: g / n_micro, grads)
            loss = loss / n_micro
        params, opt_state = optimizer.update(grads, opt_state, params)
        return params, opt_state, loss

    return train_step


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------

def init_cache(cfg: LMConfig, batch: int, max_len: int, dtype=None) -> KVCache:
    dt = jnp.dtype(dtype or cfg.dtype)
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.dh)
    return KVCache(
        k=jnp.zeros(shape, dt), v=jnp.zeros(shape, dt),
        length=jnp.zeros((), jnp.int32),
    )


def abstract_cache(cfg: LMConfig, batch: int, max_len: int) -> KVCache:
    dt = jnp.dtype(cfg.dtype)
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.dh)
    return KVCache(
        k=jax.ShapeDtypeStruct(shape, dt),
        v=jax.ShapeDtypeStruct(shape, dt),
        length=jax.ShapeDtypeStruct((), jnp.int32),
    )


def prefill(cfg: LMConfig, params, tokens: jnp.ndarray, cache: KVCache,
            *, return_hidden: bool = False):
    """Process a full prompt, fill the cache, return last-position
    logits — or, with ``return_hidden``, the last-position hidden state
    [B, d] (post final norm, pre unembed): the serve route's MIPS query
    over the unembed rows. `softcap` is strictly monotonic, so top-k
    over ``hidden @ unembed.T`` preserves the logits' argmax ordering."""
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(jnp.sqrt(float(cfg.d_model)), x.dtype)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    is_local_flags = (
        (jnp.arange(cfg.num_layers) % 2 == 0)
        if cfg.local_global_alternating
        else jnp.zeros((cfg.num_layers,), bool)
    )
    h, kv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.dh

    def body(carry, inp):
        layer, is_local = inp
        y = rms_norm(carry, layer["attn_norm"], cfg.rms_eps)
        q = rope((y @ layer["wq"]).reshape(b, s, h, dh), positions, cfg.rope_theta)
        k_ = rope((y @ layer["wk"]).reshape(b, s, kv, dh), positions, cfg.rope_theta)
        v_ = (y @ layer["wv"]).reshape(b, s, kv, dh)
        if cfg.local_global_alternating and cfg.sliding_window:
            att_l = _self_attention(cfg, q, k_, v_, window=cfg.sliding_window)
            att_g = _self_attention(cfg, q, k_, v_, window=None)
            att = jnp.where(is_local, att_l, att_g)
        else:
            att = _self_attention(cfg, q, k_, v_, window=cfg.sliding_window)
        x2 = carry + att.reshape(b, s, h * dh) @ layer["wo"]
        y2 = rms_norm(x2, layer["mlp_norm"], cfg.rms_eps)
        if cfg.num_experts:
            out, _ = moe_ffn(
                y2.reshape(b * s, -1), layer["router"], layer["we_gate"],
                layer["we_up"], layer["we_down"],
                num_experts_per_tok=cfg.num_experts_per_tok,
                capacity_factor=cfg.capacity_factor, act=cfg.gated_act,
            )
            ffn_out = out.reshape(b, s, -1)
            if cfg.dense_residual:
                from repro.models.layers import gated_mlp

                ffn_out = ffn_out + gated_mlp(y2, layer["w_gate"], layer["w_up"], layer["w_down"], cfg.gated_act)
        else:
            from repro.models.layers import gated_mlp

            ffn_out = gated_mlp(y2, layer["w_gate"], layer["w_up"], layer["w_down"], cfg.gated_act)
        x2 = x2 + ffn_out
        return x2, (k_, v_)

    if cfg.scan_layers:
        x, (k_all, v_all) = jax.lax.scan(body, x, (params["layers"], is_local_flags))
    else:
        ks, vs = [], []
        for i in range(cfg.num_layers):
            layer_i = jax.tree.map(lambda p: p[i], params["layers"])
            x, (k_, v_) = body(x, (layer_i, is_local_flags[i]))
            ks.append(k_)
            vs.append(v_)
        k_all, v_all = jnp.stack(ks), jnp.stack(vs)

    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    if return_hidden:
        out = x[:, -1]
    else:
        unembed = params.get("unembed", params["embed"])
        out = softcap(x[:, -1] @ unembed.T, cfg.final_logit_softcap)
    max_len = cache.k.shape[2]
    new_cache = KVCache(
        k=jax.lax.dynamic_update_slice(
            cache.k, k_all.astype(cache.k.dtype), (0, 0, 0, 0, 0)
        ),
        v=jax.lax.dynamic_update_slice(
            cache.v, v_all.astype(cache.v.dtype), (0, 0, 0, 0, 0)
        ),
        length=jnp.asarray(s, jnp.int32),
    )
    return out, new_cache


def decode_step(cfg: LMConfig, params, token: jnp.ndarray, cache: KVCache,
                *, return_hidden: bool = False):
    """One decode step. token [B] -> (logits [B, V], cache'); with
    ``return_hidden`` the hidden state [B, d] instead of logits (see
    `prefill` — same serve-route MIPS query)."""
    b = token.shape[0]
    h, kv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.dh
    x = jnp.take(params["embed"], token[:, None], axis=0)  # [B,1,d]
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(jnp.sqrt(float(cfg.d_model)), x.dtype)
    pos = jnp.broadcast_to(cache.length[None, None], (b, 1))
    is_local_flags = (
        (jnp.arange(cfg.num_layers) % 2 == 0)
        if cfg.local_global_alternating
        else jnp.zeros((cfg.num_layers,), bool)
    )

    def body(x_, inp, static_window="auto"):
        layer, is_local, k_c, v_c = inp
        y = rms_norm(x_, layer["attn_norm"], cfg.rms_eps)
        q = rope((y @ layer["wq"]).reshape(b, 1, h, dh), pos, cfg.rope_theta)
        k_new = rope((y @ layer["wk"]).reshape(b, 1, kv, dh), pos, cfg.rope_theta)
        v_new = (y @ layer["wv"]).reshape(b, 1, kv, dh)
        k_c = jax.lax.dynamic_update_slice(
            k_c, k_new.astype(k_c.dtype), (0, cache.length, 0, 0)
        )
        v_c = jax.lax.dynamic_update_slice(
            v_c, v_new.astype(v_c.dtype), (0, cache.length, 0, 0)
        )
        window = cfg.sliding_window if cfg.sliding_window else None
        if static_window != "auto":
            att = decode_attention(q, k_c, v_c, cache.length + 1, window=static_window, logit_cap=cfg.attn_logit_softcap, gqa_einsum=cfg.decode_gqa_einsum, slice_window=True)
        elif cfg.local_global_alternating and window:
            att_l = decode_attention(q, k_c, v_c, cache.length + 1, window=window, logit_cap=cfg.attn_logit_softcap, gqa_einsum=cfg.decode_gqa_einsum)
            att_g = decode_attention(q, k_c, v_c, cache.length + 1, window=None, logit_cap=cfg.attn_logit_softcap, gqa_einsum=cfg.decode_gqa_einsum)
            att = jnp.where(is_local, att_l, att_g)
        else:
            att = decode_attention(q, k_c, v_c, cache.length + 1, window=window, logit_cap=cfg.attn_logit_softcap, gqa_einsum=cfg.decode_gqa_einsum)
        x2 = x_ + att.reshape(b, 1, h * dh) @ layer["wo"]
        y2 = rms_norm(x2, layer["mlp_norm"], cfg.rms_eps)
        if cfg.num_experts:
            out, _ = moe_ffn(
                y2.reshape(b, -1), layer["router"], layer["we_gate"],
                layer["we_up"], layer["we_down"],
                num_experts_per_tok=cfg.num_experts_per_tok,
                capacity_factor=max(cfg.capacity_factor, 2.0),
                act=cfg.gated_act,
            )
            ffn_out = out.reshape(b, 1, -1)
            if cfg.dense_residual:
                from repro.models.layers import gated_mlp

                ffn_out = ffn_out + gated_mlp(y2, layer["w_gate"], layer["w_up"], layer["w_down"], cfg.gated_act)
        else:
            from repro.models.layers import gated_mlp

            ffn_out = gated_mlp(y2, layer["w_gate"], layer["w_up"], layer["w_down"], cfg.gated_act)
        return x2 + ffn_out, (k_c, v_c)

    pair_ok = (
        cfg.pair_scan and cfg.local_global_alternating and cfg.scan_layers
        and cfg.num_layers % 2 == 0
    )
    if pair_ok:
        # §Perf: per-parity static windows — local layers read only the
        # last `window` cache entries instead of computing both variants
        pair = lambda p: p.reshape((cfg.num_layers // 2, 2) + p.shape[1:])
        layers_p = jax.tree.map(pair, params["layers"])

        def pair_body(x_, inp):
            pl_, kc, vc = inp
            l0 = jax.tree.map(lambda p: p[0], pl_)
            l1 = jax.tree.map(lambda p: p[1], pl_)
            x_, (k0, v0) = body(
                x_, (l0, False, kc[0], vc[0]), static_window=cfg.sliding_window
            )
            x_, (k1, v1) = body(x_, (l1, False, kc[1], vc[1]), static_window=None)
            return x_, (jnp.stack([k0, k1]), jnp.stack([v0, v1]))

        x, (k_all, v_all) = jax.lax.scan(
            pair_body, x, (layers_p, pair(cache.k), pair(cache.v))
        )
        k_all = k_all.reshape((cfg.num_layers,) + k_all.shape[2:])
        v_all = v_all.reshape((cfg.num_layers,) + v_all.shape[2:])
    elif cfg.scan_layers:
        x, (k_all, v_all) = jax.lax.scan(
            body, x, (params["layers"], is_local_flags, cache.k, cache.v)
        )
    else:
        ks, vs = [], []
        for i in range(cfg.num_layers):
            layer_i = jax.tree.map(lambda p: p[i], params["layers"])
            x, (k_, v_) = body(x, (layer_i, is_local_flags[i], cache.k[i], cache.v[i]))
            ks.append(k_)
            vs.append(v_)
        k_all, v_all = jnp.stack(ks), jnp.stack(vs)

    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    if return_hidden:
        out = x[:, 0]
    else:
        unembed = params.get("unembed", params["embed"])
        out = softcap(x[:, 0] @ unembed.T, cfg.final_logit_softcap)
    return out, KVCache(k=k_all, v=v_all, length=cache.length + 1)
