"""Matrix products of the plain references at a stated precision.

``highest`` is float32 throughout. ``high`` is the TPU's three-pass
bfloat16 product written out (each float32 operand split into a
bfloat16 head and a bfloat16 tail; the tail-by-tail product dropped),
so the control computes the same arithmetic on any backend, the CPU of
a test run included.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "high")


def _split(x):
    head = x.astype(jnp.bfloat16).astype(jnp.float32)
    tail = (x - head).astype(jnp.bfloat16).astype(jnp.float32)
    return head, tail


def einsum(spec: str, a, b, precision: str):
    """``jnp.einsum(spec, a, b)`` at ``precision`` (see module doc)."""
    hi = jax.lax.Precision.HIGHEST
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=hi)
    if precision == "high":
        ah, al = _split(a)
        bh, bl = _split(b)
        return (jnp.einsum(spec, ah, bh, precision=hi)
                + jnp.einsum(spec, ah, bl, precision=hi)
                + jnp.einsum(spec, al, bh, precision=hi))
    raise ValueError(f"unknown precision {precision!r} (one of {PRECISIONS})")


def matmul(a, b, precision: str):
    """``a @ b`` for a [..., K] and b [K, N]."""
    return einsum("...k,kn->...n", a, b, precision)
