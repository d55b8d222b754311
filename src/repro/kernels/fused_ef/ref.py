"""Pure-jnp oracle for the fused error-feedback kernels (matches
core/sparsify.py REGTOP-k math exactly)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.numerics import safe_denom


def scores_ref(g, err, a_prev, g_agg, s_prev, *, omega, mu, q):
    g = g.astype(jnp.float32)
    a = err.astype(jnp.float32) + g
    denom = omega * a
    safe = safe_denom(denom)
    delta_sent = (g_agg.astype(jnp.float32) - omega * a_prev.astype(jnp.float32)) / safe
    delta = s_prev * delta_sent + q * (1.0 - s_prev)
    reg = jnp.tanh(jnp.abs(1.0 + delta) / mu)
    return a, a * reg


def apply_ref(a, mask):
    a = a.astype(jnp.float32)
    ghat = mask.astype(jnp.float32) * a
    return ghat, a - ghat
