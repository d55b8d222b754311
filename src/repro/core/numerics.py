"""Shared numeric conventions.

The fused pipeline's parity with the reference path depends on both
sides using bit-identical formulas; anything used by more than one of
{core/sparsify, kernels/compress} lives here so the convention can only
be changed in one place.
"""
from __future__ import annotations

import jax.numpy as jnp

TINY = 1e-12


def safe_denom(denom, tiny: float = TINY):
    """Zero-safe divisor for REGTOP-k's Algorithm 1 line 5: |denom| <=
    tiny is replaced by -tiny for a negative denom and by +tiny
    otherwise. It is never zero: a zero divisor turns the posterior
    distortion into inf or NaN (0 * inf off the support of s^{t-1}),
    and a NaN score outranks every finite one in top_k."""
    return jnp.where(jnp.abs(denom) > tiny, denom,
                     jnp.where(denom < 0, -tiny, tiny))
