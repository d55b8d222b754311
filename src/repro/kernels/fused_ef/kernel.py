"""Pallas TPU kernels: fused REGTOP-k error-feedback passes.

Superseded as the production fused path by repro.kernels.compress (the
two-sweep pipeline behind SparsifierConfig.pipeline="fused"); kept as
standalone, individually-testable building blocks.

Two elementwise fused passes over the flat gradient (DESIGN.md §2.2):

1. ``scores``: a = err + g; Delta = s_prev*(g_agg - w*a_prev)/(w*a) +
   Q*(1-s_prev); score = a * tanh(|1+Delta|/mu). One read per input, one
   write per output — replaces ~6 XLA-boundary HBM passes.
2. ``apply``: ghat = mask*a; err' = a - ghat.

Scalars (omega, mu, Q) are compile-time constants (config values), baked
into the kernel body. Block layout: rows of (1, BLOCK) fp32, VMEM-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.numerics import safe_denom

BLOCK = 8 * 128 * 4


def _scores_kernel(g_ref, err_ref, a_prev_ref, g_agg_ref, s_prev_ref,
                   a_ref, score_ref, *, omega: float, mu: float, q: float):
    g = g_ref[...].astype(jnp.float32)
    err = err_ref[...].astype(jnp.float32)
    a_prev = a_prev_ref[...].astype(jnp.float32)
    g_agg = g_agg_ref[...].astype(jnp.float32)
    s_prev = s_prev_ref[...].astype(jnp.float32)
    a = err + g
    denom = omega * a
    safe = safe_denom(denom)
    delta_sent = (g_agg - omega * a_prev) / safe
    delta = s_prev * delta_sent + q * (1.0 - s_prev)
    reg = jnp.tanh(jnp.abs(1.0 + delta) / mu)
    a_ref[...] = a
    score_ref[...] = a * reg


def _apply_kernel(a_ref, mask_ref, ghat_ref, err_ref):
    a = a_ref[...].astype(jnp.float32)
    m = mask_ref[...].astype(jnp.float32)
    ghat = m * a
    ghat_ref[...] = ghat
    err_ref[...] = a - ghat


def _rows(j: int) -> int:
    assert j % BLOCK == 0, j
    return j // BLOCK


def scores_pallas(g, err, a_prev, g_agg, s_prev, *, omega: float, mu: float,
                  q: float, interpret=None):
    """All inputs (J,) fp32, J % BLOCK == 0. Returns (a, score).

    interpret=None auto-selects from the JAX backend."""
    if interpret is None:
        from repro.kernels.common import auto_interpret
        interpret = auto_interpret()
    rows = _rows(g.shape[0])
    rs = lambda x: x.reshape(rows, BLOCK)
    spec = pl.BlockSpec((1, BLOCK), lambda i: (i, 0))
    a, score = pl.pallas_call(
        functools.partial(_scores_kernel, omega=omega, mu=mu, q=q),
        grid=(rows,),
        in_specs=[spec] * 5,
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((rows, BLOCK), jnp.float32)] * 2,
        interpret=interpret,
    )(rs(g), rs(err), rs(a_prev), rs(g_agg), rs(s_prev))
    return a.reshape(-1), score.reshape(-1)


def apply_pallas(a, mask, *, interpret=None):
    if interpret is None:
        from repro.kernels.common import auto_interpret
        interpret = auto_interpret()
    rows = _rows(a.shape[0])
    rs = lambda x: x.reshape(rows, BLOCK)
    spec = pl.BlockSpec((1, BLOCK), lambda i: (i, 0))
    ghat, err = pl.pallas_call(
        _apply_kernel,
        grid=(rows,),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((rows, BLOCK), jnp.float32)] * 2,
        interpret=interpret,
    )(rs(a), rs(mask))
    return ghat.reshape(-1), err.reshape(-1)
