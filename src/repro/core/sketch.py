"""CountSketch-coordinated global TOP-k (beyond-paper extension).

The paper's Bayesian framework identifies GLOBAL TOP-k (the genie that
selects on the aggregated accumulated gradient) as the ideal sparsifier
(§3.1). REGTOP-k approximates it with one-round-stale evidence; our linreg
study (EXPERIMENTS.md) shows stale evidence plateaus where the genie
converges. This module closes that gap with one cheap extra collective:

1. every worker encodes its accumulated gradient a_n into a CountSketch
   S(a_n) (rows x width, width ~ O(k));
2. one all-reduce of the sketches yields S(sum_n w_n a_n) — sketches are
   LINEAR, so this is a sketch of the true aggregated accumulated gradient;
3. every worker decodes magnitude estimates for all J entries (median of
   rows) and selects the SAME top-k mask -> coordinated selection;
4. workers exchange only the k selected values (mask is shared, so the
   index list is implied).

Extra communication per step: rows*width floats (e.g. 3 x 4k), sub-linear in
J — for a 3B-parameter model at S=1e-3 this is ~0.1% of the dense gradient.

Hashing is stateless (multiplicative universal hashing on the index), so no
O(J) hash tables are stored.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np

# fixed odd multipliers (Knuth multiplicative hashing), one pair per row.
# Plain numpy, never device arrays: the fused sweep-1 encode
# (kernels/compress/kernel.py) bakes these into its kernel body as
# python ints — kernels must not capture arrays — and plain hosts
# constants can never leak tracers into a traced caller.
_MULTS = np.array([2654435761, 2246822519, 3266489917, 668265263,
                   374761393, 2654435789, 1597334677, 2869860233],
                  dtype=np.uint32)
_ADDS = np.array([374761393, 3266489917, 1181783497, 2549297995,
                  4279918613, 1609587929, 2246822519, 2654435761],
                 dtype=np.uint32)

_WIDTH_CAP = 1 << 22

# k values already warned about — the width cap is surfaced once per
# process per k, same pattern as aggregate's sparse->simulate degrade
_CAP_WARNED: set = set()


def resolve_width(k: int, width: int = 0) -> int:
    """Effective sketch width: the explicit ``width`` verbatim, else
    4*k clamped to [256, 2^22]. Hitting the upper cap degrades estimate
    quality (more colliding coordinates per bucket than the 4x
    provisioning assumes) — warned once, never silent."""
    if width:
        return int(width)
    w = max(4 * k, 256)
    if w > _WIDTH_CAP:
        if k not in _CAP_WARNED:
            _CAP_WARNED.add(k)
            warnings.warn(
                f"sketch width 4*k = {w} exceeds the {_WIDTH_CAP} "
                f"auto-width cap at k={k}; the capped sketch packs "
                f"~{4 * k / _WIDTH_CAP:.1f}x more coordinates per bucket "
                "than the 4x provisioning assumes, degrading the "
                "magnitude estimates. Set SparsifierConfig.sketch_width "
                "explicitly to override the cap.",
                RuntimeWarning, stacklevel=2)
        return _WIDTH_CAP
    return int(w)


def hash_row(idx, mult: int, add: int, width: int):
    """One row's CountSketch hash of a uint32 index stream: (h int32
    bucket indices, s ±1 fp32 signs). The one formula that encode,
    estimate and the fused kernel's encode share; ``mult``/``add`` are
    python ints, so a kernel body can call it."""
    x = idx * jnp.uint32(mult) + jnp.uint32(add)
    h = jax.lax.rem(x >> 8, jnp.uint32(width)).astype(jnp.int32)
    s = ((x >> 31) & 1).astype(jnp.float32) * 2.0 - 1.0
    return h, s


def _row_hashes(j: int, rows: int, width: int):
    idx = jnp.arange(j, dtype=jnp.uint32)
    return [hash_row(idx, int(_MULTS[r]), int(_ADDS[r]), width)
            for r in range(rows)]


def encode(a: jnp.ndarray, rows: int, width: int) -> jnp.ndarray:
    """a (J,) -> sketch (rows, width). Linear in a.

    One 1D scatter-add per row, hashing the index stream row by row, so
    no (rows, J) hash/sign array is built: each row costs one J-sized
    scatter pass over ``a``."""
    af = a.astype(jnp.float32)
    return jnp.stack([jnp.zeros((width,), jnp.float32).at[h].add(s * af)
                      for h, s in _row_hashes(a.shape[0], rows, width)])


def estimate(sketch: jnp.ndarray, j: int) -> jnp.ndarray:
    """Magnitude estimates for all J entries (median over rows)."""
    rows, width = sketch.shape
    vals = jnp.stack([s * sketch[r][h] for r, (h, s)
                      in enumerate(_row_hashes(j, rows, width))])
    return jnp.median(vals, axis=0)
