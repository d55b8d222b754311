"""XLA execution strategy for the two-sweep fused compression pipeline.

On CPU/GPU the Pallas grid (interpret mode) costs far more than the
memory traffic it saves, so the same two-sweep contract is lowered to
fusion-friendly XLA ops instead:

- Sweep 1 is the elementwise (a, score) computation — XLA fuses it into
  one loop over the dense inputs (and into the sweep-2 operand read).
- Sweep 2 is a batched per-row ``lax.top_k``: each row of up to CHUNK
  entries emits its top-W |score| candidates, the row analogue of the
  Pallas kernel's per-block threshold slots. W is sized ~4x the
  expected per-row share of the caller's packing budget (k for exact
  selection, hist_capacity for the histogram selector — ops passes the
  budget as ``k``), so the candidate set provably covers the true
  top-budget unless a row's W-th candidate reaches the selection
  threshold (the exact k-th key, or the histogram bin edge below it —
  the witness ops checks before trusting the compaction). The
  histogram selector needs NO dense histogram on this strategy: its tau
  is key_bin_edge(k-th |score|), computable from the same trimmed
  candidates (kernel.key_bin_edge docstring).

Rows are INTERLEAVED: of R rows, row r holds positions r, r + R,
r + 2R, ... Real gradients are large in contiguous runs (an embedding
row's 768 entries, a hot weight block), so a contiguous row can hold
thousands of the top-k and fail the cover witness on every step. An
interleaved row takes ~1/R of every run, so its expected share of the
top-k is k * chunk / J whatever the concentration, and a contiguous run
of length L adds at most ceil(L / R) to any row. The witness still
guards adversarial strides (entries spaced exactly R apart). Rows
interleave within a bucket or segment, so each chain stays independent;
concentration at that level is not averaged across buckets.

Cost: O(J log W) compute in one O(J) read — no full-array O(J log k)
sort, and no second sort for packing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 8192
LANES = 128     # the TPU's lane width: a row count that keeps the view aligned


def row_shape(j: int, k: int, density_len: int = 0,
              slack: float = 4.0) -> tuple:
    """(rows, chunk, W) for the candidate sweep over ``j`` keys: ``rows``
    interleaved rows of ``chunk`` entries (row r holds positions
    c * rows + r), W candidate slots each; rows * chunk >= j, and the
    tail is padding.

    A row holds at most CHUNK entries. From LANES rows up, the row count
    is a multiple of LANES and the chunk shrinks to match (a multiple of
    8, so the padding stays under 8 entries a row): the (chunk, rows)
    view is then tiled like the TPU's (8, 128) layout, and its relayout
    is a plain copy. A row count off the lanes makes the TPU compiler
    move the view row by row, into two more J-sized buffers. Below LANES
    rows the view is small, and rows and chunk are as before.

    ``slack`` scales W relative to the expected per-row share (default
    4x — the historical provisioning; the allocated adaptive path passes
    2x, since its per-segment cap already embeds the ADAPTIVE_CLIP**2
    headroom over the typically-realized count and the row_min witness
    + fallback guard the tail).

    ``density_len`` (default: the padded length) is the length the
    selection density k/density_len is measured over. The bucketed
    pipeline passes the GLOBAL length here: a bucket's rows are
    provisioned exactly like the flat path's rows (4x the global-density
    share), so bucketing costs no extra candidate slots — row-level
    concentration beyond W is caught by the row_min witness and falls
    back, identically to flat. The allocated per-segment path (DESIGN.md
    §2.6) instead passes its per-segment cap as ``k`` with
    density_len=0: an adaptive segment may hold up to cap_l of the
    budget regardless of global density, so its rows are provisioned for
    the segment's own worst case (caps are clipped to ~ADAPTIVE_CLIP**2
    x the proportional share, keeping total slots O(k)).
    """
    rows = -(-j // CHUNK)
    if rows >= LANES:
        rows = -(-rows // LANES) * LANES
        chunk = -(-j // (rows * 8)) * 8
    else:
        chunk = min(CHUNK, max(8, j))
        rows = -(-j // chunk)
    j_pad = rows * chunk
    dl = density_len or j_pad
    if rows <= 1 and dl == j_pad:
        # single row over the whole vector: take k (+ slack so the
        # overflow check can pass)
        w = min(chunk, k + 8)
    else:
        mean = k * chunk / dl
        # ~slack x mean, multiple of 8 (slack=4 == the original
        # 8 * round(mean / 2))
        w = int(max(16, min(chunk, 8 * round(slack * mean / 8))))
        w = min(chunk, max(w, 16))      # tiny buckets: chunk itself can be < 16
    return rows, chunk, w


def candidates_xla(keys: jnp.ndarray, k: int, density_len: int = 0,
                   slack: float = 4.0):
    """Per-row top-W compaction of a key vector over interleaved rows:
    row r is column r of the (chunk, rows) view, positions c * rows + r
    for c < chunk (module docstring). The keys are padded to rows *
    chunk with -inf, which never out-ranks a real |score| (>= 0), so
    padded slots are inert; the bucketed pipeline pads each bucket
    independently (the padding of bucket b must not alias bucket b+1's
    index range with a selectable key).

    keys: (j,) non-negative scores. Returns (cand_keys (rows*W,),
    cand_idx (rows*W,) uint32, row_min (rows,), full_cover bool) where
    row_min[r] is row r's W-th largest key — the exactness witness: if
    max(row_min) < tau (the selected k-th key), no row can hide a missed
    top-k entry. ``full_cover`` is True when W == chunk (every entry is
    a candidate). ``density_len``: see row_shape (bucketed callers pass
    the global J). Candidates come row by row, so their positions do not
    follow the index order: the trim ranks them by (key, index)
    explicitly.
    """
    j = keys.shape[0]
    rows, chunk, w = row_shape(j, k, density_len, slack)
    if rows * chunk > j:
        keys = jnp.concatenate(
            [keys, jnp.full((rows * chunk - j,), -jnp.inf, jnp.float32)])
    cv, ci = jax.lax.top_k(keys.reshape(chunk, rows).T, w)
    gi = (ci.astype(jnp.uint32) * jnp.uint32(rows)
          + jnp.arange(rows, dtype=jnp.uint32)[:, None])
    row_min = jnp.min(cv, axis=1)        # rows sorted desc: == cv[:, w-1]
    # NB: jnp.min over the contiguous row, NOT cv[:, w-1] — the strided
    # column slice of a sort output hits a pathological XLA CPU path.
    return cv.reshape(-1), gi.reshape(-1), row_min, w == chunk
