"""Pallas TPU kernels for the two-sweep fused compression pipeline.

Sweep 1 (``sweep1_pallas``): one VMEM-tiled pass over the dense inputs.
Per (1, BLOCK) grid step it

- reads the ONE J-sized state vector ``err_prev`` (the previous step's
  error feedback, already zeroed at the selected support by the O(k)
  scatter that closes each step — no dense mask exists in the fused
  state),
- emits ``a = err_prev + g`` and the selection ``score`` (``a * c`` with
  ``c`` the off-support REGTOP-k regularizer, 1 for plain TOP-k / DGC /
  step 0),
- emits the per-block amax of |score| and accumulates a BINS-bin
  *bit-pattern* histogram of |score| (top bits of the fp32 encoding —
  monotone in magnitude, so no separate amax pass is needed to scale the
  bins; this folds the reference selector's amax + histogram passes into
  the same sweep). The histogram uses an in-register bincount
  (scatter-add into the accumulated block) rather than the O(BLOCK*BINS)
  one-hot compare the ``topk_select`` kernel historically used.

Sweep 2 (``sweep2_pallas``): one pass over ``score``. Per grid step it
compacts candidate ``(value, index)`` pairs with ``|score| >= tau`` into
a fixed per-block slot region of width ``MAXPB`` (static base
``i * MAXPB`` — TPU-friendly: no cross-block running offset), plus the
per-block candidate count used by the exactness check, and optionally
the uint8 threshold mask (the fused pipeline skips it and rebuilds the
exact mask as an O(k) scatter). The O(candidates) exact-k trim runs
outside the kernel (ops.py).

Scalars (step flag, tau) travel as (1, 1) inputs; static config (mode,
regularizer constant, bins) is baked into the kernel body.

These kernels run only under ``interpret=True`` (``ops`` strategy
``pallas_interpret``). The v5e compiler (libtpu 0.0.34, JAX 0.9.0)
refuses every one of them natively: their ``(1, BLOCK)``, ``(1, 1)``,
``(1, bins)`` and ``(1, maxpb)`` blocks break the rule that the last two
block dimensions divide by 8 and 128, and even with aligned blocks
Pallas TPU lowering has no rule for the in-kernel scatter-add histogram,
the ``cumsum`` compaction, the ``.at[].set`` slot scatters or the sketch
scatter-add. ``ops.PALLAS_TPU_REFUSAL`` is the error ``strategy="pallas"``
raises; the chip runs ``strategy="xla"``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.sketch import hash_row

BLOCK = 8 * 128 * 4      # 4096 fp32 elements per grid step, VMEM tile-aligned
BINS = 2048              # 2^11 bit-pattern bins: exponent + 3 mantissa bits
_BIN_SHIFT = 20          # fp32 bits >> 20 -> [0, 2047] for non-negative floats
INVALID_IDX = 0xFFFFFFFF     # python int: kernels must not capture arrays


def bit_bin(absx: jnp.ndarray) -> jnp.ndarray:
    """Histogram bin of a non-negative fp32 value: top 11 bits of its IEEE-754
    encoding. Monotone: x <= y  =>  bit_bin(x) <= bit_bin(y)."""
    bits = jax.lax.bitcast_convert_type(absx.astype(jnp.float32), jnp.uint32)
    return (bits >> _BIN_SHIFT).astype(jnp.int32)


def bin_lower_edge(b: jnp.ndarray) -> jnp.ndarray:
    """Smallest fp32 value mapping to bin b (the bin's lower edge)."""
    return jax.lax.bitcast_convert_type(
        (b.astype(jnp.uint32) << _BIN_SHIFT), jnp.float32)


def key_bin_edge(x: jnp.ndarray) -> jnp.ndarray:
    """Lower edge of x's bit-pattern bin. For x = the exact k-th largest
    |score| this IS the histogram-selector threshold: the largest bin b
    with tail count >= k is exactly bit_bin(x) (every key above x's bin
    is > x, and there are < k of those), so
    key_bin_edge(kth) == threshold_from_hist(hist, k) — which is what
    lets the XLA strategy serve selector="histogram" without computing
    a dense histogram, and keeps both strategies' tau identical."""
    return bin_lower_edge(bit_bin(x))


# ---------------------------------------------------------------------------
# Sweep 1
# ---------------------------------------------------------------------------

def _sweep1_kernel(c_ref, *refs, mode: str, momentum: float, bins: int,
                   gated: bool = False):
    # dgc mode threads the momentum buffer; plain mode omits it entirely
    # (no dead O(J) passthrough streams on the non-dgc path). gated dgc
    # (elastic participation, DESIGN.md §2.7) prepends one more (1, 1)
    # scalar operand: the worker's participation gate.
    if gated:
        gate_ref, *refs = refs
    if mode == "dgc":
        (g_ref, err_ref, mom_ref,
         a_ref, score_ref, mom_out_ref, amax_ref, hist_ref) = refs
    else:
        (g_ref, err_ref,
         a_ref, score_ref, amax_ref, hist_ref) = refs
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    g = g_ref[...].astype(jnp.float32)
    err = err_ref[...].astype(jnp.float32)     # one state read: err_prev
    if mode == "dgc":
        mom = momentum * mom_ref[...].astype(jnp.float32) + g
        mom_out_ref[...] = mom
        if gated:
            # sitting-out worker: a = err (pre-decayed by the caller's
            # input masking) while mom_out still advances to
            # momentum * mom (g arrives pre-masked to zero). The select
            # — not a multiply — keeps 0 * inf from minting NaNs and is
            # a bitwise pass-through when the gate is on.
            a = err + jnp.where(gate_ref[0, 0] > 0.5, mom, 0.0)
        else:
            a = err + mom
    else:
        a = err + g
    score = a * c_ref[0, 0]
    a_ref[...] = a
    score_ref[...] = score
    keys = jnp.abs(score)
    amax_ref[0, 0] = jnp.max(keys)
    # in-register bincount of the block's bit-pattern bins
    bidx = bit_bin(keys)                                       # (1, BLOCK)
    hist_ref[...] += jnp.zeros((1, bins), jnp.int32).at[
        0, bidx[0]].add(1)


def sweep1_pallas(g, err_prev, c, *, mode: str = "plain",
                  momentum: float = 0.0, mom=None, gate=None,
                  bins: int = BINS, interpret: bool = True):
    """All dense inputs (J,) with J % BLOCK == 0 (caller pads).

    ``err_prev`` is the ONE J-sized state vector of the fused layout —
    the previous step's error feedback, already zero at the selected
    support (the O(k) scatter-zero that closes each step maintains the
    EF invariant err = a * (1 - s) without a dense mask).
    ``c`` is the (traced) off-support score factor: the REGTOP-k
    regularizer constant tanh(|1+Q|/mu), or 1 for TOP-k / DGC / step 0.
    ``gate`` (mode="dgc" only) is the traced elastic-participation
    scalar (DESIGN.md §2.7): when given, a = err + where(gate, mom, 0)
    so a sitting-out worker's ``a`` excludes the momentum stream while
    ``mom_out`` still advances; None keeps the ungated kernel verbatim.
    Returns (a, score, mom_out, block_amax (rows,), hist (bins,));
    mom_out is None unless mode="dgc" (which requires ``mom``).
    """
    j = g.shape[0]
    assert j % BLOCK == 0, j
    rows = j // BLOCK
    rs = lambda x: x.astype(jnp.float32).reshape(rows, BLOCK)
    spec = pl.BlockSpec((1, BLOCK), lambda i: (i, 0))
    dgc = mode == "dgc"
    gated = gate is not None
    assert not gated or dgc, "gate is a dgc-mode operand"
    vec_out = jax.ShapeDtypeStruct((rows, BLOCK), jnp.float32)
    inputs = ([jnp.asarray(c, jnp.float32).reshape(1, 1)]
              + ([jnp.asarray(gate, jnp.float32).reshape(1, 1)]
                 if gated else [])
              + [rs(g), rs(err_prev)] + ([rs(mom)] if dgc else []))
    outs = pl.pallas_call(
        functools.partial(_sweep1_kernel, mode=mode,
                          momentum=float(momentum), bins=bins,
                          gated=gated),
        grid=(rows,),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0))]      # factor c
                 * (2 if gated else 1)                         # (+ gate)
                 + [spec] * (3 if dgc else 2),
        out_specs=[spec] * (3 if dgc else 2) + [
            pl.BlockSpec((1, 1), lambda i: (i, 0)),        # per-block amax
            pl.BlockSpec((1, bins), lambda i: (0, 0)),     # accumulated hist
        ],
        out_shape=[vec_out] * (3 if dgc else 2) + [
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, bins), jnp.int32),
        ],
        interpret=interpret,
    )(*inputs)
    if dgc:
        a, score, mom_out, amax, hist = outs
        mom_out = mom_out.reshape(-1)
    else:
        a, score, amax, hist = outs
        mom_out = None
    return (a.reshape(-1), score.reshape(-1), mom_out,
            amax.reshape(-1), hist[0])


def threshold_from_hist(hist: jnp.ndarray, target) -> jnp.ndarray:
    """Lower edge of the largest bin b whose tail count >= target.

    Guarantees count(|score| >= tau) >= target (0 when target exceeds the
    histogram mass, which routes the caller to the exact fallback).
    ``target`` may be traced — the allocated per-segment path (DESIGN.md
    §2.6) derives each segment's OWN tau from its sweep-1 histogram at a
    per-segment target, instead of one merged-histogram global tau.
    """
    from repro.core.select import hist_tail_bin
    b = hist_tail_bin(hist, target)
    return jnp.where(b >= 0, bin_lower_edge(jnp.maximum(b, 0)), 0.0)


def merge_bucket_hists(hists) -> jnp.ndarray:
    """O(num_buckets x BINS) global-k histogram merge (DESIGN.md §2.4).

    Bit-pattern bins are position-independent (bin of an element depends
    only on its value), so the sum of per-bucket histograms IS the
    histogram of the whole vector: the threshold picked from the merged
    histogram is identical to the flat single-sweep threshold for any
    bucketing, which is what makes the union of per-bucket >=tau
    selections cover the exact global top-k.
    """
    merged = hists[0]
    for h in hists[1:]:
        merged = merged + h
    return merged


def threshold_from_bucket_hists(hists, target) -> jnp.ndarray:
    """Global threshold tau from per-bucket histograms (merge + tail scan)."""
    return threshold_from_hist(merge_bucket_hists(hists), target)


# ---------------------------------------------------------------------------
# Sweep 2
# ---------------------------------------------------------------------------

def _sweep2_kernel(tau_ref, score_ref, *refs, maxpb: int,
                   want_mask: bool):
    if want_mask:
        mask_ref, vals_ref, idx_ref, cnt_ref = refs
    else:
        vals_ref, idx_ref, cnt_ref = refs
    i = pl.program_id(0)
    score = score_ref[...].astype(jnp.float32)                 # (1, BLOCK)
    keys = jnp.abs(score)
    tau = tau_ref[0, 0]
    flags = keys >= tau
    if want_mask:
        mask_ref[...] = flags.astype(jnp.uint8)
    cnt = jnp.sum(flags.astype(jnp.int32))
    cnt_ref[0, 0] = cnt
    # compact candidates into this block's static MAXPB slot region;
    # overflow beyond maxpb is dropped and flagged via cnt > maxpb
    pos = jnp.cumsum(flags[0].astype(jnp.int32)) - 1           # (BLOCK,)
    pos = jnp.where(flags[0], pos, maxpb)                      # drop lanes
    lane = jax.lax.broadcasted_iota(jnp.uint32, (BLOCK,), 0)
    gidx = jax.lax.convert_element_type(i, jnp.uint32) * BLOCK + lane
    vals_ref[...] = jnp.full((1, maxpb), -jnp.inf, jnp.float32).at[
        0, pos].set(keys[0], mode="drop")
    idx_ref[...] = jnp.full((1, maxpb), INVALID_IDX, jnp.uint32).at[
        0, pos].set(gidx, mode="drop")


def sweep2_pallas(score, tau, *, maxpb: int, interpret: bool = True,
                  want_mask: bool = True):
    """score: (J,) fp32, J % BLOCK == 0. Returns
    (mask_u8 (J,) or None, cand_vals (rows*maxpb,), cand_idx
    (rows*maxpb,), block_counts (rows,)). Candidate slots hold |score|
    (key order) and global indices; invalid slots are (-inf,
    INVALID_IDX). want_mask=False skips the dense threshold-mask write
    (callers that rebuild the exact mask as an O(k) scatter)."""
    j = score.shape[0]
    assert j % BLOCK == 0, j
    rows = j // BLOCK
    rs = lambda x: x.astype(jnp.float32).reshape(rows, BLOCK)
    spec = pl.BlockSpec((1, BLOCK), lambda i: (i, 0))
    mask_specs = [spec] if want_mask else []
    mask_shapes = ([jax.ShapeDtypeStruct((rows, BLOCK), jnp.uint8)]
                   if want_mask else [])
    outs = pl.pallas_call(
        functools.partial(_sweep2_kernel, maxpb=maxpb, want_mask=want_mask),
        grid=(rows,),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)), spec],
        out_specs=mask_specs + [
            pl.BlockSpec((1, maxpb), lambda i: (i, 0)),
            pl.BlockSpec((1, maxpb), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (i, 0)),
        ],
        out_shape=mask_shapes + [
            jax.ShapeDtypeStruct((rows, maxpb), jnp.float32),
            jax.ShapeDtypeStruct((rows, maxpb), jnp.uint32),
            jax.ShapeDtypeStruct((rows, 1), jnp.int32),
        ],
        interpret=interpret,
    )(jnp.asarray(tau, jnp.float32).reshape(1, 1), rs(score))
    if want_mask:
        mask, vals, idx, cnt = outs
        mask = mask.reshape(-1)
    else:
        (vals, idx, cnt), mask = outs, None
    return mask, vals.reshape(-1), idx.reshape(-1), cnt.reshape(-1)


# ---------------------------------------------------------------------------
# CountSketch encode (sweep-1 fold, DESIGN.md §2.9)
# ---------------------------------------------------------------------------

# sketch-encode NATIVE grid step: 32x the sweep block. The encode
# touches each element once and accumulates into the tiny (rows, width)
# output, so a fat block keeps the grid short without growing any
# J-sized intermediate. Interpret mode widens further (_sketch_grid).
SKETCH_BLOCK = 32 * BLOCK


def _sketch_accum(a, base, sk_ref, *, rows: int, width: int, block: int,
                  mults, adds):
    """Accumulate one (block,) slice of ``a`` into the (rows, width)
    sketch ref. Hashing is core.sketch.hash_row itself, over the uint32
    index stream with the row constants baked as python ints (kernels
    must not capture arrays).

    Each row scatters into its own 1D (width,) accumulator: XLA lowers
    a 1D scatter-add measurably faster than the batched/2D form the
    legacy vmap encode takes (~25% at J = 2^24 on CPU), and the row
    loop is a static unroll (rows <= 8)."""
    lane = jax.lax.broadcasted_iota(jnp.uint32, (block,), 0)
    gidx = base + lane                       # uint32 global element index
    for r in range(rows):
        h, s = hash_row(gidx, mults[r], adds[r], width)
        sk_ref[r, :] += jnp.zeros((width,), jnp.float32).at[h].add(s * a)


def _sweep1_sketch_kernel(g_ref, err_ref, a_ref, sk_ref, *, rows: int,
                          width: int, block: int, mults, adds):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sk_ref[...] = jnp.zeros_like(sk_ref)

    g = g_ref[...].astype(jnp.float32)
    err = err_ref[...].astype(jnp.float32)     # one state read: err_prev
    a = err + g
    a_ref[...] = a
    base = jax.lax.convert_element_type(i, jnp.uint32) * block
    _sketch_accum(a[0], base, sk_ref, rows=rows, width=width, block=block,
                  mults=mults, adds=adds)


def _sketch_grid(j: int, interpret: bool = True):
    """(block, padded J) for the sketch-encode grid: lane-aligned block.
    Pad elements carry a = 0.0, so they add s * 0 to whatever bucket
    their (well-defined) hash picks — inert.

    Native blocks cap at SKETCH_BLOCK (VMEM-bounded). Interpret mode
    has no VMEM ceiling but pays a fixed per-grid-step dispatch cost
    (the emulated block load + scatter launches), so it widens the
    block to keep the grid at <= 8 steps at any J."""
    cap = SKETCH_BLOCK
    if interpret:
        cap = max(cap, -(-j // (8 * 128)) * 128)
    block = min(cap, -(-j // 128) * 128)
    return block, -(-j // block) * block


def sweep1_sketch_pallas(g, err_prev, *, rows: int, width: int, mults,
                         adds, interpret: bool = True):
    """Sweep 1 with the CountSketch encode folded in: one pass over
    (g, err_prev) emits both a = err_prev + g AND its sketch, so the
    Pallas strategy pays a single traversal for accumulate + encode
    (DESIGN.md §2.9). Returns (a (J,) fp32, sketch (rows, width))."""
    j = g.shape[0]
    block, j_pad = _sketch_grid(j, interpret)
    if j_pad != j:
        g = jnp.pad(g.astype(jnp.float32), (0, j_pad - j))
        err_prev = jnp.pad(err_prev.astype(jnp.float32), (0, j_pad - j))
    grid = j_pad // block
    rs = lambda x: x.astype(jnp.float32).reshape(grid, block)
    spec = pl.BlockSpec((1, block), lambda i: (i, 0))
    a, sk = pl.pallas_call(
        functools.partial(_sweep1_sketch_kernel, rows=rows, width=width,
                          block=block, mults=tuple(mults),
                          adds=tuple(adds)),
        grid=(grid,),
        in_specs=[spec, spec],
        out_specs=[spec, pl.BlockSpec((rows, width), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((grid, block), jnp.float32),
                   jax.ShapeDtypeStruct((rows, width), jnp.float32)],
        interpret=interpret,
    )(rs(g), rs(err_prev))
    return a.reshape(-1)[:j], sk
