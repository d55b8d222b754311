"""Jit-friendly entry points for the two-sweep fused compression pipeline.

``fused_compress_arrays`` runs the whole compression step for one worker:

    sweep 1:  a, score           (dense inputs read exactly once)
    sweep 2:  candidate slots    (per-row/per-block top candidates)
    O(cand):  exact-k trim, REGTOP-k posterior corrections, exactness
              checks, fixed-k (values, indices), optional dense ghat,
              and the O(k) scatter-zero that writes the next step's
              err state in place (DESIGN.md §2.2)

The step is **two O(J) traversals end to end** on the sparse-comm path:
the only J-sized state is ``err_prev`` (= a^{t-1} * (1 - s^{t-1}),
maintained by zeroing the k selected slots of ``a`` after the trim), so
no dense mask is ever written and sweep 1 reads exactly one state
vector. Dense masks, when a caller needs one, are reconstructed from
the packed indices (``core.sparsify.dense_mask``, O(k)).

With ``num_buckets > 1`` (DESIGN.md §2.4) the flat gradient is
partitioned into contiguous buckets (core.flatten.bucket_bounds); both
sweeps run per bucket and the per-bucket bit-pattern histograms are
merged (O(num_buckets x BINS)) into ONE global threshold, so the union
of per-bucket candidate selections still covers the exact global top-k.
The O(cand) trim stays global — selected support and packed order are
bit-identical to the flat (num_buckets=1) path. NB: because the trim
(and its lax.cond fallback) joins all buckets, the packed pairs exist
only after every bucket's sweeps finish; the overlap the bucketing buys
is on the COMMUNICATION side (core.aggregate chunks the packed pairs so
gather b+1 runs concurrently with scatter-add b), not compression
hidden behind collectives.

With ``allocation != "global"`` (DESIGN.md §2.6) the sweeps run per
SEGMENT (the allocation partition — layer-aligned when the caller
passes TreeFlattener bounds) instead of per bucket, each segment gets
its own threshold/provisioning sized for its cap, and the global trim
becomes per-segment trims + one O(sum(caps)) pack; sum(k_l) == k keeps
the packed output exactly k pairs. Bucketing continues to govern only
the comm-side chunking of those pairs (core.aggregate).

With ``g_segments``/``stream_bounds`` (backward-overlapped streaming,
DESIGN.md §2.8) the gradient arrives as per-segment arrays instead of
one flat vector, and the sweeps partition by the stream bounds: each
segment's sweep-1 (EF fold, score, histogram/statistics) depends only
on its own segment, so XLA schedules it as soon as the backward pass
emits that segment's leaves; the trim/pack is the only cross-segment
join. The same partition-invariance that makes bucketing bit-identical
makes streaming bit-identical — and S partial sweeps of J/S elements
still audit as the same 2 traversals (the streaming reorders WHEN
sweeps run, not how many).

The execution strategy is ``xla`` (fusion-friendly XLA lowering) on
every backend; ``pallas_interpret`` runs the Pallas kernel bodies in
interpret mode to validate them in tests. ``pallas`` (the native
kernels) raises before tracing, because the TPU compiler refuses them
(``PALLAS_TPU_REFUSAL``).

Exactness: the compacted candidate set provably covers the true top-k
unless the per-row/per-block witnesses say otherwise; those cases take a
``lax.cond`` fallback to a full ``lax.top_k`` with identical semantics.
The trims rank candidates by (key descending, index ascending)
explicitly (``_rank``), the reference ``lax.top_k``'s order over the
dense keys, so fast path and fallback both reproduce the reference
selector's tie-break support and packed order exactly, whatever the
candidates' positions. Every score-based path returns two counters of its trim
(``trim_counters``): ``topk_fallback`` (1.0 when the fallback ran) and
``topk_saturated_rows`` (on the ``xla`` strategy, the candidate rows
whose W-th key reached the selection threshold: the cover witness's
failures; 0 where a path has no row witness).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import stages
from repro.core.flatten import bucket_bounds
from repro.core.numerics import safe_denom
from repro.kernels.compress import kernel as pk
from repro.kernels.compress import xla as px
from repro.kernels.compress.dispatch import hist_capacity


PALLAS_TPU_REFUSAL = (
    "strategy='pallas' (native Pallas compress kernels) does not compile "
    "for TPU: the v5e compiler refuses their (1, BLOCK), (1, 1), "
    "(1, bins) and (1, maxpb) blocks (the last two block dimensions must "
    "be divisible by 8 and 128), and Pallas TPU lowering has no rule for "
    "the in-kernel scatter-add histogram, the cumsum compaction, the "
    ".at[].set slot scatters or the sketch scatter-add. Use "
    "strategy='xla' (the default), or 'pallas_interpret' to run the "
    "kernel bodies in interpret mode.")


def default_strategy() -> str:
    """The strategy every backend runs: ``xla`` compiles for the TPU and
    the CPU alike."""
    return "xla"


def resolve_strategy(strategy: Optional[str]) -> str:
    """``strategy`` or the default, validated before anything traces.
    Only ``pallas_interpret`` interprets; ``pallas`` raises
    ``NotImplementedError(PALLAS_TPU_REFUSAL)``."""
    strategy = strategy or default_strategy()
    if strategy == "pallas":
        raise NotImplementedError(PALLAS_TPU_REFUSAL)
    if strategy not in ("xla", "pallas_interpret"):
        raise ValueError(f"unknown strategy {strategy!r}")
    return strategy


def sweep_plan(pipeline: str, comm_mode: str = "sparse") -> dict:
    """Analytic O(J) HBM-traversal plan per compress step (DESIGN.md §2.2).

    A "pass" is a full J-sized streaming read or write. O(k) scatters and
    gathers (mask/ghat/packing fix-ups) are not passes. Bucketing does
    not change the plan: num_buckets partial sweeps of J/num_buckets
    elements are one J-equivalent traversal (the audit weights them
    fractionally, DESIGN.md §2.3). Density allocation doesn't either:
    per-segment partial sweeps weight the same way, and the allocated
    trim/pack/statistics are all O(sum(caps)) ~ O(k)
    (tests/test_allocate.py::TestAllocatedSweepCount).
    """
    if pipeline == "reference":
        # score chain reads (g, err, a_prev, g_agg_prev, s_prev) + writes
        # (a, score) + step-0 where pass + two full |score| sorts + mask
        # scatter + ghat/err pass: ~8 traversals, 2 O(J log k) sorts.
        return {"o_j_passes": 8, "full_sorts": 2}
    # fused: sweep 1 (one elementwise stream) + sweep 2 (candidate
    # compaction). State updates (err scatter-zero, mom masking, packed
    # pairs, mask reconstruction) are all O(k) — no third traversal.
    passes = 2 if comm_mode == "sparse" else 3   # +1: dense ghat write
    return {"o_j_passes": passes, "full_sorts": 0}


def _posterior_keys(a_sel, a_prev_sel, g_prev_sel, step, *,
                    omega, mu, support_valid=None):
    """|score| of the support entries (Algorithm 1 line 5, O(k)).

    ``a_sel`` is the error-compensated gradient AT the support indices.
    The production call site gathers it from the dense ``a`` buffer
    BEFORE the trim's lax.cond (a pre-cond read keeps the final err
    scatter-zero in-place); the fallback branch recomputes it from the
    function parameters (``_gather_inputs``). ``support_valid`` masks
    inert pad slots of the histogram selector's fixed-capacity support
    state (slots >= nsel_prev point at index 0 and must not contribute
    a corrected key)."""
    safe = safe_denom(omega * a_sel)
    delta_sel = (g_prev_sel - omega * a_prev_sel) / safe
    skey = jnp.abs(a_sel * jnp.tanh(jnp.abs(1.0 + delta_sel) / mu))
    skey = jnp.where(step == 0, -jnp.inf, skey)
    if support_valid is not None:
        skey = jnp.where(support_valid, skey, -jnp.inf)
    return skey


def _offsupport_reg(step, Q, mu):
    """REGTOP-k's off-support regularizer tanh(|1 + Q| / mu), 1 at step 0.

    Evaluated on the device, as the reference evaluates it per element:
    folded on the host at compile time, it can differ from the device's
    tanh in the last bit (it does on a TPU v5e), and that one-ulp scale
    between support and off-support scores reorders their ties at the
    selection boundary."""
    q = jax.lax.optimization_barrier(jnp.float32(Q))
    return jnp.where(step == 0, jnp.float32(1.0),
                     jnp.tanh(jnp.abs(1.0 + q) / mu))


def _scalar_select(pred, x, y):
    """``where(pred, x, y)`` for a SCALAR predicate, emitted as a plain
    ``select_n`` over an explicit broadcast. ``jnp.where`` traces as a
    nested pjit call, and the traversal audit (audit.py) breaks fusion
    groups at call boundaries — a where on a J-sized array would bill a
    spurious traversal + escape write. lax primitives stay inline and
    fuse into the surrounding elementwise group."""
    x = jnp.asarray(x)
    y = jnp.asarray(y, x.dtype)
    if y.shape != x.shape:
        y = jax.lax.broadcast_in_dim(y, x.shape, ())
    p = jax.lax.broadcast_in_dim(
        jnp.asarray(pred, jnp.bool_).reshape(()), x.shape, ())
    return jax.lax.select(p, x, y)


def trim_counters(ok, saturated_rows) -> dict:
    """The trim's per-step counters, float32 scalars: ``topk_fallback``
    1.0 when the ``lax.cond`` fallback ran (``ok`` False), else 0;
    ``topk_saturated_rows`` the candidate rows that failed the cover
    witness (W-th key at or above the selection threshold)."""
    return {"topk_fallback": 1.0 - jnp.asarray(ok, jnp.float32),
            "topk_saturated_rows": jnp.asarray(saturated_rows, jnp.float32)}


def _rank(keys, idx, n: int):
    """The leading ``n`` of (keys, idx) in the reference order: key
    descending, global index ascending among equal keys — what
    ``lax.top_k`` over the dense keys gives. Candidate positions do not
    follow the index (the XLA sweep's rows interleave, and REGTOP-k
    appends the corrected support keys), so the index is the second key
    of one O(cand log cand) sort: ascending by (key, ~index), read
    backwards, which also puts NaN keys first as ``lax.top_k`` does.
    Finite keys never share an index, so the order of the finite entries
    is total and the sort need not be stable. Returns (keys (n,), idx
    (n,))."""
    sk, si = jax.lax.sort((keys, ~idx), num_keys=2, is_stable=False)
    return sk[::-1][:n], ~si[::-1][:n]


def _decayed_err(err_prev, pf, err_decay):
    """``where(p, err, err_decay * err)`` — the EF-decay half of
    ``masked_inputs``, factored out so the streaming path (DESIGN.md
    §2.8, no flat ``g`` to mask) applies the bitwise-identical select
    to the flat state while masking ``g`` per segment."""
    return _scalar_select(
        pf, err_prev,
        (jnp.float32(err_decay) * err_prev.astype(jnp.float32)
         ).astype(err_prev.dtype))


def masked_inputs(g, err_prev, participate, err_decay):
    """Effective sweep-1 inputs under elastic participation (DESIGN.md
    §2.7): ``g_eff = where(p, g, 0)`` and ``err_eff = where(p, err,
    err_decay * err)``. With these as the step's inputs, a sitting-out
    worker's accumulator is ``a = err_decay * err`` — which the skipped
    (sentinel-routed) err scatter-zero then stores verbatim as the next
    err_prev, implementing the EF decay WITHOUT a third traversal: the
    wheres are elementwise with a scalar predicate, so they fuse into
    sweep 1's existing read group, and for a participating worker
    (p=True) both selects pass the original arrays through bitwise.
    The decay multiply is fp32 in-register (bf16 EF state rounds once,
    like every other sweep write). Shared verbatim by the fused pipeline
    and the reference oracle so their post-step states stay
    bit-comparable. Returns (g_eff, err_eff, p_bool)."""
    pf = jnp.asarray(participate, jnp.bool_)
    g_eff = _scalar_select(pf, g, jnp.zeros_like(g))
    return g_eff, _decayed_err(err_prev, pf, err_decay), pf


def _sweep1_xla(kind, g, err_prev, c, *, momentum, mom, gate=None):
    err = err_prev.astype(jnp.float32)               # ONE state read
    g = g.astype(jnp.float32)
    mom_out = mom
    if kind == "dgc":
        mom_out = momentum * mom.astype(jnp.float32) + g
        # elastic gate (DESIGN.md §2.7): a sitting-out worker must keep
        # a = err_eff (so err decays in place) while mom_out still
        # advances to momentum * mom (its g contribution is already
        # masked to zero) — input masking alone cannot remove the
        # momentum term from ``a``, hence the scalar select (fuses into
        # the same elementwise group; gate=True is a bitwise pass-through)
        am = mom_out if gate is None else _scalar_select(gate, mom_out, 0.0)
        a = err + am
    else:
        a = err + g
    return a, a * c, mom_out


def _sweep1_slice(kind, g_s, err_s, c, *, momentum, mom_s, gate=None):
    """One padded-slice sweep-1 launch over PRE-SLICED inputs, shared by
    the bucketed global path, the allocated per-segment path, and the
    streaming path (whose ``g_s`` arrives as a standalone segment array
    rather than a view of a flat vector — slicing happens at the call
    site so both forms share this launch verbatim). Returns
    (a (size,), score_padded, mom (size,)|None, hist) with the bin-0
    padding contribution already corrected out of the histogram.
    ``gate`` is the elastic participation scalar for mode="dgc"
    (kernel-side a = err + gate * mom select; None for the ungated
    kernel)."""
    dgc = kind == "dgc"
    size = g_s.shape[0]
    j_pad = -(-size // pk.BLOCK) * pk.BLOCK
    pad = lambda x: jnp.pad(x.astype(jnp.float32), (0, j_pad - size))
    a_p, score_p, mom_p, _amax, hist = pk.sweep1_pallas(
        pad(g_s), pad(err_s), c,
        mode=("dgc" if dgc else "plain"), momentum=momentum,
        mom=None if mom_s is None else pad(mom_s),
        gate=gate if dgc else None, interpret=True)
    # padding contributed (j_pad - size) zero keys to bin 0
    return (a_p[:size], score_p, mom_p[:size] if dgc else None,
            hist.at[0].add(-(j_pad - size)))


def _sweep2_slice(score_p, tau, off, size, maxpb: int):
    """One slice sweep-2 compaction (shared like _sweep1_slice): kills
    slice-local padding slots BEFORE the global-offset shift (they must
    not alias the next slice's index range) and reports ok iff no block
    overflowed its maxpb candidate slots. Returns (cand_keys,
    cand_idx_global, ok)."""
    _mask_t, ck, ci, cnts = pk.sweep2_pallas(
        score_p, tau, maxpb=maxpb, interpret=True, want_mask=False)
    ck = jnp.where(ci < size, ck, -jnp.inf)
    return ck, ci + jnp.uint32(off), jnp.max(cnts) <= maxpb


@stages.scope("sweep")
def _candidates_pallas(kind, g, err_prev, c, step, *, k: int,
                       regtopk: bool, momentum: float, mom, bounds,
                       gate=None, g_segments=None):
    """Per-bucket Pallas sweeps + histogram-merge global threshold.

    Sweep 1 runs once per bucket and emits that bucket's 2048-bin
    bit-pattern histogram; the merged histogram picks a single global
    tau (count(|score| >= tau) >= k + margin over the WHOLE vector, so
    per-bucket >=tau compaction unions to a global-top-k cover). Sweep 2
    then compacts each bucket independently against that shared tau.

    ``g_segments`` (streaming, DESIGN.md §2.8): per-``bounds`` gradient
    segments in place of the flat ``g`` — each slot's sweep-1 then
    depends only on its own segment array (the backward pass can still
    be producing the others), and the histogram merge is the first
    cross-segment join. Selection is partition-invariant, so the output
    is bit-identical either way.
    """
    j = err_prev.shape[0]
    dgc = kind == "dgc"
    a_parts, score_parts, mom_parts, hists = [], [], [], []
    for pos, (off, size) in enumerate(bounds):
        g_s = (g_segments[pos] if g_segments is not None
               else g[off:off + size])
        a_p, score_p, mom_p, hist = _sweep1_slice(
            kind, g_s, err_prev[off:off + size], c, momentum=momentum,
            mom_s=None if mom is None else mom[off:off + size],
            gate=gate)
        hists.append(hist)
        a_parts.append(a_p)
        score_parts.append(score_p)
        if dgc:
            mom_parts.append(mom_p)
    # margin k: REGTOP-k support corrections may drop <=k entries below
    # tau without breaking top-k coverage of the candidates
    target = k + jnp.where(jnp.logical_and(regtopk, step > 0), k, 0)
    tau = pk.threshold_from_bucket_hists(hists, target)
    # per-block slot capacity from the GLOBAL selection density (a bucket
    # block's expected candidate share does not depend on the bucketing)
    maxpb = int(min(pk.BLOCK, max(32, -(-8 * k * pk.BLOCK // j))))
    ck_parts, ci_parts, oks = [], [], []
    for (off, size), score_p in zip(bounds, score_parts):
        ck, ci, ok_b = _sweep2_slice(score_p, tau, off, size, maxpb)
        ci_parts.append(ci)
        ck_parts.append(ck)
        oks.append(ok_b)
    producer_ok = oks[0]
    for ok_b in oks[1:]:
        producer_ok = jnp.logical_and(producer_ok, ok_b)
    a = a_parts[0] if len(bounds) == 1 else jnp.concatenate(a_parts)
    mom_out = None
    if dgc:
        mom_out = (mom_parts[0] if len(bounds) == 1
                   else jnp.concatenate(mom_parts))
    cand_k = ck_parts[0] if len(bounds) == 1 else jnp.concatenate(ck_parts)
    cand_i = ci_parts[0] if len(bounds) == 1 else jnp.concatenate(ci_parts)
    return a, mom_out, cand_k, cand_i, producer_ok


@stages.scope("sweep")
def _candidates_xla(kind, g, err_prev, c, *, k: int, momentum: float,
                    mom, bounds, gate=None, g_segments=None):
    """Per-bucket XLA candidate compaction.

    Sweep 1 is one fused elementwise pass over the whole vector (XLA
    fuses across bucket slices anyway); sweep 2's per-row top-W
    compaction runs per bucket, its rows interleaved within the bucket,
    so each bucket's candidate chain is independent. Returns per-bucket
    (full_cover, row_min) witnesses — the exactness check needs the
    global tau_k, known only after the trim. Candidate positions do not
    follow the global index (interleaved rows); the trim's explicit
    (key, index) ranking (``_rank``) keeps the flat path's tie-break
    semantics bit-for-bit.

    ``g_segments`` (streaming, DESIGN.md §2.8): sweep 1 runs per
    segment over the standalone segment arrays instead, so the WHOLE
    per-segment chain (sweep-1 + compaction — no shared threshold on
    this strategy) depends only on that segment's gradient; the first
    cross-segment join is the trim. Elementwise math commutes with the
    partition, so ``a`` (concatenated) and every candidate key are
    bitwise identical to the flat pass.
    """
    j = err_prev.shape[0]
    if g_segments is None:
        a, score, mom_out = _sweep1_xla(kind, g, err_prev, c,
                                        momentum=momentum, mom=mom,
                                        gate=gate)
        keys = jnp.abs(score)
        key_parts = [keys[off:off + size] for off, size in bounds]
    else:
        a_parts, key_parts, mom_parts = [], [], []
        for pos, (off, size) in enumerate(bounds):
            a_p, score_p, mom_p = _sweep1_xla(
                kind, g_segments[pos], err_prev[off:off + size], c,
                momentum=momentum,
                mom=None if mom is None else mom[off:off + size],
                gate=gate)
            a_parts.append(a_p)
            key_parts.append(jnp.abs(score_p))
            mom_parts.append(mom_p)
        a = a_parts[0] if len(bounds) == 1 else jnp.concatenate(a_parts)
        mom_out = None
        if kind == "dgc":
            mom_out = (mom_parts[0] if len(bounds) == 1
                       else jnp.concatenate(mom_parts))
    if kind != "dgc":
        mom_out = None
    ck_parts, ci_parts, witnesses = [], [], []
    for (off, size), key_s in zip(bounds, key_parts):
        # density over the GLOBAL j: a bucket's rows are provisioned
        # exactly like the flat path's (witness + fallback cover
        # concentration), so bucketing adds no candidate-slot cost
        cv, ci, row_min, full_cover = px.candidates_xla(
            key_s, k, density_len=(j if len(bounds) > 1 else 0))
        ck_parts.append(cv)
        ci_parts.append(ci + jnp.uint32(off))
        witnesses.append((full_cover, row_min))
    cand_k = ck_parts[0] if len(bounds) == 1 else jnp.concatenate(ck_parts)
    cand_i = ci_parts[0] if len(bounds) == 1 else jnp.concatenate(ci_parts)
    return a, mom_out, cand_k, cand_i, witnesses


def _fused_randk(g, err_prev, *, k: int, key, want_ghat: bool,
                 ef_dtype, allocation: str = "global",
                 seg_bounds=None, pf=None, g_segments=None,
                 stream_bounds=None) -> dict:
    """Fused RANDOM-k: selection is score-free, so the whole step is ONE
    elementwise sweep (the err_prev + g stream) plus O(k) random gathers
    and the O(k) scatter-zero state write — no sweep 2, no histogram, no
    trim. The elementwise form is optimal on every backend (XLA fuses
    it; a Pallas grid would add nothing), so all strategies share it.
    Index stream is identical to the reference randk's (both call
    select.randk_indices — or, for allocation != "global", the shared
    per-segment sampler allocate.randk_allocated_indices — on the same
    key). Allocated randk draws a uniform k_l-subset per segment with
    the PROPORTIONAL counts (score-free selection has no statistic for
    "adaptive" to adapt to; the degrade is documented, DESIGN.md §2.6)."""
    from repro.core import bigvec
    from repro.core.select import randk_indices
    assert key is not None, "randk needs a PRNG key"
    j = err_prev.shape[0]
    with stages.scope("sweep"):
        if g_segments is not None:
            # streaming: the one elementwise sweep runs per segment (err
            # + g commutes with the partition bitwise); index sampling is
            # selection-score-free, so nothing else changes
            a_parts = [
                _sweep1_xla("randk", g_segments[pos],
                            err_prev[off:off + size], jnp.float32(1.0),
                            momentum=0.0, mom=None)[0]
                for pos, (off, size) in enumerate(stream_bounds)]
            a = (a_parts[0] if len(a_parts) == 1
                 else jnp.concatenate(a_parts))
        else:
            a, _, _ = _sweep1_xla("randk", g, err_prev, jnp.float32(1.0),
                                  momentum=0.0, mom=None)
    if allocation != "global":
        from repro.core import allocate
        bounds = seg_bounds or allocate.segment_bounds(
            j, allocate.DEFAULT_SEGMENTS)
        counts = allocate.proportional_counts(k, [sz for _, sz in bounds])
        idx = allocate.randk_allocated_indices(key, bounds, counts)
    else:
        idx = randk_indices(key, j, k)
    # gather before the scatter-zero: a's buffer is read-complete when
    # the O(k) state write runs, so it updates in place
    values = bigvec.gather(a, idx)
    count = jnp.asarray(k, jnp.int32)
    with stages.scope("ef_write"):
        if pf is None:
            err = bigvec.scatter_set(a.astype(jnp.dtype(ef_dtype)), idx,
                                     0.0)
        else:
            # elastic: a sitting-out worker keeps err = a (= decayed err
            # — inputs are pre-masked) and ships an inert payload
            err = bigvec.scatter_set(a.astype(jnp.dtype(ef_dtype)),
                                     bigvec.live_idx(idx, pf, j), 0.0,
                                     mode="drop")
            values = jnp.where(pf, values, 0.0)
            idx = jnp.where(pf, idx, jnp.zeros_like(idx))
            count = jnp.where(pf, count, 0)
    ghat = None
    if want_ghat:
        with stages.scope("exchange"):
            ghat = bigvec.scatter_set(jnp.zeros((j,), jnp.float32), idx,
                                      values)
    return {"err": err, "values": values, "indices": idx,
            "ghat": ghat, "mom": None, "count": count,
            "tau": None, **trim_counters(True, 0)}


def fused_sketch_encode(g, err_prev, *, rows: int, width: int,
                        strategy: Optional[str] = None,
                        participate=None, err_decay: float = 1.0) -> dict:
    """Sweep 1 with the CountSketch ENCODE folded in (DESIGN.md §2.9).

    The sketch-coordinated path (kind="sketchtopk") has no per-worker
    selection — the shared mask is decoded from the all-reduced sketch
    at the aggregate level — so its per-worker compress unit is exactly
    this: accumulate a = err_prev + g and encode it into a (rows, width)
    CountSketch, bit-identical to core.sketch.encode. Returns
    {"a": (J,) fp32, "sketch": (rows, width) fp32}.

    Budget (audit.py absolutes, pinned in tests/test_sketch.py):

    - strategy="pallas_interpret": ONE combined kernel emits a and the
      sketch in a single pass — 1.0 traversal, 1.0 J-sized write.
    - strategy="xla": the elementwise a-stream (XLA-fused) plus
      ``core.sketch.encode``: one 1D scatter pass over ``a`` per row,
      each with a J-sized hash and a J-sized update write of its own,
      so it audits at 1 + rows traversals and 1 + 2 * rows write units,
      above the kernel's budget (tests/test_sketch.py pins both).

    The (rows, width) sketch output is below the audit's sizable floor
    at every bench shape (width ~ 4k << J/16), so the encode adds no
    write units. ``participate`` applies the standard elastic input
    masking (masked_inputs): a sitting-out worker encodes its decayed
    error feedback — the aggregate zeroes its sketch before the
    all-reduce, this just keeps the EF stream bit-comparable.
    """
    from repro.core import sketch as core_sketch
    strategy = resolve_strategy(strategy)
    if participate is not None:
        g, err_prev, _pf = masked_inputs(g, err_prev, participate,
                                         err_decay)
    if strategy == "pallas_interpret":
        a, sk = pk.sweep1_sketch_pallas(
            g, err_prev, rows=rows, width=width,
            mults=tuple(int(x) for x in core_sketch._MULTS[:rows]),
            adds=tuple(int(x) for x in core_sketch._ADDS[:rows]),
            interpret=True)
    else:
        a = err_prev.astype(jnp.float32) + g.astype(jnp.float32)
        sk = core_sketch.encode(a, rows, width)
    return {"a": a, "sketch": sk}


@stages.scope("sweep")
def _seg_candidates_pallas(kind, g, err_prev, c, step, *, provs, k: int,
                           regtopk: bool, momentum: float, mom, bounds,
                           gate=None, g_segments=None):
    """Per-SEGMENT Pallas sweeps for allocation != "global" (DESIGN.md
    §2.6): unlike the bucketed global path (one merged-histogram tau),
    each segment's sweep-1 histogram picks its OWN threshold at target
    provs[l] (the segment's provisioning budget — its static count for
    proportional, its cap for adaptive — plus the REGTOP-k
    support-correction margin), so the segment's candidates cover its
    own top-provs[l] regardless of other segments' magnitudes — the
    coverage the per-segment trim needs. Candidate parts stay SEPARATE
    (the trim is per segment). Returns (a, mom_out, ck_parts, ci_parts,
    ok_parts)."""
    dgc = kind == "dgc"
    a_parts, mom_parts = [], []
    ck_parts, ci_parts, ok_parts = [], [], []
    for pos, (off, size) in enumerate(bounds):
        g_s = (g_segments[pos] if g_segments is not None
               else g[off:off + size])
        a_p, score_p, mom_p, hist = _sweep1_slice(
            kind, g_s, err_prev[off:off + size], c, momentum=momentum,
            mom_s=None if mom is None else mom[off:off + size],
            gate=gate)
        # support corrections may drop <= min(k, size) in-segment entries
        # below tau without breaking coverage of the segment's top-prov
        target = provs[pos] + jnp.where(
            jnp.logical_and(regtopk, step > 0), int(min(k, size)), 0)
        tau = pk.threshold_from_hist(hist, target)
        maxpb = int(min(pk.BLOCK,
                        max(32, -(-8 * provs[pos] * pk.BLOCK // size))))
        ck, ci, ok_b = _sweep2_slice(score_p, tau, off, size, maxpb)
        ck_parts.append(ck)
        ci_parts.append(ci)
        ok_parts.append(ok_b)
        a_parts.append(a_p)
        if dgc:
            mom_parts.append(mom_p)
    a = a_parts[0] if len(bounds) == 1 else jnp.concatenate(a_parts)
    mom_out = None
    if dgc:
        mom_out = (mom_parts[0] if len(bounds) == 1
                   else jnp.concatenate(mom_parts))
    return a, mom_out, ck_parts, ci_parts, ok_parts


@stages.scope("sweep")
def _seg_candidates_xla(kind, g, err_prev, c, *, provs, slack, momentum,
                        mom, bounds, gate=None, g_segments=None):
    """Per-SEGMENT XLA candidate compaction for allocation != "global":
    sweep 1 stays one fused elementwise pass; each segment's per-row
    top-W compaction is provisioned for ITS budget (provs[l] over the
    segment length — per-segment density, not global): the static
    counts for proportional (the realized selection, same 4x slack as
    the global path), the cap for adaptive (an adaptive segment may
    hold up to cap_l of the budget however the other segments score —
    at reduced slack, since the cap already embeds the clip headroom).
    Candidate parts stay separate; per-segment (full_cover, row_min)
    witnesses are checked against the segment's OWN realized threshold
    in the trim."""
    if g_segments is None:
        a, score, mom_out = _sweep1_xla(kind, g, err_prev, c,
                                        momentum=momentum, mom=mom,
                                        gate=gate)
        keys = jnp.abs(score)
        key_parts = [keys[off:off + size] for off, size in bounds]
    else:
        # streaming: sweep 1 per segment (bitwise — elementwise math
        # commutes with the partition); the candidate chain below is
        # already per segment, so each segment's whole compression chain
        # depends only on its own gradient array
        a_parts, key_parts, mom_parts = [], [], []
        for pos, (off, size) in enumerate(bounds):
            a_p, score_p, mom_p = _sweep1_xla(
                kind, g_segments[pos], err_prev[off:off + size], c,
                momentum=momentum,
                mom=None if mom is None else mom[off:off + size],
                gate=gate)
            a_parts.append(a_p)
            key_parts.append(jnp.abs(score_p))
            mom_parts.append(mom_p)
        a = a_parts[0] if len(bounds) == 1 else jnp.concatenate(a_parts)
        mom_out = (None if kind != "dgc" else
                   (mom_parts[0] if len(bounds) == 1
                    else jnp.concatenate(mom_parts)))
    if kind != "dgc":
        mom_out = None
    ck_parts, ci_parts, wit_parts = [], [], []
    for pos, (off, size) in enumerate(bounds):
        cv, ci, row_min, full_cover = px.candidates_xla(
            key_parts[pos], provs[pos], slack=slack)
        ck_parts.append(cv)
        ci_parts.append(ci + jnp.uint32(off))
        wit_parts.append((full_cover, row_min))
    return a, mom_out, ck_parts, ci_parts, wit_parts


def _fused_allocated(kind, g, err_prev, step, *, k: int, omega, mu, Q,
                     momentum, mom, idx_prev, a_prev_sel, g_prev_sel,
                     want_ghat: bool, strategy: str, allocation: str,
                     seg_bounds, ef_dtype, gate=None, pf=None,
                     g_segments=None) -> dict:
    """Fused compress step with per-segment budget allocation
    (allocation in {"proportional", "adaptive"}, DESIGN.md §2.6).

    Same two-sweep structure and O(k) state tail as the global exact
    path; what changes is the trim: the global O(cand) exact-k trim is
    replaced by PER-SEGMENT trims (top-cap_l candidates ranked, leading
    k_l live) plus one O(sum(caps)) pack that keeps the output at
    exactly k (values, indices) pairs — sum(k_l) == k, so the packed
    wire format (and sparse-comm bytes) is unchanged. Adaptive k_l
    comes from per-segment top-mass statistics of the CORRECTED ranked
    candidate pool (support corrections applied first, so the sums
    equal allocate.dense_segment_moments bitwise when the covers hold;
    O(segments * cap log cap), no extra O(J) traversal — audit-gated at
    2.0 sweeps). Exactness witnesses are per segment (coverage vs the
    segment's own realized threshold — and, for adaptive, vs the ranked
    top-cap the statistics were summed over — and candidate-capacity
    overflow; ties rank by index explicitly); any failure takes the
    lax.cond fallback to dense per-segment selection with identical
    semantics, INCLUDING densely recomputed adaptive counts — the
    fallback branch IS the reference pipeline's allocated selector
    (allocate.reference_allocated_select), which is what
    tests/test_allocate.py::TestAllocatedParity (incl. the regtopk
    stress seeds) pins."""
    from repro.core import allocate, bigvec
    j = err_prev.shape[0]
    bounds = seg_bounds or allocate.segment_bounds(
        j, allocate.DEFAULT_SEGMENTS)
    if g_segments is not None:
        # streaming requires the stream partition == the allocation
        # partition (sparsify routes both off the same resolved bounds)
        assert len(g_segments) == len(bounds), (len(g_segments),
                                                len(bounds))
    sizes = [sz for _, sz in bounds]
    caps = allocate.segment_caps(k, sizes)
    # candidate provisioning per segment: proportional realizes its
    # STATIC counts, so provision exactly those at the global path's 4x
    # row slack; adaptive may tilt any segment up to its cap, so
    # provision the cap — at 2x slack, since the cap already embeds the
    # ADAPTIVE_CLIP**2 headroom over the typically-realized count (the
    # row_min witness + fallback still guard adversarial concentration)
    if allocation == "proportional":
        counts_static = allocate.proportional_counts(k, sizes)
        provs = [max(1, ci) for ci in counts_static]
        trim_caps = provs
        slack = 4.0
    else:
        counts_static = None
        provs = caps
        trim_caps = caps
        slack = 2.0
    regtopk = kind == "regtopk"
    c = _offsupport_reg(step, Q, mu) if regtopk else jnp.float32(1.0)

    if strategy == "pallas_interpret":
        a, mom_out, ck_parts, ci_parts, ok_parts = _seg_candidates_pallas(
            kind, g, err_prev, c, step, provs=provs, k=k, regtopk=regtopk,
            momentum=momentum, mom=mom, bounds=bounds,
            gate=gate, g_segments=g_segments)
        wit_parts = None
        ok = ok_parts[0]
        for ok_b in ok_parts[1:]:
            ok = jnp.logical_and(ok, ok_b)
    else:
        a, mom_out, ck_parts, ci_parts, wit_parts = _seg_candidates_xla(
            kind, g, err_prev, c, provs=provs, slack=slack,
            momentum=momentum, mom=mom, bounds=bounds, gate=gate,
            g_segments=g_segments)
        ok = jnp.asarray(True)

    # REGTOP-k support corrections, candidate space, routed per segment:
    # disable support members' uncorrected candidate keys everywhere;
    # append every support entry to ITS segment with the corrected key
    # (masked -inf elsewhere). Done BEFORE the adaptive statistics —
    # they must see the CORRECTED pool, exactly like the dense oracle
    # (allocate.dense_segment_moments over the corrected score).
    skey = None
    if regtopk:
        with stages.scope("support"):
            skey = _posterior_keys(bigvec.gather(a, idx_prev), a_prev_sel,
                                   g_prev_sel, step, omega=omega, mu=mu)
            idx_sorted = jnp.sort(idx_prev.astype(jnp.uint32))
            for pos in range(len(bounds)):
                ci_l = ci_parts[pos]
                p = jnp.minimum(jnp.searchsorted(idx_sorted, ci_l),
                                idx_sorted.shape[0] - 1)
                hit = (idx_sorted[p] == ci_l) & (step > 0)
                ck_parts[pos] = jnp.where(hit, -jnp.inf, ck_parts[pos])

    # phase A, per segment: corrected candidate pool, rank the
    # top-trim_cap_l (counts-independent), gather the signed a-values
    # BEFORE the cond (in-place err scatter), and — for adaptive — the
    # top-cap mass moments from the RANKED CORRECTED keys, which equal
    # allocate.dense_segment_moments bitwise whenever the cover holds
    # (same sorted values, same summation order)
    with stages.scope("trim"):
        seg_trims, ms = [], []
        for pos, ((off, size), cap) in enumerate(zip(bounds, trim_caps)):
            allk, alli = ck_parts[pos], ci_parts[pos]
            if regtopk:
                in_seg = ((idx_prev >= jnp.uint32(off))
                          & (idx_prev < jnp.uint32(off + size)))
                allk = jnp.concatenate([allk,
                                        jnp.where(in_seg, skey, -jnp.inf)])
                alli = jnp.concatenate([alli, idx_prev.astype(jnp.uint32)])
            eff = max(1, int(min(cap, allk.shape[0])))
            tv, isel = _rank(allk, alli, eff)
            vsel = bigvec.gather(a, jnp.minimum(isel, jnp.uint32(j - 1)))
            seg_trims.append((tv, isel, vsel, eff))
            if allocation == "adaptive":
                ms.append(jnp.sum(jnp.where(tv > -jnp.inf, tv * tv, 0.0)))
                if eff < cap:
                    # ranked pool shorter than the statistic's window: the
                    # top-cap mass cannot be complete — route to fallback
                    ok = ok & jnp.asarray(False)
        if allocation == "adaptive":
            counts = allocate.adaptive_counts(k, sizes, jnp.stack(ms),
                                              caps=caps)
        else:
            counts = jnp.asarray(counts_static, jnp.int32)

        # phase B, per segment: leading counts[l] of the ranking are live;
        # witnesses guard the selection cover AND (adaptive) the statistic's
        # top-cap cover, so a truncated cover can never silently shift k_l
        pk_parts, pi_parts, pv_parts = [], [], []
        saturated = 0
        for pos, (tv, isel, vsel, eff) in enumerate(seg_trims):
            kl = counts[pos]
            has = kl > 0
            live = jnp.arange(eff, dtype=jnp.int32) < kl
            kth = tv[jnp.clip(kl - 1, 0, eff - 1)]
            ok = ok & jnp.where(has, kth > -jnp.inf, True) & (kl <= eff)
            if wit_parts is not None:
                full_cover, row_min = wit_parts[pos]
                tau_l = jnp.where(has, kth, jnp.inf)
                if allocation == "adaptive":
                    # stricter: no row may hide an entry that belongs in the
                    # ranked top-eff the moments were summed over
                    tau_l = jnp.minimum(tau_l, tv[eff - 1])
                ok = ok & (full_cover | (jnp.max(row_min) < tau_l))
                if not full_cover:
                    saturated = saturated + jnp.sum(row_min >= tau_l)
            pk_parts.append(jnp.where(live, tv, -jnp.inf))
            pi_parts.append(isel)
            pv_parts.append(vsel)
        # pack: one O(sum(caps)) top-k over the live-masked union -> exactly
        # the sum(k_l) == k live entries, ordered by key desc (ties resolve
        # segment-major then index asc — allocated_select_dense's order;
        # each segment's ranking is already index-ascending among ties)
        packk = jnp.concatenate(pk_parts)
        packi = jnp.concatenate(pi_parts)
        packv = jnp.concatenate(pv_parts)
        _tvg, sel = jax.lax.top_k(packk, k)
        idx_fast = packi[sel]
        val_fast = packv[sel]

    def _flat_g():
        # fallback-only: materialize the flat (effective) gradient — on
        # the streaming path it exists only as segment arrays, and the
        # concat must happen INSIDE the cond branch so the fast path
        # never pays it (cond audits as the min over branches)
        return g if g_segments is None else jnp.concatenate(g_segments)

    def _gather_inputs(idx):
        # fallback-only: recompute a[idx] from the function parameters
        # (bitwise identical; keeps `a` read-complete before the cond)
        gi = bigvec.gather(_flat_g(), idx).astype(jnp.float32)
        ei = bigvec.gather(err_prev, idx).astype(jnp.float32)
        if kind == "dgc":
            mi = momentum * bigvec.gather(mom, idx).astype(jnp.float32) + gi
            return ei + (mi if gate is None else jnp.where(gate, mi, 0.0))
        return ei + gi

    def _fast(_):
        return idx_fast, val_fast

    @stages.scope("fallback")
    def _fallback(_):
        a2, score2, _ = _sweep1_xla(kind, _flat_g(), err_prev, c,
                                    momentum=momentum, mom=mom, gate=gate)
        keys_d = jnp.abs(score2)
        if regtopk:
            base = bigvec.gather(keys_d, idx_prev)
            fix = jnp.where(step > 0, skey, base)
            keys_d = bigvec.scatter_set(keys_d, idx_prev, fix, mode="drop")
        if allocation == "adaptive":
            # dense statistics, not the (witness-failed) candidate ones:
            # this branch IS the reference allocated selector, so fused
            # output equals the reference pipeline's even when covers
            # fail (tests/test_allocate.py::TestAllocatedParity stress)
            counts_d = allocate.adaptive_counts(
                k, sizes,
                allocate.dense_segment_moments(keys_d, bounds, caps),
                caps=caps)
        else:
            counts_d = counts
        idx_d, _kv = allocate.allocated_select_dense(keys_d, bounds, caps,
                                                     counts_d, k)
        return idx_d, _gather_inputs(idx_d)

    with stages.scope("trim"):
        idx_k, values = jax.lax.cond(ok, _fast, _fallback, operand=None)
    # O(k) state tail, identical to the global exact path; under elastic
    # participation a sitting-out worker skips the scatter-zero (sentinel
    # + drop) so err/mom keep their decayed values, and the packed
    # payload is masked inert
    count = jnp.asarray(k, jnp.int32)
    idx_w = idx_k
    if pf is not None:
        idx_w = bigvec.live_idx(idx_k, pf, j)
        values = jnp.where(pf, values, 0.0)
        idx_k = jnp.where(pf, idx_k, jnp.zeros_like(idx_k))
        count = jnp.where(pf, count, 0)
    dt = jnp.dtype(ef_dtype)
    with stages.scope("ef_write"):
        err = bigvec.scatter_set(a.astype(dt), idx_w, 0.0, mode="drop")
        if kind == "dgc":
            mom_out = bigvec.scatter_set(mom_out.astype(dt), idx_w, 0.0,
                                         mode="drop")
    ghat = None
    if want_ghat:
        with stages.scope("exchange"):
            ghat = bigvec.scatter_set(jnp.zeros((j,), jnp.float32),
                                      idx_k, values)
    return {"err": err, "values": values,
            "indices": idx_k.astype(jnp.uint32), "ghat": ghat,
            "mom": mom_out, "count": count,
            "tau": None, **trim_counters(ok, saturated)}


def fused_compress_arrays(kind: str, g, err_prev, step, *, k: int,
                          omega=1.0, mu: float = 0.1, Q: float = 0.0,
                          momentum: float = 0.9, mom=None,
                          idx_prev=None, a_prev_sel=None, g_prev_sel=None,
                          nsel_prev=None, want_ghat: bool = True,
                          strategy: Optional[str] = None,
                          num_buckets: int = 1, selector: str = "exact",
                          ef_dtype="float32", key=None,
                          allocation: str = "global",
                          seg_bounds=None, participate=None,
                          err_decay: float = 1.0, g_segments=None,
                          stream_bounds=None) -> dict:
    """One fused compression step. kind in {"topk", "dgc", "regtopk",
    "randk", "thresholdk"} (thresholdk shares the plain-score path with
    topk; randk needs ``key`` and ignores ``selector``).

    Inputs: g (J,) raw gradient; err_prev (J,) the ONE J-sized state
    vector — the previous step's error feedback a^{t-1} * (1 - s^{t-1})
    (fp32 or bf16 per ``ef_dtype``; sweep math is always fp32
    in-register); step () int32. REGTOP-k additionally takes the O(k)
    posterior (idx_prev uint32, a_prev_sel, g_prev_sel; with
    selector="histogram" these are hist_capacity-sized and ``nsel_prev``
    marks how many leading slots are live) — the posterior's idx_prev
    doubles as the support set, so no dense mask exists anywhere in the
    state. DGC takes the momentum buffer ``mom``. ``num_buckets``
    partitions the sweeps into contiguous buckets (DESIGN.md §2.4);
    selection semantics are bucketing-invariant.

    Returns {"err", "values", "indices", "count", "tau", "ghat" (None
    unless want_ghat), "mom" (dgc only: the selection-masked momentum)}.
    ``err`` is the NEXT step's state — ``a`` with the selected slots
    zeroed by an O(k) scatter (bit-identical to the reference's
    a - mask*a), stored in ``ef_dtype``.

    - selector="exact": values/indices are the fixed-k packed pairs
      ordered by |score| descending; selected support is bit-identical
      to the reference exact selector's (and to the flat num_buckets=1
      path) for every num_buckets. count == k, tau is None.
    - selector="histogram": threshold selection at tau =
      key_bin_edge(k-th |score|) — the sweep-1 bit-pattern histogram
      threshold (DESIGN.md §2.5). values/indices are fixed
      hist_capacity(k, j)-sized; ``count`` in [k, capacity] entries are
      live, the tail is inert (value 0.0 at index 0). ``tau`` is the
      realized threshold.
    - allocation in {"proportional", "adaptive"} (DESIGN.md §2.6,
      exact selector only — allocate.check_allocation): the budget
      splits sum(k_l) == k over ``seg_bounds`` (static [(offset, size),
      ...]; near-equal DEFAULT_SEGMENTS cut when None) and the global
      trim becomes per-segment trims + one O(sum(caps)) pack — output
      shapes, the O(k) state tail, and the wire format are unchanged
      (still exactly k pairs).
    - participate (DESIGN.md §2.7): optional traced () bool — this
      worker's elastic participation bit. None (the default) is
      literally today's code path. With a mask, sweep 1 reads the
      masked effective inputs (g_eff = where(p, g, 0), err_eff =
      where(p, err, err_decay * err) — the wheres fuse, no extra
      traversal), a sitting-out worker's O(k) state scatters are
      sentinel-skipped (so err' = err_decay * err in place; DGC's
      mom' = momentum * mom via the kernel gate), and its packed
      payload comes back inert (values 0.0, indices 0, count 0).
      p=True is a bitwise pass-through of the unmasked path.
    - g_segments + stream_bounds (DESIGN.md §2.8): the gradient arrives
      as per-segment arrays (``g`` must be None) partitioned by the
      static ``stream_bounds`` [(offset, size), ...] — the streaming
      form the backward-overlapped train step feeds. Sweeps partition by
      stream_bounds instead of bucket_bounds, so each segment's sweep-1
      (+ EF fold + allocation statistics) depends only on its own
      segment array and can run while later segments are still being
      produced; the trim/pack is the only cross-segment join. Selection
      is partition-invariant (the bucketed-path theorem), so values/
      indices/err are BIT-identical to the flat call, and S partial
      sweeps of J/S elements still audit as 2 traversals. With
      allocation != "global", stream_bounds must equal the resolved
      ``seg_bounds``.
    """
    from repro.core import bigvec
    strategy = resolve_strategy(strategy)
    streaming = g_segments is not None
    if streaming:
        assert g is None, "streaming: pass g_segments, not a flat g"
        assert stream_bounds is not None and \
            len(stream_bounds) == len(g_segments)
        j = err_prev.shape[0]
    else:
        j = g.shape[0]
    k = int(min(k, j))
    # raw FUNCTION PARAMETERS, kept for the trim's lax.cond fallback:
    # the cond must consume these (not the produced masked arrays) or the
    # audit bills the masked intermediates as escaped cond-operand writes
    g_raw, err_raw = g, err_prev
    segs_raw = g_segments
    pf = gate = None
    if participate is not None:
        if streaming:
            # per-segment masking: a scalar-predicate select commutes
            # with the partition, so this matches masked_inputs bitwise
            pf = jnp.asarray(participate, jnp.bool_)
            g_segments = [_scalar_select(pf, gs, jnp.zeros_like(gs))
                          for gs in g_segments]
            err_prev = _decayed_err(err_prev, pf, err_decay)
        else:
            g, err_prev, pf = masked_inputs(g, err_prev, participate,
                                            err_decay)
        gate = pf                      # dgc: a = err_eff + where(p, mom, 0)
    if kind == "randk":
        return _fused_randk(g, err_prev, k=k, key=key,
                            want_ghat=want_ghat, ef_dtype=ef_dtype,
                            allocation=allocation, seg_bounds=seg_bounds,
                            pf=pf, g_segments=g_segments,
                            stream_bounds=stream_bounds)
    if allocation != "global":
        # exact-count selection only (check_allocation gates upstream)
        assert selector == "exact", (allocation, selector)
        return _fused_allocated(
            kind, g, err_prev, step, k=k, omega=omega, mu=mu, Q=Q,
            momentum=momentum, mom=mom, idx_prev=idx_prev,
            a_prev_sel=a_prev_sel, g_prev_sel=g_prev_sel,
            want_ghat=want_ghat, strategy=strategy, allocation=allocation,
            seg_bounds=seg_bounds, ef_dtype=ef_dtype, gate=gate, pf=pf,
            g_segments=g_segments)
    hist = selector == "histogram"
    # static packed capacity; also the candidate-provisioning budget —
    # for exact selection kcap == k and everything below degenerates to
    # the original exact-k trim
    kcap = hist_capacity(k, j) if hist else k
    # streaming partitions the sweeps by the stream segments; selection
    # is partition-invariant, and num_buckets keeps governing only the
    # comm-side chunking of the packed pairs (core.aggregate)
    bounds = stream_bounds if streaming else bucket_bounds(j, num_buckets)
    regtopk = kind == "regtopk"
    c = _offsupport_reg(step, Q, mu) if regtopk else jnp.float32(1.0)

    if strategy == "pallas_interpret":
        a, mom_out, cand_k, cand_i, producer_ok = _candidates_pallas(
            kind, g, err_prev, c, step, k=kcap, regtopk=regtopk,
            momentum=momentum, mom=mom, bounds=bounds,
            gate=gate, g_segments=g_segments)
        witnesses = None
    else:
        a, mom_out, cand_k, cand_i, witnesses = _candidates_xla(
            kind, g, err_prev, c, k=kcap, momentum=momentum, mom=mom,
            bounds=bounds, gate=gate, g_segments=g_segments)
        producer_ok = None                   # needs tau; checked below

    # --- O(candidates) fixed-capacity trim ------------------------------
    def _raw_flat_g():
        # fallback-only: the RAW flat gradient — on the streaming path it
        # exists only as segment params, and the concat runs INSIDE the
        # cond branch so the fast path never pays it (min over branches)
        return g_raw if segs_raw is None else jnp.concatenate(segs_raw)

    def _gather_inputs(idx):
        """a[idx] recomputed from the step's INPUT arrays (bitwise
        identical: per-element adds commute with the gather). Used only
        inside the lax.cond fallback branch, whose operands are already
        the function parameters — gathering from the dense ``a`` there
        would extend a's liveness past the cond and force the err
        scatter-zero to copy the whole buffer. Elastic masking is
        re-applied to the gathered O(k) values (a scalar-predicate
        select commutes with the gather, so this matches
        ``masked_inputs`` bitwise without touching the masked J-sized
        intermediates)."""
        gi = bigvec.gather(_raw_flat_g(), idx).astype(jnp.float32)
        ei = bigvec.gather(err_raw, idx).astype(jnp.float32)
        if pf is not None:
            gi = _scalar_select(pf, gi, 0.0)
            ei = _scalar_select(
                pf, ei,
                (jnp.float32(err_decay) * ei).astype(err_raw.dtype)
                .astype(jnp.float32))
        if kind == "dgc":
            mi = momentum * bigvec.gather(mom, idx).astype(jnp.float32) + gi
            return ei + (mi if gate is None else
                         _scalar_select(gate, mi, 0.0))
        return ei + gi

    support_valid = None
    if regtopk:
        with stages.scope("support"):
            if nsel_prev is not None:
                support_valid = (jnp.arange(idx_prev.shape[0],
                                            dtype=jnp.int32) < nsel_prev)
            skey = _posterior_keys(bigvec.gather(a, idx_prev), a_prev_sel,
                                   g_prev_sel, step, omega=omega, mu=mu,
                                   support_valid=support_valid)
            # candidates that are support members carry an uncorrected
            # key: disable them (the corrected copy is appended below).
            # With no dense mask in the state, membership is resolved
            # against the O(k) posterior support itself — sort +
            # searchsorted in candidate space, O((k + cand) log k), no
            # O(J) array touched.
            if support_valid is not None:
                # inert pad slots alias index 0: exclude them via the
                # out-of-range sentinel before the sort (bigvec.live_idx)
                idx_live = bigvec.live_idx(idx_prev, support_valid, j)
            else:
                idx_live = idx_prev.astype(jnp.uint32)
            idx_sorted = jnp.sort(idx_live)
            pos = jnp.minimum(jnp.searchsorted(idx_sorted, cand_i),
                              idx_sorted.shape[0] - 1)
            hit = (idx_sorted[pos] == cand_i) & (step > 0)
            cand_k = jnp.where(hit, -jnp.inf, cand_k)
            allk = jnp.concatenate([cand_k, skey])
            alli = jnp.concatenate([cand_i, idx_prev.astype(jnp.uint32)])
    else:
        allk, alli = cand_k, cand_i

    with stages.scope("trim"):
        tv, idx_fast = _rank(allk, alli, kcap)
        # signed a-values of the ranked entries, gathered from the dense
        # ``a`` BEFORE the cond: every read of a's buffer stays ahead of
        # the final err scatter-zero, which can then update it in place
        # (a post-cond gather would extend a's liveness and cost a
        # defensive O(J) copy). Clamp: Pallas INVALID_IDX slots carry
        # -inf keys and are never selected on the fast path.
        val_fast = bigvec.gather(a, jnp.minimum(idx_fast, jnp.uint32(j - 1)))
        kth = tv[k - 1]
        valid = kth > -jnp.inf
        # histogram tau: bit-pattern bin lower edge of the k-th key. The
        # sweep-2 compaction threshold (merged-histogram tau at target
        # kcap + margin) is <= this edge, so the candidates cover every
        # entry >= tau (kernel.key_bin_edge docstring).
        tau = pk.key_bin_edge(kth) if hist else kth
        saturated = 0
        if producer_ok is None:                  # xla strategy witness
            # a bucket can hide a missed entry only if one of its rows
            # saturated its W candidate slots at or above the selection
            # threshold (the global tau)
            producer_ok = valid
            for full_cover, row_min in witnesses:
                ok_b = full_cover | (jnp.max(row_min) < tau)
                producer_ok = jnp.logical_and(producer_ok, ok_b)
                if not full_cover:
                    saturated = saturated + jnp.sum(row_min >= tau)
        ok = producer_ok & valid

    def _fallback_keys():
        # the fallback's keys: recompute (a, keys) from the
        # *function parameters* rather than capturing the intermediate
        # `a` — XLA CPU copies non-parameter conditional operands, which
        # would tax the fast path with an O(J) copy. The elastic masking
        # is likewise re-derived INSIDE the branch from the raw params
        # (the masked J-sized arrays must not become cond operands).
        gg, ee = _raw_flat_g(), err_raw
        if pf is not None:
            gg, ee, _ = masked_inputs(gg, err_raw, pf, err_decay)
        a2, score2, _ = _sweep1_xla(kind, gg, ee, c,
                                    momentum=momentum, mom=mom, gate=gate)
        keys_d = jnp.abs(score2)
        if regtopk:
            base = bigvec.gather(keys_d, idx_prev)
            live = step > 0
            if support_valid is not None:
                live = live & support_valid
                # inert pad slots alias index 0: sentinel + drop
                # (bigvec.live_idx docstring)
                idx_w = bigvec.live_idx(idx_prev, support_valid, j)
            else:
                idx_w = idx_prev
            fix = jnp.where(live, skey, base)
            keys_d = bigvec.scatter_set(keys_d, idx_w, fix, mode="drop")
        return keys_d

    if hist:
        def _fast(_):
            return idx_fast, val_fast, tv >= tau, tau

        @stages.scope("fallback")
        def _fallback(_):
            keys_d = _fallback_keys()
            from repro.core import select
            idx_d = select.topk_indices(keys_d, kcap)
            tvd = bigvec.gather(keys_d, idx_d)
            tau_d = pk.key_bin_edge(tvd[k - 1])
            return idx_d, _gather_inputs(idx_d), tvd >= tau_d, tau_d

        with stages.scope("trim"):
            idx_k, vraw, valid_sel, tau = jax.lax.cond(ok, _fast, _fallback,
                                                       operand=None)
            if pf is not None:
                # elastic: a sitting-out worker's payload is wholly inert
                # — masking valid_sel itself routes the state scatters to
                # the sentinel (err keeps its decayed value) AND zeroes
                # values/indices/count through the pad-slot handling below
                valid_sel = valid_sel & pf
            values = jnp.where(valid_sel, vraw, 0.0)
            idx_k = jnp.where(valid_sel, idx_k, 0).astype(jnp.uint32)
            count = jnp.sum(valid_sel.astype(jnp.int32))
            # inert pad slots must never zero a live entry's error
            # feedback: sentinel + drop for the O(k) state scatters
            # (bigvec.live_idx)
            idx_w = bigvec.live_idx(idx_k, valid_sel, j)
        ghat = None
        if want_ghat:
            # scatter-ADD: a pad's (0, 0.0) never clobbers index 0
            with stages.scope("exchange"):
                ghat = bigvec.scatter_add(jnp.zeros((j,), jnp.float32),
                                          idx_k, values)
    else:
        def _fast(_):
            return idx_fast, val_fast

        @stages.scope("fallback")
        def _fallback(_):
            from repro.core import select
            idx_d = select.topk_indices(_fallback_keys(), k)
            return idx_d, _gather_inputs(idx_d)

        with stages.scope("trim"):
            idx_k, values = jax.lax.cond(ok, _fast, _fallback,
                                         operand=None)
        count = jnp.asarray(k, jnp.int32)
        tau = None
        idx_w = idx_k                        # exact: all k slots live
        if pf is not None:
            # elastic: sentinel-skip the state scatters and mask the
            # packed payload inert for a sitting-out worker
            idx_w = bigvec.live_idx(idx_k, pf, j)
            values = jnp.where(pf, values, 0.0)
            idx_k = jnp.where(pf, idx_k, jnp.zeros_like(idx_k))
            count = jnp.where(pf, count, 0)
        ghat = None
        if want_ghat:
            with stages.scope("exchange"):
                ghat = bigvec.scatter_set(jnp.zeros((j,), jnp.float32),
                                          idx_k, values)
    # --- O(k) state writes ---------------------------------------------
    # err^{t+1} = a * (1 - s): zero the selected slots of a in place —
    # the ONLY J-sized state, written by an O(k) scatter (the third
    # O(J) traversal of the old (a_prev, s_prev) layout is gone). The
    # ef_dtype cast happens BEFORE the scatter so bf16 state fuses into
    # the sweep-1 stream instead of adding a post-scatter convert pass.
    dt = jnp.dtype(ef_dtype)
    with stages.scope("ef_write"):
        err = bigvec.scatter_set(a.astype(dt), idx_w, 0.0, mode="drop")
        if kind == "dgc":
            # momentum masking mom * (1 - s), same O(k) scatter-zero
            mom_out = bigvec.scatter_set(mom_out.astype(dt), idx_w, 0.0,
                                         mode="drop")
    return {"err": err, "values": values,
            "indices": idx_k.astype(jnp.uint32), "ghat": ghat,
            "mom": mom_out, "count": count, "tau": tau,
            **trim_counters(ok, saturated)}
