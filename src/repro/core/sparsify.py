"""Gradient sparsifiers: TOP-k, REGTOP-k (the paper, Algorithm 1), and baselines.

All sparsifiers are functional and operate on a flat fp32 vector ``g`` (one
data-parallel worker's gradient, or its model-parallel shard). The state is a
small pytree carried through the training loop.

Protocol per step (worker n):

    out = compress(cfg, state, g, key)     # local: mask + sparsified gradient
    g_agg = aggregate(out.ghat over data axis)   # see core/aggregate.py
    state = observe_aggregate(cfg, out.state, g_agg)  # REGTOP-k stores g^t

``observe_aggregate`` is a no-op for history-free sparsifiers.

REGTOP-k (Algorithm 1 of the paper):
    a^t      = eps^t + g^t
    Delta^t  = s^{t-1} * (g_agg^{t-1} - w_n a^{t-1}) / (w_n a^t) + Q (1 - s^{t-1})
    s^t      = Top_k( a^t * tanh(|1 + Delta^t| / mu) )
    ghat^t   = s^t * a^t
    eps^{t+1}= a^t - ghat^t
with plain TOP-k at t=0. mu -> 0 recovers TOP-k exactly.

Execution pipelines (cfg.pipeline, DESIGN.md §2.2):

- "reference": the dense math above, selection via cfg.selector. Oracle.
- "fused": two-sweep pipeline (repro.kernels.compress) for kind in
  {topk, dgc, regtopk, randk, thresholdk}. The ONLY J-sized state is
  ``err_prev`` = eps^{t+1} = a^t * (1 - s^t), written by an O(k)
  scatter that zeroes the selected slots of ``a`` after the trim — no
  dense mask exists anywhere (CompressOut.mask is None on this path;
  reconstruct one on demand with :func:`dense_mask`), and REGTOP-k's
  posterior is O(k) (idx_prev, a_prev_sel, g_prev_sel), since
  Algorithm 1 line 5 reads a^{t-1} and g^{t-1} only at the support of
  s^{t-1} — idx_prev doubles as that support set. With
  selector="exact" the selected support is bit-identical to
  "reference"; selector="histogram" keeps the threshold-selection
  contract (count in [k, k*(1+HIST_SLACK)], tau at a bit-pattern bin
  edge); ef_dtype="bfloat16" stores the J-sized EF state in bf16 with
  fp32 in-register sweep math. In comm_mode="sparse" no dense ghat is
  materialized (CompressOut.ghat is None and the packed
  (values, indices) drive the all-gather) and the whole step is TWO
  O(J) traversals (DESIGN.md §2.2). Which path serves a config is an
  explicit table — repro.kernels.compress.dispatch (DESIGN.md §2.5) —
  not an opaque boolean.

Density allocation (cfg.allocation, DESIGN.md §2.6, core/allocate.py):
both pipelines can split the budget sum(k_l) == k across contiguous
segments (near-equal, or layer-aligned bounds passed by the train step)
before selection — "proportional" (k_l ~ J_l) and "adaptive" (k_l from
per-segment second-moment statistics). "global" is the default and is
bit-identical to the pre-allocation pipeline. State layouts, packed
shapes, and wire bytes are allocation-invariant.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import SparsifierConfig
from repro.core import select, stages
from repro.core.numerics import safe_denom


@dataclass
class CompressOut:
    ghat: Optional[jnp.ndarray]  # dense sparsified gradient (J,); None for
                                 # pipeline="fused" + comm_mode="sparse"
                                 # (reconstructible from values/indices)
    mask: Optional[jnp.ndarray]  # dense 0/1 selection mask (J,) on the
                                 # reference path; None on the fused path
                                 # (no dense mask is ever materialized —
                                 # derive one on demand via dense_mask())
    state: Any               # updated state (pre-aggregation)
    values: Optional[jnp.ndarray] = None  # (k,) packed values (exact selector)
    indices: Optional[jnp.ndarray] = None  # (k,) uint32 indices
    count: Optional[jnp.ndarray] = None   # live packed slots (() int32);
                                          # None means all slots are live
    trim: Optional[dict] = None  # the fused trim's counters
                                 # (ops.trim_counters); None where no
                                 # fused trim ran (reference pipeline)


def resolve_k(cfg: SparsifierConfig, j: int) -> int:
    if cfg.k:
        return int(min(cfg.k, j))
    return max(1, int(round(cfg.sparsity * j)))


# ---------------------------------------------------------------------------
# State
# ---------------------------------------------------------------------------

def resolve_num_buckets(cfg: SparsifierConfig, j: int,
                        n_workers: int = 1) -> int:
    """cfg.num_buckets, with 0 resolved to the auto-tuned value.

    The auto-tune (ROADMAP item, DESIGN.md §2.4) derives the bucket
    count from the sparse-collective payload this config moves —
    n_workers * packed_len * 8 bytes — against the interconnect latency
    floor, via the roofline pipelined-overlap model
    (roofline.analysis.auto_num_buckets). Deterministic in (cfg, j,
    n_workers), so a manual ``num_buckets=<resolved>`` flag reproduces
    the auto choice bit-for-bit (bucketing never changes selection
    semantics either way)."""
    if cfg.num_buckets != 0:
        return max(1, int(cfg.num_buckets))
    from repro.kernels.compress.dispatch import packed_len
    from repro.roofline.analysis import auto_num_buckets
    return auto_num_buckets(packed_len(cfg, j), n_workers)


def _workers_from_omega(omega) -> int:
    """Equal-weight worker count implied by omega = 1/N (the only
    information a bare compress() call has for the bucket auto-tune;
    sync_gradient resolves from the real mesh axis size instead). A
    TRACED omega is a hard error, not a silent N=1: auto_num_buckets
    would mis-tune the payload by the real worker count — resolve the
    bucket count upstream (resolve_num_buckets / sync_gradient) in
    that case."""
    if isinstance(omega, jax.core.Tracer):
        raise TypeError(
            "num_buckets=0 auto-tune inside compress() needs a concrete "
            "omega (= 1/N) to infer the worker count; with a traced "
            "omega, resolve the bucket count upstream via "
            "sparsify.resolve_num_buckets or aggregate.sync_gradient.")
    try:
        return max(1, int(round(1.0 / float(omega))))
    except (TypeError, ValueError, ZeroDivisionError):
        return 1


def init_state(cfg: SparsifierConfig, j: int) -> dict:
    """Zero-initialized per-worker sparsifier state for a J-length flat
    gradient.

    Shapes/dtypes by layout (all vectors cfg.ef_dtype unless noted):

    - fused (dispatch(cfg).path == "fused"): ``err_prev`` (J,) — the ONE
      J-sized vector — plus ``step`` () int32; DGC adds ``mom`` (J,);
      REGTOP-k adds the O(k) posterior ``idx_prev`` (kp,) uint32 /
      ``a_prev_sel`` / ``g_prev_sel`` (kp,) with kp = packed_len(cfg, j)
      (and ``nsel`` () int32 for the histogram selector's live count).
    - reference: ``err`` (J,) for the EF kinds; DGC adds ``mom`` (J,);
      REGTOP-k state_format="dense" adds (a_prev, s_prev, g_agg_prev)
      (J,) each, state_format="sparse" the O(k) triple instead.

    Layout parity across pipelines is pinned by
    tests/test_state_traffic.py (err_prev == reference err bitwise) and
    tests/test_checkpoint.py (round-trip + legacy migration). Density
    allocation adds NO state — every mode reuses these layouts.
    """
    from repro.kernels.compress.dispatch import dispatch
    dt = jnp.dtype(cfg.ef_dtype)
    z = jnp.zeros((j,), dt)
    if dispatch(cfg).path == "fused":
        # ONE J-sized state vector: err_prev = a^{t-1} * (1 - s^{t-1}),
        # maintained by the O(k) scatter-zero that closes each step (no
        # dense mask exists in the fused layout)
        st = {
            "err_prev": z,
            "step": jnp.zeros((), jnp.int32),
        }
        if cfg.kind == "dgc":
            st["mom"] = z
        if cfg.kind == "regtopk":
            from repro.kernels.compress.dispatch import packed_len
            kp = packed_len(cfg, j)   # k, or hist_capacity for histogram
            st["idx_prev"] = jnp.zeros((kp,), jnp.uint32)
            st["a_prev_sel"] = jnp.zeros((kp,), dt)
            st["g_prev_sel"] = jnp.zeros((kp,), dt)
            if cfg.selector == "histogram":
                # live-slot count of the fixed-capacity posterior state
                st["nsel"] = jnp.zeros((), jnp.int32)
        return st
    if cfg.kind in ("none", "globaltopk"):
        return {"step": jnp.zeros((), jnp.int32)}
    if cfg.kind in ("topk", "randk", "thresholdk", "sketchtopk"):
        return {"err": z, "step": jnp.zeros((), jnp.int32)}
    if cfg.kind == "dgc":
        return {"err": z, "mom": z, "step": jnp.zeros((), jnp.int32)}
    if cfg.kind == "regtopk":
        if cfg.state_format == "sparse":
            k = resolve_k(cfg, j)
            return {
                "err": z,                                  # eps^t
                "idx_prev": jnp.zeros((k,), jnp.uint32),   # support of s^{t-1}
                "a_prev_sel": jnp.zeros((k,), dt),         # a^{t-1}[idx]
                "g_prev_sel": jnp.zeros((k,), dt),         # g^{t-1}[idx]
                "step": jnp.zeros((), jnp.int32),
            }
        return {
            "err": z,                  # eps^t
            "a_prev": z,               # a^{t-1}
            "s_prev": jnp.zeros((j,), dt),   # s^{t-1}
            "g_agg_prev": z,           # g^{t-1} (aggregated, observed)
            "step": jnp.zeros((), jnp.int32),
        }
    raise ValueError(f"unknown sparsifier {cfg.kind!r}")


# ---------------------------------------------------------------------------
# Compressors
# ---------------------------------------------------------------------------

def _pack(a: jnp.ndarray, score: jnp.ndarray, k: int):
    from repro.core import bigvec
    idx = select.topk_indices(score, k)       # uint32 (J may exceed int32)
    vals = bigvec.gather(a, idx)
    return vals, idx


def _mask_from(score: jnp.ndarray, k: int, method: str) -> jnp.ndarray:
    return select.topk_mask(score, k, method)


def _reference_select(cfg: SparsifierConfig, a: jnp.ndarray,
                      score: jnp.ndarray, k: int, seg_bounds=None):
    """(mask, vals, idx) for the reference pipeline's score-based kinds.

    allocation="global": cfg.selector selection over the whole vector
    (vals/idx packed for selector="exact" only). Other allocation modes
    (DESIGN.md §2.6) select per segment via the shared allocated
    selector — exact-count by construction, so packed pairs always
    exist. ``a`` is the error-compensated accumulator the packed values
    are read from; ``score`` the (possibly REGTOP-k-corrected) selection
    score."""
    if cfg.allocation != "global":
        from repro.core import allocate
        return allocate.reference_allocated_select(cfg, a, score, k,
                                                   seg_bounds=seg_bounds)
    mask = _mask_from(score, k, cfg.selector)
    vals = idx = None
    if cfg.selector == "exact":
        vals, idx = _pack(a, score, k)
    return mask, vals, idx


def compress(cfg: SparsifierConfig, state: dict, g: jnp.ndarray,
             key: Optional[jax.Array] = None, omega: float = 1.0,
             seg_bounds=None, participate=None,
             g_segments=None) -> CompressOut:
    """Sparsify one worker's flat gradient. omega = this worker's weight w_n.

    Inputs: ``g`` (J,) fp gradient (cast to cfg.ef_dtype); ``state`` the
    pytree from :func:`init_state`. Returns a :class:`CompressOut`; cost
    is O(J) sweeps + O(k) packing on both pipelines (2 O(J) traversals
    fused sparse-comm, ~8 reference — DESIGN.md §2.2/§2.3, pinned by
    tests/test_state_traffic.py and tests/test_bucketed.py).

    cfg.pipeline selects the execution path: "reference" (dense math,
    cfg.selector) or "fused" (two-sweep kernels/compress pipeline). The
    dispatch decision is the explicit capability table in
    repro.kernels.compress.dispatch (DESIGN.md §2.5); configs outside it
    use the reference path, with the reason queryable via dispatch(cfg).

    cfg.allocation != "global" (DESIGN.md §2.6) splits the budget
    sum(k_l) == k across contiguous segments before selection on BOTH
    pipelines — ``seg_bounds`` optionally pins the segmentation (static
    [(offset, size), ...], e.g. layer-aligned bounds from
    TreeFlattener.layer_bounds); by default segments are the near-equal
    allocate.resolve_num_segments cut. Unsupported allocation combos
    raise ValueError here (allocate.check_allocation), never degrade
    silently.

    ``participate`` (DESIGN.md §2.7): optional traced () bool — this
    worker's elastic participation bit for the step. None (default) is
    literally the pre-elastic code path. With a bit, a sitting-out
    worker returns an inert payload (zero values/mask/ghat, count 0),
    its error feedback decays in place (err' = cfg.err_decay * err, DGC
    mom' = cfg.momentum * mom), and REGTOP-k's posterior freezes;
    ``participate=True`` is a bitwise pass-through. Both pipelines share
    the masked-input helper (kernels.compress.ops.masked_inputs), so
    their post-step states stay bit-comparable under any mask.

    cfg.overlap="backward" (DESIGN.md §2.8): the fused sweeps partition
    by the stream segments so compression can run behind the backward
    pass. ``g_segments`` feeds the gradient as per-segment arrays (the
    train step's streaming form; ``g`` must then be None); with a flat
    ``g`` the vector is sliced into the resolved stream partition
    internally, so benches and audits see the streaming program without
    a train loop. Output is BIT-identical to overlap="none" either way
    (selection is partition-invariant); unsupported configs raise via
    kernels.compress.dispatch.check_overlap, never degrade silently.
    """
    if g_segments is not None:
        if g is not None:
            raise ValueError("pass g or g_segments, not both")
        if cfg.overlap != "backward":
            raise ValueError("g_segments requires overlap='backward'")
        j = int(sum(gs.shape[0] for gs in g_segments))
    else:
        j = g.shape[0]
    k = resolve_k(cfg, j)
    dt = jnp.dtype(cfg.ef_dtype)
    if g is not None:
        g = g.astype(dt)
    pf = None
    if participate is not None:
        pf = jnp.asarray(participate, jnp.bool_)
    if cfg.num_buckets == 0:
        cfg = dataclasses.replace(cfg, num_buckets=resolve_num_buckets(
            cfg, j, _workers_from_omega(omega)))
    if cfg.allocation != "global":
        # AFTER bucket auto-resolution: num_segments=0 follows the
        # RESOLVED bucket count (segments and buckets coincide)
        from repro.core import allocate
        allocate.check_allocation(cfg)
        if seg_bounds is None and g_segments is None:
            seg_bounds = allocate.segment_bounds(
                j, allocate.resolve_num_segments(cfg, j))

    stream_bounds = None
    if cfg.overlap != "none":
        from repro.kernels.compress.dispatch import check_overlap
        check_overlap(cfg)           # fused-dispatch configs only
        if g_segments is not None:
            g_segments = [gs.astype(dt) for gs in g_segments]
            off = 0
            stream_bounds = []
            for gs in g_segments:
                stream_bounds.append((off, gs.shape[0]))
                off += gs.shape[0]
            if cfg.allocation != "global":
                # one partition drives both the stream and the
                # allocation (the train step builds them from the same
                # layer-aligned bounds)
                if seg_bounds is None:
                    seg_bounds = stream_bounds
                elif [tuple(b) for b in seg_bounds] != stream_bounds:
                    raise ValueError(
                        "streaming with allocation != 'global' needs "
                        "seg_bounds == the g_segments partition")
        else:
            # flat g + overlap="backward": slice into the stream
            # partition here so the streaming program structure is
            # exercised (and audited) without a segment-feeding caller
            if cfg.allocation != "global":
                stream_bounds = [tuple(b) for b in seg_bounds]
            else:
                from repro.core import allocate
                stream_bounds = allocate.segment_bounds(
                    j, allocate.resolve_num_segments(cfg, j))
            g_segments = [g[o:o + sz] for o, sz in stream_bounds]
            g = None

    from repro.kernels.compress.dispatch import dispatch
    if dispatch(cfg).path == "fused":
        return _compress_fused(cfg, state, g, k, omega, key, seg_bounds,
                               participate=pf, g_segments=g_segments,
                               stream_bounds=stream_bounds)

    if pf is not None and "err" in state:
        # reference oracle under elastic participation: the SAME masked
        # effective inputs as the fused pipeline (g_eff = where(p, g, 0),
        # err_eff = where(p, err, err_decay * err)), so both pipelines'
        # post-step states stay bit-comparable under any mask
        from repro.kernels.compress import ops as _cops
        g, err_eff, pf = _cops.masked_inputs(g, state["err"], pf,
                                             cfg.err_decay)
        state = dict(state, err=err_eff)

    if cfg.kind == "none":
        ones = jnp.ones((j,), dt)
        if pf is not None:
            g = jnp.where(pf, g, jnp.zeros_like(g))
            ones = jnp.where(pf, ones, jnp.zeros_like(ones))
        return CompressOut(g, ones, {"step": state["step"] + 1})

    if cfg.kind == "globaltopk":
        # Genie sparsifier: the mask is decoded from the AGGREGATED
        # accumulated gradient, so there is no per-worker compress step —
        # aggregate.GradientSync serves it (dispatch selection="global").
        raise RuntimeError("globaltopk is aggregate-level; run it through "
                           "aggregate.GradientSync (sync or round)")

    if cfg.kind == "sketchtopk":
        # Sketch-coordinated selection: the shared mask exists only after
        # the sketch all-reduce — aggregate.GradientSync runs the whole
        # step (dispatch selection="sketch"; the per-worker half is
        # kernels.compress.ops.fused_sketch_encode).
        raise RuntimeError("sketchtopk selection is aggregate-level; run "
                           "it through aggregate.GradientSync (sync or "
                           "round)")

    if cfg.kind == "topk":
        a = state["err"] + g
        mask, vals, idx = _reference_select(cfg, a, a, k, seg_bounds)
        mask, vals, idx, count = _mask_elastic(pf, mask, vals, idx, k)
        ghat = mask * a
        new = {"err": a - ghat, "step": state["step"] + 1}
        return CompressOut(ghat, mask, new, vals, idx, count)

    if cfg.kind == "randk":
        a = state["err"] + g
        assert key is not None, "randk needs a PRNG key"
        # uint32 indices + bigvec indexing end to end: select.randk_indices
        # samples the k-subset as top-k of random bits (J > 2^31 safe —
        # no int32-bound jax.random.choice permutation sort)
        from repro.core import bigvec
        if cfg.allocation != "global":
            # score-free selection: allocation draws a uniform k_l-subset
            # per segment with the PROPORTIONAL counts (same shared
            # sampler as the fused path -> identical index streams)
            from repro.core import allocate
            counts = allocate.proportional_counts(
                k, [sz for _, sz in seg_bounds])
            idx = allocate.randk_allocated_indices(key, seg_bounds, counts)
        else:
            idx = select.randk_indices(key, j, k)
        mask = bigvec.mask_from_indices(j, idx, dt)
        vals = bigvec.gather(a, idx)
        mask, vals, idx, count = _mask_elastic(pf, mask, vals, idx, k)
        ghat = mask * a
        return CompressOut(ghat, mask,
                           {"err": a - ghat, "step": state["step"] + 1},
                           vals, idx, count)

    if cfg.kind == "thresholdk":
        # Strom'15-style magnitude thresholding, ADAPTIVE per step: the
        # threshold is re-derived from the current accumulator every step
        # (the k-th magnitude for selector="exact", the histogram bin edge
        # for selector="histogram") — not Strom's original fixed
        # first-step threshold, which stalls under shifting gradient
        # scales. Selection therefore coincides with topk; the kind
        # exists as the threshold-family baseline.
        a = state["err"] + g
        mask, vals, idx = _reference_select(cfg, a, a, k, seg_bounds)
        mask, vals, idx, count = _mask_elastic(pf, mask, vals, idx, k)
        ghat = mask * a
        new = {"err": a - ghat, "step": state["step"] + 1}
        return CompressOut(ghat, mask, new, vals, idx, count)

    if cfg.kind == "dgc":
        # Deep Gradient Compression [Lin et al. '18]: momentum correction.
        mom = cfg.momentum * state["mom"] + g
        # elastic gate, same select as the fused sweep: a sitting-out
        # worker's a excludes the momentum stream (so err decays in
        # place) while mom still advances to cfg.momentum * mom
        am = mom if pf is None else jnp.where(pf, mom, 0.0)
        a = state["err"] + am
        mask, vals, idx = _reference_select(cfg, a, a, k, seg_bounds)
        mask, vals, idx, count = _mask_elastic(pf, mask, vals, idx, k)
        ghat = mask * a
        new = {"err": a - ghat, "mom": mom * (1.0 - mask), "step": state["step"] + 1}
        return CompressOut(ghat, mask, new, vals, idx, count)

    if cfg.kind == "regtopk":
        if cfg.state_format == "sparse":
            return _compress_regtopk_sparse(cfg, state, g, k, omega, pf)
        a = state["err"] + g
        # posterior distortion (Algorithm 1, line 5); safe-divide where a ~ 0
        safe = safe_denom(omega * a)
        delta_sent = (state["g_agg_prev"] - omega * state["a_prev"]) / safe
        delta = state["s_prev"] * delta_sent + cfg.Q * (1.0 - state["s_prev"])
        reg = jnp.tanh(jnp.abs(1.0 + delta) / cfg.mu)
        score = a * reg
        is_first = state["step"] == 0
        score = jnp.where(is_first, a, score)   # t=0: plain TOP-k
        mask, vals, idx = _reference_select(cfg, a, score, k, seg_bounds)
        mask, vals, idx, count = _mask_elastic(pf, mask, vals, idx, k)
        ghat = mask * a
        new = {
            "err": a - ghat,
            "a_prev": a,
            "s_prev": mask,
            "g_agg_prev": state["g_agg_prev"],  # replaced by observe_aggregate
            "step": state["step"] + 1,
        }
        if pf is not None:
            # posterior freeze: a sitting-out worker neither sent nor
            # observed anything, so Algorithm 1's t-1 quantities stay
            # those of its LAST participating step
            new["a_prev"] = jnp.where(pf, a, state["a_prev"])
            new["s_prev"] = jnp.where(pf, mask, state["s_prev"])
        return CompressOut(ghat, mask, new, vals, idx, count)

    raise ValueError(f"unknown sparsifier {cfg.kind!r}")


def _mask_elastic(pf, mask, vals, idx, k: int):
    """Reference-path elastic payload masking (DESIGN.md §2.7): a
    sitting-out worker's dense mask and packed pairs come back inert
    (mask 0, values 0.0, indices 0, count 0). pf=None (or a True bit)
    passes everything through bitwise; count is None when all slots are
    unconditionally live (the pre-elastic contract)."""
    if pf is None:
        return mask, vals, idx, None
    mask = jnp.where(pf, mask, jnp.zeros_like(mask))
    count = jnp.where(pf, jnp.asarray(k, jnp.int32), 0)
    if vals is not None:
        vals = jnp.where(pf, vals, jnp.zeros_like(vals))
        idx = jnp.where(pf, idx, jnp.zeros_like(idx))
    return mask, vals, idx, count


def _compress_regtopk_sparse(cfg: SparsifierConfig, state: dict,
                             g: jnp.ndarray, k: int, omega: float,
                             pf=None) -> CompressOut:
    """REGTOP-k with O(k) posterior state (state_format="sparse").

    Algorithm 1 line 5 reads a^{t-1} and g^{t-1} ONLY at the support of
    s^{t-1}; everywhere else Delta = Q. So the dense (a_prev, s_prev,
    g_agg_prev) vectors reduce to three k-sized arrays — 4J fp32 of state
    becomes J (+O(k)), which is what lets the 32B-class configs fit HBM.
    Update math is identical to the dense path.
    """
    dt = jnp.dtype(cfg.ef_dtype)
    a = state["err"].astype(dt) + g.astype(dt)
    idx_p = state["idx_prev"]
    from repro.core import bigvec as _bv
    a_sel = _bv.gather(a, idx_p)
    safe = safe_denom(omega * a_sel)
    delta_sel = (state["g_prev_sel"] - omega * state["a_prev_sel"]) / safe
    reg_sel = jnp.tanh(jnp.abs(1.0 + delta_sel) / cfg.mu)
    reg_q = jnp.tanh(jnp.abs(1.0 + cfg.Q) / cfg.mu).astype(dt)
    from repro.core import bigvec
    reg = bigvec.scatter_set(jnp.full(a.shape, reg_q, dt), idx_p,
                             reg_sel.astype(dt))
    score = jnp.where(state["step"] == 0, a, a * reg)
    from repro.core import select as _select
    idx = _select.topk_indices(score, k)
    vals = bigvec.gather(a, idx)
    if pf is None:
        err_new = bigvec.scatter_set(a, idx, 0.0)
        mask = bigvec.mask_from_indices(a.shape[0], idx, a.dtype)
        count = None
        idx_prev_new, a_prev_new = idx.astype(jnp.uint32), vals
    else:
        # elastic sit-out: skip the scatter-zero (err keeps the decayed
        # a), freeze the O(k) posterior, ship an inert payload
        err_new = bigvec.scatter_set(
            a, bigvec.live_idx(idx, pf, a.shape[0]), 0.0, mode="drop")
        idx_prev_new = jnp.where(pf, idx.astype(jnp.uint32),
                                 state["idx_prev"])
        a_prev_new = jnp.where(pf, vals, state["a_prev_sel"])
        vals = jnp.where(pf, vals, jnp.zeros_like(vals))
        idx = jnp.where(pf, idx, jnp.zeros_like(idx))
        count = jnp.where(pf, jnp.asarray(k, jnp.int32), 0)
        mask = jnp.where(pf, bigvec.mask_from_indices(a.shape[0], idx, a.dtype),
                         jnp.zeros_like(a))
    ghat = bigvec.scatter_set(jnp.zeros_like(a), idx, vals)
    new = {
        "err": err_new,
        "idx_prev": idx_prev_new,
        "a_prev_sel": a_prev_new,
        "g_prev_sel": state["g_prev_sel"],   # filled by observe_aggregate
        "step": state["step"] + 1,
    }
    return CompressOut(ghat, mask, new, vals, idx, count)


def _compress_fused(cfg: SparsifierConfig, state: dict, g: jnp.ndarray,
                    k: int, omega: float, key=None,
                    seg_bounds=None, participate=None, g_segments=None,
                    stream_bounds=None) -> CompressOut:
    """Two-sweep fused pipeline (repro.kernels.compress, DESIGN.md §2.2).

    selector="exact": reference-parity top-k semantics;
    selector="histogram": threshold selection at the bit-pattern bin
    edge with fixed-capacity packed pairs (inert pads, DESIGN.md §2.5).
    ef_dtype="bfloat16" keeps the J-sized state in bf16 (sweep math is
    fp32 in-register). In comm_mode="sparse" no dense ghat is
    materialized — the packed (values, indices) drive the sparse
    all-gather and CompressOut.ghat is None. The state update is O(k):
    ops scatter-zeroes the selected slots of ``a`` into the next
    ``err_prev`` (and masks DGC's momentum the same way), so the step is
    two O(J) traversals end to end and no dense mask is written
    (CompressOut.mask is None — use dense_mask() on demand).
    cfg.num_buckets > 1 runs the sweeps per contiguous bucket with a
    histogram-merge global threshold (DESIGN.md §2.4); selection, packed
    order, and post-step state stay bit-identical to num_buckets=1.
    """
    from repro.kernels.compress import ops as cops
    hist = cfg.selector == "histogram" and cfg.kind != "randk"
    kwargs = {}
    if cfg.kind == "regtopk":
        kwargs = dict(idx_prev=state["idx_prev"],
                      a_prev_sel=state["a_prev_sel"].astype(jnp.float32),
                      g_prev_sel=state["g_prev_sel"].astype(jnp.float32))
        if hist:
            kwargs["nsel_prev"] = state["nsel"]
    if cfg.kind == "dgc":
        kwargs["mom"] = state["mom"]
    out = cops.fused_compress_arrays(
        cfg.kind, g, state["err_prev"], state["step"],
        k=k, omega=omega, mu=cfg.mu, Q=cfg.Q, momentum=cfg.momentum,
        want_ghat=cfg.comm_mode != "sparse", selector=cfg.selector,
        ef_dtype=cfg.ef_dtype, key=key, num_buckets=cfg.num_buckets,
        allocation=cfg.allocation, seg_bounds=seg_bounds,
        participate=participate, err_decay=cfg.err_decay,
        g_segments=g_segments, stream_bounds=stream_bounds,
        **kwargs)
    dt = jnp.dtype(cfg.ef_dtype)
    new = {"err_prev": out["err"], "step": state["step"] + 1}
    if cfg.kind == "dgc":
        new["mom"] = out["mom"]              # selection-masked, ef_dtype
    if cfg.kind == "regtopk":
        new["idx_prev"] = out["indices"]
        new["a_prev_sel"] = out["values"].astype(dt)
        new["g_prev_sel"] = jnp.zeros_like(state["g_prev_sel"])  # observe_aggregate
        if hist:
            new["nsel"] = out["count"]
        if participate is not None:
            # posterior freeze (O(k) selects): a sitting-out worker's
            # t-1 support/values stay those of its last participating
            # step — observe_aggregate applies the matching freeze to
            # g_prev_sel
            pf = jnp.asarray(participate, jnp.bool_)
            new["idx_prev"] = jnp.where(pf, out["indices"],
                                        state["idx_prev"])
            new["a_prev_sel"] = jnp.where(pf, out["values"].astype(dt),
                                          state["a_prev_sel"])
            new["g_prev_sel"] = jnp.where(pf, new["g_prev_sel"],
                                          state["g_prev_sel"])
            if hist:
                new["nsel"] = jnp.where(pf, out["count"], state["nsel"])
    trim = {name: out[name]
            for name in ("topk_fallback", "topk_saturated_rows")}
    return CompressOut(out["ghat"], None, new,
                       out["values"], out["indices"], out["count"], trim)


def observe_aggregate(cfg: SparsifierConfig, state: dict, g_agg: jnp.ndarray,
                      participate=None) -> dict:
    """Store the aggregated gradient g^t the server 'broadcasts'
    (footnote 1). No-op except for REGTOP-k, where it is O(k) on the
    fused/sparse layouts (one gather at the support) and one O(J) cast
    on the dense reference layout. g_agg: (J,) — must be rank-identical
    (the sparse combine guarantees it; DESIGN.md §2.1).

    ``participate`` (DESIGN.md §2.7): a sitting-out worker observed
    nothing, so its posterior keeps the g^{t-1} of its last
    participating step (matching the compress-side posterior freeze)."""
    if cfg.kind == "regtopk":
        state = dict(state)
        pf = None if participate is None else jnp.asarray(participate,
                                                          jnp.bool_)
        from repro.kernels.compress.dispatch import dispatch
        with stages.scope("posterior"):
            if dispatch(cfg).path == "fused" or cfg.state_format == "sparse":
                # O(k) posterior: g^{t-1} is read only at the support of
                # s^{t-1}
                from repro.core import bigvec
                gsel = bigvec.gather(g_agg, state["idx_prev"]).astype(
                    jnp.dtype(cfg.ef_dtype))
                state["g_prev_sel"] = gsel if pf is None else jnp.where(
                    pf, gsel, state["g_prev_sel"])
            else:
                gobs = g_agg.astype(jnp.dtype(cfg.ef_dtype))
                state["g_agg_prev"] = gobs if pf is None else jnp.where(
                    pf, gobs, state["g_agg_prev"])
    return state


def dense_mask(out: CompressOut, j: int, dtype=jnp.float32) -> jnp.ndarray:
    """Dense 0/1 selection mask for a CompressOut, in the requested dtype.

    The ONE shared reconstruction both pipelines funnel through: the
    reference path carries a dense mask (returned cast), the fused path
    carries none — its mask is derived from the packed indices by an
    O(k) scatter. Histogram-selector outputs pad their fixed-capacity
    tail with inert (index 0) slots; ``out.count`` marks the live
    prefix, and pads are routed to an out-of-range sentinel + dropped
    (a duplicate write at index 0 would corrupt the mask there).
    """
    if out.mask is not None:
        return out.mask.astype(dtype)
    from repro.core import bigvec
    idx = out.indices.astype(jnp.uint32)
    if out.count is not None:
        live = jnp.arange(idx.shape[0], dtype=jnp.int32) < out.count
        idx = bigvec.live_idx(idx, live, j)
    return bigvec.scatter_set(jnp.zeros((j,), dtype), idx,
                              jnp.ones(idx.shape, dtype), mode="drop")


def dense_ghat(out: CompressOut, j: int) -> jnp.ndarray:
    """Dense sparsified gradient from a CompressOut, reconstructing from the
    packed (values, indices) when the fused sparse-comm path skipped it.
    Scatter-ADD, not set: the histogram selector's fixed-capacity packing
    pads its tail with inert (index 0, value 0.0) pairs, and a duplicate
    scatter-set at index 0 would be order-undefined; live indices are
    unique, so add == set for them."""
    if out.ghat is not None:
        return out.ghat
    from repro.core import bigvec
    return bigvec.scatter_add(jnp.zeros((j,), out.values.dtype),
                              out.indices, out.values)


# ---------------------------------------------------------------------------
# Single-process multi-worker reference driver (tests / paper experiments)
# ---------------------------------------------------------------------------

def make_round_fn(cfg: SparsifierConfig, n_workers: int):
    """Jitted vmapped aggregation round over stacked worker states/grads.

    Thin delegate to :meth:`core.aggregate.GradientSync.make_round_fn`
    (the unified simulation surface — one code path for the train step,
    the round drivers, and the tests): states_stacked is a pytree with
    leading (N,) axis, grads (N, J); returns (g_agg (J,),
    new_states_stacked). Equal weights w_n = 1/N. The returned function
    takes an optional trailing PRNG ``key``; each worker i compresses
    with ``fold_in(key, i)`` (matching ``sparsified_round``) — required
    for kind="randk", ignored by the deterministic sparsifiers.
    """
    from repro.core import aggregate
    return aggregate.GradientSync(cfg, None).make_round_fn(n_workers)


def stack_states(states: list):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


def sparsified_round(cfg: SparsifierConfig, states: list, grads: list,
                     omegas: Optional[list] = None, key=None,
                     participate: Optional[list] = None):
    """One aggregation round over N in-process workers (validation path).

    Thin delegate to :meth:`core.aggregate.GradientSync.round` — the
    round logic lives on the same GradientSync object the production
    train step builds (axes=None runs the combine in-process), so tests,
    the paper-experiment benchmarks, and the train path exercise one
    code path. Returns (g_agg, new_states).

    ``participate`` (DESIGN.md §2.7): optional per-worker participation
    bits. Sitting-out workers contribute nothing; the combine divides by
    n_active (cfg.combine="mean") or per-coordinate selection counts
    (cfg.combine="support"), mirroring sync_gradient's elastic paths.
    """
    from repro.core import aggregate
    return aggregate.GradientSync(cfg, None).round(
        states, grads, omegas=omegas, key=key, participate=participate)
