"""Gradient aggregation paths over the data-parallel mesh axes.

Three communication modes (DESIGN.md §2.1), all used inside ``shard_map``:

- ``dense``    : plain all-reduce (``psum``) of the raw gradient. Baseline.
- ``simulate`` : sparsify locally, all-reduce the (mostly-zero) dense vector.
                 Exact sparsified-training numerics; comm volume unchanged.
                 Used for CPU validation of the paper's claims.
- ``sparse``   : all-gather fixed-k (values, indices) pairs over the data axes
                 and scatter-add locally. Comm per step = N*k*8 bytes instead
                 of ~2*J*4 — the production path whose collective-term drop
                 the roofline quantifies.

Sketch-coordinated selection (dispatch ``selection="sketch"``, DESIGN.md
§2.9) adds a pre-selection collective — one all-reduce of per-worker
CountSketches — after which every rank decodes the SAME top-k mask, so
the sparse exchange ships VALUES ONLY (``shared_mask_allgather_combine``;
indices are implied by the coordinated mask): N*k*4 bytes, half the
packed-pair wire, compounding with ``wire_dtype="bfloat16"``.

Which path serves a config is entirely the dispatch decision
(``CompressDispatch.selection`` / ``.wire``); the sync code never
branches on ``cfg.kind``.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Sequence, Union

import jax
import jax.numpy as jnp

from repro.configs.base import SparsifierConfig
from repro.core import sketch, sparsify, stages
from repro.kernels.compress.dispatch import (  # noqa: F401  (re-export)
    dispatch as compress_dispatch,
    effective_comm_mode,
)

AxisNames = Union[str, Sequence[str]]

# (kind, selector, pipeline) combos already warned about — the sparse ->
# simulate degrade is surfaced once per config per process, at trace time
_DEGRADE_WARNED: set = set()


def _warn_sparse_degrade(cfg: SparsifierConfig) -> None:
    keyc = (cfg.kind, cfg.selector, cfg.pipeline)
    if keyc in _DEGRADE_WARNED:
        return
    _DEGRADE_WARNED.add(keyc)
    d = compress_dispatch(cfg)
    # only advise switching pipelines when that actually helps: the
    # fused-pipeline variant of this config must dispatch fused
    fused_var = dataclasses.replace(cfg, pipeline="fused")
    hint = (" pipeline='fused' serves this config sparsely."
            if compress_dispatch(fused_var).path == "fused" else "")
    warnings.warn(
        f"comm_mode='sparse' with kind={cfg.kind!r} selector={cfg.selector!r}"
        f" pipeline={cfg.pipeline!r} packs no fixed-size (values, indices)"
        f" pairs ({d.reason or 'no packed output'}); degrading to a dense"
        " simulate all-reduce (effective_comm_mode(cfg) == 'simulate')."
        + hint,
        RuntimeWarning, stacklevel=3)


def _axis_size(axes: AxisNames) -> jnp.ndarray:
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n = n * jax.lax.axis_size(a)
    return n


def dense_allreduce(g: jnp.ndarray, axes: AxisNames) -> jnp.ndarray:
    return jax.lax.pmean(g, axes)


def simulate_allreduce(ghat: jnp.ndarray, axes: AxisNames) -> jnp.ndarray:
    return jax.lax.pmean(ghat, axes)


def sparse_allgather_combine(values: jnp.ndarray, indices: jnp.ndarray,
                             j: int, axes: AxisNames,
                             num_buckets: int = 1,
                             wire_dtype: str = "float32",
                             participate=None, count=None,
                             combine: str = "mean") -> jnp.ndarray:
    """All-gather (k,) sparse contributions over `axes`; dense-combine locally.

    Every worker ends up with g_agg = (1/N) sum_n scatter(values_n, idx_n),
    identical on all data ranks (required: REGTOP-k's posterior distortion
    assumes the same g^t is observed everywhere).

    ``num_buckets > 1`` (DESIGN.md §2.4) splits the packed pairs into
    that many fixed-size chunks and issues ONE collective per chunk:
    chunk b's local scatter-add depends only on chunk b's gather, so
    XLA's latency-hiding scheduler overlaps chunk b+1's all-gather with
    chunk b's compaction instead of serializing one monolithic gather
    ahead of one monolithic scatter. The combined g_agg is the same sum
    (chunking only reorders additions at duplicate indices).

    ``wire_dtype="bfloat16"`` casts the packed VALUES (never the
    indices) right before each chunk's all-gather and upcasts in the
    scatter-add combine: 6 wire bytes per pair instead of 8. Every rank
    applies the same cast, so g_agg stays rank-identical.

    ``participate`` (DESIGN.md §2.7) is this rank's per-step liveness, a
    traced () bool. The collective stays fixed-shape — a sitting-out
    worker ships its (inert) payload like everyone else — but its slots
    are routed out of range and dropped in the combine, and the
    normalizer becomes the ACTIVE worker count. ``count`` marks the live
    packed prefix (None = all k slots); one position test
    ``p_w & (pos < count_w)`` handles histogram-capacity pads and
    chunk-tail pads uniformly. ``combine="support"`` divides each
    coordinate by the number of active workers that selected it instead
    of by n_active (coordinates nobody selected stay 0).
    """
    if isinstance(axes, str):
        axes = (axes,)
    n = _axis_size(axes)
    from repro.core import bigvec
    k = values.shape[0]
    num_buckets = max(1, int(num_buckets))   # 0 (auto) is resolved upstream
    if k <= num_buckets:
        num_buckets = 1          # degenerate: one pair per chunk gains nothing
    chunk = -(-k // num_buckets)
    pad = chunk * num_buckets - k
    if pad:
        # inert tail: scatter-add of 0.0 at index 0
        values = jnp.concatenate([values, jnp.zeros((pad,), values.dtype)])
        indices = jnp.concatenate([indices, jnp.zeros((pad,), indices.dtype)])
    acc_dtype = values.dtype
    wire_dt = jnp.dtype(wire_dtype)
    dense = jnp.zeros((j,), acc_dtype)
    if participate is None and combine == "mean":
        for b in range(num_buckets):
            vb = values[b * chunk:(b + 1) * chunk].astype(wire_dt)
            ib = indices[b * chunk:(b + 1) * chunk]
            for a in axes:
                vb = jax.lax.all_gather(vb, a)     # stacks leading axis
                ib = jax.lax.all_gather(ib, a)
            dense = bigvec.scatter_add(dense, ib.reshape(-1),
                                       vb.reshape(-1).astype(acc_dtype))
        return dense / n
    if combine not in ("mean", "support"):
        raise ValueError(f"unknown combine={combine!r} (mean | support)")
    # elastic path: two extra scalars per worker on the wire (liveness
    # bit + live count) — the payload collectives are unchanged
    p = (jnp.ones((), jnp.bool_) if participate is None
         else jnp.asarray(participate, jnp.bool_).reshape(()))
    cnt = (jnp.asarray(k if count is None else count, jnp.int32)
           .reshape(()))
    cnt = jnp.where(p, cnt, 0)
    pall = p.astype(jnp.float32)
    call = cnt
    for a in axes:
        pall = jax.lax.all_gather(pall, a)
        call = jax.lax.all_gather(call, a)
    pall = pall.reshape(-1) > 0.5                  # (n,) worker liveness
    call = call.reshape(-1)                        # (n,) live prefix length
    counts = jnp.zeros((j,), jnp.float32) if combine == "support" else None
    for b in range(num_buckets):
        vb = values[b * chunk:(b + 1) * chunk].astype(wire_dt)
        ib = indices[b * chunk:(b + 1) * chunk]
        for a in axes:
            vb = jax.lax.all_gather(vb, a)
            ib = jax.lax.all_gather(ib, a)
        pos = jnp.arange(b * chunk, (b + 1) * chunk, dtype=jnp.int32)
        live = pall[:, None] & (pos[None, :] < call[:, None])   # (n, chunk)
        il = bigvec.live_idx(ib.reshape(n, chunk), live, j).reshape(-1)
        dense = bigvec.scatter_add(dense, il,
                                   vb.reshape(-1).astype(acc_dtype),
                                   mode="drop")
        if counts is not None:
            counts = bigvec.scatter_add(counts, il,
                                        jnp.ones(il.shape, jnp.float32),
                                        mode="drop")
    if combine == "support":
        return jnp.where(counts > 0,
                         dense / jnp.maximum(counts, 1.0).astype(acc_dtype),
                         jnp.zeros((), acc_dtype))
    n_active = jnp.sum(pall.astype(jnp.float32))
    return dense / jnp.maximum(n_active, 1.0).astype(acc_dtype)


def shared_mask_allgather_combine(values: jnp.ndarray, indices: jnp.ndarray,
                                  j: int, axes: AxisNames,
                                  num_buckets: int = 1,
                                  wire_dtype: str = "float32",
                                  participate=None) -> jnp.ndarray:
    """All-gather (k,) VALUES under a COORDINATED shared mask; combine
    locally (DESIGN.md §2.9).

    Every rank holds the SAME index list — decoded from the all-reduced
    sketch — so the indices never travel: wire bytes are n * k *
    value_bytes, HALF the packed (values, indices) exchange at fp32,
    compounding with ``wire_dtype="bfloat16"`` (n * k * 2). ``indices``
    is that shared list; it only steers the local scatter.

    Because the support coincides on every rank, the per-coordinate
    support count equals the active worker count — ``combine="support"``
    and ``"mean"`` coincide, so there is exactly one combine:
    sum / n_active. ``num_buckets > 1`` chunks the gather like
    :func:`sparse_allgather_combine` (same latency-hiding rationale).

    ``participate``: this rank's liveness bit. A sitting-out worker's
    values arrive pre-zeroed by the caller (its slots are inert — the
    index list is shared, so no per-worker routing is needed), and the
    normalizer becomes the active count via one scalar psum. With
    ``participate=None`` the normalizer is the same float n, so an
    all-ones mask is bit-identical.
    """
    if isinstance(axes, str):
        axes = (axes,)
    n = _axis_size(axes)
    from repro.core import bigvec
    k = values.shape[0]
    num_buckets = max(1, int(num_buckets))
    if k <= num_buckets:
        num_buckets = 1
    chunk = -(-k // num_buckets)
    pad = chunk * num_buckets - k
    if pad:
        # inert tail: scatter-add of 0.0 at (shared) index 0
        values = jnp.concatenate([values, jnp.zeros((pad,), values.dtype)])
        indices = jnp.concatenate([indices, jnp.zeros((pad,), indices.dtype)])
    acc_dtype = values.dtype
    wire_dt = jnp.dtype(wire_dtype)
    dense = jnp.zeros((j,), acc_dtype)
    for b in range(num_buckets):
        vb = values[b * chunk:(b + 1) * chunk].astype(wire_dt)
        for a in axes:
            vb = jax.lax.all_gather(vb, a)     # stacks leading axis
        vsum = jnp.sum(vb.reshape(-1, chunk).astype(acc_dtype), axis=0)
        dense = bigvec.scatter_add(dense, indices[b * chunk:(b + 1) * chunk],
                                   vsum)
    if participate is None:
        return dense / jnp.float32(n).astype(acc_dtype)
    p = jnp.asarray(participate, jnp.bool_).reshape(())
    na = jax.lax.psum(p.astype(jnp.float32), axes)
    return dense / jnp.maximum(na, 1.0).astype(acc_dtype)


class GradientSync:
    """Per-run gradient-sync surface: static fields bound once, per-step
    work through ``__call__`` or the ``begin()/feed_segment()/finish()``
    streaming interface (DESIGN.md §2.8).

    ``sync_gradient`` had accreted eight positional/keyword parameters,
    most of them static per run (cfg, axes, seg_bounds) — and streaming
    adds more. GradientSync splits the two lifetimes: construction takes
    the static fields and validates them ONCE (allocation combos,
    ``cfg.overlap`` capability, optional bucket auto-resolution when the
    problem size + worker count are known), per-step calls take only the
    traced values.

    Per-step surfaces (inside ``shard_map``; ``axes`` required):

    - ``sync(state, g, key=..., participate=...)`` — flat-gradient step,
      the exact ``sync_gradient`` semantics (returns ``(g_agg,
      new_state)``, plus stats with ``with_stats=True``).
    - ``begin(state, ...)`` → stream; ``stream.feed_segment(g_seg)`` per
      layer-aligned segment as the backward pass emits it;
      ``stream.finish()`` runs the global trim/pack, the sparse
      collective, and ``observe_aggregate`` — the only tail barrier.
      Requires ``cfg.overlap == "backward"``; output is BIT-identical to
      the flat call (selection is partition-invariant, DESIGN.md §2.8).

    In-process simulation surfaces (``axes=None`` is fine — the combine
    runs locally): :meth:`round` over lists of per-worker states/grads
    and :meth:`make_round_fn` for the jitted vmapped variant. These
    absorb the former ``sparsify.sparsified_round`` / ``_elastic_round``
    / ``make_round_fn`` trio so the tests, the paper-experiment
    benchmarks, and the production train step exercise one code path.

    Semantics carried over verbatim from ``sync_gradient`` (that name
    remains as a deprecated shim):

    - pipeline/fused dispatch, chunked bucket collectives (§2.4), density
      allocation with layer-aligned ``seg_bounds`` (§2.6) — wire format
      allocation-invariant.
    - ``participate`` elastic liveness (§2.7): inert payloads, EF decay,
      active-set normalization, non-finite payload demotion,
      ``with_stats`` health counters as rank-identical psums, beside
      the trim counters ``topk_fallback`` / ``topk_saturated_rows``
      averaged over the ranks.
    """

    def __init__(self, cfg: SparsifierConfig, axes,
                 *, j: int = None, n_workers: int = None, seg_bounds=None):
        if cfg.allocation != "global":
            from repro.core import allocate
            allocate.check_allocation(cfg)     # explicit build-time error
        from repro.kernels.compress.dispatch import check_overlap
        check_overlap(cfg)                     # overlap="backward" capability
        if (cfg.num_buckets == 0 and j is not None and n_workers is not None
                and compress_dispatch(cfg).selection != "none"):
            # bucket auto-tune resolved at build time when the problem
            # size and fleet size are concrete; otherwise deferred to the
            # per-step call where the mesh axis size is known
            cfg = dataclasses.replace(
                cfg, num_buckets=sparsify.resolve_num_buckets(cfg, j,
                                                              n_workers))
        self.cfg = cfg
        self.axes = axes
        self.j = j
        self.n_workers = n_workers
        self.seg_bounds = seg_bounds

    def __call__(self, state: dict, g: jnp.ndarray, *, key=None,
                 participate=None, with_stats: bool = False):
        """One flat-gradient sync step: returns (g_agg, new_state[, stats])."""
        return self._sync(state, g=g, key=key, participate=participate,
                          with_stats=with_stats)

    def begin(self, state: dict, *, key=None, participate=None):
        """Open a streaming step (cfg.overlap='backward' only): feed
        gradient segments in layer order as the backward pass emits
        them, then ``finish()``."""
        if getattr(self.cfg, "overlap", "none") != "backward":
            raise ValueError(
                "begin()/feed_segment streaming needs overlap='backward' "
                f"(got overlap={getattr(self.cfg, 'overlap', 'none')!r})")
        return _GradientStream(self, state, key, participate)

    # -- per-step core (refactored sync_gradient body) ------------------

    def _sync(self, state: dict, g=None, g_segments=None, key=None,
              participate=None, with_stats: bool = False):
        cfg, axes = self.cfg, self.axes
        if axes is None:
            raise ValueError(
                "this GradientSync was built without mesh axes (in-process "
                "simulation only); per-step sync runs inside shard_map and "
                "needs the data-parallel axis name(s) — use round() / "
                "make_round_fn() for axis-free aggregation rounds")
        streaming = g_segments is not None
        j = (int(sum(gs.shape[0] for gs in g_segments)) if streaming
             else g.shape[0])
        p = None if participate is None else (
            jnp.asarray(participate, jnp.bool_).reshape(()))
        n = _axis_size(axes)
        zero = jnp.zeros((), jnp.float32)

        def _ret(g_agg, new_state, p_eff, dropped_local, trim=None):
            if not with_stats:
                return g_agg, new_state
            if p_eff is None:
                stats = {"n_active": jnp.float32(n),
                         "dropped_nonfinite": zero}
            else:
                stats = {"n_active": jax.lax.psum(p_eff.astype(jnp.float32),
                                                  axes),
                         "dropped_nonfinite": jax.lax.psum(dropped_local,
                                                           axes)}
            # the trim's counters (ops.trim_counters; zeros where no fused
            # trim ran), averaged over the ranks: the share whose trim fell
            # back and their mean count of saturated candidate rows
            trim = trim or {"topk_fallback": zero,
                            "topk_saturated_rows": zero}
            stats.update({k: jax.lax.pmean(v, axes) for k, v in trim.items()})
            return g_agg, new_state, stats

        d = compress_dispatch(cfg)
        if d.selection == "none":
            gd = g.astype(jnp.dtype(cfg.ef_dtype))
            if p is None:
                g_agg = dense_allreduce(gd, axes)
            else:
                dsum = jax.lax.psum(jnp.where(p, gd, jnp.zeros((), gd.dtype)),
                                    axes)
                na = jax.lax.psum(p.astype(jnp.float32), axes)
                g_agg = dsum / jnp.maximum(na, 1.0).astype(gd.dtype)
            return _ret(g_agg, {"step": state["step"] + 1}, p, zero)
        if cfg.num_buckets == 0:
            # auto-tune (DESIGN.md §2.4): resolved here, where the real
            # data-parallel axis size is known, so the compress sweeps and
            # the chunked collective share one concrete bucket count
            cfg = dataclasses.replace(
                cfg, num_buckets=sparsify.resolve_num_buckets(cfg, j, n))
        omega = 1.0 / n
        if d.selection == "global":
            # genie baseline: TOP-k on the true aggregated accumulated
            # gradient
            from repro.core import select as _select
            gf = g.astype(jnp.float32)
            if p is None:
                a_agg = dense_allreduce(gf, axes)
            else:
                a_agg = jax.lax.psum(jnp.where(p, gf, 0.0), axes)
                na = jax.lax.psum(p.astype(jnp.float32), axes)
                a_agg = a_agg / jnp.maximum(na, 1.0)
            k = sparsify.resolve_k(cfg, j)
            mask = _select.topk_mask(a_agg, k, cfg.selector)
            return _ret(mask * a_agg, {"step": state["step"] + 1}, p, zero)
        if d.selection == "sketch":
            return self._sync_sketch(cfg, d, state, g, p, n, _ret)

        out = sparsify.compress(cfg, state, g, key=key, omega=omega,
                                seg_bounds=self.seg_bounds, participate=p,
                                g_segments=g_segments)
        p_eff, dropped = p, zero
        if p is not None and out.values is not None:
            # non-finite payload guard: a worker whose packed values went
            # NaN/Inf is dropped for this step (its EF state already
            # updated under plain participation — one-step posterior
            # skew, §2.7)
            finite = jnp.all(jnp.isfinite(out.values.astype(jnp.float32)))
            p_eff = p & finite
            dropped = (p & ~finite).astype(jnp.float32)
        elastic = p is not None or cfg.combine != "mean"
        with stages.scope("exchange"):
            if cfg.comm_mode == "sparse" and out.values is not None:
                extra = (dict(participate=p_eff, count=out.count,
                              combine=cfg.combine) if elastic else {})
                g_agg = sparse_allgather_combine(
                    out.values, out.indices, j, axes,
                    num_buckets=cfg.num_buckets, wire_dtype=cfg.wire_dtype,
                    **extra)
            else:
                if cfg.comm_mode == "sparse":
                    # explicit, not silent: this config emits no packed
                    # pairs, so the sparse path cannot run — warn once
                    # (trace time) and surface the realized mode via
                    # effective_comm_mode
                    _warn_sparse_degrade(cfg)
                ghat = sparsify.dense_ghat(out, j)
                if p is not None and out.values is None:
                    finite = jnp.all(jnp.isfinite(
                        ghat.astype(jnp.float32)))
                    p_eff = p & finite
                    dropped = (p & ~finite).astype(jnp.float32)
                if not elastic:
                    g_agg = simulate_allreduce(ghat, axes)
                else:
                    pe = (jnp.ones((), jnp.bool_) if p_eff is None
                          else p_eff)
                    dsum = jax.lax.psum(
                        jnp.where(pe, ghat, jnp.zeros((), ghat.dtype)),
                        axes)
                    if cfg.combine == "support":
                        m = sparsify.dense_mask(out, j)
                        cnts = jax.lax.psum(
                            jnp.where(pe, m, jnp.zeros((), m.dtype)), axes)
                        g_agg = jnp.where(
                            cnts > 0,
                            dsum / jnp.maximum(cnts, 1.0).astype(ghat.dtype),
                            jnp.zeros((), ghat.dtype))
                    else:
                        na = jax.lax.psum(pe.astype(jnp.float32), axes)
                        g_agg = dsum / jnp.maximum(na, 1.0).astype(
                            ghat.dtype)
        new_state = sparsify.observe_aggregate(cfg, out.state, g_agg,
                                               participate=p_eff)
        return _ret(g_agg, new_state, p_eff, dropped, out.trim)

    def _sync_sketch(self, cfg, d, state, g, p, n, _ret):
        """Sketch-coordinated global top-k step (DESIGN.md §2.9).

        1. encode: a = err + g into a (rows, width) CountSketch — folded
           into sweep 1 on the fused path (ops.fused_sketch_encode, one
           traversal on Pallas, two under the XLA strategy), legacy
           two-pass encode on the reference path;
        2. pre-selection collective: ONE all-reduce of the linear
           sketches. Elastic: absent workers contribute ZERO sketches
           and the combine renormalizes by the active count (an
           all-ones mask is bit-identical to p=None — the psum operands
           pass through bitwise and the normalizer is the same float n);
        3. decode: identical magnitude estimates on every rank ->
           the SAME shared top-k mask everywhere;
        4. exchange: comm_mode="sparse" ships the k values only via
           shared_mask_allgather_combine (indices implied by the
           coordinated mask — half the packed-pair wire); otherwise the
           dense masked ghat is averaged (simulate semantics);
        5. EF closes O(k): the shared support of a is scatter-zeroed
           into the next err state (a sitting-out worker's scatter is
           sentinel-routed, so its decayed err survives verbatim).
        """
        axes = self.axes
        j = g.shape[0]
        k = sparsify.resolve_k(cfg, j)
        width = sketch.resolve_width(k, cfg.sketch_width)
        zero = jnp.zeros((), jnp.float32)
        ek = "err_prev" if d.path == "fused" else "err"
        if d.path == "fused":
            from repro.kernels.compress import ops as cops
            enc = cops.fused_sketch_encode(
                g, state[ek], rows=cfg.sketch_rows, width=width,
                participate=p, err_decay=cfg.err_decay)
            a, sk = enc["a"], enc["sketch"]
        else:
            err = state[ek]
            if p is not None:
                from repro.kernels.compress import ops as cops
                g, err, _ = cops.masked_inputs(g, err, p, cfg.err_decay)
            a = err + g.astype(jnp.dtype(cfg.ef_dtype))
            sk = sketch.encode(a, cfg.sketch_rows, width)
        if p is None:
            sk_agg = jax.lax.psum(sk, axes) / jnp.float32(n)
        else:
            sk_agg = jax.lax.psum(
                jnp.where(p, sk, jnp.zeros((), sk.dtype)), axes)
            na = jax.lax.psum(p.astype(jnp.float32), axes)
            sk_agg = sk_agg / jnp.maximum(na, 1.0)
        gmag = sketch.estimate(sk_agg, j)        # identical on all ranks
        from repro.core import select as _select
        if effective_comm_mode(cfg) == "sparse":
            from repro.core import bigvec
            idx = _select.topk_indices(gmag, k)  # the shared mask, as indices
            vals = bigvec.gather(a, idx)         # O(k)
            if p is not None:
                vals = jnp.where(p, vals, jnp.zeros((), vals.dtype))
            g_agg = shared_mask_allgather_combine(
                vals, idx, j, axes, num_buckets=cfg.num_buckets,
                wire_dtype=cfg.wire_dtype, participate=p)
            live = idx if p is None else bigvec.live_idx(idx, p, j)
            err_new = bigvec.scatter_set(a.astype(state[ek].dtype), live,
                                         0.0, mode="drop")
        else:
            mask = _select.topk_mask(gmag, k, cfg.selector)
            ghat = mask * a
            if p is None:
                g_agg = simulate_allreduce(ghat, axes)
            else:
                ghat = jnp.where(p, ghat, jnp.zeros((), ghat.dtype))
                dsum = jax.lax.psum(ghat, axes)
                na = jax.lax.psum(p.astype(jnp.float32), axes)
                g_agg = dsum / jnp.maximum(na, 1.0).astype(ghat.dtype)
            err_new = (a - ghat).astype(state[ek].dtype)
        new_state = {ek: err_new, "step": state["step"] + 1}
        return _ret(g_agg, new_state, p, zero)

    # -- in-process simulation surfaces ---------------------------------

    def round(self, states: list, grads: list, omegas=None, key=None,
              participate=None):
        """One aggregation round over N in-process workers.

        Returns (g_agg, new_states). The former sparsify.sparsified_round
        — the combine runs locally, so ``axes`` may be None.

        ``participate`` (DESIGN.md §2.7): optional per-worker
        participation bits; sitting-out workers contribute nothing and
        the combine divides by n_active (cfg.combine="mean") or
        per-coordinate selection counts ("support"), mirroring the
        per-step elastic paths.
        """
        cfg = self.cfg
        d = compress_dispatch(cfg)
        if d.selection == "sketch":
            return self._round_sketch(states, grads, omegas, key,
                                      participate)
        if d.selection == "global":
            return self._round_global(states, grads, omegas, participate)
        n = len(grads)
        omegas = omegas or [1.0 / n] * n
        j = grads[0].shape[0]
        if participate is not None:
            return self._round_elastic(states, grads, participate, key)
        outs = []
        for i in range(n):
            ki = None if key is None else jax.random.fold_in(key, i)
            outs.append(sparsify.compress(cfg, states[i], grads[i], key=ki,
                                          omega=omegas[i]))
        g_agg = sum(w * sparsify.dense_ghat(o, j)
                    for w, o in zip(omegas, outs))
        new_states = [sparsify.observe_aggregate(cfg, o.state, g_agg)
                      for o in outs]
        return g_agg, new_states

    def _round_elastic(self, states: list, grads: list, participate: list,
                       key):
        """round() under a per-worker participation mask — the in-process
        mirror of the per-step elastic combine (DESIGN.md §2.7): inert
        payloads from sitting-out workers, equal weights over the ACTIVE
        set ("mean") or per-coordinate support counts ("support"). An
        all-absent round yields g_agg = 0 and every state decays."""
        cfg = self.cfg
        n = len(grads)
        j = grads[0].shape[0]
        pfs = [jnp.asarray(p, jnp.bool_) for p in participate]
        outs = []
        for i in range(n):
            ki = None if key is None else jax.random.fold_in(key, i)
            outs.append(sparsify.compress(cfg, states[i], grads[i], key=ki,
                                          omega=1.0 / n,
                                          participate=pfs[i]))
        ghats = [sparsify.dense_ghat(o, j) for o in outs]  # inert when absent
        dense = sum(ghats)
        if cfg.combine == "support":
            counts = sum(sparsify.dense_mask(o, j) for o in outs)
            g_agg = jnp.where(counts > 0,
                              dense / jnp.maximum(counts, 1.0), 0.0)
        else:
            n_active = sum(p.astype(jnp.float32) for p in pfs)
            g_agg = dense / jnp.maximum(n_active, 1.0)
        new_states = [sparsify.observe_aggregate(cfg, o.state, g_agg,
                                                 participate=p)
                      for o, p in zip(outs, pfs)]
        return g_agg, new_states

    def _round_sketch(self, states, grads, omegas, key, participate):
        """In-process sketch-coordinated round (DESIGN.md §2.9): encode
        per worker (folded into sweep 1 on the fused path), ONE sketch
        combine, one SHARED mask, per-worker EF closed at that mask.

        Elastic participation: absent workers contribute ZERO sketches
        and zero gradient payloads, and both combines renormalize over
        the active count; a sitting-out worker's error feedback decays
        in place (masked_inputs). An all-ones mask is bit-identical to
        ``participate=None`` — the masked operands pass through bitwise
        and the normalizer is the same float n. Explicit ``omegas``
        weight the non-elastic combines only (the elastic combine is
        equal-weight over the active set, like every other elastic
        path)."""
        cfg = self.cfg
        d = compress_dispatch(cfg)
        n = len(grads)
        j = grads[0].shape[0]
        k = sparsify.resolve_k(cfg, j)
        width = sketch.resolve_width(k, cfg.sketch_width)
        ek = "err_prev" if d.path == "fused" else "err"
        if participate is not None and omegas is not None:
            raise ValueError(
                "explicit omegas with a participation mask are not "
                "defined for sketch coordination — the elastic combine "
                "renormalizes equal weights over the active set")
        pfs = (None if participate is None
               else [jnp.asarray(pi, jnp.bool_) for pi in participate])
        a_list, sk_list = [], []
        for i in range(n):
            pi = None if pfs is None else pfs[i]
            if d.path == "fused":
                from repro.kernels.compress import ops as cops
                enc = cops.fused_sketch_encode(
                    grads[i], states[i][ek], rows=cfg.sketch_rows,
                    width=width, participate=pi, err_decay=cfg.err_decay)
                a, sk = enc["a"], enc["sketch"]
            else:
                g, err = grads[i], states[i][ek]
                if pi is not None:
                    from repro.kernels.compress import ops as cops
                    g, err, _ = cops.masked_inputs(g, err, pi,
                                                   cfg.err_decay)
                a = err + g.astype(jnp.float32)
                sk = sketch.encode(a, cfg.sketch_rows, width)
            a_list.append(a)
            sk_list.append(sk)
        if pfs is not None:
            na = sum(pi.astype(jnp.float32) for pi in pfs)
            norm = jnp.maximum(na, 1.0)
            sk_agg = sum(jnp.where(pi, sk, jnp.zeros((), sk.dtype))
                         for pi, sk in zip(pfs, sk_list)) / norm
        elif omegas is None:
            sk_agg = sum(sk_list) / jnp.float32(n)
        else:
            sk_agg = sum(w * sk for w, sk in zip(omegas, sk_list))
        gmag = sketch.estimate(sk_agg, j)
        from repro.core import select as _select
        mask = _select.topk_mask(gmag, k, cfg.selector)   # SHARED
        ghats = [mask * a for a in a_list]
        if pfs is not None:
            ghats = [jnp.where(pi, gh, jnp.zeros((), gh.dtype))
                     for pi, gh in zip(pfs, ghats)]
            g_agg = sum(ghats) / norm
        elif omegas is None:
            g_agg = sum(ghats) / jnp.float32(n)
        else:
            g_agg = sum(w * gh for w, gh in zip(omegas, ghats))
        # absent workers' ghat is zero, so a - ghat keeps their decayed
        # err verbatim — same EF semantics as the per-step path
        new_states = [{ek: (a - gh).astype(st[ek].dtype),
                       "step": st["step"] + 1}
                      for a, gh, st in zip(a_list, ghats, states)]
        return g_agg, new_states

    def _round_global(self, states, grads, omegas, participate):
        """Genie-baseline round: top-k mask decoded from the true
        aggregated accumulated gradient. Elastic semantics (DESIGN.md
        §2.7/§2.9): absent workers contribute nothing, the aggregate
        renormalizes over the active count, and the genie mask is
        decoded from that active-mean aggregate; an all-ones mask is
        bit-identical to ``participate=None``. States pass through
        unchanged (the genie keeps no error feedback)."""
        cfg = self.cfg
        n = len(grads)
        j = grads[0].shape[0]
        k = sparsify.resolve_k(cfg, j)
        from repro.core import select as _select
        gfs = [g.astype(jnp.float32) for g in grads]
        if participate is not None:
            if omegas is not None:
                raise ValueError(
                    "explicit omegas with a participation mask are not "
                    "defined for the genie baseline — the elastic "
                    "combine renormalizes equal weights over the active "
                    "set")
            pfs = [jnp.asarray(pi, jnp.bool_) for pi in participate]
            na = sum(pi.astype(jnp.float32) for pi in pfs)
            a_agg = sum(jnp.where(pi, gf, jnp.zeros((), gf.dtype))
                        for pi, gf in zip(pfs, gfs))
            a_agg = a_agg / jnp.maximum(na, 1.0)
        elif omegas is None:
            a_agg = sum(gfs) / jnp.float32(n)
        else:
            a_agg = sum(w * gf for w, gf in zip(omegas, gfs))
        mask = _select.topk_mask(a_agg, k, cfg.selector)
        return mask * a_agg, states

    def make_round_fn(self, n_workers: int = None):
        """Jitted vmapped aggregation round over stacked worker
        states/grads (the former sparsify.make_round_fn).

        states_stacked: pytree with leading (N,) axis; grads: (N, J).
        Returns (g_agg (J,), new_states_stacked). Equal weights
        w_n = 1/N. The returned function takes an optional trailing PRNG
        ``key``; each worker i compresses with ``fold_in(key, i)``
        (matching :meth:`round`) — required for kind="randk", ignored by
        the deterministic sparsifiers.
        """
        cfg = self.cfg
        if n_workers is None:
            n_workers = self.n_workers
        if n_workers is None:
            raise ValueError("make_round_fn needs n_workers (at "
                             "construction or per call)")
        omega = 1.0 / n_workers

        if compress_dispatch(cfg).selection in ("sketch", "global"):
            # coordinated selection: unstack and delegate to round() —
            # the fused sketch encode is a Pallas launch, which vmap
            # cannot batch; a python loop over the N in-process workers
            # jits into the same program
            def round_coord(states, grads, key=None):
                n = grads.shape[0]
                sts = [jax.tree_util.tree_map(lambda x, i=i: x[i], states)
                       for i in range(n)]
                g_agg, new_sts = self.round(
                    sts, [grads[i] for i in range(n)], key=key)
                stacked = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *new_sts)
                return g_agg, stacked

            return jax.jit(round_coord)

        def one(state, g, k_i):
            out = sparsify.compress(cfg, state, g, key=k_i, omega=omega)
            return sparsify.dense_ghat(out, g.shape[0]), out.state

        def round_fn(states, grads, key=None):
            if key is None:
                ghats, new_states = jax.vmap(
                    lambda s, g: one(s, g, None))(states, grads)
            else:
                # per-worker folded key, matching round()'s
                # fold_in(key, i) stream
                keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
                    jnp.arange(n_workers))
                ghats, new_states = jax.vmap(one)(states, grads, keys)
            g_agg = jnp.sum(ghats, 0) * omega
            new_states = jax.vmap(
                lambda s: sparsify.observe_aggregate(cfg, s,
                                                     g_agg))(new_states)
            return g_agg, new_states

        return jax.jit(round_fn)


class _GradientStream:
    """Streaming handle from :meth:`GradientSync.begin`: feed
    layer-aligned gradient segments in emission order as the backward
    pass produces them; ``finish()`` runs the tail barrier (global
    trim/pack + sparse collective + ``observe_aggregate``) and returns
    (g_agg, new_state[, stats]). Single-shot: segments cannot be fed
    after finish()."""

    def __init__(self, sync: "GradientSync", state: dict, key, participate):
        self._gs = sync
        self._state = state
        self._key = key
        self._participate = participate
        self._segments = []
        self._done = False

    def feed_segment(self, g_seg: jnp.ndarray):
        """Append one flat gradient segment (layer order, contiguous)."""
        if self._done:
            raise RuntimeError("feed_segment() after finish()")
        self._segments.append(g_seg)
        return self

    def finish(self, *, with_stats: bool = False):
        """Tail barrier: trim/pack globally, run the collective, observe."""
        if self._done:
            raise RuntimeError("finish() called twice on one stream")
        if not self._segments:
            raise ValueError("finish() with no fed segments")
        self._done = True
        return self._gs._sync(self._state, g_segments=list(self._segments),
                              key=self._key, participate=self._participate,
                              with_stats=with_stats)


# one-shot deprecation marker for the sync_gradient shim (tests reset it)
_shim_warned = False


def sync_gradient(cfg: SparsifierConfig, state: dict, g: jnp.ndarray,
                  axes: AxisNames, key=None, seg_bounds=None,
                  participate=None, with_stats: bool = False):
    """DEPRECATED thin shim over :class:`GradientSync`.

    Bit-identical to ``GradientSync(cfg, axes, seg_bounds=seg_bounds)(
    state, g, key=key, participate=participate, with_stats=with_stats)``
    — the per-run object is the supported surface (build it once from
    the static fields; call it per step). Warns ``DeprecationWarning``
    exactly once per process.
    """
    global _shim_warned
    if not _shim_warned:
        _shim_warned = True
        warnings.warn(
            "aggregate.sync_gradient is deprecated: build an "
            "aggregate.GradientSync(cfg, axes, ...) once per run and call "
            "it per step (DESIGN.md §2.8).",
            DeprecationWarning, stacklevel=2)
    return GradientSync(cfg, axes, seg_bounds=seg_bounds)(
        state, g, key=key, participate=participate, with_stats=with_stats)


def comm_bytes_per_step(cfg: SparsifierConfig, j: int, n_workers: int,
                        n_active=None) -> dict:
    """Analytic communication volume per worker per step (benchmarks).

    Uses the EFFECTIVE comm mode (DESIGN.md §2.5): configs whose
    compress step packs no pairs move dense bytes even when
    comm_mode="sparse" was requested, and the fused histogram selector
    moves its fixed hist_capacity packed length, not k. Density
    allocation (DESIGN.md §2.6) never changes the volume — every
    allocation mode conserves sum(k_l) == k and packs exactly
    packed_len pairs; the returned dict carries ``allocation`` so
    benchmark rows can still distinguish the modes.

    ``n_active`` (DESIGN.md §2.7): expected live worker count under a
    fault schedule (may be fractional). Models the idealized elastic
    wire — absent workers transmit nothing — which is what a
    participation-aware transport would realize; the in-simulation
    fixed-shape collectives ship inert payloads instead. The ratio
    denominator stays the FULL-fleet dense all-reduce so fault rows
    remain comparable to fault-free ones.
    """
    k = sparsify.resolve_k(cfg, j)
    dense_ar = 2 * j * 4 * (n_workers - 1) / n_workers     # ring all-reduce fp32
    na = n_workers if n_active is None else min(float(n_active),
                                                float(n_workers))
    extra = {} if n_active is None else {"n_active": na}
    d = compress_dispatch(cfg)
    eff = effective_comm_mode(cfg)
    if d.selection == "none" or eff in ("dense", "simulate"):
        b = dense_ar if na <= 1 else 2 * j * 4 * (na - 1) / na
        return {"bytes": b, "k": k, "ratio": b / dense_ar,
                "effective_comm_mode": eff, "allocation": cfg.allocation,
                **extra}
    if d.selection == "sketch":
        # pre-selection sketch all-reduce (participation-invariant: an
        # absent worker's ring slot still moves, carrying zeros) + the
        # shared-mask values-only exchange (indices implied; §2.9)
        sk = sketch_allreduce_bytes(cfg, j, n_workers)
        vb = _wire_value_bytes(cfg)
        vals = na * k * vb
        b = sk + vals
        return {"bytes": b, "k": k, "ratio": b / dense_ar,
                "sketch_bytes": sk, "wire_value_bytes": vb,
                "effective_comm_mode": eff, "allocation": cfg.allocation,
                **extra}
    from repro.kernels.compress.dispatch import packed_len
    kp = packed_len(cfg, j)                 # k, or hist_capacity (fused hist)
    vb = _wire_value_bytes(cfg)             # 4, or 2 for wire_dtype=bf16
    sparse = na * kp * (vb + 4)             # allgather vals+idx, live ranks
    return {"bytes": sparse, "k": k, "packed_len": kp,
            "wire_value_bytes": vb, "ratio": sparse / dense_ar,
            "effective_comm_mode": eff, "allocation": cfg.allocation,
            **extra}


def _wire_value_bytes(cfg: SparsifierConfig) -> int:
    """Wire bytes per packed VALUE (dtype-aware; indices stay uint32)."""
    import numpy as np
    return int(np.dtype(cfg.wire_dtype).itemsize)


def sparse_gather_wire_bytes(cfg: SparsifierConfig, j: int,
                             n_workers: int, n_active=None):
    """Per-device wire bytes of the sparse gradient all-gather, or None
    when the config's EFFECTIVE comm mode is not sparse. This is the
    chunked-collective share the roofline's ``collective_exposed_s``
    overlap model scopes to (roofline/analysis.py) — dtype-aware, so a
    ``wire_dtype="bfloat16"`` run is modeled at its real 6-bytes-per-pair
    payload. Shared-mask configs (dispatch ``wire="values"``) gather
    VALUES ONLY — the coordinated mask implies the indices (§2.9); their
    pre-selection sketch collective is modeled separately
    (:func:`sketch_allreduce_bytes`)."""
    if effective_comm_mode(cfg) != "sparse":
        return None
    from repro.kernels.compress.dispatch import packed_len
    na = n_workers if n_active is None else min(float(n_active),
                                                float(n_workers))
    pair_bytes = _wire_value_bytes(cfg)
    if compress_dispatch(cfg).wire != "values":
        pair_bytes += 4                     # uint32 index rides along
    return na * packed_len(cfg, j) * pair_bytes


def sketch_allreduce_bytes(cfg: SparsifierConfig, j: int, n_workers: int):
    """Per-device wire bytes of the sketch all-reduce pre-selection
    collective (DESIGN.md §2.9), or None for non-sketch selection.
    Ring all-reduce of the (rows, width) fp32 sketch: 2 * rows * width
    * 4 * (N-1)/N. Participation-invariant — absent workers' ring slots
    still move (carrying zero sketches), so no n_active discount
    applies, unlike the values exchange."""
    if compress_dispatch(cfg).selection != "sketch":
        return None
    k = sparsify.resolve_k(cfg, j)
    width = sketch.resolve_width(k, cfg.sketch_width)
    return 2 * cfg.sketch_rows * width * 4 * (n_workers - 1) / n_workers
