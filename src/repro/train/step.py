"""Distributed training step: shard_map over (pod?, data, model).

Composition per step (DESIGN.md §2.1):

1. local microbatch loss + grad (TP collectives inside the model);
2. psum over model for gradients of REPLICATED leaves (Megatron-SP rule);
3. flatten to the per-rank J_local fp32 vector;
4. THE PAPER: sparsified gradient sync over the data axes via the
   per-run core.aggregate.GradientSync object (TOP-k / REGTOP-k /
   baselines); sparsifier.overlap="backward" feeds stage 4 per
   layer-aligned segment as stage 1's VJP emits it (DESIGN.md §2.8),
   leaving the global trim/pack + collective as the only tail barrier. With
   sparsifier.num_buckets > 1 this stage uses the bucketed schedule of
   DESIGN.md §2.4: the fused sweeps run per bucket (histogram-merge
   global threshold), and the sparse all-gather is issued in
   num_buckets chunks so each chunk's collective overlaps the previous
   chunk's local scatter-add combine;
5. ZeRO-1 optimizer: each data rank updates its 1/DP slice of the fp32
   master + moments, params all-gathered back over data.

State layout (global arrays over the mesh):
- params: pytree, model-sharded per models/specs.py, replicated over data;
- opt:   {master,m,v}: (DP, TP, shard) sharded (dpaxes, model, -);
- ef:    sparsifier vectors (DP, TP, J_local) sharded likewise.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import RunConfig
from repro.core import aggregate as agg
from repro.core import sparsify
from repro.core import stages
from repro.core.flatten import TreeFlattener
from repro.models import init_params, loss_fn
from repro.models.parallel import Parallel
from repro.models.specs import param_specs, replicated_mask
from repro.optim import apply_updates, init_opt_state, opt_shard_len


def resolve_model_cfg(run: RunConfig):
    cfg = run.model
    if run.attn_override == "sliding" and cfg.attn_kind == "full":
        cfg = dataclasses.replace(cfg, attn_kind="sliding")
    return cfg


def build_parallel(mesh, *, seq_parallel=True, cache_seq_axis=None,
                   attn_dist="sp") -> Parallel:
    axes = mesh.axis_names
    tp = mesh.shape["model"]
    dpaxes = tuple(a for a in axes if a != "model")
    return Parallel(model_axis="model" if tp > 1 else None,
                    data_axes=dpaxes, tp=tp,
                    seq_parallel=seq_parallel and tp > 1,
                    cache_seq_axis=cache_seq_axis, attn_dist=attn_dist)


def _dp_size(mesh) -> int:
    n = 1
    for a in mesh.axis_names:
        if a != "model":
            n *= mesh.shape[a]
    return n


def _dp_index(dpaxes):
    idx = jnp.zeros((), jnp.int32)
    for a in dpaxes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def _gather_dp(x, dpaxes):
    with stages.scope("master_gather"):
        for a in reversed(dpaxes):
            x = jax.lax.all_gather(x, a, axis=0, tiled=True)
        return x


# ---------------------------------------------------------------------------
# State construction
# ---------------------------------------------------------------------------

def abstract_params(run: RunConfig, pal: Parallel):
    cfg = resolve_model_cfg(run)
    return jax.eval_shape(partial(init_params, cfg, pal),
                          jax.random.PRNGKey(0))


def auto_num_buckets_for_run(run: RunConfig, mesh, pal: Parallel = None):
    """Trace-accurate mirror of GradientSync's ``num_buckets=0``
    resolution: the SAME flattened per-rank gradient length (TreeFlattener
    total over the abstract per-rank params — what step_fn's
    ``g.shape[0]`` is) and the same data-parallel extent. The single
    helper every out-of-band consumer (launch log line, dryrun record)
    must use, so logs and records can never disagree with the chunk
    count the compiled program executes. Returns (num_buckets, j_local,
    dp)."""
    from repro.core.flatten import tree_size
    from repro.core.sparsify import resolve_num_buckets
    pal = pal or build_parallel(mesh)
    dp = 1
    for a in pal.data_axes:
        dp *= int(mesh.shape[a])
    j_local = tree_size(abstract_params(run, pal))
    return resolve_num_buckets(run.sparsifier, j_local, dp), j_local, dp


def stream_bounds_for_run(run: RunConfig, mesh, pal: Parallel = None):
    """Trace-accurate mirror of build_train_step's streaming partition
    (DESIGN.md §2.8): the layer-aligned (offset, size) bounds the step
    feeds per segment under ``sparsifier.overlap="backward"``, or None
    when streaming is off. Out-of-band consumers (launch log line,
    dryrun record's ``num_stream_segments``) must use this helper so
    they can never disagree with the compiled program's cut."""
    sp = run.sparsifier
    if getattr(sp, "overlap", "none") != "backward":
        return None
    from repro.core import allocate
    pal = pal or build_parallel(mesh)
    flat = TreeFlattener(abstract_params(run, pal))
    return allocate.layer_segments(
        flat.layer_bounds(), allocate.resolve_num_segments(sp, flat.total))


def delta_publisher_for_run(run: RunConfig, params, delta_k: int = 0, *,
                            record_history: bool = False):
    """Trainer-side delta-broadcast publisher (DESIGN.md §2.10), budget
    resolved the same way the sparsifier resolves k: ``delta_k <= 0``
    falls back to ``resolve_k(run.sparsifier, J)`` over the whole flat
    model, so by default the serving channel ships the same per-step
    volume the gradient sync does. The caller publishes AFTER each
    optimizer step (``publish(params)``) and ships the version-0 base
    via ``write_snapshot`` before any replica subscribes."""
    from repro.core.flatten import tree_size
    from repro.core.sparsify import resolve_k
    from repro.serve.delta import DeltaPublisher
    k = int(delta_k)
    if k <= 0:
        k = resolve_k(run.sparsifier, tree_size(params))
    return DeltaPublisher(params, k, record_history=record_history)


def train_state_specs(run: RunConfig, mesh, pal: Parallel):
    """(param_specs, opt_specs, ef_specs) PartitionSpec trees."""
    tmpl = abstract_params(run, pal)
    pspecs = param_specs(tmpl) if pal.tp_on else jax.tree_util.tree_map(
        lambda _: P(), tmpl)
    dpaxes = pal.data_axes
    vec = P(dpaxes, "model", None) if pal.tp_on else P(dpaxes, None, None)

    def st_spec(tree):
        return jax.tree_util.tree_map(
            lambda l: vec if getattr(l, "ndim", 0) >= 1 else P(), tree)

    flat = TreeFlattener(tmpl)
    dp = _dp_size(mesh)
    shard = opt_shard_len(flat.total, dp)
    opt_tmpl = init_opt_state(run.optimizer,
                              jax.ShapeDtypeStruct((shard,), jnp.float32))
    ef_tmpl = sparsify.init_state(run.sparsifier, flat.total)
    return tmpl, pspecs, st_spec(opt_tmpl), st_spec(ef_tmpl)


def init_train_state(run: RunConfig, mesh, pal: Parallel, key):
    """shard_map'd initializer: returns (params, opt_state, ef_state)."""
    cfg = resolve_model_cfg(run)
    tmpl, pspecs, ospecs, especs = train_state_specs(run, mesh, pal)
    flat = TreeFlattener(tmpl)
    dp = _dp_size(mesh)
    shard = opt_shard_len(flat.total, dp)
    dpaxes = pal.data_axes

    def init_fn(k):
        params = init_params(cfg, pal, k)
        if pal.tp_on:
            # sharded leaves draw per-rank streams; REPLICATED leaves must be
            # bit-identical across model ranks -> init twice and select.
            kf = jax.random.fold_in(k, jax.lax.axis_index("model"))
            params_f = init_params(cfg, pal, kf)
            repl = replicated_mask(params)
            params = jax.tree_util.tree_map(
                lambda u, f, r: u if r else f, params, params_f, repl)
        vec = flat.flatten(params)
        r = _dp_index(dpaxes)
        vpad = jnp.pad(vec, (0, dp * shard - flat.total))
        mslice = jax.lax.dynamic_slice_in_dim(vpad, r * shard, shard)
        opt = init_opt_state(run.optimizer, mslice)
        ef = sparsify.init_state(run.sparsifier, flat.total)
        exp = lambda t: jax.tree_util.tree_map(
            lambda l: l.reshape((1, 1) + l.shape) if l.ndim >= 1 else l, t)
        return params, exp(opt), exp(ef)

    fn = jax.jit(jax.shard_map(
        init_fn, mesh=mesh, in_specs=(P(),),
        out_specs=(pspecs, ospecs, especs), check_vma=False))
    return fn(key)


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------

def build_train_step(run: RunConfig, mesh, pal: Parallel):
    """Returns (step_fn, in_specs, out_specs) — step_fn is the UNJITTED
    shard_map'd function; caller jits (and .lower()s for the dry-run)."""
    cfg = resolve_model_cfg(run)
    sp = run.sparsifier
    opt = run.optimizer
    # fault injection (DESIGN.md §2.7): parsed ONCE at build time — the
    # schedule is static config; only the per-(step, worker) liveness
    # bit is traced. None (no/empty spec) keeps the sync call and the
    # metrics tree byte-identical to the fault-free build.
    from repro.core import faults
    sched = faults.parse_schedule(run.fault_schedule)
    tmpl, pspecs, ospecs, especs = train_state_specs(run, mesh, pal)
    repl = replicated_mask(tmpl)
    flat = TreeFlattener(tmpl)
    dp = _dp_size(mesh)
    shard = opt_shard_len(flat.total, dp)
    dpaxes = pal.data_axes
    window = cfg.window if run.attn_override == "sliding" else 0

    # density allocation (DESIGN.md §2.6): the train step owns the leaf
    # layout, so it pins LAYER-ALIGNED segment bounds (grouped leaves,
    # never cutting inside a parameter) instead of the near-equal
    # default cut GradientSync would fall back to. Static python ints
    # — safe to close over under shard_map/jit.
    seg_bounds = None
    if sp.allocation != "global":
        from repro.core import allocate
        allocate.check_allocation(sp)      # fail at build, not at trace
        seg_bounds = allocate.layer_segments(
            flat.layer_bounds(), allocate.resolve_num_segments(sp, flat.total))

    # streaming compression (DESIGN.md §2.8): with overlap="backward" the
    # gradient is fed into the fused pipeline per layer-aligned segment
    # as the VJP emits it, instead of as one flat concatenate. The
    # partition is pinned at build time (static ints); when allocation
    # also segments, the SAME bounds drive both, so the per-segment
    # sweeps and the density budget share one cut.
    stream_bounds = None
    if sp.overlap == "backward":
        from repro.core import allocate
        stream_bounds = seg_bounds if seg_bounds is not None else \
            allocate.layer_segments(
                flat.layer_bounds(),
                allocate.resolve_num_segments(sp, flat.total))

    # per-run sync object (static fields bound once; validates the
    # allocation/overlap combos and resolves num_buckets=0 at build time
    # — same resolution auto_num_buckets_for_run mirrors for logs)
    gsync = agg.GradientSync(sp, dpaxes, j=flat.total, n_workers=dp,
                             seg_bounds=seg_bounds)

    # duplicate-weights: replicated leaves appear in every model-rank's flat
    # vector; weight 1/tp in global-norm computations.
    dup = jnp.concatenate([
        jnp.full((s,), (1.0 / max(pal.tp, 1)) if r else 1.0, jnp.float32)
        for s, r in zip(flat.sizes, jax.tree_util.tree_leaves(repl))]) \
        if pal.tp_on else None

    def sq(t):
        return jax.tree_util.tree_map(
            lambda l: l.reshape(l.shape[2:]) if getattr(l, "ndim", 0) >= 3 else l, t)

    def exp(t):
        return jax.tree_util.tree_map(
            lambda l: (l.reshape((1, 1) + l.shape)
                       if getattr(l, "ndim", 0) >= 1 else l), t)

    def step_fn(params, opt_state, ef_state, batch, key):
        opt_state = sq(opt_state)
        ef_state = sq(ef_state)

        def loss_f(p):
            with stages.scope("fwd"):
                return loss_fn(p, batch, cfg, pal, window=window)

        (loss, aux), grads = jax.value_and_grad(loss_f, has_aux=True)(params)
        if pal.tp_on:
            grads = jax.tree_util.tree_map(
                lambda g, r: jax.lax.psum(g, "model") if r else g, grads, repl)
        if stream_bounds is not None:
            # streaming: one flat per segment, each depending only on its
            # own leaves' gradients — compression runs behind the
            # remaining backward work (DESIGN.md §2.8)
            g_segments = flat.flatten_segments(grads, stream_bounds)
            with stages.scope("step_metrics"):
                gnorm_local = jnp.sqrt(sum(
                    jnp.sum(jnp.square(s.astype(jnp.float32)))
                    for s in g_segments))
        else:
            g_segments = None
            g = flat.flatten(grads)
            with stages.scope("step_metrics"):
                gnorm_local = jnp.linalg.norm(g)

        key = jax.random.fold_in(key, _dp_index(dpaxes))
        part = None
        if sched is not None:
            part = faults.participates(sched, ef_state["step"],
                                       _dp_index(dpaxes))
        if g_segments is not None:
            stream = gsync.begin(ef_state, key=key, participate=part)
            for gseg in g_segments:
                stream.feed_segment(gseg)
            g_agg, ef_new, fstats = stream.finish(with_stats=True)
        else:
            g_agg, ef_new, fstats = gsync(ef_state, g, key=key,
                                          participate=part, with_stats=True)

        # ZeRO-1 slice update
        with stages.scope("adam"):
            r = _dp_index(dpaxes)
            gpad = jnp.pad(g_agg.astype(jnp.float32),
                           (0, dp * shard - flat.total))
            gs = jax.lax.dynamic_slice_in_dim(gpad, r * shard, shard)
            if opt.grad_clip:
                w = dup if dup is not None else 1.0
                gn2 = jnp.sum(g_agg.astype(jnp.float32) ** 2 * w)
                gn2 = jax.lax.psum(gn2, "model") if pal.tp_on else gn2
                opt_state = dict(opt_state, gnorm=jnp.sqrt(gn2))
            master, opt_new = apply_updates(opt, opt_state, gs)
        mall = _gather_dp(master, dpaxes)[:flat.total]
        params_new = flat.unflatten(mall)

        from repro.models.transformer import global_loss
        with stages.scope("step_metrics"):
            # topk_fallback / topk_saturated_rows: the trim counters,
            # already averaged over the data ranks by GradientSync
            metrics = {
                "loss": global_loss(loss, pal),      # psum over model first
                "gnorm_local": gnorm_local,
                "agg_nonzero": jnp.mean((g_agg != 0).astype(jnp.float32)),
                "topk_fallback": fstats["topk_fallback"],
                "topk_saturated_rows": fstats["topk_saturated_rows"],
            }
            metrics.update(aux)
            all_axes = dpaxes + (("model",) if pal.tp_on else ())
            metrics = {k_: jax.lax.pmean(
                v, dpaxes if k_ == "loss" else all_axes)
                for k_, v in metrics.items()}
        if sched is not None:
            # already rank-identical psums from GradientSync — no pmean
            metrics["n_active"] = fstats["n_active"]
            metrics["dropped_nonfinite"] = fstats["dropped_nonfinite"]
        return params_new, exp(opt_new), exp(ef_new), metrics

    batch_specs = {k: P(dpaxes, None) for k in ("tokens", "targets")}
    if cfg.frontend == "vision_stub":
        batch_specs["patches"] = P(dpaxes, None, None)
    elif cfg.frontend == "audio_stub":
        batch_specs["frames"] = P(dpaxes, None, None)
    mkeys = ["loss", "gnorm_local", "agg_nonzero", "topk_fallback",
             "topk_saturated_rows", "lb_loss", "z_loss", "drop_frac"]
    if sched is not None:
        mkeys += ["n_active", "dropped_nonfinite"]
    mspecs = {k: P() for k in mkeys}
    in_specs = (pspecs, ospecs, especs, batch_specs, P())
    out_specs = (pspecs, ospecs, especs, mspecs)
    wrapped = jax.shard_map(step_fn, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)
    return wrapped, in_specs, out_specs
