"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch stablelm-3b \
      --smoke --steps 50 --sparsifier regtopk --sparsity 0.01 \
      --data 4 --model 2 --devices 8

--devices N forces N host devices (set BEFORE jax import); --smoke uses the
reduced config of the arch family so the run fits on CPU.
"""
import argparse
import os
import sys


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--sparsifier", default="regtopk")
    ap.add_argument("--sparsity", type=float, default=0.01)
    ap.add_argument("--mu", type=float, default=0.5)
    ap.add_argument("--comm", default="simulate",
                    choices=["simulate", "sparse", "dense"])
    ap.add_argument("--pipeline", default="reference",
                    choices=["reference", "fused"],
                    help="compression execution pipeline (DESIGN.md §2.2): "
                         "dense reference math, or the two-sweep fused "
                         "kernels/compress path")
    ap.add_argument("--num-buckets", type=int, default=1,
                    help="bucketed compression (DESIGN.md §2.4): partition "
                         "the flat gradient into this many contiguous "
                         "buckets; the fused sweeps and the sparse "
                         "all-gather run per bucket so collectives overlap "
                         "compaction. Selection is bucketing-invariant; "
                         "1 disables bucketing; 0 auto-tunes the count from "
                         "the sparse-collective payload vs the interconnect "
                         "latency floor (roofline.analysis.auto_num_buckets)")
    ap.add_argument("--allocation", default="global",
                    choices=["global", "proportional", "adaptive"],
                    help="density allocation (DESIGN.md §2.6): how the "
                         "global budget k splits across layer-aligned "
                         "segments of the flat gradient before selection. "
                         "global = one flat top-k (the paper, default); "
                         "proportional = k_l ~ segment size; adaptive = "
                         "k_l from per-segment second-moment statistics "
                         "(Adaptive Top-K style). Every mode conserves "
                         "sum(k_l) == k, so sparse-comm bytes are "
                         "unchanged. Requires --selector exact")
    ap.add_argument("--num-segments", type=int, default=0,
                    help="segment count for --allocation != global: 0 "
                         "follows --num-buckets (or 8 for the flat "
                         "schedule); the train step aligns the cut to "
                         "parameter-leaf boundaries")
    ap.add_argument("--overlap", default="none",
                    choices=["none", "backward"],
                    help="streaming compression (DESIGN.md §2.8): "
                         "backward feeds the gradient into the fused "
                         "pipeline per layer-aligned segment as the "
                         "backward pass emits it, so sweep-1 + EF fold "
                         "run behind the remaining backward work; the "
                         "global trim/pack + sparse collective are the "
                         "only tail barrier. Bit-identical selection/EF "
                         "state to none; requires --pipeline fused")
    ap.add_argument("--selector", default="exact",
                    choices=["exact", "histogram"],
                    help="top-k selection rule: exact lax.top_k semantics, "
                         "or histogram threshold selection (over-selects "
                         "within [k, k*(1+slack)]; served by the fused "
                         "pipeline's sweep-1 bit-pattern histogram, "
                         "DESIGN.md §2.5)")
    ap.add_argument("--wire-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="wire dtype of the packed VALUES the sparse "
                         "all-gather moves (indices stay uint32): "
                         "bfloat16 cuts sparse comm bytes by 25%% with "
                         "bf16 rounding of the combined gradient "
                         "(upcast in the scatter-add combine)")
    ap.add_argument("--err-decay", type=float, default=1.0,
                    help="per-step decay of a sitting-out worker's "
                         "error-feedback memory (DESIGN.md §2.7): "
                         "err' = err_decay * err on non-participating "
                         "steps; 1.0 holds the memory, <1 forgets stale "
                         "residuals a straggler accumulated while absent")
    ap.add_argument("--combine", default="mean",
                    choices=["mean", "support"],
                    help="elastic combine rule (DESIGN.md §2.7): mean = "
                         "sum over active workers / n_active; support = "
                         "each coordinate divided by the number of active "
                         "workers that SELECTED it")
    ap.add_argument("--fault-schedule", default="",
                    help="fault-injection spec (DESIGN.md §2.7): "
                         "'iid:P[,seed=S]' drops each worker each step "
                         "with prob P; 'bursty:period=P,outage=O"
                         "[,workers=i+j]' sits listed workers out for the "
                         "first O of every P steps; 'permanent:step=T"
                         "[,workers=i]' kills them from step T on. Empty "
                         "= full participation (byte-identical program "
                         "to the fault-free build)")
    ap.add_argument("--drop-prob", type=float, default=0.0,
                    help="shorthand for --fault-schedule iid:<p>")
    ap.add_argument("--sketch-rows", type=int, default=3,
                    help="CountSketch rows for kind='sketchtopk' "
                         "(DESIGN.md §2.9); the sketch all-reduce moves "
                         "rows*width floats per step")
    ap.add_argument("--sketch-width", type=int, default=0,
                    help="CountSketch width for kind='sketchtopk'; 0 "
                         "auto-sizes to min(max(4k, 256), 2^22) "
                         "(sketch.resolve_width — warns once when 4k "
                         "exceeds the cap)")
    ap.add_argument("--optimizer", default="adam")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--fixed-batch", action="store_true",
                    help="reuse step 0's batch every step (deterministic "
                         "overfit mode for convergence smoke tests; the "
                         "synthetic stream is uniform-random tokens, which "
                         "carry no learnable signal across fresh batches)")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--publish-deltas", default="",
                    help="spool directory for the learning-while-serving "
                         "delta broadcast (DESIGN.md §2.10): after each "
                         "optimizer step the trainer publishes a "
                         "version-stamped, checksummed top-k delta of its "
                         "params there (plus full resync snapshots under "
                         "<dir>/snapshots), which a replica started with "
                         "launch/serve.py --apply-deltas consumes")
    ap.add_argument("--delta-k", type=int, default=0,
                    help="entries per published delta; 0 resolves from "
                         "--sparsity over the whole flat model (the same "
                         "rule as the gradient sync's k)")
    ap.add_argument("--delta-every", type=int, default=1,
                    help="publish every N optimizer steps (>=1)")
    ap.add_argument("--delta-snapshot-every", type=int, default=0,
                    help="write a full resync snapshot every N published "
                         "versions (0 = only the version-0 base and the "
                         "final snapshot); replicas that hit a version gap "
                         "wait for the next snapshot, so lossy channels "
                         "want this small enough to bound the wait")
    ap.add_argument("--delta-fault-schedule", default="",
                    help="delta-channel fault spec (DESIGN.md §2.10): "
                         "'loss:P' drops each published version with prob "
                         "P; 'corrupt:P' bit-flips it in flight (the "
                         "replica's checksum guard detects it); "
                         "'reorder:W' delays each version by a seeded "
                         "amount <= W; 'stall:N[,at=V]' pauses the link "
                         "for N versions and flushes the backlog in order")
    return ap.parse_args(argv)


def resolve_fault_spec(args) -> str:
    """--drop-prob is sugar for --fault-schedule iid:<p>. Validates the
    spec at launch time (argparse surface) instead of deep in trace."""
    spec = args.fault_schedule.strip()
    drop = getattr(args, "drop_prob", 0.0)
    if drop:
        if spec:
            raise SystemExit("--drop-prob is shorthand for --fault-schedule "
                             f"iid:<p>; it conflicts with --fault-schedule "
                             f"{spec!r} — pass one of them")
        spec = f"iid:{drop}"
    if spec:
        from repro.core import faults
        faults.parse_schedule(spec)
    return spec


def resolve_delta_fault_spec(args) -> str:
    """Validate --delta-fault-schedule at the argparse surface."""
    spec = getattr(args, "delta_fault_schedule", "").strip()
    if spec:
        from repro.core import faults
        faults.parse_channel_schedule(spec)
    return spec


def build_run(args):
    """The RunConfig the parsed flags describe (chip_smoke.py builds its
    runs through this too, so both train the same configuration)."""
    from repro.configs.base import (OptimizerConfig, RunConfig, SHAPES,
                                    SparsifierConfig, get_config,
                                    reduced_config)
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced_config(cfg)
    fault_spec = resolve_fault_spec(args)
    return RunConfig(
        model=cfg, shape=SHAPES["train_4k"],
        sparsifier=SparsifierConfig(kind=args.sparsifier,
                                    sparsity=args.sparsity, mu=args.mu,
                                    comm_mode=args.comm,
                                    pipeline=args.pipeline,
                                    selector=args.selector,
                                    num_buckets=args.num_buckets,
                                    allocation=args.allocation,
                                    num_segments=args.num_segments,
                                    wire_dtype=args.wire_dtype,
                                    err_decay=args.err_decay,
                                    combine=args.combine,
                                    overlap=args.overlap,
                                    sketch_rows=args.sketch_rows,
                                    sketch_width=args.sketch_width),
        optimizer=OptimizerConfig(kind=args.optimizer, lr=args.lr),
        seed=args.seed, steps=args.steps,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        fault_schedule=fault_spec,
    )


def compress_strategy(sp) -> str:
    """The compress strategy a SparsifierConfig runs, for banners."""
    if sp.kind == "none":
        return "none (dense sync)"
    if sp.pipeline != "fused":
        return "reference (dense jnp oracle)"
    from repro.kernels.compress.ops import default_strategy
    return default_strategy()


def main(argv=None):
    args = parse_args(argv)
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.devices}")
    import jax
    from repro.data import lm_batch
    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.train.step import (build_parallel, build_train_step,
                                  init_train_state, resolve_model_cfg)

    enable_compile_cache()
    run = build_run(args)
    cfg = run.model
    mesh = make_mesh(args.data, args.model, args.pods)
    pal = build_parallel(mesh)
    mcfg = resolve_model_cfg(run)
    key = jax.random.PRNGKey(args.seed)
    with jax.set_mesh(mesh):
        params, opt_state, ef_state = init_train_state(run, mesh, pal, key)
        step, _, _ = build_train_step(run, mesh, pal)
        jstep = jax.jit(step, donate_argnums=(0, 1, 2))
        n = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
        print(f"[train] {cfg.name}: {n:,} params (global), mesh="
              f"{dict(zip(mesh.axis_names, mesh.devices.shape))}, "
              f"sparsifier={args.sparsifier}@{args.sparsity}")
        from repro.core.aggregate import effective_comm_mode
        sp = run.sparsifier
        if sp.num_buckets == 0:
            # the shared trace-accurate mirror of GradientSync's
            # resolution (train/step.auto_num_buckets_for_run)
            from repro.train.step import auto_num_buckets_for_run
            nb, j_local, dp = auto_num_buckets_for_run(run, mesh, pal)
            print(f"[train] num_buckets=0 -> auto-tuned {nb} "
                  f"(J_local={j_local:,}, dp={dp})")
        print(f"[train] effective comm mode: {effective_comm_mode(sp)}, "
              f"compress strategy: {compress_strategy(sp)}")
        if sp.overlap == "backward":
            from repro.train.step import stream_bounds_for_run
            sb = stream_bounds_for_run(run, mesh, pal)
            print(f"[train] overlap=backward: {len(sb)} stream segments "
                  f"(layer-aligned; DESIGN.md §2.8)")
        if run.fault_schedule:
            from repro.core import faults as _faults
            sched = _faults.parse_schedule(run.fault_schedule)
            ndp = args.data * args.pods
            print(f"[train] fault schedule: {_faults.format_schedule(sched)}"
                  f" (E[n_active]={_faults.expected_active(sched, ndp):.2f}"
                  f"/{ndp}, err_decay={sp.err_decay}, combine={sp.combine})")
        publisher = chan = snap_dir = None
        if args.publish_deltas:
            # learning-while-serving broadcast (DESIGN.md §2.10): the
            # trainer is the publisher; replicas subscribe to the spool
            from repro.core import faults as _faults
            from repro.serve.delta import (FaultyChannel, SpoolChannel,
                                           delta_wire_bytes)
            from repro.train.step import delta_publisher_for_run
            delta_fault = resolve_delta_fault_spec(args)
            publisher = delta_publisher_for_run(run, params, args.delta_k)
            chan = SpoolChannel(args.publish_deltas)
            if delta_fault:
                csched = _faults.parse_channel_schedule(delta_fault)
                chan = FaultyChannel(chan, csched)
                print(f"[train] delta channel faults: "
                      f"{_faults.format_channel_schedule(csched)}")
            snap_dir = os.path.join(args.publish_deltas, "snapshots")
            publisher.write_snapshot(snap_dir)       # version-0 base
            print(f"[train] publishing deltas: k={publisher.k} "
                  f"({delta_wire_bytes(publisher.k):,} wire bytes/delta, "
                  f"J={publisher.j:,}) every {max(1, args.delta_every)} "
                  f"steps -> {args.publish_deltas}")
        import time
        t0 = time.time()
        for t in range(args.steps):
            batch = lm_batch(mcfg, args.batch, args.seq, args.seed,
                             0 if args.fixed_batch else t)
            params, opt_state, ef_state, metrics = jstep(
                params, opt_state, ef_state, batch, key)
            if t % args.log_every == 0 or t == args.steps - 1:
                m = {k: float(v) for k, v in metrics.items()}
                health = (f"active {m['n_active']:.0f} "
                          if "n_active" in m else "")
                print(f"step {t:5d} loss {m['loss']:.4f} "
                      f"gnorm {m['gnorm_local']:.3f} "
                      f"nz {m['agg_nonzero']:.4f} "
                      f"fb {m['topk_fallback']:.2f} "
                      f"sat {m['topk_saturated_rows']:.0f} "
                      f"{health}({time.time()-t0:.1f}s)")
            if publisher is not None and (t + 1) % max(
                    1, args.delta_every) == 0:
                chan.send(publisher.publish(params))
                if (args.delta_snapshot_every and publisher.version
                        % args.delta_snapshot_every == 0):
                    publisher.write_snapshot(snap_dir)
            if (run.checkpoint_every and run.checkpoint_dir
                    and t and t % run.checkpoint_every == 0):
                from repro.checkpoint import save_checkpoint
                save_checkpoint(run.checkpoint_dir, t, params, opt_state,
                                ef_state, param_version=(
                                    publisher.version if publisher else None))
        if publisher is not None:
            if hasattr(chan, "flush"):
                chan.flush()
            publisher.write_snapshot(snap_dir)
            sent = getattr(chan, "counters", {}).get(
                "sent", publisher.version)
            print(f"[train] published {publisher.version} delta versions "
                  f"({sent} reached the spool); final snapshot at "
                  f"v{publisher.version}")
        if run.checkpoint_dir:
            from repro.checkpoint import save_checkpoint
            save_checkpoint(run.checkpoint_dir, args.steps, params,
                            opt_state, ef_state, param_version=(
                                publisher.version if publisher else None))
    return 0


if __name__ == "__main__":
    sys.exit(main())
