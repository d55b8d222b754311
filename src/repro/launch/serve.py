"""Serving launcher: prefill a batch of prompts, then stream decode steps.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --smoke \
      --devices 8 --data 4 --model 2 --prompt-len 48 --new-tokens 16
"""
import argparse
import os
import sys
import time


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--mla-absorb", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--apply-deltas", default="",
                    help="subscribe to a trainer's delta broadcast "
                         "(DESIGN.md §2.10): serving params start from the "
                         "latest full snapshot under <dir>/snapshots and "
                         "versioned sparse deltas from the spool apply "
                         "between decode steps; in-flight decode stays "
                         "pinned to the version it started on, version "
                         "gaps trigger a snapshot resync, and corrupt or "
                         "non-finite payloads are dropped on health "
                         "counters. Point it at the same directory as "
                         "launch/train.py --publish-deltas")
    ap.add_argument("--delta-fault-schedule", default="",
                    help="inject receive-side delta-channel faults "
                         "(loss:P | corrupt:P | reorder:W | stall:N; "
                         "DESIGN.md §2.10) — same seeded schedules the "
                         "trainer can inject on the send side")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.devices}")
    import dataclasses

    import jax
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    import jax.numpy as jnp
    from repro.configs.base import (RunConfig, SHAPES, SparsifierConfig,
                                    get_config, reduced_config)
    from repro.launch.mesh import make_mesh
    from repro.models.specs import param_specs, replicated_mask
    from repro.models import init_params
    from repro.serve.step import (build_decode_step, build_prefill,
                                  delta_applier_from_snapshot,
                                  serve_parallel)
    from jax.sharding import PartitionSpec as P

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced_config(cfg)
    if args.mla_absorb:
        cfg = dataclasses.replace(cfg, mla_absorb=True)
    max_seq = args.prompt_len + args.new_tokens
    run = RunConfig(
        model=cfg,
        shape=dataclasses.replace(SHAPES["decode_32k"], seq_len=max_seq,
                                  global_batch=args.batch),
        sparsifier=SparsifierConfig(kind="none"),
    )
    mesh = make_mesh(args.data, args.model)
    pal = serve_parallel(mesh, run, decode=True)
    key = jax.random.PRNGKey(args.seed)
    with jax.set_mesh(mesh):
        tmpl_pal = pal
        pspecs = param_specs(
            jax.eval_shape(lambda k: init_params(cfg, tmpl_pal, k), key)) \
            if pal.tp_on else None

        def init_fn(k):
            pu = init_params(cfg, pal, k)
            if pal.tp_on:
                kf = jax.random.fold_in(k, jax.lax.axis_index("model"))
                pf = init_params(cfg, pal, kf)
                pu = jax.tree_util.tree_map(
                    lambda u, f, r: u if r else f, pu, pf,
                    replicated_mask(pu))
            return pu

        applier = chan = snap_dir = None
        if args.apply_deltas:
            # learning-while-serving (DESIGN.md §2.10): params come from
            # the trainer's latest snapshot, not a fresh init, so the
            # held version means something
            from repro.core import faults as _faults
            from repro.serve.delta import FaultyChannel, SpoolChannel
            snap_dir = os.path.join(args.apply_deltas, "snapshots")
            applier, params = delta_applier_from_snapshot(
                run, mesh, pal, snap_dir)
            chan = SpoolChannel(args.apply_deltas)
            if args.delta_fault_schedule.strip():
                csched = _faults.parse_channel_schedule(
                    args.delta_fault_schedule)
                chan = FaultyChannel(chan, csched)
                print(f"[serve] delta channel faults (recv side): "
                      f"{_faults.format_channel_schedule(csched)}")
            print(f"[serve] applying deltas from {args.apply_deltas} "
                  f"(snapshot v{applier.version})")
        elif pal.tp_on:
            params = jax.jit(jax.shard_map(
                init_fn, mesh=mesh, in_specs=(P(),), out_specs=pspecs,
                check_vma=False))(key)
        else:
            params = init_fn(key)
        n = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
        print(f"[serve] {cfg.name}: {n/1e6:.1f}M params, batch {args.batch}, "
              f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}"
              f"{', absorbed MLA' if args.mla_absorb else ''}")

        pre, _ = build_prefill(run, mesh, pal)
        dec, _ = build_decode_step(run, mesh, pal)
        batch = {"tokens": jax.random.randint(
            key, (args.batch, args.prompt_len), 0, cfg.vocab_size)}
        if cfg.frontend == "vision_stub":
            batch["patches"] = jax.random.normal(
                key, (args.batch, cfg.n_frontend_tokens, cfg.d_model))
        elif cfg.frontend == "audio_stub":
            batch["frames"] = jax.random.normal(
                key, (args.batch, cfg.n_frontend_tokens, cfg.d_model))
        t0 = time.time()
        logits, cache = jax.jit(pre)(params, batch)
        jax.block_until_ready(logits)
        t_pre = time.time() - t0
        jdec = jax.jit(dec)
        toks = []
        # in-flight consistency contract (DESIGN.md §2.10): this decode
        # stream pins the (params, version) it started on; deltas
        # arriving between its steps advance the applier's LIVE tree
        # without touching the pinned buffers
        pinned, pinned_v = (applier.acquire() if applier is not None
                            else (params, None))
        t0 = time.time()
        for _ in range(args.new_tokens):
            nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            toks.append(nxt)
            logits, cache = jdec(pinned, cache, nxt)
            if applier is not None:
                for p in chan.recv():
                    applier.offer(p)
                if applier.needs_resync and applier.can_resync(snap_dir):
                    applier.resync_from(snap_dir)
        jax.block_until_ready(logits)
        t_dec = time.time() - t0
        out = jnp.concatenate(toks, 1)
        print(f"prefill {args.prompt_len} tokens x {args.batch}: {t_pre:.2f}s")
        print(f"decode {args.new_tokens} steps: {t_dec:.2f}s "
              f"({t_dec/args.new_tokens*1e3:.0f} ms/step incl. dispatch)")
        print("first sequences:", out[:2].tolist())
        if applier is not None:
            if hasattr(chan, "flush"):
                for p in chan.flush():
                    applier.offer(p)
            if applier.needs_resync and applier.can_resync(snap_dir):
                applier.resync_from(snap_dir)
            m = applier.metrics()
            print(f"[serve] stream pinned at v{pinned_v}; live params now "
                  f"v{m['param_version']}"
                  f"{' (resync pending)' if m['needs_resync'] else ''}")
            print("[serve] delta health:",
                  {k: m[k] for k in ("received", "applied", "dropped_corrupt",
                                     "dropped_nonfinite", "dropped_stale",
                                     "gaps_detected", "resyncs")})
    return 0


if __name__ == "__main__":
    sys.exit(main())
