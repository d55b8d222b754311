"""End-to-end driver: train a ~100M-parameter member of the stablelm family
for a few hundred steps with REGTOP-k sparsified gradient sync over
simulated data-parallel workers.

Full run (a few hundred steps; takes a while on CPU):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/train_100m.py --steps 300

Smoke (CI-speed): --steps 5 --tiny
"""
import argparse
import dataclasses
import os
import time

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax

from repro.configs.base import (OptimizerConfig, RunConfig, SHAPES,
                                SparsifierConfig, get_config, reduced_config)
from repro.data import lm_batch
from repro.launch.mesh import make_mesh
from repro.train.step import (build_parallel, build_train_step,
                              init_train_state)


def model_100m():
    """~100M-param member of the stablelm family (same code path)."""
    base = get_config("stablelm-3b")
    return dataclasses.replace(
        base, name="stablelm-100m", n_layers=8, d_model=768, n_heads=12,
        n_kv_heads=12, head_dim=64, d_ff=2048, vocab_size=50304,
        dtype="float32",
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--sparsity", type=float, default=0.01)
    ap.add_argument("--checkpoint-dir", default="")
    args = ap.parse_args()

    cfg = reduced_config(get_config("stablelm-3b")) if args.tiny else model_100m()
    run = RunConfig(
        model=cfg, shape=SHAPES["train_4k"],
        sparsifier=SparsifierConfig(kind="regtopk", sparsity=args.sparsity,
                                    mu=0.5, comm_mode="sparse"),
        optimizer=OptimizerConfig(kind="adam", lr=3e-4, warmup_steps=20,
                                  schedule="cosine", total_steps=args.steps),
    )
    mesh = make_mesh(data=4, model=2)
    pal = build_parallel(mesh)
    key = jax.random.PRNGKey(0)
    with jax.set_mesh(mesh):
        params, opt_state, ef_state = init_train_state(run, mesh, pal, key)
        n = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
        print(f"{cfg.name}: {n/1e6:.1f}M params, REGTOP-k S={args.sparsity}, "
              f"sparse all-gather DP sync, ZeRO-1 Adam")
        step, _, _ = build_train_step(run, mesh, pal)
        jstep = jax.jit(step, donate_argnums=(0, 1, 2))
        t0 = time.time()
        for t in range(args.steps):
            batch = lm_batch(cfg, args.batch, args.seq, 0, t)
            params, opt_state, ef_state, m = jstep(params, opt_state,
                                                   ef_state, batch, key)
            if t % 10 == 0 or t == args.steps - 1:
                print(f"step {t:4d} loss {float(m['loss']):.4f} "
                      f"gnorm {float(m['gnorm_local']):.2f} "
                      f"({time.time()-t0:.0f}s)", flush=True)
        if args.checkpoint_dir:
            from repro.checkpoint import save_checkpoint
            save_checkpoint(args.checkpoint_dir, args.steps, params,
                            opt_state, ef_state)
            print("checkpoint saved to", args.checkpoint_dir)


if __name__ == "__main__":
    main()
