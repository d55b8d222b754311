"""Quickstart: train a reduced-config LM with REGTOP-k sparsified data
parallelism on simulated workers (8 host devices), then serve it.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/quickstart.py
"""
import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp

from repro.configs.base import (OptimizerConfig, RunConfig, SHAPES,
                                SparsifierConfig, get_config, reduced_config)
from repro.data import lm_batch
from repro.launch.mesh import make_mesh
from repro.serve.step import build_decode_step, build_prefill, serve_parallel
from repro.train.step import (build_parallel, build_train_step,
                              init_train_state)


def main():
    cfg = reduced_config(get_config("stablelm-3b"))
    run = RunConfig(
        model=cfg, shape=SHAPES["train_4k"],
        sparsifier=SparsifierConfig(kind="regtopk", sparsity=0.01, mu=0.5,
                                    comm_mode="sparse"),
        optimizer=OptimizerConfig(kind="adam", lr=1e-3),
    )
    mesh = make_mesh(data=4, model=2)
    pal = build_parallel(mesh)
    key = jax.random.PRNGKey(0)

    with jax.set_mesh(mesh):
        params, opt_state, ef_state = init_train_state(run, mesh, pal, key)
        step, _, _ = build_train_step(run, mesh, pal)
        jstep = jax.jit(step, donate_argnums=(0, 1, 2))
        print(f"training {cfg.name} with {run.sparsifier.kind} "
              f"(S={run.sparsifier.sparsity}, sparse all-gather comm) on "
              f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}")
        for t in range(30):
            batch = lm_batch(cfg, 8, 64, 0, t)
            params, opt_state, ef_state, m = jstep(params, opt_state,
                                                   ef_state, batch, key)
            if t % 5 == 0:
                print(f"  step {t:3d} loss {float(m['loss']):.4f} "
                      f"nonzero-frac {float(m['agg_nonzero']):.4f}")

    # serve: prefill a prompt + greedy-decode a few tokens
    import dataclasses
    srun = dataclasses.replace(
        run, shape=dataclasses.replace(SHAPES["decode_32k"], seq_len=96,
                                       global_batch=8))
    spal = serve_parallel(mesh, srun, decode=True)
    with jax.set_mesh(mesh):
        pre, _ = build_prefill(srun, mesh, spal)
        dec, _ = build_decode_step(srun, mesh, spal)
        prompt = jax.random.randint(key, (8, 16), 0, cfg.vocab_size)
        logits, cache = jax.jit(pre)(params, {"tokens": prompt})
        toks = []
        for _ in range(8):
            nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            toks.append(nxt)
            logits, cache = jax.jit(dec)(params, cache, nxt)
        out = jnp.concatenate(toks, 1)
        print("greedy decode (batch 8 x 8 new tokens):")
        print(out[:2])


if __name__ == "__main__":
    main()
