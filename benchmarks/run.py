"""Benchmark harness — one entry per paper table/figure plus system
benchmarks. Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--json] \
      [--only fig1,kernels,compress,...]

``--json`` additionally persists machine-readable results for benches
that support it (currently ``compress`` -> BENCH_compress.json), so the
perf trajectory of the hot path is tracked across PRs.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

WRITE_JSON = False


def _row(name, us, derived):
    print(f"{name},{us:.1f},{derived}", flush=True)


def bench_fig1_toy(quick):
    from benchmarks.paper_experiments import fig1_toy_logistic
    t0 = time.time()
    out = fig1_toy_logistic(iters=100)
    us = (time.time() - t0) * 1e6 / 100
    stall = sum(1 for v in out["topk"] if abs(v - out["topk"][0]) < 1e-6)
    track = max(abs(a - b) for a, b in zip(out["regtopk"], out["none"]))
    _row("fig1_toy_top1_stall_iters", us, stall)
    _row("fig1_toy_regtop1_max_gap_vs_dense", us, f"{track:.4f}")


def bench_fig2_linreg(quick):
    from benchmarks.paper_experiments import fig2_linreg
    iters = 800 if quick else 3000
    t0 = time.time()
    res = fig2_linreg(iters=iters)
    us = (time.time() - t0) * 1e6 / (iters * 9)
    for S in (0.4, 0.5, 0.6):
        g_t = res[(S, "topk")][-1]
        g_r = res[(S, "regtopk")][-1]
        g_d = res[(S, "none")][-1]
        _row(f"fig2_linreg_S{S}_final_gap_topk", us, f"{g_t:.4e}")
        _row(f"fig2_linreg_S{S}_final_gap_regtopk", us, f"{g_r:.4e}")
        _row(f"fig2_linreg_S{S}_final_gap_dense", us, f"{g_d:.4e}")
        g_s = res[(S, "sketchtopk")][-1]
        _row(f"fig2_linreg_S{S}_final_gap_sketchtopk", us, f"{g_s:.4e}")
        _row(f"fig2_linreg_S{S}_regtopk_improvement", us,
             f"{g_t / max(g_r, 1e-12):.1f}x")
        _row(f"fig2_linreg_S{S}_sketchtopk_improvement", us,
             f"{g_t / max(g_s, 1e-12):.1f}x")


def bench_fig3_nn(quick):
    from benchmarks.paper_experiments import fig3_nn
    iters = 120 if quick else 400
    t0 = time.time()
    out = fig3_nn(iters=iters, eval_every=max(iters // 4, 1))
    us = (time.time() - t0) * 1e6 / iters
    acc_t = out["topk"][-1][1]
    acc_r = out["regtopk"][-1][1]
    _row("fig3_nn_S0.001_acc_topk", us, f"{acc_t:.4f}")
    _row("fig3_nn_S0.001_acc_regtopk", us, f"{acc_r:.4f}")
    _row("fig3_nn_S0.001_acc_gain", us, f"{(acc_r - acc_t) * 100:.1f}pp")


def bench_comm_volume(quick):
    from repro.configs.base import SparsifierConfig, get_config, list_archs
    from repro.core.aggregate import comm_bytes_per_step
    n_workers = 16
    for arch in list_archs():
        cfg = get_config(arch)
        j = cfg.param_count()
        dense = comm_bytes_per_step(
            SparsifierConfig(kind="none"), j, n_workers)["bytes"]
        for S in (0.01, 0.001):
            sp = comm_bytes_per_step(
                SparsifierConfig(kind="regtopk", sparsity=S,
                                 comm_mode="sparse"), j, n_workers)
            _row(f"comm_{arch}_S{S}_reduction", 0.0,
                 f"{dense / sp['bytes']:.0f}x")


def bench_kernels(quick):
    from repro.core import select
    j = 200_000 if quick else 1_000_000
    x = jax.random.normal(jax.random.PRNGKey(0), (j,))
    k = j // 1000
    for name, fn in (
        ("exact_topk_mask", jax.jit(lambda v: select.topk_mask_exact(v, k))),
        ("histogram_topk_mask_jnp",
         jax.jit(lambda v: select.topk_mask_histogram(v, k))),
    ):
        fn(x).block_until_ready()
        t0 = time.time()
        for _ in range(5):
            fn(x).block_until_ready()
        _row(f"kernel_{name}_J{j}", (time.time() - t0) * 1e6 / 5, k)
    # fused EF pass (Pallas; interpret mode on CPU -> correctness timing only)
    from repro.kernels.fused_ef.ops import fused_regtopk_scores
    je = 131_072
    args = [jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(1), i),
                              (je,)) for i in range(5)]
    fn = jax.jit(lambda g, e, a, ga, s: fused_regtopk_scores(
        g, e, a, ga, s, omega=1 / 16, mu=0.5, Q=0.0))
    fn(*args)[0].block_until_ready()
    t0 = time.time()
    for _ in range(3):
        fn(*args)[0].block_until_ready()
    _row(f"kernel_fused_ef_scores_J{je}", (time.time() - t0) * 1e6 / 3,
         "interpret" if jax.default_backend() != "tpu" else "native")


def bench_compress(quick):
    """Reference vs fused two-sweep compress on the production
    (comm_mode="sparse") paths (DESIGN.md §2.2/§2.5):

    - group "regtopk_exact": the REGTOP-k exact-selector path, plus the
      bucketed (num_buckets=8) and auto-bucketed (num_buckets=0) fused
      variants (§2.4), and the density-allocation variants (§2.6:
      fused_prop / fused_adapt — per-segment budget split; every row
      carries an ``allocation`` column and the allocated rows must hold
      the same absolute 2-sweep / 2-write-unit fused budget), and the
      streaming variant (§2.8: fused_stream — overlap="backward" per-
      segment sweeps; same 2-sweep budget, plus the analytic
      exposed-comm pair the check_compress streaming gate compares);
    - group "topk_hist": the histogram-selector path — fused since the
      capability-dispatch PR (reference-pipeline histogram packs no
      pairs and degrades sparse comm, so its row times the simulate
      path);
    - group "fused_sketch": the per-worker unit of the sketch-
      coordinated path (§2.9) — accumulate a = err + g and CountSketch-
      encode it. reference = legacy vmap encode (materializes (rows, J)
      hash/sign intermediates); fused = ops.fused_sketch_encode (encode
      kernel reads a once), which must hold the same absolute 2-sweep
      sparse-path budget as every other fused row.
      benchmarks.check_compress REQUIRES this group in fresh results.

    us/call = min over repeats (microbenchmark convention); sweeps/step
    from the traced-shape audit. --json -> BENCH_compress.json (the
    committed copy is the baseline benchmarks.check_compress gates CI
    against: audit metrics per row + fused-beats-reference per group at
    the largest J)."""
    import dataclasses
    from repro.configs.base import SparsifierConfig

    sizes = [1 << 20] if quick else [1 << 20, 1 << 24]
    # min-over-repeats strips scheduler/steal noise; the 2-vCPU CI-class
    # boxes this runs on need a few more samples for a clean window
    repeats = 3 if quick else 8
    rows = []
    for j in sizes:
        cfg_ref = SparsifierConfig(kind="regtopk", sparsity=0.001, mu=0.5,
                                   selector="exact", comm_mode="sparse")
        cfg_fus = dataclasses.replace(cfg_ref, pipeline="fused")
        cfg_hr = SparsifierConfig(kind="topk", sparsity=0.001,
                                  selector="histogram", comm_mode="sparse")
        groups = (
            ("regtopk_exact", "regtopk", (
                ("reference", cfg_ref),
                ("fused", cfg_fus),
                ("fused_b8", dataclasses.replace(cfg_fus, num_buckets=8)),
                ("fused_auto", dataclasses.replace(cfg_fus, num_buckets=0)),
                ("fused_prop", dataclasses.replace(
                    cfg_fus, allocation="proportional")),
                ("fused_adapt", dataclasses.replace(
                    cfg_fus, allocation="adaptive")),
                ("fused_stream", dataclasses.replace(
                    cfg_fus, overlap="backward")),
            )),
            ("topk_hist", "topk_hist", (
                ("reference", cfg_hr),
                ("fused", dataclasses.replace(cfg_hr, pipeline="fused")),
            )),
        )
        cfg_sk = SparsifierConfig(kind="sketchtopk", sparsity=0.001,
                                  selector="exact", comm_mode="sparse")
        groups += (
            ("fused_sketch", "sketch", (
                ("reference", cfg_sk),
                ("fused", dataclasses.replace(cfg_sk, pipeline="fused")),
            )),
        )
        g = jax.random.normal(jax.random.PRNGKey(0), (j,), jnp.float32)
        for group, stem, variants in groups:
            us = {}
            for label, cfg in variants:
                bench_one = (_bench_sketch_one if group == "fused_sketch"
                             else _bench_compress_one)
                row = bench_one(cfg, g, j, repeats)
                us[label] = row["us_per_call"]
                row.update({"name": f"compress_{stem}_{label}_J{j}",
                            "group": group, "pipeline": label,
                            "selector": cfg.selector,
                            "comm_mode": cfg.comm_mode})
                rows.append(row)
                _row(row["name"], row["us_per_call"],
                     f"sweeps={row['sweeps_per_step']}")
            speedup = us["reference"] / us["fused"]
            tag = "" if group == "regtopk_exact" else f"_{group}"
            rows.append({"name": f"compress_speedup{tag}_J{j}", "j": j,
                         "group": group, "speedup": round(speedup, 2)})
            _row(f"compress_speedup{tag}_J{j}", 0.0, f"{speedup:.2f}x")
    if WRITE_JSON:
        payload = {"bench": "compress", "backend": jax.default_backend(),
                   "sparsity": 0.001, "comm_mode": "sparse",
                   "rows": rows}
        with open("BENCH_compress.json", "w") as fh:
            json.dump(payload, fh, indent=2)
            fh.write("\n")


# worker count the compress benchmark models (omega = 1/N_WORKERS and the
# num_buckets=0 auto-resolution must agree on it)
N_WORKERS = 16


def _bench_compress_one(cfg, g, j, repeats) -> dict:
    from repro.core import sparsify
    from repro.kernels.compress.audit import audit_fn
    state = sparsify.init_state(cfg, j)

    def f(state, g):
        o = sparsify.compress(cfg, state, g, omega=1 / N_WORKERS)
        outs = [o.state, o.values, o.indices]
        if o.ghat is not None:
            outs.append(o.ghat)
        return tuple(jax.tree_util.tree_leaves(outs))

    # timing methodology unchanged across PRs (fixed inputs, undonated,
    # min over repeats) so us_per_call rows stay comparable; the audit
    # below models the PRODUCTION calling convention — launch/train.py
    # donates the state, so err_prev/mom O(k) scatters update in place
    # (audit_fn's donate_argnums mirrors jit's).
    fn = jax.jit(f)
    jax.block_until_ready(fn(state, g))       # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(state, g))
        best = min(best, time.perf_counter() - t0)
    aud = audit_fn(f, state, g, j=j, donate_argnums=(0,))
    row = {"j": j, "num_buckets": cfg.num_buckets,
           "allocation": cfg.allocation, "overlap": cfg.overlap,
           "us_per_call": round(best * 1e6, 1),
           "sweeps_per_step": aud["traversals"],
           "read_units": round(aud["read_units"], 2),
           "write_units": round(aud["write_units"], 2)}
    if cfg.num_buckets == 0:
        row["num_buckets_resolved"] = sparsify.resolve_num_buckets(
            cfg, j, N_WORKERS)
    if cfg.overlap == "backward":
        # analytic exposed-comm model (roofline.comm_behind_backward_s,
        # DESIGN.md §2.8): the sparse gather either serializes after the
        # backward pass (serial) or streams behind it per segment
        # (stream). t_backward is LOWER-bounded by one fp32 re-read of
        # the gradient, so the streamed term is a conservative claim;
        # check_compress gates stream <= serial.
        from repro.core import allocate
        from repro.core.aggregate import sparse_gather_wire_bytes
        from repro.roofline.analysis import HW_V5E, comm_behind_backward_s
        gw = sparse_gather_wire_bytes(cfg, j, N_WORKERS)
        t_gather = (gw or 0) / HW_V5E.ici_bw
        t_bwd = j * 4 / HW_V5E.hbm_bw
        nseg = allocate.resolve_num_segments(cfg, j)
        row["num_stream_segments"] = nseg
        row["exposed_comm_serial_s"] = t_gather
        row["exposed_comm_stream_s"] = comm_behind_backward_s(
            t_gather, t_bwd, nseg)
    return row


def _bench_sketch_one(cfg, g, j, repeats) -> dict:
    """Per-worker unit of the sketch-coordinated path (DESIGN.md §2.9):
    accumulate a = err + g and CountSketch-encode it. Selection and the
    shared-mask decode run at the AGGREGATE level (after the sketch
    all-reduce), so they are not part of the per-worker compress unit
    this row times and audits."""
    from repro.core import sketch, sparsify
    from repro.kernels.compress import ops as cops
    from repro.kernels.compress.audit import audit_fn
    state = sparsify.init_state(cfg, j)
    n_rows = cfg.sketch_rows
    width = sketch.resolve_width(sparsify.resolve_k(cfg, j),
                                 cfg.sketch_width)
    if cfg.pipeline == "fused":
        def f(state, g):
            out = cops.fused_sketch_encode(g, state["err_prev"],
                                           rows=n_rows, width=width)
            return out["a"], out["sketch"]
    else:
        def f(state, g):
            a = state["err"].astype(jnp.float32) + g
            return a, sketch.encode(a, n_rows, width)

    fn = jax.jit(f)
    jax.block_until_ready(fn(state, g))       # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(state, g))
        best = min(best, time.perf_counter() - t0)
    aud = audit_fn(f, state, g, j=j, donate_argnums=(0,))
    return {"j": j, "num_buckets": cfg.num_buckets,
            "allocation": cfg.allocation, "overlap": cfg.overlap,
            "sketch_rows": n_rows, "sketch_width": width,
            "us_per_call": round(best * 1e6, 1),
            "sweeps_per_step": aud["traversals"],
            "read_units": round(aud["read_units"], 2),
            "write_units": round(aud["write_units"], 2)}


def bench_train_step(quick):
    """Smoke-scale distributed train step wall time per sparsifier."""
    from repro.configs.base import (OptimizerConfig, RunConfig, SHAPES,
                                    SparsifierConfig, get_config,
                                    reduced_config)
    from repro.data import lm_batch
    from repro.train.step import (build_parallel, build_train_step,
                                  init_train_state)
    cfg = reduced_config(get_config("stablelm-3b"))
    from repro.launch.mesh import make_mesh
    mesh = make_mesh(1, 1)
    for kind, pipeline in (("none", "reference"), ("topk", "reference"),
                           ("regtopk", "reference"), ("regtopk", "fused")):
        run = RunConfig(model=cfg, shape=SHAPES["train_4k"],
                        sparsifier=SparsifierConfig(kind=kind, sparsity=0.01,
                                                    pipeline=pipeline),
                        optimizer=OptimizerConfig(kind="adam", lr=1e-3))
        pal = build_parallel(mesh)
        with jax.set_mesh(mesh):
            params, opt_state, ef_state = init_train_state(
                run, mesh, pal, jax.random.PRNGKey(0))
            step, _, _ = build_train_step(run, mesh, pal)
            jstep = jax.jit(step)
            batch = lm_batch(cfg, 4, 64, 0, 0)
            out = jstep(params, opt_state, ef_state, batch,
                        jax.random.PRNGKey(0))
            jax.block_until_ready(out)
            t0 = time.time()
            n = 3
            m = None
            for t in range(n):
                params, opt_state, ef_state, m = jstep(
                    params, opt_state, ef_state, batch, jax.random.PRNGKey(t))
            jax.block_until_ready(params)
            tag = kind if pipeline == "reference" else f"{kind}_{pipeline}"
            _row(f"train_step_smoke_{tag}", (time.time() - t0) * 1e6 / n,
                 f"loss={float(m['loss']):.3f}")


BENCHES = {
    "fig1": bench_fig1_toy,
    "fig2": bench_fig2_linreg,
    "fig3": bench_fig3_nn,
    "comm": bench_comm_volume,
    "kernels": bench_kernels,
    "compress": bench_compress,
    "train_step": bench_train_step,
}


def main() -> None:
    global WRITE_JSON
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", action="store_true",
                    help="persist machine-readable results (BENCH_*.json)")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    WRITE_JSON = args.json
    names = args.only.split(",") if args.only else list(BENCHES)
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        ap.error(f"unknown bench(es) {unknown}; known: {sorted(BENCHES)}")
    print("name,us_per_call,derived")
    for n in names:
        BENCHES[n](args.quick)


if __name__ == "__main__":
    main()
