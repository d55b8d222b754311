import os
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production mesh, WITHOUT allocating any real arrays (ShapeDtypeStruct
inputs only). Proves the sharding config is coherent and yields the
memory/cost/collective numbers for EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch stablelm-3b \
      --shape train_4k [--multi-pod] [--out results/dryrun.json] \
      [--sparsifier regtopk --sparsity 0.001 --comm sparse] [--mesh 4x4]

The XLA_FLAGS lines below MUST run before any other jax import — jax locks
the device count at first init. Smoke tests and benches do NOT import this
module (they see 1 device).
"""
import argparse
import dataclasses
import json
import re
import time
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (
    SHAPES, OptimizerConfig, RunConfig, SparsifierConfig,
    get_config, list_archs,
)
from repro.launch.mesh import make_production_mesh, make_mesh
from repro.models.params import count_active_params, count_params_analytic


# ---------------------------------------------------------------------------
# input_specs: ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------

def input_specs(run: RunConfig, mesh, pal, kind: str):
    """Abstract inputs for the given step kind: train | prefill | decode."""
    from repro.data.synthetic import lm_batch_specs
    from repro.serve.step import decode_cache_specs
    from repro.train.step import resolve_model_cfg
    cfg = resolve_model_cfg(run)
    gb, seq = run.shape.global_batch, run.shape.seq_len
    dpaxes = pal.data_axes

    def shd(spec):
        return NamedSharding(mesh, spec)

    if kind in ("train", "prefill"):
        b = lm_batch_specs(cfg, gb, seq)
        specs = {"tokens": P(dpaxes, None), "targets": P(dpaxes, None),
                 "patches": P(dpaxes, None, None), "frames": P(dpaxes, None, None)}
        return {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=shd(specs[k]))
                for k, v in b.items() if not (kind == "prefill" and k == "targets")}
    # decode: one token per sequence + cache
    tok_spec = P(dpaxes, None) if pal.cache_seq_axis is None else P(None, None)
    token = jax.ShapeDtypeStruct((gb, 1), jnp.int32, sharding=shd(tok_spec))
    cache_abs, cspecs, b_local, seq_local = decode_cache_specs(run, mesh, pal)
    cache = jax.tree_util.tree_map(
        lambda l, s: jax.ShapeDtypeStruct(
            _globalize_shape(l.shape, s, mesh), l.dtype, sharding=shd(s)),
        cache_abs, cspecs)
    return {"token": token, "cache": cache}


def _axsize(mesh, ax):
    if isinstance(ax, (tuple, list)):
        n = 1
        for a in ax:
            n *= mesh.shape[a]
        return n
    return mesh.shape[ax]


def _globalize_shape(shape, spec, mesh):
    out = list(shape)
    for d, ax in enumerate(spec):
        if ax is not None:
            out[d] = out[d] * _axsize(mesh, ax)
    return tuple(out)


def _globalize_tree(tmpl, specs, mesh):
    return jax.tree_util.tree_map(
        lambda l, s: jax.ShapeDtypeStruct(
            _globalize_shape(l.shape, s, mesh), l.dtype,
            sharding=NamedSharding(mesh, s)),
        tmpl, specs)


# ---------------------------------------------------------------------------
# Lower + compile one (arch, shape, mesh)
# ---------------------------------------------------------------------------

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def collective_bytes(hlo_text: str) -> dict:
    """Sum operand bytes of collective ops in (optimized) HLO text."""
    dt_bytes = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2,
                "u16": 2}
    out = {c: 0 for c in COLLECTIVES}
    # lines like: %x = bf16[2,16,128]{...} all-gather(...)
    pat = re.compile(
        r"=\s+(?:\()?([a-z0-9]+)\[([0-9,]*)\][^=]*?\b("
        + "|".join(COLLECTIVES) + r")\b")
    for m in pat.finditer(hlo_text):
        dt, dims, op = m.groups()
        if dt not in dt_bytes:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[op] += n * dt_bytes[dt]
    out["total"] = sum(out[c] for c in COLLECTIVES)
    return out


def build_step(run: RunConfig, mesh, kind: str):
    from repro.serve.step import (build_decode_step, build_prefill,
                                  serve_parallel)
    from repro.train.step import (build_parallel, build_train_step,
                                  train_state_specs)
    if kind == "train":
        pal = build_parallel(mesh)
        step, in_specs, _ = build_train_step(run, mesh, pal)
        tmpl, pspecs, ospecs, especs = train_state_specs(run, mesh, pal)
        params_abs = _globalize_tree(tmpl, pspecs, mesh)
        from repro.core import sparsify
        from repro.optim import init_opt_state, opt_shard_len
        flat_total = sum(int(l.size) for l in jax.tree_util.tree_leaves(tmpl))
        dp = 1
        for a in pal.data_axes:
            dp *= mesh.shape[a]
        shard = opt_shard_len(flat_total, dp)
        opt_tmpl = jax.eval_shape(partial(init_opt_state, run.optimizer),
                                  jax.ShapeDtypeStruct((shard,), jnp.float32))
        ef_tmpl = jax.eval_shape(
            lambda: sparsify.init_state(run.sparsifier, flat_total))
        exp = lambda t: jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct((1, 1) + l.shape, l.dtype)
            if l.ndim >= 1 else l, t)
        opt_abs = _globalize_tree(exp(opt_tmpl), ospecs, mesh)
        ef_abs = _globalize_tree(exp(ef_tmpl), especs, mesh)
        batch_abs = input_specs(run, mesh, pal, "train")
        key_abs = jax.ShapeDtypeStruct((2,), jnp.uint32,
                                       sharding=NamedSharding(mesh, P()))
        return step, (params_abs, opt_abs, ef_abs, batch_abs, key_abs), pal
    if kind == "prefill":
        pal = serve_parallel(mesh, run, decode=False)
        step, (pspecs, bspecs) = build_prefill(run, mesh, pal)
        from repro.train.step import abstract_params
        tmpl = abstract_params(run, pal)
        params_abs = _globalize_tree(
            tmpl, pspecs, mesh)
        batch_abs = input_specs(run, mesh, pal, "prefill")
        return step, (params_abs, batch_abs), pal
    # decode
    pal = serve_parallel(mesh, run, decode=True)
    step, (pspecs, cspecs, tok_spec) = build_decode_step(run, mesh, pal)
    from repro.train.step import abstract_params
    tmpl = abstract_params(run, pal)
    params_abs = _globalize_tree(tmpl, pspecs, mesh)
    ins = input_specs(run, mesh, pal, "decode")
    return step, (params_abs, ins["cache"], ins["token"]), pal


def dryrun_one(arch: str, shape_name: str, mesh, *, sparsifier="regtopk",
               sparsity=0.001, comm="sparse", verbose=True,
               variant="", state_format="dense", ef_dtype="float32",
               pipeline="reference", num_buckets=1, selector="exact",
               wire_dtype="float32", allocation="global", num_segments=0,
               fault_schedule="", err_decay=1.0, combine="mean",
               overlap="none", sketch_rows=3, sketch_width=0,
               delta_k=0, delta_fault_schedule="",
               **cfg_overrides) -> dict:
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    moe_over = {k[4:]: v for k, v in cfg_overrides.items()
                if k.startswith("moe_") and k != "moe_every"}
    cfg_overrides = {k: v for k, v in cfg_overrides.items()
                     if not (k.startswith("moe_") and k != "moe_every")}
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    if moe_over and cfg.moe is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, **moe_over))
    attn_override = ""
    if shape_name == "long_500k" and cfg.attn_kind == "full" and \
            cfg.family not in ("ssm",) and cfg.attn_every == 1:
        attn_override = "sliding"   # dense archs: sliding-window variant
    run = RunConfig(
        model=cfg, shape=shape,
        sparsifier=SparsifierConfig(kind=sparsifier, sparsity=sparsity,
                                    comm_mode=comm, selector=selector,
                                    mu=0.5, state_format=state_format,
                                    ef_dtype=ef_dtype, pipeline=pipeline,
                                    num_buckets=num_buckets,
                                    allocation=allocation,
                                    num_segments=num_segments,
                                    wire_dtype=wire_dtype,
                                    err_decay=err_decay, combine=combine,
                                    overlap=overlap,
                                    sketch_rows=sketch_rows,
                                    sketch_width=sketch_width),
        optimizer=OptimizerConfig(kind="adam", lr=1e-4),
        attn_override=attn_override,
        fault_schedule=fault_schedule,
    )
    kind = shape.kind
    num_buckets_resolved = num_buckets
    gather_wire = None
    fault_rec = None
    num_stream_segments = None
    sketch_rec = None
    if kind == "train":
        # the trace resolves num_buckets inside GradientSync; the shared
        # helper mirrors it exactly (same flattened per-rank J, same dp
        # extent) so the record — which the roofline's
        # collective_exposed_s consumes — carries the chunk count the
        # compiled program actually executes. The same (j_local, dp)
        # yields the dtype-aware sparse-gather payload
        # (aggregate.sparse_gather_wire_bytes, None off the sparse path).
        from repro.core.aggregate import sparse_gather_wire_bytes
        from repro.train.step import auto_num_buckets_for_run
        nb_auto, j_local, dp = auto_num_buckets_for_run(run, mesh)
        if num_buckets == 0:
            num_buckets_resolved = nb_auto
        gather_wire = sparse_gather_wire_bytes(run.sparsifier, j_local, dp)
        from repro.core.aggregate import sketch_allreduce_bytes
        skb = sketch_allreduce_bytes(run.sparsifier, j_local, dp)
        if skb is not None:
            # sketch-coordinated selection: the record carries the
            # EFFECTIVE width (resolve_width may cap the 4k auto-size,
            # warned once) and the analytic all-reduce payload the
            # roofline's sketch_allreduce_s term consumes
            from repro.core import sketch as core_sketch
            from repro.core.sparsify import resolve_k
            sketch_rec = {
                "sketch_rows": run.sparsifier.sketch_rows,
                "sketch_width_effective": core_sketch.resolve_width(
                    resolve_k(run.sparsifier, j_local),
                    run.sparsifier.sketch_width),
                "sketch_allreduce_bytes": float(skb),
            }
        if overlap == "backward":
            # the streaming partition the compiled step executes — the
            # roofline's backward-overlap model consumes the count
            from repro.train.step import stream_bounds_for_run
            num_stream_segments = len(stream_bounds_for_run(run, mesh))
        if fault_schedule:
            # fault config rides in the record (DESIGN.md §2.7) so the
            # roofline can expose the straggler-scaled collective share;
            # the _active volume is the idealized elastic wire (absent
            # workers transmit nothing), NOT what the fixed-shape
            # compiled collectives move
            from repro.core import faults
            sched = faults.parse_schedule(fault_schedule)
            fault_rec = faults.describe(sched, dp)
            gw_act = sparse_gather_wire_bytes(
                run.sparsifier, j_local, dp,
                n_active=fault_rec["n_active_expected"])
            if gw_act is not None:
                fault_rec["sparse_gather_wire_bytes_active"] = float(gw_act)
    t0 = time.time()
    step, abs_args, pal = build_step(run, mesh, kind)
    with jax.set_mesh(mesh):
        lowered = jax.jit(step).lower(*abs_args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):          # jaxlib < 0.5 returns [dict]
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    from repro.roofline.hlo_parser import analyze_hlo
    parsed = analyze_hlo(hlo, mesh.shape["model"])
    n_params = count_params_analytic(cfg)
    n_active = count_active_params(cfg)
    rec = {
        "arch": arch, "shape": shape_name, "variant": variant,
        "mesh": dict(zip(mesh.axis_names,
                         [int(mesh.shape[a]) for a in mesh.axis_names])),
        "kind": kind, "attn_override": attn_override,
        "num_buckets": num_buckets_resolved,
        "num_buckets_requested": num_buckets,
        "allocation": allocation,
        "params": int(n_params), "active_params": int(n_active),
        "flops": float(cost.get("flops", -1)),
        "bytes_accessed": float(cost.get("bytes accessed", -1)),
        "collective_bytes": coll,
        # loop-aware HLO parse (scan bodies x trip count) — the numbers the
        # roofline uses; cost_analysis counts while bodies once (see
        # roofline/hlo_parser.py docstring)
        "hlo_flops": parsed["flops"],
        "hlo_bytes": parsed["hbm_bytes"],
        "hlo_collectives": parsed["collectives"],
        "hlo_collective_wire_bytes": parsed["collective_wire_bytes"],
        "unknown_trip_loops": parsed["unknown_trip_loops"],
        "t_lower_s": round(t_lower, 1), "t_compile_s": round(t_compile, 1),
        "wire_dtype": wire_dtype,
        "overlap": overlap,
        "memory": {
            k: int(getattr(mem, k, -1)) for k in
            ("temp_size_in_bytes", "argument_size_in_bytes",
             "output_size_in_bytes", "generated_code_size_in_bytes",
             "alias_size_in_bytes", "peak_memory_in_bytes")
        },
    }
    if gather_wire is not None:
        rec["sparse_gather_wire_bytes"] = int(gather_wire)
    if num_stream_segments is not None:
        rec["num_stream_segments"] = int(num_stream_segments)
    if sketch_rec is not None:
        rec.update(sketch_rec)
    if fault_rec is not None:
        rec["fault"] = fault_rec
    if delta_k:
        # learning-while-serving channel (DESIGN.md §2.10): the record
        # carries the analytic per-delta wire size, the full-snapshot
        # resync size, and the staleness-vs-bandwidth breakeven so the
        # roofline's delta_apply_s / delta_bcast_s / resync_s terms are
        # modeled, not guessed. k counts against the GLOBAL param vector
        # (the published flat-J space), independent of the mesh.
        from repro.core import faults
        from repro.serve.delta import (delta_wire_bytes, resync_bytes,
                                       resync_equiv_deltas)
        k_eff = int(min(delta_k, n_params))
        rec["delta"] = {
            "k": k_eff,
            "wire_bytes": int(delta_wire_bytes(k_eff)),
            "resync_bytes": int(resync_bytes(n_params)),
            "resync_equiv_deltas": float(
                resync_equiv_deltas(n_params, k_eff)),
        }
        if delta_fault_schedule:
            rec["delta"]["fault"] = faults.describe_channel(
                faults.parse_channel_schedule(delta_fault_schedule))
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} mesh={rec['mesh']}: "
              f"lower {t_lower:.0f}s compile {t_compile:.0f}s", flush=True)
        print("  memory_analysis:", mem)
        print("  cost_analysis: flops={:.3e} bytes={:.3e}".format(
            rec["flops"], rec["bytes_accessed"]))
        print("  hlo(loop-aware): flops={:.3e} bytes={:.3e} wire={:.3e}".format(
            parsed["flops"], parsed["hbm_bytes"],
            parsed["collective_wire_bytes"]))
        print("  collectives(wire):",
              {k: f"{v:.3e}" for k, v in parsed["collectives"].items() if v},
              flush=True)
    return rec


def main():
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mesh", default="", help="e.g. 4x4 or 2x4x4 (override)")
    ap.add_argument("--sparsifier", default="regtopk")
    ap.add_argument("--sparsity", type=float, default=0.001)
    ap.add_argument("--comm", default="sparse")
    ap.add_argument("--pipeline", default="reference",
                    choices=["reference", "fused"])
    ap.add_argument("--num-buckets", type=int, default=1,
                    help="bucketed compression + chunked sparse collectives "
                         "(DESIGN.md §2.4); the record carries num_buckets "
                         "so the roofline reports collective_exposed_s. "
                         "0 auto-tunes the count (the record then carries "
                         "the resolved value)")
    ap.add_argument("--selector", default="exact",
                    choices=["exact", "histogram"])
    ap.add_argument("--allocation", default="global",
                    choices=["global", "proportional", "adaptive"],
                    help="density allocation (DESIGN.md §2.6): split of "
                         "the budget k across segments before selection; "
                         "sum(k_l) == k so sparse wire bytes (and the "
                         "record's sparse_gather_wire_bytes) are "
                         "allocation-invariant")
    ap.add_argument("--num-segments", type=int, default=0,
                    help="segment count for --allocation != global "
                         "(0: follow --num-buckets, else 8)")
    ap.add_argument("--wire-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="wire dtype of the packed VALUES in "
                         "comm_mode='sparse' (indices stay uint32); "
                         "bfloat16 cuts sparse wire bytes 25%% and the "
                         "record's sparse_gather_wire_bytes reflects it")
    ap.add_argument("--fault-schedule", default="",
                    help="fault-injection spec (DESIGN.md §2.7, e.g. "
                         "'iid:0.3'); the record then carries the parsed "
                         "schedule + expected active-worker count and "
                         "sparse_gather_wire_bytes scales to E[n_active]")
    ap.add_argument("--overlap", default="none",
                    choices=["none", "backward"],
                    help="streaming compression (DESIGN.md §2.8): feed "
                         "the gradient into the fused pipeline per "
                         "layer-aligned segment behind the backward "
                         "pass; the record carries num_stream_segments "
                         "so the roofline reports the "
                         "comm-behind-backward exposed term")
    ap.add_argument("--err-decay", type=float, default=1.0,
                    help="EF memory decay on sat-out steps (DESIGN.md §2.7)")
    ap.add_argument("--sketch-rows", type=int, default=3,
                    help="CountSketch rows for --sparsifier sketchtopk "
                         "(DESIGN.md §2.9); the record carries "
                         "sketch_allreduce_bytes so the roofline reports "
                         "the pre-selection barrier term")
    ap.add_argument("--sketch-width", type=int, default=0,
                    help="CountSketch width for --sparsifier sketchtopk; "
                         "0 auto-sizes to min(max(4k, 256), 2^22) and the "
                         "record carries sketch_width_effective")
    ap.add_argument("--combine", default="mean",
                    choices=["mean", "support"],
                    help="elastic combine rule (DESIGN.md §2.7)")
    ap.add_argument("--delta-k", type=int, default=0,
                    help="learning-while-serving delta budget (DESIGN.md "
                         "§2.10): when > 0 the record carries the per-delta "
                         "wire bytes, the full-snapshot resync bytes, and "
                         "the resync breakeven, and the roofline reports "
                         "delta_bcast_s / delta_apply_s / resync_s")
    ap.add_argument("--delta-fault-schedule", default="",
                    help="delta-channel fault spec (loss:P | corrupt:P | "
                         "reorder:W | stall:N); the record's delta section "
                         "then carries the parsed schedule + expected "
                         "first-try delivery rate")
    ap.add_argument("--out", default="")
    ap.add_argument("--variant", default="", help="perf-variant tag for the record")
    ap.add_argument("--state-format", default="dense")
    ap.add_argument("--ef-dtype", default="float32")
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override key=value (e.g. mla_absorb=true)")
    args = ap.parse_args()
    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        if v.lower() in ("true", "false"):
            overrides[k] = v.lower() == "true"
        else:
            try:
                overrides[k] = int(v)
            except ValueError:
                try:
                    overrides[k] = float(v)
                except ValueError:
                    overrides[k] = v

    if args.mesh:
        dims = [int(x) for x in args.mesh.split("x")]
        mesh = make_mesh(*dims[-2:], pods=dims[0] if len(dims) == 3 else 1)
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)

    archs = list_archs() if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    results, failures = [], []
    for a in archs:
        for s in shapes:
            try:
                results.append(dryrun_one(
                    a, s, mesh, sparsifier=args.sparsifier,
                    sparsity=args.sparsity, comm=args.comm,
                    variant=args.variant, state_format=args.state_format,
                    ef_dtype=args.ef_dtype, pipeline=args.pipeline,
                    num_buckets=args.num_buckets, selector=args.selector,
                    wire_dtype=args.wire_dtype, allocation=args.allocation,
                    num_segments=args.num_segments,
                    fault_schedule=args.fault_schedule,
                    err_decay=args.err_decay, combine=args.combine,
                    overlap=args.overlap,
                    sketch_rows=args.sketch_rows,
                    sketch_width=args.sketch_width,
                    delta_k=args.delta_k,
                    delta_fault_schedule=args.delta_fault_schedule,
                    **overrides))
            except Exception as e:  # noqa: BLE001 — report every combo
                import traceback
                traceback.print_exc()
                failures.append({"arch": a, "shape": s, "error": repr(e)})
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        payload = {"results": results, "failures": failures}
        if os.path.exists(args.out):
            try:
                old = json.load(open(args.out))
                keyf = lambda r: (r["arch"], r["shape"], r.get("variant", ""),
                                  tuple(sorted(r["mesh"].items())))
                seen = {keyf(r) for r in results}
                payload["results"] += [
                    r for r in old.get("results", []) if keyf(r) not in seen]
                ok = {(r["arch"], r["shape"]) for r in payload["results"]}
                fseen = set()
                merged = []
                for f in payload["failures"] + old.get("failures", []):
                    kk = (f["arch"], f["shape"])
                    if kk in ok or kk in fseen:
                        continue
                    fseen.add(kk)
                    merged.append(f)
                payload["failures"] = merged
            except Exception:
                pass
        json.dump(payload, open(args.out, "w"), indent=1)
    print(f"\n{len(results)} ok, {len(failures)} failed")
    for f in failures:
        print("FAIL:", f["arch"], f["shape"], f["error"][:200])
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
