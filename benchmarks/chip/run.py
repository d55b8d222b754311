"""Runs one cell of the on-chip benchmark of the REGTOP-k train step once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

From the root of a checkout, on a machine that holds the TPU chips the
cell asks for (BENCHMARK.json); anything else exits 2 with no result.
Set-up makes the weights and batches from the seed, builds and compiles
the step (JAX's persistent cache in ``.jax_cache/``), and drives it
through the check steps. ``--trace 0`` then measures the window of
``--seconds`` and reports the cell's end-to-end metrics; ``--trace 1``
records a profiler trace of a few steps instead and reports the per-layer
metrics. Either way the reference then checks the check steps, and the
numbers compared go, each beside its limit, to the last lines of stderr
and to the ``checks`` key that ends the result: one JSON object, the last
line of stdout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from cells import ROOT, load_cell  # noqa: E402

sys.path.insert(1, os.path.join(ROOT, "src"))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def peak_table(kind):
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise SystemExit(f"[bench] no peaks for device kind {kind!r}; "
                         f"known: {sorted(table)}")
    return table[kind]


def peak_bytes(device):
    """The device's peak HBM use: the allocator's peak of live buffers
    plus its peak reservation for compiled programs' scratch space, which
    ``peak_bytes_in_use`` leaves out."""
    s = device.memory_stats() or {}
    return int(s.get("peak_bytes_in_use", 0)) + int(
        s.get("peak_bytes_reserved", 0))


def device_info(devices):
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peak_bytes(x) for x in devices)}


def per_layer(cell, reduction):
    out = {}
    for m in cell["per_layer"]:
        reader = importlib.import_module("metrics." + m["name"])
        value = reader.read(reduction)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def counters(kept):
    """The mean over the steps of each scalar entry of the step's metrics
    dicts, under the step's own names."""
    import jax
    import numpy as np
    host = jax.device_get(kept)
    return {k: float(np.mean([m[k] for m in host])) for k in host[0]
            if np.ndim(host[0][k]) == 0}


def traced(run, cell, seed, peaks):
    """Trace ``trace_steps`` steps; returns the reduction of the trace,
    with the mean of each of the step's scalar metrics over the traced
    steps under ``counters``, read once the trace has ended."""
    import jax
    import trace_reduce
    out = os.path.join(ROOT, ".bench", f"trace-{cell['name']}-{seed}")
    shutil.rmtree(out, ignore_errors=True)
    steps = cell["traffic"]["trace_steps"]
    jax.profiler.start_trace(out)
    try:
        with jax.profiler.TraceAnnotation("window"):
            n, secs, losses, kept = run.window(steps=steps, annotate=True,
                                               keep_metrics=True)
    finally:
        jax.profiler.stop_trace()
    try:
        red = trace_reduce.reduce_dir(
            out, run.step.as_text(), steps=n, j_local=run.prog.j_local,
            peaks=peaks)
    finally:
        shutil.rmtree(out, ignore_errors=True)
    import flops
    red.update(counters=counters(kept),
               tokens_per_step=run.tokens_per_step, chips=cell["chips"],
               flops_per_token=flops.train_per_token(
                   cell["config"], cell["traffic"]["seq"]))
    return n, secs, losses, red


def execute(cell, seed, seconds, trace, devices, peaks):
    """One run of ``cell`` on ``devices``: set-up, the window (or the
    trace), and the check. Returns the result object."""
    import harness
    harness.enable_cache()
    run = harness.Run(cell, seed, devices)
    setup_s = time.perf_counter() - T_START
    red = None
    if trace:
        n, secs, losses, red = traced(run, cell, seed, peaks)
    else:
        n, secs, losses = run.window(seconds)
    device = device_info(devices)
    run.free_state()
    prog, ref = run.reference()
    import checks
    values = checks.numbers(prog, ref)
    if cell["limits"] is None:
        raise SystemExit(f"[bench] no limits/{cell['name']}.json; "
                         f"numbers {values}")
    checked, ok = checks.judge(values, cell["limits"])
    finite = all(math.isfinite(x) for x in run.check_losses + losses)
    checked["losses_finite"] = {"value": int(finite), "limit": 1}
    ok = ok and finite

    if trace:
        metrics = per_layer(cell, red)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
    else:
        import flops
        tps = n * run.tokens_per_step / secs
        fpt = flops.train_per_token(cell["config"], cell["traffic"]["seq"])
        metrics = {
            "tokens_per_s": {"value": tps, "unit": "tokens/s"},
            "step_mfu": {"value": 100.0 * tps * fpt / (
                len(devices) * peaks["bf16_flops_per_s"]), "unit": "%"},
            "peak_hbm_gib": {"value": device["memory_peak_bytes"] / 2 ** 30,
                             "unit": "GiB"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        want = {m["name"] for m in cell["end_to_end"]}
        metrics = {k: v for k, v in metrics.items() if k in want}
    result = {"correct": ok, "attempted": n,
              "failed": sum(not math.isfinite(x) for x in losses),
              "metrics": metrics, "device": device}
    if red is not None:
        result["breakdown"] = red["breakdown"]
    result["checks"] = checked
    return result


def main(argv=None):
    args = parse_args(argv)
    cell = load_cell(args.workload)
    import harness
    devices = harness.require_chips(cell["chips"])
    peaks = peak_table(devices[0].device_kind)
    result = execute(cell, args.seed, args.seconds, args.trace, devices,
                     peaks)
    for name, c in result["checks"].items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
