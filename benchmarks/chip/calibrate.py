"""Readings from which the limits on ``correct`` are set (limits/<cell>.json).

    python3 benchmarks/chip/calibrate.py --workload <name> \
        --seeds 101,102,... [--control-seeds 3] [--faults flip_update,...]

On the chip the cell asks for, in one process: for each seed, set-up and
the check steps of the program as a run makes them, then the float32
reference; the compared numbers of the program against it, its memory
statistics and, for the first seed, the compiled step's memory analysis.
For the first ``--control-seeds`` seeds also the control (the reference
computed with float8 operands, in the program's place) and each planted
fault of the reference (``reference.train.FAULTS``), against the same
float32 reference. One JSON line per reading goes to stdout and to
chiprun_out/calibrate-<cell>.jsonl.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from cells import ROOT, load_cell  # noqa: E402

sys.path.insert(1, os.path.join(ROOT, "src"))


def memory_analysis(step):
    m = step.memory_analysis()
    return {k: getattr(m, k) for k in dir(m) if k.endswith("_in_bytes")}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    import checks
    import harness
    devices = harness.require_chips(cell["chips"])
    harness.enable_cache()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, f"calibrate-{cell['name']}.jsonl"), "a")

    def emit(rec):
        line = json.dumps(rec, default=lambda x: x.tolist())
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    built = None
    faults = [f for f in args.faults.split(",") if f]
    seeds = [int(s) for s in args.seeds.split(",")]
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        run = harness.Run(cell, seed, devices, built=built)
        built = (run.prog, run.step)
        rec = {"seed": seed, "setup_s": time.perf_counter() - t0,
               "memory_stats": [d.memory_stats() for d in devices]}
        if i == 0:
            rec["memory_analysis"] = memory_analysis(run.step)
        run.free_state()
        t0 = time.perf_counter()
        prog, ref = run.reference()
        rec["reference_s"] = time.perf_counter() - t0
        rec["program"] = checks.numbers(prog, ref)
        rec["change"] = prog["change"]
        rec["losses"] = {"program": prog["losses"], "reference": ref["losses"]}
        rec["dense_g1_leaf_norms"] = ref["dense_g1_leaf_norms"]
        emit(rec)
        for mode, fault in ([("fp8", None)] + [("f32", f) for f in faults]
                            if i < args.control_seeds else []):
            other = harness.reference_run(cell, run.keys, run.prog, devices,
                                          mode=mode, fault=fault)
            read = harness.against(other, ref)
            emit({"seed": seed, "as_program": fault or "control_" + mode,
                  "numbers": checks.numbers(read, ref),
                  "change": read["change"]})
            del other
        del run, ref
    log.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
