"""The reduction from a device trace to per-layer numbers: self time of
nested ops, layer attribution by scope and by source frame, the busy
union and idle gaps, and the exposed time of collectives."""
import gzip
import json
import os

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))

HLO = """HloModule jit_step_fn, is_scheduled=true

FileNames
1 "/x/src/repro/models/xlstm.py"
2 "/x/src/repro/core/aggregate.py"
3 "/x/benchmarks/chip/program.py"

FunctionNames
1 "f"

FileLocations
1 {file_name_id=1 function_name_id=1 line=80 end_line=80 column=0 end_column=0}
2 {file_name_id=2 function_name_id=1 line=120 end_line=120 column=0 end_column=0}
3 {file_name_id=3 function_name_id=1 line=7 end_line=7 column=0 end_column=0}

StackFrames
1 {file_location_id=3 parent_frame_id=0}
2 {file_location_id=1 parent_frame_id=2}
3 {file_location_id=2 parent_frame_id=2}

ENTRY %main {
""" + "\n".join([
    "  %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%c1"
    ', metadata={op_name="jit(step_fn)/jvp(BENCH_model)/dot" stack_frame_id=1}',
    "  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%c2"
    ', metadata={op_name="jit(step_fn)/mul" stack_frame_id=2}',
    "  %conditional = (f32[8]{0}) conditional(%p), branch_computations={%a, %b}",
    "  %sort.7 = (f32[8]{0}) sort(%p), dimensions={0}"
    ', metadata={op_name="jit(step_fn)/BENCH_sync/cond/top_k" stack_frame_id=1}',
    "  %all-gather-start.3 = (f32[8]{0}, f32[32]{0}) all-gather-start(%p)"
    ', metadata={op_name="jit(step_fn)/BENCH_sync/all_gather" stack_frame_id=3}',
    "  %all-gather-done.3 = f32[32]{0} all-gather-done(%all-gather-start.3)"
    ', metadata={op_name="jit(step_fn)/BENCH_sync/all_gather" stack_frame_id=3}',
    "  %fusion.9 = f32[8]{0} fusion(%p), kind=kLoop, calls=%c9"
    ', metadata={op_name="jit(step_fn)/BENCH_optimizer/add" stack_frame_id=1}',
]) + """
}
"""


def _ev(start, dur, name):
    return [start, dur, f"%{name} = f32[8] op()"]


def _trace():
    ops = [
        _ev(0, 10, "fusion.1"),          # model, by scope
        _ev(10, 5, "fusion.2"),          # model, by frame (xlstm.py)
        _ev(20, 40, "conditional"),      # holds the sort: 10 ns of its own
        _ev(25, 30, "sort.7"),           # sync, by scope
        _ev(60, 2, "all-gather-start.3"),
        _ev(62, 8, "all-gather-done.3"),  # 62..70; fusion.9 covers 66..70
        _ev(66, 14, "fusion.9"),         # optimizer
        _ev(90, 5, "fusion.1"),          # after a 10 ns idle gap
    ]
    return {"devices": {0: {"ops": ops,
                            "modules": [[0, 100, "jit_step_fn(123)"]]}},
            "host": [["window", 0, 100], ["loss_fetch", 80, 10]]}


def test_reduction_of_a_small_trace():
    src = tr.hlo_sources(HLO)
    assert src["fusion.1"][1] == "model"
    assert src["fusion.2"][1:3] == ("model", "repro/models/xlstm.py")
    assert src["sort.7"][1] == "sync"
    assert src["conditional"][1] == "unattributed"
    red = tr.reduce_events(_trace(), src, steps=1, j_local=100,
                           peaks={"hbm_bytes_per_s": 1.0},
                           step_module="jit_step_fn")
    d = red["devices"][0]
    assert d["layer_ns"] == {"model": 20, "unattributed": 10, "sync": 30,
                             "sync_collective": 10, "optimizer": 14}
    # busy: 0..15, 20..80, 90..95
    assert d["busy_ns"] == 80 and d["window_ns"] == 100
    # the collectives cover 60..70, fusion.9 overlaps 66..70
    assert d["sync_exposed_ns"] == 6
    gaps = red["breakdown"]["idle_gaps"]
    assert gaps[0] == ["host:loss_fetch", 10e-9]
    assert sorted(g[1] for g in gaps) == [5e-9, 5e-9, 10e-9]


def test_metric_readers_on_the_small_trace():
    import importlib
    red = tr.reduce_events(_trace(), tr.hlo_sources(HLO), steps=2,
                           j_local=100, peaks={"hbm_bytes_per_s": 1e12},
                           step_module="jit_step_fn")
    read = {m: importlib.import_module("metrics." + m).read for m in (
        "model_ms", "compress_ms", "optimizer_ms", "device_idle_share",
        "compress_hbm_roofline")}
    assert read["model_ms"](red) == pytest.approx(20 / 2 / 1e6)
    assert read["compress_ms"](red) == pytest.approx(30 / 2 / 1e6)
    assert read["optimizer_ms"](red) == pytest.approx(14 / 2 / 1e6)
    assert read["device_idle_share"](red) == pytest.approx(20.0)
    # 12 B x 100 entries at 1e12 B/s = 1.2 ns, against 15 ns per step
    assert read["compress_hbm_roofline"](red) == pytest.approx(8.0)
    trace_mfu = importlib.import_module("metrics.trace_mfu").read
    assert trace_mfu(red) is None
    red.update(tokens_per_step=10, flops_per_token=1e3, chips=1,
               peaks={"bf16_flops_per_s": 1e12})
    # 2 steps x 10 tokens x 1e3 FLOPs over 100 ns at 1e12 FLOP/s
    assert trace_mfu(red) == pytest.approx(20.0)


RECORDED = os.path.join(HERE, "recorded_trace.json.gz")


def test_recorded_trace():
    """An excerpt of one fused step traced on a TPU v5e (xlstm-125m,
    seq 1,024; ``source`` in the file): the device ops join the step's
    HLO by name, the named ops land in their layers, self times add up to
    the busy time, and the top-k sort of the sync dominates."""
    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)
    trace = {"devices": {int(k): v for k, v in rec["devices"].items()},
             "host": rec["host"]}
    src = tr.hlo_sources(rec["hlo"])
    for op, layer in rec["expect_layer"].items():
        assert src[op][1] == layer, op
    red = tr.reduce_events(trace, src, steps=rec["steps"],
                           j_local=rec["j_local"], peaks=rec["peaks"],
                           step_module=tr.module_name(rec["hlo"]))
    d = red["devices"][0]
    assert sum(d["layer_ns"].values()) == pytest.approx(d["busy_ns"],
                                                        rel=1e-9)
    assert d["layer_ns"]["sync"] / d["busy_ns"] > 0.5
    assert d["layer_ns"].get("unattributed", 0) / d["busy_ns"] < 0.05
    top = red["breakdown"]["device_ops"][0]
    assert top[0].startswith("sort.7 [sync]") and top[1] > 0.5
