"""The reduction from a device trace to per-layer numbers: self time of
nested ops, layer attribution by scope and by source frame, stage
attribution by the program's stage scopes, the busy union and idle gaps,
and the exposed time of collectives."""
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
    # no op names a program stage
    assert d["stage_ns"] == {"unstaged": 84} and d["stage_inner_ns"] == {}
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


STAGED_HLO = """HloModule jit_step_fn, is_scheduled=true

%fused_bwd (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step_fn)/transpose(jvp(stage_fwd))/mul"}
  %mul.2 = f32[8]{0} multiply(%mul.1, %mul.1), metadata={op_name="jit(step_fn)/transpose(jvp(stage_fwd))/mul"}
  ROOT %add.3 = f32[8]{0} add(%mul.1, %mul.2), metadata={op_name="jit(step_fn)/jvp(stage_fwd)/add"}
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%c1, metadata={op_name="jit(step_fn)/jvp(stage_fwd)/dot"}
  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%c2, metadata={op_name="jit(step_fn)/transpose(jvp(stage_fwd))/dot"}
  %sort.3 = f32[8]{0} sort(%p), dimensions={0}, metadata={op_name="jit(step_fn)/stage_sweep/stage_support/sort"}
  %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_bwd
  ROOT %copy.5 = f32[8]{0} copy(%p), metadata={op_name="jit(step_fn)/reshape"}
}
"""


def _staged_trace():
    ops = [
        _ev(0, 10, "fusion.1"),          # fwd
        _ev(10, 30, "fusion.2"),         # bwd, less copy.5 nested in it
        _ev(20, 5, "copy.5"),            # unstaged
        _ev(40, 10, "sort.3"),           # support: the innermost stage
        _ev(50, 10, "fusion.4"),         # bwd only inside: apart
        _ev(60, 10, "fusion.1"),         # another module's: in no stage
        _ev(75, 15, "sort.3"),           # 5 ns inside the window
    ]
    return {"devices": {0: {"ops": ops,
                            "modules": [[0, 60, "jit_step_fn(1)"],
                                        [60, 10, "jit_other(2)"],
                                        [70, 30, "jit_step_fn(1)"]]}},
            "host": [["window", 0, 80]]}


def test_stages_of_a_small_trace():
    """(a) an op's stage is the innermost its own op_name names, fwd
    under transpose( being bwd; (b) one that names none but calls a
    computation of staged ops counts apart, under their most common
    stage; (c) self time inside the window, the step module's ops only."""
    src = tr.hlo_sources(STAGED_HLO)
    assert {op: src[op][4:] for op in src if not op.startswith(
        ("p", "mul", "add"))} == {
        "fusion.1": ("fwd", None), "fusion.2": ("bwd", None),
        "sort.3": ("support", None), "fusion.4": (None, "bwd"),
        "copy.5": (None, None)}
    assert src["add.3"][4] == "fwd"
    red = tr.reduce_events(_staged_trace(), src, steps=1, j_local=8,
                           peaks={}, step_module="jit_step_fn")
    d = red["devices"][0]
    assert d["stage_ns"] == {"fwd": 10, "bwd": 25, "unstaged": 5,
                             "support": 15}
    assert d["stage_inner_ns"] == {"bwd": 10}
    # the layers see every op, the other module's apart
    assert sum(d["layer_ns"].values()) == 75
    assert d["layer_ns"]["other:jit_other"] == 10


def test_stage_and_counter_readers():
    import importlib
    red = tr.reduce_events(_staged_trace(), tr.hlo_sources(STAGED_HLO),
                           steps=5, j_local=8, peaks={},
                           step_module="jit_step_fn")
    read = {m: importlib.import_module("metrics." + m).read for m in (
        "model_fwd_ms", "model_bwd_ms", "sweep_ms", "support_merge_ms",
        "unstaged_ms", "topk_fallback_share", "topk_saturated_rows")}
    assert read["model_fwd_ms"](red) == pytest.approx(10 / 5 / 1e6)
    assert read["model_bwd_ms"](red) == pytest.approx(25 / 5 / 1e6)
    assert read["support_merge_ms"](red) == pytest.approx(15 / 5 / 1e6)
    assert read["unstaged_ms"](red) == pytest.approx(5 / 5 / 1e6)
    # no op ran in the stage; the reduction holds no counters
    for name in ("sweep_ms", "topk_fallback_share", "topk_saturated_rows"):
        assert read[name](red) is None, name
    red["counters"] = {"topk_fallback": 0.5, "topk_saturated_rows": 3.0,
                       "loss": 2.0}
    assert read["topk_fallback_share"](red) == pytest.approx(50.0)
    assert read["topk_saturated_rows"](red) == pytest.approx(3.0)
    red["counters"]["topk_fallback"] = 0.0
    assert read["topk_fallback_share"](red) == 0.0


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
    # the layers as before stages were read; the trace predates the
    # program's stage scopes, so every op of the step is unstaged
    assert d["layer_ns"] == {"model": 254216815.0, "optimizer": 19251448.0,
                             "other:jit_batch": 4188.0, "sync": 868444433.0,
                             "unattributed": 37823345.0}
    assert d["stage_ns"] == {"unstaged": pytest.approx(
        d["busy_ns"] - d["layer_ns"]["other:jit_batch"], rel=1e-9)}
    assert sum(d["layer_ns"].values()) == pytest.approx(d["busy_ns"],
                                                        rel=1e-9)
    assert d["layer_ns"]["sync"] / d["busy_ns"] > 0.5
    assert d["layer_ns"].get("unattributed", 0) / d["busy_ns"] < 0.05
    top = red["breakdown"]["device_ops"][0]
    assert top[0].startswith("sort.7 [sync]") and top[1] > 0.5
