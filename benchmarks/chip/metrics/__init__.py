"""Per-layer metric readers, one module per metric, found by the metric's
name in BENCHMARK.json. Each has ``read(reduction) -> float | None``, taking
what ``trace_reduce.reduce_events`` returns, with the step's ``counters``
that ``run.traced`` adds; None where the trace holds nothing for it, and
the metric is then left out of the result."""


def _per_step_ms(red, table, name):
    vals = [d.get(table, {}).get(name, 0) for d in red["devices"].values()]
    if not any(vals):
        return None
    return sum(vals) / len(vals) / red["steps"] / 1e6


def layer_ms(red, layer):
    """Mean over devices of the layer's device ms per step; None if no op
    of the layer ran."""
    return _per_step_ms(red, "layer_ns", layer)


def stage_ms(red, stage):
    """Mean over devices of the program stage's device ms per step
    (``repro.core.stages``; ``unstaged`` for the step's ops that name
    none); None if no op of the stage ran, or the reduction has no
    stages."""
    return _per_step_ms(red, "stage_ns", stage)


def counter(red, name):
    """The step's metric ``name``, averaged over the traced steps; None if
    the reduction has no such counter."""
    return red.get("counters", {}).get(name)
