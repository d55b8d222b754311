"""Per-layer metric readers, one module per metric, found by the metric's
name in BENCHMARK.json. Each has ``read(reduction) -> float | None``, taking
what ``trace_reduce.reduce_events`` returns; None where the trace holds
nothing for it, and the metric is then left out of the result."""


def layer_ms(red, layer):
    """Mean over devices of the layer's device ms per step; None if no op
    of the layer ran."""
    vals = [d["layer_ns"].get(layer, 0) for d in red["devices"].values()]
    if not any(vals):
        return None
    return sum(vals) / len(vals) / red["steps"] / 1e6
