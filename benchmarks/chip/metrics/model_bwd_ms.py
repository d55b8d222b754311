"""Device ms per step of the stage ``bwd``: the transpose of ``fwd``, the
model's backward with the remat recompute."""
from metrics import stage_ms


def read(red):
    return stage_ms(red, "bwd")
