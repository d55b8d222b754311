"""Device ms per step of the stage ``fwd``: the loss inside the step's
``value_and_grad``, the model's forward (remat recompute excluded)."""
from metrics import stage_ms


def read(red):
    return stage_ms(red, "fwd")
