"""Device ms per step of the gradient sync's own ops (repro/core/,
repro/kernels/), collectives excluded: flatten, the compress sweeps,
trim/pack and the scatter-add combine."""
from metrics import layer_ms


def read(red):
    return layer_ms(red, "sync")
