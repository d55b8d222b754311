"""Device ms per step of ops sourced in repro/models/: the forward and
backward, remat included."""
from metrics import layer_ms


def read(red):
    return layer_ms(red, "model")
