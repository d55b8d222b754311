"""Device ms per step of the stage ``support``: REGTOP-k's posterior keys,
the sort of the previous support and its ``searchsorted`` merge with the
candidates."""
from metrics import stage_ms


def read(red):
    return stage_ms(red, "support")
