"""Device ms per step of the stage ``sweep``: sweep 1 over the flat
gradient and the per-row top-k compaction into candidates."""
from metrics import stage_ms


def read(red):
    return stage_ms(red, "sweep")
