"""The share of traced steps, in %, in which the exact top-k trim fell
back to the full sort of the flat gradient: 100 x the step's counter
``topk_fallback`` (1 when it fell back, averaged over the data ranks),
averaged over the traced steps."""
from metrics import counter


def read(red):
    value = counter(red, "topk_fallback")
    return None if value is None else 100.0 * value
