"""Candidate rows per step whose W slots all hold keys at or above the
top-k threshold, so that the trim cannot prove its cover: the step's
counter ``topk_saturated_rows``, averaged over the traced steps."""
from metrics import counter


def read(red):
    return counter(red, "topk_saturated_rows")
