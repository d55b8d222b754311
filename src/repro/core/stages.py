"""Named stages of the train step, as they appear in the compiled program.

Each stage's code runs inside ``jax.named_scope(PREFIX + stage)``, so every
HLO instruction it lowers to carries the stage in its ``op_name``
metadata, and a profiler trace can be read per stage by joining its
device ops to the step's HLO. Scopes change metadata only: the program is
op-for-op the one built without them.

Under ``jax.value_and_grad`` the forward ops read ``jvp(<PREFIX>fwd)`` and
the backward ops ``transpose(jvp(<PREFIX>fwd))``; :func:`stage_of` reads
the latter as ``bwd``.

- ``fwd``: the loss inside ``train.step``'s ``value_and_grad``
  (``bwd``: its transpose);
- ``flatten`` / ``unflatten``: ``TreeFlattener.flatten`` and
  ``flatten_segments`` / ``TreeFlattener.unflatten``;
- ``sweep``: sweep 1 and the per-row or per-block candidate compaction;
- ``support``: REGTOP-k's posterior keys and the candidate/support merge;
- ``trim``: the top-k over candidates, the fast-path gathers and the
  exactness witnesses;
- ``fallback``: each ``lax.cond`` fallback branch of the trim;
- ``ef_write``: the O(k) error-feedback scatter-zero (and DGC's momentum);
- ``exchange``: the sparse all-gather combine and the dense combines;
- ``posterior``: ``sparsify.observe_aggregate``;
- ``adam`` / ``master_gather``: the ZeRO-1 update / the master all-gather;
- ``step_metrics``: the step's own metric reductions.
"""
from __future__ import annotations

import contextlib
import re

import jax

PREFIX = "stage_"
STAGES = ("fwd", "flatten", "unflatten", "sweep", "support", "trim",
          "fallback", "ef_write", "exchange", "posterior", "adam",
          "master_gather", "step_metrics")

_NAME = re.compile(r"(transpose\()?[^/]*?" + re.escape(PREFIX) + r"([a-z_]+)")


@contextlib.contextmanager
def scope(stage: str):
    """The named scope of ``stage`` (one of ``STAGES``); a context manager
    or a function decorator."""
    if stage not in STAGES:
        raise KeyError(f"unknown stage {stage!r}; known: {STAGES}")
    with jax.named_scope(PREFIX + stage):
        yield


def stage_of(op_name: str):
    """The innermost stage named in an HLO ``op_name``, or None; ``fwd``
    inside a ``transpose(`` is ``bwd``. Each ``/``-separated part may hold
    a stage wrapped in transforms, e.g. ``transpose(jvp(stage_fwd))``."""
    found = None
    for part in op_name.split("/"):
        m = _NAME.match(part)
        if m and m.group(2) in STAGES:
            found = "bwd" if m.group(1) and m.group(2) == "fwd" \
                else m.group(2)
    return found
