"""Two-sweep fused compression pipeline (DESIGN.md §2.2).

Executes the entire TOP-k / DGC / REGTOP-k compression step in two
O(J) sweeps over the flat gradient — total — instead of the ~8 HBM
passes plus two O(J log k) ``lax.top_k`` sorts the reference path
performs:

- **Sweep 1** reads the dense inputs (g, err_prev [, mom]) exactly once
  and emits ``a`` (the error-compensated gradient) and the selection
  ``score``. ``err_prev`` is the ONE J-sized state vector: the previous
  step's error feedback a^{t-1} * (1 - s^{t-1}), maintained by the O(k)
  scatter-zero that closes each step — no dense mask or ``a_prev`` copy
  exists in the state, and no traversal is ever spent writing next-step
  state. The Pallas kernel additionally accumulates the bit-pattern
  histogram the TPU threshold is derived from, plus per-block amax (a
  diagnostic witness exercised by the kernel tests; the threshold
  itself needs no amax, since bit-pattern bins are scale-free).
- **Sweep 2** compacts per-block top-candidate (value, index) slots; a
  small O(candidates) trim then selects the exact top-k with
  ``lax.top_k`` tie-break semantics (value desc, index asc). REGTOP-k's
  O(k) posterior corrections (Algorithm 1 line 5) are applied in
  candidate space, never densely — ``idx_prev`` doubles as the support
  set for the candidate/support membership test.

Execution strategies (``ops.resolve_strategy``):

- ``xla``:     batched-row ``lax.top_k`` compaction, the default on
  every backend (TPU and CPU).
- ``pallas_interpret``: the Pallas kernels under ``interpret=True`` —
  threshold from the accumulated bit-pattern histogram, compaction via
  per-block slots; used by tests to validate the kernel bodies.
- ``pallas``:  the same kernels compiled natively. The TPU compiler
  refuses them, so this raises ``ops.PALLAS_TPU_REFUSAL`` before
  tracing.

Both strategies verify exactness (the per-row cover witness or
per-block overflow) and fall back to a full ``lax.top_k`` under
``lax.cond`` on the rare adversarial inputs where the compacted
candidate set cannot be proven to cover the true top-k. The trims rank
candidates by (key desc, index asc) explicitly, so ties need no
fallback.

Density allocation (DESIGN.md §2.6, ``core/allocate.py``): with
``SparsifierConfig.allocation`` in {"proportional", "adaptive"} the
budget splits sum(k_l) == k across contiguous segments and the global
trim becomes per-segment trims with per-segment thresholds — same two
sweeps, same O(k) state tail, same k-pair wire format. Contract tests:
tests/test_compress_pipeline.py (exact parity), tests/test_bucketed.py
(bucketing invariance), tests/test_fused_configs.py (capability
matrix), tests/test_state_traffic.py (2-traversal audit),
tests/test_allocate.py (budget conservation + allocated parity).
"""
from repro.kernels.compress.dispatch import (  # noqa: F401
    CompressDispatch,
    dispatch,
    effective_comm_mode,
    hist_capacity,
    packed_len,
)
from repro.kernels.compress.ops import (  # noqa: F401
    fused_compress_arrays,
    sweep_plan,
)
