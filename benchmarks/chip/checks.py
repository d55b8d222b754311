"""The numbers that decide ``correct``, each against its limit.

- ``grad1_support_miss``: the share of the entries of the reference's
  first combined gradient (the gradient Adam is given at step one) that
  the program's leaves out. Rounding moves only entries near the top-k
  threshold across it.
- ``grad1_value_err``: over the entries both select, the median relative
  gap between the program's first gradient and the reference's.
- ``change_leaf_gap``: per parameter leaf, the gap between the norm of
  the leaf's change over the check steps in the program and in the
  reference, over the larger of the reference's norm of that leaf and
  the median of its norms over the leaves it moves; the worst leaf.
- ``change_support_miss``: the share of the entries that the reference
  moved over the check steps that the program left unmoved.
- ``change_dir_gap``: per leaf, over the entries that both moved, the
  norm of (program change - reference change) over the reference
  change's, floored as in ``change_leaf_gap``; the worst leaf. It has a
  sign: an update applied the wrong way reads 2 where the norms agree.

Leaves whose dense first gradient in the reference is under a
thousandth of the median leaf's take no part in the last three: Adam
moves them by round-off alone.

``loss_gap``, the largest relative gap of the check steps' losses, is
reported beside them and not judged: at initialisation the loss is
near ln(vocab) whatever the precision, and no fault or control moves it
three times past the program's own readings (PERF.md).
"""
import math

import numpy as np

NAMES = ("grad1_support_miss", "grad1_value_err", "change_leaf_gap",
         "change_support_miss", "change_dir_gap")
QUIET = 1e-3


def _floored(gap, ref, keep):
    """Worst over the kept leaves of gap / max(ref, median moved ref)."""
    gap = np.asarray(gap, np.float64)[keep]
    r = np.asarray(ref, np.float64)[keep]
    moved = r[r > 0]
    if not moved.size:
        return math.inf
    den = np.maximum(r, np.median(moved))
    return float(np.max(gap / den))


def sparse_gaps(prog, ref):
    """(support miss, median value error) of the program's sparse vector
    (ascending indices, values) against the reference's."""
    ip, vp = (np.asarray(x) for x in prog)
    ir, vr = (np.asarray(x, np.float64) for x in ref)
    if ir.size == 0:
        return math.inf, math.inf
    pos = np.clip(np.searchsorted(ip, ir), 0, max(ip.size - 1, 0))
    hit = (ip[pos] == ir) if ip.size else np.zeros(ir.shape, bool)
    p_at = np.where(hit, vp[pos] if ip.size else 0.0, 0.0)
    hit &= p_at != 0
    both = hit & (vr != 0)
    err = (float(np.median(np.abs(p_at[both] - vr[both]) / np.abs(vr[both])))
           if both.any() else math.inf)
    return float(1.0 - hit.mean()), err


def numbers(prog, ref):
    """The compared numbers of ``prog`` against ``ref``; ``prog["change"]``
    holds its parameter change read against the reference's
    (``reference.train.change_readings``)."""
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    dense = np.asarray(ref["dense_g1_leaf_norms"], np.float64)
    keep = dense >= QUIET * np.median(dense)
    ch = {k: np.asarray(v, np.float64) for k, v in prog["change"].items()}
    miss, err = sparse_gaps(prog["g1"], ref["g1"])
    moved = ch["ref_moved"][keep].sum()
    return {
        "grad1_support_miss": miss,
        "grad1_value_err": err,
        "change_leaf_gap": _floored(np.abs(ch["norm"] - ch["ref_norm"]),
                                    ch["ref_norm"], keep),
        "change_support_miss": (float(ch["missed"][keep].sum() / moved)
                                if moved else math.inf),
        "change_dir_gap": _floored(ch["both_gap"], ch["both_ref"], keep),
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
    }


def judge(values, limits):
    """{name: {"value", "limit"}} and whether every value is finite and
    within its limit."""
    out, ok = {}, True
    for name in NAMES:
        v, lim = values[name], limits[name]
        out[name] = {"value": v, "limit": lim}
        ok = ok and math.isfinite(v) and v <= lim
    return out, ok
