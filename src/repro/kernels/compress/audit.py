"""Traced-shape audit: count O(J) HBM traversals of a jitted function.

Walks the jaxpr of a function and models XLA's loop fusion to estimate
how many full J-sized streaming passes over HBM the computation
performs — and, since the write-traffic PR, how many J-sized WRITES it
lands. Used by the sweep-count regression test and the compression
benchmark, so the two-sweep pipeline's pass count is measured, not
asserted by hand.

Model (intentionally simple, deterministic, and version-stable):

- *Elementwise* equations (adds, multiplies, selects, converts, pads,
  concats, broadcasts, ...) over sizable operands fuse into connected
  groups; one group = one streaming traversal, regardless of how many
  sizable arrays it reads or writes (``traversals``), with the bytes it
  touches accounted separately (``read_units`` — J-fp32-equivalents of
  distinct sizable group inputs).
- *Barrier* equations — sort/top_k, reductions, cumsums, scans,
  pallas_call — each count as one traversal and read their sizable
  operands.
- Scatter equations with small (O(k)) updates and gather equations with
  small outputs are O(k) random accesses, not streaming passes.
- ``cond`` contributes the *minimum* over its branches: the fused
  pipeline's exact-top-k fallback branch exists for adversarial inputs
  only, and the audit measures the steady-state path.

Write accounting (``write_units``, J-fp32-equivalents of streamed
writes — the half of a streaming kernel's HBM traffic the read-only
audit used to leave invisible):

- An elementwise group writes each sizable array it produces that
  ESCAPES the group — is consumed by a barrier/scatter/gather/cond or
  returned from the jaxpr. Fusion-internal temporaries stay in
  registers and cost nothing, mirroring the read model.
- Barriers and sizable gathers write their sizable outputs.
- Scatters with O(k) updates are O(k) random writes — free — UNLESS the
  scattered-into operand is an UNDONATED function input: XLA cannot
  mutate a caller-visible argument in place, so the scatter pays a
  defensive O(J) copy (billed as its write volume). Donated inputs
  (``audit_fn(..., donate_argnums=...)``, matching
  ``jax.jit(donate_argnums=...)``) and intermediates update in place
  and stay free — which is exactly the err_prev/mom in-place update the
  donated train step relies on.
- Pass-through outputs (a returned input, or a view of one) were never
  produced and cost nothing.

Traversals are **J-equivalents** (DESIGN.md §2.3): each group/barrier is
weighted by its largest operand's size relative to the threshold ``j``,
so the bucketed pipeline's num_buckets sweeps of J/num_buckets elements
correctly total ~1 traversal instead of either vanishing below a "big"
cutoff or counting num_buckets times — and their partial writes sum the
same way (bytes-weighted). Gathers are weighted by their OUTPUT size
(random access, not a stream over the operand). Arrays smaller than
max(1024, j/16) stay free (O(k) packing fix-ups, per-row candidate
slots, O(candidates) trim arrays); the audit therefore resolves
bucketings up to ~16 buckets.
"""
from __future__ import annotations

from collections import defaultdict

import jax
import jax.extend.core
import numpy as np

_ELEMENTWISE = {
    "add", "sub", "mul", "div", "max", "min", "pow", "rem", "and", "or",
    "xor", "not", "neg", "sign", "abs", "exp", "log", "tanh", "sqrt",
    "rsqrt", "integer_pow", "select_n", "convert_element_type", "clamp",
    "eq", "ne", "ge", "gt", "le", "lt", "stop_gradient", "pad",
    "concatenate", "broadcast_in_dim", "iota", "bitcast_convert_type",
    "shift_right_logical", "shift_left", "is_finite", "square", "copy",
    "nextafter", "floor", "ceil", "round",
}
_FREE = {"reshape", "squeeze", "expand_dims", "transpose", "rev",
         "slice", "dynamic_slice"}
_BARRIERS = {
    "sort", "top_k", "approx_top_k", "reduce_sum", "reduce_max",
    "reduce_min", "reduce_prod", "reduce_and", "reduce_or", "argmax",
    "argmin", "cumsum", "cumlogsumexp", "cummax", "cummin", "cumprod",
    "scan", "while", "pallas_call", "reduce_precision", "clz",
}


def _size(var) -> int:
    try:
        return int(np.prod(var.aval.shape)) if var.aval.shape else 1
    except Exception:
        return 1


def _bytes(var) -> int:
    try:
        return _size(var) * var.aval.dtype.itemsize
    except Exception:
        return 0


class _UnionFind:
    def __init__(self):
        self.parent = {}

    def find(self, x):
        self.parent.setdefault(x, x)
        while self.parent[x] != x:
            self.parent[x] = self.parent[self.parent[x]]
            x = self.parent[x]
        return x

    def union(self, a, b):
        ra, rb = self.find(a), self.find(b)
        if ra != rb:
            self.parent[rb] = ra


def audit_jaxpr(jaxpr, j: int, unit_bytes: int = 4,
                donated=frozenset()) -> dict:
    """Count traversals/read-units/write-units of a ClosedJaxpr for
    threshold size j.

    Returns {"traversals": float, "read_units": float,
    "write_units": float}: traversals are J-equivalent streaming passes
    (a pass over J/B elements weighs 1/B); read_units is sizable-input
    bytes / (j * unit_bytes) — J-fp32-equivalents of streamed reads;
    write_units the same for streamed writes (see module docstring for
    what counts as a write). ``donated`` is a set of input vars whose
    buffers the caller donates (in-place scatter updates of them are
    free; undonated inputs pay a defensive copy).
    """
    floor = max(1024, j // 16)
    sizable = lambda v: _size(v) >= floor
    frac = lambda v: _size(v) / float(j)
    uf = _UnionFind()
    group_of_var = {}
    barrier_weight = 0.0
    read_bytes = 0.0
    write_bytes = 0.0
    produced = set()
    escaped = set()
    # alias root: tracks which vars are (views of) function inputs, for
    # the donated-in-place vs defensive-copy scatter distinction
    invars = set(jaxpr.jaxpr.invars) | set(jaxpr.jaxpr.constvars)
    alias_root = {v: v for v in invars}

    def _mark_escapes(eqn):
        for v in eqn.invars:
            if hasattr(v, "aval") and sizable(v) and v in produced:
                escaped.add(v)

    def handle(eqns):
        nonlocal barrier_weight, read_bytes, write_bytes
        for eqn in eqns:
            prim = eqn.primitive.name
            if prim in ("jit", "pjit", "closed_call", "custom_jvp_call",
                        "custom_vjp_call", "custom_vjp_call_jaxpr",
                        "remat", "checkpoint"):
                # the sub-jaxpr's vars are disjoint from the outer ones,
                # so a produced array feeding the call crosses an HBM
                # boundary in this model (groups already break here)
                _mark_escapes(eqn)
                sub = eqn.params.get("jaxpr")
                if sub is not None:
                    handle(sub.jaxpr.eqns if hasattr(sub, "jaxpr")
                           else sub.eqns)
                continue
            if prim == "cond":
                # min over branches (steady-state path; the exact-top-k
                # fallback branch is adversarial-input-only)
                _mark_escapes(eqn)
                results = []
                for br in eqn.params["branches"]:
                    # thread donation through: a branch invar aliases the
                    # outer operand it binds, so a donated (or view-of-
                    # donated) operand stays donated inside the branch
                    don_br = {bv for bv, ov in zip(br.jaxpr.invars,
                                                   eqn.invars[1:])
                              if not isinstance(ov, jax.extend.core.Literal)
                              and alias_root.get(ov) in donated}
                    results.append(audit_jaxpr(br, j, unit_bytes,
                                               donated=frozenset(don_br)))
                best = min(results, key=lambda r: (r["traversals"],
                                                   r["read_units"],
                                                   r["write_units"]))
                barrier_weight += best["traversals"]
                read_bytes += best["read_units"] * j * unit_bytes
                write_bytes += best["write_units"] * j * unit_bytes
                continue
            big_in = [v for v in eqn.invars
                      if hasattr(v, "aval") and sizable(v)]
            big_out = [v for v in eqn.outvars if sizable(v)]
            if not big_in and not big_out:
                continue
            weight = max(frac(v) for v in big_in + big_out)
            if prim in _FREE:
                # view-ish: propagate group membership through; a view of
                # a produced array is itself produced (its bytes were
                # already written in-stream — counting the view as an
                # external group input would double-bill bucket slices)
                for vo in big_out:
                    for vi in big_in:
                        if vi in group_of_var:
                            group_of_var[vo] = group_of_var[vi]
                        if vi in produced:
                            produced.add(vo)
                        if vi in alias_root:
                            alias_root[vo] = alias_root[vi]
                continue
            if prim == "gather":
                _mark_escapes(eqn)
                if not big_out:
                    continue                   # O(k) random reads
                # random access costs its output volume, not a stream
                # over the (possibly J-sized) operand
                barrier_weight += max(frac(v) for v in big_out)
                read_bytes += sum(_bytes(v) for v in big_out)
                write_bytes += sum(_bytes(v) for v in big_out)
                continue
            if prim == "scatter" or prim.startswith("scatter-"):
                _mark_escapes(eqn)
                upd = eqn.invars[-1] if eqn.invars else None
                if upd is not None and not sizable(upd):
                    # O(k) random writes — free in place. The operand
                    # buffer must exist, though: an UNDONATED function
                    # input cannot be mutated, so XLA copies it first
                    # (an O(J) write the donated path never pays).
                    op = eqn.invars[0] if eqn.invars else None
                    root = alias_root.get(op)
                    if (root is not None and root not in donated
                            and op is not None and sizable(op)):
                        write_bytes += _bytes(op)
                    continue
                barrier_weight += weight
                read_bytes += sum(_bytes(v) for v in big_in)
                write_bytes += sum(_bytes(v) for v in big_out)
                continue
            if prim in _ELEMENTWISE:
                key = ("eqn", id(eqn))
                uf.find(key)
                for v in big_in + big_out:
                    if v in group_of_var:
                        uf.union(key, group_of_var[v])
                    group_of_var[v] = key
                for v in big_out:
                    produced.add(v)
                continue
            # everything else (sorts, reductions, pallas, unknown prims
            # touching sizable data) is a barrier traversal weighted by
            # its largest operand
            _mark_escapes(eqn)
            barrier_weight += weight
            read_bytes += sum(_bytes(v) for v in big_in)
            write_bytes += sum(_bytes(v) for v in big_out)

    handle(jaxpr.jaxpr.eqns)

    # group accounting: each fused elementwise group = 1 J-equivalent
    # traversal weighted by its largest array, reading its distinct
    # sizable external inputs and writing the produced arrays that
    # escape the fused loop (barrier/scatter/gather consumers, or the
    # jaxpr outputs)
    outvars = {v for v in jaxpr.jaxpr.outvars if hasattr(v, "aval")}
    groups = defaultdict(set)
    for v, key in group_of_var.items():
        groups[uf.find(key)].add(v)
    group_weight = 0.0
    for root, vars_ in groups.items():
        group_weight += max(frac(v) for v in vars_)
        for v in vars_:
            if v not in produced:              # external sizable input
                read_bytes += _bytes(v)
            elif v in escaped or v in outvars:
                write_bytes += _bytes(v)
    return {"traversals": round(barrier_weight + group_weight, 3),
            "read_units": round(read_bytes / float(j * unit_bytes), 3),
            "write_units": round(write_bytes / float(j * unit_bytes), 3)}


def audit_fn(fn, *args, j: int, donate_argnums=(), **kwargs) -> dict:
    """Audit a python function by tracing it with jax.make_jaxpr.

    ``donate_argnums`` mirrors ``jax.jit``'s: the flattened leaves of
    those positional args are treated as donated buffers, so O(k)
    scatter updates INTO them audit as free in-place writes instead of
    paying the undonated defensive copy.
    """
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    donated = set()
    if donate_argnums:
        donate_argnums = set(donate_argnums)
        flat_invars = list(jaxpr.jaxpr.invars)
        pos = 0
        for i, a in enumerate(args):
            n = len(jax.tree_util.tree_leaves(a))
            if i in donate_argnums:
                donated.update(flat_invars[pos:pos + n])
            pos += n
    return audit_jaxpr(jaxpr, j, donated=frozenset(donated))
