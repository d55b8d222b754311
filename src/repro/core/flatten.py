"""Pytree <-> flat-vector utilities for whole-model sparsification.

The paper treats the model as a single J-dimensional vector (flat-J
sparsification). ``TreeFlattener`` caches the unravel function and leaf
layout so the hot path is a single concatenate / split.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from repro.core import stages


class TreeFlattener:
    """Flattens a gradient pytree to one fp vector and back.

    Built once from an abstract (or concrete) example tree; ``flatten`` and
    ``unflatten`` are then pure jnp ops safe under jit/shard_map.
    """

    def __init__(self, example_tree, dtype=jnp.float32):
        leaves, self.treedef = jax.tree_util.tree_flatten(example_tree)
        self.shapes = [l.shape for l in leaves]
        self.sizes = [int(l.size) for l in leaves]
        self.dtypes = [l.dtype for l in leaves]
        self.offsets = []
        off = 0
        for s in self.sizes:
            self.offsets.append(off)
            off += s
        self.total = off
        self.dtype = dtype

    def flatten(self, tree) -> jnp.ndarray:
        leaves = jax.tree_util.tree_leaves(tree)
        if not leaves:
            return jnp.zeros((0,), self.dtype)
        with stages.scope("flatten"):
            return jnp.concatenate(
                [jnp.ravel(l).astype(self.dtype) for l in leaves])

    def unflatten(self, vec: jnp.ndarray):
        leaves = []
        with stages.scope("unflatten"):
            for off, size, shape, dt in zip(self.offsets, self.sizes,
                                            self.shapes, self.dtypes):
                leaves.append(jax.lax.dynamic_slice_in_dim(
                    vec, off, size).reshape(shape).astype(dt))
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def flatten_segments(self, tree, bounds) -> list:
        """Per-segment flats for streaming compression (DESIGN.md §2.8).

        ``bounds`` is a leaf-aligned contiguous partition of [0, total)
        — (offset, size) pairs such as ``core.allocate.layer_segments``
        over :meth:`layer_bounds`. Returns one flat array per segment,
        each built ONLY from that segment's leaves (no global
        concatenate), so a segment's compression sweep depends on
        nothing produced after its last leaf's gradient — which is what
        lets XLA schedule it behind the remaining backward pass under
        ``overlap="backward"``. ``concatenate(result) == flatten(tree)``
        bitwise."""
        leaves = jax.tree_util.tree_leaves(tree)
        segs, li = [], 0
        for off, size in bounds:
            if li >= len(self.offsets) or self.offsets[li] != off:
                raise ValueError(
                    f"segment offset {off} is not leaf-aligned "
                    f"(leaf offsets: {self.offsets[li:li + 2]}...)")
            parts, have = [], 0
            while have < size:
                parts.append(leaves[li])
                have += self.sizes[li]
                li += 1
            if have != size:
                raise ValueError(
                    f"segment (off={off}, size={size}) cuts inside a leaf")
            with stages.scope("flatten"):
                parts = [jnp.ravel(l).astype(self.dtype) for l in parts]
                segs.append(parts[0] if len(parts) == 1
                            else jnp.concatenate(parts))
        if li != len(leaves):
            raise ValueError("bounds do not cover every leaf")
        return segs

    def layer_bounds(self) -> list:
        """Per-leaf (offset, size) metadata of the flat vector — the
        layer-aligned segmentation source for density allocation:
        ``core.allocate.layer_segments`` groups these into the segment
        bounds the train step hands ``aggregate.sync_gradient`` when
        ``SparsifierConfig.allocation != "global"`` (DESIGN.md §2.6).
        Static Python ints (safe to bake into traced code)."""
        return list(zip(self.offsets, self.sizes))


def bucket_bounds(j: int, num_buckets: int) -> list:
    """Contiguous near-equal partition of [0, j) into buckets.

    Returns [(offset, size), ...] with sizes differing by at most one and
    sum(sizes) == j. The bucketed compression pipeline (DESIGN.md §2.4)
    sweeps each bucket independently and merges their bit-pattern
    histograms into one global threshold, so the partition must be
    deterministic and order-preserving (global index = offset + local).
    num_buckets is clamped to [1, j] (a bucket is never empty).

    The density-allocation subsystem (DESIGN.md §2.6) reuses this exact
    rule for its near-equal segment cut (``core.allocate.segment_bounds``
    delegates here), so segments and buckets coincide whenever
    ``num_segments`` follows ``num_buckets``.
    """
    b = max(1, min(int(num_buckets), max(j, 1)))
    base, rem = divmod(j, b)
    bounds, off = [], 0
    for i in range(b):
        size = base + (1 if i < rem else 0)
        bounds.append((off, size))
        off += size
    return bounds


def tree_size(tree) -> int:
    return sum(int(l.size) for l in jax.tree_util.tree_leaves(tree))


def ravel(tree):
    """One-shot ravel (test convenience)."""
    vec, unravel = ravel_pytree(tree)
    return vec, unravel
