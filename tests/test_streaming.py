"""Backward-overlapped streaming compression contracts (DESIGN.md §2.8).

Pins the claims the ``overlap="backward"`` path exists to make:

- streaming compression (per-segment sweep-1, global trim/pack tail) is
  BITWISE identical to the flat path — selection, packed order,
  ``err_prev``, and the full post-step state — across kinds x
  num_buckets x allocation, whether the flat vector is sliced
  internally or the segments are fed explicitly;
- the streaming program stays within the absolute audited 2-traversal /
  2-write-unit budget (per-segment sweeps fuse; streaming reorders WHEN
  sweeps run, not how many);
- the ``GradientSync`` API: build-once semantics, the
  ``begin()/feed_segment()/finish()`` stream lifecycle and its error
  paths, elastic participation through the stream, and the deprecated
  ``sync_gradient`` shim (bit-identical, warns exactly once).
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SparsifierConfig
from repro.core import aggregate as agg
from repro.core import allocate, flatten, sparsify

J = 4096

KIND_KW = {
    "topk": {},
    "dgc": {"momentum": 0.9},
    "regtopk": {"mu": 0.5},
}


def mkcfg(kind, *, num_buckets=1, allocation="global", **kw):
    kw.setdefault("sparsity", 0.02)
    kw.setdefault("selector", "exact")
    kw.setdefault("comm_mode", "sparse")
    kw.setdefault("pipeline", "fused")
    kw.setdefault("overlap", "backward")
    return SparsifierConfig(kind=kind, num_buckets=num_buckets,
                            allocation=allocation, **KIND_KW[kind], **kw)


def stream_partition(cfg, j):
    """The partition compress resolves for a flat-g streaming call."""
    return allocate.segment_bounds(j, allocate.resolve_num_segments(cfg, j))


def assert_trees_equal(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _grad(seed=0, j=J):
    return jax.random.normal(jax.random.PRNGKey(seed), (j,))


# ---------------------------------------------------------------------------
# bit-parity: streaming == flat, kinds x buckets x allocation
# ---------------------------------------------------------------------------

class TestStreamingCompressParity:
    @pytest.mark.parametrize("kind", ["topk", "dgc", "regtopk"])
    @pytest.mark.parametrize("num_buckets", [1, 8])
    @pytest.mark.parametrize("allocation", ["global", "proportional"])
    def test_bitwise_parity(self, kind, num_buckets, allocation):
        cfg = mkcfg(kind, num_buckets=num_buckets, allocation=allocation)
        cfg_flat = dataclasses.replace(cfg, overlap="none")
        g = _grad()
        base = sparsify.compress(cfg_flat, sparsify.init_state(cfg_flat, J),
                                 g, omega=0.25)

        # flat g under overlap="backward": compress slices internally
        sliced = sparsify.compress(cfg, sparsify.init_state(cfg, J), g,
                                   omega=0.25)
        # explicit per-segment feed (the train step's streaming form)
        bounds = stream_partition(cfg, J)
        assert len(bounds) > 1       # the streaming program actually splits
        segs = [g[off:off + size] for off, size in bounds]
        fed = sparsify.compress(cfg, sparsify.init_state(cfg, J), None,
                                omega=0.25, g_segments=segs)

        for out in (sliced, fed):
            np.testing.assert_array_equal(np.asarray(base.values),
                                          np.asarray(out.values))
            np.testing.assert_array_equal(np.asarray(base.indices),
                                          np.asarray(out.indices))
            assert_trees_equal(base.state, out.state)

    def test_layer_aligned_segments_parity(self):
        """Uneven (layer-like) partitions select identically too —
        partition invariance is not a property of the near-equal cut."""
        cfg = mkcfg("regtopk")
        cfg_flat = dataclasses.replace(cfg, overlap="none")
        g = _grad(3)
        base = sparsify.compress(cfg_flat, sparsify.init_state(cfg_flat, J),
                                 g, omega=0.5)
        bounds = [(0, 100), (100, 1000), (1100, 2996)]
        segs = [g[off:off + size] for off, size in bounds]
        out = sparsify.compress(cfg, sparsify.init_state(cfg, J), None,
                                omega=0.5, g_segments=segs)
        np.testing.assert_array_equal(np.asarray(base.values),
                                      np.asarray(out.values))
        np.testing.assert_array_equal(np.asarray(base.indices),
                                      np.asarray(out.indices))
        assert_trees_equal(base.state, out.state)

    def test_streaming_allocation_needs_matching_seg_bounds(self):
        cfg = mkcfg("topk", allocation="proportional")
        g = _grad()
        segs = [g[:1000], g[1000:]]
        with pytest.raises(ValueError, match="seg_bounds"):
            sparsify.compress(cfg, sparsify.init_state(cfg, J), None,
                              seg_bounds=[(0, 2048), (2048, 2048)],
                              g_segments=segs)

    def test_g_and_segments_exclusive(self):
        cfg = mkcfg("topk")
        g = _grad()
        with pytest.raises(ValueError, match="not both"):
            sparsify.compress(cfg, sparsify.init_state(cfg, J), g,
                              g_segments=[g])
        cfg_flat = dataclasses.replace(cfg, overlap="none")
        with pytest.raises(ValueError, match="overlap"):
            sparsify.compress(cfg_flat, sparsify.init_state(cfg_flat, J),
                              None, g_segments=[g])


# ---------------------------------------------------------------------------
# elastic participation through the stream (DESIGN.md §2.7 x §2.8)
# ---------------------------------------------------------------------------

class TestStreamingElastic:
    @pytest.mark.parametrize("bit", [True, False])
    def test_participation_parity(self, bit):
        """Sitting-out (and participating) workers behave bitwise the
        same whether the gradient streams or not: inert payload, EF
        decay, frozen posterior are all segment-local operations."""
        cfg = mkcfg("regtopk", err_decay=0.9)
        cfg_flat = dataclasses.replace(cfg, overlap="none")
        g = _grad(7)
        p = jnp.asarray(bit)
        st0 = sparsify.init_state(cfg, J)
        st0["err_prev"] = 0.1 * _grad(8)
        base = sparsify.compress(cfg_flat, dict(st0), g, omega=0.25,
                                 participate=p)
        segs = [g[off:off + size] for off, size in stream_partition(cfg, J)]
        out = sparsify.compress(cfg, dict(st0), None, omega=0.25,
                                participate=p, g_segments=segs)
        np.testing.assert_array_equal(np.asarray(base.values),
                                      np.asarray(out.values))
        np.testing.assert_array_equal(np.asarray(base.indices),
                                      np.asarray(out.indices))
        assert_trees_equal(base.state, out.state)

    def test_stream_finish_with_stats_under_shard_map(self):
        """Full GradientSync streaming step (collective included) on a
        1-device mesh: finish(with_stats=True) == the flat __call__ of
        an overlap='none' sync, and the health stats agree."""
        from jax.sharding import PartitionSpec as P
        cfg = mkcfg("topk")
        cfg_flat = dataclasses.replace(cfg, overlap="none")
        mesh = jax.make_mesh((1,), ("data",))
        g = _grad(11)
        bounds = stream_partition(cfg, J)
        st = sparsify.init_state(cfg, J)

        def run(streaming):
            gs = agg.GradientSync(cfg if streaming else cfg_flat, ("data",))

            def f(g, st):
                p = jnp.asarray(True)
                if streaming:
                    stream = gs.begin(st, participate=p)
                    for off, size in bounds:
                        stream.feed_segment(
                            jax.lax.dynamic_slice_in_dim(g, off, size))
                    return stream.finish(with_stats=True)
                return gs(st, g, participate=p, with_stats=True)

            with mesh:
                fn = jax.jit(jax.shard_map(
                    f, mesh=mesh,
                    in_specs=(P("data"),
                              jax.tree_util.tree_map(lambda _: P(), st)),
                    out_specs=(P("data"),
                               jax.tree_util.tree_map(lambda _: P(), st),
                               {"n_active": P(),
                                "dropped_nonfinite": P(),
                                "topk_fallback": P(),
                                "topk_saturated_rows": P()}),
                    check_vma=False))
                return fn(g, dict(st))

        ga_s, st_s, stats_s = run(True)
        ga_f, st_f, stats_f = run(False)
        np.testing.assert_array_equal(np.asarray(ga_s), np.asarray(ga_f))
        assert_trees_equal(st_s, st_f)
        assert float(stats_s["n_active"]) == float(stats_f["n_active"]) == 1.0
        assert float(stats_s["dropped_nonfinite"]) == 0.0


# ---------------------------------------------------------------------------
# audit: streaming stays inside the absolute write budget
# ---------------------------------------------------------------------------

class TestStreamingWriteBudget:
    def test_streaming_compress_budget(self):
        """Per-segment sweep-1 slices are elementwise over their own
        segment and concatenate into the global trim — they must fuse
        into the audited sweep groups, keeping the streaming step at the
        absolute 2.0-traversal / 2.0-write-unit budget (DESIGN.md
        §2.3/§2.8)."""
        from repro.kernels.compress.audit import audit_fn
        j = 1 << 18
        cfg = SparsifierConfig(kind="topk", k=j // 1000, selector="exact",
                               comm_mode="sparse", pipeline="fused",
                               overlap="backward")
        state = sparsify.init_state(cfg, j)
        g = jax.random.normal(jax.random.PRNGKey(0), (j,))

        def f(state, g):
            o = sparsify.compress(cfg, state, g, omega=0.25)
            return tuple(jax.tree_util.tree_leaves(
                [o.state, o.values, o.indices]))

        res = audit_fn(f, state, g, j=j, donate_argnums=(0,))
        assert res["traversals"] <= 2.0, res
        assert res["write_units"] <= 2.0, res


# ---------------------------------------------------------------------------
# GradientSync API surface
# ---------------------------------------------------------------------------

class TestGradientSyncAPI:
    def test_begin_requires_backward_overlap(self):
        gs = agg.GradientSync(mkcfg("topk", overlap="none"), ("data",))
        with pytest.raises(ValueError, match="overlap"):
            gs.begin({"step": jnp.zeros((), jnp.int32)})

    def test_stream_lifecycle_errors(self):
        gs = agg.GradientSync(mkcfg("topk"), ("data",))
        st = sparsify.init_state(gs.cfg, J)
        stream = gs.begin(st)
        with pytest.raises(ValueError, match="no fed segments"):
            stream.finish()
        # a consumed stream refuses further use (single-shot)
        stream2 = gs.begin(st)
        stream2.feed_segment(_grad())
        stream2._done = True
        with pytest.raises(RuntimeError):
            stream2.feed_segment(_grad())
        with pytest.raises(RuntimeError):
            stream2.finish()

    def test_axisless_sync_raises(self):
        gs = agg.GradientSync(mkcfg("topk", overlap="none"), None)
        st = sparsify.init_state(gs.cfg, J)
        with pytest.raises(ValueError, match="round"):
            gs(st, _grad())

    def test_overlap_capability_checked_at_build(self):
        with pytest.raises(ValueError):
            agg.GradientSync(mkcfg("topk", pipeline="reference"), ("data",))

    def test_bucket_preresolution(self):
        cfg = mkcfg("topk", num_buckets=0, overlap="none")
        gs = agg.GradientSync(cfg, ("data",), j=J, n_workers=4)
        assert gs.cfg.num_buckets == sparsify.resolve_num_buckets(cfg, J, 4)
        # without the concrete sizes, resolution is deferred to the step
        assert agg.GradientSync(cfg, ("data",)).cfg.num_buckets == 0

    def test_make_round_fn_needs_workers(self):
        gs = agg.GradientSync(mkcfg("topk", overlap="none"), None)
        with pytest.raises(ValueError, match="n_workers"):
            gs.make_round_fn()

    def test_round_delegates_match(self):
        """sparsify.sparsified_round / make_round_fn are thin delegates
        onto GradientSync — identical outputs, one code path."""
        cfg = mkcfg("regtopk", overlap="none", comm_mode="simulate")
        n = 3
        grads = [_grad(i) for i in range(n)]
        s0 = [sparsify.init_state(cfg, J) for _ in range(n)]
        s1 = [sparsify.init_state(cfg, J) for _ in range(n)]
        a0, n0 = sparsify.sparsified_round(cfg, s0, grads)
        a1, n1 = agg.GradientSync(cfg, None).round(s1, grads)
        np.testing.assert_array_equal(np.asarray(a0), np.asarray(a1))
        assert_trees_equal(n0, n1)


# ---------------------------------------------------------------------------
# flatten_segments
# ---------------------------------------------------------------------------

class TestFlattenSegments:
    def _tree(self):
        k = jax.random.PRNGKey(0)
        return {"w1": jax.random.normal(k, (32, 8)),
                "w2": jax.random.normal(jax.random.fold_in(k, 1), (100,)),
                "w3": jax.random.normal(jax.random.fold_in(k, 2), (6, 6))}

    def test_concat_equals_flatten(self):
        tree = self._tree()
        fl = flatten.TreeFlattener(tree)
        bounds = allocate.layer_segments(fl.layer_bounds(), 2)
        segs = fl.flatten_segments(tree, bounds)
        assert len(segs) == len(bounds)
        np.testing.assert_array_equal(
            np.asarray(jnp.concatenate(segs)), np.asarray(fl.flatten(tree)))

    def test_misaligned_bounds_raise(self):
        tree = self._tree()
        fl = flatten.TreeFlattener(tree)
        with pytest.raises(ValueError, match="leaf-aligned"):
            fl.flatten_segments(tree, [(1, fl.total - 1)])
        with pytest.raises(ValueError, match="inside a leaf"):
            fl.flatten_segments(tree, [(0, 10), (10, fl.total - 10)])
        with pytest.raises(ValueError, match="every leaf"):
            fl.flatten_segments(tree, [(0, 256)])


# ---------------------------------------------------------------------------
# deprecated sync_gradient shim
# ---------------------------------------------------------------------------

class TestSyncGradientShim:
    def test_shim_bit_identical_and_warns_once(self):
        from jax.sharding import PartitionSpec as P
        cfg = mkcfg("regtopk", overlap="none")
        mesh = jax.make_mesh((1,), ("data",))
        g = _grad(5)
        st = sparsify.init_state(cfg, J)

        def run(use_shim):
            gs = agg.GradientSync(cfg, ("data",))

            def f(g, st):
                if use_shim:
                    return agg.sync_gradient(cfg, st, g, ("data",))[0]
                return gs(st, g)[0]

            with mesh:
                fn = jax.shard_map(
                    f, mesh=mesh,
                    in_specs=(P("data"),
                              jax.tree_util.tree_map(lambda _: P(), st)),
                    out_specs=P("data"), check_vma=False)
                return fn(g, dict(st))

        agg._shim_warned = False
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            shim_out = run(True)
            dep = [w for w in rec if issubclass(w.category,
                                                DeprecationWarning)]
            assert len(dep) == 1, [str(w.message) for w in rec]
            assert "GradientSync" in str(dep[0].message)
            # second use: the one-shot marker suppresses the warning
            run(True)
            dep = [w for w in rec if issubclass(w.category,
                                                DeprecationWarning)]
            assert len(dep) == 1
        np.testing.assert_array_equal(np.asarray(shim_out),
                                      np.asarray(run(False)))
