"""Two-sweep fused compression pipeline (kernels/compress) vs the dense
reference path: parity matrix, kernel-body checks (interpret mode),
adversarial tie/overflow fallbacks, and the O(J) sweep-count regression
(DESIGN.md §2.2)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SparsifierConfig
from repro.core import sparsify
from repro.kernels.compress import kernel as ck
from repro.kernels.compress import ops as cops
from repro.kernels.compress import ref as cref
from repro.kernels.compress.audit import audit_fn
from repro.kernels.compress.ops import sweep_plan


def _pair(kind, **kw):
    kw.setdefault("selector", "exact")
    ref = SparsifierConfig(kind=kind, **kw)
    return ref, dataclasses.replace(ref, pipeline="fused")


def _roundtrip(cfg_r, cfg_f, j, steps=4, seed=0, omega=0.25):
    """Run both pipelines side by side; assert support + value parity at
    every step (including the t=0 plain-top-k branch)."""
    key = jax.random.PRNGKey(seed)
    sr = sparsify.init_state(cfg_r, j)
    sf = sparsify.init_state(cfg_f, j)
    for t in range(steps):
        g = jax.random.normal(jax.random.fold_in(key, t), (j,))
        orr = sparsify.compress(cfg_r, sr, g, omega=omega)
        off = sparsify.compress(cfg_f, sf, g, omega=omega)
        # fused carries no dense mask; both reconstruct via the one
        # shared O(k) helper (no dtype branching)
        assert (sparsify.dense_mask(orr, j) ==
                sparsify.dense_mask(off, j)).all(), f"mask diverged at t={t}"
        gr = np.asarray(orr.ghat)
        gf = np.asarray(sparsify.dense_ghat(off, j))
        np.testing.assert_allclose(gr, gf, rtol=1e-5, atol=1e-6)
        # error feedback parity: fused err_prev is the ONE state vector,
        # maintained by the O(k) scatter-zero — bit-identical, not close
        np.testing.assert_array_equal(np.asarray(orr.state["err"]),
                                      np.asarray(off.state["err_prev"]))
        if orr.values is not None:
            assert set(np.asarray(orr.indices).tolist()) == \
                set(np.asarray(off.indices).tolist())
        agg = omega * gr
        sr = sparsify.observe_aggregate(cfg_r, orr.state, jnp.asarray(agg))
        sf = sparsify.observe_aggregate(cfg_f, off.state, jnp.asarray(agg))


class TestParityMatrix:
    @pytest.mark.parametrize("kind", ["topk", "dgc", "regtopk"])
    @pytest.mark.parametrize("comm_mode", ["simulate", "sparse"])
    def test_fused_matches_reference(self, kind, comm_mode):
        cfg_r, cfg_f = _pair(kind, sparsity=0.02, mu=0.5,
                             comm_mode=comm_mode)
        _roundtrip(cfg_r, cfg_f, j=12_345)

    def test_histogram_selector_is_fused_with_contract(self):
        """selector="histogram" is served by the fused pipeline since the
        capability-dispatch PR: threshold selection at the sweep-1
        bit-pattern bin edge, count in [k, hist_capacity]. The full
        contract suite lives in tests/test_fused_configs.py."""
        from repro.kernels.compress.dispatch import dispatch, hist_capacity
        cfg_r, cfg_f = _pair("topk", sparsity=0.02, selector="histogram")
        assert dispatch(cfg_f).path == "fused"
        assert dispatch(cfg_r).path == "reference"
        j = 20_000
        k = sparsify.resolve_k(cfg_f, j)
        st_f = sparsify.init_state(cfg_f, j)
        assert "err_prev" in st_f and "err" not in st_f   # fused layout
        assert "s_prev" not in st_f                       # no dense mask state
        g = jax.random.normal(jax.random.PRNGKey(11), (j,))
        off = sparsify.compress(cfg_f, st_f, g)
        n = int(sparsify.dense_mask(off, j).sum())
        assert k <= n <= hist_capacity(k, j)
        assert n == int(off.count)
        # the reference histogram selector keeps its own (linear-bin)
        # over-selection; both are supersets of the exact top-k
        orr = sparsify.compress(cfg_r, sparsify.init_state(cfg_r, j), g)
        assert int(orr.mask.sum()) >= k

    def test_bf16_ef_dtype_is_fused(self):
        """ef_dtype="bfloat16" takes the fused path: bf16 J-sized state,
        fp32 in-register sweep math (tolerance contract vs the fp32
        reference in tests/test_fused_configs.py)."""
        _, cfg_f = _pair("regtopk", sparsity=0.02, mu=0.5,
                         ef_dtype="bfloat16")
        j = 2_000
        st_f = sparsify.init_state(cfg_f, j)
        assert "err_prev" in st_f and "err" not in st_f   # fused layout
        assert st_f["err_prev"].dtype == jnp.bfloat16
        out = sparsify.compress(cfg_f, st_f, jax.random.normal(
            jax.random.PRNGKey(1), (j,)))
        assert int(sparsify.dense_mask(out, j).sum()) == \
            sparsify.resolve_k(cfg_f, j)

    @pytest.mark.parametrize("kind", ["randk", "thresholdk"])
    def test_randk_thresholdk_fused_parity(self, kind):
        """randk/thresholdk are fused since the capability-dispatch PR and
        must match the reference path (identical sampler / identical
        exact selection) — and both now pack (values, indices)."""
        cfg_r, cfg_f = _pair(kind, sparsity=0.05)
        j = 2_000
        key = jax.random.PRNGKey(1)
        sr = sparsify.init_state(cfg_r, j)
        sf = sparsify.init_state(cfg_f, j)
        assert "err_prev" in sf and "err" not in sf     # fused layout
        g = jax.random.normal(key, (j,))
        orr = sparsify.compress(cfg_r, sr, g, key=key)
        off = sparsify.compress(cfg_f, sf, g, key=key)
        assert (sparsify.dense_mask(orr, j) ==
                sparsify.dense_mask(off, j)).all()
        assert orr.values is not None and off.values is not None
        if kind == "randk":
            # shared sampler => identical index STREAM, not just support
            np.testing.assert_array_equal(np.asarray(orr.indices),
                                          np.asarray(off.indices))
        else:
            assert set(np.asarray(orr.indices).tolist()) == \
                set(np.asarray(off.indices).tolist())

    def test_sparse_comm_skips_dense_ghat(self):
        _, cfg_f = _pair("regtopk", sparsity=0.01, mu=0.5,
                         comm_mode="sparse")
        j = 8_192
        st = sparsify.init_state(cfg_f, j)
        out = sparsify.compress(cfg_f, st, jnp.ones((j,)))
        assert out.ghat is None
        assert out.values.shape[0] == sparsify.resolve_k(cfg_f, j)
        dense = sparsify.dense_ghat(out, j)
        assert int((dense != 0).sum()) == out.values.shape[0]

    def test_mu_small_reduces_to_topk(self):
        """mu -> 0 regularizer => fused REGTOP-k == fused TOP-k masks."""
        _, cfg_t = _pair("topk", k=15)
        _, cfg_r = _pair("regtopk", k=15, mu=1e-6, Q=0.0)
        j = 3_000
        st_t = sparsify.init_state(cfg_t, j)
        st_r = sparsify.init_state(cfg_r, j)
        key = jax.random.PRNGKey(7)
        for t in range(4):
            g = jax.random.normal(jax.random.fold_in(key, t), (j,))
            ot = sparsify.compress(cfg_t, st_t, g)
            orr = sparsify.compress(cfg_r, st_r, g)
            assert (sparsify.dense_mask(ot, j) ==
                    sparsify.dense_mask(orr, j)).all(), f"t={t}"
            agg = 0.5 * (sparsify.dense_ghat(ot, j) +
                         sparsify.dense_ghat(orr, j))
            st_t = sparsify.observe_aggregate(cfg_t, ot.state, agg)
            st_r = sparsify.observe_aggregate(cfg_r, orr.state, agg)


class TestAdversarial:
    """Tie and fixed-k compaction overflow cases route through the exact
    fallback and must still match the reference selector bit-for-bit."""

    @pytest.mark.parametrize("kind", ["topk", "regtopk"])
    @pytest.mark.parametrize("gname,gfn", [
        ("all-equal", lambda j: jnp.ones((j,))),          # compaction overflow
        ("all-zero", lambda j: jnp.zeros((j,))),
        ("boundary-ties", lambda j: jnp.where(
            jnp.arange(j) % 11 == 0, 2.0, 1.0)),          # ties at tau
        ("few-distinct", lambda j: (jnp.arange(j) % 3).astype(jnp.float32)),
    ])
    def test_degenerate_inputs(self, kind, gname, gfn):
        cfg_r, cfg_f = _pair(kind, k=64, mu=0.5)
        j = 6_000
        g = gfn(j)
        _roundtrip_static(cfg_r, cfg_f, g, steps=3)

    def test_tiny_and_edge_k(self):
        for j, k in ((64, 1), (100, 100), (257, 256)):
            cfg_r, cfg_f = _pair("regtopk", k=k, mu=0.5)
            _roundtrip(cfg_r, cfg_f, j=j, steps=3, seed=j)

    @pytest.mark.parametrize("pipeline", ["reference", "fused"])
    def test_tiny_negative_accumulator_one_worker_is_topk(self, pipeline):
        """With one worker and Q = 0 every REGTOP-k score is a * tanh(1/mu),
        so the selection is TOP-k's. Entries whose accumulator lands in
        [-1e-12, 0) (off the previous support: entry 0; on it: entry 15)
        must not win through a zero safe divisor's NaN score."""
        j, k = 16, 2
        cfg_t = SparsifierConfig(kind="topk", k=k, pipeline=pipeline)
        cfg_r = SparsifierConfig(kind="regtopk", k=k, mu=0.5, Q=0.0,
                                 pipeline=pipeline)
        st = sparsify.init_state(cfg_r, j)
        g0 = (0.1 * jnp.arange(1, j + 1, dtype=jnp.float32)).at[0].set(1e-13)
        out = sparsify.compress(cfg_r, st, g0)
        st = sparsify.observe_aggregate(cfg_r, out.state,
                                        sparsify.dense_ghat(out, j))
        err = g0 * (1.0 - sparsify.dense_mask(out, j))
        a1 = (jnp.zeros((j,)).at[0].set(-5e-13).at[15].set(-5e-13)
              .at[3].set(0.9).at[5].set(0.8))
        g1 = a1 - err
        assert float((err + g1)[0]) < 0 and float((err + g1)[15]) < 0
        out_r = sparsify.compress(cfg_r, st, g1)
        out_t = sparsify.compress(cfg_t, sparsify.init_state(cfg_t, j),
                                  err + g1)
        sel = np.flatnonzero(np.asarray(sparsify.dense_mask(out_r, j)))
        assert sel.tolist() == [3, 5]
        assert (sparsify.dense_mask(out_r, j) ==
                sparsify.dense_mask(out_t, j)).all()


def _roundtrip_static(cfg_r, cfg_f, g, steps=3, omega=0.5):
    j = g.shape[0]
    sr = sparsify.init_state(cfg_r, j)
    sf = sparsify.init_state(cfg_f, j)
    for t in range(steps):
        orr = sparsify.compress(cfg_r, sr, g, omega=omega)
        off = sparsify.compress(cfg_f, sf, g, omega=omega)
        assert (sparsify.dense_mask(orr, j) ==
                sparsify.dense_mask(off, j)).all(), f"t={t}"
        np.testing.assert_allclose(
            np.asarray(orr.ghat), np.asarray(sparsify.dense_ghat(off, j)),
            rtol=1e-5, atol=1e-6)
        agg = omega * orr.ghat
        sr = sparsify.observe_aggregate(cfg_r, orr.state, agg)
        sf = sparsify.observe_aggregate(cfg_f, off.state, agg)


class TestPallasKernels:
    """Kernel bodies under interpret=True vs the pure-jnp oracle."""

    def test_sweep1_plain(self):
        j = 3 * ck.BLOCK
        key = jax.random.PRNGKey(0)
        ks = jax.random.split(key, 3)
        g = jax.random.normal(ks[0], (j,))
        # err_prev: the ONE state vector (zero at the previous support)
        err_prev = jax.random.normal(ks[1], (j,)) * (
            jax.random.uniform(ks[2], (j,)) >= 0.1)
        a, score, _mom, amax, hist = ck.sweep1_pallas(
            g, err_prev, 1.0, mode="plain", interpret=True)
        a_ref, score_ref, _ = cref.dense_scores_ref(g, err_prev,
                                                    1, kind="topk")
        np.testing.assert_allclose(np.asarray(a), np.asarray(a_ref),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(score), np.asarray(score_ref),
                                   rtol=1e-6, atol=1e-6)
        # per-block amax + accumulated bit-pattern histogram
        keys = np.abs(np.asarray(score_ref)).reshape(-1, ck.BLOCK)
        np.testing.assert_allclose(np.asarray(amax), keys.max(axis=1),
                                   rtol=1e-6)
        assert int(hist.sum()) == j
        bins = np.asarray(ck.bit_bin(jnp.abs(score_ref)))
        np.testing.assert_array_equal(np.asarray(hist),
                                      np.bincount(bins, minlength=ck.BINS))

    def test_sweep1_dgc_momentum(self):
        j = ck.BLOCK
        key = jax.random.PRNGKey(1)
        g = jax.random.normal(key, (j,))
        mom = jax.random.normal(jax.random.fold_in(key, 1), (j,))
        a, _score, mom_out, _amax, _hist = ck.sweep1_pallas(
            g, jnp.zeros((j,)), 1.0, mode="dgc",
            momentum=0.9, mom=mom, interpret=True)
        np.testing.assert_allclose(np.asarray(mom_out),
                                   np.asarray(0.9 * mom + g),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(a),
                                   np.asarray(0.9 * mom + g),
                                   rtol=1e-6, atol=1e-6)

    def test_threshold_brackets_kth(self):
        j = 2 * ck.BLOCK
        x = jax.random.normal(jax.random.PRNGKey(2), (j,))
        keys = jnp.abs(x)
        hist = jnp.asarray(np.bincount(np.asarray(ck.bit_bin(keys)),
                                       minlength=ck.BINS), jnp.int32)
        for k in (1, 17, 500):
            tau = float(ck.threshold_from_hist(hist, k))
            kth = float(jnp.sort(keys)[-k])
            assert tau <= kth + 1e-7
            assert int((keys >= tau).sum()) >= k

    def test_sweep2_compaction(self):
        j = 4 * ck.BLOCK
        x = jax.random.normal(jax.random.PRNGKey(3), (j,))
        score = x
        tau = float(jnp.sort(jnp.abs(x))[-100])
        maxpb = 64
        mask, vals, idx, cnts = ck.sweep2_pallas(score, tau, maxpb=maxpb,
                                                 interpret=True)
        keys = np.abs(np.asarray(score))
        expect = keys >= tau
        np.testing.assert_array_equal(np.asarray(mask), expect.astype(np.uint8))
        assert np.asarray(cnts).sum() == expect.sum()
        valid = np.asarray(idx) != ck.INVALID_IDX
        got = set(np.asarray(idx)[valid].tolist())
        assert got == set(np.nonzero(expect)[0].tolist())
        np.testing.assert_allclose(np.sort(np.asarray(vals)[valid]),
                                   np.sort(keys[expect]), rtol=1e-6)

    def test_pallas_strategy_full_parity(self):
        """fused_compress_arrays(strategy="pallas_interpret") == reference."""
        j, k = 2 * ck.BLOCK, 37
        cfg_r = SparsifierConfig(kind="regtopk", k=k, mu=0.5,
                                 selector="exact")
        sr = sparsify.init_state(cfg_r, j)
        err_prev = jnp.zeros((j,))
        idx_prev = jnp.zeros((k,), jnp.uint32)
        aps = jnp.zeros((k,))
        gps = jnp.zeros((k,))
        step = jnp.zeros((), jnp.int32)
        key = jax.random.PRNGKey(5)
        for t in range(3):
            g = jax.random.normal(jax.random.fold_in(key, t), (j,))
            orr = sparsify.compress(cfg_r, sr, g, omega=0.25)
            out = cops.fused_compress_arrays(
                "regtopk", g, err_prev, step, k=k, omega=0.25, mu=0.5,
                Q=0.0, idx_prev=idx_prev, a_prev_sel=aps, g_prev_sel=gps,
                want_ghat=True, strategy="pallas_interpret")
            assert set(np.asarray(orr.indices).tolist()) == \
                set(np.asarray(out["indices"]).tolist()), f"t={t}"
            np.testing.assert_allclose(np.asarray(orr.ghat),
                                       np.asarray(out["ghat"]),
                                       rtol=1e-6, atol=1e-7)
            # post-step state parity: err_prev == reference a * (1 - s)
            np.testing.assert_array_equal(np.asarray(orr.state["err"]),
                                          np.asarray(out["err"]))
            agg = 0.25 * orr.ghat
            sr = sparsify.observe_aggregate(cfg_r, orr.state, agg)
            err_prev = out["err"]
            idx_prev, aps = out["indices"], out["values"]
            gps = agg[idx_prev.astype(jnp.int32)]
            step = step + 1


class TestSweepCount:
    """Traced-shape audit: the fused pipeline must stay <= 2 O(J) HBM
    traversals per compress step on the production (sparse-comm) path —
    the err_prev layout leaves NO third sweep (state writes are O(k)
    scatters) — vs ~8 logical passes (audit: >= 6) for the reference
    path. Writes are gated too (write_units, DESIGN.md §2.3)."""

    @staticmethod
    def _audit(pipeline, comm_mode, j=1 << 18):
        cfg = SparsifierConfig(kind="regtopk", k=j // 1000, mu=0.5,
                               selector="exact", comm_mode=comm_mode,
                               pipeline=pipeline)
        state = sparsify.init_state(cfg, j)
        g = jax.random.normal(jax.random.PRNGKey(0), (j,))

        def f(state, g):
            o = sparsify.compress(cfg, state, g, omega=0.25)
            outs = [o.mask, o.state, o.values, o.indices]
            if o.ghat is not None:
                outs.append(o.ghat)
            return tuple(jax.tree_util.tree_leaves(outs))

        return audit_fn(f, state, g, j=j, donate_argnums=(0,))

    def test_fused_sparse_within_budget(self):
        res = self._audit("fused", "sparse")
        assert res["traversals"] <= 2, res
        assert res["read_units"] <= 3.5, res
        # writes: sweep-1's (a, keys) streams only — the mask-write
        # sweep of the (a_prev, s_prev) layout is gone
        assert res["write_units"] <= 2.0, res

    def test_fused_simulate_within_budget(self):
        res = self._audit("fused", "simulate")
        assert res["traversals"] <= sweep_plan("fused", "simulate")["o_j_passes"], res

    def test_reference_is_heavier(self):
        ref = self._audit("reference", "sparse")
        fus = self._audit("fused", "sparse")
        assert ref["traversals"] >= 6, ref
        assert ref["traversals"] > fus["traversals"]
        assert ref["read_units"] > 2 * fus["read_units"], (ref, fus)
        assert ref["write_units"] > fus["write_units"], (ref, fus)

    def test_plan_matches_audit(self):
        assert sweep_plan("fused", "sparse")["o_j_passes"] == 2
        assert sweep_plan("fused", "simulate")["o_j_passes"] == 3
        assert sweep_plan("reference")["full_sorts"] == 2


class TestShardMapSync:
    """sync_gradient under shard_map: fused sparse == fused simulate ==
    reference, on a 1-device mesh."""

    @pytest.mark.parametrize("comm_mode", ["simulate", "sparse"])
    def test_sync_parity(self, comm_mode):
        from jax.sharding import PartitionSpec as P
        from repro.core import aggregate as agg
        j = 4_096
        cfg_r, cfg_f = _pair("regtopk", sparsity=0.01, mu=0.5,
                             comm_mode=comm_mode)
        mesh = jax.make_mesh((1,), ("data",))
        g = jax.random.normal(jax.random.PRNGKey(0), (j,))

        def run(cfg):
            st = sparsify.init_state(cfg, j)

            def f(g, st):
                return agg.GradientSync(cfg, ("data",))(st, g)[0]

            with mesh:
                fn = jax.jit(jax.shard_map(
                    f, mesh=mesh,
                    in_specs=(P("data"), jax.tree_util.tree_map(
                        lambda _: P(), st)),
                    out_specs=P("data"), check_vma=False))
                return fn(g, st)

        np.testing.assert_allclose(np.asarray(run(cfg_r)),
                                   np.asarray(run(cfg_f)),
                                   rtol=1e-5, atol=1e-6)


class TestRandkBigIndex:
    def test_randk_uses_uint32_and_bigvec(self):
        cfg = SparsifierConfig(kind="randk", k=16, selector="exact")
        j = 1_000
        st = sparsify.init_state(cfg, j)
        out = sparsify.compress(cfg, st, jnp.arange(j, dtype=jnp.float32),
                                key=jax.random.PRNGKey(0))
        assert out.indices.dtype == jnp.uint32
        assert int(sparsify.dense_mask(out, j).sum()) == 16
        np.testing.assert_allclose(
            np.asarray(out.values),
            np.asarray(out.indices).astype(np.float32))
