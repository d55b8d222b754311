"""Unit + property tests for the core sparsification library (the paper)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import given, settings, st   # hypothesis or skip-stubs

from repro.configs.base import SparsifierConfig
from repro.core import select, sparsify
from repro.core.aggregate import comm_bytes_per_step


def _cfg(kind="topk", **kw):
    kw.setdefault("selector", "exact")
    return SparsifierConfig(kind=kind, **kw)


class TestSelect:
    def test_exact_mask_counts(self):
        x = jnp.asarray(np.random.default_rng(0).normal(size=1000))
        for k in (1, 10, 500, 1000):
            m = select.topk_mask_exact(x, k)
            assert int(m.sum()) == k

    def test_exact_mask_selects_largest(self):
        x = jnp.asarray([0.1, -5.0, 2.0, 0.0, 3.0])
        m = select.topk_mask_exact(x, 2)
        assert m.tolist() == [0, 1, 0, 0, 1]

    def test_histogram_brackets_k(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=20_000) * np.exp(rng.normal(size=20_000)))
        for k in (20, 200, 2000):
            m = select.topk_mask(x, k, "histogram")
            n = int(m.sum())
            assert n >= k
            assert n <= k * 1.2 + 32   # at most one bin of over-selection

    def test_scale_invariance(self):
        x = jnp.asarray(np.random.default_rng(2).normal(size=512))
        m1 = select.topk_mask_exact(x, 32)
        m2 = select.topk_mask_exact(4.0 * x, 32)
        assert (m1 == m2).all()


class TestErrorFeedback:
    @pytest.mark.parametrize("kind", ["topk", "regtopk", "dgc", "thresholdk"])
    def test_ef_invariant(self, kind):
        """a^t == ghat + eps^{t+1} (error feedback conserves mass)."""
        cfg = _cfg(kind, sparsity=0.05, mu=0.5)
        j = 400
        st_ = sparsify.init_state(cfg, j)
        key = jax.random.PRNGKey(0)
        for t in range(4):
            g = jax.random.normal(jax.random.fold_in(key, t), (j,))
            mom = st_.get("mom")
            out = sparsify.compress(cfg, st_, g, key=key)
            if kind == "dgc":
                a = st_["err"] + (cfg.momentum * mom + g)
            else:
                a = st_["err"] + g
            np.testing.assert_allclose(np.asarray(a),
                                       np.asarray(out.ghat + out.state["err"]),
                                       rtol=1e-6, atol=1e-6)
            st_ = sparsify.observe_aggregate(cfg, out.state, out.ghat)

    def test_regtopk_reduces_to_topk_mu_small(self):
        """mu -> 0 => tanh(|1+Delta|/mu) -> 1 (a.e.) => same mask as TOP-k."""
        j, k = 300, 15
        key = jax.random.PRNGKey(1)
        cfg_t = _cfg("topk", k=k)
        cfg_r = _cfg("regtopk", k=k, mu=1e-6, Q=0.0)
        st_t = sparsify.init_state(cfg_t, j)
        st_r = sparsify.init_state(cfg_r, j)
        for t in range(5):
            g = jax.random.normal(jax.random.fold_in(key, t), (j,))
            ot = sparsify.compress(cfg_t, st_t, g)
            orr = sparsify.compress(cfg_r, st_r, g)
            assert (ot.mask == orr.mask).all(), f"step {t}"
            agg = 0.5 * (ot.ghat + orr.ghat)
            st_t = sparsify.observe_aggregate(cfg_t, ot.state, agg)
            st_r = sparsify.observe_aggregate(cfg_r, orr.state, agg)

    def test_regtopk_damps_cancelling_entry(self):
        """Paper §3.2 discussion case (2): entries that cancel after
        aggregation get Delta = -1 and are damped to zero next round."""
        cfg = _cfg("regtopk", k=1, mu=0.5)
        j = 4
        # two workers, first entry large but opposite signs
        g1 = jnp.asarray([10.0, 1.0, 0.1, 0.1])
        g2 = jnp.asarray([-10.0, 1.0, 0.1, 0.1])
        states = [sparsify.init_state(cfg, j) for _ in range(2)]
        g_agg, states = sparsify.sparsified_round(cfg, states, [g1, g2])
        assert float(jnp.abs(g_agg).max()) == 0.0   # cancels at t=0 (TOP-k)
        g_agg, states = sparsify.sparsified_round(cfg, states, [g1, g2])
        # REGTOP-k now selects entry 1 (constructive), not entry 0
        assert float(g_agg[1]) > 0.0
        assert float(g_agg[0]) == 0.0

    def test_randk_mask_size(self):
        cfg = _cfg("randk", k=7)
        st_ = sparsify.init_state(cfg, 100)
        out = sparsify.compress(cfg, st_, jnp.ones(100),
                                key=jax.random.PRNGKey(0))
        assert int(out.mask.sum()) == 7


@settings(max_examples=25, deadline=None)
@given(
    j=st.integers(16, 400),
    sp=st.floats(0.01, 0.9),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_topk_exact_k_and_ef(j, sp, seed):
    cfg = _cfg("topk", sparsity=sp)
    k = sparsify.resolve_k(cfg, j)
    g = jax.random.normal(jax.random.PRNGKey(seed), (j,))
    st_ = sparsify.init_state(cfg, j)
    out = sparsify.compress(cfg, st_, g)
    assert int(out.mask.sum()) == k
    np.testing.assert_allclose(np.asarray(out.ghat + out.state["err"]),
                               np.asarray(g), rtol=1e-5, atol=1e-6)
    # ghat entries are exactly a*mask
    assert float(jnp.abs(out.ghat * (1 - out.mask)).max()) == 0.0


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(2, 6))
def test_property_regtopk_round_deterministic_and_conservative(seed, n):
    """Multi-worker round: aggregated gradient only contains selected
    entries; state step counters advance; permuting workers permutes
    nothing (aggregation is symmetric)."""
    j, k = 64, 5
    cfg = _cfg("regtopk", k=k, mu=0.7)
    key = jax.random.PRNGKey(seed)
    grads = [jax.random.normal(jax.random.fold_in(key, i), (j,))
             for i in range(n)]
    states = [sparsify.init_state(cfg, j) for _ in range(n)]
    agg1, st1 = sparsify.sparsified_round(cfg, states, grads)
    agg2, _ = sparsify.sparsified_round(
        cfg, list(reversed(states)), list(reversed(grads)))
    np.testing.assert_allclose(np.asarray(agg1), np.asarray(agg2), rtol=1e-6)
    assert int(jnp.sum(agg1 != 0)) <= n * k


def test_comm_volume_model():
    cfg = _cfg("topk", sparsity=0.001, comm_mode="sparse")
    j, n = 10_000_000, 16
    v = comm_bytes_per_step(cfg, j, n)
    assert v["ratio"] < 0.05          # >20x reduction at S=0.1%
    assert v["bytes"] == n * v["k"] * 8


@pytest.mark.parametrize("denom", [-1e-12, -5e-13, -1e-40, -0.0, 0.0,
                                   5e-13, 1e-12])
def test_safe_denom_never_zero_keeps_sign(denom):
    from repro.core.numerics import TINY, safe_denom
    d = jnp.float32(denom)
    s = float(safe_denom(d))
    assert abs(s) == float(jnp.float32(TINY))
    if abs(denom) >= np.finfo(np.float32).tiny:
        # a subnormal denom may be flushed to zero first (then +TINY)
        assert (s < 0) == (denom < 0)
    assert np.isfinite(float(jnp.float32(0.1) / safe_denom(d)))


def test_safe_denom_passes_large_values():
    from repro.core.numerics import safe_denom
    d = jnp.asarray([-2.0, -1.1e-12, 1.1e-12, 3.0], jnp.float32)
    assert (safe_denom(d) == d).all()
