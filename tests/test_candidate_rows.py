"""The XLA strategy's interleaved candidate rows and the trim's explicit
tie order (kernels/compress/xla.py, ops._rank).

- ``row_shape``: rows, chunk and W, lane-aligned from 128 rows up;
- ``candidates_xla``: row r holds the positions c * rows + r;
- gradients concentrated the way real ones are (contiguous embedding
  rows, a hot dense block) keep the exact fast path: no fallback, no
  saturated row, output bit-identical to the reference selector, flat,
  bucketed, streaming and allocated;
- ties at and above the k-th key across interleaved rows, and a
  corrected REGTOP-k support key tied at the k-th key, resolve in the
  reference order (key desc, index asc) on the fast path;
- selected entries strided exactly ``rows`` apart saturate one row, and
  the witness still falls back and stays exact.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SparsifierConfig
from repro.core import allocate, select, sparsify
from repro.kernels.compress import ops as cops
from repro.kernels.compress import xla as px

CHUNK = px.CHUNK


# ---------------------------------------------------------------------------
# row layout
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("j", [1, 7, 8192, 8193, 96 * 8192, 127 * 8192 + 5,
                               128 * 8192, 128 * 8192 + 1, 164_669_952])
def test_row_shape(j):
    rows, chunk, w = px.row_shape(j, max(1, j // 1000))
    assert rows * chunk >= j and chunk <= CHUNK and 1 <= w <= chunk
    if -(-j // CHUNK) >= px.LANES:
        # lane-aligned: rows a multiple of 128, chunk of 8, little padding
        assert rows % px.LANES == 0 and chunk % 8 == 0
        assert rows * chunk - j < 8 * rows
    else:
        assert chunk == min(CHUNK, max(8, j))
        assert rows == -(-j // chunk)


def test_row_shape_of_the_xlstm_cell():
    """J = 164,669,952 at k = 0.1 %: 20,224 rows of 8,144, W = 32."""
    assert px.row_shape(164_669_952, 164_670) == (20_224, 8_144, 32)


@pytest.mark.parametrize("j", [5 * 8192 + 77, 130 * 8192])
def test_candidates_come_from_interleaved_rows(j):
    """Row r's candidates are positions congruent to r modulo rows, and
    each row's are its W largest keys."""
    k = j // 1000
    rows, chunk, w = px.row_shape(j, k)
    keys = jax.random.uniform(jax.random.PRNGKey(0), (j,))
    cv, ci, row_min, full_cover = px.candidates_xla(keys, k)
    cv = np.asarray(cv).reshape(rows, w)
    ci = np.asarray(ci).reshape(rows, w)
    assert not bool(full_cover)
    assert (ci % rows == np.arange(rows)[:, None]).all()
    kn = np.concatenate([np.asarray(keys),
                         np.full(rows * chunk - j, -np.inf, np.float32)])
    for r in (0, rows // 2, rows - 1):
        want = np.sort(kn[r::rows])[::-1][:w]
        np.testing.assert_array_equal(cv[r], want)
        np.testing.assert_array_equal(kn[ci[r]], cv[r])
    np.testing.assert_array_equal(np.asarray(row_min), cv[:, -1])


# ---------------------------------------------------------------------------
# concentrated gradients keep the fast path
# ---------------------------------------------------------------------------

J = 96 * CHUNK                     # 96 rows of 8,192
K = 787                            # 0.1 %
EMB = 768                          # one token's embedding row


def _concentrated(step):
    """Small background keys, twelve contiguous 768-entry "embedding
    rows" (one in every eighth chunk, at a slot that moves with the
    step) and one hot dense block of 2,048: the top-k sits in a few
    contiguous runs, as a real model's gradient does."""
    r = np.random.default_rng([16, step])
    g = r.normal(0.0, 1e-2, J).astype(np.float32)
    for i in range(12):
        off = 8 * i * CHUNK + EMB * int(r.integers(0, 10))
        g[off:off + EMB] = r.normal(0.0, 4.0, EMB)
    hot = 66 * CHUNK + 500
    g[hot:hot + 2048] = r.normal(0.0, 3.5, 2048)
    return jnp.asarray(g)


def test_concentrated_data_overfills_a_contiguous_chunk():
    """The data is what the interleave is for: one contiguous 8,192-entry
    chunk holds more of the top-k than a row has candidate slots."""
    w = px.row_shape(J, K)[2]
    for step in range(3):
        top = np.asarray(select.topk_indices(_concentrated(step), K))
        assert np.bincount(top // CHUNK).max() > w


LAYOUTS = {
    "flat": {},
    "buckets3": {"num_buckets": 3},
    "streaming": {"overlap": "backward"},
    "allocated": {"allocation": "proportional"},
}


def _configs(kind, layout):
    kw = dict(kind=kind, k=K, mu=0.5, Q=0.0, selector="exact",
              comm_mode="sparse")
    if layout == "allocated":
        kw["allocation"] = "proportional"
    ref = SparsifierConfig(pipeline="reference", **kw)
    fused = dataclasses.replace(ref, pipeline="fused", **LAYOUTS[layout])
    return ref, fused


def _compress_fused(cfg, state, g, omega):
    if cfg.overlap == "backward":
        bounds = allocate.segment_bounds(
            J, allocate.resolve_num_segments(cfg, J))
        assert len(bounds) > 1
        segs = [g[off:off + size] for off, size in bounds]
        return sparsify.compress(cfg, state, None, omega=omega,
                                 g_segments=segs)
    return sparsify.compress(cfg, state, g, omega=omega)


def _assert_same_selection(ref, out, ctx):
    np.testing.assert_array_equal(np.asarray(ref.indices),
                                  np.asarray(out.indices), err_msg=ctx)
    np.testing.assert_array_equal(np.asarray(ref.values),
                                  np.asarray(out.values), err_msg=ctx)
    np.testing.assert_array_equal(np.asarray(ref.state["err"]),
                                  np.asarray(out.state["err_prev"]),
                                  err_msg=ctx)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("kind", ["topk", "regtopk"])
def test_concentrated_gradients_take_the_fast_path(kind, layout):
    """Three steps with error feedback: the fused step never falls back,
    no candidate row saturates, and indices, values and the error state
    are bit-identical to the reference selector's."""
    cfg_r, cfg_f = _configs(kind, layout)
    sr = sparsify.init_state(cfg_r, J)
    sf = sparsify.init_state(cfg_f, J)
    omega = 0.5
    for t in range(3):
        g = _concentrated(t)
        orr = sparsify.compress(cfg_r, sr, g, omega=omega)
        off = _compress_fused(cfg_f, sf, g, omega)
        ctx = f"{kind} {layout} t={t}"
        assert float(off.trim["topk_fallback"]) == 0.0, ctx
        assert float(off.trim["topk_saturated_rows"]) == 0.0, ctx
        _assert_same_selection(orr, off, ctx)
        agg = omega * sparsify.dense_ghat(orr, J)
        sr = sparsify.observe_aggregate(cfg_r, orr.state, agg)
        sf = sparsify.observe_aggregate(cfg_f, off.state, agg)


# ---------------------------------------------------------------------------
# ties resolve in the reference order on the fast path
# ---------------------------------------------------------------------------

JT = 16 * CHUNK                    # 16 rows
KT = 100


def _tied(seed=0):
    """Distinct small background keys, 30 entries at 5.0 and 30 at 4.0
    (ties above the k-th key) and 80 at 3.0 (the k-th key: 40 of them
    fit), at random positions spread over the interleaved rows."""
    r = np.random.default_rng(seed)
    g = (r.permutation(JT) / JT).astype(np.float32)
    pos = r.choice(JT, 140, replace=False)
    g[pos[:30]] = 5.0
    g[pos[30:60]] = 4.0
    g[pos[60:]] = 3.0
    return jnp.asarray(g), pos


def _assert_ties_cross_rows(g, k):
    rows = px.row_shape(g.shape[0], k)[0]
    keys = np.abs(np.asarray(g))
    kth = np.sort(keys)[::-1][k - 1]
    at = np.flatnonzero(keys == kth)
    assert len(at) > k - np.sum(keys > kth)          # the tie is cut
    assert len(np.unique(at % rows)) > 1             # across rows


@pytest.mark.parametrize("nb", [1, 3])
def test_ties_follow_the_reference_order(nb):
    """Flat and bucketed: no fallback, and the packed order is
    ``select.topk_indices``' bit for bit."""
    g, _ = _tied()
    _assert_ties_cross_rows(g, KT)
    out = cops.fused_compress_arrays("topk", g, jnp.zeros((JT,)),
                                     jnp.int32(0), k=KT, want_ghat=False,
                                     num_buckets=nb)
    assert float(out["topk_fallback"]) == 0.0
    want = select.topk_indices(g, KT)
    np.testing.assert_array_equal(np.asarray(out["indices"]),
                                  np.asarray(want))
    np.testing.assert_array_equal(np.asarray(out["values"]),
                                  np.asarray(g[want.astype(jnp.int32)]))


def test_ties_follow_the_reference_order_allocated():
    """Allocated proportional: each segment's ties resolve by index, and
    the pack matches the dense allocated selector."""
    g, _ = _tied(1)
    _assert_ties_cross_rows(g, KT)
    cfg = SparsifierConfig(kind="topk", k=KT, selector="exact",
                           allocation="proportional")
    out = cops.fused_compress_arrays("topk", g, jnp.zeros((JT,)),
                                     jnp.int32(0), k=KT, want_ghat=False,
                                     allocation="proportional")
    assert float(out["topk_fallback"]) == 0.0
    _mask, vals, idx = allocate.reference_allocated_select(cfg, g, g, KT)
    np.testing.assert_array_equal(np.asarray(out["indices"]),
                                  np.asarray(idx))
    np.testing.assert_array_equal(np.asarray(out["values"]),
                                  np.asarray(vals))


@pytest.mark.parametrize("nb", [1, 3])
def test_corrected_support_key_tied_at_the_kth_key(nb):
    """REGTOP-k at step 1 with one worker (omega 1, the aggregate equal to
    the sent values): a support entry's corrected key equals the
    off-support key of the same magnitude, and support entries sit in
    the boundary tie. The fast path takes it, in index order, where the
    dense reference keys put it."""
    g, pos = _tied(2)
    mu = 0.5
    support = np.concatenate([pos[:10], pos[60:80],
                              np.setdiff1d(np.arange(0, JT, 997), pos)[:70]])
    idx_prev = jnp.asarray(support, jnp.uint32)
    a_prev_sel = jnp.asarray(np.linspace(1.0, 2.0, KT), jnp.float32)
    out = cops.fused_compress_arrays(
        "regtopk", g, jnp.zeros((JT,)), jnp.int32(1), k=KT, omega=1.0,
        mu=mu, Q=0.0, idx_prev=idx_prev, a_prev_sel=a_prev_sel,
        g_prev_sel=a_prev_sel, want_ghat=False, num_buckets=nb)
    # dense reference keys: |a| * tanh(1 / mu) everywhere (delta = Q = 0)
    t = jnp.tanh(jnp.abs(1.0 + jnp.float32(0.0)) / mu)
    keys = jnp.abs(g * t)
    kth = float(jnp.sort(keys)[::-1][KT - 1])
    assert np.any(np.asarray(keys)[support] == kth)   # support in the tie
    assert np.sum(np.asarray(keys) == kth) > KT - np.sum(
        np.asarray(keys) > kth)
    assert float(out["topk_fallback"]) == 0.0
    want = select.topk_indices_by_key(keys, KT)
    np.testing.assert_array_equal(np.asarray(out["indices"]),
                                  np.asarray(want))
    np.testing.assert_array_equal(np.asarray(out["values"]),
                                  np.asarray(g[want.astype(jnp.int32)]))


# ---------------------------------------------------------------------------
# a stride of exactly ``rows`` saturates one row: the witness falls back
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("j", [16 * CHUNK, 130 * CHUNK])
@pytest.mark.parametrize("kind", ["topk", "regtopk"])
def test_strided_top_k_saturates_a_row_and_falls_back(kind, j):
    k = j // 1000
    rows, _chunk, w = px.row_shape(j, k)
    r = np.random.default_rng(j)
    g = r.uniform(0.0, 1.0, j).astype(np.float32)
    hot = rows * r.choice(j // rows, 2 * k, replace=False) + 5
    g[hot] = r.uniform(2.0, 3.0, 2 * k)
    g = jnp.asarray(g)
    assert 2 * k > w
    kw = {}
    if kind == "regtopk":
        kw = dict(idx_prev=jnp.zeros((k,), jnp.uint32),
                  a_prev_sel=jnp.zeros((k,)), g_prev_sel=jnp.zeros((k,)))
    out = cops.fused_compress_arrays(kind, g, jnp.zeros((j,)),
                                     jnp.int32(0), k=k, want_ghat=False,
                                     **kw)
    assert float(out["topk_fallback"]) == 1.0
    assert float(out["topk_saturated_rows"]) == 1.0
    want = select.topk_indices(g, k)
    np.testing.assert_array_equal(np.asarray(out["indices"]),
                                  np.asarray(want))
    np.testing.assert_array_equal(np.asarray(out["err"]),
                                  np.asarray(g.at[want.astype(jnp.int32)]
                                             .set(0.0)))
