"""The measured window of a tiny cell on the CPU: with ``keep_metrics`` it
keeps each step's metrics, the trim's counters among them, as device
arrays; without it, it returns what it always did, and keeping the
metrics changes nothing the steps compute."""
import jax
import numpy as np
import pytest

import cells
import harness
import run
import tiny

SEED = (1 << 33) + 5


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    bench, here, name = tiny.make(str(tmp_path_factory.mktemp("bench")))
    return cells.load_cell(name, bench, here)


def test_kept_metrics_carry_the_trim_counters(cell):
    r = harness.Run(cell, SEED, jax.devices()[:1])
    n, secs, losses, kept = r.window(steps=3, keep_metrics=True)
    assert n == len(kept) == 3 and secs > 0
    assert len(losses) == 1  # loss_every 2
    for m in kept:
        assert {"topk_fallback", "topk_saturated_rows"} <= set(m)
        assert all(isinstance(v, jax.Array) for v in m.values())
    means = run.counters(kept)
    assert set(means) == set(kept[0])
    for k in means:
        assert means[k] == pytest.approx(
            np.mean([float(m[k]) for m in kept]))
    assert 0.0 <= means["topk_fallback"] <= 1.0
    assert means["topk_saturated_rows"] >= 0.0


def test_window_without_kept_metrics_is_unchanged(cell):
    """The same seed run with and without keeping: the plain window returns
    (steps, seconds, losses), and both fetch the same losses and end on
    the same parameters."""
    a = harness.Run(cell, SEED, jax.devices()[:1])
    b = harness.Run(cell, SEED, jax.devices()[:1], built=(a.prog, a.step))
    plain = a.window(steps=4)
    kept = b.window(steps=4, keep_metrics=True)
    assert len(plain) == 3 and len(kept) == 4
    assert plain[0] == kept[0] == 4
    assert plain[2] == kept[2] and len(plain[2]) == 2
    for x, y in zip(jax.tree_util.tree_leaves(a.state[0]),
                    jax.tree_util.tree_leaves(b.state[0])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
