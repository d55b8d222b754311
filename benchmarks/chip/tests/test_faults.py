"""A whole run, past the look for a chip, on the CPU at a tiny size: a
sound step comes out correct, and each fault of the timed path that a
training cell can have comes out not correct."""
import jax
import jax.numpy as jnp
import pytest

import cells
import harness
import program
import run
import tiny

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = (1 << 33) + 7


@pytest.fixture(autouse=True)
def no_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_cache", lambda: None)


def _execute(tmp_path, chips=1, batch=2):
    bench, here, name = tiny.make(str(tmp_path), chips=chips, batch=batch)
    cell = cells.load_cell(name, bench, here)
    return run.execute(cell, SEED, 0.2, 0, jax.devices()[:chips], PEAKS)


def _patch_step(monkeypatch, wrap):
    """Replaces the compiled step by wrap(step without donation)."""
    def compile_(self, state, batch, key, scopes=None):
        f = jax.jit(self.step_fn)

        def step(p, o, e, b, k):
            with jax.set_mesh(self.mesh):
                return wrap(f, p, o, e, b, k)
        return step
    monkeypatch.setattr(program.Program, "compile", compile_)


def test_sound_run_is_correct(tmp_path):
    r = _execute(tmp_path)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1
    assert list(r)[-1] == "checks"


def test_sound_run_on_four_devices_is_correct(tmp_path):
    r = _execute(tmp_path, chips=4, batch=1)
    assert r["correct"], r["checks"]


def test_state_unchanged(tmp_path, monkeypatch):
    def wrap(f, p, o, e, b, k):
        return (p, o, e, f(p, o, e, b, k)[3])
    _patch_step(monkeypatch, wrap)
    r = _execute(tmp_path)
    assert not r["correct"]
    assert r["checks"]["change_leaf_gap"]["value"] > 0.99


def test_half_batch_left_out(tmp_path, monkeypatch):
    """(The benchmark's cells run one row per chip; this guards a cell
    with more.)"""
    def wrap(f, p, o, e, b, k):
        rows = b["tokens"].shape[0] // 2
        return f(p, o, e, {n: v[:rows] for n, v in b.items()}, k)
    _patch_step(monkeypatch, wrap)
    r = _execute(tmp_path)
    assert not r["correct"], r["checks"]


def test_update_altered_where_produced(tmp_path, monkeypatch):
    """Every step moves the parameters, and the optimizer's master copy
    of them, twice as far as the step computed: an answer altered where
    it is produced. (At the cell's size the output head takes no entry
    in the first steps, so a fault confined to it would alter nothing.)"""
    def wrap(f, p, o, e, b, k):
        p2, o2, e2, m = f(p, o, e, b, k)
        twice = lambda old, new: 2 * new - old
        p2 = jax.tree_util.tree_map(twice, p, p2)
        o2 = dict(o2, master=twice(o["master"], o2["master"]))
        return p2, o2, e2, m
    _patch_step(monkeypatch, wrap)
    r = _execute(tmp_path)
    assert not r["correct"], r["checks"]
    assert r["checks"]["change_leaf_gap"]["value"] > 0.5


def test_update_sign_flipped(tmp_path, monkeypatch):
    """Every step moves the parameters, and the optimizer's master copy
    of them, by the update with the wrong sign."""
    def wrap(f, p, o, e, b, k):
        p2, o2, e2, m = f(p, o, e, b, k)
        back = lambda old, new: 2 * old - new
        p2 = jax.tree_util.tree_map(back, p, p2)
        o2 = dict(o2, master=back(o["master"], o2["master"]))
        return p2, o2, e2, m
    _patch_step(monkeypatch, wrap)
    r = _execute(tmp_path)
    assert not r["correct"], r["checks"]
    assert r["checks"]["change_dir_gap"]["value"] > 1.5


def test_exchange_left_out(tmp_path, monkeypatch):
    """Each worker combines its own sparse gradient alone."""
    from repro.core import aggregate

    def own_only(values, indices, j, axes, num_buckets=1, **kw):
        dense = jnp.zeros((j,), values.dtype).at[indices].add(values)
        return dense / aggregate._axis_size(axes)
    monkeypatch.setattr(aggregate, "sparse_allgather_combine", own_only)
    r = _execute(tmp_path, chips=4, batch=1)
    assert not r["correct"], r["checks"]
