"""Stage scopes of the train step (core/stages.py) and the trim counters.

Every stage names its ops in the compiled step's HLO metadata, the scopes
leave the program op-for-op as it is without them, and the fused trim
reports whether it fell back and how many candidate rows saturated."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import stages
from repro.kernels.compress import ops as cops

HLO_SECTIONS = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _tiny_step(pipeline="fused"):
    """(compiled step, state, batch, key) of a tiny xlstm REGTOP-k run on
    one device."""
    from repro.data.synthetic import lm_batch
    from repro.launch.mesh import make_mesh
    from repro.launch.train import build_run, parse_args
    from repro.train.step import (build_parallel, build_train_step,
                                  init_train_state)
    run = build_run(parse_args([
        "--arch", "xlstm-125m", "--smoke", "--data", "1",
        "--sparsifier", "regtopk", "--sparsity", "0.01", "--comm", "sparse",
        "--pipeline", pipeline]))
    mesh = make_mesh(1, 1)
    pal = build_parallel(mesh)
    step, _, _ = build_train_step(run, mesh, pal)
    with jax.set_mesh(mesh):
        state = init_train_state(run, mesh, pal, jax.random.PRNGKey(0))
        batch = lm_batch(run.model, 2, 32, 0, 0)
        key = jax.random.PRNGKey(1)
        compiled = jax.jit(step).lower(*state, batch, key).compile()
    return compiled, state, batch, key


@pytest.fixture(scope="module")
def fused_step():
    return _tiny_step("fused")


def _ops(hlo_text):
    """The instruction lines of an HLO text, metadata stripped."""
    out, section = [], False
    for line in hlo_text.splitlines():
        if line.strip() in HLO_SECTIONS:
            section = True
        elif section:
            section = bool(line.strip())
        else:
            out.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    return out


def test_every_stage_names_ops_of_the_compiled_step(fused_step):
    names = re.findall(r'op_name="([^"]*)"', fused_step[0].as_text())
    found = {stages.stage_of(n) for n in names}
    assert set(stages.STAGES) | {"bwd"} <= found, (
        set(stages.STAGES) | {"bwd"}) - found


def test_scopes_change_metadata_only(fused_step, monkeypatch):
    """Built with every stage scope a no-op, the compiled step has the
    same instructions, in the same order, as with them."""
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _tiny_step("fused")[0].as_text()
    monkeypatch.undo()
    assert stages.PREFIX not in bare
    assert _ops(bare) == _ops(fused_step[0].as_text())


@pytest.mark.parametrize("op_name, stage", [
    ("jit(step_fn)/jvp(stage_fwd)/while/body/dot_general", "fwd"),
    ("jit(step_fn)/transpose(jvp(stage_fwd))/while/body/checkpoint/"
     "rematted_computation/mul", "bwd"),
    ("jit(step_fn)/stage_trim/cond/branch_0_fun/stage_fallback/sort",
     "fallback"),
    ("jit(step_fn)/stage_ef_write/scatter", "ef_write"),
    ("stage_support/jit(searchsorted)/while/body/gather", "support"),
    ("jit(step_fn)/BENCH_sync/add", None),
    ("jit(step_fn)/stage_nonesuch/add", None),
])
def test_stage_of(op_name, stage):
    assert stages.stage_of(op_name) == stage


def test_unknown_stage_is_an_error():
    with pytest.raises(KeyError):
        with stages.scope("sweep2"):
            pass


J = 4 * 8192
K = 32


def _gradient(spread: bool):
    """Small distinct background keys plus large entries: K distinct ones
    spread evenly over the four interleaved 8,192-entry candidate rows
    (row r holds the positions r mod 4), or 512 equal ones at the
    positions 0 mod 4, all in row 0: more than any row's W candidate
    slots here."""
    g = 1e-3 * jax.random.uniform(jax.random.PRNGKey(7), (J,))
    if spread:
        idx = np.arange(K) * (J // K) + np.arange(K)
        return g.at[idx].set(5.0 + 0.01 * np.arange(K))
    return g.at[4 * np.arange(512)].set(5.0)


CASES = {
    "exact": dict(kind="regtopk", selector="exact"),
    "histogram": dict(kind="topk", selector="histogram"),
    # two 16,384-entry segments of two rows each, so a row can saturate
    "allocated": dict(kind="topk", selector="exact",
                      allocation="proportional",
                      seg_bounds=[(0, J // 2), (J // 2, J // 2)]),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("spread", [True, False], ids=["spread", "row"])
def test_trim_counters(case, spread):
    kw = dict(CASES[case])
    if kw["kind"] == "regtopk":
        kw.update(idx_prev=jnp.zeros((K,), jnp.uint32),
                  a_prev_sel=jnp.zeros((K,)), g_prev_sel=jnp.zeros((K,)))
    out = cops.fused_compress_arrays(
        kw.pop("kind"), _gradient(spread), jnp.zeros((J,)),
        jnp.int32(0), k=K, want_ghat=False, **kw)
    fallback = float(out["topk_fallback"])
    saturated = float(out["topk_saturated_rows"])
    if spread:
        assert (fallback, saturated) == (0.0, 0.0)
    else:
        assert fallback == 1.0 and saturated >= 1.0


def test_step_metrics_carry_the_trim_counters(fused_step):
    """The fused step reports both counters; the reference pipeline,
    which has no fused trim, reports 0 for each."""
    compiled, state, batch, key = fused_step
    m = compiled(*state, batch, key)[3]
    assert float(m["topk_fallback"]) in (0.0, 1.0)
    assert float(m["topk_saturated_rows"]) >= 0.0
    ref, state, batch, key = _tiny_step("reference")
    m = ref(*state, batch, key)[3]
    assert float(m["topk_fallback"]) == 0.0
    assert float(m["topk_saturated_rows"]) == 0.0
