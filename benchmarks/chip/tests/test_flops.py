"""FLOPs per token against XLA's count of a remat-free float32 forward of
the reference, for every configuration BENCHMARK.json names, at its own
sizes: each family's ``forward_flops`` is checked with no edit here. XLA
also counts the norms, activations, gates and loss that the model count
leaves out, so its count is a little higher; the model count must lie
within 15 % below it. (At one token XLA also folds away a few products
with the scans' zero initial state, so there 2 % above it is allowed.)"""
import importlib
import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import pytest

import cells
import flops
import tiny
from reference.common import Static
from reference.train import init_params

CONFIGS = cells.load_benchmark()["configs"]


def _xla_forward_flops(fam, cfg, seq):
    """XLA's count, compiled from the parameters' shapes alone."""
    params = jax.eval_shape(lambda k: init_params(fam, cfg, k),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((seq,), jnp.int32)
    f = jax.jit(lambda p, t: fam.nll(p, t, t, Static(cfg), "f32"))
    cost = f.lower(params, tokens).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    return cost["flops"]


@pytest.mark.parametrize("conf", CONFIGS, ids=lambda c: c["name"])
def test_forward_flops_match_xla(conf):
    # one token: XLA counts a scan's body once, so the recurrence is
    # counted right only where the scan runs once
    with open(os.path.join(cells.ROOT, conf["file"])) as f:
        cfg = json.load(f)
    fam = importlib.import_module("reference." + cfg["reference"])
    xla = _xla_forward_flops(fam, cfg, 1)
    ours = flops.forward_per_token(cfg, 1)
    assert 0.85 * xla <= ours <= 1.02 * xla, (ours, xla)


def test_train_is_three_forwards():
    assert flops.train_per_token(tiny.XLSTM, 64) == pytest.approx(
        3 * flops.forward_per_token(tiny.XLSTM, 64))


def test_family_without_a_count_is_an_error(monkeypatch):
    """No family falls back on another's count."""
    monkeypatch.setitem(sys.modules, "reference.nocount",
                        types.ModuleType("reference.nocount"))
    with pytest.raises(AttributeError, match=r"reference\.nocount"):
        flops.train_per_token(dict(tiny.XLSTM, reference="nocount"), 64)


def test_published_sizes():
    """The count of the benchmark's xlstm configuration, worked by hand:
    2 FLOPs per multiply-add of each product, plus the recurrences."""
    import cells
    x = cells.load_cell("xlstm-125m.seq1k")["config"]
    d, di, hd, ds, v = 768, 1536, 384, 1024, 50304
    per_pair = (2 * (2 * d * di + 3 * di * di + 8 * di + di * d)
                + 4 * (6 * hd * hd + 6 * hd)
                + 2 * (4 * d * ds + ds * d) + 7 * ds)
    assert flops.forward_per_token(x, 1024) == 6 * per_pair + 2 * d * v
    assert flops.train_per_token(x, 1024) == 3 * (6 * per_pair + 2 * d * v)
