"""FLOPs per token against XLA's count of a remat-free float32 forward of
the reference at a reduced size. XLA also counts the norms, activations,
gates and loss that the model count leaves out, so its count is a little
higher; the model count must lie within 15 % below it. (At one token XLA
also folds away a few products with the scans' zero initial state, so
there 2 % above it is allowed.)"""
import jax
import jax.numpy as jnp
import pytest

import flops
import tiny
from reference import xlstm
from reference.common import Static
from reference.train import init_params

# widths at which products dominate, as they do at the published sizes
XLSTM = dict(tiny.XLSTM, d_model=256, vocab_size=2048)


def _xla_forward_flops(fam, cfg, seq):
    params = init_params(fam, cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((seq,), jnp.int32)
    f = jax.jit(lambda p, t: fam.nll(p, t, t, Static(cfg), "f32"))
    cost = f.lower(params, tokens).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    return cost["flops"]


def test_forward_flops_match_xla():
    # one token: XLA counts a scan's body once, so the recurrence is
    # counted right only where the scan runs once
    xla = _xla_forward_flops(xlstm, XLSTM, 1)
    ours = flops.forward_per_token(XLSTM, 1)
    assert 0.85 * xla <= ours <= 1.02 * xla, (ours, xla)


def test_train_is_three_forwards():
    assert flops.train_per_token(XLSTM, 64) == pytest.approx(
        3 * flops.forward_per_token(XLSTM, 64))


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
