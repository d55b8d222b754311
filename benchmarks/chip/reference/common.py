"""Numerics shared by the reference model families.

Every matrix product goes through :func:`einsum`, which computes it in
float32 at ``Precision.HIGHEST`` (on a TPU a float32 product otherwise runs
in bfloat16 passes). With ``mode="fp8"`` its operands, and the residual
stream between blocks, are first rounded to float8_e4m3fn with a
per-tensor scale to the format's largest value, and the rounding is
passed straight through in the backward pass: that is the control, the
reference computed one precision below the bfloat16 in which the
configurations state the program keeps its matmul operands and its
residual stream.
"""
import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("f32", "fp8")
_E4M3_MAX = 448.0


def quant(x, mode):
    if mode == "f32":
        return x
    if mode != "fp8":
        raise ValueError(f"unknown reference mode {mode!r}; known {MODES}")
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    s = jnp.where(amax > 0, _E4M3_MAX / amax, 1.0)
    q = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    return x + jax.lax.stop_gradient(q - x)


def einsum(spec, a, b, mode):
    return jnp.einsum(spec, quant(a, mode), quant(b, mode), precision=HIGHEST)


def layernorm(x, p, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def nll_sum(x, head, targets, mode, block=1024):
    """Sum over positions with ``targets >= 0`` of -log softmax(x @ head)
    at the target, in blocks of positions so that the logits of one block
    are alive at a time. x (S, d), head (d, V), targets (S,)."""
    total = jnp.zeros((), jnp.float32)
    for s0 in range(0, x.shape[0], block):
        total = total + jax.checkpoint(_nll_block, static_argnums=(3,))(
            x[s0:s0 + block], head, targets[s0:s0 + block], mode)
    return total


def _nll_block(x, head, targets, mode):
    logits = einsum("sd,dv->sv", x, head, mode)
    lse = jax.nn.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[:, None],
                              -1)[:, 0]
    return jnp.sum(jnp.where(targets >= 0, lse - tgt, 0.0))


def layer(stacked, i):
    """Layer ``i`` of a parameter group stacked over layers."""
    return jax.tree_util.tree_map(lambda a: a[i], stacked)


class Static(dict):
    """A config dict that jax.checkpoint can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))
