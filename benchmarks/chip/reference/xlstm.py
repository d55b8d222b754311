"""The reference forward loss of the repo's xLSTM variant (after
arXiv:2405.04517).

The stack alternates an mLSTM block and an sLSTM block, each a pre-
LayerNorm residual block with its own up and down projections, then a
final LayerNorm and an untied output head. No position embedding.

mLSTM block (heads h, inner width di = proj_factor * d, head size
hd = di / h): u = LN(x) @ up; output gate o = sigmoid(LN(x) @ up_gate);
q = u @ wq, k = (u @ wk) / sqrt(hd), v = u @ wv; input gate i and forget
gate f = log_sigmoid(.) from u @ wif. Per token, with the stabiliser m:
m' = max(f + m, i), C' = e^(f+m-m') C + e^(i-m') v k^T,
n' = e^(f+m-m') n + e^(i-m') k, h = C' q / max(|n' . q|, 1). The block
returns ((h * ln_h) * o) @ down.

sLSTM block (width ds = proj_factor * d rounded up to a multiple of 16):
gates i, f, z, o from LN(x) @ (wi, wf, wz, wo), f = log_sigmoid(f),
z = tanh(z), o = sigmoid(o); per token m' = max(f + m, i),
c' = e^(f+m-m') c + e^(i-m') z, n' = e^(f+m-m') n + e^(i-m'),
h = o c' / max(n', 1); it returns (h * ln_h) @ down.

This is the system under test's variant of xLSTM, not the paper's
block. It departs from the paper's 125M models in structure: the blocks
alternate mLSTM and sLSTM 1:1 (the paper's are xLSTM[7:1] and
xLSTM[1:0]); q, k and v are dense products (the paper's are headwise
block-diagonal); the sLSTM runs ds wide with no recurrent (hidden-to-
gate) weights and no feed-forward after it (the paper's runs at d_model
with recurrent weights and a gated feed-forward); neither block has the
causal convolution; and ``ln_h`` is a learned scale with no
normalisation.
"""
import jax
import jax.numpy as jnp

from reference.common import (Static, einsum, layer, layernorm, nll_sum,
                              quant)


def _dims(cfg):
    d, h = cfg["d_model"], cfg["n_heads"]
    di = int(cfg["ssm"]["mlstm_proj_factor"] * d)
    ds = -(-int(cfg["ssm"]["slstm_proj_factor"] * d) // 16) * 16
    return d, h, di, di // h, ds


def param_specs(cfg):
    """[(path, shape, init)] where init is a standard deviation, "ones"
    or "zeros"."""
    d, h, di, hd, ds = _dims(cfg)
    n, v = cfg["n_layers"] // 2, cfg["vocab_size"]
    m, s = ("blocks", "l0", "mlstm"), ("blocks", "l1", "slstm")
    return [
        (m + ("down",), (n, h, hd, d), di ** -0.5),
        (m + ("ln_h",), (n, h, hd), "ones"),
        (m + ("norm", "bias"), (n, d), "zeros"),
        (m + ("norm", "scale"), (n, d), "ones"),
        (m + ("up",), (n, d, di), d ** -0.5),
        (m + ("up_gate",), (n, d, h, hd), d ** -0.5),
        (m + ("wif",), (n, di, 2 * h), 0.02),
        (m + ("wk",), (n, di, di), di ** -0.5),
        (m + ("wq",), (n, di, di), di ** -0.5),
        (m + ("wv",), (n, di, h, hd), di ** -0.5),
        (s + ("down",), (n, ds, d), ds ** -0.5),
        (s + ("ln_h",), (n, ds), "ones"),
        (s + ("norm", "bias"), (n, d), "zeros"),
        (s + ("norm", "scale"), (n, d), "ones"),
        (s + ("wf",), (n, d, ds), d ** -0.5),
        (s + ("wi",), (n, d, ds), d ** -0.5),
        (s + ("wo",), (n, d, ds), d ** -0.5),
        (s + ("wz",), (n, d, ds), d ** -0.5),
        (("embed", "head"), (d, v), d ** -0.5),
        (("embed", "tok"), (v, d), 0.02),
        (("final_norm", "bias"), (d,), "zeros"),
        (("final_norm", "scale"), (d,), "ones"),
    ]


def forward_flops(cfg, seq):
    """Model FLOPs per token of the forward: every layer's matrix products
    (2 FLOPs per multiply-add), the mLSTM and sLSTM recurrences and the
    output head; not the embedding lookup, norms, activations or the
    loss. The recurrences are per token, so ``seq`` does not enter."""
    d, h, di, hd, ds = _dims(cfg)
    m_proj = 2 * (2 * d * di + 3 * di * di + di * 2 * h + di * d)
    # per head: C' = f C + i v k^T (4 hd^2), C q (2 hd^2); n' and n . q
    m_rec = h * (6 * hd * hd + 6 * hd)
    s_proj = 2 * (4 * d * ds + ds * d)
    # per unit: c' = f c + i z, n' = f n + i, h = o c / max(n, 1)
    s_rec = 7 * ds
    return cfg["n_layers"] // 2 * (m_proj + m_rec + s_proj + s_rec) \
        + 2 * d * cfg["vocab_size"]


def _mlstm(p, x, cfg, mode):
    d, h, di, hd, _ = _dims(cfg)
    S = x.shape[0]
    xi = layernorm(x, p["norm"])
    u = einsum("sd,de->se", xi, p["up"], mode)
    og = jax.nn.sigmoid(einsum("sd,dhv->shv", xi, p["up_gate"], mode))
    q = einsum("se,ef->sf", u, p["wq"], mode).reshape(S, h, hd)
    k = einsum("se,ef->sf", u, p["wk"], mode).reshape(S, h, hd) * hd ** -0.5
    v = einsum("se,ehv->shv", u, p["wv"], mode)
    gates = einsum("se,eg->sg", u, p["wif"], mode)
    ig, fg = gates[:, :h], jax.nn.log_sigmoid(gates[:, h:])

    def step(carry, inp):
        c, n, m = carry
        qt, kt, vt, it, ft = inp
        m2 = jnp.maximum(ft + m, it)
        i_, f_ = jnp.exp(it - m2), jnp.exp(ft + m - m2)
        c = f_[:, None, None] * c + i_[:, None, None] * (
            vt[:, :, None] * kt[:, None, :])
        n = f_[:, None] * n + i_[:, None] * kt
        num = jnp.einsum("hvk,hk->hv", c, qt, precision="highest")
        den = jnp.maximum(jnp.abs(jnp.sum(n * qt, -1)), 1.0)
        return (c, n, m2), num / den[:, None]

    init = (jnp.zeros((h, hd, hd)), jnp.zeros((h, hd)), jnp.zeros((h,)))
    _, hs = jax.lax.scan(step, init, (q, k, v, ig, fg))
    return einsum("shv,hvd->sd", hs * p["ln_h"] * og, p["down"], mode)


def _slstm(p, x, cfg, mode):
    xi = layernorm(x, p["norm"])
    ig, fg, zg, og = (einsum("sd,de->se", xi, p[w], mode)
                      for w in ("wi", "wf", "wz", "wo"))
    fg, zg, og = jax.nn.log_sigmoid(fg), jnp.tanh(zg), jax.nn.sigmoid(og)

    def step(carry, inp):
        c, n, m = carry
        it, ft, zt, ot = inp
        m2 = jnp.maximum(ft + m, it)
        i_, f_ = jnp.exp(it - m2), jnp.exp(ft + m - m2)
        c = f_ * c + i_ * zt
        n = f_ * n + i_
        return (c, n, m2), ot * c / jnp.maximum(n, 1.0)

    z = jnp.zeros(ig.shape[1:])
    _, hs = jax.lax.scan(step, (z, z, z), (ig, fg, zg, og))
    return einsum("se,ed->sd", hs * p["ln_h"], p["down"], mode)


def _pair(p, x, cfg, mode):
    x = x + _mlstm(p["l0"]["mlstm"], x, cfg, mode)
    return x + _slstm(p["l1"]["slstm"], x, cfg, mode)


def nll(params, tokens, targets, cfg, mode):
    """Summed next-token loss of one sequence: tokens, targets (S,)."""
    x = quant(params["embed"]["tok"][tokens], mode)
    body = jax.checkpoint(_pair, static_argnums=(2, 3))
    for i in range(cfg["n_layers"] // 2):
        x = quant(body(layer(params["blocks"], i), x, Static(cfg), mode),
                  mode)
    x = layernorm(x, params["final_norm"])
    return nll_sum(x, params["embed"]["head"], targets, mode)
