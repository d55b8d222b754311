"""Model FLOPs per token of training: forward and backward, no remat.

The forward counts every layer's matrix products (2 FLOPs per multiply-
add), the mLSTM and sLSTM recurrences, and the output head; not the
embedding lookup, norms, activations or the loss. Training is three
times the forward: the backward computes the gradients of both operands
of each product.
"""


def _xlstm_forward(cfg, seq):
    d, h, v = cfg["d_model"], cfg["n_heads"], cfg["vocab_size"]
    di = int(cfg["ssm"]["mlstm_proj_factor"] * d)
    hd = di // h
    ds = -(-int(cfg["ssm"]["slstm_proj_factor"] * d) // 16) * 16
    m_proj = 2 * (2 * d * di + 3 * di * di + di * 2 * h + di * d)
    # per head: C' = f C + i v k^T (4 hd^2), C q (2 hd^2); n' and n . q
    m_rec = h * (6 * hd * hd + 6 * hd)
    s_proj = 2 * (4 * d * ds + ds * d)
    # per unit: c' = f c + i z, n' = f n + i, h = o c / max(n, 1)
    s_rec = 7 * ds
    return cfg["n_layers"] // 2 * (m_proj + m_rec + s_proj + s_rec) \
        + 2 * d * v


FORWARD = {"xlstm": _xlstm_forward}


def forward_per_token(cfg, seq):
    return FORWARD[cfg["reference"]](cfg, seq)


def train_per_token(cfg, seq):
    return 3 * forward_per_token(cfg, seq)
