"""Reference data-parallel training with REGTOP-k and Adam.

Per step and worker n (weight w = 1/N), Algorithm 1 of the REGTOP-k paper
(arXiv:2409.14893): a = eps + g; at the first step the score is a (plain
TOP-k); later, off the previous support the score is
a * tanh(|1 + Q| / mu), and on it a * tanh(|1 + Delta| / mu) with
Delta = (g_agg_prev - w a_prev) / (w a); the k entries of largest |score|
are sent (ties go to the lower index), and eps' is a with them zeroed.
``kind="topk"`` scores by a at every step. The combined gradient is the
mean over workers of their sparse gradients, and Adam (bias-corrected)
applies it.

Everything is float32. Parameters, error feedback and Adam moments are
kept as trees of leaves, never as one flat copy, so that the training
state of a 416-million-parameter model and one step's activations fit on
a 16 GB chip. The k selected entries are named by their index in the
concatenation of the leaves in tree order. Worker n runs on
``devices[n % len(devices)]``; the combine and Adam run on ``devices[0]``.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from reference.common import Static

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
Q = 0.0          # posterior distortion off the previous support
FAULTS = (None, "flip_update", "double_update")
TINY = 1e-12     # |w a| at or below this divides as +-TINY


def init_params(family, cfg, key):
    """Parameters of ``family`` from ``key``: one normal draw per leaf,
    scaled by its standard deviation, or ones / zeros."""
    tree = {}
    for i, (path, shape, init) in enumerate(family.param_specs(cfg)):
        if init == "ones":
            a = jnp.ones(shape, jnp.float32)
        elif init == "zeros":
            a = jnp.zeros(shape, jnp.float32)
        else:
            a = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * init
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = a
    return tree


class Layout:
    """Leaf sizes and offsets of a parameter tree in tree order; hashable,
    so that jit can take it as a static argument."""

    def __init__(self, tree):
        leaves = jax.tree_util.tree_leaves(tree)
        self.sizes = tuple(int(np.prod(l.shape)) for l in leaves)
        self.offsets = tuple(int(o) for o in np.cumsum((0,) + self.sizes[:-1]))
        self.total = int(sum(self.sizes))

    def parts(self):
        return list(zip(self.offsets, self.sizes))

    def __hash__(self):
        return hash(self.sizes)

    def __eq__(self, other):
        return isinstance(other, Layout) and self.sizes == other.sizes


def leaf_norms(tree):
    return jnp.stack([jnp.linalg.norm(l.astype(jnp.float32).ravel())
                      for l in jax.tree_util.tree_leaves(tree)])


_leaf_norms = jax.jit(leaf_norms)


@jax.jit
def change_readings(new, ref_new, old):
    """Per leaf, of the change new - old against the reference's change
    ref_new - old: the norm of each change; over the entries that both
    moved, the norm of the difference of the changes and of the
    reference's change; the count of entries the reference moved, and of
    those that ``new`` left unmoved."""
    out = {k: [] for k in ("norm", "ref_norm", "both_gap", "both_ref",
                           "ref_moved", "missed")}
    for a, r, o in zip(*(jax.tree_util.tree_leaves(t)
                         for t in (new, ref_new, old))):
        da, dr = (a - o).ravel(), (r - o).ravel()
        both = (da != 0) & (dr != 0)
        out["norm"].append(jnp.linalg.norm(da))
        out["ref_norm"].append(jnp.linalg.norm(dr))
        out["both_gap"].append(jnp.linalg.norm(jnp.where(both, da - dr, 0)))
        out["both_ref"].append(jnp.linalg.norm(jnp.where(both, dr, 0)))
        out["ref_moved"].append(jnp.sum(dr != 0, dtype=jnp.int32))
        out["missed"].append(jnp.sum((dr != 0) & (da == 0), dtype=jnp.int32))
    return {k: jnp.stack(v) for k, v in out.items()}


def _gather(tree, idx, layout):
    """tree entries at flat indices idx (k,) int32."""
    out = jnp.zeros(idx.shape, jnp.float32)
    for leaf, (off, size) in zip(jax.tree_util.tree_leaves(tree),
                                 layout.parts()):
        local = idx - off
        inside = (local >= 0) & (local < size)
        v = leaf.ravel()[jnp.clip(local, 0, size - 1)]
        out = jnp.where(inside, v, out)
    return out


def _scatter(tree, idx, vals, layout, add):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = []
    for leaf, (off, size) in zip(leaves, layout.parts()):
        local = idx - off
        local = jnp.where((local >= 0) & (local < size), local, size)
        flat = leaf.ravel()
        flat = (flat.at[local].add(vals, mode="drop") if add
                else flat.at[local].set(vals, mode="drop"))
        out.append(flat.reshape(leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, out)


def _select(score, k, layout):
    """Flat indices (k,) int32, ascending, of the k largest entries of the
    non-negative tree ``score``; ties at the k-th value go to the lower
    index. The k-th value is found exactly by fixing the bits of its
    float32 pattern from the top, each by one count over the tree."""
    keys = [jax.lax.bitcast_convert_type(l.ravel(), jnp.uint32)
            for l in jax.tree_util.tree_leaves(score)]

    def count_ge(t):
        return sum(jnp.sum(kl >= t, dtype=jnp.int32) for kl in keys)

    def bit(i, t):
        cand = t | jnp.left_shift(jnp.uint32(1), (31 - i).astype(jnp.uint32))
        return jnp.where(count_ge(cand) >= k, cand, t)

    t = jax.lax.fori_loop(0, 32, bit, jnp.uint32(0))
    need = k - sum(jnp.sum(kl > t, dtype=jnp.int32) for kl in keys)
    parts, taken_eq = [], jnp.zeros((), jnp.int32)
    for kl, (off, size) in zip(keys, layout.parts()):
        eq = kl == t
        rank = taken_eq + jnp.cumsum(eq, dtype=jnp.int32)
        taken_eq = taken_eq + jnp.sum(eq, dtype=jnp.int32)
        take = (kl > t) | (eq & (rank <= need))
        local = jnp.nonzero(take, size=min(k, size), fill_value=size)[0]
        parts.append(jnp.where(local < size, local + off, -1))
    cand = jnp.concatenate(parts)
    return cand[jnp.nonzero(cand >= 0, size=k, fill_value=0)[0]]


@partial(jax.jit, static_argnames=("k", "layout", "first", "kind"),
         donate_argnums=(1,))
def _compress(g, err, idx_prev, a_prev_sel, g_prev_sel, omega, mu, *, k,
              layout, first, kind):
    """One worker's sparsification. Returns (vals, idx, new err)."""
    a = jax.tree_util.tree_map(jnp.add, err, g)
    if first or kind == "topk":
        score = a
    else:
        c = jnp.tanh(jnp.abs(1.0 + Q) / mu)
        score = jax.tree_util.tree_map(lambda x: x * c, a)
        a_at = _gather(a, idx_prev, layout)
        den = omega * a_at
        den = jnp.where(jnp.abs(den) > TINY, den,
                        jnp.where(den < 0, -TINY, TINY))
        delta = (g_prev_sel - omega * a_prev_sel) / den
        reg = jnp.tanh(jnp.abs(1.0 + delta) / mu)
        score = _scatter(score, idx_prev, a_at * reg, layout, add=False)
    idx = _select(jax.tree_util.tree_map(jnp.abs, score), k, layout)
    vals = _gather(a, idx, layout)
    err = _scatter(a, idx, jnp.zeros_like(vals), layout, add=False)
    return vals, idx, err


_gather_jit = jax.jit(_gather, static_argnames=("layout",))


@partial(jax.jit, static_argnames=("layout",), donate_argnums=(0,))
def _combine(acc, vals, idx, *, layout):
    return _scatter(acc, idx, vals, layout, add=True)


@partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam(p, m, v, g_sum, n, t, lr):
    g = jax.tree_util.tree_map(lambda x: x / n, g_sum)
    m = jax.tree_util.tree_map(lambda a, b: ADAM_B1 * a + (1 - ADAM_B1) * b,
                               m, g)
    v = jax.tree_util.tree_map(
        lambda a, b: ADAM_B2 * a + (1 - ADAM_B2) * b * b, v, g)
    c1, c2 = 1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t
    p = jax.tree_util.tree_map(
        lambda x, a, b: x - lr * (a / c1) / (jnp.sqrt(b / c2) + ADAM_EPS),
        p, m, v)
    return p, m, v, g


@partial(jax.jit, static_argnames=("family", "cfg", "mode"))
def _row_grad(params, tokens, targets, scale, *, family, cfg, mode):
    def f(p):
        return family.nll(p, tokens, targets, cfg, mode) * scale
    return jax.value_and_grad(f)(params)


@partial(jax.jit, donate_argnums=(0,))
def _add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def train(family, cfg, job, params0, batches, n_workers, devices, *, steps,
          mode="f32", fault=None):
    """Trains ``steps`` steps from ``params0`` on ``batches`` [(tokens,
    targets)] of global shape (n_workers * rows, S), worker n taking rows
    n*rows .. (n+1)*rows. ``job`` names kind ("regtopk" | "topk"),
    sparsity, mu and lr. Returns the per-step mean loss over workers, the
    per-leaf norms of worker 0's first dense gradient, the first combined
    gradient as (flat indices, values) of its support, and the parameters
    after the steps (on devices[0]).

    ``fault`` plants a fault, for reading what it does to the compared
    numbers: "flip_update" applies every Adam update with the wrong sign,
    "double_update" twice as far as Adam says."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known {FAULTS}")
    kind = job["sparsifier"]
    if kind not in ("regtopk", "topk"):
        raise NotImplementedError(f"reference sparsifier {kind!r}")
    layout = Layout(params0)
    k = max(1, int(round(job["sparsity"] * layout.total)))
    omega = 1.0 / n_workers
    mu = float(job.get("mu", 0.5))
    static = Static(cfg)
    dev0 = devices[0]
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    p = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))(
        jax.device_put(params0, dev0))   # Adam donates p; params0 stays
    m, v = zeros(p), zeros(p)
    work = [{"dev": devices[n % len(devices)]} for n in range(n_workers)]
    for w in work:
        w["err"] = jax.device_put(zeros(p), w["dev"])
        w["post"] = (jnp.zeros((k,), jnp.int32), jnp.zeros((k,)),
                     jnp.zeros((k,)))
    losses, out = [], {}
    for t in range(steps):
        tokens, targets = (np.asarray(x) for x in batches[t])
        rows = tokens.shape[0] // n_workers
        step_losses = []
        for n, w in enumerate(work):
            pw = jax.device_put(p, w["dev"])
            r0 = n * rows
            count = int(np.sum(targets[r0:r0 + rows] >= 0))
            g = None
            for r in range(r0, r0 + rows):
                loss, gr = _row_grad(pw, tokens[r], targets[r], 1.0 / count,
                                     family=family, cfg=static, mode=mode)
                step_losses.append(loss)
                g = gr if g is None else _add(g, gr)
            del pw
            if t == 0 and n == 0:
                out["dense_g1_leaf_norms"] = np.asarray(_leaf_norms(g))
            post = jax.device_put(w["post"], w["dev"])
            w["vals"], w["idx"], w["err"] = _compress(
                g, w["err"], *post, omega, mu, k=k, layout=layout,
                first=(t == 0), kind=kind)
            del g
        acc = zeros(p)
        for w in work:
            acc = _combine(acc, jax.device_put(w["vals"], dev0),
                           jax.device_put(w["idx"], dev0),
                           layout=layout)
        lr = job["lr"] * {"flip_update": -1, "double_update": 2}.get(fault, 1)
        p, m, v, g_agg = _adam(p, m, v, acc, float(n_workers),
                               float(t + 1), float(lr))
        del acc
        for w in work:
            idx = jax.device_put(w["idx"], dev0)
            g_sel = _gather_jit(g_agg, idx, layout=layout)
            w["post"] = (w["idx"], w["vals"], g_sel)
        if t == 0:
            idx = np.unique(np.concatenate(
                [np.asarray(w["idx"]) for w in work]))
            vals = _gather_jit(g_agg, jax.device_put(idx.astype(np.int32),
                                                     dev0), layout=layout)
            out["g1"] = (idx, np.asarray(vals))
        del g_agg
        losses.append(sum(float(x) for x in step_losses) / n_workers)
    out["losses"] = losses
    out["params"] = p
    out["k"] = k
    return out
