"""One run of one cell: set-up, the measured window, and the check.

Set-up builds the step and its state from the seed, and drives that same
state through the check steps with the window's own call and feed; the
window then goes on from there. After the window the state is freed and
the plain reference (``reference/``) trains the check steps from the same
weights and batches; ``checks`` compares the two.
"""
import importlib
import os
import sys
import time
from functools import partial

import numpy as np

from cells import ROOT

CHECK_STEPS = 3


def keys_from_seed(seed):
    """Keys for weights, data and the step from any whole seed (more than
    32 bits): the low 31 bits seed the key, the rest is folded in."""
    import jax
    base = jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)
    return {name: jax.random.fold_in(base, i)
            for i, name in enumerate(("weights", "data", "step"))}


def make_batch_fn(cell, vocab, sharding):
    """Batch t of the cell's traffic: fresh uniform token rows from the
    data key, one per sequence, with next-token targets and the last
    position unscored. One compiled program serves every t."""
    import jax
    import jax.numpy as jnp
    tr = cell["traffic"]
    rows, seq = tr["batch_per_chip"] * cell["chips"], tr["seq"]

    @partial(jax.jit, out_shardings=sharding)
    def batch(key, t):
        tokens = jax.random.randint(jax.random.fold_in(key, t), (rows, seq),
                                    0, vocab, jnp.int32)
        targets = jnp.concatenate(
            [tokens[:, 1:], jnp.full((rows, 1), -1, jnp.int32)], 1)
        return {"tokens": tokens, "targets": targets}

    return lambda key, t: batch(key, jnp.int32(t))


def family(cell):
    return importlib.import_module("reference." + cell["config"]["reference"])


def require_chips(chips):
    """The TPU devices for the cell, or exit 2 with no result."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"[bench] needs {chips} TPU chip(s); JAX found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        sys.exit(2)
    return devs[:chips]


def enable_cache():
    """JAX's persistent compile cache, in the directory that
    JAX_COMPILATION_CACHE_DIR names, else in ``.jax_cache/`` of the
    checkout. It is set in JAX's config here, since JAX reads the variable
    only when it is imported, and the harness imports it before this.
    Every program is cached, so a second run of a cell compiles nothing."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class Run:
    """Set-up of one cell at one seed: the compiled step, its state after
    the check steps, and what the check needs from them."""

    def __init__(self, cell, seed, devices, built=None):
        import jax
        from program import Program
        from reference.train import init_params
        self.cell, self.seed, self.devices = cell, seed, devices
        self.keys = keys_from_seed(seed)
        self.prog = built[0] if built else Program(cell)
        self.family = family(cell)
        self.tr = cell["traffic"]
        self.batch = make_batch_fn(cell, cell["config"]["vocab_size"],
                                   self.prog.batch_sharding)
        init = jax.jit(partial(init_params, self.family, cell["config"]),
                       out_shardings=self.prog.shardings[0])
        self.init_params = init
        make = jax.jit(self.prog.state_from, donate_argnums=0,
                       out_shardings=self.prog.shardings)
        state = make(init(self.keys["weights"]))
        self.step_key = jax.device_put(self.keys["step"],
                                       self.prog.replicated)
        b0 = self.batch(self.keys["data"], 0)
        if built:
            self.step = built[1]
        else:
            from trace_reduce import load_layers
            self.step = self.prog.compile(state, b0, self.step_key,
                                          scopes=load_layers())
        self.state, self.t = state, 0
        self.check_losses, self.g1 = [], None
        for _ in range(CHECK_STEPS):
            m = self.advance()
            self.check_losses.append(float(m["loss"]))
            if self.g1 is None:
                self.g1 = self.prog.first_grad(self.state[1])
        self.p3 = jax.device_get(self.state[0])
        self.tokens_per_step = self.tr["batch_per_chip"] * \
            cell["chips"] * self.tr["seq"]

    def advance(self):
        batch = self.batch(self.keys["data"], self.t)
        *self.state, metrics = self.step(*self.state, batch, self.step_key)
        self.t += 1
        return metrics

    def window(self, seconds=None, steps=None, annotate=False,
               keep_metrics=False):
        """Steps dispatched back to back, the loss fetched every
        ``loss_every`` steps, the end blocking on the last step: until
        ``seconds`` have passed, or for ``steps`` steps. Returns (steps,
        seconds, fetched losses); with ``keep_metrics`` also each step's
        metrics dict, as device arrays that no host sync in the window
        has read."""
        import jax
        every = self.tr["loss_every"]
        ann = (jax.profiler.TraceAnnotation if annotate
               else _NoAnnotation)
        losses, kept, n = [], [], 0
        t0 = time.perf_counter()
        while True:
            with ann("dispatch"):
                m = self.advance()
            if keep_metrics:
                kept.append(m)
            n += 1
            if n % every == 0:
                with ann("loss_fetch"):
                    losses.append(float(m["loss"]))
            if (n >= steps if steps is not None
                    else time.perf_counter() - t0 >= seconds):
                break
        with ann("block"):
            jax.block_until_ready((self.state, m))
        secs = time.perf_counter() - t0
        if keep_metrics:
            return n, secs, losses, kept
        return n, secs, losses

    def free_state(self):
        import jax
        for x in jax.tree_util.tree_leaves(self.state):
            x.delete()
        self.state = None

    def reference(self):
        """The program's readings over the check steps against the
        reference's, both from the same initial weights; and the
        reference's."""
        import jax
        ref = reference_run(self.cell, self.keys, self.prog, self.devices)
        prog = {"losses": self.check_losses, "g1": self.g1,
                "params": jax.device_put(self.p3, self.devices[0])}
        return against(prog, ref), ref


def reference_run(cell, keys, prog, devices, mode="f32", fault=None):
    """The reference trained over the check steps from the cell's initial
    weights (``p0``, on devices[0]) and batches for ``keys``; returns its
    readings, its parameters after the steps and ``p0``."""
    import jax
    from reference.train import init_params, train
    fam = family(cell)
    p0 = jax.device_put(jax.jit(partial(init_params, fam, cell["config"]))(
        keys["weights"]), devices[0])
    batch = make_batch_fn(cell, cell["config"]["vocab_size"],
                          prog.batch_sharding)
    batches = [tuple(np.asarray(b[x]) for x in ("tokens", "targets"))
               for b in (batch(keys["data"], t) for t in range(CHECK_STEPS))]
    ref = train(fam, cell["config"], prog.job(), p0, batches, cell["chips"],
                devices, steps=CHECK_STEPS, mode=mode, fault=fault)
    ref["p0"] = p0
    return ref


def against(side, ref):
    """What ``checks`` compares of ``side`` (losses, first gradient and
    parameters after the check steps), with its parameter change read
    against ``ref``'s."""
    from reference.train import change_readings
    change = change_readings(side["params"], ref["params"], ref["p0"])
    return {"losses": side["losses"], "g1": side["g1"],
            "change": {k: np.asarray(v) for k, v in change.items()}}


class _NoAnnotation:
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
