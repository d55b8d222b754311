"""The system under test, as the benchmark builds and drives it.

The run is built the way a user's launch builds it: the traffic file's
``launcher`` flags (with ``--data`` set to the cell's chips) go through
``repro.launch.train.parse_args`` and ``build_run``, and the model is the
registered arch with the configuration file's ``reduced`` keys set. The
step is ``repro.train.step.build_train_step``'s, jitted with the state
donated. Nothing else of the program is used.
"""
import contextlib
import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P


def launcher_argv(cell):
    argv = ["--arch", cell["config"]["arch"], "--data", str(cell["chips"])]
    for key, value in cell["traffic"]["launcher"].items():
        argv += ["--" + key.replace("_", "-"), str(value)]
    return argv


def model_config(conf, reduced):
    """The registered arch with the file's ``reduced`` keys set; every
    other number in the file has to equal the registered one."""
    from repro.configs.base import get_config
    cfg = get_config(conf["arch"])
    fields = {f.name for f in dataclasses.fields(cfg)}
    cfg = dataclasses.replace(
        cfg, **{k: conf[k] for k in reduced if k in fields})
    for key, want in conf.items():
        if key not in fields or key in ("name", "source"):
            continue
        have = getattr(cfg, key)
        if dataclasses.is_dataclass(have):
            have = dataclasses.asdict(have)
            want = {k: want[k] for k in have if k in want}
            have = {k: have[k] for k in want}
        if have != want:
            raise ValueError(f"{conf['arch']}.{key}: the configuration file "
                             f"says {want!r}, the registered arch {have!r}")
    return cfg


@contextlib.contextmanager
def layer_scopes(table):
    """While inside, each (module, attribute, layer) of ``table["scopes"]``
    is wrapped in ``jax.named_scope(table["scope_prefix"] + layer)``. A
    listed attribute that is missing or not callable is an error, so that
    a rename in the program cannot move ops between layers unseen."""
    saved = []
    for mod_name, attr, layer in (table or {}).get("scopes", []):
        owner = importlib.import_module(mod_name)
        *path, name = attr.split(".")
        for part in path:
            owner = getattr(owner, part)
        fn = getattr(owner, name)
        if not callable(fn):
            raise TypeError(f"layers.json scope {mod_name}.{attr} is not "
                            f"callable")
        saved.append((owner, name, fn))
        setattr(owner, name, _scoped(fn, table["scope_prefix"] + layer))
    try:
        yield
    finally:
        for owner, name, fn in reversed(saved):
            setattr(owner, name, fn)


def _scoped(fn, scope):
    @functools.wraps(fn)
    def inner(*args, **kwargs):
        with jax.named_scope(scope):
            return fn(*args, **kwargs)
    return inner


class Program:
    """One built run: mesh, step, and the shapes of its state."""

    def __init__(self, cell):
        from repro.launch.mesh import make_mesh
        from repro.launch.train import build_run, parse_args
        from repro.train.step import (build_parallel, build_train_step,
                                      init_train_state, train_state_specs)
        run = build_run(parse_args(launcher_argv(cell)))
        self.run = dataclasses.replace(
            run, model=model_config(cell["config"], cell["reduced"]))
        dp = cell["chips"]
        self.mesh = make_mesh(dp, 1)
        pal = build_parallel(self.mesh)
        step, _, _ = build_train_step(self.run, self.mesh, pal)
        _, pspecs, ospecs, especs = train_state_specs(self.run, self.mesh,
                                                      pal)
        with jax.set_mesh(self.mesh):
            self.abstract = jax.eval_shape(
                lambda k: init_train_state(self.run, self.mesh, pal, k),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
        named = lambda t: jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), t,
            is_leaf=lambda x: isinstance(x, P))
        self.shardings = (named(pspecs), named(ospecs), named(especs))
        self.batch_sharding = NamedSharding(self.mesh, P("data", None))
        self.replicated = NamedSharding(self.mesh, P())
        self.step_fn = step
        self.j_local = sum(int(l.size) for l in jax.tree_util.tree_leaves(
            self.abstract[0]))

    def state_from(self, params):
        """(params, opt_state, ef_state) for the step from ``params`` in
        the program's tree layout: the optimizer's master copy is the
        flattened parameters, and every other optimizer and sparsifier
        entry starts at zero, as the program's own initialiser makes
        them. Checks the tree against the program's."""
        want = jax.tree_util.tree_structure(self.abstract[0])
        have = jax.tree_util.tree_structure(params)
        if want != have:
            raise ValueError(f"parameter tree differs from the program's:\n"
                             f"{have}\n{want}")
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(self.abstract[0])):
            if a.shape != b.shape or a.dtype != b.dtype:
                raise ValueError(f"parameter leaf {a.shape} {a.dtype} differs "
                                 f"from the program's {b.shape} {b.dtype}")
        flat = jnp.concatenate([l.ravel().astype(jnp.float32)
                                for l in jax.tree_util.tree_leaves(params)])

        def opt_leaf(path, a):
            if jax.tree_util.keystr(path) == "['master']":
                return jnp.pad(flat, (0, a.size - flat.size)).reshape(a.shape)
            return jnp.zeros(a.shape, a.dtype)

        opt = jax.tree_util.tree_map_with_path(opt_leaf, self.abstract[1])
        ef = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype),
                                    self.abstract[2])
        return params, opt, ef

    def compile(self, state, batch, key, scopes=None):
        """The jitted step, traced with the calls into each layer wrapped
        in the named scopes of ``scopes`` (layers.json) so that the
        trace can attribute its ops; the scopes change only metadata."""
        with layer_scopes(scopes), jax.set_mesh(self.mesh):
            return jax.jit(self.step_fn, donate_argnums=(0, 1, 2)).lower(
                *state, batch, key).compile()

    def first_grad(self, opt):
        """The gradient Adam was given at its first step, from its first
        moment m = (1 - b1) g, as host (indices, values) of its non-zero
        entries in the flat parameter order."""
        m = np.asarray(jax.device_get(opt["m"])).reshape(-1)[:self.j_local]
        idx = np.flatnonzero(m)
        return idx, m[idx] / (1 - self.run.optimizer.b1)

    def job(self):
        """The settings the reference trains with, as the program resolved
        them. Refuses a run whose other settings depart from what the
        reference implements (bias-corrected Adam at a constant rate, an
        exact global selection, float32 state and wire, every worker in
        every step)."""
        from reference.train import ADAM_B1, ADAM_B2, ADAM_EPS, Q
        sp, opt = self.run.sparsifier, self.run.optimizer
        want = {"optimizer": ("adam", opt.kind), "b1": (ADAM_B1, opt.b1),
                "b2": (ADAM_B2, opt.b2), "eps": (ADAM_EPS, opt.eps),
                "schedule": ("constant", opt.schedule),
                "warmup_steps": (0, opt.warmup_steps),
                "weight_decay": (0.0, opt.weight_decay),
                "grad_clip": (0.0, opt.grad_clip), "Q": (Q, sp.Q),
                "k": (0, sp.k), "allocation": ("global", sp.allocation),
                "selector": ("exact", sp.selector),
                "ef_dtype": ("float32", sp.ef_dtype),
                "wire_dtype": ("float32", sp.wire_dtype),
                "combine": ("mean", sp.combine),
                "fault_schedule": ("", self.run.fault_schedule)}
        off = {k: v for k, v in want.items() if v[0] != v[1]}
        if off:
            raise ValueError(f"the reference does not implement "
                             f"(reference, program): {off}")
        return {"sparsifier": sp.kind, "sparsity": sp.sparsity, "mu": sp.mu,
                "lr": opt.lr}
