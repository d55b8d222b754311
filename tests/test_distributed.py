"""Distributed correctness tests — run in SUBPROCESSES so they can set
--xla_force_host_platform_device_count without polluting the main test
process (which must keep seeing 1 device)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


COMMON = """
import warnings; warnings.filterwarnings("ignore")
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs.base import (get_config, reduced_config, RunConfig,
                                SparsifierConfig, OptimizerConfig, SHAPES)
from repro.train.step import build_parallel, build_train_step, init_train_state
from repro.data import lm_batch
from repro.launch.mesh import make_mesh

def make_run(arch, sp_kind="regtopk", comm="simulate", opt="adam", sparsity=0.05):
    cfg = reduced_config(get_config(arch))
    if cfg.moe is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=16.0))
    return RunConfig(model=cfg, shape=SHAPES["train_4k"],
        sparsifier=SparsifierConfig(kind=sp_kind, sparsity=sparsity, mu=0.5,
                                    comm_mode=comm, selector="exact"),
        optimizer=OptimizerConfig(kind=opt, lr=1e-3))

def train(run, mesh_shape, steps=3, key_seed=0, fixed_batch=False):
    # fixed_batch: uniform-random token streams carry no cross-batch signal;
    # convergence assertions must overfit one batch to be meaningful
    mesh = make_mesh(*mesh_shape)
    pal = build_parallel(mesh)
    key = jax.random.PRNGKey(key_seed)
    with jax.set_mesh(mesh):
        params, opt_state, ef_state = init_train_state(run, mesh, pal, key)
        step, _, _ = build_train_step(run, mesh, pal)
        jstep = jax.jit(step)
        losses = []
        for t in range(steps):
            batch = lm_batch(run.model, 8, 64, 0, 0 if fixed_batch else t)
            params, opt_state, ef_state, m = jstep(
                params, opt_state, ef_state, batch, key)
            losses.append(float(m["loss"]))
    return losses, m
"""


def test_dp_equivalence_dense_sync():
    """dp=4 with dense sync must equal dp=1 (grad averaging is exact)."""
    out = run_py(COMMON + """
run = make_run("stablelm-3b", sp_kind="none")
l1, _ = train(run, (1, 1))
l4, _ = train(run, (4, 1))
assert np.allclose(l1, l4, rtol=2e-4), (l1, l4)
print("OK", l1[-1])
""")
    assert "OK" in out


def test_sparse_comm_equals_simulate():
    """allgather(values, idx) + scatter-add == masked dense all-reduce."""
    out = run_py(COMMON + """
r1 = make_run("stablelm-3b", comm="simulate")
r2 = make_run("stablelm-3b", comm="sparse")
l1, _ = train(r1, (4, 2), steps=4)
l2, _ = train(r2, (4, 2), steps=4)
assert np.allclose(l1, l2, rtol=1e-4), (l1, l2)
print("OK", l1, l2)
""")
    assert "OK" in out


@pytest.mark.parametrize("arch", ["stablelm-3b", "jamba-v0.1-52b",
                                  "xlstm-125m", "deepseek-v2-lite-16b"])
def test_tp_matches_single_device(arch):
    """Sharded (2,4) forward loss == single-device on reassembled params."""
    out = run_py(COMMON + f"""
from repro.models import Parallel, loss_fn
run = make_run("{arch}", sp_kind="none", opt="sgd")
run = dataclasses.replace(run, optimizer=OptimizerConfig(kind="sgd", lr=1e-2))
mesh = make_mesh(2, 4)
pal = build_parallel(mesh)
key = jax.random.PRNGKey(0)
with jax.set_mesh(mesh):
    params, opt_state, ef_state = init_train_state(run, mesh, pal, key)
    step, _, _ = build_train_step(run, mesh, pal)
    batch = lm_batch(run.model, 8, 64, 0, 0)
    p2, o2, e2, m = jax.jit(step)(params, opt_state, ef_state, batch, key)
host = jax.tree_util.tree_map(lambda x: jnp.asarray(np.array(x)), params)
lref, _ = jax.jit(lambda p, b: loss_fn(p, b, run.model, Parallel()))(host, batch)
d = abs(float(m["loss"]) - float(lref))
assert d < 5e-3, d
# one-step param update vs reference gradient
gref = jax.jit(jax.grad(lambda p: loss_fn(p, batch, run.model, Parallel())[0]))(host)
import jax.flatten_util as fu
v_ref = fu.ravel_pytree(jax.tree_util.tree_map(lambda p, g: p - 0.01*g, host, gref))[0]
v_new = fu.ravel_pytree(jax.tree_util.tree_map(
    lambda x: jnp.asarray(np.array(x)), p2))[0]
du = float(jnp.max(jnp.abs(v_ref - v_new)))
assert du < 5e-4, du
print("OK", d, du)
""")
    assert "OK" in out


def test_bucketed_sparse_comm_matches_flat():
    """num_buckets > 1 chunked all-gather + scatter-add == the monolithic
    sparse path AND the simulate path, with REAL axis size > 1 (rank
    stacking, replicated padded tails)."""
    out = run_py(COMMON + """
run_sim = make_run("stablelm-3b", comm="simulate")
run_b1 = make_run("stablelm-3b", comm="sparse")
run_b4 = dataclasses.replace(run_b1, sparsifier=dataclasses.replace(
    run_b1.sparsifier, pipeline="fused", num_buckets=4))
l_sim, _ = train(run_sim, (4, 2), steps=4)
l_b1, _ = train(run_b1, (4, 2), steps=4)
l_b4, m = train(run_b4, (4, 2), steps=4)
assert np.allclose(l_b1, l_b4, rtol=1e-4), (l_b1, l_b4)
assert np.allclose(l_sim, l_b4, rtol=1e-4), (l_sim, l_b4)
assert 0 < float(m["agg_nonzero"]) < 0.5
print("OK", l_b1, l_b4)
""")
    assert "OK" in out


def test_regtopk_trains_distributed():
    out = run_py(COMMON + """
run = make_run("stablelm-3b", sp_kind="regtopk", comm="sparse", sparsity=0.02)
losses, m = train(run, (4, 2), steps=10, fixed_batch=True)
assert losses[-1] < losses[0], losses
assert 0 < float(m["agg_nonzero"]) < 0.3
print("OK", losses[0], losses[-1])
""")
    assert "OK" in out


def test_serve_decode_sharded_batch():
    """decode step under shard_map, batch over data + heads over model."""
    out = run_py(COMMON + """
from repro.serve.step import build_decode_step, build_prefill, serve_parallel
from repro.models import init_params, prefill as mprefill, decode_step as mdecode
from repro.models import Parallel
from jax.sharding import PartitionSpec as P
from repro.models.specs import param_specs

run = make_run("granite-8b", sp_kind="none")
run = dataclasses.replace(run, shape=dataclasses.replace(
    SHAPES["decode_32k"], seq_len=64, global_batch=8))
mesh = make_mesh(4, 2)
pal = serve_parallel(mesh, run, decode=True)
assert pal.cache_seq_axis is None
with jax.set_mesh(mesh):
    tmpl = __import__("repro.train.step", fromlist=["x"]).abstract_params(run, pal)
    pspecs = param_specs(tmpl)
    def init_fn(k):
        kf = jax.random.fold_in(k, jax.lax.axis_index("model"))
        from repro.models.specs import replicated_mask
        pu = init_params(run.model, pal, k)
        pf = init_params(run.model, pal, kf)
        return jax.tree_util.tree_map(lambda u, f, r: u if r else f, pu, pf,
                                      replicated_mask(pu))
    params = jax.jit(jax.shard_map(
        init_fn, mesh=mesh, in_specs=(P(),), out_specs=pspecs,
        check_vma=False))(jax.random.PRNGKey(0))
    pre, _ = build_prefill(run, mesh, pal)
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (8, 63), 0, run.model.vocab_size)}
    logits, cache = jax.jit(pre)(params, batch)
    dec, _ = build_decode_step(run, mesh, pal)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    logits2, cache = jax.jit(dec)(params, cache, tok)
    assert logits2.shape[0] == 8
    assert not bool(jnp.isnan(logits2).any())
    # reference: single-device
    host = jax.tree_util.tree_map(lambda x: jnp.asarray(np.array(x)), params)
    pal1 = Parallel()
    lg1, c1 = mprefill(host, batch, run.model, pal1, max_seq=64)
    lg2, _ = mdecode(host, c1, tok, run.model, pal1)
    scale = float(jnp.max(jnp.abs(lg2))) + 1e-6
    err = float(jnp.max(jnp.abs(np.array(logits2)[:, :run.model.vocab_size] -
                                np.array(lg2)[:, :run.model.vocab_size]))) / scale
    assert err < 5e-3, err
print("OK")
""")
    assert "OK" in out


def test_decode_context_parallel_cache():
    """batch=1 decode: cache seq-sharded over data with LSE merge — must
    match the single-device decode."""
    out = run_py(COMMON + """
from repro.serve.step import build_decode_step, serve_parallel, decode_cache_specs
from repro.models import (init_params, prefill as mprefill,
                          decode_step as mdecode, Parallel)
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.models.specs import param_specs

run = make_run("granite-8b", sp_kind="none")
run = dataclasses.replace(run, shape=dataclasses.replace(
    SHAPES["long_500k"], seq_len=64, global_batch=1))
mesh = make_mesh(4, 2)
pal = serve_parallel(mesh, run, decode=True)
assert pal.cache_seq_axis == "data"
# single-device reference prefill builds the cache; shard it onto the mesh
pal1 = Parallel()
params1 = init_params(run.model, pal1, jax.random.PRNGKey(0))
batch = {"tokens": jax.random.randint(
    jax.random.PRNGKey(1), (1, 48), 0, run.model.vocab_size)}
lg1, c1 = mprefill(params1, batch, run.model, pal1, max_seq=64)
tok = jnp.argmax(lg1, -1)[:, None].astype(jnp.int32)
lg_ref, _ = mdecode(params1, c1, tok, run.model, pal1)

# sharded: tp=1 on model axis? use (4,1) mesh to isolate ctx-parallel over data
mesh = make_mesh(4, 1)
pal = serve_parallel(mesh, run, decode=True)
with jax.set_mesh(mesh):
    dec, (pspecs, cspecs, tok_spec) = build_decode_step(run, mesh, pal)
    cache_sharded = jax.device_put(c1, jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), cspecs))
    params_sharded = jax.device_put(params1, NamedSharding(mesh, P()))
    lg2, _ = jax.jit(dec)(params_sharded, cache_sharded, tok)
err = (float(jnp.max(jnp.abs(np.array(lg2) - np.array(lg_ref))))
       / (float(jnp.max(jnp.abs(lg_ref))) + 1e-6))
assert err < 5e-3, err
print("OK", err)
""")
    assert "OK" in out


def test_multipod_mesh_small():
    """3-axis (pod, data, model) mesh trains and matches 2-axis semantics."""
    out = run_py(COMMON + """
run = make_run("stablelm-3b", sp_kind="topk", comm="sparse", sparsity=0.1)
mesh3 = make_mesh(2, 2, pods=2)
pal3 = build_parallel(mesh3)
key = jax.random.PRNGKey(0)
with jax.set_mesh(mesh3):
    params, opt_state, ef_state = init_train_state(run, mesh3, pal3, key)
    step, _, _ = build_train_step(run, mesh3, pal3)
    jstep = jax.jit(step)
    losses = []
    for t in range(10):
        batch = lm_batch(run.model, 8, 64, 0, t)
        params, opt_state, ef_state, m = jstep(params, opt_state, ef_state, batch, key)
        losses.append(float(m["loss"]))
import math
assert all(math.isfinite(l) for l in losses)
assert min(losses[5:]) < losses[0], losses
print("OK", losses)
""")
    assert "OK" in out


def test_elastic_fault_injection_trains():
    """30% iid worker drop (decayed EF) still overfits the fixed batch,
    within tolerance of the full-participation run, and the step metrics
    report the fluctuating active count."""
    out = run_py(COMMON + """
import math
run = make_run("stablelm-3b", sp_kind="regtopk", comm="sparse", sparsity=0.05)
run = dataclasses.replace(run, sparsifier=dataclasses.replace(
    run.sparsifier, err_decay=0.9))
run_f = dataclasses.replace(run, fault_schedule="iid:0.3,seed=0")
l_full, _ = train(run, (4, 2), steps=12, fixed_batch=True)
l_drop, m = train(run_f, (4, 2), steps=12, fixed_batch=True)
assert all(math.isfinite(l) for l in l_drop), l_drop
assert l_drop[-1] < l_drop[0], l_drop
# convergence contract: the faulted run's progress stays within 35% of
# the full-participation run's progress on the same overfit batch
prog_full = l_full[0] - l_full[-1]
prog_drop = l_drop[0] - l_drop[-1]
assert prog_full > 0, l_full
assert prog_drop > 0.65 * prog_full, (l_full, l_drop)
assert 0 < float(m["n_active"]) <= 4
print("OK", prog_full, prog_drop)
""")
    assert "OK" in out


def test_elastic_nonfinite_payload_guard():
    """A worker whose gradient goes NaN is dropped for the step by the
    payload guard: the aggregate stays finite, n_active excludes it, and
    the health counter reports exactly one drop."""
    out = run_py(COMMON + """
from jax.sharding import PartitionSpec as P
from repro.core import aggregate as agg
from repro.core import sparsify
cfg = SparsifierConfig(kind="topk", sparsity=0.02, comm_mode="sparse",
                       selector="exact", pipeline="fused")
j = 4096
mesh = jax.make_mesh((8,), ("data",))
g = jax.random.normal(jax.random.PRNGKey(0), (8, j), jnp.float32)
g = g.at[3].set(jnp.nan)                       # worker 3 poisoned
def body(gw):
    gw = gw.reshape(-1)
    state = sparsify.init_state(cfg, j)
    g_agg, _, stats = agg.GradientSync(cfg, ("data",))(
        state, gw, participate=jnp.ones((), jnp.bool_), with_stats=True)
    return g_agg, stats["n_active"], stats["dropped_nonfinite"]
with mesh:
    g_agg, na, dr = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("data"),), out_specs=(P(), P(), P()),
        check_vma=False))(g)
assert np.isfinite(np.array(g_agg)).all()
assert float(np.ravel(na)[0]) == 7.0, na
assert float(np.ravel(dr)[0]) == 1.0, dr
print("OK")
""")
    assert "OK" in out


def test_elastic_combine_bucket_invariant_8dev():
    """Partial participation on a REAL 8-way axis: the chunked elastic
    all-gather combine (num_buckets 1 vs 4) and both combine modes are
    bucketing-invariant."""
    out = run_py(COMMON + """
from jax.sharding import PartitionSpec as P
from repro.core import aggregate as agg
from repro.core import sparsify
j = 4096
mesh = jax.make_mesh((8,), ("data",))
g = jax.random.normal(jax.random.PRNGKey(0), (8, j), jnp.float32)
absent = np.array([0, 0, 1, 0, 0, 1, 0, 0], bool)      # workers 2,5 out
def make(combine, nb):
    cfg = SparsifierConfig(kind="regtopk", sparsity=0.02, mu=0.5,
                           comm_mode="sparse", selector="exact",
                           pipeline="fused", num_buckets=nb,
                           combine=combine, err_decay=0.9)
    def body(gw, pw):
        state = sparsify.init_state(cfg, j)
        g_agg, _ = agg.GradientSync(cfg, ("data",))(
            state, gw.reshape(-1), participate=pw.reshape(()))
        return g_agg
    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(P("data"), P("data")),
                                 out_specs=P(), check_vma=False))
p = jnp.asarray(~absent)
with mesh:
    for combine in ("mean", "support"):
        a1 = np.array(make(combine, 1)(g, p))
        a4 = np.array(make(combine, 4)(g, p))
        np.testing.assert_array_equal(a1, a4)
print("OK")
""")
    assert "OK" in out


@pytest.mark.slow
def test_elastic_long_horizon_convergence():
    """Long-horizon fault-injection contract (CI fault-injection job):
    40 fixed-batch steps under 30% iid drop land within 25% of the
    full-participation loss."""
    out = run_py(COMMON + """
run = make_run("stablelm-3b", sp_kind="regtopk", comm="sparse", sparsity=0.05)
run = dataclasses.replace(run, sparsifier=dataclasses.replace(
    run.sparsifier, err_decay=0.9))
run_f = dataclasses.replace(run, fault_schedule="iid:0.3,seed=1")
l_full, _ = train(run, (4, 2), steps=40, fixed_batch=True)
l_drop, _ = train(run_f, (4, 2), steps=40, fixed_batch=True)
prog_full = l_full[0] - l_full[-1]
prog_drop = l_drop[0] - l_drop[-1]
assert prog_full > 0, l_full
assert prog_drop > 0.75 * prog_full, (l_full[-1], l_drop[-1])
print("OK", l_full[-1], l_drop[-1])
""", timeout=1800)
    assert "OK" in out


def test_delta_apply_sharded_with_psum_health_guard():
    """§2.10 on a real 8-way mesh: versioned deltas scatter into SHARDED
    replica params bit-identically to the host-replica reference (and
    keep their shardings); the payload_health guard evaluates the same
    verdict on every rank and psums into a global health counter; a
    pinned (acquire'd) tree stays bit-unchanged while the live one
    advances."""
    out = run_py(COMMON + """
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.serve.delta import DeltaApplier, DeltaPublisher, payload_health

mesh = make_mesh(4, 2)
key = jax.random.PRNGKey(0)
host = {"w": jax.random.normal(key, (16, 8)),
        "b": jax.random.normal(jax.random.fold_in(key, 1), (64,))}

def walk(tree, t):
    leaves, td = jax.tree_util.tree_flatten(tree)
    k = jax.random.PRNGKey(100 + t)
    return jax.tree_util.tree_unflatten(td, [
        l + 0.1 * jax.random.normal(jax.random.fold_in(k, i), l.shape)
        for i, l in enumerate(leaves)])

with mesh:
    sharded = {
        "w": jax.device_put(host["w"], NamedSharding(mesh, P("model", None))),
        "b": jax.device_put(host["b"], NamedSharding(mesh, P("data"))),
    }
    pub = DeltaPublisher(host, k=24)
    app_host = DeltaApplier(host)
    app_shard = DeltaApplier(sharded)
    cur = host
    for t in range(4):
        cur = walk(cur, t)
        p = pub.publish(cur)
        assert app_host.offer(p) == "applied"
        assert app_shard.offer(p) == "applied"
    pinned, pv = app_shard.acquire()
    frozen = np.array(pinned["w"], copy=True)
    for t in range(4, 8):
        cur = walk(cur, t)
        p = pub.publish(cur)
        app_host.offer(p); app_shard.offer(p)
    # sharded replica == host replica, bit for bit, shardings kept
    for a, b in zip(jax.tree_util.tree_leaves(app_host.params),
                    jax.tree_util.tree_leaves(app_shard.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert app_shard.params["w"].sharding.spec == P("model", None), \
        app_shard.params["w"].sharding
    # the pinned tree never moved
    np.testing.assert_array_equal(np.asarray(pinned["w"]), frozen)
    assert app_shard.version == 8 and pv == 4

    # psum'd intake guard: flip one bit, every rank sees 'corrupt',
    # global counter = 1 drop x 8 ranks
    bad = np.array(p.values, np.float32)
    bad.view(np.uint32)[0] ^= np.uint32(1 << 9)
    def guard(vals, idx):
        ok, corrupt, nonfinite = payload_health(
            vals, idx, jnp.uint32(p.checksum), p.version, p.count, p.j)
        one = lambda b: jax.lax.psum(
            jnp.where(b, 1, 0), ("data", "model"))
        return one(corrupt), one(nonfinite), one(ok)
    c, nf, ok = jax.jit(jax.shard_map(
        guard, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P(), P()),
        check_vma=False))(jnp.asarray(bad), jnp.asarray(p.indices))
    assert int(np.ravel(c)[0]) == 8 and int(np.ravel(nf)[0]) == 0
    c2, nf2, ok2 = jax.jit(jax.shard_map(
        guard, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P(), P()),
        check_vma=False))(jnp.asarray(p.values), jnp.asarray(p.indices))
    assert int(np.ravel(ok2)[0]) == 8 and int(np.ravel(c2)[0]) == 0
print("OK")
""")
    assert "OK" in out
