"""Compiles for a described TPU v5e chip; no chip is needed.

The TPU compiler is installed with JAX, and it compiles for a chip that is
described and not attached. These tests compile the compress path the train
step runs on a TPU at a real flat-gradient size, so a kernel or lowering the
chip refuses fails here and not on the chip. The topology is described
inside a fixture, never at import: only one process at a time may load the
TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.compress import ops as cops

J = 1 << 20
K = J // 1000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        # the TPU library otherwise writes its logs under /tmp
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _regtopk_args(one_chip):
    return (_spec((J,), jnp.float32, one_chip),          # g
            _spec((J,), jnp.float32, one_chip),          # err_prev
            _spec((), jnp.int32, one_chip),              # step
            _spec((K,), jnp.uint32, one_chip),           # idx_prev
            _spec((K,), jnp.float32, one_chip),          # a_prev_sel
            _spec((K,), jnp.float32, one_chip))          # g_prev_sel


def _regtopk(strategy):
    def f(g, err, step, idx_prev, a_prev_sel, g_prev_sel):
        out = cops.fused_compress_arrays(
            "regtopk", g, err, step, k=K, mu=0.5, idx_prev=idx_prev,
            a_prev_sel=a_prev_sel, g_prev_sel=g_prev_sel, want_ghat=False,
            strategy=strategy)
        return out["err"], out["values"], out["indices"]
    return f


def test_default_strategy_compiles_fused_regtopk(one_chip):
    """The fused REGTOP-k compress, under the strategy a TPU gets, compiles
    for one v5e chip at J = 2^20, k = J/1000, as plain XLA: no Pallas
    kernel (native or interpreted) is left in the program."""
    strategy = cops.default_strategy()
    compiled = jax.jit(_regtopk(strategy), donate_argnums=(1,)).lower(
        *_regtopk_args(one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 2 * J * 4
    hlo = compiled.as_text()
    assert "tpu_custom_call" not in hlo


def test_sketch_encode_compiles(one_chip):
    """The CountSketch encode of the XLA strategy compiles for the chip."""
    g = _spec((J,), jnp.float32, one_chip)
    f = lambda g, e: cops.fused_sketch_encode(g, e, rows=3, width=4 * K)
    compiled = jax.jit(f).lower(g, g).compile()
    assert "tpu_custom_call" not in compiled.as_text()


def test_pallas_strategy_refuses_before_tracing(one_chip):
    """strategy="pallas" never falls back to interpret mode: it names the
    compiler's refusal before anything traces."""
    with pytest.raises(NotImplementedError, match="does not compile for TPU"):
        jax.jit(_regtopk("pallas")).lower(*_regtopk_args(one_chip))
