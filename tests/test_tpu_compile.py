"""The main path's Pallas kernels compile for a TPU v5e chip.

Nothing runs: each case lowers and compiles for a described (not attached)
``v5e:2x2`` chip at the shapes the round programs use, which is where the
TPU compiler refuses tiles that interpret mode accepts.  The topology is
described inside a fixture, never at import: only one process may load the
TPU library, and the test workers all import this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("lanes,rows,vocab", [
    (8, 32, 10),      # MNIST CNN logits, packed lanes (vmap over the lane axis)
    (8, 64, 6),       # HAR CNN logits
])
def test_vmapped_kd_loss_and_grad_compile(one_chip, lanes, rows, vocab):
    """The packed engine's call: ``vmap`` over lanes of the fused loss and
    its custom-vjp gradient (``fed/sharded.make_packed_kd_round``)."""
    f = jax.jit(jax.vmap(jax.value_and_grad(
        lambda s, t, y: ops.kd_distillation_loss(s, t, y, 2.0, 0.5, False))))
    logits = _sds((lanes, rows, vocab), jnp.float32, one_chip)
    labels = _sds((lanes, rows), jnp.int32, one_chip)
    compiled = f.lower(logits, logits, labels).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dim", [
    2560,             # MNIST student head weight (256 x 10), 512-column blocks
    200,              # a leaf narrower than one block
])
def test_fused_merge_compiles(one_chip, dim):
    f = jax.jit(lambda x, w, s: ops.fused_merge(x, w, s, decay=0.5,
                                                interpret=False))
    compiled = f.lower(_sds((8, dim), jnp.float32, one_chip),
                       _sds((8,), jnp.float32, one_chip),
                       _sds((8,), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
