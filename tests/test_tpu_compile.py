"""The main path's Pallas kernels, and the round programs that call them,
compile for a TPU v5e chip.

Nothing runs: each case lowers and compiles for a described (not attached)
``v5e:2x2`` chip at the shapes the round programs use, which is where the
TPU compiler refuses tiles that interpret mode accepts and where a
program's memory is counted against one chip's HBM.  The topology is
described inside a fixture, never at import: only one process may load the
TPU library, and the test workers all import this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.fed import sharded as sh
from repro.kernels import ops
from repro.optim import adamw
# the certificate's aval builders: the same layout math the strategies use
from tools.shapecert.cert import _model_avals as model_avals
from tools.shapecert.cert import _opt_state_avals as opt_state
from tools.shapecert.cert import _stack as rows


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


# --------------------------------------------- the round programs, whole
# The benchmark cell mnist.fedsikd-paper (bench/configs/mnist-cnn.json):
# Table III's CNNs in float32, 16 clients in one 16-lane wave on one chip,
# 186 scan steps of batch 64, K = 2 leader teachers.
LANES, STEPS, BATCH, CLUSTERS = 16, 186, 64, 2
V5E_HBM = 16 * 2**30


def _round_program(name, mesh):
    """(program, ShapeDtypeStruct args) for one of the runtime's round
    programs at the cell's shapes, every argument placed on ``mesh``."""

    def feed(n):
        return (jax.ShapeDtypeStruct((n, STEPS, BATCH, 28, 28, 1),
                                     jnp.float32),
                jax.ShapeDtypeStruct((n, STEPS, BATCH), jnp.int32))

    def per(n, dtype=jnp.int32):
        return jax.ShapeDtypeStruct((n,), dtype)

    def keys(n):
        return jax.ShapeDtypeStruct((n, 2), jnp.uint32)

    t_opt, s_opt = adamw(1e-3), adamw(3e-3)
    (t1, t_fwd), (s1, s_fwd) = (model_avals("mnist", student=False),
                                model_avals("mnist", student=True))
    kd = dict(kd_temperature=3.0, kd_alpha=0.5, kd_impl="fused")
    sx, sy = feed(LANES)
    sp = rows(s1, LANES)
    if name == "leader_kd_round":
        tp = rows(t1, CLUSTERS)
        fn = sh.make_leader_kd_round(mesh, LANES, t_fwd, s_fwd, t_opt, s_opt,
                                     **kd)
        args = (tp, opt_state(t_opt, tp), sp, opt_state(s_opt, sp),
                *feed(CLUSTERS), per(CLUSTERS), sx, sy, per(LANES),
                keys(CLUSTERS), keys(LANES), per(LANES),
                per(LANES, jnp.float32))
    elif name == "leader_teacher_phase":
        tp = rows(t1, CLUSTERS)
        fn = sh.make_leader_teacher_phase(mesh, t_fwd, t_opt)
        args = (tp, opt_state(t_opt, tp), *feed(CLUSTERS), per(CLUSTERS),
                keys(CLUSTERS))
    elif name == "packed_kd_round":
        tp = rows(t1, LANES)
        fn = sh.make_packed_kd_round(mesh, LANES, t_fwd, s_fwd, t_opt, s_opt,
                                     **kd)
        args = (tp, opt_state(t_opt, tp), sp, opt_state(s_opt, sp), sx, sy,
                per(LANES), sx, sy, per(LANES), keys(LANES), keys(LANES),
                jax.ShapeDtypeStruct((LANES, LANES), jnp.float32),
                per(LANES, jnp.float32))
    else:                                   # packed_baseline_round (FedAvg)
        p = rows(t1, LANES)
        fn = sh.make_packed_baseline_round(mesh, LANES, t_fwd, t_opt)
        args = (p, opt_state(t_opt, p), sx, sy, per(LANES), keys(LANES),
                per(LANES, jnp.float32), t1)
    on_mesh = NamedSharding(mesh, P())
    return fn, jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, on_mesh), args)


@pytest.mark.parametrize("name,fused_kd", [
    ("leader_kd_round", True),         # the cell's program
    ("leader_teacher_phase", False),   # its teacher warm-up
    ("packed_kd_round", True),         # cluster-pooled teachers
    ("packed_baseline_round", False),  # FedAvg / FedProx
])
def test_round_program_compiles_for_one_chip(one_chip, monkeypatch, name,
                                             fused_kd):
    """A round program, whole, compiled for one v5e chip at the benchmark
    cell's shapes: the Pallas KD kernel compiled (not interpreted) inside
    the scan, and arguments, outputs and temporaries within the chip's
    HBM."""
    # the kernels interpret on the CPU backend by default; here they must
    # lower for the described chip
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    mesh = Mesh(list(one_chip.device_set), (sh.AXIS,))
    fn, args = _round_program(name, mesh)
    compiled = fn.lower(*args).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == fused_kd
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < V5E_HBM, (name, used)
