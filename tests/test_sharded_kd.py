"""Sharded FedSiKD engine (teacher replicas + fused Pallas KD steps):
loop/sharded parity on a tiny synthetic dataset, and the batched
``kd_distillation_loss`` entry point under ``shard_map``.  Both need 8 host
devices, so they run in subprocesses (XLA_FLAGS must be set pre-import).
"""
import textwrap

from _subproc import run_script as _run


_PARITY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    from repro.data.synthetic import load_dataset
    from repro.fed.rounds import FedConfig, run_federated

    ds = load_dataset("mnist", small=True)
    common = dict(algorithm="fedsikd", num_clients=6, alpha=1.0, rounds=3,
                  local_epochs=2, teacher_warmup_epochs=2, batch_size=32,
                  num_clusters=2, seed=0)
    h_loop = run_federated(ds, FedConfig(engine="loop", **common))
    h_shard = run_federated(ds, FedConfig(engine="sharded", kd_impl="fused",
                                          **common))
    assert h_shard["engine"] == "sharded"
    assert len(h_shard["acc"]) == len(h_loop["acc"]) == 3
    # acceptance: per-round accuracy within 3 points of the loop engine.
    # The engines are equivalent but not bit-identical (per-step PRNG key
    # derivation and fused-kernel numerics differ), so this is a stochastic
    # bound; re-pinned from 2pt when ClientShard.batches moved to
    # SeedSequence seeding (observed per-round gap 0.25/0.5/2.5 pt).
    for rnd, (a, b) in enumerate(zip(h_loop["acc"], h_shard["acc"]), 1):
        assert abs(a - b) <= 0.03, (rnd, h_loop["acc"], h_shard["acc"])
    # both engines must actually learn
    assert h_shard["acc"][-1] > h_shard["acc"][0]
    print("PARITY-OK", h_loop["acc"], h_shard["acc"])
""")


_BATCHED_KD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.fed import sharded as sh
    from repro.kernels import ops, ref

    C, B, T, V = 8, 2, 16, 24
    key = jax.random.PRNGKey(0)
    s = jax.random.normal(key, (C, B, T, V)) * 2
    t = jax.random.normal(jax.random.fold_in(key, 1), (C, B, T, V)) * 2
    # include -1 padding labels: fused loss masks the WHOLE per-token loss
    # and divides by the valid count (same as ref.kd_loss_ref valid-mean)
    y = jax.random.randint(jax.random.fold_in(key, 2), (C, B, T), -1, V)

    mesh = sh.make_client_mesh(C)

    def ref_loss(s, t, y):
        per_tok = ref.kd_loss_ref(s.reshape(-1, V), t.reshape(-1, V),
                                  y.reshape(-1), tau=3.0, alpha=0.25)
        valid = jnp.maximum(jnp.sum((y.reshape(-1) >= 0)
                                    .astype(jnp.float32)), 1.0)
        return jnp.sum(per_tok) / valid

    def per_device(s, t, y):
        loss = ops.kd_distillation_loss_batched(
            s[0], t[0], y[0], tau=3.0, alpha=0.25)
        return loss[None]

    f = jax.jit(jax.shard_map(per_device, mesh=mesh,
                              in_specs=(P("clients"),) * 3,
                              out_specs=P("clients"), check_vma=False))
    got = np.asarray(f(s, t, y))
    want = np.asarray(jax.vmap(ref_loss)(s, t, y))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    # gradient path under shard_map too
    def per_device_grad(s, t, y):
        g = jax.grad(lambda s_: ops.kd_distillation_loss_batched(
            s_, t[0], y[0], tau=3.0, alpha=0.25))(s[0])
        return g[None]

    fg = jax.jit(jax.shard_map(per_device_grad, mesh=mesh,
                               in_specs=(P("clients"),) * 3,
                               out_specs=P("clients"), check_vma=False))
    gg = np.asarray(fg(s, t, y))
    gr = np.asarray(jax.vmap(jax.grad(ref_loss))(s, t, y))
    np.testing.assert_allclose(gg, gr, rtol=1e-4, atol=1e-5)
    print("BATCHED-KD-OK")
""")


def test_sharded_engine_matches_loop_engine():
    r = _run(_PARITY_SCRIPT)
    assert "PARITY-OK" in r.stdout, r.stdout + r.stderr


def test_batched_kd_loss_under_shard_map_matches_reference():
    r = _run(_BATCHED_KD_SCRIPT)
    assert "BATCHED-KD-OK" in r.stdout, r.stdout + r.stderr


def test_kd_batched_shape_validation():
    import numpy as np
    import pytest

    from repro.kernels import ops
    s = np.zeros((2, 4, 8), np.float32)
    with pytest.raises(ValueError):
        ops.kd_distillation_loss_batched(s, np.zeros((2, 4, 9), np.float32),
                                         np.zeros((2, 4), np.int32))
    with pytest.raises(ValueError):
        ops.kd_distillation_loss_batched(s, s, np.zeros((3, 4), np.int32))
