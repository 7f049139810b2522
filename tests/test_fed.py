"""Federated runtime: all five algorithms end-to-end through the shared
RoundDriver on tiny data, plus the shard_map cluster-collective operators
and the packed baseline engine (subprocess with 8 host devices).
"""
import textwrap

import pytest
from _subproc import run_script

from repro.data.synthetic import load_dataset
from repro.fed.rounds import FedConfig, run_federated


@pytest.fixture(scope="module")
def tiny_ds():
    return load_dataset("mnist", small=True)


@pytest.mark.parametrize("alg", ["fedsikd", "fedavg", "random", "flhc",
                                 "fedprox"])
def test_round_engine_runs_and_records(alg, tiny_ds):
    cfg = FedConfig(algorithm=alg, num_clients=6, alpha=1.0, rounds=2,
                    teacher_warmup_epochs=1,
                    num_clusters=2 if alg != "fedsikd" else None)
    h = run_federated(tiny_ds, cfg)
    assert len(h["acc"]) == 2 and len(h["loss"]) == 2
    assert all(0.0 <= a <= 1.0 for a in h["acc"])
    if alg in ("fedsikd", "random", "flhc"):
        assert h["num_clusters"] >= 1


def test_fedsikd_beats_chance_quickly(tiny_ds):
    cfg = FedConfig(algorithm="fedsikd", num_clients=6, alpha=1.0, rounds=4,
                    local_epochs=3, teacher_warmup_epochs=5)
    h = run_federated(tiny_ds, cfg)
    assert h["acc"][-1] > 0.2      # 10 classes -> chance = 0.1


def test_dp_noise_changes_clustering(tiny_ds):
    from repro.data.pipeline import make_client_shards
    from repro.fed.rounds import _cluster_by_stats
    shards = make_client_shards(tiny_ds, 8, 0.2, seed=0)
    base = _cluster_by_stats(shards, FedConfig(num_clusters=3))
    noisy = _cluster_by_stats(shards, FedConfig(num_clusters=3, dp_noise=5.0))
    assert base.shape == noisy.shape == (8,)


_SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core import cluster_collectives as cc
    from repro.fed import sharded as sh

    mesh = sh.make_client_mesh(8)
    groups = cc.cluster_groups([0, 0, 0, 1, 1, 2, 2, 2])

    # grouped mean correctness
    x = jnp.arange(8.0)
    f = jax.jit(jax.shard_map(
        lambda v: cc.intra_cluster_mean(v, "clients", groups),
        mesh=mesh, in_specs=P("clients"), out_specs=P("clients"),
        check_vma=False))
    out = np.asarray(f(x))
    want = np.array([1, 1, 1, 3.5, 3.5, 6, 6, 6])
    np.testing.assert_allclose(out, want)

    # two-level mean: (1/3)(1 + 3.5 + 6) everywhere
    g = jax.jit(jax.shard_map(
        lambda v: cc.fedsikd_global_mean(v, "clients", groups),
        mesh=mesh, in_specs=P("clients"), out_specs=P("clients"),
        check_vma=False))
    np.testing.assert_allclose(np.asarray(g(x)), np.full(8, 3.5), rtol=1e-6)

    # fedavg weighted mean
    sizes = jnp.array([1., 1., 1., 1., 1., 1., 1., 9.])
    h = jax.jit(jax.shard_map(
        lambda v, n: cc.fedavg_mean(v, "clients", n),
        mesh=mesh, in_specs=(P("clients"), P("clients")), out_specs=P("clients"),
        check_vma=False))
    want = float((np.arange(8) * np.array([1,1,1,1,1,1,1,9])).sum() / 16)
    np.testing.assert_allclose(np.asarray(h(x, sizes)), np.full(8, want), rtol=1e-6)

    # leader broadcast per cluster
    b = jax.jit(jax.shard_map(
        lambda v: cc.broadcast_from(v, "clients", 0, groups),
        mesh=mesh, in_specs=P("clients"), out_specs=P("clients"),
        check_vma=False))
    np.testing.assert_allclose(np.asarray(b(x)), [0,0,0,3,3,5,5,5])

    # end-to-end packed baseline round on the paper's CNN (the mesh entry
    # point for the fedavg/fedprox family, fed/algorithms/baselines.py)
    from repro.data.synthetic import load_dataset
    from repro.fed.rounds import FedConfig, run_federated
    ds = load_dataset("mnist", small=True)
    h = run_federated(ds, FedConfig(
        algorithm="fedavg", engine="sharded", num_clients=16, pack=2,
        alpha=1.0, rounds=2, local_epochs=1, batch_size=32, seed=0))
    assert h["engine"] == "sharded" and h["pack"] == 2
    assert len(h["acc"]) == 2 and all(0.0 <= a <= 1.0 for a in h["acc"])
    assert all(np.isfinite(l) for l in h["train_loss"]), h["train_loss"]
    print("SHARDED-OK")
""")


def test_sharded_cluster_collectives_8dev():
    r = run_script(_SHARDED_SCRIPT, timeout=600)
    assert "SHARDED-OK" in r.stdout, r.stdout + r.stderr
