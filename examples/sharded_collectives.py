"""Federated algorithms on a device mesh (DESIGN.md §3, §8, §10): 8
placeholder devices.  Part 1 shows the raw collective pattern —
intra-cluster grouped all-reduce + two-level global mean operators.
Part 2 runs the FULL FedSiKD algorithm (Alg. 1) on the mesh: per-cluster
teacher replicas, KD-establishment warm-up, fused Pallas distillation
steps inside lax.scan, grouped student aggregation.  Part 3 breaks the
clients==devices coupling: 24 clients packed 3-per-device with stratified
partial participation (12 sampled clients per round) through the same
jitted program.  Part 4 runs a BASELINE (FedAvg) through the same packed
runtime — since the algorithm-strategy layer, the paper's comparison
algorithms share the mesh engine.

  JAX_PLATFORMS=cpu PYTHONPATH=src python examples/sharded_collectives.py
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np

from repro.core import cluster_collectives as cc
from repro.core import kmeans, stats
from repro.data.pipeline import make_client_shards
from repro.data.synthetic import load_dataset
from repro.fed import sharded as sh
from repro.fed.rounds import FedConfig, run_federated

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def main():
    print(f"jax backend: {jax.default_backend()} ({len(jax.devices())} devices)")
    ds = load_dataset("mnist", small=True)
    shards = make_client_shards(ds, 8, 0.3, seed=0)

    # paper phase 1-2: stats -> k-means clusters (on host, pre-optimization)
    feats = stats.standardize(stats.stack_stats(
        [stats.compute_stats(s.x.reshape(s.num_examples, -1))
         for s in shards]))
    res = kmeans.kmeans(jax.random.PRNGKey(0), feats, 3)
    cluster_of = np.asarray(res.assignments)
    print("cluster assignment:", cluster_of)

    mesh = sh.make_client_mesh(8)

    # ---- part 1: the raw grouped-collective operators (Alg. 1 lines 16-18)
    groups = cc.cluster_groups(cluster_of)
    x = jnp.arange(8.0)
    intra = jax.jit(jax.shard_map(
        lambda v: cc.intra_cluster_mean(v, sh.AXIS, groups),
        mesh=mesh, in_specs=P(sh.AXIS), out_specs=P(sh.AXIS),
        check_vma=False))
    two_level = jax.jit(jax.shard_map(
        lambda v: cc.fedsikd_global_mean(v, sh.AXIS, groups),
        mesh=mesh, in_specs=P(sh.AXIS), out_specs=P(sh.AXIS),
        check_vma=False))
    print("per-cluster means:", np.asarray(intra(x)))
    print("two-level global mean:", np.asarray(two_level(x)))

    # ---- part 2: the full Alg. 1 on the mesh (teachers + fused Pallas KD)
    print("sharded FedSiKD (teacher replicas + fused KD steps):")
    hist = run_federated(ds, FedConfig(
        algorithm="fedsikd", engine="sharded", num_clients=8,
        alpha=0.3, rounds=3, local_epochs=1, teacher_warmup_epochs=2,
        batch_size=32, num_clusters=3, kd_temperature=3.0, kd_impl="fused",
        seed=0), progress=True)
    print("accuracy curve:", ["%.3f" % a for a in hist["acc"]])

    # ---- part 3: C >> devices — client packing + partial participation
    # (fed/schedule.py: the scheduler assigns sampled clients to mesh slots
    # and the packed round program is reused across rounds, DESIGN.md §8)
    print("packed FedSiKD: 24 clients on 8 devices (pack=3), "
          "12 sampled per round:")
    hist3 = run_federated(ds, FedConfig(
        algorithm="fedsikd", engine="sharded", num_clients=24, pack=3,
        participation="stratified", clients_per_round=12,
        alpha=0.5, rounds=3, local_epochs=1, teacher_warmup_epochs=2,
        batch_size=32, num_clusters=3, seed=0), progress=True)
    print("accuracy curve:", ["%.3f" % a for a in hist3["acc"]],
          "participants/round:", hist3["participants"])

    # ---- part 4: a baseline on the SAME packed mesh (fed/algorithms/
    # baselines.py): 24 FedAvg clients, 3 lanes per device, one all-clients
    # example-weighted grouped mean per round
    print("packed FedAvg: 24 clients on 8 devices (pack=3):")
    hist4 = run_federated(ds, FedConfig(
        algorithm="fedavg", engine="sharded", num_clients=24, pack=3,
        alpha=0.5, rounds=3, local_epochs=1, batch_size=32, seed=0),
        progress=True)
    print("accuracy curve:", ["%.3f" % a for a in hist4["acc"]])


if __name__ == "__main__":
    main()
