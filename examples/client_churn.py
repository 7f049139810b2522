"""Client churn: dynamic joins/leaves + periodic re-clustering on the
packed mesh (DESIGN.md §11).

The paper's clustering story is incremental — "as clients join the system,
they securely share relevant statistics about their data distribution"
(§IV-A) — and real federated populations churn.  This example runs FedSiKD
on the packed client mesh (16 clients on 8 host devices, pack=2) through a
churn scenario:

- 12 clients are online from round 1; 4 more JOIN at rounds 2 and 4
  (``join_schedule``);
- every active client has a 5% chance per round of LEAVING for good
  (``leave_rate`` — permanent, unlike ``dropout_rate``'s one-round failure);
- the server re-clusters on every membership change AND every 2 rounds
  (``recluster_every``): the batched stats front-end recomputes the roster's
  (mu, sigma, gamma) in one jitted program, k-means warm-starts from the
  previous centroids, each cluster's teacher migrates from the nearest
  surviving centroid's teacher, and the scheduler + slot staging are
  rebuilt — the compiled round program survives every event because the
  mesh is sized for the full client universe up front.

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/client_churn.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

from repro.data.synthetic import load_dataset
from repro.fed.rounds import FedConfig, run_federated


def main():
    print(f"jax backend: {jax.default_backend()} ({len(jax.devices())} devices)")
    ds = load_dataset("mnist", small=True)
    cfg = FedConfig(algorithm="fedsikd", engine="sharded",
                    num_clients=16, pack=2, alpha=1.0, rounds=5,
                    local_epochs=1, teacher_warmup_epochs=1, batch_size=32,
                    num_clusters=2, seed=0,
                    join_schedule=((2, 2), (4, 2)),
                    leave_rate=0.05, recluster_every=2)
    print("FedSiKD with client churn on the packed mesh "
          f"(C={cfg.num_clients}, pack={cfg.pack}):")
    h = run_federated(ds, cfg, progress=True)

    print("\nroster + re-clustering timeline:")
    for rnd, labels in h["labels_history"]:
        online = sum(1 for l in labels if l >= 0)
        tag = "initial clustering" if rnd == 0 else f"re-cluster @ round {rnd}"
        print(f"  {tag:24s} {online:2d} clients online   labels={labels}")
    recl = [r for r, v in zip(h["round"], h["recluster"]) if v]
    print(f"re-cluster rounds: {recl}")
    print(f"participants/round: {h['participants']}")
    print(f"final: acc={h['acc'][-1]:.4f} loss={h['loss'][-1]:.4f}")
    assert len(h["labels_history"]) >= 3    # initial + both join events
    assert h["participants"][-1] >= 12


if __name__ == "__main__":
    main()
