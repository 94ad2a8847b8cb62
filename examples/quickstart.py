"""Quickstart: FedDUMAP in ~40 lines.

Builds a small federated world (20 non-IID clients + shared server data),
trains the paper's CNN with the full method (FedDU dynamic server update +
FedDUM two-sided momentum + FedAP adaptive pruning at round 6) under a
declarative TrainPlan, and prints the accuracy trajectory and the dynamic
tau_eff schedule.

Pruning uses the static-shape MASK mode: the FedAP keep-masks enter the
scan carry at the Prune event, so all 10 rounds run inside compiled scan
chunks — no re-jit.  Swap mode="shrink" to re-materialize a genuinely
smaller model instead.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.core import FedAPConfig, FederatedTrainer, fedap_plan, feddumap_config
from repro.data import build_federated_data
from repro.data.synthetic import SyntheticSpec
from repro.models import SimpleCNN
from repro.utils import tree_size
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    spec = SyntheticSpec(num_classes=10, image_shape=(10, 10, 3),
                         train_size=5200, test_size=800, noise_scale=0.5)
    data = build_federated_data(num_clients=20, server_fraction=0.08,
                                device_pool=4000, spec=spec)
    model = SimpleCNN(num_classes=10, image_shape=(10, 10, 3))

    # min_rate: a compression-budget floor — the pure eigen-gap rule can
    # decide "prune nothing" on this easy synthetic task
    fedap = FedAPConfig(prune_round=6, probe_size=16, participants=4,
                        min_rate=0.3)
    cfg = feddumap_config(num_clients=20, clients_per_round=5, local_epochs=2,
                          batch_size=10, lr=0.08, fedap=fedap)
    trainer = FederatedTrainer(model, data, cfg)

    plan = fedap_plan(10, prune_round=fedap.prune_round, mode="mask")
    res = trainer.run(plan)

    print("\nround  acc     tau_eff")
    for r, a, t in zip(res.history["round"], res.history["acc"],
                       res.history["tau_eff"]):
        print(f"{r:>5}  {a:.3f}  {t:8.3f}")

    prune = res.artifacts["prune"]
    # the masks are factored (size 1 on every axis FedAP never prunes):
    # count live coordinates at each parameter's own shape
    live = sum(int(jnp.sum(jnp.broadcast_to(m, p.shape)))
               for p, m in zip(jax.tree.leaves(res.params),
                               jax.tree.leaves(res.state["masks"])))
    print(f"\nFedAP: global rate p*={prune['p_star']:.3f}, kept filters "
          f"{prune['kept_counts']}")
    print(f"masked params {live:,} live of {tree_size(res.params):,} "
          f"(static shapes — every round ran inside the compiled scan)")


if __name__ == "__main__":
    main()
