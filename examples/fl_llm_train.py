"""Federated LM fine-tuning on the unified executor: TrainPlan in, RunResult out.

The transformer LM runs the SAME TrainPlan/PlanExecutor stack as the CNN
repro — one driver for both model families:

  * :func:`repro.data.pipeline.build_lm_federated_data` transplants the
    paper's Section-4.1 protocol to a next-token corpus (sequences
    label-shard partitioned by TOPIC over the clients, IID-controllable
    server pool, held-out test split);
  * :class:`repro.models.lm.LM` plugs into the executor through the
    simulation-model contract (``loss_and_acc(params, x, y, masks=)``),
    so ``FederatedTrainer`` drives it over the local scan backend or —
    ``--backend mesh`` — client-sharded over a device mesh, unchanged;
  * ``--prune-round K`` schedules FedAP as a first-class ``Prune`` event
    (:func:`repro.core.plan.fedap_plan`): the layer-adaptive decision
    (Fisher eigen-gap rates -> Formula 15 -> uniform 128-lane-aligned
    FFN-unit selection, ``core.pruning_lm``) is injected as keep-masks
    carried in the scan — structure fixed from round 0, zero re-jit —
    or re-materializes the smaller stack with ``--prune-mode shrink``;
  * ``--masked-compute kernel`` additionally routes the masked FFN
    matmuls through the differentiable Pallas ``masked_matmul`` kernel
    (pruned 128-column blocks skipped on the MXU; on the CPU backend
    the kernel runs in Pallas interpret mode).

Examples::

  PYTHONPATH=src python examples/fl_llm_train.py --rounds 20 --scale tiny
  PYTHONPATH=src python examples/fl_llm_train.py --rounds 10 \
      --prune-round 5 --prune-mode mask
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python examples/fl_llm_train.py --rounds 4 --backend mesh

--scale 25m/100m train larger models (slow on CPU; tiny finishes in
seconds per round).
"""
import argparse

from repro.configs.base import ModelConfig
from repro.core.plan import TrainPlan, fedap_plan
from repro.core.pruning import FedAPConfig
from repro.core.rounds import FederatedTrainer, feddumap_config
from repro.data.pipeline import build_lm_federated_data
from repro.data.synthetic import TokenSpec
from repro.models.lm import LM
from repro.utils.compile_cache import enable_compile_cache

SCALES = {
    "tiny": dict(num_layers=2, d_model=128, num_heads=4, num_kv_heads=2,
                 d_ff=512, vocab_size=2048),
    "25m": dict(num_layers=6, d_model=512, num_heads=8, num_kv_heads=4,
                d_ff=2048, vocab_size=8192),
    "100m": dict(num_layers=12, d_model=768, num_heads=12, num_kv_heads=4,
                 d_ff=3072, vocab_size=32768),
}


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--scale", default="tiny", choices=list(SCALES))
    ap.add_argument("--backend", default="local", choices=("local", "mesh"))
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--clients-per-round", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--sequences", type=int, default=512)
    ap.add_argument("--batch", type=int, default=4, help="per-client batch")
    ap.add_argument("--local-epochs", type=int, default=1)
    ap.add_argument("--eval-every", type=int, default=5)
    ap.add_argument("--prune-round", type=int, default=0,
                    help="0 = no FedAP event")
    ap.add_argument("--prune-mode", default="mask",
                    choices=("mask", "shrink"))
    ap.add_argument("--masked-compute", default="params",
                    choices=("params", "kernel"))
    ap.add_argument("--prune-floor", type=float, default=0.5,
                    help="FedAPConfig.min_rate compression-budget floor")
    args = ap.parse_args()

    mcfg = ModelConfig(name=f"dense-{args.scale}", family="dense",
                       rope="1d", norm="rmsnorm", act="silu",
                       param_dtype="float32", remat="none",
                       **SCALES[args.scale])
    model = LM(mcfg)
    data = build_lm_federated_data(
        num_clients=args.clients,
        spec=TokenSpec(vocab_size=mcfg.vocab_size,
                       num_topics=2 * args.clients,
                       seq_len=args.seq + 1,
                       num_sequences=args.sequences))

    cfg = feddumap_config(
        num_clients=args.clients,
        clients_per_round=args.clients_per_round,
        local_epochs=args.local_epochs,
        batch_size=args.batch,
        server_batch_size=2 * args.batch,
        lr=3e-3, lr_decay=1.0,
        masked_compute=args.masked_compute,
        # the FFN stack prunes at the 128-lane boundary (core.pruning_lm's
        # uniform kept count); the floor guarantees a visible compression
        fedap=FedAPConfig(align=128, min_rate=args.prune_floor,
                          probe_size=8,
                          participants=min(4, args.clients)))
    trainer = FederatedTrainer(model, data, cfg, backend=args.backend)

    if args.prune_round:
        plan = fedap_plan(args.rounds, prune_round=args.prune_round,
                          mode=args.prune_mode, eval_every=args.eval_every)
    else:
        plan = TrainPlan.standard(args.rounds, eval_every=args.eval_every)

    res = trainer.run(plan)
    for r, loss, acc, tau, dt in zip(res.history["round"],
                                     res.history["loss"],
                                     res.history["acc"],
                                     res.history["tau_eff"],
                                     res.history["time"]):
        print(f"round {r:>3}  loss {loss:.4f}  token-acc {acc:.4f}  "
              f"tau_eff {tau:.3f}  ({dt:.0f}s)", flush=True)
    if args.prune_round:
        art = res.artifacts["prune"]
        print(f"FedAP: p*={art['p_star']:.3f}  "
              f"kept={art['kept_counts']}  mode={art['mode']}")


if __name__ == "__main__":
    main()
