"""Paper-faithful FL experiments (EXPERIMENTS.md §Paper-validation).

Reproduces the paper's evaluation protocol on the synthetic CIFAR-10
substitute (DESIGN.md §2): 100 devices, 10 sampled/round, E=5, B=10,
lr=0.1 decayed 0.99/round, server data p * 40000 drawn from a held-out
pool, pruning at round 30.

  PYTHONPATH=src python -m benchmarks.paper_experiments --suite main
  PYTHONPATH=src python -m benchmarks.paper_experiments --suite ablations

The heterogeneity scenario matrix (client algorithm x Dirichlet skew x
participation/stragglers, both backends) is a separate grid runner:

  PYTHONPATH=src python -m benchmarks.paper_experiments --grid smoke --backend mesh
  PYTHONPATH=src python -m benchmarks.paper_experiments --grid full --backend both

Writes one JSON per run into benchmarks/results/paper/ (the grid writes
one combined BENCH_scenario_matrix.json).  Every cell trains on its OWN
key derived from (base_seed, cell_index) via ``jax.random.fold_in`` —
rerunning a grid reproduces it array-exactly.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
import zlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    FedAPConfig,
    FedDUConfig,
    FedDynConfig,
    FedProxConfig,
    FederatedTrainer,
    TrainPlan,
    baselines,
    fedap_plan,
    feddumap_config,
    niid,
)
from repro.core.rounds import FLConfig
from repro.data import build_federated_data
from repro.data.synthetic import SyntheticSpec
from repro.models import LeNet5, SimpleCNN

OUT = Path("benchmarks/results/paper")


def _cell_seed(base_seed: int, cell_index: int) -> int:
    """The per-cell seed: fold the cell index into the base key.  Every
    grid cell gets its own deterministic key chain instead of all cells
    silently reusing the raw base seed."""
    key = jax.random.fold_in(jax.random.key(base_seed), cell_index)
    return int(jax.random.bits(key, dtype=jnp.uint32))

# Scaled-down paper protocol (1-core CPU): 100 clients, 10/round, E=5, B=10.
NUM_CLIENTS = 100
ROUNDS = 60
SPEC = SyntheticSpec(num_classes=10, image_shape=(10, 10, 3),
                     train_size=13000, test_size=2000, noise_scale=0.45)
DEVICE_POOL = 10000
COMMON = dict(num_clients=NUM_CLIENTS, clients_per_round=10, local_epochs=5,
              batch_size=10, lr=0.1, lr_decay=0.99)


def make_model(name: str):
    if name == "cnn":
        return SimpleCNN(num_classes=10, image_shape=SPEC.image_shape)
    if name == "lenet":
        return LeNet5(num_classes=10, image_shape=SPEC.image_shape)
    raise ValueError(name)


def run_one(tag: str, *, model_name="cnn", algo="fedavg", p=0.05,
            server_niid="iid", rounds=ROUNDS, seed=0, cell_index=None,
            feddu_overrides=None, prune_round=30, static_tau=None,
            backend="local", out_dir: Path = OUT):
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"{tag}.json"
    if path.exists():
        print(f"[skip] {tag}")
        return json.loads(path.read_text())
    t0 = time.time()
    # per-cell key threading: a suite cell trains on fold_in(base, cell),
    # never the raw base seed shared across every run (tag-hash fallback
    # keeps ad-hoc single runs distinct too)
    base_seed = seed
    if cell_index is None:
        cell_index = zlib.crc32(tag.encode())
    seed = _cell_seed(base_seed, cell_index)
    data = build_federated_data(num_clients=NUM_CLIENTS, server_fraction=p,
                                server_niid=server_niid, device_pool=DEVICE_POOL,
                                spec=SPEC, seed=seed)
    model = make_model(model_name)
    feddu = FedDUConfig(**(feddu_overrides or {}),
                        **({"static_tau_eff": static_tau} if static_tau else {}))
    # Paper-faithful FedAP re-materializes the model (the device-FLOP shrink
    # of Tables 6-9) -> Prune(mode="shrink").
    apcfg = FedAPConfig(prune_round=prune_round, probe_size=32, participants=6)
    plan = TrainPlan.standard(rounds, eval_every=2)

    if algo == "fedavg":
        cfg = baselines.fedavg_config(**COMMON, seed=seed)
    elif algo == "feddu":
        cfg = baselines.feddu_config(**COMMON, seed=seed, feddu=feddu)
    elif algo == "feddum":
        cfg = feddumap_config(**COMMON, seed=seed, feddu=feddu)
    elif algo == "serverm":
        cfg = baselines.server_momentum_config(**COMMON, seed=seed, feddu=feddu)
    elif algo == "devicem":
        cfg = baselines.device_momentum_config(**COMMON, seed=seed, feddu=feddu)
    elif algo == "fedda":
        cfg = baselines.fedda_config(**COMMON, seed=seed, feddu=feddu)
    elif algo == "datasharing":
        data = baselines.apply_data_sharing(data, np.random.default_rng(seed))
        cfg = baselines.fedavg_config(**COMMON, seed=seed)
    elif algo == "hybridfl":
        data = baselines.apply_hybrid_fl(data)
        cfg = baselines.fedavg_config(
            **{**COMMON, "num_clients": NUM_CLIENTS + 1}, seed=seed)
    elif algo in ("feddf", "fedkt"):
        cfg = baselines.fedavg_config(**COMMON, seed=seed)
        hook = baselines.make_distillation_round_end(
            model, data, mode=algo, steps=10, batch=32, seed=seed)
        plan = TrainPlan.with_callback(rounds, hook, eval_every=2)
    elif algo in ("imc", "prunefl"):
        cfg = baselines.fedavg_config(**COMMON, seed=seed)
        hook = baselines.make_unstructured_pruning_hook(
            rate=0.5, prune_round=prune_round,
            refresh_every=10 if algo == "prunefl" else None)
        plan = TrainPlan.with_callback(rounds, hook, eval_every=2)
    elif algo == "hrank":
        cfg = baselines.fedavg_config(**COMMON, seed=seed)
        hook = baselines.make_hrank_pruning_hook(
            model, data, rate=0.4, prune_round=prune_round, probe=32)
        plan = TrainPlan.with_callback(rounds, hook, eval_every=2)
    elif algo == "fedap":
        cfg = baselines.fedavg_config(**COMMON, seed=seed, fedap=apcfg)
        plan = fedap_plan(rounds, prune_round=prune_round, mode="shrink",
                          eval_every=2)
    elif algo == "fedduap":   # FedDU + FedAP, no momentum
        cfg = baselines.feddu_config(**COMMON, seed=seed, feddu=feddu,
                                     fedap=apcfg)
        plan = fedap_plan(rounds, prune_round=prune_round, mode="shrink",
                          eval_every=2)
    elif algo == "feddumap":  # the full method
        cfg = feddumap_config(**COMMON, seed=seed, feddu=feddu, fedap=apcfg)
        plan = fedap_plan(rounds, prune_round=prune_round, mode="shrink",
                          eval_every=2)
    else:
        raise ValueError(algo)

    trainer = FederatedTrainer(model, data, cfg, backend=backend)
    init_params = model.init(jax.random.key(seed))
    flops_before = model.flops_per_example(init_params, SPEC.image_shape)
    res = trainer.run(plan)
    params, hist = res.params, res.history
    flops_after = model.flops_per_example(params, SPEC.image_shape) \
        if algo in ("fedap", "fedduap", "feddumap", "hrank") else flops_before

    rec = {
        "tag": tag, "algo": algo, "model": model_name, "p": p,
        "server_niid": server_niid, "rounds": rounds, "seed": seed,
        "base_seed": base_seed, "cell_index": cell_index,
        "final_acc": hist["acc"][-1],
        "best_acc": max(hist["acc"]),
        "history": hist,
        "mflops_before": flops_before / 1e6,
        "mflops_after": flops_after / 1e6,
        "wall_s": time.time() - t0,
    }
    prune_art = res.artifacts.get("prune")
    if prune_art is not None:
        rec["fedap"] = {"p_star": prune_art["p_star"],
                        "layer_rates": prune_art["layer_rates"],
                        "kept_counts": prune_art["kept_counts"]}
    path.write_text(json.dumps(rec))
    print(f"[done] {tag}: acc={rec['final_acc']:.3f} best={rec['best_acc']:.3f} "
          f"({rec['wall_s']:.0f}s)", flush=True)
    return rec


def suite_main():
    """The paper's Table 10/12 comparison on the CNN model."""
    for algo in ["fedavg", "feddu", "feddum", "fedap", "fedduap", "feddumap",
                 "datasharing", "hybridfl", "serverm", "devicem", "fedda",
                 "feddf", "fedkt", "imc", "prunefl", "hrank"]:
        run_one(f"main_cnn_{algo}", algo=algo, p=0.05)


def suite_p_sweep():
    """Figure 2: FedDU with p in {1%, 5%, 10%}."""
    for p in [0.01, 0.05, 0.10]:
        run_one(f"psweep_feddu_p{int(p * 100)}", algo="feddu", p=p)


def suite_ablations():
    """Tables 2-5: tau_eff static vs dynamic, f'(acc), C, server non-IID."""
    for tau in [5, 10, 20]:
        run_one(f"abl_static_tau{tau}", algo="feddu", static_tau=float(tau))
    run_one("abl_fprime_inv", algo="feddu",
            feddu_overrides={"f_prime_kind": "inv"})
    for c in [0.5, 1.5]:
        run_one(f"abl_C{c}", algo="feddu", feddu_overrides={"C": c})
    for kind in ["iid", "mild", "severe"]:
        run_one(f"abl_server_{kind}", algo="feddu", server_niid=kind)


def suite_lenet():
    for algo in ["fedavg", "feddu", "feddumap"]:
        run_one(f"lenet_{algo}", model_name="lenet", algo=algo, p=0.05)


# ---------------------------------------------------------------------------
# Heterogeneity scenario matrix: client algorithm x Dirichlet skew x
# participation/stragglers, on both execution backends
# ---------------------------------------------------------------------------

SCEN_CLIENTS = 16
SCEN_SPEC = SyntheticSpec(num_classes=10, image_shape=(8, 8, 3),
                          train_size=2600, test_size=400, noise_scale=0.45)
SCEN_POOL = 2000
SCEN_COMMON = dict(num_clients=SCEN_CLIENTS, local_epochs=1, batch_size=10,
                   lr=0.08, lr_decay=0.98, server_batch_size=16)
SCEN_MU, SCEN_FEDDYN_ALPHA = 0.01, 0.01


def scenario_cells(grid: str):
    """The grid: 3 algorithms x Dirichlet alpha x (clients_per_round,
    dropout_rate).  ``smoke`` is the CI gate (one scenario per algorithm,
    2 rounds); ``full`` is the recorded BENCH matrix."""
    algos = ("fedavg", "fedprox", "feddyn")
    if grid == "smoke":
        alphas, participation, rounds = (0.5,), ((4, 0.25),), 2
    elif grid == "full":
        alphas = (0.1, 0.5, 100.0)
        participation = ((8, 0.0), (4, 0.0), (8, 0.25))
        rounds = 8
    else:
        raise ValueError(grid)
    cells = [dict(algo=a, dirichlet_alpha=al, clients_per_round=c,
                  dropout_rate=d)
             for a in algos for al in alphas for c, d in participation]
    return cells, rounds


def _scenario_config(cell: dict, seed: int) -> FLConfig:
    common = dict(SCEN_COMMON, clients_per_round=cell["clients_per_round"],
                  dropout_rate=cell["dropout_rate"], seed=seed)
    if cell["algo"] == "fedavg":
        return baselines.fedavg_config(**common)
    if cell["algo"] == "fedprox":
        return baselines.fedprox_config(
            **common, fedprox=FedProxConfig(mu=SCEN_MU))
    if cell["algo"] == "feddyn":
        return baselines.feddyn_config(
            **common, feddyn=FedDynConfig(alpha=SCEN_FEDDYN_ALPHA))
    raise ValueError(cell["algo"])


def run_scenario_cell(cell: dict, *, rounds: int, backend: str = "local",
                      base_seed: int = 0, cell_index: int = 0) -> dict:
    seed = _cell_seed(base_seed, cell_index)
    data = build_federated_data(
        num_clients=SCEN_CLIENTS, server_fraction=0.1, device_pool=SCEN_POOL,
        spec=SCEN_SPEC, partition="dirichlet",
        dirichlet_alpha=cell["dirichlet_alpha"], seed=seed)
    p_bar = niid.global_distribution(data.client_dists, data.sizes)
    degree = float(np.mean(np.asarray(
        niid.non_iid_degree(data.client_dists, p_bar))))
    model = SimpleCNN(num_classes=10, image_shape=SCEN_SPEC.image_shape,
                      channels=(4, 8, 8), fc_width=16)
    cfg = _scenario_config(cell, seed)
    t0 = time.time()
    res = FederatedTrainer(model, data, cfg, backend=backend).run(
        TrainPlan.standard(rounds, eval_every=1))
    return {**cell, "backend": backend, "rounds": rounds,
            "base_seed": base_seed, "cell_index": cell_index, "seed": seed,
            "mean_niid_degree": degree,
            "final_acc": float(res.history["acc"][-1]),
            "final_loss": float(res.history["loss"][-1]),
            "history": {k: [float(v) for v in vs]
                        for k, vs in res.history.items()},
            "wall_s": time.time() - t0}


def suite_scenario_matrix(grid: str = "smoke", backends=("local",),
                          base_seed: int = 0, out_dir: Path = OUT):
    cells, rounds = scenario_cells(grid)
    recs = []
    for backend in backends:
        for i, cell in enumerate(cells):
            rec = run_scenario_cell(cell, rounds=rounds, backend=backend,
                                    base_seed=base_seed, cell_index=i)
            print(f"[grid] {backend} {cell['algo']} "
                  f"alpha={cell['dirichlet_alpha']} "
                  f"C={cell['clients_per_round']} "
                  f"drop={cell['dropout_rate']} "
                  f"d={rec['mean_niid_degree']:.3f} "
                  f"acc={rec['final_acc']:.3f} ({rec['wall_s']:.0f}s)",
                  flush=True)
            recs.append(rec)
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / "BENCH_scenario_matrix.json"
    path.write_text(json.dumps({"grid": grid, "rounds": rounds,
                                "base_seed": base_seed, "cells": recs},
                               indent=1))
    print(f"[done] scenario matrix -> {path} ({len(recs)} cells)")
    return recs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", default="all",
                    choices=["main", "psweep", "ablations", "lenet", "all"])
    ap.add_argument("--grid", default=None, choices=["smoke", "full"],
                    help="run the heterogeneity scenario matrix instead of "
                         "the paper suites")
    ap.add_argument("--backend", default="local",
                    choices=["local", "mesh", "both"])
    ap.add_argument("--base-seed", type=int, default=0)
    args = ap.parse_args()
    t0 = time.time()
    if args.grid:
        backends = ("local", "mesh") if args.backend == "both" \
            else (args.backend,)
        suite_scenario_matrix(args.grid, backends, args.base_seed)
        print(f"total {time.time() - t0:.0f}s")
        return
    if args.suite in ("main", "all"):
        suite_main()
    if args.suite in ("psweep", "all"):
        suite_p_sweep()
    if args.suite in ("ablations", "all"):
        suite_ablations()
    if args.suite in ("lenet", "all"):
        suite_lenet()
    print(f"total {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
