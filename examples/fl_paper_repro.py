"""Scenario: the paper's CIFAR-10 protocol end-to-end, one algorithm.

  PYTHONPATH=src python examples/fl_paper_repro.py --algo feddumap --rounds 30

This is a thin CLI over benchmarks/paper_experiments.run_one; it reproduces
one cell of the paper's Tables 10/12 on the synthetic CIFAR substitute
(100 clients, 10/round, E=5, B=10, p=5% server data, prune at round 30).
"""
import argparse
from pathlib import Path

import benchmarks.paper_experiments as PE
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--algo", default="feddumap",
                    choices=["fedavg", "feddu", "feddum", "fedap", "fedduap",
                             "feddumap", "datasharing", "hybridfl", "serverm",
                             "devicem", "fedda", "feddf", "fedkt", "imc",
                             "prunefl", "hrank"])
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--p", type=float, default=0.05)
    ap.add_argument("--backend", default="local", choices=["local", "mesh"],
                    help="execution backend: single-host scan, or the "
                         "client-sharded device mesh (same numerics; run "
                         "with XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=8 to simulate a mesh on CPU)")
    ap.add_argument("--out", default="/tmp/fl_paper_repro")
    args = ap.parse_args()
    tag = (f"example_{args.algo}" if args.backend == "local"
           else f"example_{args.algo}_{args.backend}")
    rec = PE.run_one(tag, algo=args.algo, p=args.p,
                     rounds=args.rounds, prune_round=min(args.rounds // 2, 30),
                     backend=args.backend, out_dir=Path(args.out))
    accs = rec["history"]["acc"]
    print(f"\n{args.algo}: final acc {rec['final_acc']:.3f}; trajectory "
          f"{[round(a, 3) for a in accs[:: max(1, len(accs) // 8)]]}")
    print(f"device MFLOPs {rec['mflops_before']:.2f} -> {rec['mflops_after']:.2f}")


if __name__ == "__main__":
    main()
