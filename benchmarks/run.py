"""Accuracy benchmarks: one section per paper table/figure.  Speed is
measured on the chip by ``bench/run.py`` and recorded in
``PERF_LEDGER.jsonl``, not here.

  PYTHONPATH=src python -m benchmarks.run            # quick mode
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale alphas
  PYTHONPATH=src python -m benchmarks.run --only clustering,dp
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="all four alpha levels, full-size twins")
    ap.add_argument("--only", default=None,
                    help="comma list: tables,clustering,dp")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    def want(name):
        return only is None or name in only

    t0 = time.time()
    if want("clustering"):
        print("== clustering quality (paper §IV-A: metric-voted K) ==")
        from benchmarks import clustering_bench
        clustering_bench.main(quick=not args.full)
    if want("dp"):
        print("== DP-noise ablation (beyond paper; cached) ==")
        import json, pathlib
        f = pathlib.Path("results/dp_ablation.json")
        if f.exists():
            for r in json.loads(f.read_text()):
                print(f"dp_noise={r['dp_noise']},agreement={r['cluster_agreement']:.3f},"
                      f"K={r['K']},acc={['%.3f' % a for a in r['acc']]}")
        else:
            print("dp_ablation,SKIP,run benchmarks.dp_ablation first")
    if want("tables"):
        print("== paper tables V-IX (MNIST/HAR twins x alpha x algorithm) ==")
        from benchmarks import paper_tables
        paper_tables.main(quick=not args.full)
    print(f"benchmarks done in {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
