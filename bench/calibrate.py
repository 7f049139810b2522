#!/usr/bin/env python3
"""Readings that a cell's limits are set from, in one process on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 2001-2012 \\
        --control 3 --faults 3 --out out/calib.jsonl

For every seed: a short run of the cell (set-up, warm rounds, a window of
``--seconds``) and its comparison with the reference; these are the sound
readings, whose largest is a limit's lower end.  For the first
``--control`` seeds also the control, the reference in bfloat16 put in the
program's place; for the first ``--faults`` seeds each fault of
``faults.py`` planted in the program but ``unchanged``, which reads 1 by
construction; ``--fault`` names the faults to read, all by default.  The
least of these is a limit's upper end.  One JSON line per reading.
"""
import argparse
import json
import os
import sys
import time

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from bench import check, faults, harness  # noqa: E402


def seeds(text):
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--fault", action="append", choices=sorted(
        set(faults.FAULTS) - {"unchanged"}))
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    log = lambda *a: print(*a, file=sys.stderr, flush=True)
    with open(args.out, "a") as out:
        def emit(rec):
            out.write(json.dumps(harness._finite(rec)) + "\n")
            out.flush()
            print(json.dumps(harness._finite(rec)), flush=True)

        for i, seed in enumerate(args.seeds):
            keep = {}
            t0 = time.perf_counter()
            r = harness.run(args.workload, seed, args.seconds, False, t0=t0,
                            log=log, keep=keep)
            emit({"kind": "program", "seed": seed, "values": keep["values"],
                  "correct": r["correct"], "metrics": r["metrics"],
                  "prog": {k: keep["prog"][k] for k in (
                      "teacher_loss", "student_loss", "eval_loss",
                      "eval_acc")},
                  "ref": {k: keep["ref"][k] for k in (
                      "teacher_loss", "student_loss", "eval_loss",
                      "eval_acc")},
                  "wall_s": time.perf_counter() - t0})
            if i < args.control:
                import jax.numpy as jnp

                from bench.reference import Reference
                spec = harness.Spec()
                wl = spec.workload(args.workload)
                from bench.data import make_dataset
                config = spec.config(wl["config"])
                t1 = time.perf_counter()
                ctl = Reference(config, spec.traffic(wl["traffic"]),
                                make_dataset(config, seed), seed,
                                dtype=jnp.bfloat16).run(harness.WARM_MIN)
                emit({"kind": "control", "seed": seed,
                      "values": check.numbers(ctl, keep["ref"]),
                      "control": {k: ctl[k] for k in (
                          "teacher_loss", "student_loss", "eval_loss",
                          "eval_acc")},
                      "wall_s": time.perf_counter() - t1})
            if i < args.faults:
                for name, plant in faults.FAULTS.items():
                    if name == "unchanged" or (    # reads 1 by construction
                            args.fault and name not in args.fault):
                        continue
                    fk = {}
                    t1 = time.perf_counter()
                    harness.run(args.workload, seed, args.seconds, False,
                                t0=t1, log=log, keep=fk, plant=plant)
                    emit({"kind": "fault", "fault": name, "seed": seed,
                          "values": fk["values"],
                          "wall_s": time.perf_counter() - t1})


if __name__ == "__main__":
    main()
