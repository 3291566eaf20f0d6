"""Readings that set a cell's check limit, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 3
    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --fault lost_injection

For each seed: one run of the cell (a short window at the cell's own
rate, the same sample size as a benchmark run). Its served slates give
the program's reading, the widest gap against the reference. With
``--control`` (default fp8) the reference at that precision then decodes
the same sampled prompts in the program's place, and the run's check
judges those slates: ``correct`` must come out false. With ``--fault``
a fault of bench/faults.py is planted in the served path instead, and
the run's check judges the served slates. One JSON line per seed, then
the largest served reading and the smallest reading of what was judged.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", default="fp8")
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".")
                   != os.path.dirname(os.path.abspath(__file__))]
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import cell, faults, harness
    harness.enable_compile_cache(ROOT)

    spec = cell.load_spec(args.workload)
    fault = None if args.fault is None else faults.ALL[args.fault]
    control = None if fault is not None else (args.control or None)
    served, judged = [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        r = harness.run(spec, seed, args.seconds, False, control=control,
                        fault=fault)
        served.append(r["_served_gap"])
        judged.append(r["check"]["logit_gap"]["value"])
        print(json.dumps({"seed": seed, "served_gap": r["_served_gap"],
                          "judged": args.fault or control or "served",
                          "correct": r["correct"], "check": r["check"],
                          "window": r["_window"]}), flush=True)
    print(json.dumps({"workload": args.workload,
                      "judged": args.fault or control or "served",
                      "served_gap_max": max(served),
                      "judged_gap_min": min(judged),
                      "judged_gap_max": max(judged)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
