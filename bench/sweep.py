"""The knee of a cell: the highest arrival rate the gateway sustains.

    python3 bench/sweep.py --workload <cell> --rates 60,90,120 --seconds 6

Runs the cell's window once per rate, in one process, without the check,
and prints per rate the slates completed per second, the latency tails
and how late the generator submitted arrivals. Below the knee the
completions keep pace with the arrivals and lateness stays within about
a pane; above it the backlog, and so lateness, grows through the window.
"""
import argparse
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".")
                   != os.path.dirname(os.path.abspath(__file__))]
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import cell, harness
    harness.enable_compile_cache(ROOT)

    base = cell.load_spec(args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        spec = copy.deepcopy(base)
        spec.cell["rate_per_s"] = rate
        r = harness.run(spec, args.seed, args.seconds, False,
                        reference=False)
        w = r["_window"]
        print(json.dumps({"rate": rate, "slates_per_s":
                          r["metrics"]["slates_per_s"]["value"],
                          "seconds": w["seconds"], "turns": w["turns"],
                          "rows_per_pane": w["rows_per_pane"],
                          "latency_ms_p50": w["latency_ms_p50"],
                          "latency_ms_p95": w["latency_ms_p95"],
                          "lateness_ms_p95": w["lateness_ms_p95"],
                          "lateness_ms_max": w["lateness_ms_max"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
