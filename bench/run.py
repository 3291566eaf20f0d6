"""The benchmark's command: one run of one cell, in one process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, metrics and bounds are in BENCHMARK.json; PERF.md says what each
one measures and why. Without the chips the cell asks for (a TPU) the run
stops before any phase and exits non-zero. The last line of standard
output is one JSON object: correct, attempted, failed, metrics, device
(and, traced, breakdown), then check: each number compared, with its
limit. The same numbers close standard error.

JAX's persistent compilation cache is kept in ``<checkout>/.jax_cache``,
so only a checkout's first run of a cell compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the benchmark's modules by package name; the system under test from
    # the checkout's src/ (bench/ itself is not an import root: it holds
    # a module named like the standard library's trace)
    sys.path[:] = [p for p in sys.path
                   if os.path.abspath(p or ".") != os.path.dirname(
                       os.path.abspath(__file__))]
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import cell, harness

    harness.enable_compile_cache(ROOT)

    try:
        spec = cell.load_spec(args.workload)
        result = harness.run(spec, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START)
    except harness.NoDevice as e:
        print(f"bench: {e}; this benchmark runs only on the chip",
              file=sys.stderr)
        return 2
    print(json.dumps({k: v for k, v in result.items()
                      if not k.startswith("_")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
