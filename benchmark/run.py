#!/usr/bin/env python3
"""run.py - run one cell of BENCHMARK.json and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic mix, makes inputs and weights
from the seed, warms up the cell's own shapes (all of that is `setup_s`),
measures for `--seconds`, checks the outputs against the plain reference,
and prints one JSON object as the last line of standard output: the cell's
end-to-end metrics with `--trace 0`, its per-layer metrics, the device's
busy seconds and a breakdown with `--trace 1`. It exits non-zero, printing
no result, when JAX finds no TPU or another number of chips than the cell
asks for. `BENCH_RUN` in the environment is ignored.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    import harness
    try:
        line = harness.run_cell(os.path.join(ROOT, "BENCHMARK.json"),
                                args.workload, args.seed, args.seconds,
                                args.trace, t_start=T_START)
    except harness.BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
