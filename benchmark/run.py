#!/usr/bin/env python3
"""One run of one benchmark cell on the machine it is started on:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, with no result line, without the cell's chips.  The
last line of standard output is the result object (BENCHMARK.json's
contract); every earlier line is one JSON fact.
"""
import time

T_START = time.perf_counter()   # set-up is counted from process start

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    from benchmark.harness import run
    result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                 root=ROOT, t_start=T_START)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
