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
#: set by the process that started this one anew: when that one began
STARTED_AT = "BENCHMARK_STARTED_AT"


def under_process_env(environ: dict, t_start: float) -> dict | None:
    """The environment to start anew under, or None where ``environ``
    already holds every variable of benchmark/process_env.json (glibc
    reads its own at process start, so setting them here is too late).
    The clock is the system's monotonic one, so the first start's
    reading stays good in the process that follows."""
    with open(os.path.join(ROOT, "benchmark", "process_env.json")) as f:
        want = json.load(f)["env"]
    if all(environ.get(k) == v for k, v in want.items()):
        return None
    return {**environ, **want, STARTED_AT: repr(t_start)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args()
    env = under_process_env(dict(os.environ), T_START)
    if env is not None:
        sys.stdout.flush()
        os.execve(sys.executable, [sys.executable] + sys.argv, env)
    t_start = float(os.environ.get(STARTED_AT, T_START))
    sys.path.insert(0, ROOT)
    from benchmark.harness import run
    result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                 root=ROOT, t_start=t_start)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
