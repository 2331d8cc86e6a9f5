#!/usr/bin/env python3
"""Keep a cell's loaded trace for a look by hand (and for tests/data):

    python3 benchmark/tests/record_trace.py <cell> <seed> <seconds> <out_dir>

A traced run as ``benchmark/run.py --trace 1`` makes it, plus
``<out_dir>/<cell>.json``: the planes, lines and events the reduction
reads (reduce_trace.load), before they are reduced.
"""
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

if __name__ == "__main__":
    cell, seed, seconds, out_dir = sys.argv[1:5]
    sys.path.insert(0, ROOT)
    from benchmark.harness import run
    print(json.dumps(run(cell, int(seed), float(seconds), True, root=ROOT,
                         t_start=T_START, keep_trace_dir=out_dir)),
          flush=True)
