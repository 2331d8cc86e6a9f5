#!/usr/bin/env python3
"""Cut a loaded trace (tests/record_trace.py's ``<cell>.json``) down to
one slice small enough to check by hand:

    python3 benchmark/tests/trim_trace.py <in.json> <out.json> <start_us> <end_us>

Times are microseconds from the first ``bench.collect``'s start.  Device
events that lie wholly inside the slice are kept, host annotations are
clipped to it, and every time is rebased to the slice's start and
rounded to the nanosecond, so the numbers in the file are the numbers a
reader adds up.
"""
import json
import sys


def trim(planes: dict, start_us: float, end_us: float) -> dict:
    t0 = min(e[1] for ln in planes["host"] for e in ln["events"]
             if e[0] == "bench.collect")
    lo, hi = t0 + start_us * 1e3, t0 + end_us * 1e3

    def inside(events):
        return [[n, round(s - lo), round(d)] for n, s, d in events
                if s >= lo and s + d <= hi]

    def clip(events):
        return [[n, round(max(s, lo) - lo),
                 round(min(s + d, hi) - max(s, lo))]
                for n, s, d in events if s < hi and s + d > lo]
    out = {"plane_names": planes["plane_names"], "devices": [], "host": []}
    for dev in planes["devices"]:
        out["devices"].append({"name": dev["name"],
                               "modules": inside(dev["modules"]),
                               "ops": inside(dev["ops"])})
    for ln in planes["host"]:
        events = clip(ln["events"])
        if events:
            out["host"].append({"line": ln["line"], "events": events})
    return out


if __name__ == "__main__":
    src, dst, start_us, end_us = sys.argv[1:5]
    with open(src) as f:
        planes = json.load(f)
    with open(dst, "w") as f:
        json.dump(trim(planes, float(start_us), float(end_us)), f, indent=0)
