"""From a ``jax.profiler`` trace to the numbers the per-layer metrics
read.  Nothing but JAX reads the file (``ProfileData.from_file``).

``load`` keeps what the reduction needs as plain lists, in nanoseconds
on the profiler's clock (host and device planes share it):

* device planes (``/device:TPU:n``): the lines ``XLA Modules`` — one
  event per execution of a compiled program — and ``XLA Ops``, one per
  operation inside it;
* host planes (``/host:CPU``), one line per thread: the events named
  ``bench.collect`` (the benchmark's, around each traced collect) and
  ``<Operator>Exec`` (the engine's ``TraceAnnotation`` around every
  batch pull, exec/core.py).

``reduce`` works on that form alone, so benchmark/tests checks it on a
small recorded trace kept as JSON.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

COLLECT = "bench.collect"
MODULES, OPS = "XLA Modules", "XLA Ops"
#: idle gaps are named in pieces of at most a millisecond
GAP_PIECE_NS = 1e6
#: an op's name is ``%all-gather.3`` or its whole HLO line, which starts
#: the same way; the -start and -done halves of an async one both count
COLLECTIVE = re.compile(
    r"^%?(all-to-all|all-gather|all-reduce|collective-permute|"
    r"reduce-scatter)")


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(
            f"{len(found)} .xplane.pb files under {trace_dir}, expected 1")
    return found[0]


def _is_annotation(name: str) -> bool:
    return name == COLLECT or name.endswith("Exec")


def load(path: str) -> dict:
    """``{"devices": [{"name", "modules": [[name, start, dur]..],
    "ops": [..]}], "host": [{"line", "events": [..]}],
    "plane_names": [..]}``, events sorted by start."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, host, names = [], [], []
    for plane in data.planes:
        names.append(plane.name)
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if MODULES not in lines and OPS not in lines:
                continue
            dev = {"name": plane.name}
            for key, line_name in (("modules", MODULES), ("ops", OPS)):
                line = lines.get(line_name)
                dev[key] = sorted(
                    ([e.name, e.start_ns, e.duration_ns]
                     for e in line.events),
                    key=lambda e: e[1]) if line is not None else []
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = sorted(
                    ([e.name, e.start_ns, e.duration_ns]
                     for e in line.events if _is_annotation(e.name)),
                    key=lambda e: (e[1], -e[2]))
                if events:
                    host.append({"line": line.name, "events": events})
    devices.sort(key=lambda d: d["name"])
    return {"devices": devices, "host": host, "plane_names": names}


# ---------------------------------------------------------------- pieces

def union(intervals) -> list:
    """Sorted, disjoint ``[start, end]`` covering the same instants."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clipped(merged, lo: float, hi: float) -> float:
    """Length of disjoint ``merged`` inside ``[lo, hi]``."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def module_name(event_name: str) -> str:
    """``jit_update(1234567)`` -> ``jit_update``: the trace appends the
    program's fingerprint."""
    return re.sub(r"\(\d+\)$", "", event_name)


def self_times(events) -> list:
    """``[name, start, self_ns]`` for each event of one thread: its
    duration minus the part its children (events nested in it on the
    same thread) cover.  ``events`` sorted by (start, -duration)."""
    out, stack = [], []     # stack of [name, start, end, child_cover]

    def close(upto):
        while stack and stack[-1][2] <= upto:
            name, start, end, cover = stack.pop()
            out.append([name, start, (end - start) - cover])
            if stack:
                stack[-1][3] += end - start
    for name, start, dur in events:
        close(start)
        stack.append([name, start, start + dur, 0.0])
    close(float("inf"))
    return out


def innermost_timeline(events) -> tuple:
    """Change points ``(times, labels)`` of one thread: from
    ``times[i]`` on, ``labels[i]`` is the innermost event open (None:
    none).  ``events`` sorted by (start, -duration)."""
    times, labels, stack = [], [], []

    def mark(t):
        label = stack[-1][0] if stack else None
        if times and times[-1] == t:
            labels[-1] = label
        else:
            times.append(t)
            labels.append(label)

    def close(upto):
        while stack and stack[-1][1] <= upto:
            _, end = stack.pop()
            mark(end)
    for name, start, dur in events:
        close(start)
        stack.append((name, start + dur))
        mark(start)
    close(float("inf"))
    return times, labels


def host_label_at(timelines, t: float) -> str | None:
    """The innermost annotation open at ``t`` on any thread; with
    several threads inside one, the one entered last."""
    best, best_since = None, -1.0
    for times, labels in timelines:
        i = bisect.bisect_right(times, t) - 1
        if i >= 0 and labels[i] is not None and times[i] > best_since:
            best, best_since = labels[i], times[i]
    return best


# ------------------------------------------------------------- reduction

def reduce(planes: dict, n_devices: int) -> dict:
    """Seconds and counts per traced collect and per device; see the
    metric readers under benchmark/layer_metrics for who reads what."""
    ns = 1e-9
    collects = sorted(
        (e for ln in planes["host"] for e in ln["events"]
         if e[0] == COLLECT), key=lambda e: e[1])
    if not collects:
        raise ValueError("trace holds no bench.collect annotation")
    spans = [(s, s + d) for _, s, d in collects]
    lo, hi = spans[0][0], spans[-1][1]
    devices = planes["devices"][:n_devices]

    per_thread_self = [self_times(ln["events"]) for ln in planes["host"]]
    timelines = [innermost_timeline(ln["events"]) for ln in planes["host"]]
    busy = [union([s, s + d] for _, s, d in (dev["ops"] or dev["modules"]))
            for dev in devices]

    out_collects = []
    for c0, c1 in spans:
        op_self, first_op = {}, None
        for thread in per_thread_self:
            for name, start, self_ns in thread:
                if name != COLLECT and c0 <= start < c1:
                    op_self[name] = op_self.get(name, 0.0) + self_ns * ns
                    first_op = start if first_op is None \
                        else min(first_op, start)
        dev0 = devices[0] if devices else {"modules": [], "ops": []}
        out_collects.append({
            "seconds": (c1 - c0) * ns,
            "plan_s": None if first_op is None else (first_op - c0) * ns,
            "op_self_s": op_self,
            "device_busy_s": [clipped(b, c0, c1) * ns for b in busy],
            "program_launches": sum(1 for _, s, _ in dev0["modules"]
                                    if c0 <= s < c1),
            "collective_s": sum(d for n, s, d in dev0["ops"]
                                if c0 <= s < c1 and COLLECTIVE.match(n)) * ns,
        })

    # the whole traced window: first collect's start to last one's end
    window_s = (hi - lo) * ns
    per_device = []
    for dev, b in zip(devices, busy):
        busy_s = clipped(b, lo, hi) * ns
        per_device.append({"plane": dev["name"], "busy_s": busy_s,
                           "idle_pct": 100.0 * (1.0 - busy_s / window_s)})

    by_module: dict = {}
    idle: dict = {}
    if devices:
        for name, s, d in devices[0]["modules"]:
            if lo <= s < hi:
                by_module[module_name(name)] = \
                    by_module.get(module_name(name), 0.0) + d * ns
        # device 0's idle gaps, each named by what the host was in
        edges = [[lo, lo]] + [[max(s, lo), min(e, hi)] for s, e in busy[0]
                              if e > lo and s < hi] + [[hi, hi]]
        for (_, gap0), (gap1, _) in zip(edges, edges[1:]):
            if gap1 <= gap0:
                continue
            # a long gap outlasts what the host was doing when it began:
            # name it piece by piece, each at most GAP_PIECE_NS long
            pieces = max(1, int(-(-(gap1 - gap0) // GAP_PIECE_NS)))
            step = (gap1 - gap0) / pieces
            for i in range(pieces):
                label = host_label_at(timelines, gap0 + (i + 0.5) * step)
                if label == COLLECT:
                    label = "no_annotation"
                elif label is None:
                    label = "between_collects"
                idle[label] = idle.get(label, 0.0) + step * ns

    def top(d):
        return sorted(([k, v] for k, v in d.items()),
                      key=lambda kv: -kv[1])

    return {
        "window_s": window_s,
        "busy_s": (sum(d["busy_s"] for d in per_device) / len(per_device)
                   if per_device else 0.0),
        "devices": per_device,
        "collects": out_collects,
        "device_ops": top(by_module),
        "idle_gaps": top(idle),
        "plane_names": planes["plane_names"],
    }
