"""From one ``jax.profiler`` trace to numbers.

    python chipbench/trace_reduce.py <trace-dir> [--program jit_step_ticks] [--describe]

Reads the newest ``*.xplane.pb`` under ``<trace-dir>`` with
``jax.profiler.ProfileData`` (nothing but jax; run it pinned to
``JAX_PLATFORMS=cpu``, it needs no device) and prints one JSON object:

``window_s``   the traced span: first to last event on any device plane
``busy_s``     seconds in which an operation ran on the device — the union
               of the intervals on the device's "XLA Ops" line — averaged
               over the device planes
``metrics``    ``program_s`` and ``programs``: device time and count of the
               executions of ``--program`` on the "XLA Modules" line
``breakdown``  ``device_ops``: the ten operations with most device time of
               their own (a ``while`` without the operations in its body);
               ``idle_gaps``: the ten kinds of gap with most time — between
               two programs, named by the programs either side, or inside a
               program, between its operations (what the host did in a gap
               the program does not say yet: see PERF.md)

A TPU's planes are named ``/device:TPU:<n>``.  Their "XLA Modules" line
has one event per executed program (``jit_step_ticks(<fingerprint>)``),
their "XLA Ops" line one per operation inside it, named by its whole HLO
text (cut here to the name before `` = ``).  ``--describe`` lists planes,
lines and the commonest event names instead, for a look by hand;
``--dump-json FILE --cut-ms N`` writes the first N ms of the device
planes as plain intervals, which is what ``tests/`` keeps a recording as.
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import sys
from typing import Any, Dict, List, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
TOP = 10

Interval = Tuple[float, float, str]  # start_ns, end_ns, name


def newest_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise SystemExit(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def read_planes(path: str) -> Dict[str, Dict[str, List[Interval]]]:
    """{plane: {line: [(start, end, name), ...]}} for the device planes."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes: Dict[str, Dict[str, List[Interval]]] = {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines[line.name] = [
                (e.start_ns, e.start_ns + e.duration_ns, short_name(e.name))
                for e in line.events
            ]
    return planes


def short_name(name: str) -> str:
    """An operation's event is named by its whole HLO text:
    ``%fusion.3 = s32[10000,3]{...} fusion(...)`` -> ``%fusion.3``."""
    return name.split(" = ", 1)[0][:80]


def union_s(intervals: List[Interval]) -> float:
    """Seconds covered by at least one interval."""
    busy, end = 0.0, float("-inf")
    for s, e, _ in sorted(intervals):
        if s > end:
            busy += e - s
            end = e
        elif e > end:
            busy += e - end
            end = e
    return busy / 1e9


def gaps(intervals: List[Interval]) -> List[Tuple[str, float]]:
    """Every gap in which none of ``intervals`` ran: (name, seconds)."""
    out: List[Tuple[str, float]] = []
    end, last = None, ""
    for s, e, name in sorted(intervals):
        if end is not None and s > end:
            out.append((f"after {base_name(last)} before {base_name(name)}",
                        (s - end) / 1e9))
        if end is None or e > end:
            end, last = e, name
    return out


def self_times(intervals: List[Interval]) -> Dict[str, float]:
    """Seconds of each operation's own: an operation that encloses
    others (a ``while`` and its body) is charged what they leave."""
    own: Dict[str, float] = collections.defaultdict(float)
    stack: List[Interval] = []
    for iv in sorted(intervals, key=lambda iv: (iv[0], -iv[1])):
        while stack and stack[-1][1] <= iv[0]:
            stack.pop()
        own[iv[2]] += (iv[1] - iv[0]) / 1e9
        if stack:
            own[stack[-1][2]] -= (iv[1] - iv[0]) / 1e9
        stack.append(iv)
    return own


def base_name(name: str) -> str:
    """``jit_step_ticks(123)`` -> ``jit_step_ticks``."""
    return name.split("(", 1)[0]


def reduce(planes: Dict[str, Dict[str, List[Interval]]], program: str) -> Dict[str, Any]:
    if not planes:
        raise SystemExit("the trace has no device plane: nothing ran on a TPU")
    every = [iv for lines in planes.values() for evs in lines.values() for iv in evs]
    if not every:
        raise SystemExit("the device planes hold no event")
    window_s = (max(e for _, e, _ in every) - min(s for s, _, _ in every)) / 1e9
    busy: List[float] = []
    op_time: Dict[str, float] = collections.defaultdict(float)
    gap_time: Dict[str, float] = collections.defaultdict(float)
    program_s, programs = 0.0, 0
    n = len(planes)
    for lines in planes.values():
        ops, modules = lines.get(OPS_LINE, []), lines.get(MODULES_LINE, [])
        busy.append(union_s(ops))
        for name, secs in self_times(ops).items():
            op_time[name] += secs
        # Gaps of one kind are one entry: its seconds are their sum,
        # averaged over the devices like busy_s.
        for name, secs in gaps(modules):
            gap_time[name] += secs / n
        inside = union_s(modules) - busy[-1]
        if inside > 0:
            gap_time["inside a program, between its operations"] += inside / n
        for s, e, name in modules:
            if base_name(name) == program:
                program_s += (e - s) / 1e9
                programs += 1
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    metrics: Dict[str, float] = {}
    if programs:
        metrics = {"program_s": program_s / n, "programs": programs / n}
    return {
        "window_s": window_s, "busy_s": sum(busy) / n, "devices": n,
        "metrics": metrics,
        "breakdown": {
            "device_ops": top({k: v / n for k, v in op_time.items()}),
            "idle_gaps": top(gap_time),
        },
    }


def describe(path: str) -> None:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            names = collections.Counter()
            total = collections.Counter()
            n = 0
            for e in line.events:
                names[e.name] += 1
                total[e.name] += e.duration_ns
                n += 1
            print(f"  LINE {line.name!r}: {n} events")
            for name, secs in total.most_common(8):
                print(f"      {names[name]:7d} x {name[:90]!r}  {secs / 1e6:.3f} ms")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir")
    ap.add_argument("--program", default="")
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--dump-json", default="")
    ap.add_argument("--cut-ms", type=float, default=50.0)
    ns = ap.parse_args(argv)
    path = newest_xplane(ns.trace_dir)
    if ns.describe:
        describe(path)
        return 0
    planes = read_planes(path)
    if ns.dump_json:
        t0 = min(s for ls in planes.values() for evs in ls.values() for s, _, _ in evs)
        cut = {
            plane: {line: [[s - t0, e - t0, name] for s, e, name in evs
                           if e - t0 <= ns.cut_ms * 1e6]
                    for line, evs in ls.items() if line in (OPS_LINE, MODULES_LINE)}
            for plane, ls in planes.items()
        }
        with open(ns.dump_json, "w") as f:
            json.dump(cut, f, separators=(",", ":"))
        return 0
    print(json.dumps(reduce(planes, ns.program)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
