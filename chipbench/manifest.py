"""Find a cell's files by the names ``BENCHMARK.json`` gives.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix; each is one JSON file under ``chipbench/``, found by that name.  A
per-layer metric is ``chipbench/layers/<name>.json``.  Nothing here
knows any particular cell: a later PR adds files and manifest entries,
and edits no code.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class ManifestError(Exception):
    """``BENCHMARK.json`` and the files under ``chipbench/`` disagree."""


def _load(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"{os.path.relpath(path, ROOT)}: no such file") from None


def manifest() -> Dict[str, Any]:
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


FAULT_KINDS = ("kill", "admin")
_ENTRY_KEYS = {"kill": {"kind", "at_s"}, "admin": {"kind", "at_s", "op", "gids"}}


def _schedule(traffic: Dict[str, Any], seconds: float) -> List[Dict[str, Any]]:
    """A mix's ``faults``, checked: one ``kill``, or any number of
    ``admin`` calls in time order (``[]`` for a mix with none).  Each entry
    names its kind and is inside the window; a schedule that mixes the
    two kinds is not built."""
    faults = traffic.get("faults")
    if not faults:
        return []
    kinds = {f.get("kind") for f in faults}
    if not kinds <= set(FAULT_KINDS) or len(kinds) != 1:
        raise ManifestError(f"faults {faults!r}: one kind of {FAULT_KINDS} a schedule")
    kind = kinds.pop()
    if kind == "kill" and len(faults) != 1:
        raise ManifestError(f"faults {faults!r}: one kill a run is the schedule built")
    last = 0.0
    for f in faults:
        if set(f) != _ENTRY_KEYS[kind]:
            raise ManifestError(f"faults: {f!r} is not {sorted(_ENTRY_KEYS[kind])}")
        at_s = float(f["at_s"])
        if not 0.0 < at_s < seconds:
            raise ManifestError(f"faults: a {kind} at {at_s:g}s is outside a window of "
                                f"{seconds:g}s")
        if at_s <= last:
            raise ManifestError(f"faults: {f!r} is not after the entry before it")
        last = at_s
        if kind == "admin":
            rule = f["gids"]
            if (not isinstance(rule, dict) or set(rule) != {"every"}
                    or not isinstance(rule["every"], int) or rule["every"] < 1):
                raise ManifestError(f"faults: gids {rule!r} is not {{'every': n}}, n >= 1")
    return faults


def kill_at(traffic: Dict[str, Any], seconds: float) -> Optional[float]:
    """Seconds after the window starts at which the server is killed and
    started again (``{"kind": "kill", "at_s": s}``), or None for a mix
    whose ``faults`` schedule no kill."""
    faults = _schedule(traffic, seconds)
    return float(faults[0]["at_s"]) if faults and faults[0]["kind"] == "kill" else None


def admin_calls(traffic: Dict[str, Any], seconds: float) -> List[Dict[str, Any]]:
    """The admin calls a mix schedules inside the window, in time order:
    ``{"kind": "admin", "at_s": s, "op": <one of the service's ADMIN_OPS>,
    "gids": {"every": n}}``, or ``[]``."""
    return [f for f in _schedule(traffic, seconds) if f["kind"] == "admin"]


def gids(rule: Dict[str, int], groups: int) -> List[int]:
    """The replica groups a ``gids`` rule names at ``groups`` engine
    groups (group 0 is the controller): ``{"every": n}`` is n, 2n, ...
    below ``groups``, so the rule keeps its shape at any size."""
    return list(range(rule["every"], groups, rule["every"]))


def admin_ops(service: str) -> Tuple[str, ...]:
    """The ``ADMIN_OPS`` of the program's ``<service>Service`` class (the
    operations its ``admin`` RPC takes), read from its source and not
    imported: the manifest needs no jax.  Empty where the service takes
    no admin calls."""
    pkg = os.path.join(ROOT, "multiraft_tpu", "distributed")
    if not os.path.isdir(pkg):
        raise ManifestError("no program beside chipbench/: multiraft_tpu/distributed is missing")
    for name in sorted(os.listdir(pkg)):
        if not name.endswith(".py"):
            continue
        with open(os.path.join(pkg, name)) as f:
            src = f.read()
        m = re.search(rf"^class {re.escape(service)}Service\b.*?(?=^\S|\Z)", src, re.S | re.M)
        if m:
            ops = re.search(r"^\s+ADMIN_OPS = \(([^)]*)\)", m.group(0), re.M)
            return tuple(re.findall(r'"(\w+)"', ops.group(1))) if ops else ()
    return ()


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str) -> Dict[str, Any]:
    """Everything one run of the cell ``name`` needs, from the files."""
    man = manifest()
    found = [w for w in man["workloads"] if w["name"] == name]
    if not found:
        known = ", ".join(w["name"] for w in man["workloads"])
        raise ManifestError(f"no workload {name!r} in BENCHMARK.json (has: {known})")
    w = found[0]
    cfg_entry = [c for c in man["configs"] if c["name"] == w["config"]]
    if not cfg_entry:
        raise ManifestError(f"workload {name!r} names no known config {w['config']!r}")
    config = _load(os.path.join(ROOT, cfg_entry[0]["file"]))
    traffic = _load(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    calls = admin_calls(traffic, man["run_seconds"])
    if calls:
        service = config.get("service", "EngineKV")
        ops = admin_ops(service)
        for f in calls:
            if f["op"] not in ops:
                raise ManifestError(f"workload {name!r}: admin op {f['op']!r} is not one of "
                                    f"{service}'s ADMIN_OPS {ops}")
    layers: List[Dict[str, Any]] = []
    for m in man["per_layer"]:
        if _applies(m, name):
            spec = _load(os.path.join(HERE, "layers", m["name"] + ".json"))
            layers.append({**spec, "name": m["name"], "unit": m["unit"]})
    e2e = [m for m in man["end_to_end"] if _applies(m, name)]
    return {
        "name": name, "chips": w["chips"], "config": config,
        "traffic": traffic, "end_to_end": e2e, "layers": layers,
    }
