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
from typing import Any, Dict, List, Optional

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


def kill_at(traffic: Dict[str, Any], seconds: float) -> Optional[float]:
    """The fault a mix schedules inside the window, from its ``faults``
    list: seconds after the window starts at which the server is killed
    and started again (``{"kind": "kill", "at_s": s}``), or None for a
    mix with no ``faults``.  ``kill`` is the one kind built, once a run;
    the entry names its kind so that another can be added beside it."""
    faults = traffic.get("faults")
    if not faults:
        return None
    if len(faults) != 1 or faults[0].get("kind") != "kill" or set(faults[0]) != {"kind", "at_s"}:
        raise ManifestError(f"faults {faults!r}: one {{'kind': 'kill', 'at_s': s}} is the "
                            f"only schedule built")
    at_s = float(faults[0]["at_s"])
    if not 0.0 < at_s < seconds:
        raise ManifestError(f"faults: a kill at {at_s:g}s is outside a window of {seconds:g}s")
    return at_s


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
