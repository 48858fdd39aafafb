"""Readers of per-layer metrics.  A metric is ``layers/<name>.json``:
``layer``, ``moves`` and a ``reader`` with its arguments; the reader
takes the number from what one run gathered — the server's counters and
cumulative histograms scraped just before and just after the window
(``Obs.snapshot``, ``Obs.hist``) and differenced, the client's own
clock, and the reduced profiler trace.  A reader that finds no event in
the window returns ``None`` and the metric is left out of the line,
with the reason on an earlier one: never a 0.

Readers (``"reader": {"kind": ..., ...}``):

``hist_mean``      ``hists`` (one name or a list), ``scale``: the sum over
                   the named histograms of (sum delta / n delta), times scale
``hist_sum``       ``hist``, ``scale``: sum delta times scale
``counter_delta``  ``counter``: how much it grew
``counter_rate``   ``counter``: growth per second of window
``counter_ratio``  ``num``, ``den`` (one name or a list, summed), ``scale``:
                   growth of one over growth of the other, times scale
                   (0 where ``num`` is one of ``den`` and only the rest grew)
``counter_per_op`` ``counter``, ``scale``: growth over the operations the
                   load generator completed in the window, times scale
                   (0 where the counter is there and did not grow: a share
                   of the operations)
``client``         ``field``: a number the load generator measured
``trace``          ``field``: a number from the reduced trace
``restart_gauge``  ``gauge``: a gauge of the server started again after a
                   ``kill -9`` inside the window, from its first scrape after
                   ``ready`` (a run with no such restart reads nothing)
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

Reading = Tuple[Optional[float], str]  # (value or None, why not)


class Gathered:
    """What one run hands the readers."""

    def __init__(self, window_s: float, counters0: Dict[str, float],
                 counters1: Dict[str, float], hists0: Dict[str, Any],
                 hists1: Dict[str, Any], client: Dict[str, float],
                 trace: Dict[str, float],
                 restarted: Optional[Dict[str, float]] = None) -> None:
        self.window_s = window_s
        self.c0, self.c1 = counters0, counters1
        self.h0, self.h1 = hists0, hists1
        self.client, self.trace = client, trace
        self.restarted = restarted

    def counter(self, name: str) -> Optional[float]:
        if name not in self.c1:
            return None
        return float(self.c1[name]) - float(self.c0.get(name, 0))

    def hist(self, name: str) -> Optional[Tuple[float, float]]:
        """(n delta, sum delta) of a cumulative histogram."""
        if name not in self.h1:
            return None
        then = self.h0.get(name, {"n": 0, "sum": 0.0})
        return (float(self.h1[name]["n"]) - float(then["n"]),
                float(self.h1[name]["sum"]) - float(then["sum"]))


def _hist_mean(g: Gathered, r: Dict[str, Any]) -> Reading:
    names = r["hists"] if isinstance(r["hists"], list) else [r["hists"]]
    total = 0.0
    for name in names:
        d = g.hist(name)
        if d is None or d[0] <= 0:
            return None, f"histogram {name} took no sample in the window"
        total += d[1] / d[0]
    return total * float(r.get("scale", 1.0)), ""


def _hist_sum(g: Gathered, r: Dict[str, Any]) -> Reading:
    d = g.hist(r["hist"])
    if d is None or d[0] <= 0:
        return None, f"histogram {r['hist']} took no sample in the window"
    return d[1] * float(r.get("scale", 1.0)), ""


def _counter_delta(g: Gathered, r: Dict[str, Any]) -> Reading:
    d = g.counter(r["counter"])
    if d is None or d <= 0:
        return None, f"counter {r['counter']} did not grow in the window"
    return d, ""


def _counter_rate(g: Gathered, r: Dict[str, Any]) -> Reading:
    d, why = _counter_delta(g, r)
    return (None, why) if d is None else (d / g.window_s, "")


def _counter_ratio(g: Gathered, r: Dict[str, Any]) -> Reading:
    dens = r["den"] if isinstance(r["den"], list) else [r["den"]]
    grown = sum(g.counter(name) or 0.0 for name in dens)
    if grown <= 0:
        return None, f"counter {' + '.join(dens)} did not grow in the window"
    num, why = _counter_delta(g, {"counter": r["num"]})
    if num is None:
        # A share of a whole that grew (``num`` is one of ``den``) reads 0
        # where its part did not: no call shed is 0 %, not nothing to read.
        return (0.0, "") if r["num"] in dens else (None, why)
    return num / grown * float(r.get("scale", 1.0)), ""


def _counter_per_op(g: Gathered, r: Dict[str, Any]) -> Reading:
    d, ops = g.counter(r["counter"]), g.client.get("completed")
    if d is None or not ops:
        return None, f"no counter {r['counter']}, or no operation completed in the window"
    return d / ops * float(r.get("scale", 1.0)), ""


def _field(source: str) -> Callable[[Gathered, Dict[str, Any]], Reading]:
    def read(g: Gathered, r: Dict[str, Any]) -> Reading:
        v = getattr(g, source).get(r["field"])
        if v is None:
            return None, f"the {source} gave no {r['field']}"
        return float(v), ""
    return read


def _restart_gauge(g: Gathered, r: Dict[str, Any]) -> Reading:
    if g.restarted is None:
        return None, "the server was not started again inside the window"
    if r["gauge"] not in g.restarted:
        return None, f"the restarted server has no gauge {r['gauge']}"
    return float(g.restarted[r["gauge"]]), ""


READERS: Dict[str, Callable[[Gathered, Dict[str, Any]], Reading]] = {
    "hist_mean": _hist_mean,
    "hist_sum": _hist_sum,
    "counter_delta": _counter_delta,
    "counter_rate": _counter_rate,
    "counter_ratio": _counter_ratio,
    "counter_per_op": _counter_per_op,
    "client": _field("client"),
    "trace": _field("trace"),
    "restart_gauge": _restart_gauge,
}


def read(spec: Dict[str, Any], g: Gathered) -> Reading:
    r = spec["reader"]
    kind = r.get("kind")
    if kind not in READERS:
        raise ValueError(f"layer metric {spec['name']}: no reader {kind!r}")
    return READERS[kind](g, r)
