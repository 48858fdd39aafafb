"""Wedge detection: a per-group no-progress watchdog.

Gray failures wedge groups without killing anything: a leader severed
from its quorum (but not from its clients) keeps accepting proposals
that can never commit; a one-way partition leaves heartbeats flowing
in the direction that placates followers while append replies die in
the other.  Every liveness signal built on "is the process up" stays
green.  The only honest symptom is *no progress*: the group's commit
frontier stops advancing while proposals are pending.

This watch turns that symptom into evidence while the wedge is live.
Every ``interval`` seconds it compares two vectors the host already
holds — the commit frontier the pump's readback brought over
(``driver.last_metrics["commit_index"]``, set when a pump completes)
and the driver's per-group ``Start()`` backlog — and counts consecutive
scrapes in which a group had work pending but its commit index did not
move.  At ``stall_ticks`` consecutive no-progress scrapes the group is
declared WEDGED:

* a ``WEDGE`` flight record (flightrec.py) names the group, its stall
  length, the stalled commit index, the pending backlog, and — in the
  tag — the stuck leader and its term (``"p<peer>@t<term>"``, ``p-1``
  when the group has no leader at all);
* ``gauge.wedged_groups`` (ObsControl.gauges) carries the live count,
  so a fleet scrape sees the wedge mid-run;
* ``wedge.trips`` counts wedge onsets, ``wedge.active`` mirrors the
  gauge in the metrics registry.

Recovery is detected the same way: one commit advance (or an emptied
backlog) clears the group's stall count, drops it from the wedged set,
and the gauge falls.  The postmortem doctor pairs the WEDGE records
with the chaos fault windows to name the partition that caused the
wedge (analysis/postmortem.py, "wedged leadership").

What a scrape costs.  The decision is array arithmetic over G: ``moved
= commit > previous``, ``candidate = ~moved & (backlog > 0)``, stall
counts ``+1`` where candidate and 0 elsewhere; Python touches only
``np.flatnonzero`` of the candidates.  ``ObsControl.groups()`` (the
fleet's introspection verb: a dozen device planes turned into Python
lists) is NOT on this path.  The device is asked in two cases only,
each a gather of the rows concerned (``EngineDriver.rows_of``), never a
``[G, P]`` plane:

* a candidate exists AND the driver has begun a membership change (or
  restored one open: ``driver.config_changes``): the candidates'
  ``joint`` / ``cfg_idx`` / ``commit`` rows decide the reconfig
  exemption (``driver.reconfiguring(candidates)``).  The driver is the only origin of config entries, so a
  server that never reconfigures never pays this;
* a group is being recorded as wedged: its ``role`` / ``alive`` /
  ``term`` rows, at that scrape, for the tag.

The sealed exemption (``kv.is_sealed``) is host state, looked up for
candidates only.  A non-candidate is treated as it always was, exempt
or not (stall 0, not wedged); ``wedge.reconfig_exempt`` therefore counts
exempted *candidates*.

The frontier read here is at most one pump older than the device's.  It
is compared with itself one interval earlier and a trip takes
``stall_ticks`` scrapes in a row, so a group that commits at all still
clears; a pump that stops completing freezes the frontier and reads,
rightly, as no progress.  Before the first pump completes there is
nothing to compare and the scrape is skipped.  (On the serial tick path,
``MRT_ENGINE_PIPELINE=0``, the vector is the device array the apply
sweep has just copied; jax keeps that host copy.)

How it engages: cumulative histogram ``wedge.check_s`` (one sample a
scrape), counters ``wedge.candidates`` (stall candidates, summed over
scrapes), ``wedge.device_reads`` (scrapes that asked the device),
``wedge.reconfig_exempt``, ``wedge.watch_errors``.

Knobs (env-tunable):

* ``MRT_WEDGE_INTERVAL``  watch period, seconds (default 0.25)
* ``MRT_WEDGE_TICKS``     consecutive stalled scrapes before a group
                          is declared wedged (default 8 — i.e. two
                          seconds of no progress at the default period,
                          comfortably past an election round-trip)
* ``MRT_WEDGE_WATCH=0``   disable the watch entirely

Like the overload watch it runs on the node's scheduler loop (same
thread as dispatch), so the loop-thread-only driver state is safe to
read, and a watch tick must never take the serving loop down.
"""

from __future__ import annotations

from typing import Any, List, Optional, Set

import numpy as np

from ..utils.knobs import knob_bool, knob_float, knob_int
from . import flightrec
from .observe import ObsControl

__all__ = ["WedgeWatch", "install_wedge_watch"]


class WedgeWatch:
    """Periodic commit-frontier-vs-backlog progress check on one node."""

    def __init__(self, node: Any, interval: Optional[float] = None,
                 stall_ticks: Optional[int] = None) -> None:
        self.node = node
        self.interval = (
            interval if interval is not None
            else knob_float("MRT_WEDGE_INTERVAL")
        )
        self.stall_ticks = max(1, int(
            stall_ticks if stall_ticks is not None
            else knob_int("MRT_WEDGE_TICKS")
        ))
        self._ctl = ObsControl(node)  # for its engine-service lookup
        self._prev_commit: Optional[np.ndarray] = None  # i32[G]
        self._stall = np.zeros(0, np.int64)  # [G] consecutive stalls
        self.wedged: Set[int] = set()        # groups currently wedged
        self._stopped = False
        m = node.obs.metrics
        m.inc("wedge.candidates", 0)  # present in a scrape from the start
        m.inc("wedge.device_reads", 0)
        node.sched.call_after(self.interval, self._tick)

    def stop(self) -> None:
        self._stopped = True

    # -- one watch tick ---------------------------------------------------

    def _tick(self) -> None:
        if self._stopped or getattr(self.node, "_closed", False):
            return
        m = self.node.obs.metrics
        with m.timer("wedge.check_s"):
            try:
                self.check()
            except Exception:
                # The watch must never take the serving loop down.
                m.inc("wedge.watch_errors")
        self.node.sched.call_after(self.interval, self._tick)

    def check(self) -> int:
        """Run one progress check; returns the wedged-group count."""
        m = self.node.obs.metrics
        kv = self._ctl._engine_kv()
        driver = getattr(kv, "driver", None)
        commit = getattr(driver, "last_metrics", {}).get("commit_index")
        if commit is None:  # no engine service, or no pump completed yet
            return len(self.wedged)
        commit = np.asarray(commit)  # rebound, never written, by the pump
        G = len(commit)
        prev, self._prev_commit = self._prev_commit, commit
        backlog = getattr(driver, "backlog", None)
        if prev is None or len(prev) != G or backlog is None:
            # First scrape (or a different fleet): only the baseline.
            self._stall = np.zeros(G, np.int64)
            cand = np.zeros(G, bool)
        else:
            # Progress, or nothing owed: not a wedge.  (An idle group
            # with a severed leader is invisible here by design — no
            # client is being harmed.)
            cand = (commit <= prev) & (backlog > 0)
        asked = False
        idx = np.flatnonzero(cand)
        if idx.size:
            # Candidates intentionally paused are NOT wedges: a sealed
            # group is mid-migration (its frontier freezes by design
            # until the destination adopts), and a reconfiguring group's
            # commit may legitimately stall while the joint phase waits
            # on BOTH quorums.  Counting either would fire a false
            # "wedged leadership" anomaly exactly when self-healing is
            # working.
            m.inc("wedge.candidates", int(idx.size))
            exempt = _sealed(kv, idx)
            if getattr(driver, "config_changes", 0):
                # Only now can a membership change be open at all.
                exempt |= driver.reconfiguring(idx)
                asked = True
            if exempt.any():
                m.inc("wedge.reconfig_exempt", int(exempt.sum()))
                cand[idx[exempt]] = False
        stall = self._stall = np.where(cand, self._stall + 1, 0)
        hit = np.flatnonzero(stall >= self.stall_ticks)
        wedged = set(hit.tolist())
        trips = len(wedged - self.wedged)
        if trips:
            m.inc("wedge.trips", trips)
        self.wedged = wedged
        frec = getattr(self.node, "_frec", None)
        if hit.size and frec is not None:
            # Re-recorded every stalled scrape while wedged: the ring
            # then shows the wedge's full extent, not just its onset,
            # and the doctor reads duration straight off the records.
            asked = True
            tags = _leader_tags(driver, hit)
            for g, tag in zip(hit.tolist(), tags):
                frec.record(
                    flightrec.WEDGE,
                    code=g,
                    a=int(stall[g]),
                    b=int(commit[g]),
                    c=int(backlog[g]),
                    tag=tag,
                )
        if asked:
            m.inc("wedge.device_reads")
        m.set("wedge.active", float(len(self.wedged)))
        return len(self.wedged)


def _sealed(kv: Any, groups: np.ndarray) -> np.ndarray:
    """Which of ``groups`` (local slots) host a sealed gid: host state,
    on the servers that migrate (``_l2g`` / ``is_sealed``)."""
    l2g = getattr(kv, "_l2g", None)
    is_sealed = getattr(kv, "is_sealed", None)
    if is_sealed is None or l2g is None:
        return np.zeros(groups.size, bool)
    return np.fromiter(
        (g in l2g and is_sealed(l2g[g]) for g in groups.tolist()),
        bool, groups.size,
    )


def _leader_tags(driver: Any, groups: np.ndarray) -> List[str]:
    """``"p<peer>@t<term>"`` for each of ``groups``, read off the device
    now (``p-1``: the group has no live leader)."""
    from ..engine.core import LEADER  # local: pulls jax in

    rows = driver.rows_of(("role", "alive", "term"), groups)
    lead = (rows["role"] == LEADER) & rows["alive"].astype(bool)
    leader = np.where(lead.any(axis=1), lead.argmax(axis=1), -1)
    term = rows["term"].max(axis=1)
    return [f"p{p}@t{t}" for p, t in zip(leader.tolist(), term.tolist())]


def install_wedge_watch(
    node: Any, interval: Optional[float] = None
) -> Optional[WedgeWatch]:
    """Attach the watch to a serving node (no-op when
    ``MRT_WEDGE_WATCH=0``).  Returns the watch, kept reachable on
    ``node.wedge_watch`` (ObsControl.gauges reads it for
    ``gauge.wedged_groups``)."""
    if not knob_bool("MRT_WEDGE_WATCH"):
        return None
    watch = WedgeWatch(node, interval=interval)
    node.wedge_watch = watch
    return watch
