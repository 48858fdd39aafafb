"""Admin calls inside the window: a mix's ``admin`` faults.

    "faults": [{"kind": "admin", "at_s": 14, "op": "leave", "gids": {"every": 3}},
               {"kind": "admin", "at_s": 30, "op": "join", "gids": {"every": 3}}]

:class:`AdminCalls` is a thread of its own with a connection of its own
(an operator's tool beside the clients).  At each entry's ``at_s`` it
sends ``<service>.admin((op, gids, cmd))``: ``gids`` from the entry's
rule at the configuration's group count (``manifest.gids``), ``cmd`` a
command id, so a retry of the same call is applied once.  It records
``t_call`` and ``t_ack``; a reply other than ``OK``, or no ack within
``ACK_CAP_S``, fails the run.  Where the service has a settle rule
(:data:`SETTLE`), it then polls until the call's work is over
(``t_settle``), or fails the run after ``SETTLE_CAP_S``; the next entry
waits for that.  A service with no rule records the ack alone.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import check
import manifest
import shardref

ACK_CAP_S = 60.0       # from the call to its OK
SETTLE_CAP_S = 60.0    # from the call to its work done
POLL_S = 0.5           # between two polls of a settle rule
CMD_BASE = 1000        # command ids: above any the server allocates itself at start


class AdminFailed(Exception):
    """An admin call was refused, or its work did not end in time."""


class ShardSettle:
    """The sharded service's settle rule (``EngineShardKV``; the rule of
    ``chip_smoke.py``'s ``reconfigure``).  The latest config is read just
    before the call and just after the ack; the shards whose owner
    differs are the shards that move.  Settled: every group applied the
    new config (``shard.config_applies`` grew by G - 1 a config), every
    moved shard was confirmed at its new owner (``shard.confirms`` grew
    by the shards moved), and the live slots number the shards again
    (``shard.slots == shard.count``).  ``Obs.snapshot`` is the one call
    that returns the ``shard.*`` counters; it also reads every group's
    row off the device, which the serving loop pays at every poll."""

    COUNTERS = ("shard.config_applies", "shard.confirms", "shard.inserts", "shard.deletes")
    OPS = ("join", "leave")   # the ops whose payload is a list of gids

    def __init__(self, service: str, config: Dict[str, Any]) -> None:
        self.service = service
        self.groups, self.shards = int(config["groups"]), int(config["shards"])

    def config(self, client) -> Dict[str, Any]:
        num, owners, groups = client.request(f"{self.service}.config", None)
        return {"num": int(num), "owners": np.asarray(owners, np.int64),
                "groups": sorted(int(g) for g in groups)}

    def before(self, client, leg: Dict[str, Any]) -> None:
        leg["counters0"] = client.call("Obs.snapshot")["metrics"]
        leg["config0"] = self.config(client)

    def after_ack(self, client, leg: Dict[str, Any]) -> None:
        leg["config1"] = self.config(client)
        leg["moved"] = np.flatnonzero(leg["config0"]["owners"] != leg["config1"]["owners"])

    def settled(self, client, leg: Dict[str, Any]) -> bool:
        now = client.call("Obs.snapshot")["metrics"]
        was = leg["counters0"]
        grew = {k: now.get(k, 0) - was.get(k, 0) for k in self.COUNTERS}
        leg["grew"] = grew
        configs = leg["config1"]["num"] - leg["config0"]["num"]
        return (now["shard.slots"] == now["shard.count"]
                and grew["shard.config_applies"] >= (self.groups - 1) * configs
                and grew["shard.confirms"] >= len(leg["moved"]))

    def read_back(self, legs: List[Dict[str, Any]], records) -> np.ndarray:
        """Every key of every shard that moved, to be read back after the window."""
        moved = np.unique(np.concatenate([leg["moved"] for leg in legs]))
        return np.flatnonzero(np.isin(shardref.shard_of(records.keys, self.shards), moved))

    def check(self, legs: List[Dict[str, Any]], history, keys: np.ndarray, tags: np.ndarray):
        """What ``correct`` compares across the calls: ownership against
        the plain reference, and the moved keys' values."""
        wrong, counts = check.reconfiguration(legs, self.groups, self.shards)
        lines, lost = check.lost_on_the_move(history, keys, tags)
        return wrong + lines, {**counts, **lost}


SETTLE: Dict[str, Callable[[str, Dict[str, Any]], Any]] = {"EngineShardKV": ShardSettle}


class AdminCalls(threading.Thread):
    """The mix's admin entries, each at ``t0 + at_s`` on the
    ``time.perf_counter`` clock (:meth:`begin`).  ``legs`` holds one
    record a call."""

    def __init__(self, service: str, calls: List[Dict[str, Any]],
                 config: Dict[str, Any]) -> None:
        super().__init__(name="chipbench-admin", daemon=True)
        self.client: Any = None
        self.service, self.calls = service, calls
        self.groups = int(config["groups"])
        rule = SETTLE.get(service)
        self.rule = rule(service, config) if rule else None
        bad = [f["op"] for f in calls if self.rule and f["op"] not in self.rule.OPS]
        if bad:
            raise AdminFailed(f"{service}'s settle rule follows {self.rule.OPS}, not {bad}")
        self.t0 = 0.0
        self.legs: List[Dict[str, Any]] = []
        self.error: Optional[BaseException] = None

    def send(self, op: str, gids: List[int], cmd: int) -> str:
        """One admin call, asked again under the same ``cmd`` while it
        times out; returns the reply's ``err``."""
        deadline = time.perf_counter() + ACK_CAP_S
        while True:
            left = deadline - time.perf_counter()
            reply = self.client.request(f"{self.service}.admin", (op, gids, cmd), max(left, 0.1))
            err = getattr(reply, "err", reply)
            if err != "ErrTimeout" or time.perf_counter() > deadline:
                return err

    def begin(self, client, t0: float) -> None:
        """Starts the calls on ``client`` (``run.Client``, a connection of their own)."""
        self.client, self.t0 = client, t0
        self.start()

    def run(self) -> None:
        try:
            for i, entry in enumerate(self.calls):
                self._leg(i, entry)
        except Exception as exc:   # the thread's boundary: the main thread reports it
            self.error = exc

    def _leg(self, i: int, entry: Dict[str, Any]) -> None:
        gids = manifest.gids(entry["gids"], self.groups)
        leg: Dict[str, Any] = {"op": entry["op"], "gids": gids, "at_s": float(entry["at_s"])}
        self.legs.append(leg)
        late = time.perf_counter() - (self.t0 + leg["at_s"])
        if late > 0:
            leg["late_s"] = late
        else:
            time.sleep(-late)
        if self.rule:
            self.rule.before(self.client, leg)
        leg["t_call"] = time.perf_counter()
        err = self.send(entry["op"], gids, CMD_BASE + i)
        leg["t_ack"] = time.perf_counter()
        if err != "OK":
            raise AdminFailed(f"{self.service}.admin {entry['op']} of {len(gids)} groups said "
                               f"{err!r} after {leg['t_ack'] - leg['t_call']:.1f}s")
        if not self.rule:
            return
        self.rule.after_ack(self.client, leg)
        leg["polls_s"] = []
        while True:
            p0 = time.perf_counter()
            done = self.rule.settled(self.client, leg)
            p1 = time.perf_counter()
            leg["polls_s"].append(p1 - p0)
            if done:
                leg["t_settle"] = p1
                return
            if p1 - leg["t_call"] > SETTLE_CAP_S:
                raise AdminFailed(f"{entry['op']} of {len(gids)} groups did not settle within "
                                   f"{SETTLE_CAP_S:.0f}s of the call: grew {leg['grew']}")
            time.sleep(max(p0 + POLL_S - time.perf_counter(), 0.0))

    def finish(self, cap_s: float) -> List[Dict[str, Any]]:
        """Waits for the last entry's work; re-raises what failed it."""
        self.join(cap_s)
        if self.is_alive():
            raise AdminFailed(f"the admin calls had not ended {cap_s:.0f}s after the window")
        if self.error is not None:
            raise self.error
        return self.legs
