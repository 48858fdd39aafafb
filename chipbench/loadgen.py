"""The two load generators, on one ``RpcNode`` (one loop thread, one
connection: ``tcp.py`` keeps one connection an address, whatever the
number of client ends).

:class:`ClosedLoop`: ``clients`` ``EngineClerk`` coroutines, each issuing
its next operation when the last one is acknowledged — YCSB's client
with ``-threads <clients>``.  Latency is per operation, from the call to
the acknowledged reply, as YCSB reports it.

:class:`OpenLoop`: arrivals that do not wait.  Every operation is due at
an instant drawn before the window (``traffic.arrivals``) and is sent
then whether or not earlier ones were answered, on one of ``sessions``
``EngineClerk`` sessions (a session has one operation in flight, as a
user's has).  Latency is from when the operation was DUE to the
acknowledged reply, so a stall of the server is charged to every
operation that fell due during it, not only to those already sent.

The clerks, the node and the wire are the program's own; what is here
is the loop around them and the record of every operation: when it was
due (open loop), when it was called, when its acknowledged reply
returned, and (for a read) the tag of the value that came back.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import numpy as np

from traffic import TAG, Records


LATE_MS = 50.0  # an operation sent later than this after it was due was sent late
# A second of the window was off the schedule when more than this share of
# the operations due in it were sent late, or found no free session.
OFF_SCHEDULE_SHARE = 0.01


class Recorder:
    """Per-client arrays, written by the coroutines on the node's one
    loop thread and read by the main thread after :meth:`ClosedLoop.stop`."""

    def __init__(self, clients: int, ops_per_client: int, order: str = "C",
                 due: bool = False) -> None:
        shape = (clients, ops_per_client)
        if due:                                # an open loop's third stamp
            self.due = np.full(shape, np.nan, order=order)
        self.call = np.full(shape, np.nan, order=order)
        self.ret = np.full(shape, np.nan, order=order)     # nan: never acknowledged
        self.got = np.full(shape, -1, np.int64, order=order)  # a read's returned tag
        self.bad_value: List[str] = []        # replies that are no value of ours
        self.kept: Dict[tuple, str] = {}      # whole values read from kept keys


class ClosedLoop:
    timed_from = "call"   # the stamp of the record that latency counts from

    def __init__(self, node, end, records: Records, is_update: np.ndarray,
                 key_index: np.ndarray, keep_keys, service: str = "EngineKV") -> None:
        from multiraft_tpu.distributed.engine_clerks import EngineClerk

        self.node, self.records = node, records
        self.is_update, self.key_index = is_update, key_index
        self.clients, self.cap = is_update.shape
        self.rec = Recorder(self.clients, self.cap)
        self.keep = frozenset(int(k) for k in keep_keys)
        self.clerks = [EngineClerk(node.sched, end, service) for _ in range(self.clients)]
        self._stop = False
        self.exhausted = False
        self._futs: List[Any] = []

    def _client(self, c: int):
        ck, rec, records = self.clerks[c], self.rec, self.records
        upd = self.is_update[c].tolist()
        kix = self.key_index[c].tolist()
        keys, size = records.keys, records.valuebytes
        call, ret, got = rec.call[c], rec.ret[c], rec.got[c]
        clock = time.perf_counter
        for n in range(self.cap):
            if self._stop:
                return
            key = keys[kix[n]]
            if upd[n]:
                value = records.value(c, n)
                call[n] = clock()
                yield from ck.put(key, value)
                ret[n] = clock()
            else:
                call[n] = clock()
                v = yield from ck.get(key)
                ret[n] = clock()
                if len(v) == size and v[:TAG].isdigit():
                    got[n] = int(v[:TAG])
                    if kix[n] in self.keep:
                        rec.kept[(c, n)] = v
                else:
                    rec.bad_value.append(f"{key}: {v[:40]!r} ({len(v)} B)")
        self.exhausted = True

    def start(self) -> None:
        self._futs = [
            self.node.sched.spawn(self._client(c)) for c in range(self.clients)
        ]

    def stop(self, drain_s: float) -> None:
        """No client starts another operation; wait up to ``drain_s``
        for those in flight.  One that is still unanswered then stays
        in the record with no return time, and counts as failed."""
        from multiraft_tpu.sim.scheduler import TIMEOUT

        self._stop = True
        deadline = time.monotonic() + drain_s
        for fut in self._futs:
            left = max(deadline - time.monotonic(), 0.01)
            if self.node.sched.wait(fut, left) is TIMEOUT:
                break


class OpenLoop:
    """Operation ``i`` of the schedule is recorded at ``[i % ROWS,
    i // ROWS]``: the record stays two-dimensional because a value's
    tag spells (row, column), and row ``traffic.LOADER`` is the
    loader's.  ``is_update`` and ``key_index`` are ``[ROWS, columns]``
    arrays laid out the same way (``traffic.sequences``)."""

    ROWS = 64
    timed_from = "due"

    def __init__(self, node, end, records: Records, due_s: np.ndarray,
                 is_update: np.ndarray, key_index: np.ndarray, keep_keys,
                 sessions: int, service: str = "EngineKV") -> None:
        from multiraft_tpu.distributed.engine_clerks import EngineClerk

        self.node, self.records = node, records
        self.is_update, self.key_index = is_update, key_index
        rows, cols = is_update.shape
        self.n = len(due_s)
        assert rows == self.ROWS and rows * cols >= self.n
        self.rec = Recorder(rows, cols, order="F", due=True)
        self.keep = frozenset(int(k) for k in keep_keys)
        self._due_s = due_s
        flat = self._flat
        self._upd, self._kix = flat(is_update).tolist(), flat(key_index).tolist()
        self._due, self._call = flat(self.rec.due), flat(self.rec.call)
        self._ret, self._got = flat(self.rec.ret), flat(self.rec.got)
        self._sessions = sessions
        self._free = [EngineClerk(node.sched, end, service) for _ in range(sessions)]
        self._fifo: Deque[int] = deque()       # due, and no session free
        self.waited = np.zeros(self.n, bool)   # operation i went through the FIFO
        # Operations due and not yet acknowledged, sampled at each arrival.
        self.inflight = np.full(self.n, -1, np.int64)
        self._acked = 0
        self._stop, self._stopped_at = False, np.nan
        self.exhausted = False
        self._pacer: Any = None

    @staticmethod
    def _flat(a: np.ndarray) -> np.ndarray:
        """Operation ``i`` is element ``i`` (a view of the record's arrays)."""
        return a.reshape(-1, order="F")

    def _pace(self):
        """Sleeps to each due time and hands the operation to a free
        session (the last freed first); never waits for a reply."""
        clock = time.perf_counter
        due_at = (self._due_s + clock()).tolist()
        due, inflight, waited = self._due, self.inflight, self.waited
        free, fifo, spawn = self._free, self._fifo, self.node.sched.spawn
        i, n = 0, self.n
        while i < n and not self._stop:
            wait = due_at[i] - clock()
            if wait > 0.0:
                yield wait
                continue
            due[i] = due_at[i]
            inflight[i] = i + 1 - self._acked
            if free:
                spawn(self._session(free.pop(), i))
            else:
                waited[i] = True
                fifo.append(i)
            i += 1
        self.exhausted = i >= n

    def _session(self, ck, i: int):
        rec, records, rows = self.rec, self.records, self.ROWS
        upd, kix = self._upd, self._kix
        keys, size = records.keys, records.valuebytes
        call, ret, got = self._call, self._ret, self._got
        fifo, clock = self._fifo, time.perf_counter
        while True:
            k = kix[i]
            key = keys[k]
            if upd[i]:
                value = records.value(i % rows, i // rows)
                call[i] = clock()
                yield from ck.put(key, value)
                ret[i] = clock()
            else:
                call[i] = clock()
                v = yield from ck.get(key)
                ret[i] = clock()
                if len(v) == size and v[:TAG].isdigit():
                    got[i] = int(v[:TAG])
                    if k in self.keep:
                        rec.kept[(i % rows, i // rows)] = v
                else:
                    rec.bad_value.append(f"{key}: {v[:40]!r} ({len(v)} B)")
            self._acked += 1
            if self._stop or not fifo:
                break
            i = fifo.popleft()
        self._free.append(ck)

    def start(self) -> None:
        self._pacer = self.node.sched.spawn(self._pace())

    def stop(self, drain_s: float) -> None:
        """Nothing more is sent (an operation still waiting for a
        session never is); wait up to ``drain_s`` for those in flight.
        One that is still unanswered then stays in the record with no
        return time, and counts as failed."""
        self._stop, self._stopped_at = True, time.perf_counter()
        deadline = time.monotonic() + drain_s
        self.node.sched.wait(self._pacer, drain_s)
        while len(self._free) < self._sessions and time.monotonic() < deadline:
            time.sleep(0.01)

    def window_report(self, t0: float, t1: float,
                      exempt: Optional[Tuple[float, float]] = None) -> Dict[str, float]:
        """How well the generator kept to its schedule over ``[t0, t1)``,
        and the backlog it saw, over the operations DUE in the window's
        judged seconds: lateness (``call - due``; one never sent, as late
        as the stop), the share that found no free session, the share of
        those seconds that were off the schedule (``OFF_SCHEDULE_SHARE``),
        and ``inflight``.  Every second of the window is judged but those
        that ``exempt`` (from, to) touches: a traced run's profiler."""
        due, call, ret = self._due[:self.n], self._call[:self.n], self._ret[:self.n]
        win = np.flatnonzero((due >= t0) & (due < t1))   # nan compares false
        second = (due[win] - t0).astype(np.int64)
        judged = np.ones(second.max() + 1, bool)
        if exempt is not None:
            judged[max(int(exempt[0] - t0), 0):max(int(exempt[1] - t0) + 1, 0)] = False
        ops, second = win[judged[second]], second[judged[second]]   # the judged operations
        sent = np.where(np.isnan(call[ops]), self._stopped_at, call[ops])
        late_ms = (sent - due[ops]) * 1000.0
        waited, flight = self.waited[ops], self.inflight[ops]
        in_second = np.maximum(np.bincount(second, minlength=len(judged)), 1)
        off = ((np.bincount(second, late_ms > LATE_MS, len(judged)) > OFF_SCHEDULE_SHARE * in_second)
               | (np.bincount(second, waited, len(judged)) > OFF_SCHEDULE_SHARE * in_second))
        return {
            "due": len(win),
            "answered_share": float((ret[win] < t1).mean()),
            "judged_seconds": int(judged.sum()),
            "late_p50_ms": float(np.percentile(late_ms, 50)),
            "late_p99_ms": float(np.percentile(late_ms, 99)),
            "late_max_ms": float(late_ms.max()),
            "late_share": float((late_ms > LATE_MS).mean()),
            "pool_wait_share": float(waited.mean()),
            "off_schedule_seconds_share": float(off[judged].mean()),
            "inflight_p50": float(np.percentile(flight, 50)),
            "inflight_p95": float(np.percentile(flight, 95)),
            "inflight_end": float(flight[-1]),
        }
