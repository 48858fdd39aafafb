"""The closed-loop driver: ``clients`` ``EngineClerk`` coroutines on one
``RpcNode``, each issuing its next operation when the last one is
acknowledged — YCSB's client with ``-threads <clients>``.

The clerks, the node and the wire are the program's own; what is here
is the loop around them and the record of every operation: when it was
called, when its acknowledged reply returned, and (for a read) the tag
of the value that came back.  Latency is per operation, from the call
to the acknowledged reply, as YCSB reports it.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from traffic import TAG, Records


class Recorder:
    """Per-client arrays, written by the coroutines on the node's one
    loop thread and read by the main thread after :meth:`ClosedLoop.stop`."""

    def __init__(self, clients: int, ops_per_client: int) -> None:
        shape = (clients, ops_per_client)
        self.call = np.full(shape, np.nan)
        self.ret = np.full(shape, np.nan)     # nan: never acknowledged
        self.got = np.full(shape, -1, np.int64)  # a read's returned tag
        self.bad_value: List[str] = []        # replies that are no value of ours
        self.kept: Dict[tuple, str] = {}      # whole values read from kept keys


class ClosedLoop:
    def __init__(self, node, end, records: Records, is_update: np.ndarray,
                 key_index: np.ndarray, keep_keys) -> None:
        from multiraft_tpu.distributed.engine_clerks import EngineClerk

        self.node, self.records = node, records
        self.is_update, self.key_index = is_update, key_index
        self.clients, self.cap = is_update.shape
        self.rec = Recorder(self.clients, self.cap)
        self.keep = frozenset(int(k) for k in keep_keys)
        self.clerks = [EngineClerk(node.sched, end) for _ in range(self.clients)]
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
