"""A plain reference for the sharded KV service.

One dict per shard, an owner table, and ``join`` / ``leave`` / ``move``
applied at once: no logs, no migration, no configs in flight.  It shares
no code with :class:`~multiraft_tpu.engine.shardkv.BatchedShardKV` or
with ``services.shardctrler.rebalance``; the assignment rule is written
out as the loops the reference's ``shardctrler/common.go`` describes —
orphaned shards go to the least-loaded group, then one shard at a time
moves from the most- to the least-loaded group until they differ by at
most one, lowest gid and lowest shard first wherever two are equal — so
the same operations on the same data give the same owners and the same
answers.  ``tests/test_shard_space.py`` and ``chip_smoke.py``'s
``sharded`` leg hold the served system to it.
"""

from __future__ import annotations

from typing import Callable, Dict, Iterable, List

__all__ = ["ShardRef"]


class ShardRef:
    def __init__(self, shards: int, shard_of: Callable[[str], int]) -> None:
        self.shard_of = shard_of
        self.owner: List[int] = [0] * shards  # shard -> gid, 0 = nobody
        self.groups: set = set()
        self.data: List[Dict[str, str]] = [{} for _ in range(shards)]

    # -- the controller -----------------------------------------------------

    def join(self, gids: Iterable[int]) -> None:
        self.groups.update(gids)
        self._balance()

    def leave(self, gids: Iterable[int]) -> None:
        self.groups.difference_update(gids)
        self._balance()

    def move(self, shard: int, gid: int) -> None:
        self.owner[shard] = gid

    def _balance(self) -> None:
        if not self.groups:
            self.owner = [0] * len(self.owner)
            return
        load = {g: 0 for g in self.groups}
        for s, g in enumerate(self.owner):
            if g in load:
                load[g] += 1
            else:
                self.owner[s] = 0
        for s, g in enumerate(self.owner):
            if g == 0:
                to = min(sorted(load), key=lambda x: load[x])
                self.owner[s] = to
                load[to] += 1
        while True:
            lightest = min(sorted(load), key=lambda x: load[x])
            heaviest = max(sorted(load), key=lambda x: load[x])
            if load[heaviest] - load[lightest] <= 1:
                return
            s = self.owner.index(heaviest)
            self.owner[s] = lightest
            load[heaviest] -= 1
            load[lightest] += 1

    # -- the store ----------------------------------------------------------

    def owner_of(self, key: str) -> int:
        return self.owner[self.shard_of(key)]

    def put(self, key: str, value: str) -> None:
        self.data[self.shard_of(key)][key] = value

    def append(self, key: str, value: str) -> None:
        d = self.data[self.shard_of(key)]
        d[key] = d.get(key, "") + value

    def get(self, key: str) -> str:
        return self.data[self.shard_of(key)].get(key, "")

    def apply(self, op: str, key: str, value: str = "") -> str:
        """One client operation; the reply a clerk should see."""
        if op == "Get":
            return self.get(key)
        (self.put if op == "Put" else self.append)(key, value)
        return ""

    def items(self) -> Dict[str, str]:
        return {k: v for d in self.data for k, v in d.items()}
