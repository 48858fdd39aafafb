"""The plain reference of a sharded deployment's ownership: which replica
group owns each shard after each join and leave, and which shard a key
falls in.

The rule is the reference's shard controller (``shardctrler/common.go``),
written out as its scans: shards of a group that is gone go, lowest
shard first, to the least-loaded group; then, while two groups differ
by more than one shard, the most-loaded group gives its lowest-numbered
shard to the least-loaded one; the lowest gid wins every tie.  A scan
is numpy's ``argmin`` / ``argmax`` over the groups in gid order (each
returns the first of equals), so the loops stay the reference's and
run in about a second at 33,330 shards over 9,999 groups.  Nothing here
comes from the program.
"""

from __future__ import annotations

import zlib
from typing import Iterable, List

import numpy as np


def shard_of(keys: List[str], shards: int) -> np.ndarray:
    """The shard of each key under the ``crc32`` partitioner: crc32 of
    the whole key, modulo the shard count."""
    return np.fromiter((zlib.crc32(k.encode()) % shards for k in keys), np.int64, len(keys))


def rebalance(owner: np.ndarray, groups: Iterable[int]) -> np.ndarray:
    """The owners after the controller's assignment over ``groups``
    (gid 0 = nobody), from the owners before it."""
    gids = np.array(sorted(groups), np.int64)
    owner = np.where(np.isin(owner, gids), owner, 0)
    if not len(gids):
        return owner
    load = np.bincount(np.searchsorted(gids, owner[owner > 0]), minlength=len(gids))
    for s in np.flatnonzero(owner == 0).tolist():
        i = int(np.argmin(load))
        owner[s] = gids[i]
        load[i] += 1
    while True:
        lo, hi = int(np.argmin(load)), int(np.argmax(load))
        if load[hi] - load[lo] <= 1:
            return owner
        s = int(np.flatnonzero(owner == gids[hi])[0])
        owner[s] = gids[lo]
        load[hi] -= 1
        load[lo] += 1
