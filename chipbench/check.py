"""The comparison that decides ``correct``, made outside the window.

The plain reference of this store is a register per key.  Every value
ever written carries a unique tag, so a read names the write it saw,
and the reference's rules can be checked directly on the whole record —
every key, every operation — without a search:

* a read returns a value that some operation really wrote to that key;
* that write was called before the read returned (nothing from the
  future);
* no other acknowledged write to the key lies strictly between them —
  called after the seen write was acknowledged, and acknowledged before
  the read was called (no stale read, no lost acknowledged update).

These are the conditions linearizability of a register puts on a read
and the write it saw.  The program's own porcupine checker (a full
search for a linearization) then runs over every operation on a seeded
sample of keys with whole values; it is the program's code, so it
stands beside this check and not in place of it.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

from traffic import LOADER, TAG, Records, code

NO_VALUE = "<a reply that is no value of ours>"


class History:
    """Flat arrays of every recorded operation: the load, the closed
    loop (warm-up, window, drain) and the reads made after it."""

    def __init__(self, records: Records, t_loaded: float) -> None:
        n = records.n
        self.records = records
        # The load: record i written by the loader, acknowledged by t_loaded.
        self.w_key = [np.arange(n)]
        self.w_call = [np.full(n, -np.inf)]
        self.w_ret = [np.full(n, t_loaded)]
        self.w_code = [code(LOADER, 0) + np.arange(n)]
        self.r_key: List[np.ndarray] = []
        self.r_call: List[np.ndarray] = []
        self.r_ret: List[np.ndarray] = []
        self.r_code: List[np.ndarray] = []

    def add_loop(self, loop) -> None:
        rec = loop.rec
        called = ~np.isnan(rec.call)
        acked = ~np.isnan(rec.ret)
        cl, nn = np.nonzero(called & loop.is_update)
        self.w_key.append(loop.key_index[cl, nn])
        self.w_call.append(rec.call[cl, nn])
        # An update that was never acknowledged may still have been
        # applied: it is a write that no read is obliged to see.
        self.w_ret.append(np.where(acked[cl, nn], rec.ret[cl, nn], np.inf))
        self.w_code.append(cl * 10 ** (TAG - 2) + nn)
        cl, nn = np.nonzero(acked & ~loop.is_update)
        self.add_reads(loop.key_index[cl, nn], rec.call[cl, nn],
                       rec.ret[cl, nn], rec.got[cl, nn])

    def add_reads(self, key, call, ret, got) -> None:
        self.r_key.append(np.asarray(key, np.int64))
        self.r_call.append(np.asarray(call, np.float64))
        self.r_ret.append(np.asarray(ret, np.float64))
        self.r_code.append(np.asarray(got, np.int64))


def register_check(h: History) -> Tuple[List[str], Dict[str, int]]:
    """Every read against the rules above.  Returns what is wrong, at
    most a few lines (empty: every read was allowed), and how many reads
    broke each rule: the numbers ``correct`` compares, each with limit 0."""
    wk, wc, wr, wcode = map(np.concatenate, (h.w_key, h.w_call, h.w_ret, h.w_code))
    rk, rc, rr, rcode = map(np.concatenate, (h.r_key, h.r_call, h.r_ret, h.r_code))
    wrong: List[str] = []
    # Which write did each read see?  Codes are unique over all writes.
    order = np.argsort(wcode)
    pos = np.searchsorted(wcode[order], rcode)
    pos = np.minimum(pos, len(order) - 1)
    seen = order[pos]
    known = (wcode[seen] == rcode) & (wk[seen] == rk)
    for i in np.nonzero(~known)[0][:3].tolist():
        wrong.append(f"{h.records.keys[rk[i]]}: read tag {rcode[i]} that nobody wrote to it")
    future = known & (wc[seen] > rr)
    for i in np.nonzero(future)[0][:3].tolist():
        wrong.append(f"{h.records.keys[rk[i]]}: read tag {rcode[i]} before it was written")
    # Stale reads: per key, the earliest acknowledgement among writes
    # called after time t, by a suffix minimum over writes sorted by call.
    w_by_key = np.lexsort((wc, wk))
    wk_s, wc_s, wr_s = wk[w_by_key], wc[w_by_key], wr[w_by_key]
    starts = np.searchsorted(wk_s, np.arange(h.records.n + 1))
    multi = (starts[1:] - starts[:-1]) > 1      # keys with more than the load
    check = np.nonzero(known & multi[rk])[0]
    r_by_key = check[np.argsort(rk[check], kind="stable")]
    bounds = np.searchsorted(rk[r_by_key], np.arange(h.records.n + 1))
    stale = 0
    for k in np.nonzero(bounds[1:] > bounds[:-1])[0].tolist():
        lo, hi = starts[k], starts[k + 1]
        calls = wc_s[lo:hi]
        suffix_min_ret = np.minimum.accumulate(wr_s[lo:hi][::-1])[::-1]
        suffix_min_ret = np.append(suffix_min_ret, np.inf)
        reads = r_by_key[bounds[k]:bounds[k + 1]]
        after = np.searchsorted(calls, wr[seen[reads]], side="right")
        bad = suffix_min_ret[after] < rc[reads]
        if bad.any():
            stale += int(bad.sum())
            if len(wrong) < 6:
                i = reads[np.nonzero(bad)[0][0]]
                wrong.append(
                    f"{h.records.keys[k]}: stale read of tag {rcode[i]}: a later "
                    f"update was acknowledged before the read was called"
                )
    if stale:
        wrong.append(f"{stale} stale reads in all")
    return wrong, {"reads_of_values_nobody_wrote": int((~known).sum()),
                   "reads_from_the_future": int(future.sum()), "stale_reads": stale}


def porcupine_sample(h: History, loop, keys, extra_reads, timeout_s: float
                     ) -> Tuple[str, int]:
    """The program's porcupine over every operation on ``keys`` (whole
    values).  ``extra_reads``: ``(key, call, ret, value)`` made after
    the loop.  Returns the verdict's name and the number of operations."""
    from multiraft_tpu.porcupine.checker import check_operations
    from multiraft_tpu.porcupine.kv import OP_GET, OP_PUT, KvInput, KvOutput, kv_model
    from multiraft_tpu.porcupine.model import Operation

    records, rec = h.records, loop.rec
    keyset = np.zeros(records.n, bool)
    keyset[list(keys)] = True
    t_loaded = float(h.w_ret[0][0])
    ops = [
        Operation(LOADER, KvInput(OP_PUT, records.keys[k], records.value(LOADER, k)),
                  t_loaded - 1.0, KvOutput(""), t_loaded)
        for k in keys
    ]
    acked = ~np.isnan(rec.ret)
    horizon = float(np.nanmax(rec.ret)) + 3600.0
    cl, nn = np.nonzero(~np.isnan(rec.call) & keyset[loop.key_index])
    for c, n in zip(cl.tolist(), nn.tolist()):
        key = records.keys[loop.key_index[c, n]]
        if loop.is_update[c, n]:
            # unacknowledged: may take effect at any later time
            ret = rec.ret[c, n] if acked[c, n] else horizon
            ops.append(Operation(c, KvInput(OP_PUT, key, records.value(c, n)),
                                 rec.call[c, n], KvOutput(""), ret))
        elif acked[c, n]:
            # A read kept no value where its reply was no value of ours
            # (``rec.bad_value``): it stands as one nobody wrote.
            ops.append(Operation(c, KvInput(OP_GET, key), rec.call[c, n],
                                 KvOutput(rec.kept.get((c, n), NO_VALUE)), rec.ret[c, n]))
    for k, call, ret, value in extra_reads:
        if keyset[k]:
            ops.append(Operation(LOADER, KvInput(OP_GET, records.keys[k]), call,
                                 KvOutput(value), ret))
    verdict = check_operations(kv_model, ops, timeout=timeout_s)
    return verdict.value, len(ops)


def before_the_kill(loop, t_kill: float, at_least: int, recent_s: float
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """The keys whose last acknowledged update was acknowledged before
    ``t_kill``: every one acknowledged in the ``recent_s`` before it (past
    the last checkpoint, so only the WAL holds it) and at least the
    ``at_least`` latest.  Returns the keys, sorted, and the call time of
    each one's last acknowledged update."""
    rec = loop.rec
    cl, nn = np.nonzero(loop.is_update & ~np.isnan(rec.ret))
    key, call, ret = loop.key_index[cl, nn], rec.call[cl, nn], rec.ret[cl, nn]
    order = np.lexsort((ret, key))
    key, call, ret = key[order], call[order], ret[order]
    last = np.append(key[1:] != key[:-1], True)      # each key's last acknowledged update
    before = last & (ret < t_kill)
    key, call, ret = key[before], call[before], ret[before]
    n = max(int((ret >= t_kill - recent_s).sum()), min(at_least, len(key)))
    pick = np.sort(np.argsort(ret)[::-1][:n])
    return key[pick], call[pick]


def lost_at_the_kill(h: History, loop, keys: np.ndarray, last_call: np.ndarray,
                     tags: np.ndarray) -> Tuple[List[str], Dict[str, int]]:
    """Read back after a ``kill -9`` and restart: a key whose last
    acknowledged update came before the kill (``before_the_kill``) must
    read back that update, or a write that was not acknowledged before it
    was called.  A key that reads back an older value, or one nobody
    wrote to it, lost an acknowledged update.  Returns what is wrong and
    the number compared (limit 0)."""
    rec, cap = loop.rec, loop.key_index.shape[1]
    writer, n = tags // 10 ** (TAG - 2), tags % 10 ** (TAG - 2)
    ret = np.full(len(keys), np.nan)
    loaded = (tags >= 0) & (writer == LOADER) & (n == keys)
    ret[loaded] = h.w_ret[0][0]
    ours = (tags >= 0) & (writer < loop.key_index.shape[0]) & (n < cap)
    c, m = writer[ours], n[ours]
    wrote = loop.is_update[c, m] & ~np.isnan(rec.call[c, m]) & (loop.key_index[c, m] == keys[ours])
    ret[np.flatnonzero(ours)[wrote]] = np.where(np.isnan(rec.ret[c, m]), np.inf, rec.ret[c, m])[wrote]
    lost = np.isnan(ret) | (ret < last_call)       # nobody's write, or one acknowledged before
    wrong = [f"{h.records.keys[k]}: read back tag {t} after the restart, not its update acknowledged "
             f"before the kill" for k, t in zip(keys[lost][:3].tolist(), tags[lost][:3].tolist())]
    return wrong, {"acked_before_kill_lost": int(lost.sum())}


def durability_counters(before: Dict[str, Any], after: Dict[str, Any],
                        acked_updates: int) -> Tuple[List[str], Dict[str, int]]:
    """An acknowledged update is in the WAL and flushed before its ack:
    the WAL took at least as many appends as updates were acknowledged,
    and it was fsynced.  An ack without its flush is a failed run.
    Returns what is wrong and the two numbers compared (limit 0)."""
    wrong = []
    appends = after.get("wal.appends", 0) - before.get("wal.appends", 0)
    fsyncs = after.get("wal.fsyncs", 0) - before.get("wal.fsyncs", 0)
    if appends < acked_updates:
        wrong.append(f"wal.appends grew by {appends}, under the {acked_updates} "
                     f"updates acknowledged between the scrapes")
    if acked_updates and fsyncs <= 0:
        wrong.append("updates were acknowledged and wal.fsyncs did not grow")
    return wrong, {"acked_updates_not_in_wal": max(acked_updates - int(appends), 0),
                   "acks_without_fsync": int(bool(acked_updates and fsyncs <= 0))}


def reconfiguration(legs: List[Dict[str, Any]], groups: int, shards: int
                    ) -> Tuple[List[str], Dict[str, int]]:
    """Ownership across the admin calls of a sharded deployment
    (``admin.ShardSettle``'s legs), against the plain reference
    (``shardref``): the config before the first call is the bootstrap
    join of every replica group, and each call's config is the
    reference's after the same join or leave.  Each leg is one config
    (``configs_not_one_a_call``: a call applied twice or not at all);
    after a leave no leaving group is in the config or owns a shard, and
    the shards that moved are exactly those the leaving groups held;
    after every call each group the calls leave in holds the shards
    divided evenly, rounded down or up (3 or 4 of 33,330 over 9,999), and
    the config holds no other group; the migration inserted and
    deleted each moved shard once.  Returns what is wrong and the
    numbers compared (limit 0)."""
    from shardref import rebalance

    wrong: List[str] = []
    out = dict.fromkeys(("configs_not_one_a_call", "owners_not_the_references",
                         "leavers_still_owning", "shards_moved_not_the_leavers",
                         "groups_off_even_share", "inserts_not_shards_moved",
                         "deletes_not_shards_moved", "confirms_not_shards_moved"), 0)
    members = set(range(1, groups))
    ref = rebalance(np.zeros(shards, np.int64), members)
    for i, leg in enumerate(legs):
        c0, c1, moved = leg["config0"], leg["config1"], leg["moved"]
        gone = set(leg["gids"])
        if i == 0:
            out["owners_not_the_references"] += int((c0["owners"] != ref).sum())
        members = members - gone if leg["op"] == "leave" else members | gone
        ref = rebalance(ref, members)
        counts = {
            "configs_not_one_a_call": abs(c1["num"] - c0["num"] - 1),
            "owners_not_the_references": int((c1["owners"] != ref).sum()),
            "inserts_not_shards_moved": abs(leg["grew"]["shard.inserts"] - len(moved)),
            "deletes_not_shards_moved": abs(leg["grew"]["shard.deletes"] - len(moved)),
            "confirms_not_shards_moved": abs(leg["grew"]["shard.confirms"] - len(moved)),
        }
        if leg["op"] == "leave":
            held = np.flatnonzero(np.isin(c0["owners"], leg["gids"]))
            counts["leavers_still_owning"] = (int(np.isin(c1["owners"], leg["gids"]).sum())
                                              + len(gone & set(c1["groups"])))
            counts["shards_moved_not_the_leavers"] = len(np.setxor1d(held, moved))
        load = np.bincount(c1["owners"], minlength=groups)
        even = {shards // len(members), -(-shards // len(members))}
        counts["groups_off_even_share"] = (sum(int(load[g]) not in even for g in members)
                                           + len(set(c1["groups"]) - members))
        for k, v in counts.items():
            out[k] += v
            if v:
                wrong.append(f"{leg['op']} of {len(gone)} groups: {k} {v}")
    return wrong, out


def lost_on_the_move(h: History, keys: np.ndarray, tags: np.ndarray
                     ) -> Tuple[List[str], Dict[str, int]]:
    """Read back after the window, every key of a shard that moved: each
    must read the value of a write that no acknowledged write followed
    (one called after it was acknowledged), its last acknowledged value
    or a later unacknowledged one.  ``h`` holds the loop's writes.
    Returns what is wrong and the number compared (limit 0)."""
    wk, wc, wr, wcode = map(np.concatenate, (h.w_key, h.w_call, h.w_ret, h.w_code))
    last_acked_call = np.full(h.records.n, -np.inf)
    acked = np.isfinite(wr)
    np.maximum.at(last_acked_call, wk[acked], wc[acked])
    order = np.argsort(wcode)
    pos = np.minimum(np.searchsorted(wcode[order], tags), len(order) - 1)
    seen = order[pos]
    known = (wcode[seen] == tags) & (wk[seen] == keys)
    lost = ~known | (wr[seen] < last_acked_call[keys])
    wrong = [f"{h.records.keys[k]}: read back tag {t} after the moves, not its last "
             f"acknowledged value" for k, t in zip(keys[lost][:3].tolist(), tags[lost][:3].tolist())]
    return wrong, {"moved_keys_lost": int(lost.sum())}
