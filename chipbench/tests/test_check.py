"""The register rules accept what a linearizable store may answer and
refuse a stale read, a read from the future and a made-up value."""

import numpy as np

import check
import traffic

CFG = {"recordcount": 50, "fieldcount": 10, "fieldlength": 100}


def history(reads, writes):
    """writes: (key, call, ret, client, n); reads: (key, call, ret, code)."""
    h = check.History(traffic.Records(CFG, 1), t_loaded=0.0)
    w = np.array(writes, float).reshape(-1, 5)
    h.w_key.append(w[:, 0].astype(np.int64))
    h.w_call.append(w[:, 1])
    h.w_ret.append(w[:, 2])
    h.w_code.append(np.array([traffic.code(int(c), int(n)) for c, n in w[:, 3:]], np.int64))
    r = np.array(reads, float).reshape(-1, 4)
    h.add_reads(r[:, 0], r[:, 1], r[:, 2], r[:, 3])
    return h


def wrong(h):
    lines, counts = check.register_check(h)
    assert bool(lines) == any(counts.values())
    return lines


LOADED = lambda k: traffic.code(traffic.LOADER, k)
W1, W2 = traffic.code(1, 0), traffic.code(2, 0)


def test_allowed_histories():
    writes = [(3, 1.0, 2.0, 1, 0), (3, 1.5, 2.5, 2, 0)]   # overlapping updates
    reads = [
        (3, 0.1, 0.2, LOADED(3)),    # before any update
        (3, 1.2, 1.3, LOADED(3)),    # during the first: old value still fine
        (3, 1.2, 1.3, W1),           # during the first: new value fine too
        (3, 3.0, 3.1, W1),           # after both overlapped: either may be last
        (3, 3.0, 3.1, W2),
        (7, 5.0, 5.1, LOADED(7)),    # an untouched key
    ]
    assert wrong(history(reads, writes)) == []


def test_stale_read_is_refused():
    writes = [(3, 1.0, 2.0, 1, 0), (3, 2.5, 3.0, 2, 0)]   # W2 strictly after W1
    assert wrong(history([(3, 3.5, 3.6, W1)], writes))          # lost update
    assert wrong(history([(3, 2.1, 2.2, LOADED(3))], writes))   # stale load
    assert wrong(history([(3, 3.5, 3.6, W2)], writes)) == []


def test_unacknowledged_update_obliges_nobody():
    writes = [(3, 1.0, np.inf, 1, 0)]
    assert wrong(history([(3, 5.0, 5.1, LOADED(3))], writes)) == []
    assert wrong(history([(3, 5.0, 5.1, W1)], writes)) == []


def test_future_and_made_up_values_are_refused():
    writes = [(3, 4.0, 5.0, 1, 0)]
    assert wrong(history([(3, 1.0, 1.1, W1)], writes))        # not written yet
    assert wrong(history([(3, 1.0, 1.1, 424242)], writes))    # nobody's tag
    assert wrong(history([(4, 6.0, 6.1, W1)], writes))        # another key's value


def test_durability_counters():
    before, after = {"wal.appends": 5, "wal.fsyncs": 2}, {"wal.appends": 105, "wal.fsyncs": 30}
    assert check.durability_counters(before, after, 100) == (
        [], {"acked_updates_not_in_wal": 0, "acks_without_fsync": 0})
    lines, counts = check.durability_counters(before, after, 101)
    assert lines and counts["acked_updates_not_in_wal"] == 1
    lines, counts = check.durability_counters(before, {"wal.appends": 105, "wal.fsyncs": 2}, 50)
    assert lines and counts["acks_without_fsync"] == 1


def test_acknowledged_updates_from_before_a_kill_are_read_back():
    """Client 0 updates key 3 twice (acked at 2 and 6), client 1 key 4 (acked
    at 9), key 5 (acked at 12, after the kill at 10) and key 3 again (called
    at 5.5, never acknowledged)."""
    from types import SimpleNamespace

    nan = np.nan
    loop = SimpleNamespace(
        is_update=np.array([[True, True, False], [True, True, True]]),
        key_index=np.array([[3, 3, 4], [4, 5, 3]]),
        rec=SimpleNamespace(call=np.array([[1.0, 5.0, 7.0], [8.0, 9.5, 5.5]]),
                            ret=np.array([[2.0, 6.0, 7.5], [9.0, 12.0, nan]])),
    )
    keys, last_call = check.before_the_kill(loop, 10.0, at_least=1, recent_s=2.0)
    assert keys.tolist() == [4] and last_call.tolist() == [8.0]
    keys, last_call = check.before_the_kill(loop, 10.0, at_least=5, recent_s=2.0)
    assert keys.tolist() == [3, 4] and last_call.tolist() == [5.0, 8.0]

    h = check.History(traffic.Records(CFG, 1), t_loaded=0.0)

    def lost(tag3, tag4):
        lines, counts = check.lost_at_the_kill(h, loop, keys, last_call, np.array([tag3, tag4]))
        assert bool(lines) == bool(counts["acked_before_kill_lost"])
        return counts["acked_before_kill_lost"]

    c = traffic.code
    assert lost(c(0, 1), c(1, 0)) == 0       # each key's last acknowledged update
    assert lost(c(1, 2), c(1, 0)) == 0       # an update called before it, never acknowledged
    assert lost(c(0, 0), c(1, 0)) == 1       # acknowledged before the last was called
    assert lost(c(0, 1), LOADED(4)) == 1     # the load: the update is gone
    assert lost(c(1, 0), -1) == 2            # another key's write; no value at all


def test_a_moved_key_must_read_its_last_acknowledged_value():
    writes = [(3, 1.0, 2.0, 1, 0), (3, 2.5, 3.0, 2, 0), (4, 1.0, np.inf, 1, 1)]
    h = history([], writes)
    keys = np.array([3, 3, 3, 4, 4, 5, 5])
    tags = np.array([W2, W1, LOADED(3), traffic.code(1, 1), LOADED(4), LOADED(5), W1])
    lines, counts = check.lost_on_the_move(h, keys, tags)
    # W1 and the load were overwritten by W2; an unacknowledged update may
    # or may not have landed; key 5 was never updated, and W1 is not its
    assert counts == {"moved_keys_lost": 3} and len(lines) == 3


def leg(op, gids, owners0, owners1, groups1, moved=None, grew=None, num=1):
    moved = np.flatnonzero(np.array(owners0) != np.array(owners1)) if moved is None else moved
    n = len(moved)
    return {"op": op, "gids": gids, "moved": moved,
            "config0": {"num": num, "owners": np.array(owners0), "groups": []},
            "config1": {"num": num + 1, "owners": np.array(owners1), "groups": groups1},
            "grew": grew or {f"shard.{k}": n for k in ("inserts", "deletes", "confirms")}}


def test_a_leave_and_a_join_back_against_the_reference():
    # 6 shards over groups 1-3 (G = 4), then group 3 leaves and joins back:
    # orphans to the least loaded, lowest gid first; then the most loaded
    # gives its lowest shard to the least loaded
    boot, left = [1, 2, 3, 1, 2, 3], [1, 2, 1, 1, 2, 2]
    sound = [leg("leave", [3], boot, left, [1, 2]),
             leg("join", [3], left, [3, 3, 1, 1, 2, 2], [1, 2, 3], num=2)]
    lines, counts = check.reconfiguration(sound, 4, 6)
    assert not lines and not any(counts.values()), counts
    # the join never happened: no config, group 3 holds nothing
    skipped = [sound[0], leg("join", [3], left, left, [1, 2], num=2)]
    skipped[1]["config1"]["num"] = 2
    lines, counts = check.reconfiguration(skipped, 4, 6)
    assert counts["configs_not_one_a_call"] == 1 and counts["groups_off_even_share"] == 3
    assert counts["owners_not_the_references"] == 2
    # a leaver keeps a shard, and the migration inserted one shard twice
    kept = [leg("leave", [3], boot, [1, 2, 1, 1, 2, 3], [1, 2],
                grew={"shard.inserts": 2, "shard.deletes": 1, "shard.confirms": 1})]
    lines, counts = check.reconfiguration(kept, 4, 6)
    assert counts["leavers_still_owning"] == 1 and counts["shards_moved_not_the_leavers"] == 1
    assert counts["inserts_not_shards_moved"] == 1 and len(lines) >= 3
