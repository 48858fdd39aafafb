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
