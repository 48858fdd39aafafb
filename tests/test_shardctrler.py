"""shardctrler tests (reference: shardctrler/test_test.go:12-403) plus
property tests for the pure rebalancer."""

import random

import pytest

from multiraft_tpu.harness.ctrler_harness import CtrlerHarness
from multiraft_tpu.services.shardctrler import NSHARDS, Config, rebalance


def check(cfg: Config, groups: list) -> None:
    """Validity: exact membership, no orphan shards, balance ≤ 1
    (reference: shardctrler/test_test.go:12-54)."""
    assert sorted(cfg.groups) == sorted(groups), (
        f"wanted groups {sorted(groups)}, got {sorted(cfg.groups)}"
    )
    for s, g in enumerate(cfg.shards):
        if groups:
            assert g in cfg.groups, f"shard {s} -> missing group {g}"
        else:
            assert g == 0, f"shard {s} assigned in empty config"
    if groups:
        counts = {g: 0 for g in cfg.groups}
        for g in cfg.shards:
            counts[g] += 1
        assert max(counts.values()) - min(counts.values()) <= 1, (
            f"unbalanced: {counts}"
        )


# -- rebalancer property tests -------------------------------------------


def test_rebalance_empty():
    assert rebalance([0] * NSHARDS, {}) == [0] * NSHARDS


def test_rebalance_single_group():
    out = rebalance([0] * NSHARDS, {1: ["a"]})
    assert out == [1] * NSHARDS


def test_rebalance_join_minimal_movement():
    before = rebalance([0] * NSHARDS, {1: ["a"]})
    after = rebalance(before, {1: ["a"], 2: ["b"]})
    moved = sum(1 for b, a in zip(before, after) if b != a)
    assert moved == NSHARDS // 2  # exactly the shards group 2 must take
    assert all(a in (1, 2) for a in after)


def test_rebalance_leave_moves_only_orphans():
    two = rebalance(rebalance([0] * NSHARDS, {1: ["a"]}), {1: ["a"], 2: ["b"]})
    three = rebalance(two, {1: ["a"], 2: ["b"], 3: ["c"]})
    after = rebalance(three, {1: ["a"], 2: ["b"]})
    # Shards that stayed with surviving groups must not move.
    for s in range(NSHARDS):
        if three[s] in (1, 2):
            assert after[s] == three[s], f"shard {s} moved unnecessarily"


@pytest.mark.parametrize("n_shards", [NSHARDS, 110], ids=["reference", "110-shards"])
def test_rebalance_deterministic_and_balanced(n_shards):
    """At the reference's count and at one that is not the module's
    constant: the length is the input's, never ``NSHARDS``."""
    rng = random.Random(7)
    shards = [0] * n_shards
    live = {}
    next_gid = 1
    for step in range(200):
        if live and rng.random() < 0.4:
            dead = rng.choice(sorted(live))
            del live[dead]
        else:
            live[next_gid] = [f"s{next_gid}"]
            next_gid += 1
        a = rebalance(shards, live)
        b = rebalance(list(shards), dict(live))
        assert a == b, "rebalance is not deterministic"
        shards = a
        if live:
            counts = {}
            for g in shards:
                counts[g] = counts.get(g, 0) + 1
            assert set(counts) <= set(live)
            load = [counts.get(g, 0) for g in live]  # a group may hold none
            assert max(load) - min(load) <= 1
        assert len(shards) == n_shards


# -- service tests --------------------------------------------------------


def test_basic():
    """Join/leave sequences + historical queries
    (reference: shardctrler/test_test.go:81-250 TestBasic)."""
    cfg = CtrlerHarness(3, seed=60)
    ck = cfg.make_client()

    c0 = cfg.run(ck.query(-1))
    assert c0.num == 0
    check(c0, [])

    # Join one group.
    cfg.run(ck.join({1: ["x", "y", "z"]}))
    c1 = cfg.run(ck.query(-1))
    check(c1, [1])

    # Join a second.
    cfg.run(ck.join({2: ["a", "b", "c"]}))
    c2 = cfg.run(ck.query(-1))
    check(c2, [1, 2])

    # Re-query history: old configs intact.
    h1 = cfg.run(ck.query(c1.num))
    check(h1, [1])
    h0 = cfg.run(ck.query(0))
    assert h0.num == 0

    # Move pins a shard.
    cfg.run(ck.move(3, 1))
    cm = cfg.run(ck.query(-1))
    assert cm.shards[3] == 1

    # Leave group 1.
    cfg.run(ck.leave([1]))
    c3 = cfg.run(ck.query(-1))
    check(c3, [2])

    # Leave the last group.
    cfg.run(ck.leave([2]))
    c4 = cfg.run(ck.query(-1))
    check(c4, [])
    cfg.cleanup()


def test_multi_concurrent_joins_leaves():
    """Concurrent joins/leaves from many clerks; final config valid and
    balanced (reference: shardctrler/test_test.go:253-402 TestMulti)."""
    cfg = CtrlerHarness(3, seed=61)
    nclerks = 6
    clerks = [cfg.make_client() for _ in range(nclerks)]

    def worker(i, ck):
        gid = 100 + i
        yield from ck.join({gid: [f"{gid}-a", f"{gid}-b"]})
        yield cfg.rng.uniform(0, 0.05)
        yield from ck.query(-1)
        return gid

    futs = [cfg.sched.spawn(worker(i, c)) for i, c in enumerate(clerks)]
    gids = [cfg.sched.run_until(f) for f in futs]

    ck = clerks[0]
    final = cfg.run(ck.query(-1))
    check(final, gids)

    # Concurrent leaves of half the groups.
    leaving = gids[: nclerks // 2]

    def leaver(ck, gid):
        yield from ck.leave([gid])

    futs = [
        cfg.sched.spawn(leaver(clerks[i], g)) for i, g in enumerate(leaving)
    ]
    for f in futs:
        cfg.sched.run_until(f)
    final = cfg.run(ck.query(-1))
    check(final, gids[nclerks // 2 :])
    cfg.cleanup()


def test_minimal_transfer_after_joins():
    """Joins move only the shards the new group must take
    (reference: shardctrler/test_test.go:341-360)."""
    cfg = CtrlerHarness(3, seed=62)
    ck = cfg.make_client()
    cfg.run(ck.join({1: ["a"]}))
    cfg.run(ck.join({2: ["b"]}))
    c1 = cfg.run(ck.query(-1))
    cfg.run(ck.join({3: ["c"]}))
    c2 = cfg.run(ck.query(-1))
    # Shards that didn't go to group 3 must not have moved.
    for s in range(NSHARDS):
        if c2.shards[s] != 3:
            assert c2.shards[s] == c1.shards[s], f"shard {s} moved needlessly"
    cfg.cleanup()


def test_minimal_transfer_after_leaves():
    """(reference: shardctrler/test_test.go:362-378)"""
    cfg = CtrlerHarness(3, seed=63)
    ck = cfg.make_client()
    for g in (1, 2, 3):
        cfg.run(ck.join({g: [f"{g}"]}))
    c1 = cfg.run(ck.query(-1))
    cfg.run(ck.leave([3]))
    c2 = cfg.run(ck.query(-1))
    for s in range(NSHARDS):
        if c1.shards[s] != 3:
            assert c2.shards[s] == c1.shards[s], f"shard {s} moved needlessly"
    cfg.cleanup()


def test_config_identity_across_failover():
    """Configs agree across a leader crash
    (reference: shardctrler/test_test.go:383-402)."""
    cfg = CtrlerHarness(3, seed=64)
    ck = cfg.make_client()
    cfg.run(ck.join({1: ["a"], 2: ["b"]}))
    before = cfg.run(ck.query(-1))

    leader = cfg.cluster.current_leader()
    assert leader >= 0
    cfg.cluster.shutdown_server(leader)
    cfg.sched.run_for(1.0)

    after = cfg.run(ck.query(-1))
    assert after.num == before.num
    assert after.shards == before.shards
    assert after.groups == before.groups
    cfg.cleanup()


def test_dup_detection_across_retries():
    """An unreliable net must not double-apply a join
    (exercises the controller dup table)."""
    cfg = CtrlerHarness(3, unreliable=True, seed=65)
    ck = cfg.make_client()
    cfg.run(ck.join({7: ["x"]}))
    cfg.run(ck.leave([7]))
    cfg.run(ck.join({8: ["y"]}))
    final = cfg.run(ck.query(-1))
    check(final, [8])
    # join/leave/join = exactly 3 config transitions (+1 initial).
    assert final.num == 3, f"dup applies inflated config history: {final.num}"
    cfg.cleanup()
