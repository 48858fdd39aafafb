"""Sharded multi-group KV on the batched engine.

Conformance targets: the reference's shardkv test spec (SURVEY §4.4) —
static sharding, join/leave migration with data preservation, shard
deletion at the old owner (Challenge 1), serving unaffected and
partially-migrated shards during migration (Challenge 2), client dedup
across shard moves — driven through the device tick loop instead of the
sim scheduler.
"""

import numpy as np
import pytest

from multiraft_tpu.engine.core import EngineConfig
from multiraft_tpu.engine.host import EngineDriver
from multiraft_tpu.engine.shardkv import (
    ERR_WRONG_GROUP,
    OK,
    BatchedShardClerk,
    BatchedShardKV,
    route_keys,
)
from multiraft_tpu.services.shardctrler import NSHARDS
from multiraft_tpu.services.shardkv import BEPULLING, SERVING, key2shard


def make(G=4, seed=0, **kw):
    cfg = EngineConfig(G=G, P=3, L=64, E=8, INGEST=8, **kw)
    driver = EngineDriver(cfg, seed=seed)
    assert driver.run_until_quiet_leaders(max_ticks=1000)
    skv = BatchedShardKV(driver)
    return skv


def settle(skv, max_ticks=4000):
    """Pump until every participating group is at the latest config with
    all shards quiescent (no migration in flight)."""
    target = skv.query_latest().num
    for _ in range(0, max_ticks, 5):
        skv.pump(5)
        reps = [skv.reps[g] for g in skv.query_latest().groups]
        if reps and all(
            r.cur.num == target
            and all(sh.state == SERVING for sh in r.shards.values())
            for r in reps
        ):
            return
    raise TimeoutError(f"cluster did not settle at config {target}")


def keys_for_all_shards():
    out = {}
    for c in range(32, 127):
        k = chr(c)
        s = key2shard(k)
        if s not in out:
            out[s] = k
        if len(out) == NSHARDS:
            break
    return out  # shard -> key


def test_single_group_serves_all_shards():
    skv = make(G=2)
    skv.admin_sync("join", [1])
    clerk = BatchedShardClerk(skv, client_id=1)
    for shard, k in keys_for_all_shards().items():
        clerk.put(k, f"v{shard}")
        assert clerk.get(k) == f"v{shard}"


def test_join_migrates_and_preserves_data():
    skv = make(G=3, seed=1)
    skv.admin_sync("join", [1])
    clerk = BatchedShardClerk(skv, client_id=1)
    kmap = keys_for_all_shards()
    for shard, k in kmap.items():
        clerk.put(k, f"v{shard}")
    skv.admin_sync("join", [2])
    settle(skv)
    cfg = skv.query_latest()
    owned = {g: sum(1 for s in cfg.shards if s == g) for g in (1, 2)}
    assert abs(owned[1] - owned[2]) <= 1
    for shard, k in kmap.items():
        assert clerk.get(k) == f"v{shard}"
    # Writes after migration land at the new owners.
    for shard, k in kmap.items():
        clerk.append(k, "+")
        assert clerk.get(k) == f"v{shard}+"


def test_leave_returns_shards_with_data():
    skv = make(G=3, seed=2)
    skv.admin_sync("join", [1])
    skv.admin_sync("join", [2])
    settle(skv)
    clerk = BatchedShardClerk(skv, client_id=1)
    kmap = keys_for_all_shards()
    for shard, k in kmap.items():
        clerk.put(k, f"w{shard}")
    skv.admin_sync("leave", [2])
    settle(skv)
    cfg = skv.query_latest()
    assert all(g == 1 for g in cfg.shards)
    for shard, k in kmap.items():
        assert clerk.get(k) == f"w{shard}"


@pytest.mark.parametrize("form", ["reads-as-empty", "sparse"])
def test_challenge1_old_owner_deletes_migrated_shards(form):
    """``reads-as-empty``: the check as it always was, through ``rep.shards[s]``
    (a shard without a slot reads empty and SERVING).  ``sparse``: the slot
    itself is gone, and only owned shards have one."""
    skv = make(G=3, seed=3)
    skv.admin_sync("join", [1])
    clerk = BatchedShardClerk(skv, client_id=1)
    kmap = keys_for_all_shards()
    for shard, k in kmap.items():
        clerk.put(k, "x" * 64)
    skv.admin_sync("join", [2])
    settle(skv)
    cfg = skv.query_latest()
    rep1 = skv.reps[1]
    for s in range(NSHARDS):
        if cfg.shards[s] == 2:
            # Shard moved 1 -> 2: group 1 must hold no data for it.
            if form == "sparse":
                assert s not in rep1.shards, f"shard {s}: slot kept at old owner"
                continue
            assert rep1.shards[s].data == {}, f"shard {s} leaked at old owner"
            assert rep1.shards[s].state == SERVING
        elif cfg.shards[s] == 1 and s in kmap:
            assert kmap[s] in rep1.shards[s].data
    if form == "sparse":
        for gid in (1, 2):
            assert sorted(skv.reps[gid].shards) == [
                s for s in range(NSHARDS) if cfg.shards[s] == gid
            ]


def test_challenge2_unaffected_shards_serve_during_stalled_migration():
    skv = make(G=3, seed=4)
    skv.admin_sync("join", [1])
    clerk = BatchedShardClerk(skv, client_id=1)
    kmap = keys_for_all_shards()
    for shard, k in kmap.items():
        clerk.put(k, f"v{shard}")
    # Kill group 2's majority, then join it: migration cannot complete,
    # but group 1's *kept* shards must keep serving.
    for p in (0, 1):
        skv.driver.set_alive(2, p, False)
    skv.admin_sync("join", [2])
    for _ in range(60):
        skv.pump(5)
    cfg = skv.query_latest()
    rep1 = skv.reps[1]
    assert rep1.cur.num == cfg.num  # group 1 advanced
    kept = [s for s in range(NSHARDS) if cfg.shards[s] == 1]
    moved = [s for s in range(NSHARDS) if cfg.shards[s] == 2]
    assert kept and moved
    for s in kept:
        if s in kmap:
            assert clerk.get(kmap[s]) == f"v{s}"
    # Moved shards are parked BEPULLING at the old owner (not serving,
    # not deleted) while the new owner is down.
    assert all(rep1.shards[s].state == BEPULLING for s in moved)
    t = skv.submit(1, "Get", kmap[moved[0]], client_id=9, command_id=1)
    for _ in range(40):
        skv.pump(5)
        if t.done:
            break
    assert t.done and t.err == ERR_WRONG_GROUP
    # Revive group 2: migration completes and data arrives intact.
    for p in (0, 1):
        skv.driver.restart_replica(2, p)
    settle(skv)
    for s in moved:
        if s in kmap:
            assert clerk.get(kmap[s]) == f"v{s}"


def test_dedup_survives_shard_migration():
    skv = make(G=3, seed=5)
    skv.admin_sync("join", [1])
    clerk = BatchedShardClerk(skv, client_id=1)
    kmap = keys_for_all_shards()
    k = kmap[0]
    clerk.put(k, "base")
    # A duplicate append (same client/command id, e.g. a retried RPC)
    # must apply exactly once even when delivered twice pre-migration...
    t1 = skv.submit(1, "Append", k, "+dup", client_id=7, command_id=1)
    t2 = skv.submit(1, "Append", k, "+dup", client_id=7, command_id=1)
    for _ in range(60):
        skv.pump(5)
        if t1.done and t2.done:
            break
    assert t1.done and t2.done
    # ... and once more when replayed at the NEW owner after migration
    # (the dup table migrates with the shard data).
    skv.admin_sync("join", [2])
    settle(skv)
    owner = skv.query_latest().shards[key2shard(k)]
    t3 = skv.submit(owner, "Append", k, "+dup", client_id=7, command_id=1)
    for _ in range(60):
        skv.pump(5)
        if t3.done:
            break
    assert t3.done and t3.err == OK
    assert clerk.get(k) == "base+dup"


def test_move_pins_shard():
    skv = make(G=3, seed=6)
    skv.admin_sync("join", [1])
    skv.admin_sync("join", [2])
    settle(skv)
    cfg = skv.query_latest()
    shard = next(s for s in range(NSHARDS) if cfg.shards[s] == 1)
    skv.admin_sync("move", (shard, 2))
    settle(skv)
    assert skv.query_latest().shards[shard] == 2
    kmap = keys_for_all_shards()
    clerk = BatchedShardClerk(skv, client_id=1)
    if shard in kmap:
        clerk.put(kmap[shard], "moved")
        assert clerk.get(kmap[shard]) == "moved"
        assert kmap[shard] in skv.reps[2].shards[shard].data


def test_concurrent_clients_through_config_churn_linearizable():
    skv = make(G=4, seed=7)
    skv.admin_sync("join", [1])
    sample = sorted(keys_for_all_shards().items())[:3]
    shards = [s for s, _ in sample]
    clerks = [
        BatchedShardClerk(skv, client_id=i + 1, record_shards=shards)
        for i in range(3)
    ]
    sessions = {}
    rng = np.random.default_rng(0)
    kmap = dict(sample)
    admin_steps = iter([("join", [2, 3]), ("leave", [2])])
    admin_op = None
    admin_ticket = None
    for round_no in range(120):
        for i, c in enumerate(clerks):
            if i not in sessions or sessions[i].poll():
                shard, key = sample[rng.integers(len(sample))]
                if rng.random() < 0.5:
                    sessions[i] = c.begin("Append", key, f"({i}.{round_no})")
                else:
                    sessions[i] = c.begin("Get", key)
        # Drive config churn concurrently with client traffic; a failed
        # ticket (lost log slot) is re-issued under the same dedup id.
        if admin_ticket is not None and admin_ticket.done and admin_ticket.failed:
            admin_ticket = getattr(skv, admin_op[0])(
                admin_op[1], command_id=admin_ticket.command_id
            )
        elif admin_ticket is None or admin_ticket.done:
            admin_op = next(admin_steps, None)
            admin_ticket = (
                getattr(skv, admin_op[0])(admin_op[1]) if admin_op else None
            )
            if admin_op is None:
                admin_steps = iter(())
        skv.pump(5)
        for s in sessions.values():
            s.poll()
    # Both admin steps must have committed: join[1] + join[2,3] + leave[2].
    assert skv.query_latest().num >= 3, "config churn never happened"
    # Let stragglers finish.
    for _ in range(200):
        skv.pump(5)
        if all(s.poll() for s in sessions.values()):
            break
    from multiraft_tpu.porcupine.checker import CheckResult, check_operations
    from multiraft_tpu.porcupine.kv import kv_model

    for shard in shards:
        hist = []
        for c in clerks:
            hist.extend(c.histories[shard])
        if hist:
            res = check_operations(kv_model, hist, timeout=10.0)
            assert res is not CheckResult.ILLEGAL, (
                f"shard {shard}: history not linearizable under churn"
            )


def test_route_keys_device_table():
    skv = make(G=3, seed=8)
    skv.admin_sync("join", [1])
    skv.admin_sync("join", [2])
    settle(skv)
    table = skv.shard_table()
    hashes = np.arange(100, dtype=np.int32)
    gids = np.asarray(route_keys(table, hashes))
    cfg = skv.query_latest()
    expect = np.array([cfg.shards[h % NSHARDS] for h in range(100)])
    assert (gids == expect).all()


def test_fast_reads_match_logged_reads():
    """Service-level ReadIndex fast reads agree with logged Gets on
    every shard, and miss with ErrNoKey on absent keys."""
    from multiraft_tpu.engine.shardkv import ERR_NO_KEY

    skv = make(G=3, seed=21)
    skv.admin_sync("join", [1, 2])
    settle(skv)
    clerk = BatchedShardClerk(skv, client_id=1)
    kmap = keys_for_all_shards()
    for shard, k in kmap.items():
        clerk.put(k, f"w{shard}")
    for shard, k in kmap.items():
        t = skv.get_fast(k)
        assert t.done and t.err == OK and t.value == f"w{shard}"
        assert clerk.get(k) == t.value  # logged path agrees
    # An unwritten key on a served shard misses with ErrNoKey.
    shard0, k0 = next(iter(kmap.items()))
    k_other = chr(ord(k0) + NSHARDS)  # same shard, never written
    assert key2shard(k_other) == shard0
    assert skv.get_fast(k_other).err == ERR_NO_KEY


def test_fast_reads_respect_migration_gates():
    """During a stalled migration, fast reads refuse moved shards at
    the old owner (ErrWrongGroup) and keep serving kept shards; after
    the new owner revives, fast reads return the migrated data."""
    skv = make(G=3, seed=22)
    skv.admin_sync("join", [1])
    clerk = BatchedShardClerk(skv, client_id=1)
    kmap = keys_for_all_shards()
    for shard, k in kmap.items():
        clerk.put(k, f"v{shard}")
    for p in (0, 1):
        skv.driver.set_alive(2, p, False)
    skv.admin_sync("join", [2])
    for _ in range(40):
        skv.pump(5)
    cfg = skv.query_latest()
    kept = [s for s in range(NSHARDS) if cfg.shards[s] == 1 and s in kmap]
    moved = [s for s in range(NSHARDS) if cfg.shards[s] == 2 and s in kmap]
    assert kept and moved
    for s in kept:
        assert skv.get_fast(kmap[s]).value == f"v{s}"
    for s in moved:
        assert skv.get_fast(kmap[s]).err == ERR_WRONG_GROUP
    for p in (0, 1):
        skv.driver.restart_replica(2, p)
    settle(skv)
    for s in moved:
        assert skv.get_fast(kmap[s]).value == f"v{s}"


def test_fast_reads_in_churn_history_linearizable():
    """Clerk fast reads interleaved with logged writes through config
    churn stay linearizable on recorded shards."""
    skv = make(G=4, seed=23)
    skv.admin_sync("join", [1])
    sample = sorted(keys_for_all_shards().items())[:2]
    shards = [s for s, _ in sample]
    writer = BatchedShardClerk(skv, client_id=1, record_shards=shards)
    reader = BatchedShardClerk(skv, client_id=2, record_shards=shards)
    session = None
    rng = np.random.default_rng(3)
    admin_steps = iter([("join", [2, 3]), ("leave", [3])])
    admin_ticket = None
    admin_op = None
    for round_no in range(100):
        if session is None or session.poll():
            shard, key = sample[rng.integers(len(sample))]
            session = writer.begin("Append", key, f"[{round_no}]")
        if admin_ticket is not None and admin_ticket.done and admin_ticket.failed:
            admin_ticket = getattr(skv, admin_op[0])(
                admin_op[1], command_id=admin_ticket.command_id
            )
        elif admin_ticket is None or admin_ticket.done:
            admin_op = next(admin_steps, None)
            admin_ticket = (
                getattr(skv, admin_op[0])(admin_op[1]) if admin_op else None
            )
            if admin_op is None:
                admin_steps = iter(())
        skv.pump(5)
        session.poll()
        _, key = sample[rng.integers(len(sample))]
        reader.get_fast(key)
    for _ in range(300):
        skv.pump(5)
        if session.poll():
            break
    from multiraft_tpu.porcupine.checker import CheckResult, check_operations
    from multiraft_tpu.porcupine.kv import kv_model

    for shard in shards:
        hist = writer.histories[shard] + reader.histories[shard]
        res = check_operations(kv_model, hist, timeout=10.0)
        assert res is not CheckResult.ILLEGAL, (
            f"shard {shard}: fast reads broke linearizability"
        )


def test_migration_under_reordering_and_loss():
    """Config churn + shard pulls while the transport reorders half the
    messages and drops 10%: migration must still complete exactly-once
    and serve everything afterward."""
    skv = make(G=3, seed=31)
    skv.driver.set_reorder(0.5, 2, 8)
    skv.driver.drop_prob = 0.1
    skv.admin_sync("join", [1])
    kmap = keys_for_all_shards()
    clerk = BatchedShardClerk(skv, client_id=1)
    # Appends, not puts: a dedup failure under drops/reorder (a retried
    # command applied twice) shows up as a doubled suffix.
    for shard, k in kmap.items():
        clerk.put(k, f"r{shard}")
        clerk.append(k, "a")
    skv.admin_sync("join", [2])
    skv.admin_sync("leave", [1])
    for shard, k in kmap.items():
        clerk.append(k, "b")  # mid/post-migration appends, still faulted
    skv.driver.set_reorder(0.0)
    skv.driver.drop_prob = 0.0
    settle(skv)
    cfg = skv.query_latest()
    assert all(g == 2 for g in cfg.shards)
    for shard, k in kmap.items():
        expect = f"r{shard}ab"
        assert clerk.get(k) == expect, f"key {k}: {clerk.get(k)!r} != {expect!r}"
        assert skv.get_fast(k).value == expect


def test_restart_during_config_churn_linearizable():
    """Engine-backend analog of the reference's crash-restart-during-
    config-churn suite (shardkv/test_test.go:456-522 TestConcurrent3):
    while joins/leaves churn and clients append, every group's replicas
    take rolling crash-restarts (persistent columns survive, volatile
    state resets — the engine's per-replica crash model).  The service
    host state machine applies only committed entries, so replica
    crashes must be invisible to it; per-shard histories must stay
    linearizable and the final values exact."""
    skv = make(G=4, seed=11)
    d = skv.driver
    skv.admin_sync("join", [1])
    sample = sorted(keys_for_all_shards().items())[:3]
    shards = [s for s, _ in sample]
    clerks = [
        BatchedShardClerk(skv, client_id=i + 1, record_shards=shards)
        for i in range(3)
    ]
    sessions = {}
    rng = np.random.default_rng(5)
    admin_steps = iter(
        [("join", [2, 3]), ("leave", [2]), ("join", [2]), ("leave", [3])]
    )
    admin_op = None
    admin_ticket = None
    down = []  # (group, peer) crashed engine replicas
    for round_no in range(160):
        for i, c in enumerate(clerks):
            if i not in sessions or sessions[i].poll():
                shard, key = sample[rng.integers(len(sample))]
                if rng.random() < 0.5:
                    sessions[i] = c.begin("Append", key, f"({i}.{round_no})")
                else:
                    sessions[i] = c.begin("Get", key)
        if admin_ticket is not None and admin_ticket.done and admin_ticket.failed:
            admin_ticket = getattr(skv, admin_op[0])(
                admin_op[1], command_id=admin_ticket.command_id
            )
        elif admin_ticket is None or admin_ticket.done:
            admin_op = next(admin_steps, None)
            admin_ticket = (
                getattr(skv, admin_op[0])(admin_op[1]) if admin_op else None
            )
            if admin_op is None:
                admin_steps = iter(())
        # Rolling crash-restarts DURING the churn: crash a random live
        # replica (often the leader) every few rounds; restart the
        # oldest casualty so each group keeps a quorum.
        if round_no % 5 == 2:
            g = int(rng.integers(d.cfg.G))
            p = d.leader_of(g)
            if p is None:
                p = int(rng.integers(d.cfg.P))
            if (g, p) not in down:
                d.set_alive(g, p, False)
                down.append((g, p))
        while len(down) > d.cfg.G * ((d.cfg.P - 1) // 2) or (
            down and rng.random() < 0.3
        ):
            g, p = down.pop(0)
            d.restart_replica(g, p)
        skv.pump(5)
        for s in sessions.values():
            s.poll()
    while down:
        g, p = down.pop()
        d.restart_replica(g, p)
    assert skv.query_latest().num >= 4, "config churn never happened"
    for _ in range(400):
        skv.pump(5)
        if all(s.poll() for s in sessions.values()):
            break
    assert all(s.poll() for s in sessions.values()), (
        "sessions still pending after drain — a dropped op would "
        "silently weaken the linearizability check"
    )
    from multiraft_tpu.porcupine.kv import kv_model
    from multiraft_tpu.porcupine.visualization import assert_linearizable

    for shard in shards:
        hist = []
        for c in clerks:
            hist.extend(c.histories[shard])
        if hist:
            assert_linearizable(
                kv_model, hist, timeout=10.0,
                name=f"engine-churn-crash-shard-{shard}",
            )
    for g in range(d.cfg.G):
        d.check_log_matching(g)
