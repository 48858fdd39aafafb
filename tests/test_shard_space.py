"""The shard space at size: BASELINE config 3's ratio (10 shards : 3
groups) at 33 replica groups and 110 shards, a hashed partitioner, held
to a plain reference (``harness/shardref.py``) that shares no code with
``BatchedShardKV`` or ``rebalance``.

(a) seeded interleavings of client operations and join / leave / move
    against the reference; (b) the new ``rebalance`` against the scan it
    replaced; (c) sparse slots and the orchestration sweep's active set;
(d) the partitioners; (e) the CLI, durable, through ``kill -9``, and a
    ``--data-dir`` of another shard space refused by name; (f) a client
    process whose own ``MULTIRAFT_NSHARDS`` differs from the server's.
"""

import json
import os
import random
import signal
import subprocess
import sys
import zlib
from collections import Counter

import pytest

from multiraft_tpu.engine.core import EngineConfig
from multiraft_tpu.engine.host import EngineDriver
from multiraft_tpu.engine.shardkv import BatchedShardClerk, BatchedShardKV
from multiraft_tpu.harness.shardref import ShardRef
from multiraft_tpu.porcupine.checker import CheckResult, check_operations
from multiraft_tpu.porcupine.kv import kv_model
from multiraft_tpu.services.shardctrler import ShardSpace, rebalance
from multiraft_tpu.services.shardkv import SERVING, key2shard

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GROUPS, SHARDS = 33, 110  # replica groups, shards: the source's 3 : 10
SPACE = ShardSpace.of(SHARDS)


def make(seed=0, joined=range(1, GROUPS + 1)):
    cfg = EngineConfig(G=GROUPS + 1, P=3, L=64, E=8, INGEST=8)
    driver = EngineDriver(cfg, seed=seed)
    assert driver.run_until_quiet_leaders(max_ticks=1000)
    skv = BatchedShardKV(driver, space=SPACE)
    ref = ShardRef(SHARDS, SPACE.shard_of)
    if joined:
        skv.admin_sync("join", list(joined))  # ONE join: config 1
        ref.join(joined)
        settle(skv)
    return skv, ref


def settle(skv, max_ticks=6000):
    for _ in range(0, max_ticks, 5):
        skv.pump(5)
        if skv.at_rest():
            latest = skv.configs[-1].num
            assert all(r.cur.num == latest and r.settled() for r in skv.reps.values())
            return
    raise TimeoutError(f"did not settle at config {skv.configs[-1].num}")


def counters(skv):
    return skv.driver.metrics.counters


def served_by(skv, key):
    """The gids that would answer for ``key`` now, and those holding it."""
    shard = SPACE.shard_of(key)
    serving = [g for g, r in skv.reps.items() if r.can_serve(shard)]
    holding = [g for g, r in skv.reps.items() if key in r.shards[shard].data]
    return serving, holding


# ---------------------------------------------------------------------------
# (a) against the plain reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [11, 12, 13])
@pytest.mark.timeout_s(600)
def test_interleaved_clients_and_reconfiguration_match_the_reference(seed):
    rng = random.Random(seed)
    skv, ref = make(seed=seed, joined=range(1, 23))  # 22 of 33 to start
    assert skv.configs[-1].num == 1 and skv.configs[-1].shards == ref.owner
    n_clerks = 4
    shared = [f"shared{i}" for i in range(3)]
    record = sorted({SPACE.shard_of(k) for k in shared})
    clerks = [
        BatchedShardClerk(skv, client_id=i + 1, record_shards=record)
        for i in range(n_clerks)
    ]
    own = [[f"user{c}-{i:04d}" for i in range(40)] for c in range(n_clerks)]
    # Each clerk's own keys are written by it alone, so its replies are
    # the reference's whatever the interleaving; the shared keys are
    # appended by all four and judged by porcupine.
    expect = []      # (session, the reference's reply)
    sessions = {}
    admin_steps = [
        ("join", list(range(23, 34))),
        ("leave", rng.sample(range(1, 34), 6)),
        ("move", (rng.randrange(SHARDS), 0)),  # gid filled in when issued
        ("join", None),                        # whoever left comes back
        ("leave", rng.sample(range(1, 34), 4)),
    ]
    admin, ticket, left = None, None, []
    for round_no in range(400):
        for c, clerk in enumerate(clerks):
            s = sessions.get(c)
            if s is not None and not s.poll():
                continue
            roll = rng.random()
            if roll < 0.15:
                key = rng.choice(shared)
                sessions[c] = (
                    clerk.begin("Append", key, f"({c}.{round_no})")
                    if rng.random() < 0.6 else clerk.begin("Get", key)
                )
                continue
            key = rng.choice(own[c])
            op = "Get" if roll < 0.45 else ("Put" if roll < 0.7 else "Append")
            value = "" if op == "Get" else f"<{c}.{round_no}>"
            sessions[c] = clerk.begin(op, key, value)
            expect.append((sessions[c], ref.apply(op, key, value)))
        # One admin operation at a time, re-issued under its dedup id if
        # its log slot was lost; the reference applies it when it commits.
        if ticket is not None and ticket.done and ticket.failed:
            kind, arg = admin
            ticket = (skv.move(*arg, command_id=ticket.command_id) if kind == "move"
                      else getattr(skv, kind)(arg, command_id=ticket.command_id))
        elif (ticket is None or ticket.done) and round_no % 40 == 20 and admin_steps:
            kind, arg = admin_steps.pop(0)
            if kind == "move":
                arg = (arg[0], rng.choice(sorted(ref.groups)))
            elif kind == "join" and arg is None:
                arg = list(left)
            if kind == "leave":
                left = list(arg)
            admin = (kind, arg)
            ticket = skv.move(*arg) if kind == "move" else getattr(skv, kind)(arg)
            (ref.move if kind == "move" else getattr(ref, kind))(
                *(arg if kind == "move" else (arg,)))
        skv.pump(5)
    assert not admin_steps, "the admin steps never all ran"
    for _ in range(400):
        skv.pump(5)
        if all(s.poll() for s in sessions.values()) and (ticket is None or ticket.done):
            break
    settle(skv)
    # every acknowledged reply is the reference's
    assert all(s.done for s, _ in expect)
    wrong = [(s.op, s.key, s.result, want) for s, want in expect
             if s.op == "Get" and s.result != want]
    assert not wrong, wrong[:3]
    # the owners and the final state are the reference's
    assert skv.configs[-1].shards == ref.owner
    final = {}
    for rep in skv.reps.values():
        for sh in rep.shards.values():
            assert not (final.keys() & sh.data.keys()), "a key lives in two groups"
            final.update(sh.data)
    want = ref.items()
    for key in shared:   # their order is porcupine's to judge
        assert sorted(final.pop(key, "")) == sorted(
            "".join(s.value for s in sessions_of(clerks, key)))
        want.pop(key, None)
    assert final == want
    # each key is served by exactly the group the latest config names
    for key in list(want) + shared:
        serving, holding = served_by(skv, key)
        assert serving == [ref.owner_of(key)], (key, serving, ref.owner_of(key))
        assert holding in ([], serving), (key, holding)
    for shard in record:
        hist = [op for c in clerks for op in c.histories[shard]]
        assert check_operations(kv_model, hist, timeout=20.0) is not CheckResult.ILLEGAL


def sessions_of(clerks, key):
    """Every acknowledged Append to ``key``, from the recorded histories."""
    from multiraft_tpu.porcupine.kv import OP_APPEND

    shard = SPACE.shard_of(key)
    return [op.input for c in clerks for op in c.histories[shard]
            if op.input.op == OP_APPEND and op.input.key == key]


# ---------------------------------------------------------------------------
# (b) the rebalance against the scan it replaced
# ---------------------------------------------------------------------------


def reference_rebalance(shards, groups):
    """``rebalance`` as it stood before this file: a scan of every group
    for each unassigned shard and of every shard for each move."""
    if not groups:
        return [0] * len(shards)
    counts = {gid: 0 for gid in sorted(groups)}
    out = list(shards)
    for s, g in enumerate(out):
        if g in counts:
            counts[g] += 1
        else:
            out[s] = 0

    def min_gid():
        return min(counts, key=lambda g: (counts[g], g))

    def max_gid():
        return max(counts, key=lambda g: (counts[g], -g))

    for s in range(len(out)):
        if out[s] == 0:
            g = min_gid()
            out[s] = g
            counts[g] += 1
    while True:
        mx, mn = max_gid(), min_gid()
        if counts[mx] - counts[mn] <= 1:
            break
        for s in range(len(out)):
            if out[s] == mx:
                out[s] = mn
                counts[mx] -= 1
                counts[mn] += 1
                break
    return out


@pytest.mark.parametrize("n_shards", [10, 33, 110, 500, 2000])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_rebalance_equals_the_scan_on_seeded_histories(n_shards, seed):
    rng = random.Random(seed * 10_000 + n_shards)
    shards, groups = [0] * n_shards, {}
    ref = ShardRef(n_shards, lambda k: 0)
    for _ in range(14):
        if groups and rng.random() < 0.3:   # a move skews what comes next
            s, g = rng.randrange(n_shards), rng.choice(sorted(groups))
            shards = shards[:s] + [g] + shards[s + 1:]
            ref.move(s, g)
        if groups and rng.random() < 0.4:
            gone = rng.sample(sorted(groups), rng.randint(1, len(groups)))
            for g in gone:
                groups.pop(g)
            ref.leave(gone)
        else:
            new = rng.sample(range(1, 3 * n_shards), rng.randint(1, max(2, n_shards // 4)))
            groups.update({g: [f"s{g}"] for g in new})
            ref.join(new)
        was = list(shards)
        want = reference_rebalance(shards, groups)
        assert shards == was, "the input is not to be written"
        shards = rebalance(shards, groups)
        assert shards == want
        assert shards == ref.owner  # the plain reference's loops agree too
        if groups:
            load = Counter(shards)
            assert max(load.values()) - min(load.get(g, 0) for g in groups) <= 1


def test_rebalance_at_the_deployments_size_is_the_sources_4_3_3():
    out = rebalance([0] * 33_330, {g: [f"engine-group-{g}"] for g in range(1, 10_000)})
    assert Counter(Counter(out).values()) == {4: 3333, 3: 6666}
    assert rebalance(out, {g: [] for g in range(1, 10_000)}) == out  # at rest: no move


# ---------------------------------------------------------------------------
# (c) sparse slots, the active set
# ---------------------------------------------------------------------------


@pytest.mark.timeout_s(300)
def test_quiescent_the_sweep_visits_nobody_and_slots_number_the_shards():
    skv, ref = make(seed=3)
    assert skv.configs[-1].num == 1, "the bootstrap is ONE join"
    assert sum(len(r.shards) for r in skv.reps.values()) == SHARDS
    assert sorted(Counter(skv.configs[-1].shards).values()) == [3] * 22 + [4] * 11
    g = skv.driver.metrics.gauges
    assert (g["shard.count"], g["shard.slots"], g["shard.config_num"]) == (SHARDS, SHARDS, 1)
    visited = counters(skv)["shard.orchestrate_groups"]
    skipped = counters(skv)["shard.orchestrate_skipped"]
    clerk = BatchedShardClerk(skv, client_id=9)
    for i in range(10):  # client traffic is no work for the sweep
        clerk.put(f"user{i:012d}", "v")
    pumps0 = skv.driver.metrics.hists["shard.orchestrate_s"].count
    for _ in range(50):
        skv.pump(1)
    assert counters(skv)["shard.orchestrate_groups"] == visited
    assert counters(skv)["shard.orchestrate_skipped"] >= skipped + 50 * GROUPS
    assert skv.driver.metrics.hists["shard.orchestrate_s"].count == pumps0 + 50  # one a pump


@pytest.mark.timeout_s(300)
def test_after_a_move_only_the_two_groups_involved_stay_in_the_sweep():
    skv, ref = make(seed=4)
    clerk = BatchedShardClerk(skv, client_id=1)
    keys = {}
    for i in range(400):
        keys.setdefault(SPACE.shard_of(f"user{i:012d}"), f"user{i:012d}")
    shard, key = next(iter(sorted(keys.items())))
    clerk.put(key, "before")
    src = skv.configs[-1].shards[shard]
    dst = next(g for g in skv.gids if g != src)
    c0 = dict(counters(skv))
    skv.admin_sync("move", (shard, dst))
    # Every group has to log the new config (configs apply in order in
    # EVERY group, the parent's rule); once they have, only the old and
    # the new owner have work left.
    seen_after = set()
    for _ in range(400):
        skv.pump(1)
        if all(r.cur.num == 2 for r in skv.reps.values()):
            seen_after |= skv._active
        if skv.at_rest():
            break
    assert skv.at_rest()
    assert seen_after <= {src, dst}, seen_after
    grew = {k: counters(skv)[k] - c0.get(k, 0) for k in counters(skv) if k.startswith("shard.")}
    assert grew["shard.config_applies"] == GROUPS
    assert (grew["shard.pulls"], grew["shard.inserts"], grew["shard.deletes"],
            grew["shard.confirms"]) == (1, 1, 1, 1)
    # Challenge 1: the old owner's slot is gone, not merely emptied
    assert shard not in skv.reps[src].shards
    assert skv.reps[src].shards[shard].data == {}           # and reads as empty, SERVING
    assert skv.reps[src].shards[shard].state == SERVING
    assert skv.reps[dst].shards[shard].data == {key: "before"}
    assert sum(len(r.shards) for r in skv.reps.values()) == SHARDS
    assert skv.driver.metrics.gauges["shard.slots"] == SHARDS
    assert clerk.get(key) == "before"
    with pytest.raises((AttributeError, TypeError)):
        skv.reps[src].shards[shard].data["x"] = "lost"      # no silent write to no slot


@pytest.mark.timeout_s(300)
def test_a_checkpoint_keeps_the_sparse_form_and_shares_each_config():
    import pickle

    skv, ref = make(seed=5)
    clerk = BatchedShardClerk(skv, client_id=1)
    clerk.put("user000000000001", "v1")
    blob = pickle.loads(pickle.dumps(skv.state_dict()))
    assert blob["space"] == (SHARDS, "crc32")
    assert len({id(r.cur) for r in blob["reps"].values()}) == 1   # one config, 33 groups
    assert sum(len(r.shards) for r in blob["reps"].values()) == SHARDS
    clerk.put("user000000000001", "v2")                           # the blob does not alias
    other, _ = make(seed=5, joined=())
    other.load_state_dict(blob)
    assert other.reps[other.owner_of("user000000000001")].shards[
        SPACE.shard_of("user000000000001")].data == {"user000000000001": "v1"}
    # another shard space is refused by name, both ways
    ten = BatchedShardKV(skv.driver, space=ShardSpace.of(10))
    with pytest.raises(ValueError, match=r"110 shards \(crc32\).*asked for 10 shards \(first_byte\)"):
        ten.load_state_dict(blob)
    with pytest.raises(ValueError, match=r"--shards 10"):
        skv.load_state_dict(ten.state_dict())


# ---------------------------------------------------------------------------
# (d) the partitioners
# ---------------------------------------------------------------------------


def test_the_hashed_partitioner_spreads_ycsb_keys_and_the_default_is_the_first_byte():
    big = ShardSpace.of(33_330)
    assert big.partitioner == "crc32"
    load = Counter(big.shard_of(f"user{i:012d}") for i in range(100_000))
    assert max(load.values()) <= 20 and len(load) >= 30_000, (max(load.values()), len(load))
    assert big.shard_of("user000000000042") == zlib.crc32(b"user000000000042") % 33_330
    ref = ShardSpace.of(10)
    assert ref == ShardSpace(10, "first_byte") and ShardSpace.of().partitioner == "first_byte"
    for key in ("", "a", "user1", "user2", "zebra", "0"):
        assert ref.shard_of(key) == key2shard(key) == (ord(key[0]) if key else 0) % 10
    assert len({ref.shard_of(f"user{i}") for i in range(100)}) == 1  # why the deployment hashes
    with pytest.raises(ValueError):
        ShardSpace(0)
    with pytest.raises(ValueError):
        ShardSpace(10, "md5")


# ---------------------------------------------------------------------------
# (e), (f) through the CLI
# ---------------------------------------------------------------------------


def _serve(data_dir, *flags, wait=True):
    from multiraft_tpu.distributed.launch import check_ready, reserve_ports

    port = reserve_ports(1, "127.0.0.1")[0]
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.Popen(
        [sys.executable, "-m", "multiraft_tpu", "serve-shardkv", "--groups", "34",
         "--data-dir", str(data_dir), "--port", str(port), *flags],
        env=env, text=True, stdout=subprocess.PIPE, stderr=subprocess.PIPE, cwd=REPO,
    )
    if wait:
        check_ready(proc, "serve-shardkv", timeout=240)
    return proc, port


_CLIENT = """
import json, sys
from multiraft_tpu.distributed.engine_clerks import EngineClerk, FirehoseClerk
from multiraft_tpu.distributed.tcp import RpcNode
from multiraft_tpu.services.shardctrler import NSHARDS
port, what = int(sys.argv[1]), sys.argv[2]
node = RpcNode()
end = node.client_end("127.0.0.1", port)
run = lambda gen: node.sched.wait(node.sched.spawn(gen), 120)
keys = [f"user{i:012d}" for i in range(600)]
fc = FirehoseClerk(node.sched, end, "EngineShardKV")
out = {"nshards_here": NSHARDS, "info": node.sched.wait(end.call("EngineShardKV.info", None), 30)}
if what == "load":
    run(fc.run_batch([("Put", k, "v-" + k) for k in keys], deadline_s=90))
    ck = EngineClerk(node.sched, end, service="EngineShardKV")
    run(ck.append("shared", "(one)")); run(ck.append("shared", "(two)"))
got = run(fc.run_batch([("Get", k, "") for k in keys + ["shared"]], deadline_s=90))
out["wrong"] = [k for k, v in zip(keys, got) if v != "v-" + k]
out["shared"] = got[-1]
out["config"] = node.sched.wait(end.call("EngineShardKV.config", None), 30)[0]
print(json.dumps(out))
node.close()
"""


def _client(port, what, **env):
    out = subprocess.run(
        [sys.executable, "-c", _CLIENT, str(port), what], cwd=REPO, text=True,
        capture_output=True, timeout=300,
        env=dict(os.environ, PYTHONPATH=REPO, **env),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.timeout_s(900)
def test_cli_serves_a_hashed_shard_space_durably_and_refuses_another(tmp_path):
    flags = ("--shards", "110", "--join", "all", "--checkpoint-every", "3600")
    proc, port = _serve(tmp_path, *flags)
    try:
        # (f) this client's own constants say 7 shards; the server's say 110
        said = _client(port, "load", MULTIRAFT_NSHARDS="7")
        assert said["nshards_here"] == 7
        assert said["info"] == {"G": 34, "P": 3, "state_devices": 1,
                                "shards": 110, "partitioner": "crc32"}
        assert said["wrong"] == [] and said["shared"] == "(one)(two)"
        assert said["config"] == 1, "--join all is one join"
        proc.send_signal(signal.SIGKILL)
        proc.wait()
        # (e) every acknowledged write is read back after kill -9
        proc, port = _serve(tmp_path, *flags)
        said = _client(port, "read")
        assert said["nshards_here"] == 10
        assert said["wrong"] == [] and said["shared"] == "(one)(two)"
        assert said["config"] == 1, "a restart re-ran the bootstrap join"
    finally:
        proc.kill()
        proc.wait()
    # the same --data-dir at another shard space: refused by name, exit 1
    proc, _ = _serve(tmp_path, "--shards", "10", "--join", "all", wait=False)
    try:
        out, err = proc.communicate(timeout=240)
    finally:
        proc.kill()
    assert proc.returncode == 1, (proc.returncode, err[-500:])
    assert "ready" not in out
    assert "110 shards (crc32)" in err and "asked for 10 shards (first_byte)" in err
    assert "--shards 110" in err


@pytest.mark.parametrize("text, hosted, want", [
    ("all", [1, 2, 3], [1, 2, 3]),
    ("1", [1, 2, 3], [1]),
    ("1,3", [1, 2, 3], [1, 3]),
    ("1-3", [], [1, 2, 3]),
    ("1,4-6,9", [], [1, 4, 5, 6, 9]),
], ids=["all", "one", "list", "range", "mixed"])
def test_the_join_flag_takes_all_a_list_and_ranges(text, hosted, want):
    from multiraft_tpu.__main__ import _gid_list

    assert _gid_list(text, hosted) == want
