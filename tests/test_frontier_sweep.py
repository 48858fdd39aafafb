"""The apply sweep (engine/frontier.py ``FrontierService._sweep_frontier``)
visits the groups whose committed index is above their applied index —
``np.flatnonzero(commit > applied_upto)`` — and nothing else.

The contract held here: the sequence of applies, bulk applies, re-keyed
slice tails, evictions, ``last_applied`` and the final ``applied_upto``
are those of a plain walk over every group (the reference loop below,
the form the sweep had before); the work follows the input (counter
``apply.groups_swept``); and ``applied_upto`` has one representation, a
numpy vector in the process and a list of Python ints in a checkpoint.
"""

import pickle
import types

import numpy as np
import pytest

from multiraft_tpu.engine.core import EngineConfig
from multiraft_tpu.engine.frontier import FrontierService
from multiraft_tpu.engine.host import EngineDriver, PayloadSlice
from multiraft_tpu.engine.kv import BatchedKV, KVOp
from multiraft_tpu.porcupine.kv import OP_APPEND
from multiraft_tpu.utils.metrics import Metrics


class StubDriver:
    """What the sweep reads of an ``EngineDriver``: the size, the tick,
    the last pump's commit frontier, the payload map and a registry."""

    def __init__(self, G: int) -> None:
        self.cfg = types.SimpleNamespace(G=G)
        self.tick = 0
        self.payloads = {}
        self.last_metrics = {"commit_index": np.zeros(G, np.int32)}
        self.metrics = Metrics()
        self.on_payload_evicted = None


def describe(payload):
    if isinstance(payload, PayloadSlice):
        return ("slice", payload.frame, payload.rows.tolist())
    return payload


class Recording(FrontierService):
    """Records every hook the sweep calls, with the read index the
    group showed at that moment (it must advance entry by entry)."""

    ORPHAN_SWEEP_TICKS = 10 ** 9  # the orphan sweep is not under test

    def __init__(self, driver) -> None:
        super().__init__(driver)
        self.events = []

    def _apply(self, g, idx, payload, now):
        assert type(g) is int and type(idx) is int
        self.events.append(
            ("apply", g, idx, describe(payload), now, int(self.applied_upto[g]))
        )

    def _apply_slice(self, g, idx, sl, now):
        assert type(g) is int and type(idx) is int
        self.events.append(
            ("slice", g, idx, describe(sl), now, int(self.applied_upto[g]))
        )

    def _on_evicted(self, payload):
        self.events.append(("evict", describe(payload)))

    def _post_pump(self):
        self.events.append(("post_pump", self.last_applied))


def reference_sweep(G, commit, applied_upto, payloads, retain, now, events):
    """The sweep as a walk over every group: ``applied_upto`` is a list,
    every group is visited.  Returns the entries applied."""
    applied = 0
    for g in range(G):
        upto = int(commit[g])
        while applied_upto[g] < upto:
            idx = applied_upto[g] + 1
            if retain:
                payload = payloads.get((g, idx))
            else:
                payload = payloads.pop((g, idx), None)
            if isinstance(payload, PayloadSlice):
                assert not retain
                avail = upto - idx + 1
                if payload.count > avail:
                    tail_key = (g, idx + avail)
                    stale = payloads.get(tail_key)
                    if stale is not None:
                        events.append(("evict", describe(stale)))
                    payloads[tail_key] = payload
                    payload = payload.split_head(avail)
                events.append(
                    ("slice", g, idx, describe(payload), now, applied_upto[g])
                )
                applied_upto[g] = idx + payload.count - 1
                applied += payload.count
            else:
                events.append(
                    ("apply", g, idx, describe(payload), now, applied_upto[g])
                )
                applied_upto[g] = idx
                applied += 1
    events.append(("post_pump", applied))
    return applied


def choose_moved(rng, G, moved):
    if moved == "none":
        return np.zeros(0, np.int64)
    if moved == "few":
        return np.sort(rng.choice(G, size=min(3, G), replace=False))
    return np.arange(G)


def one_round(rng, G, applied_upto, moved, retain, round_no):
    """A commit vector and the payloads bound above the applied frontier
    of the groups that move.  The k-th moved group takes shape k % 6, so
    every shape is met whatever the seed:

    0  single commands only
    1  a slice that ends AT the committed prefix
    2  a slice that runs BEYOND it (its tail is re-keyed)
    3  as 2, over a stale binding at the tail's key (evicted)
    4  a slice INSIDE the prefix, then single commands
    5  missing payloads (``None`` reaches ``_apply``)
    """
    commit = np.array(applied_upto, np.int32)
    payloads = {}
    for k, g in enumerate(choose_moved(rng, G, moved).tolist()):
        base = int(applied_upto[g])
        delta = int(rng.integers(2, 7))
        upto = base + delta
        commit[g] = upto
        shape = k % 6
        if retain and shape in (1, 2, 3, 4):
            shape = 0 if shape % 2 else 5  # split mode has no firehose
        tag = f"r{round_no}g{g}"
        if shape == 0:
            for idx in range(base + 1, upto + 1):
                payloads[(g, idx)] = ("cmd", tag, idx)
        elif shape == 1:
            payloads[(g, base + 1)] = PayloadSlice(tag, np.arange(delta))
        elif shape in (2, 3):
            extra = int(rng.integers(1, 4))
            payloads[(g, base + 1)] = PayloadSlice(
                tag, np.arange(delta + extra)
            )
            if shape == 3:
                payloads[(g, upto + 1)] = ("stale", tag)
        elif shape == 4:
            head = delta - 1
            payloads[(g, base + 1)] = PayloadSlice(tag, np.arange(head))
            payloads[(g, base + 1 + head)] = ("cmd", tag, upto)
        else:
            payloads[(g, base + 1)] = ("cmd", tag, base + 1)  # the rest: None
    return commit, payloads


def clone(payloads):
    return {
        k: PayloadSlice(p.frame, p.rows.copy())
        if isinstance(p, PayloadSlice) else p
        for k, p in payloads.items()
    }


@pytest.mark.parametrize("moved", ["none", "few", "all"])
@pytest.mark.parametrize("retain", [False, True], ids=["pop", "retain"])
@pytest.mark.parametrize("G", [1, 7, 4096])
def test_sweep_equals_a_walk_over_every_group(G, retain, moved):
    rng = np.random.default_rng([G, int(retain), len(moved)])
    driver = StubDriver(G)
    svc = Recording(driver)
    svc.retain_payloads = retain
    start = rng.integers(0, 5, size=G)
    svc.applied_upto[:] = start
    ref_applied = start.tolist()
    ref_payloads = {}
    ref_events = []
    for round_no in range(3):
        commit, fresh = one_round(
            rng, G, ref_applied, moved, retain, round_no
        )
        ref_payloads.update(clone(fresh))
        driver.payloads.update(clone(fresh))
        driver.last_metrics = {"commit_index": commit}
        driver.tick = 10 * (round_no + 1)
        want = reference_sweep(
            G, commit, ref_applied, ref_payloads, retain, driver.tick,
            ref_events,
        )
        svc._sweep_frontier(2)
        assert svc.last_applied == want
        assert svc.events == ref_events
        assert svc.applied_upto.tolist() == ref_applied
        assert {k: describe(p) for k, p in driver.payloads.items()} == {
            k: describe(p) for k, p in ref_payloads.items()
        }
        # Keys the sweep wrote (re-keyed tails) are plain ints: the
        # payload map is pickled into every checkpoint.
        assert all(
            type(g) is int and type(i) is int for g, i in driver.payloads
        )
    if moved == "none":
        assert [e for e in svc.events if e[0] != "post_pump"] == []
    else:
        assert any(e[0] == "apply" for e in svc.events)
    if moved != "none" and not retain and G > 1:
        assert any(e[0] == "slice" for e in svc.events)
    if moved == "all" and not retain and G >= 7:
        assert any(e[0] == "evict" for e in svc.events)


def test_work_follows_the_groups_that_moved():
    G = 4096
    driver = StubDriver(G)
    svc = Recording(driver)
    svc.applied_upto[:] = 5
    commit = np.full(G, 5, np.int32)
    # A restored or snapshot-installed group sits AHEAD of the frontier
    # the device reports for a while: nothing is applied there.
    commit[[17, 2000]] = 3
    movers = {9: 6, 1234: 8, 4095: 7}
    for g, upto in movers.items():
        commit[g] = upto
        for idx in range(6, upto + 1):
            driver.payloads[(g, idx)] = ("cmd", g, idx)
    driver.last_metrics = {"commit_index": commit}
    before = driver.metrics.counters["apply.groups_swept"]
    svc._sweep_frontier(2)
    assert driver.metrics.counters["apply.groups_swept"] - before == 3
    applies = [e for e in svc.events if e[0] == "apply"]
    assert [(e[1], e[2]) for e in applies] == [
        (9, 6), (1234, 6), (1234, 7), (1234, 8), (4095, 6), (4095, 7)
    ]
    assert svc.last_applied == 6
    assert svc.applied_upto[17] == 5 and svc.applied_upto[2000] == 5
    assert driver.payloads == {}
    # A pump in which nothing committed sweeps no group at all.
    svc.events.clear()
    svc._sweep_frontier(2)
    assert driver.metrics.counters["apply.groups_swept"] - before == 3
    assert svc.events == [("post_pump", 0)]
    assert svc.last_applied == 0


def test_checkpoint_carries_a_list_of_python_ints():
    driver = StubDriver(5)
    svc = Recording(driver)
    assert isinstance(svc.applied_upto, np.ndarray)
    svc.applied_upto[:] = [0, 3, 1, 0, 9]
    blob = svc.state_dict()
    assert type(blob["applied_upto"]) is list
    assert all(type(v) is int for v in blob["applied_upto"])
    # The bytes are those of the list the service used to hold.
    assert pickle.dumps(blob, protocol=4) == pickle.dumps(
        {"applied_upto": [0, 3, 1, 0, 9]}, protocol=4
    )


@pytest.mark.parametrize(
    "held", [[2, 0, 4], np.array([2, 0, 4], np.int32)], ids=["list", "array"]
)
def test_an_old_checkpoint_loads_and_sweeps_on(held):
    driver = StubDriver(3)
    svc = Recording(driver)
    svc.load_state_dict({"applied_upto": held})
    assert isinstance(svc.applied_upto, np.ndarray)
    assert svc.applied_upto.dtype == np.int64
    assert not np.shares_memory(svc.applied_upto, np.asarray(held))
    driver.payloads[(1, 1)] = "a"
    driver.payloads[(2, 5)] = "b"
    driver.last_metrics = {"commit_index": np.array([2, 1, 5], np.int32)}
    svc._sweep_frontier(2)
    assert [e[1:4] for e in svc.events if e[0] == "apply"] == [
        (1, 1, "a"), (2, 5, "b")
    ]
    assert svc.state_dict() == {"applied_upto": [2, 1, 5]}


def test_batched_kv_round_trip_keeps_one_representation(tmp_path):
    d = EngineDriver(EngineConfig(G=4, P=3, L=32, E=4, INGEST=4), seed=5)
    assert d.run_until_quiet_leaders(400)
    kv = BatchedKV(d)
    acked = {g: "" for g in range(4)}
    for i in range(6):
        g = i % 3  # group 3 stays quiet
        t = kv.submit(g, KVOp(op=OP_APPEND, key="k", value=f".{i}"))
        for _ in range(40):
            kv.pump()
            if t.done:
                break
        assert t.done and not t.failed
        acked[g] += f".{i}"
    commit = np.asarray(d.last_metrics["commit_index"])
    assert isinstance(kv.applied_upto, np.ndarray)
    assert kv.applied_upto.tolist() == commit.tolist()
    swept = d.metrics.counters["apply.groups_swept"]
    assert 0 < swept  # and far below pumps x G: most pumps move no group
    path = str(tmp_path / "ckpt.pkl")
    d.save(path, extra=kv.state_dict())
    d2 = EngineDriver.restore(path)
    held = d2.restored_extra["applied_upto"]
    assert type(held) is list and all(type(v) is int for v in held)
    kv2 = BatchedKV(d2)
    kv2.load_state_dict(d2.restored_extra)
    assert isinstance(kv2.applied_upto, np.ndarray)
    assert kv2.applied_upto.tolist() == held
    for g in range(4):
        assert kv2.get(g, "k").value == acked[g]
    t = kv2.submit(3, KVOp(op=OP_APPEND, key="k", value="!"))
    for _ in range(40):
        kv2.pump()
        if t.done:
            break
    assert t.done and not t.failed
    assert kv2.get(3, "k").value == "!"
    assert kv2.applied_upto[3] == held[3] + 1


def test_values_that_leave_the_service_are_python_ints():
    """Snapshot slabs and persisted blobs carry ``applied_upto[g]`` out
    of the process; a numpy scalar there would change their bytes."""
    from multiraft_tpu.engine.split import SplitKV
    from multiraft_tpu.engine.split_shard import SplitShardKV

    cfg = EngineConfig(G=3, P=3, L=48, E=8, INGEST=8,
                       host_paced_compaction=True)
    kv = SplitKV(EngineDriver(cfg, seed=1))
    kv.restore_group(1, 7, {"data": {"k": "v"}, "sessions": {}})
    upto, blob = kv.snapshot_group(1)
    assert type(upto) is int and upto == 7 and blob["data"] == {"k": "v"}
    kv.install_group_snapshot(1, 5, {"data": {}, "sessions": {}})  # stale
    assert kv.snapshot_group(1)[0] == 7
    skv = SplitShardKV(EngineDriver(cfg, seed=2))
    assert type(skv.snapshot_group(0)[0]) is int
    assert type(skv.persist_group(0)[0]) is int
