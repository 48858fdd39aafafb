"""The orphan sweep (engine/frontier.py ``FrontierService.sweep_orphans``)
fails the tickets whose bound ``(group, index)`` log slot was truncated
by a leader change.  It is part of the guarantees: such a ticket must
fail promptly, and one that can still commit must not.

The sweep reads the rows of the groups that hold a binding: one gather
of a fixed width off the device (``EngineDriver.rows_stacked``), the
leader and its log end derived in numpy.  The form it had before, every
state plane copied to the host (``np_state``) and ``leader_of`` a group,
is kept here as :func:`reference_orphans`; every case runs both on the
same real CPU driver and compares the evictions in order, the surviving
bindings and the return value.  And once a served node is ``ready`` the
sweep compiles nothing, whatever the number of bound groups, and no pump
copies the whole state.
"""

import copy
import threading

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

from multiraft_tpu.distributed.engine_server import (  # noqa: E402
    EngineClerk,
    serve_engine_kv,
)
from multiraft_tpu.distributed.engine_wire import make_mesh  # noqa: E402
from multiraft_tpu.distributed.tcp import RpcNode  # noqa: E402
from multiraft_tpu.engine.core import (  # noqa: E402
    COMMIT_DEPTH,
    FOLLOWER,
    LEADER,
    EngineConfig,
)
from multiraft_tpu.engine.frontier import FrontierService  # noqa: E402
from multiraft_tpu.engine.host import EngineDriver, PayloadSlice  # noqa: E402
from multiraft_tpu.engine.kv import KVOp  # noqa: E402
from multiraft_tpu.engine.mesh import shard_arrays  # noqa: E402
from multiraft_tpu.porcupine.kv import OP_GET  # noqa: E402
from multiraft_tpu.sim.scheduler import TIMEOUT  # noqa: E402
from tests.test_frontier_sweep import describe  # noqa: E402

WIDTH = FrontierService.ORPHAN_SWEEP_ROWS
PLANES = ("role", "alive", "term", "base", "log_len")


def reference_orphans(svc) -> int:
    """``sweep_orphans`` as it was before it read rows: the whole state
    on the host, ``leader_of`` for every bound group."""
    if not svc.driver.payloads:
        return 0
    st = svc.driver.np_state()
    failed = 0
    last_cache = {}
    for (g, idx) in list(svc.driver.payloads.keys()):
        if g not in last_cache:
            p = svc.driver.leader_of(g)
            last_cache[g] = (
                None
                if p is None
                else int(st["base"][g, p] + st["log_len"][g, p])
            )
        last = last_cache[g]
        payload = svc.driver.payloads.get((g, idx))
        count = payload.count if isinstance(payload, PayloadSlice) else 1
        if (
            not svc.retain_payloads
            and idx + count - 1 <= svc.applied_upto[g]
        ):
            svc._on_evicted(svc.driver.payloads.pop((g, idx)))
            failed += 1
            continue
        if last is None:
            continue
        if idx > last:
            svc._on_evicted(svc.driver.payloads.pop((g, idx)))
            failed += 1
        elif idx + count - 1 > last:
            keep = last - idx + 1
            tail = PayloadSlice(payload.frame, payload.rows[keep:])
            payload.rows = payload.rows[:keep]
            svc._on_evicted(tail)
            failed += 1
    return failed


class Recording(FrontierService):
    def __init__(self, driver) -> None:
        super().__init__(driver)
        self.evicted = []

    def _on_evicted(self, payload):
        self.evicted.append(describe(payload))


# -- the drivers: one device, five replicas a group, a four-device mesh --------

KINDS = {"one-device": (3, 0), "p5": (5, 0), "mesh4": (3, 4)}
G = 2 * WIDTH + 48  # room for more bound groups than two gathers hold


@pytest.fixture(scope="module", params=list(KINDS), ids=list(KINDS))
def driver(request):
    replicas, mesh = KINDS[request.param]
    if len(jax.devices()) < mesh:
        pytest.skip(f"need {mesh} devices")
    return EngineDriver(
        EngineConfig(G=G, P=replicas, L=32, E=4, INGEST=4), seed=5,
        mesh=make_mesh(mesh) if mesh else None,
        check_zero_collectives=False,  # no tick runs here
    )


class Planes:
    """The five planes the sweep reads, as host arrays a case writes
    and :meth:`put` lays over the driver's state (sharded as the
    driver's is).  Every group starts with a live leader at replica 0
    in term 1 whose log ends at index 10."""

    def __init__(self, driver) -> None:
        self.driver = driver
        shape = (driver.cfg.G, driver.cfg.P)
        self.role = np.full(shape, FOLLOWER, np.int32)
        self.role[:, 0] = LEADER
        self.alive = np.ones(shape, bool)
        self.term = np.ones(shape, np.int32)
        self.base = np.zeros(shape, np.int32)
        self.log_len = np.full(shape, 10, np.int32)

    def leader(self, g, p, term, base, log_len, alive=True):
        self.role[g, p] = LEADER
        self.alive[g, p] = alive
        self.term[g, p] = term
        self.base[g, p] = base
        self.log_len[g, p] = log_len

    def put(self):
        d = self.driver
        state = d.state._replace(
            **{k: jnp.array(getattr(self, k), copy=True) for k in PLANES}
        )
        d.state = (
            shard_arrays(d.cfg, d.mesh, state) if d.mesh is not None else state
        )


def both(driver, payloads, applied=None, retain=False, sweeps=1):
    """Run the reference and the sweep ``sweeps`` times each over copies
    of ``payloads`` on ``driver``: what each returned, evicted (in
    order) and left bound must be equal; returned for the case's own
    assertions."""
    seen = []
    for sweep in (reference_orphans, FrontierService.sweep_orphans):
        svc = Recording(driver)
        svc.retain_payloads = retain
        if applied is not None:
            svc.applied_upto[:] = applied
        driver.payloads = copy.deepcopy(payloads)
        failed = [sweep(svc) for _ in range(sweeps)]
        left = [(k, describe(v)) for k, v in driver.payloads.items()]
        seen.append((failed, svc.evicted, left))
    driver.payloads = {}
    assert seen[0] == seen[1]
    return seen[1]


def rows(*ids):
    return np.array(ids, np.int32)


# -- the cases ------------------------------------------------------------------


def test_a_binding_past_the_leaders_log_end_is_evicted(driver):
    Planes(driver).put()
    failed, evicted, left = both(
        driver, {(3, 10): "at-end", (3, 11): "past", (4, 12): "far"}
    )
    assert failed == [2]
    assert evicted == ["past", "far"]
    assert left == [((3, 10), "at-end")]


def test_a_slice_wholly_past_the_log_end_is_evicted_whole(driver):
    Planes(driver).put()
    failed, evicted, left = both(
        driver,
        {(2, 11): PayloadSlice("f", rows(1, 2, 3)),
         (5, 8): PayloadSlice("g", rows(4, 5, 6))},  # 8..10: inside
    )
    assert failed == [1]
    assert evicted == [("slice", "f", [1, 2, 3])]
    assert left == [((5, 8), ("slice", "g", [4, 5, 6]))]


def test_a_straddling_slice_keeps_its_prefix_and_fails_its_tail_once(driver):
    Planes(driver).put()
    failed, evicted, left = both(
        driver, {(6, 9): PayloadSlice("f", rows(1, 2, 3, 4))}, sweeps=2
    )
    assert failed == [1, 0]  # the second sweep finds the prefix whole
    assert evicted == [("slice", "f", [3, 4])]
    assert left == [((6, 9), ("slice", "f", [1, 2]))]


@pytest.mark.parametrize("retain", [False, True], ids=["pop", "retain"])
def test_a_stale_binding_under_the_applied_frontier(driver, retain):
    planes = Planes(driver)
    planes.role[9] = FOLLOWER  # stale is stale with no leader too
    planes.put()
    applied = np.zeros(G, np.int64)
    applied[7] = 6
    applied[9] = 3
    payloads = {
        (7, 5): "stale", (7, 6): "stale-at-frontier", (7, 7): "live",
        (7, 3): PayloadSlice("f", rows(1, 2, 3)),  # 3..5: under it
        (7, 4): PayloadSlice("g", rows(4, 5, 6, 7)),  # 4..7: crosses it
        (9, 2): "stale-no-leader",
    }
    failed, evicted, left = both(driver, payloads, applied, retain=retain)
    if retain:
        # Split-group mode keeps applied payloads for resends.
        assert failed == [0] and evicted == []
        assert len(left) == len(payloads)
    else:
        assert failed == [4]
        assert evicted == [
            "stale", "stale-at-frontier", ("slice", "f", [1, 2, 3]),
            "stale-no-leader",
        ]
        assert [k for k, _ in left] == [(7, 7), (7, 4)]


def test_a_group_with_no_live_leader_is_left_alone(driver):
    planes = Planes(driver)
    planes.role[1] = FOLLOWER  # nobody leads
    planes.alive[2, 0] = False  # the leader is down
    planes.leader(3, 1, term=9, base=50, log_len=5, alive=False)
    planes.put()
    payloads = {(1, 99): "a", (2, 99): "b", (3, 99): "c", (4, 99): "gone"}
    failed, evicted, left = both(driver, payloads)
    # group 3: its higher-term leader is down, replica 0 still leads
    assert failed == [2] and evicted == ["c", "gone"]
    assert [k for k, _ in left] == [(1, 99), (2, 99)]
    planes.alive[3, 0] = False
    planes.put()
    failed, evicted, left = both(driver, payloads)
    assert failed == [1] and evicted == ["gone"]
    assert [k for k, _ in left] == [(1, 99), (2, 99), (3, 99)]


def test_of_two_live_leaders_the_higher_terms_log_end_decides(driver):
    planes = Planes(driver)
    P = driver.cfg.P
    # group 1: the stale leader (term 1, log to 10) sits first
    planes.leader(1, P - 1, term=4, base=20, log_len=3)
    # group 2: the stale leader sits last, with the longer log
    planes.leader(2, 0, term=7, base=0, log_len=6)
    planes.leader(2, P - 1, term=2, base=30, log_len=30)
    # group 3: the higher term's leader is down, so the other decides
    planes.leader(3, 1, term=8, base=40, log_len=2, alive=False)
    # group 4: equal terms (not Raft, but ``leader_of`` has an answer):
    # the lowest index
    planes.leader(4, 1, term=1, base=0, log_len=99)
    planes.put()
    assert [driver.leader_of(g) for g in (1, 2, 3, 4)] == [P - 1, 0, 0, 0]
    payloads = {
        (1, 11): "in-new-log", (1, 23): "new-end", (1, 24): "past-new",
        (2, 6): "end", (2, 7): "past-the-real-leader",
        (3, 10): "end", (3, 11): "past", (3, 42): "past",
        (4, 10): "end", (4, 11): "past",
    }
    failed, evicted, left = both(driver, payloads)
    assert failed == [5]
    assert evicted == [
        "past-new", "past-the-real-leader", "past", "past", "past",
    ]
    assert [k for k, _ in left] == [(1, 11), (1, 23), (2, 6), (3, 10), (4, 10)]


def test_more_bound_groups_than_one_gather_holds(driver):
    planes = Planes(driver)
    rng = np.random.default_rng(11)
    planes.log_len[:, 0] = rng.integers(0, 20, G)
    planes.base[:, 0] = rng.integers(0, 5, G)
    planes.role[rng.random(G) < 0.1] = FOLLOWER
    planes.put()
    bound = rng.permutation(G)[: 2 * WIDTH + 1].tolist()  # three gathers
    payloads = {}
    for g in bound:
        payloads[(g, int(rng.integers(1, 30)))] = f"p{g}"
        payloads[(g, int(rng.integers(1, 30)))] = f"q{g}"
    svc = Recording(driver)
    failed, evicted, left = both(driver, payloads)
    assert 0 < failed[0] < len(payloads)
    assert len(left) + failed[0] == len(payloads)
    # ... and what the device was asked is counted: whole gathers.
    m = driver.metrics
    before = dict(m.counters), m.hists["apply.orphan_s"].count
    driver.payloads = copy.deepcopy(payloads)
    svc.sweep_orphans()
    driver.payloads = {}
    assert m.counters["apply.orphan_sweeps"] - before[0]["apply.orphan_sweeps"] == 1
    assert m.counters["apply.orphan_rows"] - before[0]["apply.orphan_rows"] == 3 * WIDTH
    assert m.hists["apply.orphan_s"].count - before[1] == 1


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_seeded_mixes_agree_with_the_reference(driver, seed):
    """Everything at once: leaders anywhere, down, doubled; plain and
    slice bindings inside, across and past the log end and the applied
    frontier; several sweeps (a slice's surviving prefix is judged
    again by the next one)."""
    rng = np.random.default_rng(seed)
    P = driver.cfg.P
    planes = Planes(driver)
    planes.role[:] = np.where(rng.random((G, P)) < 0.4, LEADER, FOLLOWER)
    planes.alive[:] = rng.random((G, P)) < 0.8
    planes.term[:] = rng.integers(0, 6, (G, P))
    planes.base[:] = rng.integers(0, 8, (G, P))
    planes.log_len[:] = rng.integers(0, 12, (G, P))
    planes.put()
    applied = rng.integers(0, 6, G).astype(np.int64)
    payloads = {}
    for g in rng.choice(G, size=90, replace=False).tolist():
        for _ in range(int(rng.integers(1, 4))):
            idx = int(rng.integers(1, 24))
            if rng.random() < 0.4:
                n = int(rng.integers(1, 6))
                payloads[(g, idx)] = PayloadSlice(
                    f"f{g}", rng.integers(0, 1000, n).astype(np.int32)
                )
            else:
                payloads[(g, idx)] = f"p{g}.{idx}"
    for retain in (False, True):
        failed, evicted, _ = both(
            driver, payloads, applied, retain=retain, sweeps=3
        )
        assert sum(failed) == len(evicted) > 0


def test_nothing_bound_reads_nothing(driver, monkeypatch):
    def no_read(*a, **kw):
        raise AssertionError("an empty sweep read the device")

    monkeypatch.setattr(driver, "rows_stacked", no_read)
    sweeps = driver.metrics.counters.get("apply.orphan_sweeps", 0)
    assert Recording(driver).sweep_orphans() == 0
    assert driver.metrics.counters.get("apply.orphan_sweeps", 0) == sweeps


def test_the_stacked_gather_is_rows_of_in_one_array(driver):
    """``rows_stacked`` against ``rows_of``, ``np_state`` and
    ``leader_of`` row for row, at the sweep's width."""
    rng = np.random.default_rng(7)
    P = driver.cfg.P
    planes = Planes(driver)
    planes.role[:] = np.where(rng.random((G, P)) < 0.3, LEADER, FOLLOWER)
    planes.alive[:] = rng.random((G, P)) < 0.7
    planes.term[:] = rng.integers(0, 9, (G, P))
    planes.put()
    groups = rng.choice(G, size=WIDTH, replace=False)
    stacked = driver.rows_stacked(PLANES, groups)
    assert stacked.shape == (len(PLANES), WIDTH, P)
    assert stacked.dtype == np.int32
    by_name = driver.rows_of(PLANES, groups)
    st = driver.np_state()
    for k, name in enumerate(PLANES):
        assert (stacked[k] == by_name[name]).all()
        assert (stacked[k] == st[name][groups]).all()
    role, alive, term = stacked[:3]
    lead = (role == LEADER) & (alive != 0)
    best = np.where(lead, term, -1).argmax(axis=1)
    for i, g in enumerate(groups.tolist()):
        assert (int(best[i]) if lead[i].any() else None) == driver.leader_of(g)


# -- the served node: warmed before ``ready``, no whole-state copy on a pump -----


@pytest.fixture(params=[0, 4], ids=["one-device", "mesh4"])
def served(request, tmp_path, monkeypatch):
    """A durable ``serve-kv`` node, as the benchmark's cells run it
    (depth 1: without ``data_dir`` the pipeline runs two deep, and the
    first dispatch that finds a batch in flight compiles two small
    programs of its own, whenever the box is slow enough for one)."""
    mesh = request.param
    if len(jax.devices()) < mesh:
        pytest.skip(f"need {mesh} devices")
    monkeypatch.setenv("MRT_PUMP_IDLE_S", "0.005")
    node = serve_engine_kv(
        port=0, G=WIDTH + 8, data_dir=str(tmp_path), mesh_devices=mesh
    )
    assert node.engine_service.cycle.depth == 1
    try:
        yield node
    finally:
        node.sched.run_call(node.engine_service.stop, timeout=30)
        node.close()


@pytest.mark.timeout_s(240)
def test_no_sweep_compiles_once_the_node_is_ready(served):
    """``serve_engine_kv`` counts JAX's trace, lower and compile events
    (``/jax/core/compile*``, ``engine/instrument.py`` ``count_compiles``:
    what ``chipbench/server_child.py`` counts in a window).  Sweeps over
    1, 7, 9, 65 and one more bound group than a gather holds add none:
    the one program ran before ``ready``."""
    node = served
    kv = node.engine_service.kv
    driver = kv.driver
    m = node.obs.metrics
    failed = []

    def sweep(n):
        for g in range(n):  # far past any log end: each is evicted
            driver.payloads[(g, 10 ** 6)] = (KVOp(op=OP_GET, key=""), None)
        failed.append(kv.sweep_orphans())

    compiles = m.counters["engine.compiles"]
    rows_before = m.counters.get("apply.orphan_rows", 0)
    sizes = (1, 7, 9, 65, WIDTH + 1)
    for n in sizes:
        node.sched.run_call(lambda: sweep(n), timeout=60)
    assert failed == list(sizes)
    assert m.counters["apply.orphan_rows"] - rows_before == 6 * WIDTH
    assert m.counters["engine.compiles"] == compiles


@pytest.mark.timeout_s(240)
def test_no_pump_copies_the_whole_state(served, monkeypatch):
    node = served
    kv = node.engine_service.kv
    m = node.obs.metrics
    called = []

    def refuse(name):
        def method(self, *a, **kw):
            called.append(name)
            raise AssertionError(f"{name} on the serving loop")
        return method

    monkeypatch.setattr(EngineDriver, "np_state", refuse("np_state"))
    monkeypatch.setattr(EngineDriver, "leader_of", refuse("leader_of"))
    # A sweep at every pump's end, so that many meet a binding; and a
    # cycle one tick short of the commit path, so that a binding outlives
    # the pump that made it (at the served ticks a pump a write commits
    # and applies inside its own pump, and the sweep finds nothing bound).
    kv.ORPHAN_SWEEP_TICKS = 2
    node.sched.run_call(
        lambda: setattr(node.engine_service.cycle, "ticks", COMMIT_DEPTH)
    )
    client = RpcNode()
    try:
        end = client.client_end("127.0.0.1", node.port)
        clerks = [EngineClerk(client.sched, end) for _ in range(4)]

        def put_some(ck, who, n=12):
            for i in range(n):
                out = client.sched.wait(
                    client.sched.spawn(ck.put(f"k{who}.{i % 5}", f"v{i}")), 30.0
                )
                assert out is not TIMEOUT

        # The first loaded dispatch compiles its own small programs (a
        # benchmark run pays them in its load phase): not under test.
        put_some(clerks[0], "warm", n=2)
        sweeps = m.counters.get("apply.orphan_sweeps", 0)
        compiles = m.counters["engine.compiles"]
        writers = [
            threading.Thread(target=put_some, args=(ck, who))
            for who, ck in enumerate(clerks)
        ]
        for w in writers:
            w.start()
        for w in writers:
            w.join(120.0)
            assert not w.is_alive()
    finally:
        client.close()
    assert called == []
    assert m.counters["apply.orphan_sweeps"] > sweeps  # sweeps read the device
    assert m.hists["apply.orphan_s"].count == m.counters["apply.orphan_sweeps"]
    assert m.counters["engine.compiles"] == compiles
