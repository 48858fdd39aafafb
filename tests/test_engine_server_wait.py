"""How a parked ``EngineKV.command`` handler waits: on the end of a
pump cycle (``EngineKVService._cycle_end``), bounded by its resubmit
and RPC deadlines — not on a timer every 2 ms.

Everything a parked update waits for (its ticket resolving or failing,
the WAL's synced frontier) changes at the end of a pump cycle and
nowhere else, so a handler steps once per pump end it spans and costs
the serving loop nothing in between.  ``kv.wait_steps`` counts the
resumptions, ``kv.wait_timeouts`` those a deadline caused.

Most tests here run the service on the sim ``Scheduler`` with the
synchronous pump and ARE the pump (``_pump_sync`` is called where the
test says a cycle ends), so "at that pump end and never before" is
exact and the deadline tests take no wall seconds.
"""

from __future__ import annotations

import threading

import pytest

jax = pytest.importorskip("jax")

from multiraft_tpu.distributed.engine_server import (  # noqa: E402
    EngineClerk,
    EngineKVService,
    serve_engine_kv,
)
from multiraft_tpu.distributed.engine_wire import (  # noqa: E402
    ERR_TIMEOUT,
    OK,
    EngineCmdArgs,
    route_group,
)
from multiraft_tpu.distributed.observe import Observability  # noqa: E402
from multiraft_tpu.distributed.tcp import RpcNode  # noqa: E402
from multiraft_tpu.engine.core import EngineConfig  # noqa: E402
from multiraft_tpu.engine.host import EngineDriver  # noqa: E402
from multiraft_tpu.engine.kv import BatchedKV  # noqa: E402
from multiraft_tpu.sim.scheduler import TIMEOUT, Scheduler  # noqa: E402

G = 4
CYCLE_S = 0.012  # what the tests let pass between two pump ends


class _Dur:
    """Durability stub: hands out WAL seqs, and ``synced`` says what the
    test last set — the state-plane's extra gate can turn true between
    two pumps, so the flag is not tied to ``after_pump``."""

    def __init__(self):
        self.seq = 0
        self.is_synced = False

    def log(self, record):
        self.seq += 1
        return self.seq

    def synced(self, seq):
        return self.is_synced

    def after_pump(self):
        pass


@pytest.fixture
def sim(monkeypatch):
    """``make(durability=None) -> (sched, svc)``: a service on virtual
    time whose own pump loop is stopped; the test runs the cycles."""
    monkeypatch.setenv("MRT_ENGINE_PIPELINE", "0")  # the whole cycle inline

    def make(durability=None):
        sched = Scheduler()
        d = EngineDriver(EngineConfig(G=G, P=3, L=32, E=4, INGEST=4), seed=3)
        assert d.run_until_quiet_leaders(2000)
        svc = EngineKVService(
            sched, BatchedKV(d), durability=durability, obs=Observability()
        )
        assert svc._pipe is None
        svc.stop()
        sched.run_for(0)  # the constructor's pump timer finds it stopped
        return sched, svc

    return make


def _write(sched, svc, i, op="Put"):
    """Spawn one update's handler as the RPC dispatcher does; it has
    submitted and parked when this returns."""
    fut = sched.spawn(svc.command(EngineCmdArgs(
        op=op, key=f"key{i}", value=f"v{i}", client_id=100 + i, command_id=1,
    )))
    sched.run_for(0)
    return fut


def _pump_until(svc, futs, cap=60):
    """Run pump cycles back to back (no virtual time passes) until every
    reply has left; the number of cycles it took."""
    pumps = 0
    while not all(f.done for f in futs):
        svc._pump_sync()
        pumps += 1
        assert pumps < cap, "the writes did not commit"
    return pumps


def _pump_until_logged(svc, dur, cap=60):
    """Run pump cycles until the write is applied and in the (stub) WAL."""
    while dur.seq == 0:
        svc._pump_sync()
        cap -= 1
        assert cap, "the write did not commit"


def _capture_submits(sched, svc):
    """[(virtual time, ticket)] of every ``kv.submit`` from here on."""
    seen = []
    inner = svc.kv.submit

    def submit(g, op):
        t = inner(g, op)
        seen.append((sched.now, t))
        return t

    svc.kv.submit = submit
    return seen


# -- (a) one step per pump end, nothing in between ---------------------------


def test_a_parked_write_steps_once_per_pump_end_it_spans(sim):
    sched, svc = sim()
    n = 12
    futs = [_write(sched, svc, i) for i in range(n)]
    pumps = _pump_until(svc, futs)
    assert [f.value.err for f in futs] == [OK] * n
    c = svc.m.counters
    assert c["kv.writes"] == n
    # every handler parked at least once, and none stepped more often
    # than pump cycles ended while it waited
    assert n <= c["kv.wait_steps"] <= n * pumps
    assert c["kv.wait_steps"] / c["kv.writes"] <= pumps + 2
    assert c["kv.wait_timeouts"] == 0 and c["kv.resubmits"] == 0


def _events_between_two_pump_ends(make, parked):
    sched, svc = make()
    futs = [_write(sched, svc, i) for i in range(parked)]
    svc._pump_sync()  # first pump end: nothing has committed yet
    assert not any(f.done for f in futs)
    before = sched.fired_events
    sched.run_for(CYCLE_S)
    assert not any(f.done for f in futs)
    svc._pump_sync()
    steps = svc.m.counters["kv.wait_steps"]
    assert steps == 2 * parked  # one step a pump end each, taken inline
    return sched.fired_events - before


def test_a_events_between_pump_ends_do_not_grow_with_parked_updates(sim):
    one = _events_between_two_pump_ends(sim, 1)
    many = _events_between_two_pump_ends(sim, 24)
    # the pump's own timer, and no timer of any handler
    assert one == many <= 2


@pytest.fixture
def served(tmp_path, monkeypatch):
    """A durable ``serve-kv`` node in this process (IoScheduler loop,
    pump thread, WAL), one pump cadence busy or not."""
    monkeypatch.setenv("MRT_PUMP_IDLE_S", str(CYCLE_S))
    monkeypatch.setenv("MRT_PUMP_HOT", "0")
    node = serve_engine_kv(port=0, G=G, data_dir=str(tmp_path))
    client = RpcNode()
    try:
        yield node, client
    finally:
        client.close()
        node.sched.run_call(node.engine_service.stop, timeout=30)
        node.close()


def _put(client, ck, key, value):
    out = client.sched.wait(client.sched.spawn(ck.put(key, value)), 30.0)
    assert out is not TIMEOUT


@pytest.mark.timeout_s(240)
def test_a_served_writes_step_at_pump_ends_only(served):
    """Over real sockets, on the real loop: a write's handler steps at
    most (pump cycles between two readings around it + 2) times, and
    eight concurrent writers step at most eight times a pump end."""
    node, client = served
    svc = node.engine_service
    names = ("kv.writes", "kv.wait_steps", "kv.wait_timeouts", "pump.count")

    def read():
        return {k: svc.m.counters[k] for k in names}

    end = client.client_end("127.0.0.1", node.port)
    ck = EngineClerk(client.sched, end)
    for i in range(8):
        a = node.sched.run_call(read)
        _put(client, ck, f"solo{i}", "v")
        b = node.sched.run_call(read)
        assert b["kv.writes"] - a["kv.writes"] == 1
        steps = b["kv.wait_steps"] - a["kv.wait_steps"]
        assert 1 <= steps <= b["pump.count"] - a["pump.count"] + 2, (a, b)

    writers = 8
    a = node.sched.run_call(read)

    def run(w):
        mine = EngineClerk(client.sched, client.client_end(
            "127.0.0.1", node.port))
        for i in range(6):
            _put(client, mine, f"w{w}-{i}", "v")

    threads = [threading.Thread(target=run, args=(w,)) for w in range(writers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120.0)
        assert not t.is_alive()
    b = node.sched.run_call(read)
    d = {k: b[k] - a[k] for k in names}
    assert d["kv.writes"] >= writers * 6
    woken_by_pump = d["kv.wait_steps"] - d["kv.wait_timeouts"]
    assert d["kv.writes"] <= woken_by_pump <= writers * d["pump.count"], d
    # ... and the scrape carries both counters, where kv.writes is
    snap = client.sched.wait(end.call("Obs.snapshot", None), 30.0)
    assert snap["metrics"]["kv.wait_steps"] >= b["kv.wait_steps"]
    assert snap["metrics"].get("kv.wait_timeouts", 0) >= b["kv.wait_timeouts"]


# -- (b) the fsync gate ------------------------------------------------------


def test_b_reply_leaves_at_the_pump_end_that_finds_the_record_synced(sim):
    dur = _Dur()
    sched, svc = sim(dur)
    fut = _write(sched, svc, 0)
    _pump_until_logged(svc, dur)
    assert svc._write_seqs == {(100, 1): 1}
    for _ in range(3):  # applied, not fsynced: no ack, pump after pump
        svc._pump_sync()
        sched.run_for(CYCLE_S)
        assert not fut.done
    dur.is_synced = True  # between two pumps (the state plane's gate can)
    sched.run_for(10 * CYCLE_S)
    assert not fut.done  # nothing looks before a pump ends
    before = svc.m.counters["kv.wait_steps"]
    svc._pump_sync()
    assert fut.done and fut.value.err == OK
    assert svc.m.counters["kv.wait_steps"] == before + 1
    assert svc.m.counters["kv.wait_timeouts"] == 0
    assert svc._write_seqs == {}  # pruned once synced


def test_b_an_applied_write_that_never_syncs_answers_timeout_not_ok(sim):
    dur = _Dur()
    sched, svc = sim(dur)
    fut = _write(sched, svc, 0)
    _pump_until_logged(svc, dur)
    for _ in range(5):  # pumps end, the record stays unsynced
        svc._pump_sync()
        sched.run_for(0.5)
        assert not fut.done
    # no pump ends any more either: the RPC deadline still answers
    assert sched.run_until(fut).err == ERR_TIMEOUT
    assert sched.now == pytest.approx(EngineKVService.DEADLINE_S)
    assert svc.m.counters["kv.wait_timeouts"] == 1
    assert svc.kv.get(route_group("key0", G), "key0").value == "v0"


# -- (c) a failed ticket -----------------------------------------------------


def test_c_evicted_ticket_is_resubmitted_at_the_pump_end_that_failed_it(sim):
    sched, svc = sim()
    submits = _capture_submits(sched, svc)
    fut = _write(sched, svc, 0, op="Append")
    assert len(submits) == 1
    svc._pump_sync()
    assert not fut.done and len(submits) == 1
    # A leader change overwrites the binding: the sweep of the next pump
    # would call this hook from after_step.
    svc.kv._on_evicted((None, submits[0][1]))
    assert submits[0][1].failed
    sched.run_for(10 * CYCLE_S)
    assert len(submits) == 1  # nothing looks before a pump ends
    svc._pump_sync()
    assert len(submits) == 2 and svc.m.counters["kv.resubmits"] == 1
    assert submits[1][0] == submits[0][0] + 10 * CYCLE_S  # not at RESUBMIT_S
    _pump_until(svc, [fut])
    assert fut.value.err == OK
    assert svc.m.counters["kv.resubmits"] == 1
    assert svc.m.counters["kv.wait_timeouts"] == 0
    # the first incarnation committed too: dedup applied the append once
    assert svc.kv.get(route_group("key0", G), "key0").value == "v0"


# -- (d) no pump ends: the deadlines still fire ------------------------------


def test_d_stalled_pump_resubmits_at_resubmit_s_and_times_out_at_deadline(sim):
    sched, svc = sim()
    submits = _capture_submits(sched, svc)
    fut = _write(sched, svc, 0)
    before = sched.fired_events
    reply = sched.run_until(fut)  # virtual time: no pump ever ends
    assert reply.err == ERR_TIMEOUT
    assert sched.now == pytest.approx(EngineKVService.DEADLINE_S)
    resubmit, deadline = svc.RESUBMIT_S, svc.DEADLINE_S
    whole = int(round(deadline / resubmit))
    assert [t for t, _ in submits] == pytest.approx(
        [i * resubmit for i in range(whole)]
    )
    c = svc.m.counters
    assert c["kv.resubmits"] == whole
    # every resumption was a deadline's; a rounding of the clock may add
    # one of no length, and nothing fired every 2 ms
    assert whole <= c["kv.wait_timeouts"] == c["kv.wait_steps"] <= 2 * whole
    assert sched.fired_events - before <= 2 * whole + 2


def test_d_deadline_wakes_only_the_handler_it_belongs_to(sim):
    sched, svc = sim()
    submits = _capture_submits(sched, svc)
    first = _write(sched, svc, 0)
    sched.run_for(0.2)
    second = _write(sched, svc, 1)
    assert len(submits) == 2
    sched.run_for(0.1)  # 0.3: the first has resubmitted, the second not
    assert [round(t, 6) for t, _ in submits] == [0.0, 0.2, 0.25]
    assert svc.m.counters["kv.wait_timeouts"] == 1
    pumps = _pump_until(svc, [first, second])
    assert first.value.err == OK and second.value.err == OK
    assert svc.m.counters["kv.wait_steps"] <= 1 + 2 * pumps
