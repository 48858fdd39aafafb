"""How a parked handler of an engine service waits: on the end of a
pump cycle (``PumpCycle.wait``, distributed/pump_cycle.py), bounded by
its resubmit and RPC deadlines — not on a timer every 2 ms.

Everything a parked request waits for (its ticket or frame resolving or
failing, the WAL's synced frontier) changes at the end of a pump cycle
and nowhere else, so a handler steps once per pump end it spans and
costs the serving loop nothing in between.  ``kv.wait_steps`` counts
the resumptions of a parked ``command`` update, ``kv.wait_timeouts``
those a deadline caused; ``batch`` and ``firehose`` park on the same
wait and count nothing there.

Most tests here run a service on the sim ``Scheduler`` with the
synchronous pump and ARE the pump (``cycle._pump_sync`` is called where
the test says a cycle ends), so "at that pump end and never before" is
exact and the deadline tests take no wall seconds.  They run against
both services: the plain one and the sharded one compose the same cycle.
"""

from __future__ import annotations

import threading
import types

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from multiraft_tpu.distributed.engine_durability import (  # noqa: E402
    EngineDurability,
)
from multiraft_tpu.distributed.engine_server import (  # noqa: E402
    EngineClerk,
    EngineKVService,
    EngineShardKVService,
    EngineShardNetClerk,
    serve_engine_kv,
    serve_engine_shardkv,
)
from multiraft_tpu.distributed.engine_wire import (  # noqa: E402
    ERR_TIMEOUT,
    OK,
    EngineCmdArgs,
    route_group,
)
from multiraft_tpu.distributed.observe import Observability  # noqa: E402
from multiraft_tpu.distributed.pump_cycle import SERVED_TICKS  # noqa: E402
from multiraft_tpu.distributed.tcp import RpcNode  # noqa: E402
from multiraft_tpu.engine.core import COMMIT_DEPTH, EngineConfig  # noqa: E402
from multiraft_tpu.engine.firehose import (  # noqa: E402
    FH_OK,
    FH_RETRY,
    FH_TIMEOUT,
    pack_request,
    unpack_reply,
)
from multiraft_tpu.engine.host import EngineDriver  # noqa: E402
from multiraft_tpu.engine.kv import BatchedKV  # noqa: E402
from multiraft_tpu.engine.shardkv import SERVING, BatchedShardKV  # noqa: E402
from multiraft_tpu.sim.scheduler import TIMEOUT, Scheduler  # noqa: E402

G = 4
GID = 1  # the sharded rigs join one gid: it serves every shard
CYCLE_S = 0.012  # what the tests let pass between two pump ends
HANDLERS = ("command", "batch", "firehose")
# Ticks a pump one short of the commit path: a cycle that ends before
# the write it ingested commits, so a handler parks across a pump end.
SHORT = COMMIT_DEPTH


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


class _Rig:
    """One service on virtual time whose own pump loop is stopped; the
    test runs the cycles.  ``kind`` picks the service, and the methods
    hide what differs between the two engines under them.  ``ticks``:
    the cycle's ticks a pump (the services' default, ``SERVED_TICKS``,
    commits a write inside the pump that ingests it; ``SHORT`` ends a
    pump before it commits).  ``data_dir``: a real ``EngineDurability``
    there in place of ``durability``."""

    def __init__(self, kind, durability=None, ticks=SERVED_TICKS,
                 data_dir=None):
        self.kind = kind
        self.sched = sched = Scheduler()
        d = EngineDriver(EngineConfig(G=G, P=3, L=32, E=4, INGEST=4), seed=3)
        assert d.run_until_quiet_leaders(2000)
        obs = Observability()
        d.metrics = obs.metrics  # one registry, as a served node has
        if kind == "kv":
            self.engine = BatchedKV(d)
            if data_dir is not None:
                durability = EngineDurability(
                    data_dir, d, self.engine, metrics=obs.metrics
                )
            self.svc = EngineKVService(
                sched, self.engine, durability=durability, obs=obs,
                ticks_per_pump=ticks,
            )
        else:
            self.engine = skv = BatchedShardKV(d)
            skv.admin_sync("join", [GID])
            for _ in range(200):  # until GID serves config 1
                if skv.reps[GID].cur.num == 1 and all(
                    sh.state == SERVING
                    for sh in skv.reps[GID].shards.values()
                ):
                    break
                skv.pump(2)
            else:
                raise AssertionError("the join did not settle")
            if data_dir is not None:
                durability = EngineDurability(
                    data_dir, d, skv, metrics=obs.metrics
                )
            self.svc = EngineShardKVService(
                sched, skv, durability=durability, obs=obs,
                ticks_per_pump=ticks,
            )
        self.svc.stop()
        sched.run_for(0)  # the constructor's pump timer finds it stopped
        self.counters = self.svc.m.counters
        self.deadline_s = type(self.svc).DEADLINE_S

    def pump(self):
        self.svc.cycle._pump_sync()

    def call(self, handler, i, op="Put"):
        """Spawn one single-write request's handler as the RPC
        dispatcher does; it has submitted and parked when this
        returns."""
        args = EngineCmdArgs(
            op=op, key=f"key{i}", value=f"v{i}", client_id=100 + i,
            command_id=1,
        )
        if handler == "command":
            gen = self.svc.command(args)
        elif handler == "batch":
            gen = self.svc.batch([args])
        else:
            group = GID if self.kind == "shardkv" else route_group(args.key, G)
            gen = self.svc.firehose(pack_request(
                np.asarray([1], np.uint8), np.asarray([group], np.uint32),
                np.asarray([args.client_id], np.uint64),
                np.asarray([args.command_id], np.uint64),
                [args.key.encode()], [args.value.encode()],
            ))
        fut = self.sched.spawn(gen)
        self.sched.run_for(0)
        return fut

    @staticmethod
    def outcome(handler, reply):
        """The one write's answer, in ``command``'s vocabulary."""
        if handler == "command":
            return reply.err
        if handler == "batch":
            return reply[0].err
        code = int(unpack_reply(reply)[0][0])
        # RETRY: applied, not synced by the deadline; TIMEOUT: the row
        # never resolved.  Either way not a durable ack.
        return {FH_OK: OK, FH_RETRY: ERR_TIMEOUT, FH_TIMEOUT: ERR_TIMEOUT}[code]

    def read(self, key):
        if self.kind == "kv":
            return self.engine.get(route_group(key, G), key).value
        return self.engine.get_fast(key).value

    def evict(self, ticket):
        """What the sweep does to a ticket whose log slot a leader
        change overwrote."""
        if self.kind == "kv":
            self.engine._on_evicted((None, ticket))
        else:
            self.engine._on_evicted(types.SimpleNamespace(ticket=ticket))

    def pump_until(self, futs, cap=60):
        """Run pump cycles back to back (no virtual time passes) until
        every reply has left; the number of cycles it took."""
        pumps = 0
        while not all(f.done for f in futs):
            self.pump()
            pumps += 1
            assert pumps < cap, "the writes did not commit"
        return pumps

    def pump_until_logged(self, dur, cap=60):
        """Run pump cycles until the write is applied and in the (stub)
        WAL."""
        while dur.seq == 0:
            self.pump()
            cap -= 1
            assert cap, "the write did not commit"

    def capture_submits(self):
        """[(virtual time, ticket)] of every ``submit`` from here on."""
        seen = []
        inner = self.engine.submit

        def submit(*a, **kw):
            t = inner(*a, **kw)
            seen.append((self.sched.now, t))
            return t

        self.engine.submit = submit
        return seen


@pytest.fixture(params=["kv", "shardkv"])
def sim(request, monkeypatch):
    """``make(**kw) -> _Rig`` for the service under test."""
    monkeypatch.setenv("MRT_ENGINE_PIPELINE", "0")

    def make(**kw):
        rig = _Rig(request.param, **kw)
        assert rig.svc.cycle.pipe is None  # the whole cycle inline
        return rig

    return make


# -- (a) one step per pump end, nothing in between ---------------------------


def test_a_parked_write_steps_once_per_pump_end_it_spans(sim):
    rig = sim()
    n = 12
    futs = [rig.call("command", i) for i in range(n)]
    pumps = rig.pump_until(futs)
    assert [f.value.err for f in futs] == [OK] * n
    c = rig.counters
    assert c["kv.writes"] == n
    # every handler parked at least once, and none stepped more often
    # than pump cycles ended while it waited
    assert n <= c["kv.wait_steps"] <= n * pumps
    assert c["kv.wait_steps"] / c["kv.writes"] <= pumps + 2
    assert c["kv.wait_timeouts"] == 0 and c["kv.resubmits"] == 0


def _events_between_two_pump_ends(make, parked):
    rig = make(ticks=SHORT)
    futs = [rig.call("command", i) for i in range(parked)]
    rig.pump()  # first pump end: nothing has committed yet
    assert not any(f.done for f in futs)
    before = rig.sched.fired_events
    rig.sched.run_for(CYCLE_S)
    assert not any(f.done for f in futs)
    rig.pump()
    steps = rig.counters["kv.wait_steps"]
    assert steps == 2 * parked  # one step a pump end each, taken inline
    return rig.sched.fired_events - before


def test_a_events_between_pump_ends_do_not_grow_with_parked_updates(sim):
    one = _events_between_two_pump_ends(sim, 1)
    many = _events_between_two_pump_ends(sim, 24)
    # the pump's own timer, and no timer of any handler
    assert one == many <= 2


@pytest.mark.parametrize("handler", HANDLERS)
def test_a_every_handler_wakes_at_a_pump_end_and_not_between(sim, handler):
    rig = sim(ticks=SHORT)
    fut = rig.call(handler, 0)
    rig.pump()  # first pump end: nothing has committed yet
    assert not fut.done
    before = rig.sched.fired_events
    rig.sched.run_for(10 * CYCLE_S)
    # the pump's own timer, and no timer of the handler's
    assert rig.sched.fired_events - before <= 1 and not fut.done
    pumps = rig.pump_until([fut])
    assert rig.outcome(handler, fut.value) == OK
    assert rig.read("key0") == "v0"
    c = rig.counters
    if handler == "command":
        assert 1 <= c["kv.wait_steps"] <= pumps + 1
    else:  # handler.steps_per_update divides by kv.writes: command's alone
        assert c["kv.wait_steps"] == 0 and c["kv.writes"] == 0
    assert c["kv.wait_timeouts"] == 0


@pytest.fixture(params=["kv", "shardkv"])
def served(request, tmp_path, monkeypatch):
    """A durable ``serve-kv`` / ``serve-shardkv`` node in this process
    (IoScheduler loop, pump thread, WAL), one pump cadence busy or
    not; ``(node, client, clerk class)``."""
    monkeypatch.setenv("MRT_PUMP_IDLE_S", str(CYCLE_S))
    monkeypatch.setenv("MRT_PUMP_HOT", "0")
    if request.param == "kv":
        node = serve_engine_kv(port=0, G=G, data_dir=str(tmp_path))
        clerk = EngineClerk
    else:
        node = serve_engine_shardkv(
            port=0, G=G, join_gids=[GID], data_dir=str(tmp_path)
        )
        clerk = EngineShardNetClerk
    client = RpcNode()
    try:
        yield node, client, clerk
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
    node, client, clerk = served
    svc = node.engine_service
    names = ("kv.writes", "kv.wait_steps", "kv.wait_timeouts", "pump.count")

    def read():
        return {k: svc.m.counters[k] for k in names}

    end = client.client_end("127.0.0.1", node.port)
    ck = clerk(client.sched, end)
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
        mine = clerk(client.sched, client.client_end(
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


@pytest.mark.parametrize("handler", HANDLERS)
def test_b_reply_leaves_at_the_pump_end_that_finds_the_record_synced(
    sim, handler
):
    dur = _Dur()
    rig = sim(durability=dur)
    fut = rig.call(handler, 0)
    rig.pump_until_logged(dur)
    assert rig.svc._write_seqs == {(100, 1): 1}
    for _ in range(3):  # applied, not fsynced: no ack, pump after pump
        rig.pump()
        rig.sched.run_for(CYCLE_S)
        assert not fut.done
    dur.is_synced = True  # between two pumps (the state plane's gate can)
    rig.sched.run_for(10 * CYCLE_S)
    assert not fut.done  # nothing looks before a pump ends
    before = rig.counters["kv.wait_steps"]
    rig.pump()
    assert fut.done and rig.outcome(handler, fut.value) == OK
    assert rig.counters["kv.wait_steps"] == before + (handler == "command")
    assert rig.counters["kv.wait_timeouts"] == 0
    assert rig.svc._write_seqs == {}  # pruned once synced


@pytest.mark.parametrize("handler", HANDLERS)
def test_b_an_applied_write_that_never_syncs_answers_timeout_not_ok(
    sim, handler
):
    """Every handler of both services: the sharded ``command``'s gate
    had no deadline before it parked on the cycle's wait, and a WAL that
    never synced held its handler for ever."""
    dur = _Dur()
    rig = sim(durability=dur)
    fut = rig.call(handler, 0)
    rig.pump_until_logged(dur)
    for _ in range(5):  # pumps end, the record stays unsynced
        rig.pump()
        rig.sched.run_for(0.5)
        assert not fut.done
    # no pump ends any more either: the RPC deadline still answers
    assert rig.outcome(handler, rig.sched.run_until(fut)) == ERR_TIMEOUT
    assert rig.sched.now == pytest.approx(rig.deadline_s)
    assert rig.counters["kv.wait_timeouts"] == (handler == "command")
    assert rig.read("key0") == "v0"


# -- (c) a failed ticket -----------------------------------------------------


def test_c_evicted_ticket_is_resubmitted_at_the_pump_end_that_failed_it(sim):
    rig = sim(ticks=SHORT)
    submits = rig.capture_submits()
    fut = rig.call("command", 0, op="Append")
    assert len(submits) == 1
    rig.pump()
    assert not fut.done and len(submits) == 1
    # A leader change overwrites the binding: the sweep of the next pump
    # would call this hook from after_step.
    rig.evict(submits[0][1])
    assert submits[0][1].failed
    rig.sched.run_for(10 * CYCLE_S)
    assert len(submits) == 1  # nothing looks before a pump ends
    rig.pump()
    assert len(submits) == 2
    assert submits[1][0] == submits[0][0] + 10 * CYCLE_S  # not at RESUBMIT_S
    rig.pump_until([fut])
    assert fut.value.err == OK
    assert len(submits) == 2
    if rig.kind == "kv":  # the sharded service does not count them
        assert rig.counters["kv.resubmits"] == 1
    assert rig.counters["kv.wait_timeouts"] == 0
    # the first incarnation committed too: dedup applied the append once
    assert rig.read("key0") == "v0"


# -- (d) no pump ends: the deadlines still fire ------------------------------


def test_d_stalled_pump_resubmits_at_resubmit_s_and_times_out_at_deadline(sim):
    rig = sim()
    submits = rig.capture_submits()
    fut = rig.call("command", 0)
    before = rig.sched.fired_events
    reply = rig.sched.run_until(fut)  # virtual time: no pump ever ends
    assert reply.err == ERR_TIMEOUT
    assert rig.sched.now == pytest.approx(rig.deadline_s)
    resubmit = rig.svc.RESUBMIT_S
    whole = int(round(rig.deadline_s / resubmit))
    assert [t for t, _ in submits] == pytest.approx(
        [i * resubmit for i in range(whole)]
    )
    c = rig.counters
    if rig.kind == "kv":
        assert c["kv.resubmits"] == whole
    # every resumption was a deadline's; a rounding of the clock may add
    # one of no length, and nothing fired every 2 ms
    assert whole <= c["kv.wait_timeouts"] == c["kv.wait_steps"] <= 2 * whole
    assert rig.sched.fired_events - before <= 2 * whole + 2


@pytest.mark.parametrize("handler", ("batch", "firehose"))
def test_d_stalled_pump_answers_a_frame_at_its_deadline_in_one_step(
    sim, handler
):
    rig = sim()
    fut = rig.call(handler, 0)
    before = rig.sched.fired_events
    reply = rig.sched.run_until(fut)  # virtual time: no pump ever ends
    assert rig.outcome(handler, reply) == ERR_TIMEOUT
    assert rig.sched.now == pytest.approx(rig.deadline_s)
    # the deadline's own timer (a rounding of the clock may add one of
    # no length), and nothing every 2 ms
    assert rig.sched.fired_events - before <= 3
    assert rig.counters["kv.wait_steps"] == 0


def test_d_deadline_wakes_only_the_handler_it_belongs_to(sim):
    rig = sim()
    submits = rig.capture_submits()
    first = rig.call("command", 0)
    rig.sched.run_for(0.2)
    second = rig.call("command", 1)
    assert len(submits) == 2
    rig.sched.run_for(0.1)  # 0.3: the first has resubmitted, the second not
    assert [round(t, 6) for t, _ in submits] == [0.0, 0.2, 0.25]
    assert rig.counters["kv.wait_timeouts"] == 1
    pumps = rig.pump_until([first, second])
    assert first.value.err == OK and second.value.err == OK
    assert rig.counters["kv.wait_steps"] <= 1 + 2 * pumps


# -- (e) the served ticks a pump: a write commits inside the pump -----------


@pytest.fixture(params=["kv", "shardkv"])
def durable(request, tmp_path, monkeypatch):
    """A ``_Rig`` at the services' default ticks a pump with a real WAL
    in ``tmp_path``, its pump fused as the served one is (the pipeline
    on; the test still runs the cycles inline)."""
    monkeypatch.delenv("MRT_ENGINE_PIPELINE", raising=False)
    rig = _Rig(request.param, data_dir=str(tmp_path))
    assert rig.svc.cycle.ticks == SERVED_TICKS
    assert rig.engine.driver.fused_eligible()
    return rig


def test_e_an_update_is_acknowledged_at_the_end_of_the_first_pump_that_carries_it(
    durable,
):
    rig = durable
    c = rig.counters
    before = dict(c)  # the rig's own set-up pumped too (the join)
    waves, per_wave = 6, 4  # a wave fits one tick's ingest (INGEST=4)
    for w in range(waves):
        futs = [rig.call("command", per_wave * w + i) for i in range(per_wave)]
        assert not any(f.done for f in futs)  # parked on an idle pipeline
        rig.pump()
        assert all(f.done for f in futs), w
        assert [f.value.err for f in futs] == [OK] * per_wave
    assert rig.read("key0") == "v0"
    assert c["kv.writes"] == waves * per_wave
    assert c["kv.wait_steps"] / c["kv.writes"] <= 2
    assert c["kv.wait_timeouts"] == 0
    # the entries ingested were committed by their own batch's last tick
    ingested = c["pump.ingested"] - before.get("pump.ingested", 0)
    done = c["pump.committed_in_batch"] - before.get("pump.committed_in_batch", 0)
    assert ingested >= waves * per_wave
    assert done >= 0.9 * ingested
