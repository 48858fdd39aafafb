"""The open loop against a stub ``EngineKV`` in this process (a register
a key, over the program's own node, wire and clerks): the three stamps,
the record's layout, latency from due under a stall, the pool's limit,
and ``check.py`` reading the record as it reads the closed loop's."""

import math
import time

import numpy as np
import pytest

import check
import traffic
from loadgen import ClosedLoop, OpenLoop

CFG = {"recordcount": 200, "fieldcount": 10, "fieldlength": 100}
MIX = {"rate_ops_s": 400, "read": 0.5, "update": 0.5, "distribution": "zipfian", "theta": 0.99}
SEED = 2 ** 31 + 17


class StubKV:
    """Answers ``command`` from a dict on the node's loop.  ``stall``:
    (from, to) on ``time.perf_counter``: a request that arrives inside it
    is answered when it ends.  ``service_s``: every request takes this long."""

    def __init__(self, records):
        self.data = {k: records.value(traffic.LOADER, i) for i, k in enumerate(records.keys)}
        self.stall, self.service_s = (0.0, 0.0), 0.0

    def command(self, args):
        from multiraft_tpu.distributed.engine_wire import EngineCmdReply

        now = time.perf_counter()
        if self.stall[0] <= now < self.stall[1]:
            yield self.stall[1] - now
        if self.service_s:
            yield self.service_s
        if args.op == "Put":
            self.data[args.key] = args.value
            return EngineCmdReply(value="")
        return EngineCmdReply(value=self.data[args.key])


@pytest.fixture
def rig():
    from multiraft_tpu.distributed.tcp import RpcNode

    records = traffic.Records(CFG, SEED)
    server, client = RpcNode(listen=True), RpcNode()
    stub = StubKV(records)
    server.add_service("EngineKV", stub)
    server.add_service("OtherKV", stub)    # a service by another name (the configuration's "service")
    yield records, stub, client, client.client_end("127.0.0.1", server.port)
    client.close()
    server.close()


def open_loop(records, client, end, seconds, sessions, mix=MIX, service="EngineKV"):
    due_s = traffic.arrivals(mix, SEED, seconds)
    cols = math.ceil(len(due_s) / OpenLoop.ROWS)
    upd, key = traffic.sequences(mix, records, SEED, cols, clients=OpenLoop.ROWS)
    keep = records.key_of_rank[:20]
    return OpenLoop(client, end, records, due_s, upd, key, keep, sessions, service), keep


def drive(loop, seconds):
    t0 = time.perf_counter()
    loop.start()
    time.sleep(seconds)
    loop.stop(5.0)
    return t0, t0 + seconds


def test_three_stamps_layout_and_the_check_reads_the_record(rig):
    records, _stub, client, end = rig
    t_loaded = time.perf_counter()
    loop, keep = open_loop(records, client, end, 1.5, sessions=64)
    t0, t1 = drive(loop, 1.6)
    rec, n, rows = loop.rec, loop.n, OpenLoop.ROWS
    assert loop.exhausted and n == 600
    i = np.arange(n)
    due, call, ret = rec.due[i % rows, i // rows], rec.call[i % rows, i // rows], rec.ret[i % rows, i // rows]
    assert not np.isnan(ret).any()
    assert (np.diff(due) >= 0).all() and (due <= call).all() and (call <= ret).all()
    assert np.isnan(rec.call.ravel()[np.isnan(rec.due.ravel())]).all()   # nothing past the schedule
    report = loop.window_report(t0, t1)
    assert report["due"] == n and report["answered_share"] == 1.0
    assert report["pool_wait_share"] == 0.0 and report["late_p99_ms"] < 50.0
    assert report["off_schedule_seconds_share"] == 0.0
    assert report["inflight_p50"] >= 1
    # check.py reads it as it reads a closed loop's record: the tag of an
    # update spells (row, column), a read names the write it saw.
    h = check.History(records, t_loaded)
    h.add_loop(loop)
    lines, counts = check.register_check(h)
    assert lines == [] and not any(counts.values())
    assert sum(map(len, h.r_key)) + sum(map(len, h.w_key)) == n + records.n
    verdict, ops = check.porcupine_sample(h, loop, keep.tolist(), [], 20.0)
    assert verdict == "ok" and ops > len(keep)


def test_a_stall_is_charged_to_every_operation_due_during_it(rig):
    """Coordinated omission: with few sessions, most operations due during
    a stall are sent after it, so from the call they look fast; from when
    they were due they waited for the stall."""
    records, stub, client, end = rig
    loop, _ = open_loop(records, client, end, 1.5, sessions=4, mix={**MIX, "rate_ops_s": 200})
    now = time.perf_counter()
    stub.stall = (now + 0.5, now + 0.8)
    drive(loop, 1.6)
    due, call, ret = loop._due[:loop.n], loop._call[:loop.n], loop._ret[:loop.n]
    assert not np.isnan(ret).any()
    hit = (due >= stub.stall[0] + 0.01) & (due < stub.stall[1] - 0.1)
    calm = due < stub.stall[0] - 0.05
    assert hit.sum() >= 30
    from_due, from_call = (ret - due)[hit], (ret - call)[hit]
    assert np.median(from_due) > 0.1 and from_due.min() > 0.09       # each waited for the stall's end
    assert np.median(from_call) < 0.05 < np.median(from_due) / 2     # the call's clock hides it
    assert np.median((ret - due)[calm]) < 0.02
    assert loop.waited[hit].mean() > 0.8                              # they queued in the generator
    # ... which puts the stall's seconds off the schedule, and no others
    t0 = stub.stall[0] - 0.5
    report = loop.window_report(t0, t0 + 2.0)
    assert report["off_schedule_seconds_share"] == 0.5 and report["judged_seconds"] == 2
    # a traced run's profiler: the seconds it touches are not judged, and the
    # generator's numbers are those of the rest
    calm = loop.window_report(t0, t0 + 2.0, exempt=stub.stall)
    assert calm["off_schedule_seconds_share"] == 0.0 and calm["judged_seconds"] == 1
    assert calm["due"] == report["due"] and calm["pool_wait_share"] < 0.01 < report["pool_wait_share"]


def test_an_open_loops_window_is_the_operations_due_in_it(rig):
    """A stall that outlasts the window: the operations due before its
    close are answered in the drain, and their waits are in the tails
    (from the call's end of things they would be outside the window)."""
    import run

    records, stub, client, end = rig
    loop, _ = open_loop(records, client, end, 2.0, sessions=512, mix={**MIX, "rate_ops_s": 300})
    now = time.perf_counter()
    stub.stall = (now + 1.0, now + 1.6)
    t0, t1 = drive(loop, 1.3)                     # the window closes inside the stall
    e2e = run.end_to_end(loop, t0, t1)
    due, ret = loop._due[:loop.n], loop._ret[:loop.n]
    in_window = (due >= t0) & (due < t1)
    late = in_window & (ret >= t1)
    assert late.sum() >= 50 and e2e["failed"] == 0
    assert e2e["completed"] == in_window.sum() == e2e["updates"] + e2e["reads"]
    assert e2e["update_p99_ms"] > 400.0            # an operation due at the stall's start


def test_a_pool_too_small_for_the_rate_shows_as_pool_waits(rig):
    records, stub, client, end = rig
    stub.service_s = 0.02                       # 500 ops/s x 20 ms needs 10 sessions
    loop, _ = open_loop(records, client, end, 1.0, sessions=2, mix={**MIX, "rate_ops_s": 500})
    t0, t1 = drive(loop, 1.0)
    report = loop.window_report(t0, t1)
    assert report["pool_wait_share"] > 0.5 and report["late_share"] > 0.5
    assert report["off_schedule_seconds_share"] == 1.0
    assert report["answered_share"] < 0.5 and report["inflight_end"] > 100


def test_both_loops_take_the_service_name(rig):
    records, _stub, client, end = rig
    upd, key = traffic.sequences({**MIX, "clients": 2}, records, SEED, 50)
    closed = ClosedLoop(client, end, records, upd, key, [], service="OtherKV")
    closed.start()
    time.sleep(0.3)
    closed.stop(5.0)
    assert closed.exhausted and closed.timed_from == "call" and not np.isnan(closed.rec.ret).any()
    loop, _ = open_loop(records, client, end, 0.2, sessions=8, service="OtherKV")
    drive(loop, 0.3)
    assert loop.timed_from == "due" and not np.isnan(loop._ret[:loop.n]).any()
