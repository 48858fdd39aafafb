"""Asynchronous engine pipeline tests (engine/pipeline.py,
distributed/engine_pump.py).

The load-bearing contract is TICK PARITY: the fused multi-tick scan
(``step_ticks``) and the dispatch/complete split must produce
bit-identical ``EngineState``/``Mailbox`` to N serial ``step(1)`` calls
under seeded traffic AND chaos (drops, partitions, restarts) — pinned
via the ``state_planes.content_fingerprint`` value digests.  On top of
that: the double-ingest guard at pipeline depth ≥ 2, the checkpoint
guard against half-accounted batches, the serial fallbacks (kill
switch, reorder chaos), the engine-pump thread's post-back discipline,
its lock in the sanitizer's recorded order graph, and the pipelined
serving loop end to end.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from multiraft_tpu.engine.core import EngineConfig  # noqa: E402
from multiraft_tpu.engine.host import EngineDriver  # noqa: E402
from multiraft_tpu.engine.state_planes import content_fingerprint  # noqa: E402

CFG = dict(G=4, P=3, L=32, E=4, INGEST=4)


def make_driver(seed: int = 3) -> EngineDriver:
    return EngineDriver(EngineConfig(**CFG), seed=seed)


def drive(d: EngineDriver, fused: bool) -> EngineDriver:
    """Seeded traffic + chaos script; the SAME tick sequence either
    way — serial runs each multi-tick request as N step(1) calls."""
    if not fused:
        d._pipeline_on = False
    rng = np.random.default_rng(11)
    for rnd in range(12):
        for g in range(d.cfg.G):
            for _ in range(int(rng.integers(0, 6))):
                d.start(g, ("cmd", rnd, g))
        if rnd == 3:
            d.drop_prob = 0.15
        if rnd == 5:
            d.partition_replica(0, 1, False)
        if rnd == 7:
            d.partition_replica(0, 1, True)
        if rnd == 8:
            d.restart_replica(1, 2)
        if rnd == 9:
            d.drop_prob = 0.0
        n = int(rng.integers(2, 7))
        if fused:
            d.step(n)
        else:
            for _ in range(n):
                d.step(1)
    return d


def assert_same_world(a: EngineDriver, b: EngineDriver) -> None:
    assert content_fingerprint(a.state) == content_fingerprint(b.state)
    assert content_fingerprint(a.inbox) == content_fingerprint(b.inbox)
    assert a.tick == b.tick
    assert a.backlog.tolist() == b.backlog.tolist()
    assert a.payloads == b.payloads
    assert a._max_bound == b._max_bound
    assert a.commits_total == b.commits_total
    for k in a.last_metrics:
        assert np.array_equal(
            np.asarray(a.last_metrics[k]), np.asarray(b.last_metrics[k])
        ), k


# -- tick parity ------------------------------------------------------------


def test_fused_step_bit_identical_to_serial_under_chaos():
    serial = drive(make_driver(), fused=False)
    fused = drive(make_driver(), fused=True)
    assert serial.tick > 30  # the script actually ran
    assert serial.commits_total > 0  # and committed through chaos
    assert_same_world(serial, fused)


def test_overlapped_dispatch_depth2_matches_serial():
    """Two batches in flight before any completion: the second
    dispatch must subtract the first's (device-resident) accepted
    counts from the backlog it ships, or commands ingest twice."""
    def seeded() -> EngineDriver:
        d = make_driver(seed=7)
        assert d.run_until_quiet_leaders(500)
        for g in range(d.cfg.G):
            for i in range(10):  # 10 > 2 batches * 3 ticks * INGEST/tick
                d.start(g, ("w", g, i))
        return d

    serial = seeded()
    serial._pipeline_on = False
    for _ in range(6):
        serial.step(1)

    piped = seeded()
    p1 = piped.dispatch_ticks(3)
    p2 = piped.dispatch_ticks(3)
    assert len(piped._inflight) == 2
    r1, r2 = p1.fetch(), p2.fetch()
    piped.complete_ticks(p1, r1)
    piped.complete_ticks(p2, r2)
    assert (piped.backlog >= 0).all()
    assert_same_world(serial, piped)


def _root(a):
    while isinstance(a.base, np.ndarray):
        a = a.base
    return a


@pytest.mark.parametrize("n", [1, 2])
@pytest.mark.parametrize("faults", ["clean", "drop", "edges"])
def test_fetch_unpacks_the_record_the_scan_produced(faults, n):
    """The record leaves the device as one packed buffer: ``fetch``
    hands ``complete_ticks`` what the scan stacked, field by field —
    same keys, shapes, dtypes and values, and as many bytes — from ONE
    device copy, and ``accepts_dev`` is the record's ``accepted``
    summed over the batch's ticks."""
    from multiraft_tpu.engine.core import METRIC_KEYS
    from multiraft_tpu.engine.pipeline import _scan_ticks

    d = make_driver(seed=7)
    assert d.run_until_quiet_leaders(500)
    rng = np.random.default_rng(n)
    for g in range(d.cfg.G):
        for i in range(int(rng.integers(1, 9))):
            d.start(g, ("w", g, i))
    if faults == "drop":
        d.drop_prob = 0.3
    elif faults == "edges":
        d.partition_replica(1, 0, False)
        d.set_edge(2, 1, 2, False)
    with_edges = not bool(d.edge_up.all())
    edge = d._edge_mask() if with_edges else jax.numpy.zeros((), bool)
    # The reference: the scan's own per-field record on the same inputs
    # (not donated, so the driver can step the same state after it).
    ref = jax.jit(_scan_ticks, static_argnums=(0, 3, 4, 5))(
        d.cfg, d.state, d.inbox, n, d.drop_prob > 0.0, with_edges,
        jax.numpy.asarray(d.backlog.astype(np.int32)),
        jax.numpy.float32(d.drop_prob), edge, jax.numpy.int32(d.tick), d.key,
    )[3]
    ref = {k: np.asarray(v) for k, v in ref.items()}
    assert ref["accepted"].any()  # the batch did bind something

    p = d.dispatch_ticks(n)
    got = p.fetch()
    assert list(got) == list(METRIC_KEYS)
    for k in METRIC_KEYS:
        assert got[k].shape == ref[k].shape, k
        assert got[k].dtype == ref[k].dtype, k
        assert np.array_equal(got[k], ref[k]), k
        # one device: every field is a view of the one fetched buffer
        assert _root(got[k]) is _root(got["commits"]), k
    assert np.array_equal(np.asarray(p.accepts_dev), ref["accepted"].sum(0))
    assert p.ncopies == 1
    assert p.nbytes == sum(v.nbytes for v in ref.values())
    d.complete_ticks(p, got)


def test_complete_out_of_dispatch_order_asserts():
    d = make_driver()
    d.start(0, ("x",))
    p1 = d.dispatch_ticks(2)
    p2 = d.dispatch_ticks(2)
    rec2 = p2.fetch()
    with pytest.raises(AssertionError, match="dispatch order"):
        d.complete_ticks(p2, rec2)
    d.complete_ticks(p1, p1.fetch())
    d.complete_ticks(p2, rec2)


def test_save_refuses_inflight_batches(tmp_path):
    d = make_driver()
    p = d.dispatch_ticks(2)
    with pytest.raises(RuntimeError, match="in flight"):
        d.save(str(tmp_path / "x.ckpt"))
    d.complete_ticks(p, p.fetch())
    d.save(str(tmp_path / "x.ckpt"))  # drained: fine


# -- serial fallbacks -------------------------------------------------------


def test_kill_switch_forces_serial(monkeypatch):
    monkeypatch.setenv("MRT_ENGINE_PIPELINE", "0")
    d = make_driver()
    assert d._pipeline_on is False
    assert not d.fused_eligible()
    d.start(0, ("x",))
    d.step(4)  # serial path, still advances
    assert d.tick == 4
    assert not d._inflight


def test_reorder_chaos_falls_back_to_serial():
    d = make_driver()
    assert d.fused_eligible()
    d.set_reorder(0.5, 2, 4)
    assert not d.fused_eligible()
    d.start(0, ("x",))
    d.step(4)  # must not raise; serial loop handles reorder
    assert d.tick == 4
    d.set_reorder(0.0, 2, 4)
    # held messages may still be in the delay queue; only a fully
    # drained queue re-enables fusion
    assert d.fused_eligible() == (not d._delayed)


def test_serial_step_asserts_with_inflight():
    d = make_driver()
    p = d.dispatch_ticks(2)
    with pytest.raises(AssertionError, match="in flight"):
        d._step_serial(1)
    d.complete_ticks(p, p.fetch())


# -- tracer buffering -------------------------------------------------------


class _SpanTracer:
    def __init__(self):
        self.spans = []
        self.counters = []

    def span(self, name, ts, dur, **kw):
        self.spans.append((name, ts, dur, kw))

    def counter(self, name, ts, values):
        self.counters.append((name, ts, dict(values)))


def test_fused_tracer_buffers_per_tick_spans():
    """Tracing must not force the serial path: a fused step(n) emits n
    per-tick spans (from the stacked metrics) and ONE consensus
    counter per pump."""
    d = make_driver()
    d.tracer = _SpanTracer()
    assert d.fused_eligible()
    d.start(0, ("x",))
    d.step(5)
    assert not d._inflight  # fused path ran and completed
    ticks = [s for s in d.tracer.spans if s[0] == "tick"]
    assert len(ticks) == 5
    assert [s[3]["tick"] for s in ticks] == [1, 2, 3, 4, 5]
    assert all("commits" in s[3] and "leaders" in s[3] for s in ticks)
    assert len(d.tracer.counters) == 1
    assert "backlog" in d.tracer.counters[0][2]


# -- the engine-pump thread -------------------------------------------------


def test_engine_pump_posts_result_on_loop_thread():
    from multiraft_tpu.distributed.engine_pump import EnginePump
    from multiraft_tpu.distributed.realtime import RealtimeScheduler

    sched = RealtimeScheduler(name="multiraft-loop/pump-test")
    pump = EnginePump(sched, name="multiraft-pump/pump-test")
    got = []
    done = threading.Event()
    try:
        def fetch():
            assert threading.current_thread().name == "multiraft-pump/pump-test"
            return 42

        def on_done(res):
            got.append((res, sched.on_loop_thread()))
            done.set()

        pump.submit(fetch, on_done)
        assert done.wait(10.0)
        assert got == [(42, True)]

        # exceptions ship back as the result (loop-side handler raises)
        got.clear()
        done.clear()
        pump.submit(lambda: 1 / 0, lambda r: (got.append(r), done.set()))
        assert done.wait(10.0)
        assert isinstance(got[0], ZeroDivisionError)
    finally:
        pump.stop()
        sched.stop()
    assert not pump._thread.is_alive()


def test_pump_lock_joins_sanitizer_order_graph(monkeypatch):
    from multiraft_tpu.analysis.lockorder import RecordingLock
    from multiraft_tpu.distributed import sanitize
    from multiraft_tpu.distributed.engine_pump import EnginePump
    from multiraft_tpu.distributed.realtime import RealtimeScheduler

    monkeypatch.setenv("MRT_SANITIZE", "1")
    monkeypatch.setattr(sanitize, "_san", None)
    sched = RealtimeScheduler(name="multiraft-loop/san-test")
    pump = EnginePump(sched, name="multiraft-pump/san-test")
    try:
        san = sanitize.get_sanitizer()
        assert san is not None
        # the queue lock is the recorded proxy — every acquire from
        # both threads lands in the order graph
        assert isinstance(pump._lock, RecordingLock)
        done = threading.Event()
        pump.submit(lambda: "ok", lambda r: done.set())
        assert done.wait(10.0)
        assert san.violations == []
        san.recorder.assert_acyclic()
    finally:
        pump.stop()
        sched.stop()
        monkeypatch.setattr(sanitize, "_san", None)


# -- the pipelined serving loop end to end ----------------------------------


@pytest.mark.timeout_s(180)
def test_pipelined_service_serves_and_reports():
    from multiraft_tpu.distributed.engine_server import EngineKVService
    from multiraft_tpu.distributed.observe import Observability
    from multiraft_tpu.distributed.realtime import RealtimeScheduler
    from multiraft_tpu.engine.kv import BatchedKV, KVOp
    from multiraft_tpu.porcupine.kv import OP_PUT

    sched = RealtimeScheduler(name="multiraft-loop/pipe-e2e")
    svc = None
    try:
        obs = Observability()

        def build():
            d = EngineDriver(EngineConfig(G=4, P=3, L=64, E=8, INGEST=8),
                             seed=0)
            d.metrics = obs.metrics  # as serve_engine_kv folds them
            assert d.run_until_quiet_leaders(2000)
            return EngineKVService(sched, BatchedKV(d), obs=obs)

        svc = sched.run_call(build, timeout=150)
        assert svc.cycle.pipe is not None
        assert svc.cycle.pipe._thread.name.startswith("multiraft-pump")
        t = sched.run_call(lambda: svc.kv.submit(
            0, KVOp(op=OP_PUT, key="a", value="1",
                    client_id=1, command_id=1)))
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not t.done:
            time.sleep(0.02)
        assert t.done and not t.failed
        g = sched.run_call(lambda: svc.kv.get(0, "a"))
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not g.done:
            time.sleep(0.02)
        assert g.done and g.value == "1"
        snap = svc.m.snapshot()
        assert snap.get("pump.count", 0) > 0
        # the pump thread's wait and the loop's share, by phase
        assert svc.m.hists["pump.fetch_s"].total > 0.0
        assert snap["pump.readback_bytes"] > 0
        for phase in ("gap", "dispatch", "handoff", "post", "complete",
                      "apply"):
            assert snap[f"pump.{phase}_s_count"] > 0, phase
        assert "pump.sync_s_count" not in snap  # not durable: no WAL
    finally:
        if svc is not None:
            sched.run_call(svc.stop, timeout=30)
        sched.stop()
