"""The program's own account of the pump cycle and the serving loop
(engine/instrument.py, IoScheduler's loop account, the tick's named
scopes, the compile counter, time to ready).

The load-bearing contract is that the clocks TILE: over any run of
durable pump cycles at depth 1, gap + dispatch + handoff + fetch + post +
complete + apply + sync + checkpoint sum to the elapsed wall, and the loop's
timer + io + idle seconds sum to the loop thread's wall.  What does not
tile hides time, and the next optimisation is chosen from these numbers.
"""

from __future__ import annotations

import gc
import glob
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from multiraft_tpu.distributed.engine_server import (  # noqa: E402
    EngineClerk,
    EngineKVService,
    EngineShardNetClerk,
    serve_engine_kv,
    serve_engine_shardkv,
)
from multiraft_tpu.distributed.observe import ObsControl  # noqa: E402
from multiraft_tpu.distributed.realtime import LOOP_OWNERS, IoScheduler  # noqa: E402
from multiraft_tpu.distributed.tcp import RpcNode  # noqa: E402
from multiraft_tpu.engine.core import (  # noqa: E402
    METRIC_KEYS,
    SCALAR_METRIC_KEYS,
    EngineConfig,
)
from multiraft_tpu.engine.host import EngineDriver  # noqa: E402
from multiraft_tpu.engine.kv import BatchedKV  # noqa: E402
from multiraft_tpu.engine.state_planes import content_fingerprint  # noqa: E402
from multiraft_tpu.sim.scheduler import TIMEOUT  # noqa: E402

PHASES = (
    "gap", "dispatch", "handoff", "fetch", "post", "complete", "apply", "sync",
)
# One tolerance for both tilings.  What the clocks leave out is the
# flight-ring record between apply and sync, the stamps' own reads and
# the loop's heap and lock handling: tens of microseconds a cycle,
# against a cycle stretched (MRT_PUMP_IDLE_S below) to about what it
# lasts on the chip at 10,000 groups.  Five per cent leaves room for six
# xdist workers on the box.
TILE_TOL = 0.05
CYCLE_S = 0.012


@pytest.fixture(
    params=[("kv", 0, 3), ("kv", 4, 3), ("shardkv", 0, 3), ("kv", 0, 5)],
    ids=["one-device", "mesh4", "shardkv", "one-device-p5"],
)
def served(request, tmp_path, monkeypatch):
    """A durable ``serve-kv`` node in this process: IoScheduler loop,
    pump thread, WAL, a checkpoint every 0.4 s; on one device, with
    the groups sharded over four (``--mesh-devices 4``), and with five
    replicas a group (``--replicas 5``).  And a durable
    ``serve-shardkv`` node: the sharded service composes the same cycle
    (distributed/pump_cycle.py), so the same clocks tile its loop."""
    kind, mesh, replicas = request.param
    if len(jax.devices()) < mesh:
        pytest.skip(f"need {mesh} devices")
    monkeypatch.setenv("MRT_PUMP_IDLE_S", str(CYCLE_S))
    monkeypatch.setenv("MRT_PUMP_HOT", "0")  # one cadence, busy or not
    if kind == "kv":
        node = serve_engine_kv(
            port=0, G=8 if mesh else 4, data_dir=str(tmp_path),
            checkpoint_every_s=0.4, mesh_devices=mesh, replicas=replicas,
        )
    else:
        node = serve_engine_shardkv(
            port=0, G=4, join_gids=[1], data_dir=str(tmp_path),
            checkpoint_every_s=0.4,
        )
    client = RpcNode()
    try:
        yield node, client
    finally:
        client.close()
        node.sched.run_call(node.engine_service.stop, timeout=30)
        node.close()


def _hist_state(m, names):
    return {
        n: ((h.count, h.total) if (h := m.hists.get(n)) else (0, 0.0))
        for n in names
    }


def _cycle_snapshots(svc, pumps, cap_s=60.0):
    """Two readings of the registry taken ON the loop at the end of a
    pump cycle (right after ``after_pump``, where ``pump.gap_s`` starts
    counting), ``pumps`` cycles apart: no cycle is cut in two."""
    names = [f"pump.{p}_s" for p in PHASES] + ["ckpt.save_s"]
    snaps = []
    done = threading.Event()
    cycle = svc.cycle
    inner = cycle._end_cycle

    def at_cycle_end():
        inner()
        if done.is_set():
            return
        n = svc.m.counters["pump.count"]
        if not snaps or n >= snaps[0]["pumps"] + pumps:
            snaps.append({
                "pumps": n, "t": cycle.t_end,
                "bytes": svc.m.counters["pump.readback_bytes"],
                "copies": svc.m.counters["pump.readback_copies"],
                "hists": _hist_state(svc.m, names),
            })
            if len(snaps) == 2:
                done.set()

    cycle._end_cycle = at_cycle_end
    try:
        assert done.wait(cap_s), "the pump loop did not run"
    finally:
        cycle._end_cycle = inner
    return snaps


def _put_some(node, client, n):
    end = client.client_end("127.0.0.1", node.port)
    sharded = hasattr(node.engine_service, "skv")
    ck = (EngineShardNetClerk if sharded else EngineClerk)(client.sched, end)
    for i in range(n):
        out = client.sched.wait(
            client.sched.spawn(ck.put(f"k{i % 7}", f"v{i}")), 30.0
        )
        assert out is not TIMEOUT


@pytest.mark.timeout_s(240)
def test_phases_tile_the_durable_pump_cycle(served):
    node, client = served
    svc = node.engine_service
    driver = svc.cycle.engine.driver
    shards = driver.mesh.devices.size if driver.mesh is not None else 1
    assert svc.cycle.depth == 1 and svc.cycle.pipe is not None
    assert driver.fused_eligible()  # a mesh server pipelines like any other
    # The fetch splits at the device's completion: wait, then copy, in
    # every pump, and the two histograms sum to the fetch's.
    out_of_order = []
    complete = driver.complete_ticks

    def checked(pending, rec):
        if not pending.t_fetch <= pending.t_ready <= pending.t_fetched:
            out_of_order.append(pending.pump)
        return complete(pending, rec)

    driver.complete_ticks = checked
    split = ("pump.fetch_s", "pump.wait_s", "pump.copy_s")
    writer = threading.Thread(target=_put_some, args=(node, client, 25))
    writer.start()
    try:
        before = node.sched.run_call(_hist_state, svc.m, split)
        a, b = _cycle_snapshots(svc, pumps=90)
        after = node.sched.run_call(_hist_state, svc.m, split)
    finally:
        driver.complete_ticks = complete
    writer.join(60.0)
    assert not writer.is_alive()
    assert not out_of_order, out_of_order
    (fn, fetch), (wn, wait), (cn, copy) = (
        (after[k][0] - before[k][0], after[k][1] - before[k][1]) for k in split
    )
    assert fn == wn == cn >= 90
    assert wait + copy == pytest.approx(fetch, rel=1e-6)
    pumps = b["pumps"] - a["pumps"]
    assert pumps >= 90
    wall = b["t"] - a["t"]
    total = 0.0
    for name, (n1, s1) in b["hists"].items():
        n0, s0 = a["hists"][name]
        total += s1 - s0
        if name == "ckpt.save_s":
            assert n1 - n0 >= 1, "no checkpoint fell in the interval"
        else:
            # every phase took one sample a pump
            assert abs((n1 - n0) - pumps) <= 1, (name, n1 - n0, pumps)
    assert abs(total - wall) <= TILE_TOL * wall, (total, wall)
    # Readback: what fetch brought over is what the per-field record's
    # shapes say ([n] scalars, one lane a device on a mesh, and [n, G]
    # fields, all int32), in one copy a device: the record is one
    # packed buffer with a shard on every device.
    twin = EngineDriver(driver.cfg, seed=1, mesh=driver.mesh)
    n = svc.cycle.ticks
    p = twin.dispatch_ticks(n)
    assert len(p.buf.addressable_shards) == shards
    rec = p.fetch()
    twin.complete_ticks(p, rec)
    lanes = shards if driver.mesh is not None else 1
    n_fields = len(METRIC_KEYS) - len(SCALAR_METRIC_KEYS)
    per_pump = 4 * n * (len(SCALAR_METRIC_KEYS) * lanes + n_fields * driver.cfg.G)
    assert sum(v.nbytes for v in rec.values()) == per_pump
    assert b["bytes"] - a["bytes"] == pumps * per_pump
    assert b["copies"] - a["copies"] == pumps * shards


@pytest.mark.timeout_s(240)
def test_loop_account_tiles_the_loop_threads_wall(served):
    node, client = served
    sched = node.sched

    def read():
        return (time.perf_counter(), sched.timer_s, sched.io_s,
                sched.idle_s, sched.polls)

    t0, timer0, io0, idle0, polls0 = sched.run_call(read)
    _put_some(node, client, 10)
    time.sleep(1.0)
    t1, timer1, io1, idle1, polls1 = sched.run_call(read)
    wall = t1 - t0
    parts = (timer1 - timer0, io1 - io0, idle1 - idle0)
    assert all(p > 0.0 for p in parts), parts
    assert polls1 > polls0
    assert abs(sum(parts) - wall) <= TILE_TOL * wall, (parts, wall)
    # ... and a scrape publishes them, cumulative, where counters are.
    end = client.client_end("127.0.0.1", node.port)
    snap = client.sched.wait(end.call("Obs.snapshot", None), 30.0)
    m = snap["metrics"]
    assert m["loop.timer_s"] >= timer1 and m["loop.idle_s"] >= idle1
    assert m["loop.io_s"] >= io1 and m["loop.polls"] >= polls1
    # The owners, and the callees under them, tile loop.timer_s.
    owners = [m[f"loop.{o}_s"] for o in LOOP_OWNERS]
    assert sum(owners) == pytest.approx(m["loop.timer_s"], rel=1e-9)
    callees = sum(v for k, v in m.items() if k.startswith("loop.cb."))
    assert callees == pytest.approx(m["loop.timer_s"], rel=1e-9)
    assert all(o > 0.0 for o in owners), owners  # the watches run every 0.25 s
    assert m["loop.cb.PumpCycle._pump_done_s"] > 0.0
    assert m["loop.cb.PumpCycle.wake_s"] > 0.0  # the parked updates' wake
    # The kernel's account of the two serving threads, and the
    # collector's, only grow.
    again = client.sched.wait(end.call("Obs.snapshot", None), 30.0)["metrics"]
    grows = ["gc.collections", "gc.gen2", "loop.gc_s", "loop.long_turns"]
    if os.path.exists(f"/proc/self/task/{threading.get_native_id()}/schedstat"):
        grows += ["loop.oncpu_s", "loop.runq_s", "pump.runq_s"]
    for k in grows:
        assert again[k] >= m[k], k
    assert again["loop.oncpu_s"] > m["loop.oncpu_s"] or "loop.oncpu_s" not in grows
    if hasattr(node.engine_service, "skv"):
        return  # serve_engine_kv alone publishes what follows
    # the compile counter and time to ready ride the same scrape
    assert m["engine.compiles"] >= 0 and "ready.warm_s" in m
    assert m["ready.checkpoint_s"] > 0.0 and m["ready.restore_s"] == 0.0
    driver = node.engine_service.kv.driver
    mesh = driver.mesh
    assert m["engine.mesh_devices"] == (mesh.devices.size if mesh else 0)
    assert m["engine.replicas"] == driver.cfg.P


def _host_lines_with(trace_dir, prefix):
    """{event name: set of (plane, line number) it appears on} for the
    events named ``prefix``* in the newest xplane under ``trace_dir``.
    A line is one thread; Python threads all carry the line name
    "python", so a line is told by its place in the plane."""
    paths = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    assert paths, "the profiler wrote no xplane"
    data = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
    found = {}
    for plane in data.planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(prefix):
                    found.setdefault(e.name, set()).add((plane.name, i))
    return found


@pytest.mark.timeout_s(240)
def test_phases_are_on_the_profilers_clock(served, tmp_path):
    """A profiler session holds the phases on two thread lines (the
    loop's and the pump thread's) of the xplane that would carry the
    device's lines on a chip."""
    node, _client = served
    svc = node.engine_service
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        _cycle_snapshots(svc, pumps=8)
        node.sched.run_call(gc.collect)  # a collection on the loop's line
    finally:
        jax.profiler.stop_trace()
    found = _host_lines_with(trace_dir, "mrt.")
    for phase in ("dispatch", "fetch", "wait", "copy", "complete", "apply", "sync"):
        assert f"mrt.pump.{phase}" in found, sorted(found)
    loop_lines = found["mrt.pump.dispatch"]
    assert found["mrt.pump.apply"] == loop_lines
    assert found["mrt.pump.fetch"].isdisjoint(loop_lines), found
    # The device wait and the copy sit inside the fetch, on its line.
    assert found["mrt.pump.wait"] == found["mrt.pump.copy"] == found["mrt.pump.fetch"]
    # The loop's line: every turn under a span of its own.
    for span in ("mrt.loop.io", "mrt.loop.pump", "mrt.gc"):
        assert loop_lines <= found[span], (span, found)


def _bare_loop():
    """An IoScheduler whose poll only sleeps: nothing but the timers a
    test schedules runs on it."""
    return IoScheduler(
        lambda delay: time.sleep(min(delay, 0.001)), lambda ev: None,
        lambda: None, name="multiraft-loop/bare",
    )


def test_loop_charges_each_turn_to_its_owner_and_counts_long_ones():
    sched = _bare_loop()

    def parked():
        yield 0.01  # the step after it is a timer turn of its own
        return 1

    def cycle_end():
        # What PumpCycle._end_cycle does with the parked handlers' wake.
        sched.run_as("test.wake", time.sleep, 0.03)

    try:
        t0 = sched.run_call(sched.loop_account)
        assert sched.wait(sched.spawn(parked()), 5.0) == 1
        sched.run_call(time.sleep, 0.15)  # a long turn, charged to other
        sched.run_call(cycle_end)
        t1 = sched.run_call(sched.loop_account)
    finally:
        sched.stop()
    for acct in (t0, t1):
        owners = [acct[f"loop.{o}_s"] for o in LOOP_OWNERS]
        assert sum(owners) == pytest.approx(acct["loop.timer_s"], rel=1e-9)
    assert t1["loop.long_turns"] - t0["loop.long_turns"] >= 1
    assert t1["loop.long_turn_s"] - t0["loop.long_turn_s"] >= 0.15
    assert t1["loop.other_s"] - t0["loop.other_s"] >= 0.15
    name = parked.__qualname__
    assert t1[f"loop.cb.{name}_s"] > 0.0  # a coroutine, by its generator
    # The lent block is the handlers', not its callback's.
    assert t1["loop.cb.test.wake_s"] >= 0.03
    assert t1["loop.handlers_s"] - t0["loop.handlers_s"] >= 0.03
    invoke = "RealtimeScheduler.run_call.<locals>._invoke"
    assert t1[f"loop.cb.{invoke}_s"] - t0[f"loop.cb.{invoke}_s"] < 0.15 + 0.03


def test_gc_clock_charges_the_loop_only_its_own_collections():
    from multiraft_tpu.engine.instrument import count_gc
    from multiraft_tpu.utils.metrics import Metrics

    m = Metrics()
    sched = _bare_loop()
    try:
        count_gc(m, sched._thread)
        keys = (set(m.counters), set(m.hists))
        gen2, pauses = m.counters["gc.gen2"], m.hists["gc.pause_s"].count
        sched.run_call(gc.collect, 2)
        assert m.counters["gc.gen2"] > gen2
        assert m.hists["gc.pause_s"].count > pauses
        on_loop = m.counters["loop.gc_s"]
        assert on_loop > 0.0
        gen2 = m.counters["gc.gen2"]
        worker = threading.Thread(target=gc.collect, args=(2,))
        worker.start()
        worker.join()
        assert m.counters["gc.gen2"] > gen2
        assert m.counters["loop.gc_s"] == on_loop
        assert (set(m.counters), set(m.hists)) == keys
    finally:
        sched.stop()


def test_run_queue_seconds_are_left_out_without_schedstat(monkeypatch):
    node = RpcNode(listen=True)
    try:
        ctl = ObsControl(node)
        with monkeypatch.context() as patch:
            patch.setattr(
                "multiraft_tpu.distributed.observe._SCHEDSTAT", "/nonexistent/{}"
            )
            m = node.sched.run_call(ctl.snapshot)["metrics"]
        assert "loop.timer_s" in m
        assert not {"loop.oncpu_s", "loop.runq_s", "pump.runq_s"} & set(m)
        tid = node.sched._thread.native_id
        if os.path.exists(f"/proc/self/task/{tid}/schedstat"):
            m = node.sched.run_call(ctl.snapshot)["metrics"]
            assert m["loop.runq_s"] >= 0.0 and m["loop.oncpu_s"] > 0.0
            assert "pump.runq_s" not in m  # no engine, no pump thread
    finally:
        node.close()


def test_sync_pump_gets_apply_and_sync_and_nothing_else(tmp_path, monkeypatch):
    """The synchronous pump (the kill switch; reorder chaos in flight
    takes the same path) runs no dispatch/fetch/complete and closes no
    gap: it gets ``pump.apply_s`` and ``pump.sync_s`` from the shared
    code."""
    from multiraft_tpu.distributed.engine_durability import EngineDurability
    from multiraft_tpu.distributed.observe import Observability
    from multiraft_tpu.distributed.realtime import RealtimeScheduler

    monkeypatch.setenv("MRT_ENGINE_PIPELINE", "0")
    obs = Observability()
    sched = RealtimeScheduler(name="multiraft-loop/sync-pump")
    svc = None
    try:
        def build():
            d = EngineDriver(EngineConfig(G=4, P=3, L=32, E=4, INGEST=4))
            d.metrics = obs.metrics
            kv = BatchedKV(d)
            dur = EngineDurability(str(tmp_path), d, kv,
                                   metrics=obs.metrics)
            return EngineKVService(sched, kv, durability=dur, obs=obs)

        svc = sched.run_call(build, timeout=150)
        assert svc.cycle.pipe is None
        deadline = time.monotonic() + 30
        while (time.monotonic() < deadline
               and obs.metrics.counters["pump.count"] < 5):
            time.sleep(0.02)
    finally:
        if svc is not None:
            sched.run_call(svc.stop, timeout=30)
        sched.stop()
    pumps = obs.metrics.counters["pump.count"]
    assert pumps >= 5
    got = {n for n in obs.metrics.hists if n.startswith("pump.")}
    assert got == {"pump.wall_s", "pump.apply_s", "pump.sync_s"}, got
    assert abs(obs.metrics.hists["pump.apply_s"].count - pumps) <= 1
    assert "pump.readback_bytes" not in obs.metrics.counters
    assert "pump.readback_copies" not in obs.metrics.counters


_CLIENT_ONLY = """
import sys
from multiraft_tpu.distributed.tcp import RpcNode
node = RpcNode()
node.close()
mods = sorted(m for m in sys.modules if m.startswith("multiraft_tpu"))
print("\\n".join(mods))
print("jax-imported", any(m == "jax" or m.startswith("jax.") for m in sys.modules))
"""

# What a client-only RpcNode imported at the parent of the PR that put
# the pump's clocks in: the instrumentation lives where jax already is,
# so this list gained nothing.
_CLIENT_MODULES = """multiraft_tpu
multiraft_tpu.distributed
multiraft_tpu.distributed.admission
multiraft_tpu.distributed.disk
multiraft_tpu.distributed.engine_wire
multiraft_tpu.distributed.flightrec
multiraft_tpu.distributed.native
multiraft_tpu.distributed.observe
multiraft_tpu.distributed.profile
multiraft_tpu.distributed.realtime
multiraft_tpu.distributed.sanitize
multiraft_tpu.distributed.tail
multiraft_tpu.distributed.tcp
multiraft_tpu.porcupine
multiraft_tpu.porcupine.kv
multiraft_tpu.porcupine.model
multiraft_tpu.sim
multiraft_tpu.sim.scheduler
multiraft_tpu.transport
multiraft_tpu.transport.codec
multiraft_tpu.utils
multiraft_tpu.utils.cpus
multiraft_tpu.utils.knobs
multiraft_tpu.utils.metrics
multiraft_tpu.utils.native_build
multiraft_tpu.utils.trace""".split()


def test_client_only_node_imports_no_jax_and_nothing_new():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", _CLIENT_ONLY], cwd=root, text=True,
        capture_output=True, timeout=100,
    )
    assert out.returncode == 0, out.stderr
    lines = out.stdout.split()
    assert lines[-2:] == ["jax-imported", "False"], lines[-2:]
    assert lines[:-2] == _CLIENT_MODULES


TICK_SCOPES = (
    "tick.1_votes", "tick.2_tally", "tick.3_append", "tick.4_replies_commit",
    "tick.4b_check_quorum", "tick.5_timers", "tick.5b_ingest",
    "tick.5c_sends", "tick.6_apply_compact",
)
MEMBERSHIP_SCOPES = ("tick.4c_membership", "tick.5a_membership")


def test_tick_phases_are_named_scopes_in_the_lowered_program():
    """Every numbered phase of ``tick_impl`` is a ``jax.named_scope``:
    the lowered ``step_ticks`` names each (so a device trace's ops say
    which phase they belong to), and the names are metadata only — the
    fused and the serial path still agree bit for bit."""
    from multiraft_tpu.engine.pipeline import step_ticks

    cfg = EngineConfig(G=4, P=3, L=32, E=4, INGEST=4)
    assert cfg.check_quorum and cfg.prevote
    d = EngineDriver(cfg, seed=5)
    text = step_ticks.lower(
        cfg, d.state, d.inbox, 2, False, False,
        jax.numpy.zeros(cfg.G, jax.numpy.int32), jax.numpy.float32(0.0),
        jax.numpy.zeros((), jax.numpy.bool_), jax.numpy.int32(0), d.key,
    ).as_text(debug_info=True)
    want = TICK_SCOPES + (MEMBERSHIP_SCOPES if cfg.membership_on else ())
    missing = [s for s in want if s not in text]
    assert not missing, missing

    fused, serial = EngineDriver(cfg, seed=5), EngineDriver(cfg, seed=5)
    serial._pipeline_on = False
    rng = np.random.default_rng(2)
    for _ in range(6):
        for g in range(cfg.G):
            for _ in range(int(rng.integers(0, 4))):
                fused.start(g, ("c",))
                serial.start(g, ("c",))
        fused.step(3)
        for _ in range(3):
            serial.step(1)
    assert content_fingerprint(fused.state) == content_fingerprint(serial.state)
    assert content_fingerprint(fused.inbox) == content_fingerprint(serial.inbox)


def test_compile_counter_counts_traces_lowerings_and_compiles():
    from multiraft_tpu.engine.instrument import count_compiles
    from multiraft_tpu.utils.metrics import Metrics

    m = Metrics()
    count_compiles(m)
    assert m.counters["engine.compiles"] == 0
    assert m.hists["engine.compile_s"].count == 0

    @jax.jit
    def fresh(x):
        return x * 3 + 1

    fresh(jax.numpy.arange(7))  # trace + lower + compile: three events
    assert m.counters["engine.compiles"] >= 3
    assert m.hists["engine.compile_s"].count == m.counters["engine.compiles"]
    before = m.counters["engine.compiles"]
    fresh(jax.numpy.arange(7))  # cached: nothing compiles
    assert m.counters["engine.compiles"] == before
