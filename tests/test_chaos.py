"""Chaos transport + nemesis harness tests: the deployment path under
labrpc's fault model (distributed/chaos.py, harness/nemesis.py) plus
the wire/host validation hardening that rode along — key-length and
route-group checks on the firehose path, the accept-batch bound, and
the plain-KV handler's demote-before-Get-gate ordering."""

from __future__ import annotations

import time
import types

import numpy as np
import pytest

from multiraft_tpu.distributed.chaos import (
    ChaosRule,
    ChaosState,
    install_chaos,
)
from multiraft_tpu.distributed.native import native_available
from multiraft_tpu.harness.nemesis import (
    ChaosClient,
    Nemesis,
    make_schedule,
    run_clerk_load,
)
from multiraft_tpu.sim.scheduler import TIMEOUT

needs_native = pytest.mark.skipif(
    not native_available(), reason="native transport did not build"
)


# ---------------------------------------------------------------------------
# ChaosState / ChaosRule (no sockets)
# ---------------------------------------------------------------------------


class TestChaosState:
    def test_rule_wire_roundtrip(self):
        r = ChaosRule(drop=0.3, delay=0.5, delay_min=0.01, delay_max=0.2,
                      block=False)
        q = ChaosRule.from_wire(r.to_wire())
        assert q.to_wire() == r.to_wire()

    def test_seeded_decisions_reproducible(self):
        rule = ChaosRule(drop=0.4, delay=0.4, delay_min=0.0, delay_max=0.1)
        runs = []
        for _ in range(2):
            st = ChaosState(seed=42)
            st.all_in = rule
            runs.append([st.decide_in() for _ in range(64)])
        assert runs[0] == runs[1]
        assert "drop" in runs[0]  # the mix actually drops sometimes
        assert any(isinstance(d, float) for d in runs[0])  # ...and delays

    def test_block_always_drops_and_counts(self):
        st = ChaosState(seed=1)
        st.all_out = ChaosRule(block=True)
        assert all(
            st.decide_out(("h", 1)) == "drop" for _ in range(10)
        )
        assert st.dropped == 10

    def test_peer_rule_overrides_catch_all(self):
        st = ChaosState(seed=1)
        st.all_out = ChaosRule(block=True)
        st.peer_out[("ok", 5)] = ChaosRule()  # clean edge
        assert st.decide_out(("ok", 5)) == "pass"
        assert st.decide_out(("other", 6)) == "drop"

    def test_configure_replaces_and_clear_empties(self):
        st = ChaosState(seed=0)
        st.configure({
            "peers": {"10.0.0.1:700": {"block": True}},
            "all_in": {"drop": 0.5},
            "reply": None,
        })
        assert st.peer_out[("10.0.0.1", 700)].block
        assert st.all_in is not None and st.all_in.drop == 0.5
        # Full-state replace: a second configure drops the old peer.
        st.configure({"all_out": {"delay": 1.0, "delay_max": 0.1}})
        assert st.peer_out == {} and st.all_in is None
        assert st.all_out is not None
        st.clear()
        assert st.all_out is None and st.decide_in() == "pass"


def test_make_schedule_same_seed_same_schedule():
    kw = dict(duration_s=9.0, crash_procs=[1], crash_down_s=0.5)
    s1 = make_schedule(7, 3, **kw)
    s2 = make_schedule(7, 3, **kw)
    assert s1 == s2
    assert make_schedule(8, 3, **kw) != s1  # seed actually matters
    kinds = [k for _, k, _ in s1]
    assert kinds[-1] == "heal" and kinds.count("crash") == 1
    assert all(at <= s1[-1][0] for at, _, _ in s1)


def test_make_schedule_partition_needs_two_procs():
    sched = make_schedule(3, 1, duration_s=5.0, include=("partition",))
    assert [k for _, k, _ in sched] == ["heal"]


def test_make_schedule_load_surge_window():
    kw = dict(duration_s=6.0, surge_rate=1500.0, surge_dur_s=1.2)
    s1 = make_schedule(7, 2, **kw)
    assert s1 == make_schedule(7, 2, **kw)
    surges = [(at, p) for at, k, p in s1 if k == "load_surge"]
    assert surges == [(2.4, {"proc": 0, "rate": 1500.0, "dur": 1.2})]
    # The heal still closes the schedule, after the surge window ends.
    assert s1[-1][1] == "heal" and s1[-1][0] >= 2.4 + 1.2
    # No surge_rate, no surge window (the default schedule is unchanged).
    assert not any(
        k == "load_surge" for _, k, _ in make_schedule(7, 2, duration_s=6.0)
    )


@needs_native
def test_nemesis_load_surge_runs_and_verifies():
    """The load_surge verb end to end with an injected burst driver:
    the window opens at its scheduled instant, the driver fires with
    the schedule's (rate, dur), the replied count lands as the
    window's hits, and verify_windows(require_hits) accepts it."""
    from multiraft_tpu.distributed.tcp import RpcNode

    server = RpcNode(listen=True)
    server.add_service("Echo", _Echo())
    install_chaos(server, seed=2)
    fired = []

    def fake_surge(host, port, rate, dur, seed):
        fired.append((host, port, rate, dur, seed))
        return 37  # "37 requests got replies"

    sched = make_schedule(
        9, 1, duration_s=0.6, include=(),
        surge_rate=800.0, surge_dur_s=0.2,
    )
    assert [k for _, k, _ in sched] == ["load_surge", "heal"]
    nem = Nemesis([(server.host, server.port)], surge_fire=fake_surge)
    try:
        nem.run(sched)  # verify=True: must not raise
        assert fired == [(server.host, server.port, 800.0, 0.2,
                          800 + 1009 * 0)]
        (w,) = nem.windows
        assert w["kind"] == "load_surge" and w["acked"]
        assert w["hits"] == 37 and w["t_stop_us"] is not None
        nem.verify_windows(require_hits=("load_surge",))
        kinds = [(ph, k) for ph, k, _ in nem.applied]
        assert ("start", "load_surge") in kinds
        assert ("stop", "load_surge") in kinds
    finally:
        nem.close()
        server.close()


@needs_native
def test_nemesis_load_surge_failed_burst_is_a_silent_miss():
    """A burst driver that errors (or a server that never replied)
    must FAIL verification — a surge that never reached the fleet is
    exactly the false green verify_windows exists to catch."""
    from multiraft_tpu.distributed.tcp import RpcNode
    from multiraft_tpu.harness.nemesis import NemesisVerificationError

    server = RpcNode(listen=True)
    install_chaos(server, seed=2)

    def broken_surge(host, port, rate, dur, seed):
        raise RuntimeError("generator never started")

    sched = make_schedule(
        9, 1, duration_s=0.4, include=(),
        surge_rate=500.0, surge_dur_s=0.1,
    )
    nem = Nemesis([(server.host, server.port)], surge_fire=broken_surge)
    try:
        with pytest.raises(NemesisVerificationError, match="load_surge"):
            nem.run(sched)
        (w,) = nem.windows
        assert not w["acked"] and "surge burst failed" in w["excused"]
    finally:
        nem.close()
        server.close()


# ---------------------------------------------------------------------------
# Chaos over real sockets (RpcNode level)
# ---------------------------------------------------------------------------


class _Echo:
    def ping(self, args):
        return ("pong", args)


@needs_native
def test_chaos_block_heals_and_control_plane_exempt():
    """An isolated node times out data RPCs but still answers its
    "Chaos" control service — the harness can always heal."""
    from multiraft_tpu.distributed.tcp import RpcNode

    server = RpcNode(listen=True)
    server.add_service("Echo", _Echo())
    install_chaos(server, seed=3)
    client = RpcNode()
    try:
        addr = (server.host, server.port)
        end = client.client_end(*addr)
        assert client.sched.wait(end.call("Echo.ping", 1), 5.0) == ("pong", 1)

        ctl = ChaosClient([addr])
        try:
            ctl.set_rules(addr, {"all_in": {"block": True}})
            # Data path dark...
            assert client.sched.wait(end.call("Echo.ping", 2), 0.5) is TIMEOUT
            # ...control path alive (the exemption under test).
            assert ctl.ping(addr)
            stats = ctl.stats(addr)
            assert stats["dropped"] >= 1
            ctl.clear(addr)
            assert client.sched.wait(
                end.call("Echo.ping", 3), 5.0
            ) == ("pong", 3)
        finally:
            ctl.close()
    finally:
        client.close()
        server.close()


@needs_native
def test_sever_cuts_connections_then_reconnects():
    from multiraft_tpu.distributed.tcp import RpcNode

    server = RpcNode(listen=True)
    server.add_service("Echo", _Echo())
    install_chaos(server, seed=0)
    client = RpcNode()
    try:
        addr = (server.host, server.port)
        end = client.client_end(*addr)
        assert client.sched.wait(end.call("Echo.ping", 1), 5.0) == ("pong", 1)
        ctl = ChaosClient([addr])
        try:
            assert ctl.sever(addr) >= 1
        finally:
            ctl.close()
        # The client's cached conn died; the next call redials.
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if client.sched.wait(
                end.call("Echo.ping", 2), 2.0
            ) == ("pong", 2):
                break
        else:
            pytest.fail("client never reconnected after sever")
    finally:
        client.close()
        server.close()


@needs_native
def test_lock_order_acyclic_under_chaos_traffic():
    """Dynamic cross-check of the static lock-graph audit (graftlint's
    lock-order rule): wrap the named locks of the live transport stack
    in a LockOrderRecorder, drive real traffic through chaos faults
    and a sever, and assert the *observed* acquisition-order graph is
    acyclic.  The static audit approximates; this is the runtime
    ground truth for the paths the chaos tests exercise."""
    from multiraft_tpu.analysis import LockOrderRecorder
    from multiraft_tpu.distributed.tcp import RpcNode

    rec = LockOrderRecorder()
    server = RpcNode(listen=True)
    server.add_service("Echo", _Echo())
    chaos = install_chaos(server, seed=11)
    client = RpcNode()
    for node, tag in ((server, "server"), (client, "client")):
        rec.wrap(node, "_lock", f"RpcNode._lock[{tag}]")
        rec.wrap(node._tr, "_lock", f"NativeTransport._lock[{tag}]")
    rec.wrap(chaos, "_lock", "ChaosState._lock[server]")
    try:
        addr = (server.host, server.port)
        end = client.client_end(*addr)
        assert client.sched.wait(end.call("Echo.ping", 0), 5.0) == ("pong", 0)
        ctl = ChaosClient([addr])
        try:
            # Exercise every chaos decision branch: drop+delay coin
            # flips (RNG under the state lock) and the block branch.
            ctl.set_rules(
                addr, {"all_in": {"drop": 0.3, "delay": 0.3,
                                  "delay_min": 0.001, "delay_max": 0.005}}
            )
            for i in range(20):
                client.sched.wait(end.call("Echo.ping", i), 0.5)
            ctl.set_rules(addr, {"all_in": {"block": True}})
            assert client.sched.wait(end.call("Echo.ping", 99), 0.3) is TIMEOUT
            ctl.clear(addr)
            assert ctl.sever(addr) >= 0
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if client.sched.wait(
                    end.call("Echo.ping", 100), 2.0
                ) == ("pong", 100):
                    break
            else:
                pytest.fail("client never reconnected after sever")
        finally:
            ctl.close()
    finally:
        client.close()
        server.close()
    # traffic must actually have produced nesting before the assert
    # means anything (RpcNode holds its lock while dialing transport)
    assert rec.edges, "recorder saw no nested acquisitions"
    rec.assert_acyclic()


# ---------------------------------------------------------------------------
# Seeded chaos smoke vs a live engine process (tier-1)
# ---------------------------------------------------------------------------


@needs_native
@pytest.mark.timeout_s(240)
def test_chaos_smoke_engine_cluster_linearizable():
    """A seeded drop/delay/sever schedule against one engine server
    process under concurrent clerk load: every op completes (faults
    heal, clerks retry) and the client-observed history stays
    linearizable.  The schedule itself is reproducible from its seed —
    and the observability plane sees the run: Obs.snapshot returns the
    server's RPC/engine counters, every window verifies as fired, and
    the merged trace carries one clerk request's id in BOTH the clerk
    process's span and the server process's dispatch span."""
    from multiraft_tpu.distributed.cluster import EngineProcessCluster
    from multiraft_tpu.harness.observe import FleetObserver
    from multiraft_tpu.porcupine.kv import kv_model
    from multiraft_tpu.porcupine.visualization import assert_linearizable

    schedule = make_schedule(
        seed=5, n_procs=1, duration_s=5.0,
        include=("delay", "drop", "sever"),
        fault_s=(0.4, 1.2), quiet_s=(0.2, 0.5),
    )
    assert schedule == make_schedule(
        seed=5, n_procs=1, duration_s=5.0,
        include=("delay", "drop", "sever"),
        fault_s=(0.4, 1.2), quiet_s=(0.2, 0.5),
    )
    assert len(schedule) > 2  # heal + at least two fault windows

    cluster = EngineProcessCluster(
        kind="engine_kv", groups=16, seed=3, chaos_seed=7
    )
    try:
        cluster.start()
        addr = (cluster.host, cluster.port)
        nem = Nemesis([addr])
        obs = FleetObserver([addr])
        clerk_events: list = []
        try:
            runner = nem.run_async(schedule)
            history = run_clerk_load(
                cluster.clerk, keys=["ca", "cb"],
                n_workers=3, ops_per_worker=9, op_timeout=60.0,
                trace_sink=clerk_events,
            )
            runner.join(timeout=60.0)
            assert not runner.is_alive()
            assert nem.error is None
            # Ran to the final heal, and the server is reachable clean.
            assert nem.applied[-1][1] == "heal"
            assert nem.ctl.ping(addr)
            # Every scheduled window demonstrably fired.
            assert len(nem.windows) == len(schedule) - 1  # all but heal
            nem.verify_windows()

            # Scrapeable per-process counters, live over the socket.
            snap = obs.snapshot(addr)
            assert snap is not None
            m = snap["metrics"]
            assert m["rpc.handled"] > 0 and m["rpc.frames_in"] > 0
            assert m["kv.writes"] >= 18  # the appends (plus retries)
            assert "rpc.handle_s_p50" in m
            # The hit ledger rides along (may be empty if the short
            # load drained before a storm window saw traffic).
            assert "hits" in snap["chaos"]

            # One merged, clock-aligned timeline: the same request id
            # in the clerk's span (pid 0) and the server's (pid 1).
            merged = obs.merged_timeline(
                local_events=clerk_events, windows=nem.windows,
            )
            assert obs.unreachable == []
            rids = {
                e["args"]["req"]
                for e in merged.events
                if e["ph"] == "X" and e["pid"] == 0
                and e["tid"] == "clerk"
            }
            assert rids
            server_rids = {
                e["args"].get("req")
                for e in merged.events
                if e["ph"] == "X" and e["pid"] == 1
            }
            assert rids & server_rids, (rids, server_rids)
            # Window annotations ride the nemesis track.
            assert sum(
                1 for e in merged.events if e.get("tid") == "nemesis"
            ) == len(nem.windows)
        finally:
            obs.close()
            nem.close()
        assert len(history) == 27
        assert_linearizable(
            kv_model, history, timeout=30.0, name="chaos-smoke"
        )
    finally:
        cluster.shutdown()


# ---------------------------------------------------------------------------
# Full nemesis: partitions + delays + crash/restart-from-WAL (slow)
# ---------------------------------------------------------------------------


@needs_native
@pytest.mark.slow
@pytest.mark.timeout_s(600)
def test_nemesis_fleet_partition_delay_crash_restart(tmp_path):
    """The acceptance scenario end to end: a seeded schedule of
    partitions, delay/drop storms, severs, and one crash+restart-from-
    WAL runs against a two-process durable engine fleet over real
    sockets while clerks apply load; everything completes and the
    history passes porcupine.

    The observability acceptance rides the same run: Obs.snapshot
    scraped MID-RUN returns non-empty per-process counters (RPC totals
    + WAL fsync latency percentiles), every scheduled window verifies
    as fired, and the run emits ONE merged clock-aligned trace JSON in
    which a single clerk request's spans appear in both the clerk and
    a server process under the same request id and every window is
    annotated — smoke-validated through scripts/trace_summary.py."""
    import json
    import threading

    from multiraft_tpu.distributed.engine_cluster import EngineFleetCluster
    from multiraft_tpu.harness.observe import FleetObserver
    from multiraft_tpu.porcupine.kv import kv_model
    from multiraft_tpu.porcupine.visualization import assert_linearizable

    kw = dict(
        duration_s=12.0,
        include=("delay", "drop", "partition", "sever"),
        crash_procs=[0], crash_down_s=1.0,
        fault_s=(0.5, 1.5), quiet_s=(0.3, 0.8),
    )
    schedule = make_schedule(11, 2, **kw)
    assert schedule == make_schedule(11, 2, **kw)
    assert any(k == "crash" for _, k, _ in schedule)

    fleet = EngineFleetCluster(
        [[1], [2]], seed=9, data_dir=str(tmp_path / "fleet"),
        checkpoint_every_s=3600.0,  # recovery must come from the WAL
        chaos_seed=11,
    )
    try:
        fleet.start_all()
        fleet.admin("join", [1])
        fleet.admin("join", [2])
        addrs = [(fleet.host, p) for p in fleet.ports]
        nem = Nemesis(addrs, kill=fleet.kill, restart=fleet.start)
        obs = FleetObserver(addrs)
        clerk_events: list = []
        mid_snaps: dict = {}

        def scrape_mid_run(stop: threading.Event) -> None:
            # Accumulate every successful snapshot per process while
            # faults are live (a crashed process skips a round, and a
            # restarted one comes back with reset counters).
            while not stop.wait(1.5):
                for key, snap in obs.snapshot_all().items():
                    mid_snaps.setdefault(key, []).append(snap)

        try:
            runner = nem.run_async(schedule)
            stop_scrape = threading.Event()
            scraper = threading.Thread(
                target=scrape_mid_run, args=(stop_scrape,), daemon=True
            )
            scraper.start()
            history = run_clerk_load(
                fleet.clerk, keys=["na", "nb", "nc"],
                n_workers=3, ops_per_worker=9, op_timeout=240.0,
                trace_sink=clerk_events,
            )
            runner.join(timeout=400.0)
            stop_scrape.set()
            scraper.join(timeout=10.0)
            assert not runner.is_alive()
            assert nem.error is None
            kinds = [(ph, k) for ph, k, _ in nem.applied]
            assert ("start", "crash") in kinds  # SIGKILL happened
            assert ("stop", "crash") in kinds   # ...and WAL recovery
            assert nem.applied[-1][1] == "heal"
            for a in addrs:
                assert nem.ctl.ping(a)

            # Every scheduled fault window demonstrably fired.
            assert len(nem.windows) == len(schedule) - 1  # all but heal
            nem.verify_windows()

            # Mid-run scrapes saw every process, with RPC totals and
            # WAL fsync percentiles (the fleet is durable).
            assert len(mid_snaps) == len(addrs), mid_snaps.keys()
            for key, snaps in mid_snaps.items():
                assert any(
                    not s.get("missing")
                    and s["metrics"]["rpc.handled"] > 0
                    and s["metrics"]["rpc.frames_in"] > 0
                    and s["metrics"]["rpc.bytes_in"] > 0
                    and "wal.fsync_s_p50" in s["metrics"]
                    and "wal.fsync_s_p99" in s["metrics"]
                    for s in snaps
                ), (key, snaps[-1])

            # ONE merged clock-aligned trace, nemesis-annotated.
            merged = obs.merged_timeline(
                local_events=clerk_events, windows=nem.windows,
                schedule=schedule, t0_us=nem.t0_us,
            )
            trace_path = str(tmp_path / "trace_nemesis.json.gz")
            merged.save(trace_path)
            snap_path = str(tmp_path / "metrics_nemesis.json")
            with open(snap_path, "w") as f:
                json.dump(obs.snapshot_all(), f, indent=2, sort_keys=True)

            # (a) one clerk request's spans in clerk AND server
            # processes under the same request id.
            clerk_rids = {
                e["args"]["req"] for e in merged.events
                if e["ph"] == "X" and e["pid"] == 0 and e["tid"] == "clerk"
            }
            server_rids = {
                e["args"].get("req") for e in merged.events
                if e["ph"] == "X" and e["pid"] >= 1
            }
            assert clerk_rids & server_rids, (clerk_rids, server_rids)
            # (b) every scheduled fault window annotated on the
            # nemesis track, plus the planned-schedule instants.
            annotated = [
                e for e in merged.events if e.get("tid") == "nemesis"
            ]
            assert len(annotated) == len(nem.windows)
            assert sorted(e["name"] for e in annotated) == sorted(
                k for _, k, _ in schedule if k != "heal"
            )
            assert sum(
                1 for e in merged.events if e.get("tid") == "nemesis-plan"
            ) == len(schedule)

            # The artifact is loadable and summarizable.
            from scripts.trace_summary import summarize

            s = summarize(trace_path)
            assert s["spans"] > 0 and s["events"] == len(merged.events)
            assert 0 in s["process_names"]
        finally:
            obs.close()
            nem.close()
        assert len(history) == 27
        assert_linearizable(
            kv_model, history, timeout=60.0, name="nemesis-fleet"
        )
    finally:
        fleet.shutdown()


# ---------------------------------------------------------------------------
# Satellite hardening: firehose wire/route validation + ack ordering
# ---------------------------------------------------------------------------


def _frame_blob(ops, groups, clients, commands, keys, vals):
    from multiraft_tpu.engine.firehose import pack_request

    n = len(ops)
    return pack_request(
        np.asarray(ops, np.uint8), np.asarray(groups, np.uint32),
        np.asarray(clients, np.uint64), np.asarray(commands, np.uint64),
        keys, vals,
    )


def test_pack_request_rejects_oversized_key():
    with pytest.raises(ValueError, match="row 1 .* caps keys"):
        _frame_blob(
            [1, 1], [0, 0], [7, 7], [1, 2],
            [b"ok", b"x" * 2 ** 16], [b"v", b"v"],
        )
    # One byte under the cap still packs.
    _frame_blob([1], [0], [7], [1], [b"x" * (2 ** 16 - 1)], [b"v"])


def test_submit_frame_validates_route_group():
    """With route_check installed (the plain-KV service does), a frame
    whose group column disagrees with the canonical key hash is
    rejected before any run starts."""
    from multiraft_tpu.distributed.engine_wire import route_group
    from multiraft_tpu.engine.kv import BatchedKV

    G = 8
    runs = []
    stub = types.SimpleNamespace(
        driver=types.SimpleNamespace(
            cfg=types.SimpleNamespace(G=G),
            start_run=lambda g, f, rows: runs.append((g, len(rows))),
        ),
        route_check=route_group,
        _now=lambda: 0,
    )
    g = route_group("a", G)
    ok = _frame_blob([1], [g], [7], [1], [b"a"], [b"v"])
    BatchedKV.submit_frame(stub, ok)
    assert runs == [(g, 1)]
    bad = _frame_blob([1], [(g + 1) % G], [7], [2], [b"a"], [b"v"])
    with pytest.raises(ValueError, match="row 0 .* expected"):
        BatchedKV.submit_frame(stub, bad)
    assert len(runs) == 1  # nothing started for the rejected frame


def test_bind_accepted_rejects_oversized_batch():
    from multiraft_tpu.engine.host import EngineDriver

    stub = types.SimpleNamespace(
        cfg=types.SimpleNamespace(INGEST=8),
        _max_bound={}, payloads={}, _pending_payloads={},
    )
    EngineDriver._bind_accepted(stub, 0, 1, 0, None)  # in-bounds: fine
    with pytest.raises(AssertionError, match="exceeds cfg.INGEST"):
        EngineDriver._bind_accepted(stub, 0, 9, 0, None)


def _drive(gen, sched, step_s=1.0):
    """Run a handler generator to completion, advancing the stub clock
    at every yield, and return its StopIteration value."""
    while True:
        try:
            next(gen)
        except StopIteration as stop:
            return stop.value
        sched.now += step_s


def _firehose_reply(synced: bool):
    """Drive the plain-KV firehose handler with a stub durability layer
    whose fsync either lands or never does."""
    from multiraft_tpu.distributed.engine_server import EngineKVService
    from multiraft_tpu.engine.firehose import FirehoseFrame, unpack_reply
    from multiraft_tpu.utils.metrics import Metrics

    blob = _frame_blob(
        [1, 0], [0, 0], [7, 7], [1, 0], [b"k", b"k"], [b"v", b""],
    )

    def submit_frame(raw):
        f = FirehoseFrame(raw, 0)
        f.rows_applied(f.write_rows)  # the write applied in memory...
        return f

    svc = EngineKVService.__new__(EngineKVService)
    svc.sched = types.SimpleNamespace(now=0.0)
    svc.kv = types.SimpleNamespace(
        submit_frame=submit_frame,
        get=lambda g, key: types.SimpleNamespace(value="applied-v"),
    )
    svc._dur = types.SimpleNamespace(synced=lambda seq: synced)
    svc._write_seqs = {(7, 1): 42}
    svc.m = Metrics()

    def wait(until):  # PumpCycle.wait, on the stub clock: no pump ever ends
        if svc.sched.now >= until:
            return False
        yield until - svc.sched.now
        return True

    svc.cycle = types.SimpleNamespace(wait=wait)
    out = _drive(svc.firehose(blob), svc.sched)
    return unpack_reply(out)


def test_firehose_get_gated_behind_unsynced_write():
    """Crash-before-fsync regression (the plain handler must demote
    BEFORE the Get gate, as the sharded one does): when a frame's write
    applied but its WAL record never fsyncs, the write demotes to RETRY
    — and the frame's own Get must NOT answer from the applied state a
    crash could still un-happen."""
    from multiraft_tpu.engine.firehose import FH_OK, FH_RETRY

    err, values = _firehose_reply(synced=False)
    assert err.tolist() == [FH_RETRY, FH_RETRY]
    assert values[1] == ""  # no read past the durability gate
    # Control: once the fsync lands, both rows ack and the Get answers.
    err, values = _firehose_reply(synced=True)
    assert err.tolist() == [FH_OK, FH_OK]
    assert values[1] == "applied-v"


# ---------------------------------------------------------------------------
# Gray-fault verbs: slow_link floor, fsync_stall, asym/partial partitions
# ---------------------------------------------------------------------------


class TestGrayFaults:
    def test_floor_rule_wire_roundtrip_and_deterministic_delay(self):
        r = ChaosRule(floor=0.08)
        assert ChaosRule.from_wire(r.to_wire()).floor == 0.08
        st = ChaosState(seed=3)
        st.all_in = ChaosRule(floor=0.05)
        # No coin flip: EVERY frame pays exactly the floor.
        assert [st.decide_in() for _ in range(5)] == [0.05] * 5
        assert st.hits["all_in"]["floor"] == 5
        assert st.delayed == 5

    def test_floor_raises_probabilistic_delay_draws(self):
        st = ChaosState(seed=4)
        st.all_in = ChaosRule(
            delay=1.0, delay_min=0.0, delay_max=0.01, floor=0.5
        )
        for _ in range(10):
            d = st.decide_in()
            assert isinstance(d, float) and d >= 0.5

    def test_note_fault_enters_hit_ledger(self):
        st = ChaosState(seed=0)
        st.note_fault("disk", "fsync_stall")
        st.note_fault("disk", "fsync_stall")
        assert st.hits["disk"]["fsync_stall"] == 2
        assert st.snapshot()["hits"]["disk"]["fsync_stall"] == 2

    def test_gray_kinds_have_flightrec_codes(self):
        from multiraft_tpu.distributed.flightrec import CHAOS_KIND_CODES

        assert CHAOS_KIND_CODES["floor"] != CHAOS_KIND_CODES["delay"]
        assert "fsync_stall" in CHAOS_KIND_CODES

    def test_fsync_stall_applies_to_persister_and_ledgers(self, tmp_path):
        from multiraft_tpu.distributed import disk

        st = ChaosState(seed=0)
        disk.set_fsync_stall(0.01, chaos=st)
        try:
            p = disk.DiskPersister(str(tmp_path / "d"), fsync=True)
            t0 = time.perf_counter()
            p.save_raft_state(b"x")
            assert time.perf_counter() - t0 >= 0.01
            assert st.hits["disk"]["fsync_stall"] >= 1
        finally:
            disk.set_fsync_stall(0.0)
        n = st.hits["disk"]["fsync_stall"]
        p.save_raft_state(b"y")  # stall lifted: no new hits
        assert st.hits["disk"]["fsync_stall"] == n

    def test_fsync_stall_applies_to_wal_sync(self, tmp_path):
        from multiraft_tpu.distributed import disk
        from multiraft_tpu.distributed.wal import WriteAheadLog

        st = ChaosState(seed=0)
        wal = WriteAheadLog(str(tmp_path / "w.wal"), fsync=True)
        disk.set_fsync_stall(0.01, chaos=st)
        try:
            wal.append(b"rec")
            wal.sync()
            assert st.hits["disk"]["fsync_stall"] >= 1
            # The stall lands inside the measured fsync latency, where
            # the postmortem doctor's fsync-gap scan looks.
            assert wal.metrics.hists["wal.fsync_s"].vmax >= 0.01
        finally:
            disk.set_fsync_stall(0.0)
            wal.close()

    def test_chaos_control_fsync_stall_verb_and_clear_lifts(self):
        from multiraft_tpu.distributed import disk
        from multiraft_tpu.distributed.chaos import ChaosControl

        st = ChaosState(seed=0)
        ctl = ChaosControl(None, st)
        try:
            assert ctl.fsync_stall([0.02]) == 0.02
            assert disk._stall_s == 0.02
            # clear() is the nemesis's heal-all: it must leave no
            # residual gray-disk fault behind.
            ctl.clear()
            assert disk._stall_s == 0.0
            assert ctl.fsync_stall([0.0]) == 0.0
        finally:
            disk.set_fsync_stall(0.0)

    def test_make_schedule_gray_kinds_deterministic(self):
        gray = ("asym_partition", "partial_partition", "slow_link",
                "fsync_stall")
        s1 = make_schedule(3, 3, duration_s=9.0, include=gray)
        assert s1 == make_schedule(3, 3, duration_s=9.0, include=gray)
        kinds = {k for _, k, _ in s1}
        assert kinds - {"heal"} <= set(gray)
        assert len(kinds - {"heal"}) >= 2
        for _, k, p in s1:
            if k == "slow_link":
                assert 0.0 < p["floor"] < 1.0
            if k == "fsync_stall":
                assert 0.0 < p["stall"] < 1.0
            if k == "asym_partition":
                assert p["a"] != p["b"]

    def test_gray_pairwise_kinds_need_two_procs(self):
        sched = make_schedule(
            3, 1, duration_s=6.0,
            include=("asym_partition", "partial_partition", "slow_link",
                     "fsync_stall"),
        )
        kinds = {k for _, k, _ in sched}
        assert "asym_partition" not in kinds
        assert "partial_partition" not in kinds
        assert kinds & {"slow_link", "fsync_stall"}

    def test_hit_specs_for_gray_kinds(self):
        addrs = [("h", 1), ("h", 2), ("h", 3)]
        # One-way: only a's outbound edge must show block hits.
        assert Nemesis._hit_spec(
            "asym_partition", {"a": 0, "b": 2}, addrs
        ) == [(("h", 1), ["peer:h:3"], ("block",))]
        # Partial: the target blocks every other engine proc, and each
        # of them blocks the target back — client paths carry no rule.
        spec = Nemesis._hit_spec("partial_partition", {"proc": 1}, addrs)
        assert spec[0] == (("h", 2), ["peer:h:1", "peer:h:3"], ("block",))
        assert (("h", 1), ["peer:h:2"], ("block",)) in spec
        assert (("h", 3), ["peer:h:2"], ("block",)) in spec
        assert len(spec) == 3
        # Pinned survivor list (stop-time symmetry) narrows the spec.
        spec = Nemesis._hit_spec(
            "partial_partition", {"proc": 1, "others": [2]}, addrs
        )
        assert spec == [
            (("h", 2), ["peer:h:3"], ("block",)),
            (("h", 3), ["peer:h:2"], ("block",)),
        ]
        assert Nemesis._hit_spec("slow_link", {"proc": 0}, addrs) == [
            (("h", 1), ["all_in"], ("floor",))
        ]
        assert Nemesis._hit_spec("fsync_stall", {"proc": 2}, addrs) == [
            (("h", 3), ["disk"], ("fsync_stall",))
        ]


@needs_native
@pytest.mark.slow
@pytest.mark.timeout_s(600)
def test_nemesis_gray_faults_fleet_linearizable(tmp_path):
    """Gray-failure acceptance: a seeded schedule of asymmetric and
    partial partitions, slow links, and fsync stalls runs against a
    two-process durable engine fleet under clerk load.  Every window
    verifies as fired — with slow_link and fsync_stall REQUIRED to show
    applied faults (clerk traffic and durable writes guarantee both see
    load) — and the client-observed history stays linearizable: gray
    faults degrade, they must not corrupt."""
    from multiraft_tpu.distributed.engine_cluster import EngineFleetCluster
    from multiraft_tpu.porcupine.kv import kv_model
    from multiraft_tpu.porcupine.visualization import assert_linearizable

    gray = ("asym_partition", "partial_partition", "slow_link",
            "fsync_stall")
    kw = dict(
        duration_s=10.0, include=gray,
        fault_s=(0.5, 1.4), quiet_s=(0.3, 0.8),
    )
    schedule = make_schedule(21, 2, **kw)
    assert schedule == make_schedule(21, 2, **kw)
    kinds = {k for _, k, _ in schedule}
    assert len(kinds - {"heal"}) >= 2  # a real gray mix scheduled

    fleet = EngineFleetCluster(
        [[1], [2]], seed=17, data_dir=str(tmp_path / "fleet"),
        checkpoint_every_s=3600.0, chaos_seed=23,
    )
    try:
        fleet.start_all()
        fleet.admin("join", [1])
        fleet.admin("join", [2])
        addrs = [(fleet.host, p) for p in fleet.ports]
        # Distinct first letters → distinct shards: with two gids
        # owning five shards each, six distinct shards guarantee BOTH
        # processes receive durable writes (fsync_stall's required
        # hits need an fsync at the faulted process mid-window; keys
        # on one shard would leave the other process fsync-idle).
        keys = ["aw", "bw", "cw", "dw", "ew", "fw"]
        # Continuous durable traffic on DISJOINT keys (the porcupine
        # model below starts from empty state, so these values must
        # never appear in a checked Get).  fsync_stall's required hits
        # need a WAL sync at the faulted process MID-WINDOW, but the
        # 27-op checked load finishes in a couple of seconds while the
        # nemesis runs ~10 s — without a pump, later windows are
        # write-idle and verify_windows fails with zero applied
        # faults.  One blocking pass first (leaders elected, first
        # fsyncs done) so even the earliest window sees real writes.
        bg_keys = ["gw", "hw", "iw", "jw", "kw", "lw"]
        import threading

        warm = fleet.clerk()
        for k in bg_keys:
            warm.append(k, "(warm)", timeout=60.0)
        stop_bg = threading.Event()

        def _pump():
            i = 0
            while not stop_bg.is_set():
                try:
                    warm.append(bg_keys[i % len(bg_keys)], "+",
                                timeout=5.0)
                except Exception:
                    time.sleep(0.05)
                i += 1

        bg = threading.Thread(target=_pump, daemon=True)
        bg.start()
        nem = Nemesis(addrs, kill=fleet.kill, restart=fleet.start)
        try:
            runner = nem.run_async(schedule)
            history = run_clerk_load(
                fleet.clerk, keys=keys,
                n_workers=3, ops_per_worker=9, op_timeout=240.0,
            )
            runner.join(timeout=400.0)
            stop_bg.set()
            bg.join(timeout=30.0)
            warm.close()
            assert not runner.is_alive()
            assert nem.error is None
            assert nem.applied[-1][1] == "heal"
            for a in addrs:
                assert nem.ctl.ping(a)
                # The heal-all left no residual gray-disk stall: fresh
                # writes ack at normal speed (stats still reachable).
                assert nem.ctl.stats(a) is not None
            assert len(nem.windows) == len(schedule) - 1
            applied_kinds = {w["kind"] for w in nem.windows}
            assert applied_kinds == kinds - {"heal"}
            nem.verify_windows(
                require_hits=("slow_link", "fsync_stall")
            )
        finally:
            stop_bg.set()
            nem.close()
        assert len(history) == 27
        assert_linearizable(
            kv_model, history, timeout=60.0, name="gray-nemesis"
        )
    finally:
        fleet.shutdown()
