"""Profiling-plane tests: the continuous sampling profiler (lifecycle
idempotence, self-accounted overhead bound, folded-stack determinism,
bounded memory under stack churn), the folded-stack algebra the fleet
merger and CLI share, the ``Obs.profile`` drain-on-read verb over a
live socket, per-stage CPU segment accounting on the serve path, the
process resource gauges, and the postmortem doctor's "CPU saturation"
vs "queueing collapse" discrimination on synthetic flight rings."""

from __future__ import annotations

import threading
import time

import pytest

from multiraft_tpu.distributed.profile import (
    OVERFLOW_FRAME,
    SamplingProfiler,
    diff_folded,
    fold_frame,
    from_collapsed,
    merge_folded,
    per_thread_totals,
    to_collapsed,
    top_functions,
)


def _parked_thread(name):
    """A named thread parked in a recognizable 3-frame call chain;
    returns ``(thread, release_event)``."""
    release = threading.Event()
    ready = threading.Event()

    def outer_frame():
        middle_frame()

    def middle_frame():
        inner_wait()

    def inner_wait():
        ready.set()
        release.wait(10.0)

    t = threading.Thread(target=outer_frame, name=name, daemon=True)
    t.start()
    assert ready.wait(5.0)
    return t, release


# ---------------------------------------------------------------------------
# Sampler core
# ---------------------------------------------------------------------------


class TestSampler:
    def test_start_stop_idempotent(self):
        p = SamplingProfiler(hz=200)
        assert not p.running
        p.stop()  # stop before start: no-op
        p.start()
        assert p.running
        t1 = p._thread
        p.start()  # second start: same thread, no respawn
        assert p._thread is t1
        p.stop()
        assert not p.running
        p.stop()  # double stop: no-op
        # restartable after stop
        p.start()
        assert p.running
        p.stop()

    def test_sampler_collects_named_thread_stacks(self):
        t, release = _parked_thread("profiled-worker")
        p = SamplingProfiler(hz=500)
        try:
            p.start()
            deadline = time.time() + 5.0
            while time.time() < deadline:
                snap = p.snapshot()
                mine = [k for k in snap["stacks"]
                        if k.startswith("profiled-worker;")]
                if mine:
                    break
                time.sleep(0.02)
            assert mine, snap["stacks"]
            # The parked chain is attributed leaf-ward: the wait frame
            # is the leaf, the nest is on the stack.
            assert any("inner_wait" in k for k in mine)
            assert any("middle_frame" in k for k in mine)
        finally:
            p.stop()
            release.set()
            t.join(2.0)

    def test_overhead_bound_self_accounted(self):
        """The sampler's own CPU (self_cpu_s, thread_time-accounted)
        stays under 2% of a second at the default rate — the budget
        that justifies MRT_PROFILE defaulting on.  Held per sample
        taken, not against one second of wall: under a loaded box the
        wall stretches and the sampler fires late, which says nothing
        about what a sample costs.  A sample walks every live thread
        (~7 us each), and an xdist worker carries whatever threads the
        files before this one left parked, so the budget is for the
        dozen threads a serving process runs and scales past that."""
        p = SamplingProfiler()  # default hz
        p.start()
        time.sleep(1.0)
        p.stop()
        snap = p.snapshot()
        assert snap["samples"] > 10  # it actually ran
        per_sample = snap["self_cpu_s"] / snap["samples"]
        budget = 0.02 / p.hz  # 2% of each sampling interval
        threads = threading.active_count()
        assert per_sample < budget * max(1.0, threads / 12.0), (
            snap, threads)

    def test_folded_stack_determinism(self):
        """Two samples of the same parked call chain fold to the same
        key (count 2), and fold_frame itself is deterministic."""
        t, release = _parked_thread("det-worker")
        p = SamplingProfiler()  # never started: sample_once directly
        try:
            p.sample_once()
            p.sample_once()
            mine = {k: v for k, v in p.stacks.items()
                    if k.startswith("det-worker;")}
            assert len(mine) == 1, mine
            ((key, count),) = mine.items()
            assert count == 2
            # Root-first ordering: outer before middle before inner.
            frames = key.split(";")[1:]
            i_outer = next(i for i, f in enumerate(frames)
                           if "outer_frame" in f)
            i_inner = next(i for i, f in enumerate(frames)
                           if "inner_wait" in f)
            assert i_outer < i_inner
        finally:
            release.set()
            t.join(2.0)

    def test_depth_cap_keeps_leaf_collapses_root(self):
        def rec(n):
            if n == 0:
                return fold_frame(__import__("sys")._getframe(), depth=4)
            return rec(n - 1)

        folded = rec(20)
        frames = folded.split(";")
        assert frames[0] == "(...)"  # truncation marker at the root
        assert len(frames) == 5  # marker + depth frames
        assert "rec" in frames[-1]  # the leaf survived

    def test_bounded_memory_under_stack_churn(self):
        """With more distinct stacks than max_stacks, extra stacks fold
        into per-thread (overflow) buckets: the aggregate stays bounded
        by max_stacks + one bucket per thread, and the overflow counter
        says what was dropped."""
        parked = [_parked_thread(f"churn-{i}") for i in range(4)]
        p = SamplingProfiler(max_stacks=2)
        try:
            for _ in range(3):
                p.sample_once()
            n_threads = len(per_thread_totals(p.stacks))
            assert len(p.stacks) <= 2 + n_threads
            assert p.overflow > 0
            assert any(k.endswith(f";{OVERFLOW_FRAME}")
                       for k in p.stacks)
        finally:
            for t, release in parked:
                release.set()
                t.join(2.0)

    def test_drain_resets_snapshot_does_not(self):
        t, release = _parked_thread("drain-worker")
        p = SamplingProfiler()
        try:
            p.sample_once()
            s1 = p.snapshot()
            assert s1["samples"] == 1 and s1["stacks"]
            s2 = p.snapshot()  # snapshot is a pure read
            assert s2["samples"] == 1
            d = p.drain()
            assert d["samples"] == 1 and d["stacks"]
            after = p.snapshot()
            assert after["samples"] == 0 and not after["stacks"]
        finally:
            release.set()
            t.join(2.0)


def test_default_hz_env_override_and_host_adaptation(monkeypatch):
    """MRT_PROFILE_HZ wins unconditionally; without it the default is
    one of the two host-shaped primes (67 multi-core, 19 on 1 CPU)."""
    from multiraft_tpu.distributed import profile as prof

    monkeypatch.setenv("MRT_PROFILE_HZ", "31")
    assert prof._default_hz() == 31.0
    monkeypatch.delenv("MRT_PROFILE_HZ")
    assert prof._default_hz() in (67.0, 19.0)


# ---------------------------------------------------------------------------
# Folded-stack algebra (pure)
# ---------------------------------------------------------------------------


class TestFoldedAlgebra:
    def test_merge_and_per_thread_totals(self):
        a = {"loop;m.f;m.g": 3, "loop;m.f": 1}
        b = {"loop;m.f;m.g": 2, "pump;m.h": 5}
        m = merge_folded([a, b])
        assert m == {"loop;m.f;m.g": 5, "loop;m.f": 1, "pump;m.h": 5}
        assert per_thread_totals(m) == {"loop": 6, "pump": 5}

    def test_diff_folded_clamps_and_drops_zero(self):
        after = {"t;a": 5, "t;b": 2, "t;c": 1}
        before = {"t;a": 3, "t;b": 2, "t;d": 9}
        assert diff_folded(after, before) == {"t;a": 2, "t;c": 1}

    def test_top_functions_self_vs_cum(self):
        folded = {
            "loop;m.outer;m.hot": 6,
            "loop;m.outer;m.cold": 1,
            "loop;m.outer": 2,
            # recursion: hot appears twice on one stack, counted once
            "loop;m.hot;m.hot": 3,
        }
        top = top_functions(folded, 3)
        assert top[0]["func"] == "m.hot"
        assert top[0]["self"] == 9  # 6 + 3 leaf samples
        assert top[0]["cum"] == 9  # once per stack, no double count
        outer = next(t for t in top if t["func"] == "m.outer")
        assert outer["self"] == 2 and outer["cum"] == 9

    def test_collapsed_round_trip(self):
        folded = {"loop;m.f;m.g": 3, "pump;m.h": 5}
        assert from_collapsed(to_collapsed(folded)) == folded
        # tolerant of blanks and junk counts
        text = to_collapsed(folded) + "\n\nnot-a-count x\n"
        assert from_collapsed(text) == folded

    def test_fleet_flame_prefixes_process(self):
        from multiraft_tpu.harness.observe import FleetObserver

        dumps = {
            "h:1": {"name": "p1", "pid": 11,
                    "profile": {"samples": 3, "stacks": {"loop;m.f": 3}}},
            "h:2": {"name": "p2", "pid": 22,
                    "profile": {"samples": 2, "stacks": {"loop;m.f": 2}}},
            "h:3": {"missing": True},
            "h:4": {"name": "p4", "pid": 44, "profile": None},
        }
        flame = FleetObserver.fleet_flame(dumps)
        assert flame == {"p1;loop;m.f": 3, "p2;loop;m.f": 2}

    def test_profile_window_ranks_serving_threads_only(self):
        """A parked main thread samples at the same rate as a pegged
        loop; the loadcurve headline must rank the loop's functions,
        with the all-threads cut preserved alongside."""
        from multiraft_tpu.harness.loadcurve import profile_window

        class _FakeFleet:
            def profile_all(self):
                return {
                    "h:1": {"name": "p1", "pid": 1, "profile": {
                        "samples": 20, "stacks": {
                            "MainThread;cluster._server_main": 10,
                            "multiraft-loop/9001;tcp._run;codec.decode": 6,
                            "multiraft-loop/9001;host.step": 4,
                        }}},
                }

        win = profile_window(_FakeFleet())
        assert win["samples"] == 20
        assert win["top"][0]["func"] == "codec.decode"
        assert all("_server_main" != t["func"] for t in win["top"])
        assert win["top_all_threads"][0]["func"] == "cluster._server_main"
        assert win["per_thread"]["p1;MainThread"] == 10


# ---------------------------------------------------------------------------
# Serve-path integration: Obs.profile, cpu.* segment clocks, gauges
# ---------------------------------------------------------------------------


class _Echo:
    def ping(self, k):
        return ("pong", k)


@pytest.mark.timeout_s(60)
def test_obs_profile_drain_on_read_over_socket():
    """Obs.profile over a live socket: returns the process profile and
    drains it (second scrape restarts from zero); {"reset": False}
    peeks without draining; cpu.* segment hists and the resource
    gauges ride the same scrape plane."""
    from multiraft_tpu.distributed.profile import maybe_start_profiler
    from multiraft_tpu.distributed.tcp import RpcNode
    from multiraft_tpu.harness.observe import FleetObserver

    if maybe_start_profiler() is None:
        pytest.skip("MRT_PROFILE=0 in this environment")
    server = RpcNode(listen=True)
    server.add_service("Echo", _Echo())
    client = RpcNode()
    obs = None
    try:
        end = client.client_end(server.host, server.port)
        for k in range(100):
            got = client.sched.wait(
                end.call("Echo.ping", k, trace=f"pp.{k}"), 5.0
            )
            assert got == ("pong", k)
        time.sleep(0.25)  # let the sampler land a few samples
        obs = FleetObserver([(server.host, server.port)])
        key = f"{server.host}:{server.port}"

        # Resource gauges ride Obs.snapshot.
        g = obs.snapshot_all()[key]["gauges"]
        assert g["gauge.cpu_s"] > 0
        assert g["gauge.threads"] >= 2
        assert "gauge.rss_mb" in g and g["gauge.rss_mb"] > 1

        # cpu.* segment clocks folded per stage on the serve path.
        h = obs.hist_all()[key]["hists"]
        for st in ("cpu.wire_s", "cpu.dispatch_s", "cpu.handler_s",
                   "cpu.ack_s", "cpu.flush_s"):
            assert st in h and h[st]["n"] > 0, (st, sorted(h))

        # Peek does not drain; drain resets.
        peek = obs.profile(obs.addrs[0], reset=False)
        assert peek["profile"] is not None
        assert peek["profile"]["samples"] > 0
        d1 = obs.profile_all()[key]
        assert d1["profile"]["samples"] >= peek["profile"]["samples"]
        assert any(
            k2.split(";", 1)[0].startswith("multiraft-loop")
            for k2 in d1["profile"]["stacks"]
        ), sorted(d1["profile"]["stacks"])
        d2 = obs.profile_all()[key]
        assert d2["profile"]["samples"] <= 2  # fresh window

        # Fleet flame of the drained dump is process-prefixed.
        flame = FleetObserver.fleet_flame({key: d1})
        assert flame
        assert all(";" in k2 for k2 in flame)
    finally:
        if obs is not None:
            obs.close()
        client.close()
        server.close()


# ---------------------------------------------------------------------------
# Postmortem doctor: CPU saturation vs queueing collapse
# ---------------------------------------------------------------------------


class TestDoctorDiscrimination:
    _n = 0

    def _ring(self, tmp_path, busy_permille, hot="codec.decode",
              with_prof=True):
        from multiraft_tpu.distributed import flightrec

        TestDoctorDiscrimination._n += 1
        rec = flightrec.FlightRecorder(
            str(tmp_path / f"prof{TestDoctorDiscrimination._n}.ring"),
            slots=64, name="srv",
        )
        stage_trip = flightrec.OVERLOAD_KIND_CODES["stage_p99"]
        rec.record(flightrec.OVERLOAD, code=stage_trip, a=700_000,
                   b=50_000, c=40, tag="stage.wire_s")
        if with_prof:
            rec.record(flightrec.PROF, code=busy_permille, a=120,
                       b=30, c=0, tag=hot)
        rec.close()
        return rec.path

    def test_pegged_cpu_reads_cpu_saturation(self, tmp_path):
        from multiraft_tpu.analysis import postmortem

        ring = self._ring(tmp_path, busy_permille=980)
        analysis = postmortem.analyze(postmortem.load_bundle(ring))
        hits = [a for a in analysis["anomalies"]
                if a["kind"] == "cpu_saturation"]
        assert len(hits) == 1, analysis["anomalies"]
        assert "queueing_collapse" not in {
            a["kind"] for a in analysis["anomalies"]
        }
        d = hits[0]["detail"]
        assert "codec.decode" in d  # profiler names the hot function
        assert "980" in d
        assert "stage.wire_s" in d  # still names the saturated stage
        proc = analysis["procs"][0]
        assert proc["overload"]["diagnosis"] == "cpu_saturation"
        assert proc["profile"]["hottest"] == "codec.decode"
        report = postmortem.build_report(
            postmortem.load_bundle(ring), analysis
        )
        assert "CPU saturation" in report
        assert "cpu_saturation" in report

    def test_idle_cpu_reads_queueing_collapse(self, tmp_path):
        from multiraft_tpu.analysis import postmortem

        ring = self._ring(tmp_path, busy_permille=120)
        analysis = postmortem.analyze(postmortem.load_bundle(ring))
        hits = [a for a in analysis["anomalies"]
                if a["kind"] == "queueing_collapse"]
        assert len(hits) == 1, analysis["anomalies"]
        d = hits[0]["detail"]
        assert "CPU idle" in d and "120" in d
        assert analysis["procs"][0]["overload"]["diagnosis"] == (
            "queueing_collapse"
        )

    def test_no_prof_records_keeps_classic_diagnosis(self, tmp_path):
        """Pre-profiling rings (no PROF breadcrumbs) keep the classic
        queueing-collapse note, without any CPU claim."""
        from multiraft_tpu.analysis import postmortem

        ring = self._ring(tmp_path, busy_permille=0, with_prof=False)
        analysis = postmortem.analyze(postmortem.load_bundle(ring))
        hits = [a for a in analysis["anomalies"]
                if a["kind"] == "queueing_collapse"]
        assert len(hits) == 1
        assert "CPU" not in hits[0]["detail"]
        assert "profile" not in analysis["procs"][0]

    def test_threshold_env_override(self, tmp_path, monkeypatch):
        from multiraft_tpu.analysis import postmortem

        monkeypatch.setenv("MRT_CPUSAT_PERMILLE", "100")
        ring = self._ring(tmp_path, busy_permille=120)
        analysis = postmortem.analyze(postmortem.load_bundle(ring))
        kinds = {a["kind"] for a in analysis["anomalies"]}
        assert "cpu_saturation" in kinds

    def test_trace_renders_prof_counter_and_hot_instant(self, tmp_path):
        from multiraft_tpu.analysis import postmortem
        from multiraft_tpu.distributed import flightrec

        rec = flightrec.FlightRecorder(
            str(tmp_path / "trace.ring"), slots=32, name="srv"
        )
        rec.record(flightrec.PROF, code=400, a=10, b=5, c=0,
                   tag="codec.decode")
        rec.record(flightrec.PROF, code=950, a=20, b=6, c=1,
                   tag="codec.decode")  # same hot: no second instant
        rec.record(flightrec.PROF, code=990, a=30, b=6, c=1,
                   tag="kv.apply")
        rec.close()
        tracer = postmortem.rings_to_trace(
            postmortem.load_bundle(rec.path)
        )
        counters = [e for e in tracer.events
                    if e.get("ph") == "C" and e["name"] == "profiler"]
        assert len(counters) == 3
        assert counters[1]["args"]["busy_permille"] == 950
        hot = [e for e in tracer.events
               if e.get("ph") == "i" and e["name"].startswith("hot:")]
        assert [e["name"] for e in hot] == [
            "hot:codec.decode", "hot:kv.apply"
        ]
        assert tracer.dropped == 0
