"""Wedge-detection tests: the per-group no-progress watchdog
(distributed/wedge.py), its ``gauge.wedged_groups`` surface in
ObsControl.gauges, and the postmortem doctor's "wedged leadership"
anomaly that names the stalled group, its stuck leader, and the fault
window that caused the wedge."""

from __future__ import annotations

import time
import types

import numpy as np
import pytest

from multiraft_tpu.analysis.postmortem import analyze, build_report
from multiraft_tpu.distributed import flightrec
from multiraft_tpu.distributed.observe import ObsControl
from multiraft_tpu.distributed.wedge import WedgeWatch, install_wedge_watch
from multiraft_tpu.engine.core import LEADER, EngineConfig
from multiraft_tpu.engine.host import EngineDriver
from multiraft_tpu.utils.metrics import Metrics


class _Rec:
    """Record-collecting stand-in for the flight recorder."""

    def __init__(self):
        self.records = []

    def record(self, etype, code=0, a=0, b=0, c=0, tag=""):
        self.records.append(
            {"type": etype, "code": code, "a": a, "b": b, "c": c,
             "tag": tag}
        )


class _Ctl:
    """The watch's inputs, scriptable: what the last completed pump
    left on the host (``driver.last_metrics["commit_index"]``,
    ``driver.backlog``), the host's sealed flags (``kv.is_sealed``) and,
    behind ``driver.reconfiguring(groups)`` and ``driver.rows_of``, the
    device rows the watch may gather:
    reconfig state and each group's leader and term (P = 3).  ``reads``
    lists every gather.  ``reconfig=None`` is a driver that has begun
    no membership change, ``sealed=None`` a server without migration
    (``serve-kv``)."""

    P = 3

    def __init__(self, commit, backlog, leader=None, term=None,
                 reconfig=None, sealed=None):
        self.commit = list(commit)
        self.backlog = np.asarray(backlog, np.int64)
        self.leader = leader or [0] * len(self.commit)
        self.term = term or [1] * len(self.commit)
        self.reconfig = reconfig
        self.sealed = sealed
        self.reads = []

    # -- the driver's side --------------------------------------------

    @property
    def last_metrics(self):
        return {"commit_index": np.asarray(self.commit, np.int32)}

    @property
    def config_changes(self):
        return 0 if self.reconfig is None else 1

    @property
    def state(self):
        raise AssertionError("the watch read a whole state plane")

    def rows_of(self, planes, groups):
        groups = [int(g) for g in groups]
        self.reads.append((tuple(planes), groups))
        k = len(groups)
        col = lambda xs: np.asarray([xs[g] for g in groups])[:, None]
        role = np.zeros((k, self.P), np.int32)
        for i, g in enumerate(groups):
            if self.leader[g] >= 0:
                role[i, self.leader[g]] = LEADER
        rows = {
            "role": role,
            "alive": np.ones((k, self.P), bool),
            "term": np.repeat(col(self.term), self.P, axis=1),
        }
        return {name: rows[name] for name in planes}

    def reconfiguring(self, groups):
        groups = [int(g) for g in groups]
        self.reads.append((("joint", "cfg_idx", "commit"), groups))
        return np.asarray([self.reconfig[g] for g in groups], bool)

    # -- the control's side -------------------------------------------

    def groups(self):
        raise AssertionError("ObsControl.groups() is off the watch's path")

    def _engine_kv(self):
        kv = types.SimpleNamespace(driver=self)
        if self.sealed is not None:
            kv._l2g = {g: g for g in range(len(self.commit))}
            kv.is_sealed = lambda gid: bool(self.sealed[gid])
        return kv


def _node(rec=None):
    return types.SimpleNamespace(
        sched=types.SimpleNamespace(call_after=lambda *_a, **_k: None),
        obs=types.SimpleNamespace(metrics=Metrics()),
        _frec=rec,
        _closed=False,
    )


def _watch(node, ctl, stall_ticks=3):
    w = WedgeWatch(node, interval=999.0, stall_ticks=stall_ticks)
    w._ctl = ctl
    return w


def test_wedge_declared_after_stall_ticks_and_recorded():
    """commit frozen + backlog pending for ``stall_ticks`` scrapes →
    the group is wedged: WEDGE record with (group, stall, commit,
    backlog) and the "p<peer>@t<term>" leader tag, gauge set, one trip
    counted."""
    rec = _Rec()
    node = _node(rec)
    ctl = _Ctl(commit=[7, 3], backlog=[5, 0], leader=[2, 0], term=[9, 1])
    w = _watch(node, ctl, stall_ticks=3)
    assert w.check() == 0  # first scrape only establishes the baseline
    assert w.check() == 0
    assert w.check() == 0
    assert w.check() == 1  # 3 consecutive stalled scrapes after baseline
    assert w.wedged == {0}
    assert node.obs.metrics.counters["wedge.trips"] == 1
    assert node.obs.metrics.gauges["wedge.active"] == 1.0
    assert len(rec.records) == 1
    r = rec.records[0]
    assert r["type"] == flightrec.WEDGE
    assert r["code"] == 0 and r["a"] == 3 and r["b"] == 7 and r["c"] == 5
    assert r["tag"] == "p2@t9"
    # Still wedged: re-recorded each scrape, but only ONE trip.
    w.check()
    assert len(rec.records) == 2 and rec.records[1]["a"] == 4
    assert node.obs.metrics.counters["wedge.trips"] == 1


def test_wedge_clears_on_commit_advance_or_drained_backlog():
    rec = _Rec()
    node = _node(rec)
    ctl = _Ctl(commit=[7], backlog=[5])
    w = _watch(node, ctl, stall_ticks=2)
    for _ in range(3):
        w.check()
    assert w.wedged == {0}
    # One commit advance: the wedge clears and the gauge falls.
    ctl.commit[0] += 1
    assert w.check() == 0
    assert w.wedged == set()
    assert node.obs.metrics.gauges["wedge.active"] == 0.0
    # Re-stall, then drain the backlog instead: idle is not wedged.
    for _ in range(3):
        w.check()
    assert w.wedged == {0}
    ctl.backlog[0] = 0
    assert w.check() == 0 and w.wedged == set()


def test_wedge_needs_pending_proposals():
    """An idle group with a frozen frontier is NOT a wedge — nothing
    is owed, so nothing is stalled."""
    node = _node()
    w = _watch(node, _Ctl(commit=[4], backlog=[0]), stall_ticks=2)
    for _ in range(10):
        assert w.check() == 0
    assert w.wedged == set()


def test_wedge_gauge_in_obs_gauges():
    node = _node()
    node.wedge_watch = types.SimpleNamespace(wedged={1, 3})
    out = ObsControl(node).gauges()
    assert out["gauge.wedged_groups"] == 2.0


def test_install_wedge_watch_env_gate(monkeypatch):
    monkeypatch.setenv("MRT_WEDGE_WATCH", "0")
    assert install_wedge_watch(_node()) is None
    monkeypatch.delenv("MRT_WEDGE_WATCH")
    node = _node()
    w = install_wedge_watch(node)
    assert w is not None and node.wedge_watch is w
    w.stop()


# ---------------------------------------------------------------------------
# The array watch against the per-group walk it replaced
# ---------------------------------------------------------------------------


class _PerGroupWalk:
    """The watch as it was before it read the pump's frontier: every
    scrape took ``ObsControl.groups()``'s columns (Python lists of G)
    and walked ``range(G)``.  Kept here as the plain reference."""

    def __init__(self, metrics, frec, stall_ticks):
        self.m, self.frec, self.stall_ticks = metrics, frec, stall_ticks
        self._prev_commit = None
        self._stall = {}
        self.wedged = set()

    def check(self, groups, backlog):
        m = self.m
        commit = groups["commit"]
        prev, self._prev_commit = self._prev_commit, list(commit)
        sealed = groups.get("sealed") or []
        reconfig = groups.get("reconfig") or []
        for g in range(len(commit)):
            pend = int(backlog[g]) if backlog is not None else 0
            moved = prev is None or g >= len(prev) or commit[g] > prev[g]
            exempt = bool(
                (g < len(sealed) and sealed[g])
                or (g < len(reconfig) and reconfig[g])
            )
            if exempt:
                self._stall[g] = 0
                if g in self.wedged:
                    self.wedged.discard(g)
                m.inc("wedge.reconfig_exempt")
                continue
            if moved or pend <= 0:
                self._stall[g] = 0
                self.wedged.discard(g)
                continue
            self._stall[g] = self._stall.get(g, 0) + 1
            if self._stall[g] < self.stall_ticks:
                continue
            if g not in self.wedged:
                self.wedged.add(g)
                m.inc("wedge.trips")
            if self.frec is not None:
                self.frec.record(
                    flightrec.WEDGE,
                    code=g,
                    a=self._stall[g],
                    b=int(commit[g]),
                    c=pend,
                    tag=f"p{groups['leader'][g]}@t{groups['term'][g]}",
                )
        m.set("wedge.active", float(len(self.wedged)))
        return len(self.wedged)


def _script_step(rng, ctl, G):
    """One scrape's worth of seeded traffic: each group sits in a
    regime for a while (committing, idle, owed-and-stalled), seals and
    reconfigs open and close, leaders and terms change."""
    flip = rng.random(G) < 0.12
    ctl.mode = np.where(flip, rng.integers(0, 3, G), ctl.mode)
    commits = (ctl.mode == 0) & (rng.random(G) < 0.8)
    ctl.commit = (np.asarray(ctl.commit) + commits).tolist()
    ctl.backlog[:] = np.where(
        ctl.mode == 1, 0, rng.integers(0, 4, G) + (ctl.mode == 2)
    )
    for name, p_on, p_off in (("sealed", 0.02, 0.2), ("reconfig", 0.03, 0.25)):
        cur = np.asarray(getattr(ctl, name), bool)
        r = rng.random(G)
        setattr(ctl, name, np.where(cur, r >= p_off, r < p_on).tolist())
    elect = rng.random(G) < 0.05
    ctl.term = (np.asarray(ctl.term) + elect).tolist()
    ctl.leader = np.where(
        elect, rng.integers(-1, _Ctl.P, G), ctl.leader
    ).tolist()


@pytest.mark.parametrize("G", [1, 7, 1000])
@pytest.mark.parametrize("seed", [0, 1])
def test_array_watch_decides_what_the_per_group_walk_decided(G, seed):
    """Same ``wedged`` set after every scrape, same ``wedge.trips`` and
    ``wedge.active``, same WEDGE records field for field, over a few
    hundred scrapes of seeded (commit, backlog, sealed, reconfig)."""
    rng = np.random.default_rng(1000 * seed + G)
    ctl = _Ctl(commit=[0] * G, backlog=[0] * G,
               reconfig=[False] * G, sealed=[False] * G)
    ctl.mode = rng.integers(0, 3, G)
    new_rec, old_rec = _Rec(), _Rec()
    node = _node(new_rec)
    new = _watch(node, ctl, stall_ticks=4)
    old_m = Metrics()
    old = _PerGroupWalk(old_m, old_rec, stall_ticks=4)
    trips = 0
    for scrape in range(300):
        _script_step(rng, ctl, G)
        groups = {
            "commit": list(ctl.commit), "leader": list(ctl.leader),
            "term": list(ctl.term), "sealed": list(ctl.sealed),
            "reconfig": list(ctl.reconfig),
        }
        assert new.check() == old.check(groups, ctl.backlog), scrape
        assert new.wedged == old.wedged, scrape
        assert all(type(g) is int for g in new.wedged)
        new_m = node.obs.metrics
        assert new_m.counters["wedge.trips"] == old_m.counters["wedge.trips"]
        assert new_m.gauges["wedge.active"] == old_m.gauges["wedge.active"]
        assert new_rec.records == old_rec.records, scrape
        trips = old_m.counters["wedge.trips"]
    assert trips > 0 and old_rec.records, "the script never wedged a group"
    assert old_m.counters["wedge.reconfig_exempt"] > 0


def test_healthy_traffic_never_asks_the_device_or_the_control():
    """1,000 scrapes with commits moving and backlog coming and going:
    candidates come up (a write submitted since the last pump) and
    clear, nothing is gathered off the device (the stub's planes and
    ``groups()`` raise), ``wedge.device_reads`` stays 0."""
    G = 500
    rng = np.random.default_rng(7)
    ctl = _Ctl(commit=[0] * G, backlog=[0] * G)  # no reconfig, no seals
    ctl.rows_of = None  # any gather is a TypeError, counted below
    node = _node(_Rec())
    w = _watch(node, ctl, stall_ticks=8)
    owed = np.zeros(G, bool)
    for _ in range(1000):
        # What was owed at the last scrape has committed by this one;
        # fresh writes arrive on other groups.
        ctl.commit = (np.asarray(ctl.commit) + owed).tolist()
        owed = rng.random(G) < 0.1
        ctl.backlog[:] = owed * rng.integers(1, 5, G)
        w._tick()
    m = node.obs.metrics
    assert m.counters["wedge.candidates"] > 1000
    assert m.counters["wedge.device_reads"] == 0
    assert m.counters["wedge.watch_errors"] == 0
    assert m.counters["wedge.trips"] == 0 and w.wedged == set()
    assert node._frec.records == []
    assert m.hists["wedge.check_s"].count == 1000


def test_first_pump_not_completed_skips_the_scrape():
    """Before the first pump there is no frontier on the host: the
    scrape is skipped (and a node without an engine service likewise)."""
    node = _node()
    w = _watch(node, _Ctl(commit=[], backlog=[]), stall_ticks=1)
    w._ctl = types.SimpleNamespace(
        _engine_kv=lambda: types.SimpleNamespace(
            driver=types.SimpleNamespace(last_metrics={},
                                         backlog=np.ones(3, np.int64))
        )
    )
    assert w.check() == 0 and w.check() == 0
    w._ctl = types.SimpleNamespace(_engine_kv=lambda: None)
    assert w.check() == 0
    assert node.obs.metrics.counters["wedge.watch_errors"] == 0


def test_reconfig_is_asked_of_the_device_for_candidates_only():
    """An open reconfig on a candidate group exempts it and counts
    ``wedge.reconfig_exempt``; the gather names the candidates' rows
    and nobody else's; a scrape is one ``wedge.check_s`` sample."""
    node = _node(_Rec())
    ctl = _Ctl(commit=[5, 9, 2, 4], backlog=[4, 0, 3, 0],
               reconfig=[True, True, False, False])
    w = _watch(node, ctl, stall_ticks=3)
    for _ in range(5):
        w._tick()
    m = node.obs.metrics
    assert m.hists["wedge.check_s"].count == 5
    # Group 1 reconfigures too, but is owed nothing: never a candidate,
    # never looked up, never counted.
    assert m.counters["wedge.reconfig_exempt"] == 4
    assert m.counters["wedge.candidates"] == 8
    assert m.counters["wedge.device_reads"] == 4
    assert w.wedged == {2}
    exempt_reads = [r for r in ctl.reads if "joint" in r[0]]
    assert [r[1] for r in exempt_reads] == [[0, 2]] * 4
    tag_reads = [r for r in ctl.reads if "role" in r[0]]
    assert [r[1] for r in tag_reads] == [[2]] * 2  # stalls 3 and 4


# ---------------------------------------------------------------------------
# On a real driver: the rows the watch gathers
# ---------------------------------------------------------------------------


def _driver_ctl(driver):
    return types.SimpleNamespace(
        _engine_kv=lambda: types.SimpleNamespace(driver=driver)
    )


def test_open_reconfig_on_a_real_driver_exempts_until_it_closes():
    """``begin_joint`` on a real driver: the candidate's ``joint`` row,
    gathered off the device, exempts it while the change is open; once
    it has closed the same stall trips."""
    d = EngineDriver(EngineConfig(G=4, P=4, L=32, E=4, INGEST=4), seed=3)
    d.seed_config([0, 1, 2])
    for _ in range(200):
        d.step()
        if (d.leaders_per_group() == 1).all():
            break
    assert d.config_changes == 0  # seeding a voter set opens nothing
    node = _node(_Rec())
    w = _watch(node, _driver_ctl(d), stall_ticks=2)
    m = node.obs.metrics
    assert w.check() == 0  # baseline
    g = 2
    d.add_learner(g, 3)
    for _ in range(30):
        d.step()
    lead = d.leader_of(g)
    d.begin_joint(g, sorted({0, 1, 2, 3} - {(lead + 1) % 3}))
    assert d.config_changes == 2
    assert w.check() == 0  # the config entries moved the frontier
    d.backlog[g] = 2  # owed work the stopped driver never ingests
    for _ in range(4):
        assert w.check() == 0
    assert m.counters["wedge.reconfig_exempt"] == 4
    assert m.counters["wedge.device_reads"] == 4
    d.backlog[g] = 0
    for _ in range(300):
        d.step()
        if not d.reconfiguring().any():
            break
    assert not d.reconfiguring().any()
    w.check()
    d.backlog[g] = 2
    assert w.check() == 0 and w.check() == 1
    assert w.wedged == {g}
    assert m.counters["wedge.reconfig_exempt"] == 4
    d.backlog[g] = 0


@pytest.mark.timeout_s(240)
@pytest.mark.parametrize(
    "mesh, replicas", [(0, 3), (4, 3), (0, 5)],
    ids=["one-device", "mesh4", "one-device-p5"],
)
def test_tag_carries_leader_and_term_read_at_the_scrape(
    mesh, replicas, tmp_path, monkeypatch
):
    """A served driver (``serve-kv``'s node: one device, the groups
    sharded over four, five replicas a group): the tripped group's tag
    is the leader and term its rows hold on the device at that scrape,
    and the server's own watch, scraping all the while, has asked the
    device nothing."""
    import jax

    from multiraft_tpu.distributed.engine_server import serve_engine_kv

    if len(jax.devices()) < mesh:
        pytest.skip(f"need {mesh} devices")
    monkeypatch.setenv("MRT_WEDGE_INTERVAL", "0.02")
    node = serve_engine_kv(
        port=0, G=8 if mesh else 4, data_dir=str(tmp_path),
        mesh_devices=mesh, replicas=replicas,
    )
    try:
        driver = node.engine_service.kv.driver
        assert driver.cfg.P == replicas
        rec = _Rec()
        stub = types.SimpleNamespace(
            sched=types.SimpleNamespace(call_after=lambda *_a, **_k: None),
            obs=types.SimpleNamespace(metrics=Metrics()),
            _frec=rec, _closed=False, engine_service=node.engine_service,
        )
        w = WedgeWatch(stub, interval=999.0, stall_ticks=3)
        g = driver.cfg.G - 1  # on the mesh: the last device's shard

        def wedge_one_group():
            # One callback on the serving loop: no pump completes and
            # nothing is dispatched while it runs, so the faked debt is
            # never ingested and the frontier stands still.
            st = driver.np_state()
            lead = driver.leader_of(g)
            want = f"p{-1 if lead is None else lead}@t{st['term'][g].max()}"
            w.check()
            driver.backlog[g] += 5
            try:
                counts = [w.check() for _ in range(4)]
            finally:
                driver.backlog[g] -= 5
            return want, counts, int(st["commit"][g].max())

        want, counts, commit = node.sched.run_call(wedge_one_group, timeout=60)
        assert counts == [0, 0, 1, 1] and w.wedged == {g}
        assert want != "p-1@t0"  # the group had elected
        assert [r["tag"] for r in rec.records] == [want, want]
        assert [r["a"] for r in rec.records] == [3, 4]
        assert all(r["code"] == g and r["c"] == 5 for r in rec.records)
        # The record's frontier is the last completed pump's: at most
        # one pump behind the device's.
        assert 0 <= commit - rec.records[0]["b"] <= 2
        assert stub.obs.metrics.counters["wedge.device_reads"] == 2
        # The server's own watch scraped a healthy fleet meanwhile.
        own = node.obs.metrics
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not (
            (h := own.hists.get("wedge.check_s")) and h.count >= 3
        ):
            time.sleep(0.02)
        assert own.hists["wedge.check_s"].count >= 3
        assert node.wedge_watch._prev_commit is not None  # it compared
        assert own.counters["wedge.device_reads"] == 0
        assert own.counters["wedge.watch_errors"] == 0
    finally:
        node.sched.run_call(node.engine_service.stop, timeout=30)
        node.close()


# ---------------------------------------------------------------------------
# Postmortem: the "wedged leadership" anomaly
# ---------------------------------------------------------------------------


def _wedge_rec(seq, ts, group=0, stall=3, commit=7, backlog=5,
               tag="p2@t9"):
    return {
        "seq": seq, "ts": ts, "type": flightrec.WEDGE,
        "type_name": "wedge", "code": group, "a": stall, "b": commit,
        "c": backlog, "tag": tag,
    }


def _bundle(records, windows):
    ring = {
        "pid": 123, "name": "srv", "wall_t0": 0.0, "slots": 64,
        "records": records, "torn": 0, "clean_close": True,
        "path": "srv.ring",
    }
    return {
        "dir": ".",
        "manifest": {
            "idents": {"h:1": {"pid": 123}},
            "offsets_us": {"h:1": 0.0},
        },
        "snapshots": {}, "windows": windows, "rings": [ring],
        "skipped": [],
    }


def test_postmortem_names_wedged_leadership_and_cause():
    """One anomaly per wedged group, anchored on the onset, naming the
    group, the stuck leader, and the covering nemesis fault window."""
    windows = [
        {"kind": "slow_link", "p": {"proc": 1}, "procs": [1],
         "t_start_us": 100.0, "t_stop_us": 500.0},
        {"kind": "partial_partition", "p": {"proc": 0}, "procs": [0],
         "t_start_us": 900.0, "t_stop_us": 2600.0},
    ]
    recs = [
        _wedge_rec(1, 1000.0, stall=3),
        _wedge_rec(2, 1500.0, stall=5),
        _wedge_rec(3, 2500.0, stall=8, commit=7, backlog=11),
    ]
    bundle = _bundle(recs, windows)
    analysis = analyze(bundle)
    wedges = [a for a in analysis["anomalies"]
              if a["kind"] == "wedged_leadership"]
    assert len(wedges) == 1
    a = wedges[0]
    assert a["ts"] == 1000.0 and a["aligned"]
    assert "group 0" in a["detail"]
    assert "p2@t9" in a["detail"]
    assert "partial_partition" in a["detail"]  # the covering window
    assert "slow_link" not in a["detail"]
    # It is also the FIRST anomaly of this clean-closing ring.
    assert analysis["first_anomaly"]["kind"] == "wedged_leadership"
    report = build_report(bundle, analysis)
    assert "wedged leadership" in report
    assert "wedged: group 0 leader p2@t9" in report


def test_postmortem_wedge_without_windows_still_reports():
    bundle = _bundle([_wedge_rec(1, 1000.0)], windows=[])
    analysis = analyze(bundle)
    wedges = [a for a in analysis["anomalies"]
              if a["kind"] == "wedged_leadership"]
    assert len(wedges) == 1
    assert "fault window" not in wedges[0]["detail"]
    # Two wedged groups → two anomalies, each naming its own group.
    bundle = _bundle(
        [_wedge_rec(1, 1000.0, group=0),
         _wedge_rec(2, 1100.0, group=3, tag="p0@t4")],
        windows=[],
    )
    kinds = [a for a in analyze(bundle)["anomalies"]
             if a["kind"] == "wedged_leadership"]
    assert len(kinds) == 2
    assert "group 3" in kinds[1]["detail"]
    assert "p0@t4" in kinds[1]["detail"]
