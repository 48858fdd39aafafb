"""Sender lanes (``core.SENDER_LANES``): the mailbox fields whose value
is the sender's alone travel once per sender, ``[G, src]``.

What this pins: the tick reads a sender lane the same way in either
form — stored per sender, or expanded per edge by a host path — so a
tick fed the expanded lanes gives the state, outbox and metrics of the
same tick fed the ``[G, src]`` lanes, through an election, a config
change and a snapshot fast-forward; the reorder fault mode, the one
path that redelivers an edge whose value differs from its sender's lane
now, delivers the old term and gets a stale answer; and a checkpoint
written with the lanes per edge (all-destinations-equal, as every bundle
once was) restores to the ``[G, src]`` form.
"""

from __future__ import annotations

import pickle

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from multiraft_tpu.engine import host as host_mod  # noqa: E402
from multiraft_tpu.engine.core import (  # noqa: E402
    LEADER,
    SENDER_LANES,
    EngineConfig,
    Mailbox,
    per_edge,
    tick_impl,
)
from multiraft_tpu.engine.host import EngineDriver  # noqa: E402
from multiraft_tpu.engine.state_planes import content_fingerprint  # noqa: E402

# No donation: one state feeds both forms of the tick.
_tick = jax.jit(tick_impl, static_argnums=0)


def _expanded(mb: Mailbox) -> Mailbox:
    return mb._replace(**{f: per_edge(getattr(mb, f)) for f in SENDER_LANES})


def _metrics_fp(m) -> list:
    return [(k, np.asarray(m[k]).tobytes()) for k in sorted(m)]


class _Checked:
    """Steps a driver one serial tick at a time and, before each, runs
    that tick twice off the driver — once on the inbox as it is, once
    with every sender lane expanded — and holds the two to one result.
    Notes which kinds of traffic the run carried."""

    def __init__(self, d: EngineDriver) -> None:
        self.d = d
        self.saw = set()

    def step(self, n: int = 1) -> None:
        d = self.d
        for _ in range(n):
            mb = d.inbox
            assert all(getattr(mb, f).ndim == 2 for f in SENDER_LANES)
            act = lambda f: np.asarray(getattr(mb, f)).any()
            if act("vr_active"):
                self.saw.add("vote")
            if act("ap_active"):
                self.saw.add("reply")
            ar = np.asarray(mb.ar_active)
            if (ar & np.asarray(mb.ar_snap)).any():
                self.saw.add("snapshot")
            if (ar.any(axis=2) & (
                np.asarray(mb.ar_cfg_joint)
                | (np.asarray(mb.ar_cfg_epoch) > 0)
            )).any():
                self.saw.add("config")
            new_cmds = jnp.asarray(
                np.minimum(d.backlog, d.cfg.INGEST), jnp.int32
            )
            key = jax.random.fold_in(d.key, d.tick + 1)
            a = _tick(d.cfg, d.state, mb, new_cmds, key)
            b = _tick(d.cfg, d.state, _expanded(mb), new_cmds, key)
            assert content_fingerprint(a[0]) == content_fingerprint(b[0])
            assert content_fingerprint(a[1]) == content_fingerprint(b[1])
            assert _metrics_fp(a[2]) == _metrics_fp(b[2])
            d.step(1)

    def until(self, pred, max_ticks: int) -> None:
        for _ in range(max_ticks):
            if pred():
                return
            self.step()
        assert pred(), "the scenario did not reach its state"


@pytest.mark.parametrize("P", [3, 5])
def test_expanded_sender_lanes_tick_as_per_sender(P):
    cfg = EngineConfig(G=2, P=P, L=32, E=4, INGEST=4)
    assert cfg.membership_on
    d = EngineDriver(cfg, seed=P)
    c = _Checked(d)
    # An election in both groups.
    c.until(lambda: (d.leaders_per_group() >= 1).all(), 300)
    # Group 0: a config change that drops one follower, to its end.
    lead = d.leader_of(0)
    keep = [q for q in range(P) if q != (lead + 1) % P]
    d.begin_joint(0, keep)
    c.until(lambda: (
        d.leader_of(0) is not None
        and not d.config_of(0)["joint"]
        and d.config_of(0)["voters_new"] == sorted(keep)
    ), 400)
    # Group 1: a follower down while the ring wraps, then back: it
    # catches up through the snapshot fast-forward.
    lead1 = d.leader_of(1)
    victim = (lead1 + 1) % P
    d.set_alive(1, victim, False)
    for i in range(60):
        d.start(1, i)
    c.step(200)
    assert int(d.np_state()["base"][1, lead1]) > 0, "ring never compacted"
    d.set_alive(1, victim, True)
    c.until(lambda: int(d.np_state()["base"][1, victim]) > 0, 200)
    assert c.saw == {"vote", "reply", "config", "snapshot"}


def test_reorder_redelivers_an_append_at_its_old_term():
    """A held append from a leader that has since moved to a higher
    term arrives with the term it was sent at, though its sender's lane
    now holds the new one, and the follower answers it as stale."""
    d = EngineDriver(EngineConfig(G=1, P=3), seed=5)
    assert d.run_until_quiet_leaders(300)
    old = d.leader_of(0)
    t_old = int(d.np_state()["term"][0, old])
    dst = (old + 1) % 3
    for _ in range(20):
        if bool(np.asarray(d.inbox.ar_active)[0, old, dst]):
            break
        d.step(1)
    host = {f: np.asarray(getattr(d.inbox, f)) for f in Mailbox._fields}
    held = {
        f: (host[f][0, old] if host[f].ndim == 2 else host[f][0, old, dst]).copy()
        for f in host_mod._CHANNELS["ar_"]
    }
    assert int(held["ar_term"]) == t_old and bool(held["ar_active"])
    d._delayed = [(10**9, "ar_", (0, old, dst), held)]
    # The old leader is cut off; the others elect a successor; back, it
    # learns the new term.
    d.set_alive(0, old, False)
    for _ in range(400):
        d.step(1)
        lead = d.leader_of(0)
        if lead is not None and lead != old:
            break
    d.set_alive(0, old, True)
    for _ in range(100):
        d.step(1)
        st = d.np_state()
        if st["role"][0, old] != LEADER and st["term"][0, old] > t_old:
            break
    t_new = int(d.np_state()["term"][0, old])
    assert t_new > t_old
    # Release it: the next outbox carries it, per edge.
    d._delayed = [(d.tick + 1, "ar_", (0, old, dst), held)]
    d.step(1)
    assert not d._delayed
    ar_term = np.asarray(d.inbox.ar_term)
    assert ar_term.ndim == 3
    assert ar_term[0, old, dst] == t_old
    assert bool(np.asarray(d.inbox.ar_active)[0, old, dst])
    term_dst = int(d.np_state()["term"][0, dst])
    assert term_dst > t_old
    d.step(1)
    # The answer: a failure carrying the follower's newer term, in an
    # outbox whose sender lanes are [G, src] again.
    mb = d.inbox
    assert all(getattr(mb, f).ndim == 2 for f in SENDER_LANES)
    assert bool(np.asarray(mb.ap_active)[0, dst, old])
    assert not bool(np.asarray(mb.ap_success)[0, dst, old])
    assert int(np.asarray(mb.ap_term)[0, dst]) == term_dst
    d.check_log_matching(0)


def _busy_driver(seed: int = 9) -> EngineDriver:
    d = EngineDriver(EngineConfig(G=4, P=3, L=32, E=4, INGEST=4), seed=seed)
    assert d.run_until_quiet_leaders(300)
    for g in range(4):
        for i in range(5):
            d.start(g, (g, i))
    d.step(3)
    return d


def test_checkpoint_roundtrip_keeps_sender_lanes(tmp_path):
    d = _busy_driver()
    path = d.save(str(tmp_path / "e.ckpt"))
    with open(path, "rb") as f:
        blob = pickle.load(f)
    assert all(blob["inbox"][f].ndim == 2 for f in SENDER_LANES)
    r = EngineDriver.restore(path)
    assert content_fingerprint(r.inbox) == content_fingerprint(d.inbox)
    assert content_fingerprint(r.state) == content_fingerprint(d.state)
    d.step(4)
    r.step(4)
    assert content_fingerprint(r.inbox) == content_fingerprint(d.inbox)
    assert content_fingerprint(r.state) == content_fingerprint(d.state)


def test_bundle_with_per_edge_sender_lanes_restores(tmp_path):
    """A bundle whose sender lanes are ``[G, src, dst]`` and equal over
    destinations (what every save wrote before the lanes went per
    sender) restores to ``[G, src]``; a lane that differs across
    destinations stays per edge, and the fused scan and the serial loop
    still agree from it."""
    d = _busy_driver()
    path = d.save(str(tmp_path / "e.ckpt"))
    with open(path, "rb") as f:
        blob = pickle.load(f)
    blob["inbox"] = {k: per_edge(v) for k, v in blob["inbox"].items()}
    old = str(tmp_path / "old.ckpt")
    with open(old, "wb") as f:
        pickle.dump(blob, f)
    r = EngineDriver.restore(old)
    assert all(getattr(r.inbox, f).ndim == 2 for f in SENDER_LANES)
    assert content_fingerprint(r.inbox) == content_fingerprint(d.inbox)
    # One lane unequal across destinations, on an edge no message uses.
    act = blob["inbox"]["ar_active"]
    g, s, t = map(int, np.argwhere(~act)[0])
    blob["inbox"]["ar_commit"][g, s, t] += 1
    odd = str(tmp_path / "odd.ckpt")
    with open(odd, "wb") as f:
        pickle.dump(blob, f)
    fused, serial = EngineDriver.restore(odd), EngineDriver.restore(odd)
    assert fused.inbox.ar_commit.ndim == 3
    assert fused.inbox.ar_term.ndim == 2
    serial._pipeline_on = False
    fused.step(4)
    for _ in range(4):
        serial.step(1)
    assert fused.inbox.ar_commit.ndim == 2
    assert content_fingerprint(fused.inbox) == content_fingerprint(serial.inbox)
    assert content_fingerprint(fused.state) == content_fingerprint(serial.state)
    d.step(4)
    assert content_fingerprint(fused.state) == content_fingerprint(d.state)
