"""Host-side driver for the batched engine.

Owns the tick loop: feeds the outbox back as the next inbox through the
tensorized fault model (drop masks + liveness — the labrpc semantics of
SURVEY §2.2 in dense form), maintains the Start() backlog and the
host-side command payload store keyed ``(group, index)`` (the device
only consensus-orders terms/indices), and accumulates metrics.

This is also where crash/restart surgery happens: a "crashed" replica is
marked dead (mask) and, on restart, its volatile state is reset while
its persistent columns (term, vote, log, base) survive — the tensor
analog of the reference's Persister carryover
(reference: raft/config.go:113-142).
"""

from __future__ import annotations

import functools
import os
import pickle
import time
from collections import defaultdict
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.knobs import knob_bool
from ..utils.metrics import Metrics
from .core import (
    SCALAR_METRIC_KEYS,
    CANDIDATE,
    FOLLOWER,
    LEADER,
    EngineConfig,
    EngineState,
    SENDER_LANES,
    Mailbox,
    empty_mailbox,
    per_edge,
    init_state,
    shard_rows,
    tick,
)
from .instrument import pump_phase

__all__ = [
    "EngineDriver",
    "PayloadRun",
    "PayloadSlice",
    "apply_faults",
    "drop_messages",
    "mask_active",
]


class PayloadRun:
    """A pending firehose run: ``rows`` (original frame row indices,
    submission order) of ``frame`` awaiting log slots in one group.
    Consumed incrementally by the binding loop — each accept batch
    takes a prefix as one :class:`PayloadSlice`."""

    __slots__ = ("frame", "rows", "consumed")

    def __init__(self, frame: Any, rows: "np.ndarray") -> None:
        self.frame = frame
        self.rows = rows
        self.consumed = 0

    @property
    def remaining(self) -> int:
        return len(self.rows) - self.consumed

    def take(self, k: int) -> "PayloadSlice":
        s = PayloadSlice(self.frame, self.rows[self.consumed: self.consumed + k])
        self.consumed += k
        return s


class PayloadSlice:
    """A bound contiguous range of log slots carrying firehose rows:
    stored in ``driver.payloads`` keyed by its FIRST (group, index);
    covers ``len(rows)`` consecutive indices.  The frontier sweep
    applies it whole (or splits it at the commit frontier); eviction
    fails all its rows at once."""

    __slots__ = ("frame", "rows")

    def __init__(self, frame: Any, rows: "np.ndarray") -> None:
        self.frame = frame
        self.rows = rows

    @property
    def count(self) -> int:
        return len(self.rows)

    def split_head(self, k: int) -> "PayloadSlice":
        """Split off the first ``k`` rows; self keeps the tail."""
        head = PayloadSlice(self.frame, self.rows[:k])
        self.rows = self.rows[k:]
        return head

# The message channels' liveness fields; every fault transform (drop,
# partition, crash edge-kill) is a mask over exactly these.  Derived
# from the Mailbox schema so a new channel can't bypass fault injection.
_ACTIVE_FIELDS = tuple(f for f in Mailbox._fields if f.endswith("_active"))

# Channel prefix -> all fields of that channel (e.g. "ar_" -> ar_active,
# ar_term, ..., ar_snap).  The reorder fault mode lifts whole messages —
# every field of a channel slot — out of the stream and redelivers them
# ticks later, so it needs the grouping, not just the active bits.
_CHANNELS = {
    f[: -len("active")]: tuple(
        g for g in Mailbox._fields if g.startswith(f[: -len("active")])
    )
    for f in _ACTIVE_FIELDS
}


def _collapse_lanes(lanes: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Checkpointed mailbox lanes with every sender lane written per
    edge, ``[G, src, dst]``, and equal across destinations collapsed to
    the ``[G, src]`` form the tick writes (core.SENDER_LANES)."""
    out = dict(lanes)
    for f in SENDER_LANES:
        v = out[f]
        if v.ndim == 3 and (v == v[:, :, :1]).all():
            out[f] = v[:, :, 0]
    return out


def mask_active(mb: Mailbox, fn) -> Mailbox:
    """Apply ``fn(field_name, bool_array) -> bool_array`` over every
    ``*_active`` channel of the mailbox."""
    return mb._replace(**{k: fn(k, getattr(mb, k)) for k in _ACTIVE_FIELDS})


def drop_messages(
    mailbox: Mailbox, key: jax.Array, drop_prob: jnp.ndarray, cfg: EngineConfig,
    shard=None,
) -> Mailbox:
    """Drop each in-flight message independently with ``drop_prob`` —
    the dense-tensor form of labrpc's unreliable mode
    (reference: labrpc/labrpc.go:228-239,279-284; request and reply
    drops both land here because each direction is its own edge-slot).
    ``shard``: as for ``tick_impl`` (``core.shard_rows``), so under
    ``shard_map`` a device's groups lose what they lose unsharded."""
    keys = jax.random.split(key, len(_ACTIVE_FIELDS))

    def drop(name, a):
        k = keys[_ACTIVE_FIELDS.index(name)]
        u = shard_rows(
            lambda rows: jax.random.uniform(k, (rows, cfg.P, cfg.P)),
            cfg.G, shard,
        )
        return a & (u >= drop_prob)

    return mask_active(mailbox, drop)


@functools.partial(jax.jit, static_argnums=(3,))
def apply_faults(
    mailbox: Mailbox, key: jax.Array, drop_prob: jnp.ndarray, cfg: EngineConfig
) -> Mailbox:
    """:func:`drop_messages` as a program of its own (the serial loop)."""
    return drop_messages(mailbox, key, drop_prob, cfg)


def _fold_lanes(metrics: Dict[str, Any], xp, axis: int) -> Dict[str, Any]:
    """A mesh driver's scalar metrics arrive as per-device lanes (the
    zero-collective contract, engine/mesh.py: no ``psum`` on the
    device): fold ``axis`` into the scalars every host-side reader
    expects, with ``xp`` = ``jnp`` (still on the devices) or ``np``."""
    out = dict(metrics)
    for k in SCALAR_METRIC_KEYS:
        out[k] = (xp.max if k == "max_term" else xp.sum)(out[k], axis=axis)
    return out


@jax.jit
def _take_rows(planes, idx):
    return tuple(p[idx] for p in planes)


@jax.jit
def _take_rows_stacked(planes, idx):
    return jnp.stack([p[idx].astype(jnp.int32) for p in planes])


def _padded_index(groups) -> np.ndarray:
    """``groups`` as an ``int32`` vector padded with group 0 to a power
    of two (8 at least): the shape the gathers below compile for."""
    idx = np.asarray(groups, np.int32)
    padded = np.zeros(1 << max(3, (len(idx) - 1).bit_length()), np.int32)
    padded[: len(idx)] = idx
    return padded


def _reconfig_open(st) -> np.ndarray:
    """Per group (row) of the host planes ``st``: in the joint phase, or
    the latest config entry not yet committed."""
    return st["joint"].any(axis=1) | (
        st["cfg_idx"].max(axis=1) > st["commit"].max(axis=1)
    )


class EngineDriver:
    def __init__(
        self, cfg: EngineConfig, seed: int = 0, mesh=None,
        check_zero_collectives: bool = True,
    ) -> None:
        """``mesh``: an optional 1-D ``jax.sharding.Mesh`` (axis
        ``"groups"``) — the driver then runs the production multi-chip
        recipe (engine/mesh.py): state/mailbox sharded on the groups
        axis, the tick under shard_map, and (by default) a compile-time
        assert that the step contains zero collectives."""
        self._init_host(cfg, seed)
        self.state: EngineState = init_state(cfg, jax.random.fold_in(self.key, 0))
        self.inbox: Mailbox = empty_mailbox(cfg)
        if mesh is not None:
            self._use_mesh(mesh)
            if check_zero_collectives:
                from .mesh import assert_zero_collectives

                assert_zero_collectives(
                    self._mesh_tick,
                    self.state,
                    self.inbox,
                    jnp.zeros(cfg.G, jnp.int32),
                    self.key,
                )

    def _use_mesh(self, mesh) -> None:
        """Spread state, mailbox and (replicated) key over ``mesh``."""
        from jax.sharding import NamedSharding, PartitionSpec

        from .mesh import make_sharded_tick, shard_arrays

        self.mesh = mesh
        self.state = shard_arrays(self.cfg, mesh, self.state)
        self.inbox = shard_arrays(self.cfg, mesh, self.inbox)
        self._mesh_tick = make_sharded_tick(self.cfg, mesh)
        self._groups_sharding = NamedSharding(mesh, PartitionSpec("groups"))
        # Every device reads the key every tick: put it there once.
        self.key = jax.device_put(
            self.key, NamedSharding(mesh, PartitionSpec())
        )

    def _init_host(self, cfg: EngineConfig, seed: int) -> None:
        """Host-side bookkeeping shared by __init__ and restore() —
        restore overwrites state/inbox from the checkpoint, so it must
        not pay for (or double-allocate) fresh device tensors."""
        self.cfg = cfg
        self.key = jax.random.PRNGKey(seed)
        self.drop_prob = 0.0
        # Per-edge enables [G, src, dst] — the dense form of labrpc's
        # per-ClientEnd enable/disable (reference: labrpc/labrpc.go:
        # 316-364; SURVEY §5.8 "partition by per-edge boolean enables").
        # Unlike ``alive`` (a crash mask that freezes the replica), a
        # partitioned replica stays live: timers run, candidacies fire,
        # but no message crosses a disabled edge.  ``replica_conn`` is
        # the per-replica connectivity that partition_replica derives
        # edges from (labrpc connect() semantics: an edge is up iff
        # *both* endpoints are connected).
        self.edge_up = np.ones((cfg.G, cfg.P, cfg.P), bool)
        self.replica_conn = np.ones((cfg.G, cfg.P), bool)
        self._edge_dev: Optional[jnp.ndarray] = None  # lazy device copy
        # Long-reordering mode (reference: labrpc/labrpc.go:289-299 —
        # 2/3 of replies delayed 200–2400 ms): each in-flight message is
        # independently pulled from the stream with ``reorder_prob`` and
        # redelivered reorder_min..reorder_max ticks later, landing
        # *behind* messages sent after it.  Held messages die if their
        # edge partitions or either endpoint restarts while in flight.
        self.reorder_prob = 0.0
        self.reorder_min, self.reorder_max = 2, 8
        self._np_rng = np.random.default_rng(seed ^ 0x5EED)
        self._delayed: list = []  # (release, prefix, (g,src,dst), fields)
        self.total_commits = 0
        self.backlog = np.zeros(cfg.G, np.int64)  # pending Start()s
        # Host-side payloads: (group, index) -> command.  The device
        # orders (term, index); data stays here (SURVEY §7.1).
        self.payloads: Dict[tuple, Any] = {}
        self._pending_payloads: Dict[int, list] = defaultdict(list)
        # Per-group bind high-water mark: an accept starting at or
        # below it is a truncation REBIND and triggers the stale-
        # binding eviction scan (see _bind_accepted).
        self._max_bound: Dict[int, int] = {}
        self.last_metrics: Dict[str, Any] = {}
        # Membership changes this driver has begun (add_learner,
        # begin_joint) or inherited open from a checkpoint (restore).
        # Nothing else opens one — seed_config writes voter masks, no
        # config entry, and the tick only finishes what begin_joint
        # started — so while this is 0 no group is reconfiguring and
        # the wedge watch (distributed/wedge.py) does not ask the device.
        self.config_changes = 0
        self.mesh = None
        self._mesh_tick = None
        self._groups_sharding = None  # the [G] vectors' sharding, on a mesh
        # Structured counters (utils/metrics.py): ticks always; per-tick
        # wall latency samples when the tracer (diagnostic mode) is on.
        self.metrics = Metrics()
        self.tick = 0  # host mirror of the device tick counter
        # Called with the old payload when a (group, index) binding is
        # overwritten — i.e. the old command lost its slot to a leader
        # change and will never commit at that index.
        self.on_payload_evicted: Optional[Any] = None
        # Called as (g, idx, term) when a payload binds at ingest —
        # split-group peering records the accept term so a stale slab
        # from a deposed leader can never replace a newer local binding
        # (engine/split.py).  None = skip the extra metric readback.
        self.on_payload_bound: Optional[Any] = None
        # Optional utils.trace.Tracer: each tick becomes a wall-clock
        # span carrying its metrics.  The fused path buffers the spans
        # from the stacked metrics and emits once per pump; only the
        # serial loop pays a per-tick sync for them.
        self.tracer = None
        # Asynchronous engine pipeline (engine/pipeline.py).
        # MRT_ENGINE_PIPELINE=0 is the kill switch: serial per-tick
        # stepping plus the synchronous pump loop, for clean A/B.
        self._pipeline_on = knob_bool("MRT_ENGINE_PIPELINE")
        # Dispatched-but-not-completed PendingTicks, oldest first.
        # Bounded by the serving pipeline depth (MRT_PIPELINE_DEPTH).
        self._inflight: list = []

    # -- fault injection --------------------------------------------------

    def set_alive(self, g: int, p: int, alive: bool) -> None:
        """Partition/crash a replica (mask form of per-edge disable,
        reference: labrpc enable/disable)."""
        self.state = self.state._replace(
            alive=self.state.alive.at[g, p].set(alive)
        )

    def set_edge(self, g: int, src: int, dst: int, up: bool) -> None:
        """Enable/disable the directed message edge src→dst in group g
        (asymmetric partitions, labrpc's raw per-ClientEnd enable).
        Note: a later ``partition_replica`` call on either endpoint
        recomputes group g's edges from per-replica connectivity,
        overriding raw edge settings."""
        self.edge_up[g, src, dst] = up
        self._edges_changed()

    def partition_replica(self, g: int, p: int, connected: bool) -> None:
        """Cut (or heal) live replica (g, p): labrpc connect()
        semantics — an edge is up iff both endpoints are connected, so
        healing one replica never resurrects edges of another that is
        still partitioned (reference: labrpc/labrpc.go:316-364)."""
        self.replica_conn[g, p] = connected
        conn = self.replica_conn[g]
        self.edge_up[g] = conn[:, None] & conn[None, :]
        self._edges_changed()

    def _edges_changed(self) -> None:
        """In-flight messages on now-disabled edges die immediately —
        the partition takes effect this tick, not next.  That includes
        messages held in the reorder delay queue: a cut-then-heal
        between two ticks must not resurrect them."""
        self._edge_dev = None
        if not self.edge_up.all():
            self.inbox = self._mask_partitions(self.inbox)
        if self._delayed:
            self._delayed = [
                it for it in self._delayed if self.edge_up[it[2]]
            ]

    def _edge_mask(self) -> jnp.ndarray:
        """``edge_up`` on the device(s), made once per change of it."""
        if self._edge_dev is None:
            # copy=True: zero-copy would alias the mutable edge_up
            # numpy mask into an async dispatch (see restore below).
            m = jnp.array(self.edge_up, copy=True)
            if self.mesh is not None:
                m = jax.device_put(m, self._groups_sharding)
            self._edge_dev = m
        return self._edge_dev

    def _mask_partitions(self, mb: Mailbox) -> Mailbox:
        m = self._edge_mask()
        return mask_active(mb, lambda _, a: a & m)

    def set_reorder(
        self, prob: float, min_ticks: int = 2, max_ticks: int = 8
    ) -> None:
        """Enable labrpc-style long reordering on the tensor transport:
        each message is delayed ``min_ticks..max_ticks`` ticks with
        probability ``prob`` (labrpc uses 2/3), arriving after traffic
        sent later — the non-FIFO delivery the conflict-backoff and
        staleness guards must survive (reference:
        raft/raft_append_entry.go:146-155)."""
        if not 0.0 <= prob <= 1.0 or min_ticks < 1 or max_ticks < min_ticks:
            raise ValueError("set_reorder: bad parameters")
        self.reorder_prob = float(prob)
        self.reorder_min, self.reorder_max = int(min_ticks), int(max_ticks)

    def _apply_reorder(self, mb: Mailbox) -> Mailbox:
        """Host-side delay queue over the dense mailbox.  A held message
        is redelivered once its release tick passes *and* its slot is
        free that tick (otherwise it waits — delaying further only
        increases reordering).  Test-path only: syncs the mailbox to
        host, so keep it off for throughput runs."""
        if self.reorder_prob == 0.0 and not any(
            release <= self.tick for release, *_ in self._delayed
        ):
            return mb  # nothing to pick, nothing due: skip the sync
        host = {f: np.array(getattr(mb, f)) for f in Mailbox._fields}
        rng = self._np_rng
        if self.reorder_prob > 0.0:
            for prefix, fields in _CHANNELS.items():
                act = host[prefix + "active"]
                pick = act & (rng.random(act.shape) < self.reorder_prob)
                for g, s, dst in np.argwhere(pick):
                    release = self.tick + int(
                        rng.integers(self.reorder_min, self.reorder_max + 1)
                    )
                    payload = {
                        f: (host[f][g, s] if host[f].ndim == 2  # sender lane
                            else host[f][g, s, dst]).copy()
                        for f in fields
                    }
                    # Chaos reorder buffer: every entry carries a
                    # release tick ≤ tick+reorder_max, so occupancy is
                    # bounded by reorder_max windows of traffic.
                    self._delayed.append(  # graftlint: disable=unbounded-queue
                        (release, prefix, (int(g), int(s), int(dst)), payload)
                    )
                act[pick] = False
        if self._delayed:
            held = []
            for item in self._delayed:
                release, prefix, (g, s, dst), payload = item
                if not self.edge_up[g, s, dst]:
                    continue  # partitioned while in flight: message dies
                if release <= self.tick and not host[prefix + "active"][g, s, dst]:
                    for f, v in payload.items():
                        # The held message may carry an older value than
                        # its sender's lane holds now (an older term):
                        # the lane goes per edge to take it.
                        host[f] = per_edge(host[f])
                        host[f][g, s, dst] = v
                else:
                    held.append(item)
            self._delayed = held
        # copy=True: this mailbox becomes self.inbox, which downstream
        # callees DONATE (split flush_staged, run_ticks) — zero-copy
        # aliasing the host scratch arrays would hand XLA memory it
        # does not own (see restore below).
        return Mailbox(**{f: jnp.array(v, copy=True) for f, v in host.items()})

    def restart_replica(self, g: int, p: int) -> None:
        """Crash-restart: persistent columns (term/vote/log/base/commit
        floor) survive; volatile leadership state resets
        (reference: raft/raft.go:69 readPersist on Make)."""
        st = self.state
        self.state = st._replace(
            role=st.role.at[g, p].set(FOLLOWER),
            votes=st.votes.at[g, p].set(False),
            pre_votes=st.pre_votes.at[g, p].set(False),
            # Conservative lease on rebirth: wait out ELECT_MIN before
            # granting prevotes (volatile, like the vote tallies).
            last_heard=st.last_heard.at[g, p].set(st.tick_no),
            # Check-quorum clock is leadership-scoped (reseeded at
            # become_leader), so rebirth just zeroes it.
            last_ack=st.last_ack.at[g, p].set(0),
            # Applied rewinds to the snapshot floor: the service replays
            # the log above base (commit knowledge is volatile in Raft).
            commit=st.commit.at[g, p].set(st.base[g, p]),
            applied=st.applied.at[g, p].set(st.base[g, p]),
            alive=st.alive.at[g, p].set(True),
        )
        # In-flight messages to/from the old incarnation die — including
        # any held in the reorder delay queue.
        self.inbox = self._mask_edges(self.inbox, g, p)
        self._delayed = [
            it
            for it in self._delayed
            if not (it[2][0] == g and p in (it[2][1], it[2][2]))
        ]

    def _mask_edges(self, mb: Mailbox, g: int, p: int) -> Mailbox:
        return mask_active(
            mb, lambda _, a: a.at[g, p, :].set(False).at[g, :, p].set(False)
        )

    def reset_replica(self, g: int, p: int) -> None:
        """Wipe slot (g, p) to a FRESH INCARNATION — the re-add path
        (a removed peer index being reused for a new server), NOT the
        crash-restart path (:meth:`restart_replica`, where persistent
        state must survive).

        Beyond the restarted-row reset, this clears the OTHER replicas'
        per-column state about p: a stale ``votes[g, :, p]`` grant from
        the old incarnation would otherwise count toward a quorum of
        the new config at the old term, and a stale ``match_idx`` would
        let a leader commit over entries the new incarnation never
        acked.  ``alive`` is left False — :meth:`add_learner` raises it
        once the config view is seeded."""
        st = self.state
        self.state = st._replace(
            # Own row: blank server.
            term=st.term.at[g, p].set(0),
            voted_for=st.voted_for.at[g, p].set(-1),
            role=st.role.at[g, p].set(FOLLOWER),
            commit=st.commit.at[g, p].set(0),
            applied=st.applied.at[g, p].set(0),
            base=st.base.at[g, p].set(0),
            base_term=st.base_term.at[g, p].set(0),
            log_len=st.log_len.at[g, p].set(0),
            log_term=st.log_term.at[g, p].set(0),
            next_idx=st.next_idx.at[g, p].set(1).at[g, :, p].set(1),
            hb_due=st.hb_due.at[g, p].set(0),
            last_heard=st.last_heard.at[g, p].set(st.tick_no),
            elect_dl=st.elect_dl.at[g, p].set(
                st.tick_no + self.cfg.ELECT_MAX
            ),
            # Cross-replica columns about p (the regression fix): no
            # vote, prevote, match or ack of the OLD incarnation may
            # leak into the new one's ledger.
            votes=st.votes.at[g, p].set(False).at[g, :, p].set(False),
            pre_votes=st.pre_votes.at[g, p]
            .set(False)
            .at[g, :, p]
            .set(False),
            match_idx=st.match_idx.at[g, p].set(0).at[g, :, p].set(0),
            last_ack=st.last_ack.at[g, p]
            .set(0)
            .at[g, :, p]
            .set(st.tick_no),
            alive=st.alive.at[g, p].set(False),
        )
        # In-flight traffic of the old incarnation dies with it.
        self.inbox = self._mask_edges(self.inbox, g, p)
        self._delayed = [
            it
            for it in self._delayed
            if not (it[2][0] == g and p in (it[2][1], it[2][2]))
        ]

    # -- membership change (joint consensus) -------------------------------

    def _require_membership(self) -> None:
        if not self.cfg.membership_on:
            raise RuntimeError(
                "membership change requires EngineConfig.membership and "
                "the jnp reduction path (use_pallas=False) — the Pallas "
                "tally/commit kernels are mask-unaware"
            )

    def config_of(self, g: int, p: Optional[int] = None) -> Dict[str, Any]:
        """Replica (g, p)'s config view (the leader's when p is None):
        voter index sets, joint flag, epoch and the latest config
        entry's log index."""
        if p is None:
            p = self.leader_of(g)
            if p is None:
                raise RuntimeError(f"group {g} has no leader")
        st = self.np_state()
        bits_old = int(st["voters_old"][g, p])
        bits_new = int(st["voters_new"][g, p])
        unpack = lambda b: sorted(
            q for q in range(self.cfg.P) if (b >> q) & 1
        )
        return {
            "peer": int(p),
            "voters_old": unpack(bits_old),
            "voters_new": unpack(bits_new),
            "joint": bool(st["joint"][g, p]),
            "epoch": int(st["cfg_epoch"][g, p]),
            "cfg_idx": int(st["cfg_idx"][g, p]),
        }

    def add_learner(self, g: int, p: int) -> None:
        """AddServer step 1: (re)seat slot (g, p) as a NON-VOTING
        learner of group g — a fresh incarnation (stale vote/match
        state of any prior tenant cleared, see :meth:`reset_replica`)
        whose config view mirrors the leader's, so it knows it is not
        a voter and never campaigns.  Catch-up is the ordinary
        replication path: the leader snapshot-fast-forwards it and
        streams the tail; promotion (:meth:`begin_joint`) should wait
        for :meth:`learner_match` to close on the leader's last index
        so the joint phase never depends on a cold log."""
        self._require_membership()
        lead = self.leader_of(g)
        if lead is None:
            raise RuntimeError(f"add_learner: group {g} has no leader")
        if lead == p:
            raise ValueError(f"add_learner: ({g},{p}) is the leader")
        st = self.np_state()
        if ((int(st["voters_old"][g, lead]) | int(st["voters_new"][g, lead]))
                >> p) & 1:
            raise ValueError(
                f"add_learner: peer {p} is a voter of group {g}; remove "
                f"it from the config before reseating the slot"
            )
        self.reset_replica(g, p)
        self.config_changes += 1
        st2 = self.state
        self.state = st2._replace(
            voters_old=st2.voters_old.at[g, p].set(
                st2.voters_old[g, lead]
            ),
            voters_new=st2.voters_new.at[g, p].set(
                st2.voters_new[g, lead]
            ),
            joint=st2.joint.at[g, p].set(st2.joint[g, lead]),
            cfg_epoch=st2.cfg_epoch.at[g, p].set(st2.cfg_epoch[g, lead]),
            cfg_idx=st2.cfg_idx.at[g, p].set(st2.cfg_idx[g, lead]),
            alive=st2.alive.at[g, p].set(True),
        )

    def learner_match(self, g: int, p: int) -> tuple:
        """(leader's match for p, leader's last index) — the catch-up
        gauge ``begin_joint`` callers poll before promoting."""
        lead = self.leader_of(g)
        if lead is None:
            raise RuntimeError(f"learner_match: group {g} has no leader")
        st = self.np_state()
        last = int(st["base"][g, lead] + st["log_len"][g, lead])
        return int(st["match_idx"][g, lead, p]), last

    def begin_joint(self, g: int, new_voters) -> int:
        """AddServer/RemoveServer step 2: append the C_old,new config
        entry at group g's leader (host surgery on the leader's row —
        the one entry the firehose cannot carry, since it must take
        effect ON APPEND).  From the next tick the leader replicates it
        like any entry; once it commits under BOTH quorums the tick
        auto-appends the C_new exit entry (core.py phase 5a-bis).
        Returns the joint entry's log index."""
        self._require_membership()
        new_voters = sorted(set(int(q) for q in new_voters))
        if not new_voters:
            raise ValueError("begin_joint: empty target voter set")
        if any(q < 0 or q >= self.cfg.P for q in new_voters):
            raise ValueError(
                f"begin_joint: voters {new_voters} out of range "
                f"0..{self.cfg.P - 1}"
            )
        lead = self.leader_of(g)
        if lead is None:
            raise RuntimeError(f"begin_joint: group {g} has no leader")
        st = self.np_state()
        if bool(st["joint"][g, lead]):
            raise RuntimeError(
                f"begin_joint: group {g} already has a config change in "
                f"flight (one at a time — Raft §6)"
            )
        mask = 0
        for q in new_voters:
            mask |= 1 << q
        if mask == int(st["voters_old"][g, lead]):
            raise ValueError("begin_joint: target equals current config")
        if self.cfg.L - 2 - self.cfg.E - int(st["log_len"][g, lead]) < 1:
            raise RuntimeError(
                f"begin_joint: group {g} leader log has no headroom"
            )
        idx = int(st["base"][g, lead] + st["log_len"][g, lead]) + 1
        term = int(st["term"][g, lead])
        self.config_changes += 1
        s = self.state
        self.state = s._replace(
            log_term=s.log_term.at[g, lead, idx % self.cfg.L].set(term),
            log_len=s.log_len.at[g, lead].add(1),
            voters_new=s.voters_new.at[g, lead].set(mask),
            joint=s.joint.at[g, lead].set(True),
            cfg_epoch=s.cfg_epoch.at[g, lead].add(1),
            cfg_idx=s.cfg_idx.at[g, lead].set(idx),
        )
        return idx

    def seed_config(self, voters) -> None:
        """Bootstrap-time config: make ``voters`` (a peer index list)
        the voter set of EVERY group, leaving the remaining slots as
        dead spares a later :meth:`add_learner` can reseat.  Host
        surgery on a cluster that has not run yet — call before the
        first tick (replica replacement on a live group goes through
        ``add_learner``/``begin_joint``)."""
        self._require_membership()
        voters = sorted(set(int(q) for q in voters))
        if not voters or any(q < 0 or q >= self.cfg.P for q in voters):
            raise ValueError(f"seed_config: bad voter set {voters}")
        if int(np.asarray(self.state.tick_no)) != 0:
            raise RuntimeError("seed_config: cluster already ticked")
        mask = 0
        for q in voters:
            mask |= 1 << q
        spares = [q for q in range(self.cfg.P) if q not in voters]
        st = self.state
        alive = st.alive
        for q in spares:
            alive = alive.at[:, q].set(False)
        self.state = st._replace(
            voters_old=jnp.full_like(st.voters_old, mask),
            voters_new=jnp.full_like(st.voters_new, mask),
            alive=alive,
        )

    def reconfiguring(self, groups=None) -> np.ndarray:
        """Per-group bool: a membership change is in flight — the group
        is in the joint phase, or its latest config entry has not yet
        committed.  Stateless read the wedge watchdog and placement
        health checks consult (a reconfiguring group's commit frontier
        may legitimately stall while it waits on BOTH quorums).  With
        ``groups``, those groups' rows alone are read (:meth:`rows_of`)
        and the answer is in their order."""
        if groups is None:
            return _reconfig_open(self.np_state())
        return _reconfig_open(
            self.rows_of(("joint", "cfg_idx", "commit"), groups)
        )

    # -- Start() ----------------------------------------------------------

    def start(self, g: int, command: Any = None) -> None:
        """Queue a command for group g (the synthetic firehose feeds
        this in bulk)."""
        self.backlog[g] += 1
        # Drained by the tick's ingest path at INGEST ops/group/tick;
        # admission control above this layer (reply-queue caps, item 3)
        # is what bounds a sustained overload.
        self._pending_payloads[g].append(command)  # graftlint: disable=unbounded-queue

    def start_bulk(self, counts: np.ndarray) -> None:
        self.backlog += counts

    def start_run(self, g: int, frame: Any, rows: "np.ndarray") -> None:
        """Queue a contiguous RUN of firehose-frame rows for group
        ``g`` — ONE pending entry and one backlog bump of ``len(rows)``
        instead of a per-op append (the columnar serving path,
        engine/firehose.py).  ``rows`` are original frame row indices
        in submission order."""
        self.backlog[g] += len(rows)
        self._pending_payloads[g].append(PayloadRun(frame, rows))

    def _evict_rebound_range(self, g: int, lo: int, hi: int) -> None:
        """A fresh accept is about to bind slots ``[lo, hi]`` of group
        ``g``: every EXISTING binding overlapping ``[lo, ...)`` is
        stale — the log was truncated below it and those slots rewritten
        (an accept at start s0 means the leader's log ended at s0, so
        everything above is gone; slots beyond ``hi`` bound earlier are
        equally stale).  Per-op bindings sit at their own key; a slice
        keyed BELOW ``lo`` can straddle into the range, but its length
        is bounded by cfg.INGEST (one accept batch), so a bounded
        backward scan finds it.  A straddler's prefix below ``lo``
        survived the truncation and stays bound; the tail is evicted."""
        pay = self.payloads
        for idx in range(max(1, lo - self.cfg.INGEST + 1), hi + 1):
            old = pay.get((g, idx))
            if old is None:
                continue
            if isinstance(old, PayloadSlice):
                end = idx + old.count - 1
                if end < lo:
                    continue  # wholly below the rewrite: still valid
                if idx < lo:
                    # Straddler: keep the surviving prefix, evict the
                    # rewritten tail.
                    tail = PayloadSlice(old.frame, old.rows[lo - idx:])
                    old.rows = old.rows[: lo - idx]
                    if self.on_payload_evicted:
                        self.on_payload_evicted(tail)
                    continue
                pay.pop((g, idx))
                if self.on_payload_evicted:
                    self.on_payload_evicted(old)
            elif idx >= lo:
                pay.pop((g, idx))
                if self.on_payload_evicted:
                    self.on_payload_evicted(old)

    def _bind_accepted(
        self, g: int, k: int, s0: int, term: Optional[int]
    ) -> None:
        """Bind ``k`` accepted slots ``s0+1..s0+k`` of group ``g`` to
        pending payloads/runs, evicting whatever stale bindings the
        rewrite invalidated first (see :meth:`_evict_rebound_range` —
        without it, a slice bound before a truncation could later
        bulk-apply rows over slots that now hold different entries).

        The eviction scan only fires on a REBIND — an accept starting
        at or below the group's bind high-water mark (leader-churn
        truncation); steady-state accepts pay one dict probe."""
        # One accept batch can never exceed the kernel's ingest lane
        # width; a larger k means the accept-count column was
        # corrupted, and binding it would smear payloads across slots
        # the kernel never accepted.
        assert k <= self.cfg.INGEST, (
            f"accept batch k={k} exceeds cfg.INGEST={self.cfg.INGEST} "
            f"for group {g}"
        )
        lo, hi = s0 + 1, s0 + k
        mb = self._max_bound.get(g, 0)
        if self.payloads and lo <= mb:
            self._evict_rebound_range(g, lo, hi)
        if hi > mb:
            self._max_bound[g] = hi
        pend = self._pending_payloads.get(g)
        if not pend:
            return
        off = 0
        while off < k and pend:
            head = pend[0]
            slot = (g, s0 + 1 + off)
            if isinstance(head, PayloadRun):
                # One bound entry covers a whole run prefix —
                # per-slice, not per-op.
                take = min(head.remaining, k - off)
                self.payloads[slot] = head.take(take)
                if head.remaining == 0:
                    pend.pop(0)
                if term is not None:
                    for j in range(take):
                        self.on_payload_bound(slot[0], slot[1] + j, term)
                off += take
            else:
                self.payloads[slot] = pend.pop(0)
                if term is not None:
                    self.on_payload_bound(slot[0], slot[1], term)
                off += 1

    # -- tick loop --------------------------------------------------------

    def step(self, n: int = 1) -> Dict[str, Any]:
        """Advance ``n`` ticks.  Multi-tick calls on a pipeline-enabled
        driver run the fused device scan (engine/pipeline.py — one host
        sync per call instead of one per tick); everything else —
        single ticks, reorder chaos in flight,
        ``MRT_ENGINE_PIPELINE=0`` — takes the serial per-tick loop.
        Both paths are bit-identical by contract
        (tests/test_engine_pipeline.py).

        Synchronous callers (admin_sync, checkpoint replay, tests) may
        land here while the serving loop still has dispatched batches
        in flight: drain them first, in dispatch order — safe because
        ``step`` already must run on the owning thread, and the serving
        loop's ``PumpCycle._pump_done`` ignores batches completed from
        under it."""
        while self._inflight:
            p = self._inflight[0]
            self.complete_ticks(p, p.fetch())
        if n > 1 and self.fused_eligible():
            pending = self.dispatch_ticks(n)
            return self.complete_ticks(pending, pending.fetch())
        return self._step_serial(n)

    @property
    def pipeline_on(self) -> bool:
        """``MRT_ENGINE_PIPELINE`` as this driver read it, the knob's
        one reader: the serving pump cycle asks here whether to start
        its pump thread."""
        return self._pipeline_on

    def fused_eligible(self) -> bool:
        """True when the fused scan path may run: pipeline enabled and
        no reorder chaos active or held (``_apply_reorder`` rewrites
        the mailbox on host between ticks — inherently unfusable).  A
        mesh driver fuses like any other (``sharded_step_ticks``)."""
        return (
            self._pipeline_on
            and self.reorder_prob == 0.0
            and not self._delayed
        )

    def _count_ticks(self, n: int) -> None:
        """``ticks``, and the replicas those ticks advanced
        (``engine.replica_ticks`` / ``ticks`` = G x P: a scrape says
        which deployment this driver holds)."""
        self.metrics.inc("ticks", n)
        self.metrics.inc("engine.replica_ticks", n * self.cfg.G * self.cfg.P)

    def _step_serial(self, n: int = 1) -> Dict[str, Any]:
        assert not self._inflight, (
            "serial step with fused tick batches in flight — complete "
            "them first, or the two tick streams interleave"
        )
        cfg = self.cfg
        self._count_ticks(n)
        for _ in range(n):
            self.tick += 1
            t_wall = time.perf_counter() if self.tracer else 0.0
            tick_key = jax.random.fold_in(self.key, self.tick)
            have_backlog = bool(self.backlog.any())
            new_cmds = jnp.asarray(
                np.minimum(self.backlog, cfg.INGEST), jnp.int32
            ) if have_backlog else jnp.zeros(cfg.G, jnp.int32)
            if self._mesh_tick is not None:
                state, outbox, metrics = self._mesh_tick(
                    self.state, self.inbox, new_cmds, tick_key
                )
                metrics = _fold_lanes(metrics, jnp, 0)
            else:
                state, outbox, metrics = tick(
                    cfg, self.state, self.inbox, new_cmds, tick_key
                )
            if self.drop_prob > 0.0:
                outbox = apply_faults(
                    outbox,
                    jax.random.fold_in(tick_key, 0xFA),
                    jnp.float32(self.drop_prob),
                    cfg,
                )
            if not self.edge_up.all():
                outbox = self._mask_partitions(outbox)
            if self.reorder_prob > 0.0 or self._delayed:
                outbox = self._apply_reorder(outbox)
            self.state, self.inbox = state, outbox
            if have_backlog:
                # Host sync only while commands are in flight.
                accepted = np.asarray(metrics["accepted"])
                starts = np.asarray(metrics["start_index"])
                terms = (
                    np.asarray(metrics["accept_term"])
                    if self.on_payload_bound else None
                )
                for g in np.nonzero(accepted)[0]:
                    k = int(accepted[g])
                    self.backlog[g] -= k
                    self._bind_accepted(
                        int(g), k, int(starts[g]),
                        int(terms[g]) if terms is not None else None,
                    )
            # Accumulate on device; converted lazily by readers.
            self._commits_dev = (
                getattr(self, "_commits_dev", jnp.int32(0)) + metrics["commits"]
            )
            self.last_metrics = metrics
            if self.tracer:
                commits = int(metrics["commits"])  # forces the sync
                now_us = time.perf_counter() * 1e6
                self.tracer.span(
                    "tick",
                    t_wall * 1e6,
                    now_us - t_wall * 1e6,
                    track="engine",
                    tick=self.tick,
                    commits=commits,
                    leaders=int(metrics["leaders"]),
                )
                self.tracer.counter(
                    "consensus", now_us,
                    {"commits": commits, "backlog": int(self.backlog.sum())},
                )
        return self.last_metrics

    # -- fused pipeline (engine/pipeline.py) ------------------------------

    def dispatch_ticks(self, n: int):
        """Dispatch a fused ``n``-tick batch to the device WITHOUT
        waiting for it: JAX async dispatch makes the returned arrays
        futures, so this only pays trace/enqueue cost on the calling
        (scheduler-loop) thread.  Requires :meth:`fused_eligible`.

        The host tick counter and state/inbox advance immediately —
        payload binding and backlog bookkeeping are deferred to
        :meth:`complete_ticks` once the stacked metrics are fetched
        (``PendingTicks.fetch``, safe off-thread)."""
        from .pipeline import PendingTicks, sharded_step_ticks, step_ticks

        cfg = self.cfg
        t_dispatch = time.perf_counter()
        with pump_phase(self.metrics, "dispatch"):
            self._count_ticks(n)
            tick0 = self.tick
            bl = np.minimum(self.backlog, np.int64(2**31 - 1)).astype(np.int32)
            if self.mesh is not None:
                bl = jax.device_put(bl, self._groups_sharding)
            for p in self._inflight:
                # Batches already dispatched will consume part of the
                # host backlog when they complete; the device must not
                # ingest those commands again (the depth ≥ 2
                # double-ingest hazard).  accepts_dev never left the
                # device (on a mesh: its shards never left theirs), so
                # this stays async.
                bl = jnp.maximum(bl - p.accepts_dev, 0)
            with_drop = self.drop_prob > 0.0
            with_edges = not bool(self.edge_up.all())
            # The scalars (and, on one chip, the backlog) go as numpy:
            # each value made by a device program of its own costs the
            # loop as much as a copy does (PERF.md §6, PR 39).  The edge
            # mask is a static-dead operand on the clean path.
            args = (
                bl, np.float32(self.drop_prob),
                self._edge_mask() if with_edges else np.zeros((), np.bool_),
                np.int32(tick0), self.key,
            )
            if self.mesh is None:
                state, inbox, _bl_left, buf, accepts = step_ticks(
                    cfg, self.state, self.inbox, n, with_drop, with_edges, *args
                )
            else:
                # The same scan, each device on its groups.
                state, inbox, _bl_left, buf, accepts = sharded_step_ticks(
                    cfg, self.mesh, n, with_drop, with_edges
                )(self.state, self.inbox, *args)
            self.state, self.inbox = state, inbox
            self.tick = tick0 + n
            pending = PendingTicks(
                n=n, tick0=tick0, buf=buf, accepts_dev=accepts,
                t_dispatch=t_dispatch,
                pump=self.metrics.counters.get("pump.count", 0),
                mesh_devices=0 if self.mesh is None else self.mesh.devices.size,
            )
            self._inflight.append(pending)  # graftlint: disable=unbounded-queue
        pending.t_dispatched = time.perf_counter()
        return pending

    def complete_ticks(self, pending, host_rec) -> Dict[str, Any]:
        """Fold a fetched batch back into host bookkeeping: per-tick
        backlog decrements and payload binding replayed in tick order
        from the stacked record, the commit accumulator, last_metrics,
        and (tracer mode) the buffered per-tick spans — one host sync
        per pump where the serial loop paid one per tick.  Must run on
        the owning (scheduler) thread, in dispatch order."""
        assert self._inflight and self._inflight[0] is pending, (
            "complete_ticks out of dispatch order"
        )
        # The fetch ran on the pump thread, which only stamps the batch:
        # the registry is written from this (the owning) thread alone.
        m = self.metrics
        m.observe("pump.handoff_s", pending.t_fetch - pending.t_dispatched)
        m.observe("pump.fetch_s", pending.t_fetched - pending.t_fetch)
        m.observe("pump.wait_s", pending.t_ready - pending.t_fetch)
        m.observe("pump.copy_s", pending.t_fetched - pending.t_ready)
        m.observe("pump.post_s", time.perf_counter() - pending.t_fetched)
        m.inc("pump.readback_bytes", pending.nbytes)
        m.inc("pump.readback_copies", pending.ncopies)
        with pump_phase(m, "complete"):
            self._inflight.pop(0)
            if self.mesh is not None:
                host_rec = _fold_lanes(host_rec, np, 1)  # [n_ticks, n_devices]
            accepted = host_rec["accepted"]  # i32[n, G]
            starts = host_rec["start_index"]
            terms = (
                host_rec["accept_term"] if self.on_payload_bound else None
            )
            # np.nonzero on [n, G] is row-major: tick-major, group-minor —
            # exactly the serial loop's binding order.
            ii, gg = np.nonzero(accepted)
            for i, g in zip(ii, gg):
                k = int(accepted[i, g])
                self.backlog[g] -= k
                self._bind_accepted(
                    int(g), k, int(starts[i, g]),
                    int(terms[i, g]) if terms is not None else None,
                )
            # Entries the batch ingested, and those of them (slots
            # start+1 .. start+k) the group's commit covers by the
            # batch's last tick: whether an update commits inside the
            # pump that ingests it (pump_cycle.SERVED_TICKS).
            took = accepted[ii, gg]
            m.inc("pump.ingested", int(took.sum()))
            m.inc("pump.committed_in_batch", int(np.clip(
                host_rec["commit_index"][-1, gg] - starts[ii, gg], 0, took
            ).sum()))
            # A host int from here on: the serial loop (warm-up,
            # elections) leaves a device scalar, which a sum would keep
            # on the device — a program launched on the loop every pump.
            self._commits_dev = (
                int(getattr(self, "_commits_dev", 0))
                + int(host_rec["commits"].sum())
            )
            self.last_metrics = {k: v[-1] for k, v in host_rec.items()}
            if self.tracer:
                self._emit_tick_spans(pending, host_rec)
        return self.last_metrics

    def _emit_tick_spans(self, pending, rec) -> None:
        """Tracer spans for a completed fused batch: the per-tick wall
        clock no longer exists (ticks fused on device), so the batch
        wall is spread evenly across its ticks.  Commit/leader fields
        come from the stacked record — no extra device syncs."""
        n = pending.n
        now = time.perf_counter()
        per = max(now - pending.t_dispatch, 1e-9) / n
        t = pending.t_dispatch
        commits_total = int(rec["commits"].sum())
        for i in range(n):
            self.tracer.span(
                "tick",
                t * 1e6,
                per * 1e6,
                track="engine",
                tick=pending.tick0 + 1 + i,
                commits=int(rec["commits"][i]),
                leaders=int(rec["leaders"][i]),
            )
            t += per
        self.tracer.counter(
            "consensus", now * 1e6,
            {"commits": commits_total, "backlog": int(self.backlog.sum())},
        )

    @property
    def commits_total(self) -> int:
        return int(getattr(self, "_commits_dev", 0)) + self.total_commits

    def run_until_quiet_leaders(self, max_ticks: int = 500) -> bool:
        """Advance until every group has exactly one live leader."""
        stride = 5  # check every few ticks: readbacks are host syncs
        for _ in range(0, max_ticks, stride):
            self.step(stride)
            if self.leaders_per_group().min() >= 1:
                if self.leaders_at_max_term_per_group().max() <= 1:
                    return True
        return False

    # -- checkpoint / resume ----------------------------------------------
    #
    # Whole-engine suspend/resume: the batched analog of the reference's
    # Persister (reference: raft/persister.go:57-64 atomic pair save),
    # scaled to the world where one host owns every replica of every
    # group.  Because the checkpoint captures the ENTIRE cluster
    # atomically at a tick boundary (state + in-flight mailbox + host
    # bookkeeping), restoring it is equivalent to pausing and resuming
    # the world — consistent by construction, no per-replica recovery
    # protocol needed.  This is the TPU-preemption recovery path;
    # *individual* crash fidelity stays with restart_replica().

    # v2: EngineState gained pre_votes/last_heard (PreVote support);
    # Mailbox gained vr_pre/vp_pre.
    # v3: EngineState gained last_ack (check-quorum stepdown).
    # v4: EngineState gained voters_old/voters_new/joint/cfg_epoch/
    # cfg_idx and Mailbox gained the ar_cfg_* lanes (joint-consensus
    # membership change) — config state rides the generic _asdict()
    # path, so an in-flight reconfig survives checkpoint/restore.
    # Still v4, same fields: the SENDER_LANES are saved as they are,
    # [G, src] (per edge only where a host path left them so), and a
    # bundle written when they were always [G, src, dst] restores by
    # collapsing each lane equal across destinations (_collapse_lanes);
    # a lane that is not stays per edge, which the tick reads too.
    CKPT_VERSION = 4

    def save(self, path: str, extra: Optional[Dict[str, Any]] = None) -> str:
        """Atomically write a full checkpoint.  ``extra`` carries
        service-level state (e.g. ``FrontierService.state_dict()``) so
        engine and services checkpoint at the same tick boundary."""
        if self._inflight:
            # state/inbox already reflect the dispatched batches but
            # backlog/payload bookkeeping does not — a checkpoint here
            # would tear the tick boundary.  The durable serving loop
            # drains the pipeline before checkpointing (and pins the
            # pipeline depth to 1); see ARCHITECTURE §20.
            raise RuntimeError(
                "save() with fused tick batches in flight — drain the "
                "pipeline (complete_ticks) before checkpointing"
            )
        blob = {
            "version": self.CKPT_VERSION,
            "mesh_devices": (
                int(self.mesh.devices.size) if self.mesh is not None else 0
            ),
            "cfg": self.cfg,
            "state": {
                k: np.asarray(v) for k, v in self.state._asdict().items()
            },
            "inbox": {
                k: np.asarray(v) for k, v in self.inbox._asdict().items()
            },
            "tick": self.tick,
            "key": np.asarray(self.key),
            "backlog": self.backlog,
            "payloads": self.payloads,
            "pending_payloads": dict(self._pending_payloads),
            "edge_up": self.edge_up,
            "replica_conn": self.replica_conn,
            "drop_prob": self.drop_prob,
            "reorder": (self.reorder_prob, self.reorder_min, self.reorder_max),
            # The reorder RNG's position: a resumed run must draw the
            # same picks/delays as the uninterrupted one (determinism
            # is the sim's debugging contract).
            "np_rng": self._np_rng.bit_generator.state,
            "delayed": self._delayed,
            "commits_total": self.commits_total,
            "extra": extra or {},
        }
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            pickle.dump(blob, f, protocol=pickle.HIGHEST_PROTOCOL)
            f.flush()
            # Intentional loop-thread sync point: checkpoint atomicity
            # (the durable server truncates its WAL right after this
            # returns, so the checkpoint must hit the platter first).
            os.fsync(f.fileno())  # graftlint: disable=blocking-in-callback
        os.replace(tmp, path)  # atomic: a crash mid-save keeps the old one
        # Make the rename itself durable: the durable-server protocol
        # truncates its WAL right after this call, and on power loss
        # POSIX gives no cross-file ordering — the truncation must not
        # become durable while the checkpoint rename does not.
        dfd = os.open(os.path.dirname(path) or ".", os.O_RDONLY)
        try:
            os.fsync(dfd)  # graftlint: disable=blocking-in-callback
        finally:
            os.close(dfd)
        return path

    @classmethod
    def restore(
        cls, path: str, mesh=None, replicas: Optional[int] = None
    ) -> "EngineDriver":
        """Rebuild a driver from :meth:`save`.  The returned driver
        continues from the exact saved tick; the checkpoint's ``extra``
        dict is available as ``driver.restored_extra``.

        The driver takes the checkpoint's own ``cfg``.  A caller that
        was asked for a replica count passes it as ``replicas``: a
        checkpoint written at another P is refused, never served under
        a flag that says otherwise.

        A checkpoint taken from a mesh driver must be restored with a
        ``mesh`` (same device count) — silently coming back
        single-device would drop the sharding/zero-collective
        guarantees and concentrate the full state on one chip."""
        with open(path, "rb") as f:
            blob = pickle.load(f)
        if blob.get("version") != cls.CKPT_VERSION:
            raise ValueError(
                f"checkpoint version {blob.get('version')} != {cls.CKPT_VERSION}"
            )
        saved_mesh = blob.get("mesh_devices", 0)
        if saved_mesh and mesh is None:
            raise ValueError(
                f"checkpoint was taken from a {saved_mesh}-device mesh "
                f"driver; pass restore(..., mesh=) with a "
                f"{saved_mesh}-device mesh to re-shard it"
            )
        if saved_mesh and mesh is not None and (
            int(mesh.devices.size) != saved_mesh
        ):
            # Silently concentrating N× the per-chip state on a smaller
            # mesh is an OOM/perf cliff, not a config the operator
            # asked for — loud beats lucky.
            raise ValueError(
                f"checkpoint was taken on {saved_mesh} devices but "
                f"restore got a {int(mesh.devices.size)}-device mesh"
            )
        if replicas is not None and blob["cfg"].P != replicas:
            raise ValueError(
                f"checkpoint {path} was written with {blob['cfg'].P} "
                f"replicas a group, this server was asked for {replicas}: "
                f"start it with --replicas {blob['cfg'].P} or on a fresh "
                f"--data-dir"
            )
        d = object.__new__(cls)  # skip __init__: no throwaway device state
        d._init_host(blob["cfg"], seed=0)
        # jnp.array(..., copy=True), NOT jnp.asarray: the CPU backend
        # may zero-copy a numpy array, leaving the device buffer
        # aliased to the unpickled blob — and the tick DONATES its
        # state/inbox inputs, so the first step after restore would
        # write through into non-jax-owned memory (observed as a
        # SIGSEGV inside the first post-restore dispatch when the
        # executable comes from the persistent compilation cache).
        d.state = EngineState(
            **{k: jnp.array(v, copy=True) for k, v in blob["state"].items()}
        )
        d.inbox = Mailbox(
            **{
                k: jnp.array(v, copy=True)
                for k, v in _collapse_lanes(blob["inbox"]).items()
            }
        )
        d.tick = blob["tick"]
        d.key = jnp.array(blob["key"], copy=True)
        if _reconfig_open(blob["state"]).any():
            d.config_changes = 1  # a reconfig was open at the checkpoint
        if mesh is not None:
            d._use_mesh(mesh)
        d.backlog = blob["backlog"]
        d.payloads = blob["payloads"]
        d._pending_payloads = defaultdict(list, blob["pending_payloads"])
        # Rebuild the bind high-water marks from the restored bindings
        # (a zeroed mark would skip the rebind eviction scan and let a
        # post-restore truncation phantom-apply a stale slice).
        d._max_bound = {}
        for (g, idx), p in d.payloads.items():
            end = idx + (p.count - 1 if isinstance(p, PayloadSlice) else 0)
            if end > d._max_bound.get(g, 0):
                d._max_bound[g] = end
        d.edge_up = blob["edge_up"]
        d.replica_conn = blob["replica_conn"]
        d._edge_dev = None
        d.drop_prob = blob["drop_prob"]
        d.reorder_prob, d.reorder_min, d.reorder_max = blob["reorder"]
        d._np_rng.bit_generator.state = blob["np_rng"]
        d._delayed = blob["delayed"]
        d.total_commits = blob["commits_total"]
        d.restored_extra = blob["extra"]
        return d

    # -- host readbacks ----------------------------------------------------
    # ``rows_of`` / ``rows_stacked`` gather the rows asked for and are
    # what a served node calls (the orphan sweep on every 32nd pump, the
    # wedge watch for a stalled group); everything built on ``np_state``
    # copies every plane whole and is the membership, test and debug
    # path.

    def np_state(self) -> Dict[str, np.ndarray]:
        return {k: np.asarray(v) for k, v in self.state._asdict().items()}

    def rows_of(self, planes, groups) -> Dict[str, np.ndarray]:
        """Host copies of the named state planes' rows for ``groups``
        alone: one gather on the device and ``len(groups)`` rows back,
        where :meth:`np_state` copies every ``[G, P]`` plane.  The index
        vector is padded to a power of two, so a growing set of groups
        compiles at most log2(G) small programs.  Waits for the tick
        batch in flight, like any read of ``state``; call it on the
        owning (scheduler) thread."""
        rows = _take_rows(
            tuple(getattr(self.state, name) for name in planes),
            _padded_index(groups),
        )
        return {
            name: np.asarray(r)[: len(groups)]
            for name, r in zip(planes, rows)
        }

    def rows_stacked(self, planes, groups) -> np.ndarray:
        """:meth:`rows_of` for ``[G, P]`` planes as ONE ``int32`` array
        ``[len(planes), len(groups), P]`` (a ``bool`` plane reads 0 / 1):
        one copy off the device where :meth:`rows_of` makes one a plane
        (0.9 ms each on the chip).  A caller on the serving path asks
        for a fixed number of rows, so that it owns one program, and
        runs it once before ``ready``."""
        rows = _take_rows_stacked(
            tuple(getattr(self.state, name) for name in planes),
            _padded_index(groups),
        )
        return np.asarray(rows)[:, : len(groups)]

    def leaders_per_group(self) -> np.ndarray:
        st = self.np_state()
        return (
            ((st["role"] == LEADER) & st["alive"]).sum(axis=1)
        )

    def leaders_at_max_term_per_group(self) -> np.ndarray:
        st = self.np_state()
        lead = (st["role"] == LEADER) & st["alive"]
        # Leaders are unique per *term*; count leaders in the max term.
        max_term = np.where(lead, st["term"], -1).max(axis=1, keepdims=True)
        return (lead & (st["term"] == max_term)).sum(axis=1)

    def leader_of(self, g: int) -> Optional[int]:
        st = self.np_state()
        lead = np.nonzero((st["role"][g] == LEADER) & st["alive"][g])[0]
        if len(lead) == 0:
            return None
        terms = st["term"][g][lead]
        return int(lead[np.argmax(terms)])

    def log_terms_of(
        self, g: int, p: int, st: Optional[Dict[str, np.ndarray]] = None
    ) -> Dict[int, int]:
        """Absolute index -> term for replica (g, p)'s ring window.

        Pass a pre-read ``st`` (from :meth:`np_state`) when reading many
        replicas — each call otherwise syncs the full state to host."""
        if st is None:
            st = self.np_state()
        base, ln = int(st["base"][g, p]), int(st["log_len"][g, p])
        ring = st["log_term"][g, p]
        return {
            i: int(ring[i % self.cfg.L]) for i in range(base + 1, base + ln + 1)
        }

    def check_log_matching(self, g: int) -> None:
        """Safety: all replicas agree on terms up to their common window
        below min(commit) (Log Matching + State Machine Safety)."""
        st = self.np_state()
        commits = st["commit"][g]
        floor = int(min(commits))
        views = [self.log_terms_of(g, p, st) for p in range(self.cfg.P)]
        bases = st["base"][g]
        for i in range(int(max(bases)) + 1, floor + 1):
            terms = {v[i] for v in views if i in v}
            assert len(terms) <= 1, (
                f"group {g}: index {i} has conflicting committed terms {terms}"
            )
