"""The SHARDED engine service behind the TCP front door — the
sharded half of the former engine_server.py (split round 4; the wire
layer lives in engine_wire.py, durability/replay in
engine_durability.py, clerks in engine_clerks.py).

``EngineShardKVService`` wraps a :class:`~multiraft_tpu.engine.shardkv.
BatchedShardKV`: server-side key→shard routing against the replicated
config, the reference clerk retry semantics (ErrWrongGroup →
re-route, shardkv/client.go:68-129), multi-op frames, fleet-mode
migration RPCs (pull_shard/delete_shard — Challenge 1 across
processes), and durable serving (checkpoint + WAL + recovery via
:class:`~.engine_durability.ShardWalReplay`).
"""

from __future__ import annotations

import functools
import os
import time
from typing import Optional, Sequence

from ..engine.core import EngineConfig
from ..engine.firehose import MAX_FIREHOSE_ROWS
from ..engine.host import EngineDriver
from ..engine.instrument import ReadyStages, count_compiles, count_gc, trace_loop
from ..services.shardctrler import ShardSpace
from ..sim.scheduler import TIMEOUT
from .engine_durability import (
    EngineDurability,
    ShardWalReplay,
    await_frame_synced,
    demote_unsynced_rows,
)
from .engine_wire import (
    ERR_TIMEOUT,
    OK,
    EngineCmdArgs,
    EngineCmdReply,
    make_mesh,
)
from .observe import Observability
from .pump_cycle import SERVED_TICKS, PumpCycle
from .realtime import RealtimeScheduler
from .tcp import RpcNode

__all__ = ["EngineShardKVService", "serve_engine_shardkv"]


class EngineShardKVService:
    """``EngineShardKV.command``: the sharded engine service behind the
    same TCP front door.  Key→shard routing happens server-side against
    the replicated config; WRONG_GROUP during migration re-routes like
    the reference clerk (shardkv/client.go:68-129).

    **Fleet mode** (``peers`` given): this process hosts a subset of
    the global gid space and its ``BatchedShardKV`` migrates shards
    to/from peer processes over the network — ``remote_fetch`` becomes
    a ``pull_shard`` RPC to the owning peer, ``remote_delete`` a
    ``delete_shard`` RPC riding the peer's log (Challenge 1 across
    processes).  Ops for a gid hosted elsewhere answer ErrWrongGroup so
    the fleet clerk re-routes, exactly like a reference group answering
    for a shard it no longer owns."""

    RESUBMIT_S = 0.25
    DEADLINE_S = 5.0
    # Per-RPC bound on one migration fetch/delete attempt; the
    # orchestration sweep re-issues after a timeout.
    MIGRATE_RPC_S = 2.0

    def __init__(
        self,
        sched: RealtimeScheduler,
        skv,  # BatchedShardKV
        pump_interval: float = 0.002,
        ticks_per_pump: int = SERVED_TICKS,
        peers: Optional[dict] = None,  # gid -> TcpClientEnd (remote owners)
        durability: Optional[EngineDurability] = None,
        obs=None,
        fleet: Optional[bool] = None,
        make_end=None,  # (host, port) -> TcpClientEnd, for placement pushes
        placement0: Optional[dict] = None,  # gid -> (host, port), version 0
        fleet_addrs: Optional[dict] = None,  # proc -> (host, port): the
        # whole fleet, state-plane ship targets (distributed/stateplane)
        me: Optional[int] = None,  # this process's index in fleet_addrs
        ship_rules=None,  # [(regex, ShipSpec)] declarative standby rules
        ship_sync: Optional[bool] = None,  # acks gate on shipment
        ship_window_s: Optional[float] = None,
    ) -> None:
        self.sched = sched
        self.skv = skv
        self.peers = dict(peers or {})
        # A fleet process whose peer map is momentarily empty (all gids
        # local, or rebuilt by a placement push) must KEEP answering
        # ErrWrongGroup for foreign gids — hence the explicit flag.
        self._fleet = bool(self.peers) if fleet is None else fleet
        self._make_end = make_end
        self._ends_by_addr: dict = {}
        # (version, {gid: (host, port)}) — advanced only by `place`
        # pushes with a strictly newer version (controller restarts and
        # reordered pushes are harmless).
        self._placement = (0, dict(placement0 or {}))
        self._dur = durability
        # The owning node's observability plane (a private one when the
        # service is built without a node).
        self.obs = obs if obs is not None else Observability()
        self.m = self.obs.metrics
        # seq of the WAL record covering each applied insert — the GC
        # gate below refuses to ask the old owner to delete until the
        # inserted blob (possibly the last copy) is fsynced here.
        self._insert_seqs: dict = {}
        # (client_id, command_id) -> WAL seq of the op's apply-time
        # (commit order) record; handlers gate their ack on it being
        # fsynced.  Pruned once synced (absence = already durable).
        self._write_seqs: dict = {}
        self._admin_seqs: dict = {}  # command_id -> WAL seq
        # seq of the WAL record covering each applied delete — the
        # delete_shard RPC reply gates on it being fsynced: the puller
        # confirms (and never re-asks) the moment we answer OK, so an
        # OK that could be lost to a crash would leave a BEPULLING slot
        # here that nothing ever clears, wedging config advance.
        self._delete_seqs: dict = {}
        if self._dur is not None:
            skv.on_insert = self._on_insert_applied
            skv.on_delete = self._on_delete_applied
            skv.on_confirm = self._on_confirm_applied
            # The committing gid travels in the record: recovery REDOES
            # the write into that gid's slot directly (see
            # _redo_client_op) — re-routing by the latest config would
            # drop a write acked at an old owner just before a config
            # change, and a peer that never pulled pre-crash would then
            # pull an empty slot.
            skv.on_write = lambda gid, op: self._write_seqs.__setitem__(
                (op.client_id, op.command_id),
                durability.log(("skv", gid, op.op, op.key, op.value,
                                op.client_id, op.command_id)),
            )
            skv.on_ctrl = lambda op: self._admin_seqs.__setitem__(
                op.command_id,
                durability.log(("admin", op.kind, op.arg, op.command_id)),
            )
        if self._fleet:
            self._fetches: dict = {}  # (gid, shard, num) -> Future
            self._deletes: dict = {}
            skv.remote_fetch = self._remote_fetch
            skv.remote_delete = self._remote_delete
        # Durable state plane (distributed/stateplane.py): ship each
        # hosted group's snapshot+tail to rule-chosen standbys, and
        # receive other owners' shipments into a StandbyStore.  Wired
        # only in fleet mode with the fleet roster known.
        self._plane = None
        self._standby = None
        self._ship_futs: dict = {}  # proc -> in-flight ship Future
        self._ship_ends: dict = {}
        self._fleet_addrs = dict(fleet_addrs or {})
        if self._fleet_addrs and me is not None:
            from . import flightrec
            from .stateplane import StandbyStore, StatePlane

            self._standby = StandbyStore(obs=obs)
            self._plane = StatePlane(
                skv, me=int(me), n_procs=len(self._fleet_addrs),
                send=self._ship_send, rules=ship_rules,
                window_s=ship_window_s, sync=ship_sync,
                wal_seq_fn=(
                    (lambda: durability.wal.appended)
                    if durability is not None else None
                ),
                obs=obs, recorder=flightrec.get_recorder(),
            )
            # Attach AFTER the durability on_write hook above, so the
            # WAL record exists (wal.appended names it) when the plane
            # captures the write.
            self._plane.attach()
            if self._plane.sync and self._dur is not None:
                # Acks additionally gate on at least one standby having
                # acked the shipment covering the record (the zero-
                # acknowledged-write-loss mode of the chaos gate).
                self._dur.extra_sync_gate = self._plane.covered
        # The pump timer, the pipeline and the wait every handler parks
        # on (pump_cycle.py).  Every served pump orchestrates migration;
        # the warm-up during construction does not.
        self.cycle = PumpCycle(
            sched, self.skv, ticks_per_pump, interval=pump_interval,
            durability=self._dur, metrics=self.m,
            on_end=self._after_pump,
            after_step=functools.partial(skv.after_step, orchestrate=True),
            warm=functools.partial(skv.pump, orchestrate=False),
        )

    # -- durability hooks (apply-time, loop thread) -----------------------

    def _on_insert_applied(self, gid, shard, num, data, latest):
        self._insert_seqs[(gid, shard, num)] = self._dur.log(
            ("insert", gid, shard, num, dict(data), dict(latest))
        )

    def _on_delete_applied(self, gid, shard, num):
        # Replayed on restore so a stale BEPULLING slot can't survive an
        # older checkpoint and wedge config advance.
        self._delete_seqs[(gid, shard, num)] = self._dur.log(
            ("delete", gid, shard, num)
        )

    def _on_confirm_applied(self, gid, shard, num):
        # Replayed on restore so recovery re-applies GCING→SERVING
        # locally instead of re-running the GC handshake — during
        # replay the loop thread is busy replaying, so an RPC to a
        # remote old owner could never resolve and recovery would
        # wedge (the confirm only ever committed because the delete
        # leg already succeeded pre-crash).
        self._dur.log(("confirm", gid, shard, num))

    # -- fleet migration hooks (run on the loop thread, inside pump) ------

    def _remote_fetch(self, src_gid: int, shard: int, num: int):
        from ..engine.shardkv import OK as SK_OK

        key = (src_gid, shard, num)
        fut = self._fetches.get(key)
        if fut is None:
            end = self.peers.get(src_gid)
            if end is None:
                return None  # unroutable: keep retrying (config may fix)
            self._fetches[key] = self.sched.with_timeout(
                end.call("EngineShardKV.pull_shard", (src_gid, shard, num)),
                self.MIGRATE_RPC_S,
            )
            return None
        if not fut.done:
            return None
        del self._fetches[key]  # resolved: consume or retry next sweep
        reply = fut.value
        if (
            reply is None or reply is TIMEOUT
            or not isinstance(reply, tuple) or reply[0] != SK_OK
        ):
            return None  # dropped / not ready: the sweep re-issues
        return reply[1], reply[2]

    def _remote_delete(self, src_gid: int, shard: int, num: int):
        from ..engine.shardkv import OK as SK_OK

        # Durability gate: never tell the old owner to delete a shard
        # whose inserted copy isn't fsynced locally yet — between its
        # delete and our next checkpoint/WAL-sync, a crash would lose
        # the only copy.  One pump's group fsync clears this.
        if self._dur is not None:
            for (g, s, n), seq in self._insert_seqs.items():
                if s == shard and n == num and not self._dur.synced(seq):
                    return None
        key = (src_gid, shard, num)
        fut = self._deletes.get(key)
        if fut is None:
            end = self.peers.get(src_gid)
            if end is None:
                return True  # owner unknown everywhere: nothing to delete
            self._deletes[key] = self.sched.with_timeout(
                end.call("EngineShardKV.delete_shard", (src_gid, shard, num)),
                self.MIGRATE_RPC_S,
            )
            return None
        if not fut.done:
            return None
        del self._deletes[key]
        reply = fut.value
        if reply is None or reply is TIMEOUT or not isinstance(reply, tuple):
            return None  # dropped: re-issue next sweep
        return reply[0] == SK_OK  # False = ErrNotReady, re-asked later

    # -- fleet migration RPC handlers (the serving side of the hooks) -----

    def pull_shard(self, args):
        """Return ``(OK, data, latest)`` for a shard this process's old
        owner holds, once it has applied the puller's config number —
        the cross-process form of the in-process applied-state read
        (engine/shardkv.py _orchestrate step (b))."""
        from ..engine.shardkv import ERR_NOT_READY, ERR_WRONG_GROUP
        from ..engine.shardkv import OK as SK_OK

        src_gid, shard, num = args
        self.m.inc("migrate.pulls_served")
        if src_gid not in self.skv.reps:
            return (ERR_WRONG_GROUP,)

        def run():
            deadline = self.sched.now + self.DEADLINE_S
            while self.sched.now < deadline:
                rep = self.skv.reps[src_gid]
                if rep.cur.num >= num:
                    sh = rep.shards[shard]
                    return (SK_OK, dict(sh.data), dict(sh.latest))
                # Not a pump-end wait: the puller is a config ahead
                # until the admin's RPC reaches THIS process too (the
                # ErrNotReady gate), so look again on a timer.
                yield 0.01
            return (ERR_NOT_READY,)

        return run()

    def delete_shard(self, args):
        """Challenge-1 deletion on behalf of a remote puller: ride the
        local old owner's log (BatchedShardKV.delete_shard) and report
        the outcome."""
        from ..engine.shardkv import ERR_WRONG_GROUP
        from ..engine.shardkv import OK as SK_OK

        src_gid, shard, num = args
        self.m.inc("migrate.deletes_served")
        if src_gid not in self.skv.reps:
            return (ERR_WRONG_GROUP,)

        def run():
            t = self.skv.delete_shard(src_gid, shard, num)
            deadline = self.sched.now + self.DEADLINE_S
            while not t.done:
                if not (yield from self.cycle.wait(deadline)):
                    return (ERR_TIMEOUT,)
            if t.failed:
                return (ERR_TIMEOUT,)
            if t.err != SK_OK:
                return (t.err,)
            # Gate the OK on the delete's WAL record being fsynced: the
            # puller confirms on our OK and never re-asks, so losing the
            # record to a crash would strand a BEPULLING slot here
            # forever.  (Absent = pruned = already durable, or the slot
            # was already clear and no record was written — also
            # durable.)  Deadline-bounded: a stalled fsync must surface
            # as a timeout the puller retries, not a pinned generator.
            while self._dur is not None:
                seq = self._delete_seqs.get((src_gid, shard, num))
                if seq is None or self._dur.synced(seq):
                    break
                if not (yield from self.cycle.wait(deadline)):
                    return (ERR_TIMEOUT,)
            return (SK_OK,)

        return run()

    # -- group placement RPCs (distributed/placement.py drives these) -----
    #
    # Whole-group migration between fleet processes: the controller
    # calls pull_group at the source (seal + export), adopt_group at
    # the destination (spare engine slot), drop_group back at the
    # source, then pushes the new placement map fleet-wide with
    # `place`.  All handlers are idempotent so the controller can
    # retry any leg after a timeout.

    ERR_NO_SLOT = "ErrNoSlot"

    def pull_group(self, args):
        """Seal ``gid`` and return ``(OK, blob)`` — its frozen applied
        state (BatchedShardKV.export_group).  Retries return the same
        blob: the seal stops every mutation."""
        from ..engine.shardkv import ERR_NOT_READY, ERR_WRONG_GROUP
        from ..engine.shardkv import OK as SK_OK

        gid = args[0] if isinstance(args, (tuple, list)) else args
        self.m.inc("place.pulls_served")

        def run():
            deadline = self.sched.now + self.DEADLINE_S
            while self.sched.now < deadline:
                if gid not in self.skv.reps:
                    return (ERR_WRONG_GROUP,)
                blob = self.skv.export_group(gid)
                if blob is not None:
                    return (SK_OK, blob)
                # Not a pump-end wait: the group settles when its peers
                # (other processes) finish the migration in flight.
                yield 0.01
            return (ERR_NOT_READY,)

        return run()

    def unseal_group(self, args):
        """Abort leg: only safe while the blob was never dispatched to
        any destination (see BatchedShardKV.unseal_group).  ``force``
        (second arg) overrides the post-dispatch refusal — the
        controller sends it only with the destination provably dead."""
        from ..engine.shardkv import OK as SK_OK

        if isinstance(args, (tuple, list)):
            gid = args[0]
            force = bool(args[1]) if len(args) > 1 else False
        else:
            gid, force = args, False
        try:
            self.skv.unseal_group(gid, force)
        except RuntimeError:
            return ("ErrDispatched",)
        return (SK_OK,)

    def adopt_group(self, args):
        """Host ``gid`` in a spare engine slot.  ``blob=None`` adopts
        empty (dead-source failover: the fresh replica re-pulls from
        whatever live owners remain).  Idempotent: a retried adopt of
        an already-hosted gid answers OK."""
        from ..engine.shardkv import OK as SK_OK

        gid, blob = args[0], args[1]
        if gid in self.skv.reps:
            return (SK_OK,)
        if self.skv.free_slots() <= 0:
            return (self.ERR_NO_SLOT,)
        self.skv.adopt_gid(gid, blob)
        self.peers.pop(gid, None)  # it's local now
        self.m.inc("place.adoptions")
        return (SK_OK,)

    def drop_group(self, args):
        """Free ``gid``'s slot after the destination adopted it.  Waits
        for the slot to quiesce (tail applies resolve as WRONG_GROUP
        no-ops) so slot reuse is safe.  Idempotent: already-dropped
        answers OK."""
        from ..engine.shardkv import OK as SK_OK

        gid = args[0] if isinstance(args, (tuple, list)) else args

        def run():
            deadline = self.sched.now + self.DEADLINE_S
            while self.sched.now < deadline:
                if gid not in self.skv.reps:
                    return (SK_OK,)
                if self.skv.group_quiesced(gid):
                    self.skv.drop_gid(gid)
                    self._rebuild_peers()  # route it to its new owner
                    self.m.inc("place.drops")
                    return (SK_OK,)
                # the slot's tail applies resolve in a pump's sweep
                yield from self.cycle.wait(deadline)
            return (ERR_TIMEOUT,)

        return run()

    # -- state-plane RPCs (distributed/stateplane.py) ---------------------

    def ship(self, args):
        """Ingest one framed shipment into the local StandbyStore;
        returns the store's ack ``{"ok", "have", "gid"}`` (the shipper
        treats ``have`` as the authoritative resend frontier)."""
        payload = args[0] if isinstance(args, (tuple, list)) else args
        if self._standby is None:
            return {"ok": False, "have": -1}
        return self._standby.receive(payload)

    def standby_state(self, args):
        """Freshness of the local standby state for ``gid`` (None when
        holding nothing) — the controller's recovery-destination probe."""
        gid = args[0] if isinstance(args, (tuple, list)) else args
        if self._standby is None:
            return None
        return self._standby.freshness(gid)

    def recover_group(self, args):
        """Stateful failover: adopt ``gid`` from the LOCAL standby store
        (snapshot fast-forward + exactly-once tail replay through the
        group's own log), answering ``(OK, "recovered")``.  With no
        shipped state here, ``(OK, "empty")`` tells the controller to
        fall back to explicit empty adoption."""
        from ..engine.shardkv import OK as SK_OK

        gid = args[0] if isinstance(args, (tuple, list)) else args

        def run():
            from .stateplane import iter_replay_tail, recovery_blob

            held = (
                self._standby.get(gid)
                if self._standby is not None else None
            )
            if held is None:
                return (SK_OK, "empty")
            snap, tail = held
            if gid not in self.skv.reps:
                blob = recovery_blob(snap, self.skv.query_latest())
                if blob is None and not tail:
                    return (SK_OK, "empty")
                if self.skv.free_slots() <= 0:
                    return (self.ERR_NO_SLOT,)
                self.skv.adopt_gid(gid, blob)
                self.peers.pop(gid, None)  # it's local now
                self.m.inc("place.adoptions")
            if tail:
                yield from iter_replay_tail(self.skv, gid, tail)
            self._standby.drop(gid)
            self.m.inc("ship.recoveries")
            return (SK_OK, "recovered")

        return run()

    def _ship_send(self, proc: int, payload: bytes):
        """StatePlane delivery hook: ONE in-flight ship RPC per standby,
        resolved by polling — the pump loop must never block on the
        network.  Returns the PREVIOUS completed reply (None while one
        is still flying); correctness rides on the reply's ``have``
        frontier being authoritative and gid-tagged, not on pairing a
        reply with the payload it answered."""
        prev = self._ship_futs.get(proc)
        reply = None
        if prev is not None:
            if not prev.done:
                return None
            del self._ship_futs[proc]
            v = prev.value
            if isinstance(v, dict):
                reply = v
        addr = self._fleet_addrs.get(proc)
        if addr is None or self._make_end is None:
            return reply
        end = self._ship_ends.get(proc)
        if end is None:
            end = self._ship_ends[proc] = self._make_end(
                addr[0], int(addr[1])
            )
        self._ship_futs[proc] = self.sched.with_timeout(
            end.call("EngineShardKV.ship", (payload,)),
            self.MIGRATE_RPC_S,
        )
        return reply

    def place(self, args):
        """Placement push from the controller: ``(version, {gid:
        (host, port)})``.  Only a strictly newer version applies —
        reordered or replayed pushes are no-ops."""
        from ..engine.shardkv import OK as SK_OK

        version, pmap = args
        cur_ver, _ = self._placement
        if version > cur_ver:
            self._placement = (
                int(version),
                {int(g): (a[0], int(a[1])) for g, a in pmap.items()},
            )
            self._rebuild_peers()
            self.m.inc("place.pushes")
        return (SK_OK, self._placement[0])

    def placement(self, args=None):
        """Current placement view ``(version, {gid: (host, port)})`` —
        the fleet clerk's re-route source after ErrWrongGroup."""
        ver, pmap = self._placement
        return (ver, {g: tuple(a) for g, a in pmap.items()})

    # -- membership-change RPCs (self-healing replica sets) ---------------
    #
    # The placement controller's replace-dead-replica policy drives
    # these: add_learner seats a fresh non-voting incarnation in a
    # spare engine slot, learner_match gauges its catch-up, begin_joint
    # appends the C_old,new entry at the leader (the engine auto-exits
    # to C_new once it commits under BOTH quorums).  All handlers are
    # idempotent — BatchedShardKV's *_gid wrappers answer True when
    # the engine is already at or past the requested state — so the
    # controller can replay any leg after a crash or lost reply.

    def replica_config(self, args):
        """``(OK, cfg)`` — the leader's config view for ``gid``
        (voter sets, joint flag, epoch), ``cfg=None`` when leaderless
        or the gid is not hosted here."""
        from ..engine.shardkv import OK as SK_OK

        gid = args[0] if isinstance(args, (tuple, list)) else args
        return (SK_OK, self.skv.config_of_gid(gid))

    def add_learner(self, args):
        """Seat engine slot ``peer`` as a non-voting learner of
        ``gid``; ``(OK, bool)``."""
        from ..engine.shardkv import OK as SK_OK

        gid, peer = args[0], args[1]
        ok = self.skv.add_learner_gid(gid, int(peer))
        if ok:
            self.m.inc("reconfig.learners_seated")
        return (SK_OK, bool(ok))

    def learner_match(self, args):
        """``(OK, (leader's match for peer, leader's last index))`` —
        the catch-up gauge; ``(OK, None)`` when leaderless."""
        from ..engine.shardkv import OK as SK_OK

        gid, peer = args[0], args[1]
        return (SK_OK, self.skv.learner_match_gid(gid, int(peer)))

    def begin_joint(self, args):
        """Append the C_old,new entry making ``voters`` the target
        config of ``gid``; ``(OK, bool)``."""
        from ..engine.shardkv import OK as SK_OK

        gid, voters = args[0], args[1]
        ok = self.skv.begin_joint_gid(gid, [int(q) for q in voters])
        if ok:
            self.m.inc("reconfig.joints_entered")
        return (SK_OK, bool(ok))

    def kill_replica(self, args):
        """Chaos verb: permanently mark engine replica ``(gid, peer)``
        dead (nemesis / acceptance harnesses only); ``(OK, bool)``."""
        from ..engine.shardkv import OK as SK_OK

        gid, peer = args[0], args[1]
        ok = self.skv.kill_replica_gid(gid, int(peer))
        if ok:
            self.m.inc("reconfig.replicas_killed")
        return (SK_OK, bool(ok))

    def _rebuild_peers(self) -> None:
        """Re-derive the gid→end peer map from the placement view,
        skipping locally hosted gids.  Ends are cached per address."""
        if self._make_end is None:
            return
        _, pmap = self._placement
        peers = {}
        for g, addr in pmap.items():
            if g in self.skv.reps:
                continue
            addr = (addr[0], int(addr[1]))
            end = self._ends_by_addr.get(addr)
            if end is None:
                end = self._make_end(addr[0], addr[1])
                self._ends_by_addr[addr] = end
            peers[g] = end
        self.peers = peers

    def info(self, _args=None) -> dict:
        """Topology, as ``EngineKV.info``, and the shard space: a clerk
        routes by ``shards`` and ``partitioner`` as THIS server states
        them (``ShardSpace(shards, partitioner)``), never by its own
        process's constants."""
        state = self.skv.driver.state.term.addressable_shards
        return {
            "G": self.skv.driver.cfg.G,
            "P": self.skv.driver.cfg.P,
            "state_devices": len({s.device for s in state}),
            "shards": self.skv.space.count,
            "partitioner": self.skv.space.partitioner,
        }

    def config(self, args):
        """Latest committed config as ``(num, shards, groups)`` — the
        fleet clerk's routing source (shardctrler Query analog)."""
        cfg = self.skv.configs[-1]
        return (
            cfg.num,
            list(cfg.shards),
            {g: list(v) for g, v in cfg.groups.items()},
        )

    # Shared wire-level frame cap (clerks split on the same constant).
    MAX_FIREHOSE = MAX_FIREHOSE_ROWS

    def firehose(self, blob):
        """Columnar frame for the sharded service (engine/firehose.py):
        the group column carries GLOBAL gids; ownership re-checks at
        apply produce per-row WRONG_GROUP outcomes the client re-routes
        after a config refresh.  Gets answer from the applied frontier
        (get_fast's ownership-gated ReadIndex) at frame completion —
        but a get whose shard had a NON-OK write row in this frame
        mirrors that row's outcome instead, preserving
        read-after-own-frame-writes under migration."""
        import numpy as np

        from ..engine.firehose import (
            FH_NO_KEY,
            FH_OK,
            FH_RETRY,
            FH_WRONG_GROUP,
            pack_reply,
        )
        from ..engine.shardkv import ERR_NO_KEY, ERR_WRONG_GROUP, OK

        key2shard = self.skv.space.shard_of

        def run():
            raw = bytes(blob)
            if len(raw) < 4:
                return ("err", "ErrMalformedFrame")
            n = int(np.frombuffer(raw, np.dtype("<u4"), 1, 0)[0])
            if n > self.MAX_FIREHOSE:
                return ("err", f"ErrFrameTooLarge:{self.MAX_FIREHOSE}")
            try:
                f = self.skv.submit_frame(raw)
            except ValueError as e:
                return ("err", str(e))
            deadline = self.sched.now + self.DEADLINE_S
            while not f.done and (yield from self.cycle.wait(deadline)):
                pass
            err = f.err.copy()
            # Durable mode: the shared firehose ack gate.
            if self._dur is not None:
                yield from demote_unsynced_rows(
                    self.cycle.wait, self._dur, self._write_seqs, f, err,
                    deadline,
                )
            # Shards whose write rows did not land OK: gets there mirror
            # the write outcome so the client re-frames them together.
            bad_shard_err: dict = {}
            for r in f.write_rows.tolist():
                if err[r] != FH_OK:
                    bad_shard_err[key2shard(f.keys[r])] = int(err[r])
            values = [b""] * len(f)
            for r in np.nonzero(f.ops == 0)[0].tolist():
                shard = key2shard(f.keys[r])
                if shard in bad_shard_err:
                    err[r] = bad_shard_err[shard]
                    continue
                t = self.skv.get_fast(f.keys[r])
                if t.err == ERR_WRONG_GROUP:
                    err[r] = FH_WRONG_GROUP
                    self.m.inc("shard.wrong_group")
                elif t.err == ERR_NO_KEY:
                    err[r] = FH_NO_KEY
                else:
                    err[r] = FH_OK
                    values[r] = t.value.encode()
            return pack_reply(err, values)

        return run()

    def stop(self) -> None:
        self.cycle.stop()

    def final_checkpoint(self) -> bool:
        return self.cycle.final_checkpoint()

    def _after_pump(self) -> None:
        """The cycle's end-of-pump hook: forget the WAL seqs the group
        fsync just covered, then ship to the standbys."""
        if self._dur is not None:
            for attr in ("_insert_seqs", "_write_seqs", "_admin_seqs",
                         "_delete_seqs"):
                seqs = getattr(self, attr)
                if seqs:
                    setattr(self, attr, {
                        k: v for k, v in seqs.items()
                        if not self._dur.synced(v)
                    })
        if self._plane is not None:
            self._plane.ship_round()

    def replay_wal(self) -> int:
        """Recovery replay — delegated to
        :class:`~.engine_durability.ShardWalReplay` (two-pass redo with
        migration paused; see its docstring for the full contract)."""
        n = ShardWalReplay(self.skv, self._dur).run()
        self.m.inc("wal.replays")
        self.m.inc("wal.replayed_records", n)
        return n

    # Largest multi-op frame one RPC may carry (see EngineKVService).
    MAX_BATCH = 1024

    def batch(self, args_list):
        """Multi-op frame for the SHARDED service.  Chains key on
        (client, shard) — a shard's dedup table travels with it and
        same-key ops share a shard — and run STRICTLY one op in flight
        each, the reference clerk's serial discipline
        (shardkv/client.go:68-129): pipelining within a chain is
        unsafe here because an away-and-back shard migration can let a
        later op apply while an earlier one bounced ErrWrongGroup, and
        the earlier op's retry then dedup-swallows into a false OK.
        The frame's parallelism comes from chains to DIFFERENT shards
        pipelining freely.  In fleet mode, ops whose shard a peer
        process owns answer ErrWrongGroup per-op so the fleet clerk
        re-frames them to the owner."""
        from ..engine.shardkv import ERR_WRONG_GROUP

        key2shard = self.skv.space.shard_of

        if len(args_list) > self.MAX_BATCH:
            return [
                EngineCmdReply(err=f"ErrBatchTooLarge:{self.MAX_BATCH}")
            ] * len(args_list)

        def run():
            deadline = self.sched.now + self.DEADLINE_S
            replies = [None] * len(args_list)
            chains: dict = {}
            for i, a in enumerate(args_list):
                if a.op == "Get":
                    continue
                chains.setdefault(
                    (a.client_id, key2shard(a.key)), []
                ).append(i)

            def submit(a):
                gid = self.skv.owner_of(a.key)
                if gid not in self.skv.reps:
                    return None  # peer-owned (or unassigned) shard
                if self._fleet and self.skv.is_sealed(gid):
                    return None  # mid-placement-migration: re-route
                return self.skv.submit(
                    gid, a.op, a.key, a.value,
                    client_id=a.client_id, command_id=a.command_id,
                )

            tickets: dict = {}   # frame idx -> resolved-OK ticket
            wrong: set = set()   # frame idx -> answer ErrWrongGroup
            heads: dict = {}     # chain -> (frame idx, live ticket)
            cursor = {qk: 0 for qk in chains}
            pending = set(chains)
            while pending and self.sched.now < deadline:
                progressed = False
                for qk in list(pending):
                    members = chains[qk]
                    if qk not in heads:
                        i = members[cursor[qk]]
                        t = submit(args_list[i])
                        if t is None:
                            if self._fleet:
                                # Peer-owned: the whole remaining chain
                                # belongs to that peer — punt it.
                                for j in members[cursor[qk]:]:
                                    wrong.add(j)
                                pending.discard(qk)
                                progressed = True
                            continue  # non-fleet: config moving; wait
                        heads[qk] = (i, t)
                        continue
                    i, t = heads[qk]
                    if not t.done:
                        continue
                    del heads[qk]
                    if t.failed or t.err == ERR_WRONG_GROUP:
                        continue  # resubmit next round (dedup-safe)
                    tickets[i] = t
                    cursor[qk] += 1
                    progressed = True
                    if cursor[qk] >= len(members):
                        pending.discard(qk)
                if pending and not progressed:
                    # tickets resolve, and the config moves, at a pump
                    # end only
                    yield from self.cycle.wait(deadline)
            # Durable frame ack (shared gate — see _await_frame_synced).
            ok = {
                i for i, t in tickets.items()
                if t.done and not t.failed and t.err == OK
            }
            yield from await_frame_synced(
                self.cycle.wait, self._dur, self._write_seqs, ok,
                args_list, deadline,
            )
            for i, a in enumerate(args_list):
                if a.op == "Get":
                    t = self.skv.get_fast(a.key)
                    if t.err == ERR_WRONG_GROUP:
                        replies[i] = EngineCmdReply(err=ERR_WRONG_GROUP)
                    else:
                        replies[i] = EngineCmdReply(
                            err=OK, value=t.value if t.err == OK else ""
                        )
                elif i in wrong:
                    replies[i] = EngineCmdReply(err=ERR_WRONG_GROUP)
                elif i in ok:
                    replies[i] = EngineCmdReply(
                        err=OK, value=tickets[i].value
                    )
                else:
                    replies[i] = EngineCmdReply(err=ERR_TIMEOUT)
            return replies

        return run()

    def command(self, args: EngineCmdArgs):
        from ..engine.shardkv import ERR_WRONG_GROUP

        if args.op == "Get":
            self.m.inc("kv.gets")

            # ReadIndex fast read (BatchedShardKV.get_fast): no log
            # entry, gated on serving-shard ownership exactly like the
            # logged path; ErrWrongGroup during migration pumps and
            # retries like any clerk op.
            def run_get():
                deadline = self.sched.now + self.DEADLINE_S
                while self.sched.now < deadline:
                    t = self.skv.get_fast(args.key)
                    if t.err == ERR_WRONG_GROUP:
                        self.m.inc("shard.wrong_group")
                        # Fleet: the owner is (probably) another
                        # process — answer so the clerk re-routes.
                        if self._fleet:
                            return EngineCmdReply(err=ERR_WRONG_GROUP)
                        # Not a pump-end wait: the shard serves here once
                        # an admin op (another process's RPC) and the
                        # migration it starts have moved the config.
                        yield 0.01
                        continue
                    value = t.value if t.err == OK else ""
                    return EngineCmdReply(err=OK, value=value)
                return EngineCmdReply(err=ERR_TIMEOUT)

            return run_get()

        # Request id + stage clock captured at handler entry (dispatch
        # breadcrumb — see EngineKVService.command).
        rid = self.obs.current_trace()
        stages = self.obs.current_stages()
        self.m.inc("kv.writes")

        def run():
            t_start = self.sched.now
            deadline = t_start + self.DEADLINE_S
            t_parked = 0.0
            while self.sched.now < deadline:
                gid = self.skv.owner_of(args.key)
                if gid not in self.skv.reps:
                    if self._fleet:
                        # Hosted by a peer process: tell the clerk.
                        self.m.inc("shard.wrong_group")
                        return EngineCmdReply(err=ERR_WRONG_GROUP)
                    # Shard unassigned: as above, waits for an admin
                    # op's RPC, not for a pump end.
                    yield 0.01
                    continue
                if self._fleet and self.skv.is_sealed(gid):
                    # Mid-placement-migration: every apply would be a
                    # WRONG_GROUP no-op — tell the clerk NOW so it
                    # refreshes placement and retries at the adopter.
                    return EngineCmdReply(err=ERR_WRONG_GROUP)
                t = self.skv.submit(
                    gid, args.op, args.key, args.value,
                    client_id=args.client_id, command_id=args.command_id,
                )
                if stages is not None:
                    if not stages.engine:
                        # First submit closes the handler leg (routing
                        # + config queries); re-routes stay in the
                        # engine leg.
                        stages.engine = True
                        stages.fold(self.m, "handler")
                    # Parked from here until a pump carries the
                    # proposal (re-stamped per re-route).
                    t_parked = time.perf_counter()
                sub_deadline = min(
                    self.sched.now + self.RESUBMIT_S, deadline
                )
                while not t.done and (
                    yield from self.cycle.wait(sub_deadline, counted=True)
                ):
                    pass
                if not t.done or t.failed or t.err == ERR_WRONG_GROUP:
                    continue  # resubmit / re-route; dedup-safe
                if stages is not None:
                    # Commit observed; the fsync gate below lands in
                    # the ack leg (folded at dispatch completion).
                    stages.fold(self.m, "engine")
                    # Tail attribution: carrying tick + parked time.
                    stages.tick = self.cycle.seq
                    stages.pump_wait_s = max(
                        0.0, self.cycle.t_dispatch - t_parked
                    )
                # Ack gates on the apply-time WAL record being fsynced
                # (absent = pruned/duplicate = already durable), checked
                # at every pump end, where the group fsync lands; at the
                # deadline the write answers ErrTimeout, never a false
                # durable ack.
                while self._dur is not None:
                    seq = self._write_seqs.get(
                        (args.client_id, args.command_id)
                    )
                    if seq is None or self._dur.synced(seq):
                        break
                    if not (
                        yield from self.cycle.wait(deadline, counted=True)
                    ):
                        return EngineCmdReply(err=ERR_TIMEOUT)
                self.m.observe("kv.command_s", self.sched.now - t_start)
                if rid is not None:
                    self.obs.tracer.instant(
                        "commit",
                        time.perf_counter() * 1e6,
                        track="engine",
                        req=rid,
                        group=gid,
                    )
                return EngineCmdReply(err=OK, value=t.value)
            return EngineCmdReply(err=ERR_TIMEOUT)

        return run()

    ADMIN_OPS = ("join", "leave", "move")

    def admin(self, args):
        """Config administration: args = (kind, payload[, command_id])
        with kind in ADMIN_OPS — a network-supplied string must never
        getattr into arbitrary methods.  The optional command_id makes
        retries exactly-once through the ctrler dedup table; a FLEET
        admin MUST pass one (a duplicate apply would fork the config
        histories' numbering across processes and wedge migration)."""
        kind, payload = args[0], args[1]
        cmd = args[2] if len(args) > 2 else None
        if kind not in self.ADMIN_OPS:
            return EngineCmdReply(err=f"ErrBadAdminOp:{kind}")

        def run():
            # join/leave take their payload whole (a gid list / mapping);
            # move takes (shard, gid) as two positionals.
            if kind == "move":
                t = self.skv.move(*payload, command_id=cmd)
            else:
                t = getattr(self.skv, kind)(payload, command_id=cmd)
            deadline = self.sched.now + self.DEADLINE_S
            while not t.done:
                if not (yield from self.cycle.wait(deadline)):
                    return EngineCmdReply(err=ERR_TIMEOUT)
            if t.failed:
                return EngineCmdReply(err=ERR_TIMEOUT)
            # Ack gates on the apply-time ("admin", ...) WAL record
            # (logged by the on_ctrl hook in commit order) being
            # fsynced; ErrTimeout at the deadline, never a false
            # durable ack.
            while self._dur is not None:
                seq = self._admin_seqs.get(t.command_id)
                if seq is None or self._dur.synced(seq):
                    break
                if not (yield from self.cycle.wait(deadline)):
                    return EngineCmdReply(err=ERR_TIMEOUT)
            return EngineCmdReply(err=OK)

        return run()


def serve_engine_shardkv(
    port: int,
    G: int = 4,
    host: str = "127.0.0.1",
    seed: int = 0,
    join_gids: Optional[Sequence[int]] = None,
    gids: Optional[Sequence[int]] = None,
    peer_addrs: Optional[dict] = None,  # gid -> (host, port) of the owner
    data_dir: Optional[str] = None,
    checkpoint_every_s: float = 30.0,
    mesh_devices: int = 0,
    spare_slots: int = 0,
    replicas: int = 3,
    shards: Optional[int] = None,  # the shard space (ShardSpace.of)
    voters: Optional[Sequence[int]] = None,
    fleet_addrs: Optional[dict] = None,  # proc -> (host, port), all procs
    me: Optional[int] = None,  # this process's index in fleet_addrs
    ship_rules=None,
    ship_sync: Optional[bool] = None,
    ship_window_s: Optional[float] = None,
) -> RpcNode:
    """The sharded engine behind TCP: BatchedShardKV (replicated config
    + per-shard migration pipeline) on one chip-owning process.

    Fleet mode: pass ``gids`` (the global gids THIS process hosts; the
    local engine is sized ``len(gids)+1``) and ``peer_addrs`` (owner
    address for every remotely hosted gid) — shard migration then rides
    ``pull_shard``/``delete_shard`` RPCs between processes.

    With ``data_dir`` the process is DURABLE (checkpoint + WAL of
    client writes, admin ops, and migration inserts/deletes); a
    restarted process recovers every acknowledged op, and in a fleet
    the GC handshake is gated so a migrated-in blob is never the only
    un-fsynced copy.

    ``shards`` states the shard space (``ShardSpace.of``: the
    reference's ten by first byte, any other count by crc32 of the
    whole key); ``EngineShardKV.info`` returns it, the checkpoint
    records it, and a ``data_dir`` written at another is refused
    (ValueError naming both).  ``join_gids`` join in ONE ``join``
    operation (config 1), as the reference's ``Join`` takes a map of
    many groups."""
    from ..engine.shardkv import BatchedShardKV

    space = ShardSpace.of(shards)
    node = RpcNode(listen=True, host=host, port=port)
    sched = node.sched
    metrics = node.obs.metrics
    count_compiles(metrics)  # engine.compiles / engine.compile_s
    count_gc(metrics, sched._thread)  # gc.pause_s, loop.gc_s
    trace_loop(sched)  # mrt.loop.* on the loop's line
    # Time to ``ready`` by stage, as serve_engine_kv's gauges; ``join``
    # is the bootstrap join from its proposal to every group at rest.
    ready = ReadyStages(
        "restore", "elect", "warm", "join", "replay", "checkpoint"
    )
    local_gids = list(gids) if gids is not None else None
    # spare_slots: extra idle engine groups the placement controller
    # can adopt migrated gids into (distributed/placement.py).
    G_local = (
        (len(local_gids) + 1 + max(0, spare_slots))
        if local_gids is not None else G
    )
    peers = {
        g: node.client_end(h, p)
        for g, (h, p) in (peer_addrs or {}).items()
        if local_gids is None or g not in local_gids
    }
    # Version-0 placement view: the static spec (peer addrs + own gids).
    placement0 = None
    if local_gids is not None:
        placement0 = {
            int(g): (h, int(p)) for g, (h, p) in (peer_addrs or {}).items()
        }
        for g in local_gids:
            placement0[int(g)] = (host, int(port))

    n_replicas = max(3, int(replicas))

    def build():
        ready.start()
        mesh = make_mesh(mesh_devices) if mesh_devices else None
        driver = None
        if data_dir:
            ckpt = os.path.join(data_dir, "engine.ckpt")
            if os.path.exists(ckpt):
                driver = EngineDriver.restore(
                    ckpt, mesh=mesh, replicas=n_replicas
                )
        restored = driver is not None
        if restored:
            metrics.inc("engine.restores")
        if not restored:
            cfg = EngineConfig(
                G=G_local, P=n_replicas, L=64, E=8, INGEST=8
            )
            driver = EngineDriver(cfg, seed=seed, mesh=mesh)
            if voters is not None and len(set(voters)) < cfg.P:
                # Spare ENGINE REPLICA slots: only ``voters`` vote; the
                # remaining rows park dead until the placement
                # controller's replace-dead-replica policy seats a
                # learner in one (self-healing replica sets).  A
                # RESTORED process skips this — its config (voter
                # masks included) comes from the checkpoint.
                driver.seed_config(voters)
            # Warm-up before readiness (see serve_engine_kv):
            # elections + both tick compiles happen here, not under
            # client traffic.
            ok = driver.run_until_quiet_leaders(2000)
            assert ok, "engine groups failed to elect"
        # The scrapeable registry from here on: the bootstrap join's
        # counters and the shard gauges belong in every scrape (tick
        # SPANS stay gated on the diagnostic tracer below).
        driver.metrics = metrics
        skv = BatchedShardKV(driver, gids=local_gids, space=space)
        if restored:
            blob = driver.restored_extra.get("service")
            if blob:
                skv.load_state_dict(blob)
        ready.lap("restore" if restored else "elect")
        # Warm the LOADED tick variant before the readiness line (the
        # jit compile takes tens of seconds on CPU and would otherwise
        # land under the first admin/client RPC and time it out).  A
        # None payload is the "binding lost" no-op: it exercises the
        # ingest path without touching config history — essential in
        # fleet mode, where every process's history must stay aligned.
        skv.driver.start(0, None)
        skv.pump(8)
        ready.lap("warm")
        if not restored and join_gids:
            # A restored process's config history lives in its
            # checkpoint + WAL — re-running the bootstrap join would
            # allocate a fresh ctrler id the dedup table can't absorb
            # and append a spurious config per restart.
            skv.admin_sync("join", list(join_gids))
            # To quiescence: every group has applied config 1 and the
            # sweep has nothing left to visit.
            for _ in range(400):
                if skv.at_rest():
                    break
                skv.pump(5)
            assert skv.at_rest(), "bootstrap join did not settle"
            ready.lap("join")
        skv._set_gauges()
        dur = (
            EngineDurability(data_dir, driver, skv,
                             checkpoint_every_s=checkpoint_every_s,
                             metrics=metrics)
            if data_dir else None
        )
        if node.tracer is not None:
            driver.tracer = node.tracer  # ticks + RPCs on one timeline
        svc = EngineShardKVService(sched, skv, peers=peers, durability=dur,
                                   obs=node.obs,
                                   fleet=local_gids is not None,
                                   make_end=node.client_end,
                                   placement0=placement0,
                                   fleet_addrs=fleet_addrs, me=me,
                                   ship_rules=ship_rules,
                                   ship_sync=ship_sync,
                                   ship_window_s=ship_window_s)
        if dur is not None:
            svc.replay_wal()  # recovery completes before readiness
            ready.lap("replay")
            dur.checkpoint()  # fold replay into a fresh checkpoint
            ready.lap("checkpoint")
        return svc

    try:
        svc = sched.run_call(build, timeout=600.0)
    except BaseException:
        node.close()  # a refused start leaves no listener behind
        raise
    ready.publish(metrics)
    metrics.set("engine.mesh_devices", float(mesh_devices))
    metrics.set("engine.replicas", float(svc.skv.driver.cfg.P))
    node.add_service("EngineShardKV", svc)
    node.engine_service = svc
    # Overload watch: stage-p99/queue-gauge bounds → OVERLOAD records.
    # Admission: the watch's brownout state drives shedding at dispatch.
    from .admission import install_admission
    from .overload import install_overload_watch
    from .wedge import install_wedge_watch

    install_admission(node)
    install_overload_watch(node)
    # Wedge watchdog: commit-frontier stall with proposals pending →
    # WEDGE records + gauge.wedged_groups (gray-failure liveness).
    install_wedge_watch(node)
    return node
