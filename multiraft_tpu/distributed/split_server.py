"""Serving layer for cross-process replica groups (engine/split.py).

Each process runs ``serve_split_kv``: one chip-owning engine whose
split groups share their P peer slots with peer processes, per-tick
boundary mailbox slabs riding ``SplitEngine.slab`` RPCs between them
(SURVEY §2.2's "node↔node over DCN/gRPC").  Unlike
``serve_engine_kv``'s whole-group engine, losing one of these
processes loses only its owned peer slots — a group whose surviving
peers still hold a quorum keeps electing and committing, and every
acknowledged write survives from replication alone (no WAL replay).

Client surface mirrors the reference kvraft deployment: a clerk
carries (client_id, command_id) sessions and rotates processes on
ErrWrongLeader/timeout (reference: kvraft/client.go:47-71); the server
gates submission on an owned slot actually leading the group and rides
EVERY op — Gets included — through the log (reference semantics,
SURVEY §3.4; the single-process ReadIndex collapse does not reason
across processes).
"""

from __future__ import annotations

import itertools
import os
import time
from typing import Dict, Optional, Sequence, Tuple

from ..engine.core import EngineConfig
from ..engine.host import EngineDriver
from ..engine.kv import KVOp
from ..engine.split import SplitKV, SplitPeering, SplitSpec
from ..porcupine.kv import OP_APPEND, OP_GET, OP_PUT
from ..sim.scheduler import TIMEOUT, Future
from ..utils.ids import unique_client_id
from .engine_server import (
    ERR_TIMEOUT,
    OK,
    EngineCmdArgs,
    EngineCmdReply,
    route_group,
)
from .realtime import PumpCadence, service_busy
from .realtime import RealtimeScheduler
from .tcp import RpcNode

__all__ = [
    "ERR_WRONG_LEADER",
    "SplitPersistence",
    "SplitKVService",
    "SplitNetClerk",
    "serve_split_kv",
]

ERR_WRONG_LEADER = "ErrWrongLeader"

_OPCODE = {"Get": OP_GET, "Put": OP_PUT, "Append": OP_APPEND}

# Raft columns a split process must persist for its owned slots (the
# reference's Persister contract — term/vote/log survive a crash,
# raft/persister.go — at engine-slice granularity).
_RAFT_COLS = ("term", "voted_for", "base", "base_term", "log_len",
              "log_term")


class SplitPersistence:
    """Per-process durability for split-group peers: safe crash +
    REJOIN under the same peer identity.

    Raft's persistence rules, mapped to the slab-exchange runtime: a
    peer must never emit a message reflecting state it could forget —
    a forgotten term/vote double-votes, a forgotten acked log entry
    un-commits acknowledged writes.  Slabs leave once per pump, so the
    whole contract collapses to ONE invariant: **fsync the owned
    slots' raft slice before this pump's slabs are extracted/sent**
    (``SplitKVService._pump_loop`` orders pump → ``after_pump()`` →
    extract/send).  A crash between append and fsync tears the tail
    record — and no slab for that pump was sent, so the restored
    (previous-pump) state is exactly what the world saw.

    On disk: an atomic SNAPSHOT (service state + live payload
    candidates + raft slice; superseding) plus a WAL of per-pump
    records — ``raft`` (full owned slice; the LAST one wins),
    ``pay`` (new payload candidates), ``app`` (applied (g, idx, term)
    — the service-state redo log).  Recovery = snapshot + last raft
    record + pay union + app replay; volatile columns (role, commit,
    applied, votes, timers) restart fresh, commit/applied rewound to
    base (the restart_replica discipline — commit knowledge is
    volatile in Raft)."""

    def __init__(self, data_dir: str, kv, peering,
                 snapshot_every_s: float = 30.0, fsync: bool = True) -> None:
        import pickle

        from .wal import WriteAheadLog

        os.makedirs(data_dir, exist_ok=True)
        self._pickle = pickle
        self.snap_path = os.path.join(data_dir, "split.snap")
        self.wal = WriteAheadLog(os.path.join(data_dir, "split.wal"),
                                 fsync=fsync)
        self.kv = kv
        self.peering = peering
        self.every = snapshot_every_s
        self._last_snap = time.monotonic()
        self._new_pays: list = []
        self._new_apps: list = []
        self._last_slice = None   # idle dedup: last persisted raft slice
        self._need_snapshot = False
        # App records carry (g, idx, term, wire|None): term >= 0 →
        # replay resolves the candidate; term -1 (fallback apply) →
        # the op rides IN the record so replay reproduces exactly what
        # the live path applied, never a silent skip.
        kv.on_applied = lambda g, idx, term, payload: (
            self._new_apps.append((
                g, idx, term,
                kv.export_payload(payload)
                if term < 0 and payload is not None else None,
            ))
        )
        peering.on_candidate = lambda g, idx, term, payload: (
            self._new_pays.append(
                (g, idx, term, kv.export_payload(payload))
            )
        )
        # An InstallSnapshot blob replaced service state whose device
        # base jumped with it: the next after_pump MUST checkpoint
        # before fsyncing that raft slice, or a crash in the window
        # restores base past a service state that never saw the blob.
        kv.on_snapshot_installed = (
            lambda g: setattr(self, "_need_snapshot", True)
        )

    # -- write path --------------------------------------------------------

    def _raft_slice(self) -> dict:
        import jax
        import numpy as np

        st = self.kv.driver.state
        gi = self.peering._g_index
        out = jax.device_get(
            {f: getattr(st, f)[gi] for f in _RAFT_COLS}
        )
        return {f: np.asarray(v) for f, v in out.items()}

    def after_pump(self) -> None:
        """Persist this pump's effects and fsync — called BEFORE the
        pump's slabs are extracted/sent (the one invariant)."""
        import numpy as np

        if self._need_snapshot:
            # Installed-snapshot service state must hit disk before the
            # raft slice whose base jumped with it.
            self._need_snapshot = False
            self.snapshot()
        slice_ = self._raft_slice()
        if (
            not self._new_pays
            and not self._new_apps
            and self._last_slice is not None
            and all(
                np.array_equal(slice_[f], self._last_slice[f])
                for f in _RAFT_COLS
            )
        ):
            return  # idle pump: nothing new to make durable, no fsync
        rec = ("pump", slice_, self._new_pays, self._new_apps)
        self._new_pays = []
        self._new_apps = []
        self._last_slice = slice_
        self.wal.append(self._pickle.dumps(rec, protocol=4))
        self.wal.sync()
        if self.every > 0 and (
            time.monotonic() - self._last_snap >= self.every
        ):
            self.snapshot()

    def snapshot(self) -> None:
        import numpy as np

        gs = self.peering.split_gs
        blob = {
            "svc": {
                # (applied_upto, service blob) via the service adapter
                # (SplitKV / SplitShardKV persist_group).
                g: self.kv.persist_group(g)
                for g in gs
            },
            "cands": [
                (g, idx, term, self.kv.export_payload(p))
                for (g, idx), by_term in self.peering._cands.items()
                for term, p in by_term.items()
            ],
            "raft": self._raft_slice(),
        }
        tmp = self.snap_path + ".tmp"
        with open(tmp, "wb") as f:
            self._pickle.dump(blob, f, protocol=4)
            f.flush()
            # Intentional loop-thread sync point: the snapshot MUST be
            # durable before wal.rotate() discards its records (same
            # contract as the WAL's allowlisted group-commit fsync).
            os.fsync(f.fileno())  # graftlint: disable=blocking-in-callback
        os.replace(tmp, self.snap_path)
        dfd = os.open(os.path.dirname(self.snap_path) or ".", os.O_RDONLY)
        try:
            os.fsync(dfd)  # graftlint: disable=blocking-in-callback
        finally:
            os.close(dfd)
        # A crash between replace and rotate leaves redundant WAL
        # records — raft records supersede and app replay dedups on
        # applied_upto, so replay is merely redundant, never wrong.
        self.wal.rotate()
        self._last_snap = time.monotonic()
        for g in gs:
            self.peering.gc_floor[g] = int(self.kv.applied_upto[g])

    # -- recovery ----------------------------------------------------------

    def load_and_install(self) -> bool:
        """Restore the previous incarnation's persisted state into the
        (freshly built) driver/service/peering.  Returns False when no
        prior state exists (first boot).  Must run BEFORE the first
        tick — pre-restore state must never act."""
        import numpy as np

        blob = None
        if os.path.exists(self.snap_path):
            with open(self.snap_path, "rb") as f:
                blob = self._pickle.load(f)
        raft = blob["raft"] if blob else None
        pays = list(blob["cands"]) if blob else []
        apps: list = []
        for body in self.wal.replay():
            kind, slice_, rec_pays, rec_apps = self._pickle.loads(body)
            raft = slice_  # last record wins
            pays.extend(rec_pays)
            apps.extend(rec_apps)
        if raft is None:
            return False

        kv, peering = self.kv, self.peering
        drv = kv.driver
        # 1. Device columns for OWNED slots (+ commit/applied rewound
        #    to base; volatile leadership state stays fresh).
        host = {
            f: np.asarray(getattr(drv.state, f)).copy()
            for f in _RAFT_COLS + ("commit", "applied")
        }
        for gi, g in enumerate(peering.split_gs):
            for p in peering._owned[g]:
                for f in _RAFT_COLS:
                    host[f][g, p] = raft[f][gi, p]
                host["commit"][g, p] = raft["base"][gi, p]
                host["applied"][g, p] = raft["base"][gi, p]
        import jax.numpy as jnp

        # copy=True: ``host`` columns mix device-copied rows with rows
        # assigned from the unpickled WAL snapshot; on the CPU backend a
        # zero-copy asarray would alias that host memory into state the
        # donated tick writes through (the PR 1 restore segfault).
        drv.state = drv.state._replace(
            **{f: jnp.array(v, copy=True) for f, v in host.items()}
        )
        # 2. Service state from the snapshot (service adapter).
        if blob:
            for g, (upto, sblob) in blob["svc"].items():
                kv.restore_group(g, upto, sblob)
        # 3. Payload candidates (snapshot + WAL increments).
        for g, idx, term, wire in pays:
            payload = kv.import_payload(wire)
            peering._cands.setdefault((g, idx), {})[term] = payload
            if (g, idx) not in drv.payloads:
                drv.payloads[(g, idx)] = payload
        # 4. Service-state redo: applied entries since the snapshot,
        #    in commit order, exact by (idx, term) — fallback applies
        #    (term -1) carry their op in the record itself.  The
        #    service adapter's replay_apply routes through the same
        #    apply path as live serving, so recovery can never drift
        #    from serving semantics.
        for g, idx, term, wire in apps:
            if idx <= kv.applied_upto[g]:
                continue  # already inside the snapshot
            payload = None
            if term >= 0:
                payload = peering._cands.get((g, idx), {}).get(term)
            elif wire is not None:
                payload = kv.import_payload(wire)
            if payload is not None:
                kv.replay_apply(g, idx, payload)
            kv.applied_upto[g] = idx
        for g in peering.split_gs:
            peering.gc_floor[g] = int(kv.applied_upto[g])
        return True


class SplitKVService:
    """``SplitKV.command`` + ``SplitEngine.slab`` on one process.

    The pump loop advances the device one tick at a time and ships the
    boundary slabs immediately — per-tick granularity matters here
    (multi-tick pumps would drop the intermediate ticks' boundary
    messages, doubling effective RTT across the process boundary)."""

    RESUBMIT_S = 0.25
    DEADLINE_S = 3.0

    def __init__(
        self,
        sched: RealtimeScheduler,
        kv: SplitKV,
        peering: SplitPeering,
        peer_ends: Dict[int, object],  # proc index -> TcpClientEnd
        pump_interval: float = 0.002,
        persistence: Optional[SplitPersistence] = None,
    ) -> None:
        self.sched = sched
        self.kv = kv
        self.peering = peering
        self.peer_ends = dict(peer_ends)
        self.G = kv.driver.cfg.G
        self._cadence = PumpCadence(pump_interval)
        self._stopped = False
        self._persist = persistence
        sched.call_soon(self._pump_loop)

    def stop(self) -> None:
        self._stopped = True

    def _pump_loop(self) -> None:
        if self._stopped:
            return
        self.kv.pump(1)
        if self._persist is not None:
            # THE persistence invariant: the pump's raft slice is
            # fsynced before any of its slabs leave the process.
            self._persist.after_pump()
        for proc, slab in self.peering.extract().items():
            end = self.peer_ends.get(proc)
            if end is not None:
                # Fire-and-forget: a lost slab is a dropped message and
                # Raft retries; the timeout just reclaims the future.
                self.sched.with_timeout(
                    end.call("SplitEngine.slab", slab), 1.0
                )
        self.sched.call_after(
            self._cadence.next_delay(service_busy(self.kv)),
            self._pump_loop,
        )

    # -- peer-facing -------------------------------------------------------

    def slab(self, blob: dict):
        """Boundary mailbox lanes (+payloads/snapshots) from a peer
        process — merged before the next tick (same loop thread)."""
        self.peering.inject(blob)
        return True

    # -- client-facing -----------------------------------------------------

    MAX_BATCH = 1024

    def batch(self, args_list):
        """Multi-op frame on the split server (same chain discipline
        as EngineKVService.batch — split groups carry plain-KV
        semantics, so per-(client, group) chains pipeline whole, with
        suffix-only resubmission after full-chain resolution).  A
        group without a local leader answers ErrWrongLeader per-op;
        the clerk re-frames those at the peer process."""
        if len(args_list) > self.MAX_BATCH:
            return [
                EngineCmdReply(err=f"ErrBatchTooLarge:{self.MAX_BATCH}")
            ] * len(args_list)

        def run():
            deadline = self.sched.now + self.DEADLINE_S
            replies = [None] * len(args_list)
            chains: dict = {}
            for i, a in enumerate(args_list):
                chains.setdefault(
                    (a.client_id, route_group(a.key, self.G)), []
                ).append(i)

            def submit(a):
                return self.kv.submit_local(
                    route_group(a.key, self.G),
                    KVOp(op=_OPCODE[a.op], key=a.key, value=a.value,
                         client_id=a.client_id, command_id=a.command_id),
                )

            tickets: dict = {}
            wrong: set = set()
            pending = set(chains)
            while pending and self.sched.now < deadline:
                progressed = False
                for qk in list(pending):
                    members = chains[qk]
                    sub = [i for i in members if i in tickets]
                    if any(not tickets[i].done for i in sub):
                        continue  # wait for the whole chain
                    k_bad = next(
                        (k for k, i in enumerate(members)
                         if i not in tickets or tickets[i].failed),
                        None,
                    )
                    if k_bad is None:
                        pending.discard(qk)
                        progressed = True
                        continue
                    if self.kv.local_leader(qk[1]) is None:
                        # The leader lives at a peer process: punt the
                        # unresolved members to the clerk.
                        for i in members[k_bad:]:
                            if i not in tickets or tickets[i].failed:
                                wrong.add(i)
                                tickets.pop(i, None)
                        pending.discard(qk)
                        progressed = True
                        continue
                    ok = True
                    for i in members[k_bad:]:
                        t = submit(args_list[i])
                        if t is None:
                            ok = False
                            break  # leadership just moved; re-check
                        tickets[i] = t
                    progressed = progressed or ok
                if pending and not progressed:
                    yield 0.002
            for i, a in enumerate(args_list):
                t = tickets.get(i)
                if i in wrong:
                    # Confirmed: the group's leader lives elsewhere.
                    replies[i] = EngineCmdReply(err=ERR_WRONG_LEADER)
                elif t is None:
                    # Never submitted before the deadline (leadership
                    # flapped locally the whole time) — a timeout, not
                    # a routing verdict (ADVICE r03).
                    replies[i] = EngineCmdReply(err=ERR_TIMEOUT)
                elif t.done and not t.failed:
                    replies[i] = EngineCmdReply(err=OK, value=t.value)
                else:
                    replies[i] = EngineCmdReply(err=ERR_TIMEOUT)
            return replies

        return run()

    def command(self, args: EngineCmdArgs):
        g = route_group(args.key, self.G)

        def run():
            deadline = self.sched.now + self.DEADLINE_S
            while self.sched.now < deadline:
                t = self.kv.submit_local(
                    g,
                    KVOp(
                        op=_OPCODE[args.op],
                        key=args.key,
                        value=args.value,
                        client_id=args.client_id,
                        command_id=args.command_id,
                    ),
                )
                if t is None:
                    # No owned slot leads this group: the leader lives
                    # in (or is being elected by) a peer process.
                    return EngineCmdReply(err=ERR_WRONG_LEADER)
                sub_deadline = min(
                    self.sched.now + self.RESUBMIT_S, deadline
                )
                while not t.done and self.sched.now < sub_deadline:
                    yield 0.002
                if t.done and not t.failed:
                    return EngineCmdReply(err=OK, value=t.value)
                # failed (lost slot / lost leadership) or wedged:
                # re-check leadership and resubmit — dedup-safe.
            return EngineCmdReply(err=ERR_TIMEOUT)

        return run()


class SplitNetClerk:
    """Generator-coroutine clerk over a set of split-KV processes:
    session dedup + rotate-on-ErrWrongLeader/timeout with a per-group
    leader cache (reference clerk loop, kvraft/client.go:47-71)."""

    _next = itertools.count(1)

    def __init__(self, sched, ends: Sequence) -> None:
        self.sched = sched
        self.ends = list(ends)
        self.client_id = unique_client_id(next(SplitNetClerk._next))
        self.command_id = 0
        self._leader: Dict[str, int] = {}  # key -> ends index

    def _command(self, op: str, key: str, value: str = ""):
        if op != "Get":
            self.command_id += 1
        args = EngineCmdArgs(
            op=op, key=key, value=value,
            client_id=self.client_id, command_id=self.command_id,
        )
        # Group routing is server-side and the clerk does not know the
        # server's G, so the leader cache keys per-KEY (ADVICE r03: a
        # bucket over the ends count aliases distinct groups and they
        # evict each other's entries) — exact, and bounded by the
        # client's own working set.
        gkey = key
        i = self._leader.get(gkey, 0)
        while True:
            end = self.ends[i % len(self.ends)]
            fut: Future = end.call("SplitKV.command", args)
            reply = yield self.sched.with_timeout(fut, 3.5)
            if (
                reply is None
                or reply is TIMEOUT
                or reply.err != OK
            ):
                i += 1  # rotate: dropped / wrong leader / timed out
                yield self.sched.sleep(0.02)
                continue
            self._leader[gkey] = i % len(self.ends)
            return reply.value

    def get(self, key: str):
        return self._command("Get", key)

    def put(self, key: str, value: str):
        return self._command("Put", key, value)

    def append(self, key: str, value: str):
        return self._command("Append", key, value)

    # Sequential-window cap: an oversized batch must not split a
    # (client, group) chain across frames whose resolutions can
    # interleave (a timed-out chain-tail op retried after a later
    # frame applied the chain's next op dedup-swallows into a false
    # OK).  Windows run strictly one after another.
    MAX_FRAME = 1024

    def run_batch(self, ops):
        """Multi-op frames against the split cluster: each ≤MAX_FRAME
        window ships whole to one process; ops answered ErrWrongLeader
        (their group's leader lives elsewhere) re-frame to the next
        process; a window fully resolves before the next ships.
        Generator (spawn on the scheduler)."""
        out = []
        for s in range(0, len(ops), self.MAX_FRAME):
            part = yield from self._one_window(ops[s:s + self.MAX_FRAME])
            out.extend(part)
        return out

    def _one_window(self, ops):
        frame = []
        for op, key, value in ops:
            if op != "Get":
                self.command_id += 1
            frame.append(
                EngineCmdArgs(
                    op=op, key=key, value=value,
                    client_id=self.client_id,
                    command_id=self.command_id,
                )
            )
        results = [None] * len(ops)
        todo = list(range(len(ops)))
        i_end = 0
        while todo:
            end = self.ends[i_end % len(self.ends)]
            fut: Future = end.call(
                "SplitKV.batch", [frame[i] for i in todo]
            )
            reply = yield self.sched.with_timeout(fut, 10.0)
            retry = []
            if reply is None or reply is TIMEOUT:
                retry = list(todo)
            else:
                if any(
                    r.err.startswith("ErrBatchTooLarge") for r in reply
                ):
                    raise ValueError(reply[0].err)
                for i, r in zip(todo, reply):
                    if r.err == OK:
                        results[i] = r.value
                    else:
                        retry.append(i)
            if retry:
                i_end += 1  # rotate: those groups lead elsewhere
                yield self.sched.sleep(0.02)
            todo = sorted(retry)
        return results


def serve_split_kv(
    port: int,
    me: int,
    owners: Dict[int, Sequence[int]],
    peer_addrs: Dict[int, Tuple[str, int]],
    G: int = 8,
    host: str = "127.0.0.1",
    seed: int = 0,
    delay_elections: int = 0,
    data_dir: Optional[str] = None,
    snapshot_every_s: float = 30.0,
) -> RpcNode:
    """Bring up one split-KV process: engine over ``G`` groups, peer
    slots placed per ``owners`` (see :class:`SplitSpec` — every process
    passes the SAME map), slab exchange with ``peer_addrs``.

    ``delay_elections`` biases this process's owned slots' first
    election deadlines later — deployments use it to steer initial
    leadership (tests park leaders on a chosen process; a real rollout
    can spread them).  Readiness prints before leaders exist: elections
    converge once the peers are up, and clerks retry ErrWrongLeader
    until then.

    With ``data_dir`` the process is DURABLE under its peer identity
    (:class:`SplitPersistence`): a kill -9'd process may be restarted
    on the same dir and REJOINS the cluster safely — its persisted
    term/vote/log make double-votes and acked-entry loss impossible
    (the reference's Persister-carryover crash model,
    raft/config.go:113-142).  Without it, a killed process must stay
    dead (fresh state under an old identity can double-vote)."""
    node = RpcNode(listen=True, host=host, port=port)
    sched = node.sched

    def build():
        cfg = EngineConfig(G=G, P=3, L=64, E=8, INGEST=8,
                           host_paced_compaction=True)
        driver = EngineDriver(cfg, seed=seed)
        kv = SplitKV(driver)
        peering = SplitPeering(
            driver, kv, SplitSpec(me=me, owners={
                int(g): list(o) for g, o in owners.items()
            })
        )
        persist = None
        if data_dir is not None:
            persist = SplitPersistence(
                data_dir, kv, peering, snapshot_every_s=snapshot_every_s
            )
            # BEFORE any tick: pre-restore state must never act.
            persist.load_and_install()
        if delay_elections:
            driver.state = driver.state._replace(
                elect_dl=driver.state.elect_dl + int(delay_elections)
            )
        # Warm both tick variants before the readiness line (first jit
        # compile would otherwise starve RPC dispatch under the first
        # client — see serve_engine_kv).
        driver.start(0, (KVOp(op=OP_GET, key=""), None))
        kv.pump(4)
        ends = {
            int(p): node.client_end(h, int(pt))
            for p, (h, pt) in peer_addrs.items()
            if int(p) != me
        }
        return SplitKVService(sched, kv, peering, ends,
                              persistence=persist)

    svc = sched.run_call(build, timeout=600.0)
    node.add_service("SplitKV", svc)
    node.add_service("SplitEngine", svc)
    node.engine_service = svc
    return node
