"""The batched engine served over the real network — one process owns
the chip; clerk RPCs come in over TCP and are coalesced into engine
ticks (the first step of SURVEY §2.2's sidecar story: "clients talk to
a thin RPC front; commands coalesce into the device firehose").

Architecture (vs the per-replica sim/process stack in ``cluster.py``):

* ``EngineKVService`` wraps a :class:`BatchedKV` on an
  :class:`EngineDriver`.  A pump timer on the process's
  ``RealtimeScheduler`` (:class:`~.pump_cycle.PumpCycle`, which both
  services compose) advances the device tick loop every couple of
  milliseconds; every RPC that arrived since the last pump has already
  queued its command into the per-group backlog, so one device step
  carries *all* concurrent client traffic — the batching that makes a
  single chip serve thousands of groups.
* Writes ride the log with kvraft session dedup (``KVOp.client_id`` /
  ``command_id``) so the at-least-once transport (client retries on
  timeout) stays exactly-once.  Reads use the ReadIndex fast path
  (zero device work, linearizable at the applied frontier).
* ``EngineShardKVService`` is the sharded form: a
  :class:`BatchedShardKV` behind the same front door, with server-side
  key→shard routing against its replicated config and the clerk retry
  semantics of the reference (ErrWrongGroup → re-route).

Wire protocol: ``EngineKV.command`` / ``EngineShardKV.command`` over
:class:`~multiraft_tpu.distributed.tcp.RpcNode` frames.
"""

from __future__ import annotations

import os
import time
from typing import Optional, Sequence

from ..engine.core import EngineConfig
from ..engine.firehose import MAX_FIREHOSE_ROWS
from ..engine.host import EngineDriver
from ..engine.instrument import ReadyStages, count_compiles, count_gc, trace_loop
from ..engine.kv import BatchedKV, KVOp
from ..porcupine.kv import OP_GET
from .engine_durability import (
    EngineDurability,
    await_frame_synced,
    demote_unsynced_rows,
    replay_kv_wal,
)
from . import flightrec
from .engine_wire import (
    _OPCODE,
    _OPNAME,
    ERR_TIMEOUT,
    OK,
    EngineCmdArgs,
    EngineCmdReply,
    make_mesh,
    route_group,
)
from .admission import install_admission
from .observe import Observability
from .overload import install_overload_watch
from .pump_cycle import SERVED_TICKS, PumpCycle
from .wedge import install_wedge_watch
from .realtime import RealtimeScheduler
from .tcp import RpcNode

__all__ = [
    "EngineCmdArgs",
    "EngineCmdReply",
    "EngineKVService",
    "EngineShardKVService",
    "EngineClerk",
    "FirehoseClerk",
    "ShardFirehoseClerk",
    "PipelinedClerk",
    "PipelinedFleetClerk",
    "EngineShardNetClerk",
    "EngineFleetClerk",
    "serve_engine_kv",
    "serve_engine_shardkv",
]


class EngineKVService:
    """``EngineKV.command`` RPC front for a :class:`BatchedKV`.

    All device work happens on the scheduler loop: the pump timer and
    the RPC handlers interleave there, so commands queued by handlers
    between pumps coalesce into the next device step."""

    # Handler-side patience before giving up on one submission and
    # resubmitting (dedup makes the duplicate harmless) — covers
    # tickets lost to leader changes.
    RESUBMIT_S = 0.25
    # Total per-RPC budget; the client retries after its own timeout.
    DEADLINE_S = 3.0

    def __init__(
        self,
        sched: RealtimeScheduler,
        kv: BatchedKV,
        pump_interval: float = 0.002,
        ticks_per_pump: int = SERVED_TICKS,
        durability: Optional[EngineDurability] = None,
        obs=None,
    ) -> None:
        self.sched = sched
        self.kv = kv
        self.G = kv.driver.cfg.G
        self._dur = durability
        # The owning node's observability plane (tick/pump latency,
        # frame sizes, commit instants tagged with the caller's request
        # id); a private one when the service is built without a node.
        self.obs = obs if obs is not None else Observability()
        self.m = self.obs.metrics
        # (client_id, command_id) -> WAL seq of the op's apply-time
        # record; handlers gate their ack on it being fsynced.  Pruned
        # once synced (absence = already durable).
        self._write_seqs: dict = {}
        self._frec = flightrec.get_recorder()  # COMMIT records
        if durability is not None:
            # WAL at APPLY time (commit order): evict-and-resubmit can
            # commit ops in a different order than submission, and
            # replay must reproduce the order reads actually saw.
            kv.on_write = lambda g, op: self._write_seqs.__setitem__(
                (op.client_id, op.command_id),
                durability.log(("kv", _OPNAME[op.op], op.key, op.value,
                                op.client_id, op.command_id)),
            )
        # The pump timer, the pipeline and the wait every handler parks
        # on (pump_cycle.py).
        self.cycle = PumpCycle(
            sched, self.kv, ticks_per_pump, interval=pump_interval,
            durability=self._dur, metrics=self.m,
            on_end=self._prune_synced,
        )

    def stop(self) -> None:
        self.cycle.stop()

    def final_checkpoint(self) -> bool:
        return self.cycle.final_checkpoint()

    def _prune_synced(self) -> None:
        """The cycle's end-of-pump hook: forget the WAL seqs the group
        fsync just covered."""
        if self._dur is not None and self._write_seqs:
            self._write_seqs = {
                k: v for k, v in self._write_seqs.items()
                if not self._dur.synced(v)
            }

    def replay_wal(self) -> int:
        """Recovery replay — delegated to
        :func:`~.engine_durability.replay_kv_wal` (strictly one record
        in flight per group; see its docstring for the full
        contract)."""
        n = replay_kv_wal(self.kv, self._dur, self.G)
        self.m.inc("wal.replays")
        self.m.inc("wal.replayed_records", n)
        return n

    # Largest multi-op frame one RPC may carry (bounds the per-pump
    # submit burst a single frame can impose).
    MAX_BATCH = 1024

    def batch(self, args_list):
        """Multi-op frame: one codec envelope carries a clerk's whole
        pipelined batch, applied in one pump (BENCHMARKS' named fix for
        the per-op RPC overhead dominating the serving path).  Writes
        are all submitted up front — they coalesce into the next device
        step together; Gets answer from the applied frontier after the
        frame's writes resolve, so a pipelined read sees its own
        frame's preceding writes.  Per-client order within the frame is
        preserved on resubmit (failures retry as an order-preserving
        subset; sessions are per group, so cross-group interleaving
        cannot trip dedup)."""
        if len(args_list) > self.MAX_BATCH:
            return [
                EngineCmdReply(err=f"ErrBatchTooLarge:{self.MAX_BATCH}")
            ] * len(args_list)
        self.m.inc("batch.frames")
        self.m.observe("batch.ops", float(len(args_list)))

        def run():
            deadline = self.sched.now + self.DEADLINE_S
            replies = [None] * len(args_list)
            # Chains: a client's writes to ONE group must apply in
            # order (same-client dedup + same-key cross-op order).
            # FIFO backlog makes the whole chain safe to pipeline AT
            # ONCE: bindings land in submission order, and a leader-
            # change truncation can only fail a contiguous SUFFIX of
            # the chain.  The one hazard is resubmitting a failed
            # member while later members are still in flight (an
            # orphan sweep can fail out of order, and an inverted
            # rebinding lets the session table swallow the earlier
            # cmd) — so a chain with failures WAITS until every member
            # resolves, then resubmits from the first failure onward,
            # in order.  Chains to different groups pipeline freely.
            chains: dict = {}
            for i, a in enumerate(args_list):
                if a.op != "Get":
                    key = (a.client_id, route_group(a.key, self.G))
                    chains.setdefault(key, []).append((i, a))

            def submit(a):
                return self.kv.submit(
                    route_group(a.key, self.G),
                    KVOp(op=_OPCODE[a.op], key=a.key, value=a.value,
                         client_id=a.client_id, command_id=a.command_id),
                )

            tickets: dict = {}  # frame index -> latest ticket
            for members in chains.values():
                for i, a in members:
                    tickets[i] = submit(a)
            pending = set(chains)
            while pending and self.sched.now < deadline:
                progressed = False
                for qk in list(pending):
                    members = chains[qk]
                    if not all(tickets[i].done for i, _ in members):
                        continue
                    first_bad = next(
                        (k for k, (i, _) in enumerate(members)
                         if tickets[i].failed),
                        None,
                    )
                    if first_bad is None:
                        pending.discard(qk)
                        progressed = True
                        continue
                    # Resubmit the suffix in order (dedup makes any
                    # already-applied member a no-op resolve).
                    for i, a in members[first_bad:]:
                        tickets[i] = submit(a)
                if pending and not progressed:
                    # tickets resolve and fail at a pump end only
                    yield from self.cycle.wait(deadline)
            tickets = {
                i: t for i, t in tickets.items()
                if t.done and not t.failed
            }
            # Durable mode: one group fsync covers the whole frame
            # (shared gate — see _await_frame_synced).
            synced_ok = set(tickets)
            yield from await_frame_synced(
                self.cycle.wait, self._dur, self._write_seqs, synced_ok,
                args_list, deadline,
            )
            for i, a in enumerate(args_list):
                if a.op == "Get":
                    replies[i] = EngineCmdReply(
                        err=OK,
                        value=self.kv.get(
                            route_group(a.key, self.G), a.key
                        ).value,
                    )
                else:
                    ok = i in synced_ok
                    replies[i] = EngineCmdReply(
                        err=OK if ok else ERR_TIMEOUT,
                        value=tickets[i].value if ok else "",
                    )
            return replies

        return run()

    # Largest columnar frame one firehose RPC may carry (the shared
    # wire-level limit — clerks split on the same constant).
    MAX_FIREHOSE = MAX_FIREHOSE_ROWS

    def info(self, _args=None) -> dict:
        """Topology: ``G`` is what the columnar clerks route by, ``P``
        the replicas a group; ``state_devices`` counts the devices that
        hold a shard of the consensus state (1, or the mesh size when it
        really is spread)."""
        shards = self.kv.driver.state.term.addressable_shards
        return {
            "G": self.G,
            "P": self.kv.driver.cfg.P,
            "state_devices": len({s.device for s in shards}),
        }

    def firehose(self, blob):
        """Columnar frame (engine/firehose.py): ONE bytes blob in, one
        out — no per-op objects anywhere on the server path.  Rows that
        lose their log slot to a leader change come back as per-row
        RETRY errors; the CLIENT retries them under the same command
        ids (dedup keeps that exactly-once), which takes retry
        bookkeeping off this hot loop entirely."""
        import numpy as np

        from ..engine.firehose import FH_RETRY, pack_reply

        def run():
            # Buffer payloads pass straight through: the OOB codec
            # delivers blobs as bytes-likes and every consumer below
            # (np.frombuffer, memoryview slicing) speaks the buffer
            # protocol, so only exotic types pay a copy.
            raw = (
                blob if isinstance(blob, (bytes, bytearray, memoryview))
                else bytes(blob)
            )
            if len(raw) < 4:
                return ("err", "ErrMalformedFrame")
            n = int(np.frombuffer(raw, np.dtype("<u4"), 1, 0)[0])
            if n > self.MAX_FIREHOSE:
                return ("err", f"ErrFrameTooLarge:{self.MAX_FIREHOSE}")
            try:
                f = self.kv.submit_frame(raw)
            except ValueError as e:
                return ("err", str(e))
            self.m.inc("firehose.frames")
            self.m.inc("firehose.rows", n)
            t0 = self.sched.now
            deadline = t0 + self.DEADLINE_S
            while not f.done and (yield from self.cycle.wait(deadline)):
                pass
            # Firehose lag: submit → frame resolution (device-side wait).
            self.m.observe("firehose.lag_s", self.sched.now - t0)
            err = f.err.copy()
            # Durable mode FIRST: the shared firehose ack gate (never
            # a false durable ack; unsynced rows demote to RETRY).
            # Must run before the Get gate below — a write that
            # applied but missed its fsync deadline is RETRY, and a
            # Get answering past it would observe state a crash could
            # still un-happen (the sharded handler orders it the same
            # way).
            if self._dur is not None:
                yield from demote_unsynced_rows(
                    self.cycle.wait, self._dur, self._write_seqs, f, err,
                    deadline,
                )
            if not f.done or (err[f.write_rows] != 0).any():
                # Writes unresolved, failed, OR demoted: Gets must NOT
                # answer (they would read before the frame's own
                # durable writes) — fail them so the client's retry
                # frame carries the gets together with the retried
                # writes.
                err[f.ops == 0] = FH_RETRY
            # Gets answer at frame completion from the applied state
            # (read-after-own-frame-writes, like the batch path).
            values = [b""] * len(f)
            for r in np.nonzero(f.ops == 0)[0].tolist():
                if err[r] == 0:
                    t = self.kv.get(int(f.groups[r]), f.keys[r])
                    values[r] = t.value.encode()
            return pack_reply(err, values)

        return run()

    def command(self, args: EngineCmdArgs):
        g = route_group(args.key, self.G)
        if args.op == "Get":
            # ReadIndex fast read: linearizable at the applied
            # frontier, no log entry, immediate reply.
            self.m.inc("kv.gets")
            t = self.kv.get(g, args.key)
            return EngineCmdReply(err=OK, value=t.value)

        # The caller's request id + stage clock, captured NOW (handler
        # entry runs on the dispatch breadcrumb; the generator body
        # runs later, when _cur_trace belongs to someone else).
        rid = self.obs.current_trace()
        stages = self.obs.current_stages()
        self.m.inc("kv.writes")

        # Write path: generator handler — yields let the pump advance.
        def run():
            t_start = self.sched.now
            deadline = t_start + self.DEADLINE_S
            t_parked = 0.0
            while self.sched.now < deadline:
                cs0 = time.thread_time() if stages is not None else 0.0
                t = self.kv.submit(
                    g,
                    KVOp(
                        op=_OPCODE[args.op],
                        key=args.key,
                        value=args.value,
                        client_id=args.client_id,
                        command_id=args.command_id,
                    ),
                )
                if stages is not None:
                    # The submit's binding cost runs in a coroutine
                    # step the dispatcher's synchronous cpu.handler_s
                    # segment can't see — fold it here (segment
                    # accounting: this CPU lands nowhere else).
                    self.m.observe(
                        "cpu.handler_s", time.thread_time() - cs0
                    )
                    # Parked from here until a pump carries the
                    # proposal (re-stamped per resubmit — churn waits
                    # are engine latency, not pump-queue latency).
                    t_parked = time.perf_counter()
                if stages is not None and not stages.engine:
                    # First submit closes the handler leg; resubmits
                    # stay inside the engine leg (they ARE the engine's
                    # latency under leader churn).
                    stages.engine = True
                    stages.fold(self.m, "handler")
                sub_deadline = min(
                    self.sched.now + self.RESUBMIT_S, deadline
                )
                while not t.done and (
                    yield from self.cycle.wait(sub_deadline, counted=True)
                ):
                    pass
                if t.done and not t.failed:
                    if stages is not None:
                        # Commit observed: submit → raft quorum +
                        # apply.  The durability gate below lands in
                        # the ack leg (folded at dispatch completion).
                        stages.fold(self.m, "engine")
                        # Tail attribution: which fused tick carried
                        # the commit, and how long the proposal sat
                        # parked before that tick was dispatched (the
                        # rest of the engine leg is device work).
                        stages.tick = self.cycle.seq
                        stages.pump_wait_s = max(
                            0.0, self.cycle.t_dispatch - t_parked
                        )
                    # Ack only once the apply-time WAL record is
                    # fsynced (absent = pruned = already durable, or
                    # a duplicate applied before this incarnation).
                    # Checked at every pump end, where the group
                    # fsync lands; at the deadline the write answers
                    # ErrTimeout, never a false durable ack.
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
                    self.m.observe(
                        "kv.command_s", self.sched.now - t_start
                    )
                    if self._frec is not None:
                        # Last-committed evidence for the postmortem:
                        # survives a SIGKILL that the tracer's commit
                        # instant (below) would die with.
                        self._frec.record(
                            flightrec.COMMIT, code=g,
                            a=args.client_id, b=args.command_id,
                            tag=rid or "",
                        )
                    if rid is not None:
                        # The engine-side leg of the request's journey:
                        # commit instant under the same id the clerk
                        # and RPC spans carry.
                        self.obs.tracer.instant(
                            "commit",
                            time.perf_counter() * 1e6,
                            track="engine",
                            req=rid,
                            group=g,
                        )
                    return EngineCmdReply(err=OK, value=t.value)
                # failed (evicted/orphaned) or wedged: resubmit under
                # the same (client_id, command_id) — dedup-safe.
                self.m.inc("kv.resubmits")
            return EngineCmdReply(err=ERR_TIMEOUT)

        return run()


def serve_engine_kv(
    port: int,
    G: int = 64,
    host: str = "127.0.0.1",
    seed: int = 0,
    record_groups: Optional[Sequence[int]] = None,
    data_dir: Optional[str] = None,
    checkpoint_every_s: float = 30.0,
    mesh_devices: int = 0,
    replicas: int = 3,
) -> RpcNode:
    """Bring up the chip-owning engine KV server process: one
    EngineDriver (G groups of ``replicas`` replicas each), a BatchedKV,
    the pump loop, and a listening RpcNode.  Returns the node (caller
    keeps the process alive).

    With ``data_dir``, the server is DURABLE: periodic atomic
    checkpoints + a write-ahead log of acked ops (see EngineDurability)
    — a kill -9'd process restarted on the same dir recovers every
    acknowledged write.

    With ``mesh_devices`` > 0, the engine's groups are sharded over
    that many local chips (G must divide evenly) and the same fused,
    asynchronous pump runs one ``shard_map`` program over them — the
    multi-chip production path; checkpoints restore back onto the
    same-size mesh.  Gauge ``engine.mesh_devices`` says how many.

    A group commits with a majority of its ``replicas`` (gauge
    ``engine.replicas``).  A ``data_dir`` whose checkpoint was written
    at another replica count is refused (ValueError naming both)."""
    node = RpcNode(listen=True, host=host, port=port)
    sched = node.sched
    metrics = node.obs.metrics
    # Trace, lower and compile events by name in every scrape
    # (engine.compiles / engine.compile_s): one inside a serving window
    # is a stall someone has to explain.
    count_compiles(metrics)
    # The collector's pauses (gc.pause_s, loop.gc_s) and the loop's turns
    # on a profiler's line (mrt.loop.*).
    count_gc(metrics, sched._thread)
    trace_loop(sched)
    # Time to ``ready`` by stage, as gauges ``ready.<stage>_s`` set once
    # (0.0: the stage did not run).  The first use of a program pays its
    # compile or cache load where it falls: the 5-tick program in
    # ``elect``, both single-tick variants and the served fused program
    # in ``warm``.
    ready = ReadyStages("restore", "elect", "warm", "replay", "checkpoint")

    def build():
        ready.start()
        mesh = make_mesh(mesh_devices) if mesh_devices else None
        driver = None
        if data_dir:
            ckpt = os.path.join(data_dir, "engine.ckpt")
            if os.path.exists(ckpt):
                driver = EngineDriver.restore(
                    ckpt, mesh=mesh, replicas=replicas
                )
        if driver is not None:
            metrics.inc("engine.restores")
            kv = BatchedKV(driver, record_groups=list(record_groups or []))
            blob = driver.restored_extra.get("service")
            if blob:
                kv.load_state_dict(blob)
            ready.lap("restore")
        else:
            # Shape knobs for throughput deployments (the firehose
            # bench serves G=256 at INGEST=24; defaults match the
            # round-2 serving shape).
            cfg = EngineConfig(
                G=G, P=replicas,
                L=int(os.environ.get("MULTIRAFT_SERVE_L", "64")),
                E=int(os.environ.get("MULTIRAFT_SERVE_E", "8")),
                INGEST=int(os.environ.get("MULTIRAFT_SERVE_INGEST", "8")),
            )
            driver = EngineDriver(cfg, seed=seed, mesh=mesh)
            kv = BatchedKV(driver, record_groups=list(record_groups or []))
            driver.run_until_quiet_leaders(2000)
            ready.lap("elect")
        # Warm-up BEFORE the readiness line: elect leaders and compile
        # both tick variants (quiet + loaded).  The first jit compile
        # takes tens of seconds and runs on the scheduler loop — doing
        # it lazily would starve RPC dispatch and time out every early
        # client (observed: all first ops stall ~10s on CPU).  A
        # restored process recompiles too (fresh interpreter).
        driver.start(0, (KVOp(op=OP_GET, key=""), None))
        for _ in range(8):
            kv.pump(1)
        # This service routes by key hash; reject firehose frames
        # whose group column disagrees with it, server-side.
        kv.route_check = route_group
        dur = (
            EngineDurability(data_dir, driver, kv,
                             checkpoint_every_s=checkpoint_every_s,
                             metrics=metrics)
            if data_dir else None
        )
        # Fold the driver's tick counter into the scrapeable registry
        # (tick SPANS stay gated on the diagnostic tracer below — they
        # force a device sync per tick).
        driver.metrics = metrics
        if node.tracer is not None:
            driver.tracer = node.tracer  # ticks + RPCs on one timeline
        svc = EngineKVService(
            sched, kv, durability=dur, obs=node.obs,
            ticks_per_pump=int(os.environ.get(
                "MULTIRAFT_SERVE_TICKS_PER_PUMP", str(SERVED_TICKS)
            )),
        )
        ready.lap("warm")
        if dur is not None:
            svc.replay_wal()  # recovery completes before readiness
            ready.lap("replay")
            # Fold the replayed state into a fresh checkpoint and
            # rotate: bounds the next recovery, and discards the
            # duplicate records the replay's own apply hooks appended.
            dur.checkpoint()
            ready.lap("checkpoint")
        return svc

    try:
        svc = sched.run_call(build, timeout=600.0)
    except BaseException:
        node.close()  # a refused start leaves no listener behind
        raise
    ready.publish(metrics)
    metrics.set("engine.mesh_devices", float(mesh_devices))
    metrics.set("engine.replicas", float(svc.kv.driver.cfg.P))
    node.add_service("EngineKV", svc)
    node.engine_service = svc  # keep reachable for introspection
    # Overload watch (overload.py): windowed stage-p99 + queue-gauge
    # bounds → OVERLOAD flight records, while the collapse is live.
    # Admission (admission.py): the watch's brownout state drives it,
    # turning those signals into shed/bounded behavior at dispatch.
    install_admission(node)
    install_overload_watch(node)
    # Wedge watchdog (wedge.py): per-group commit-frontier stall with
    # proposals pending -> WEDGE flight records + gauge.wedged_groups,
    # the gray-failure signal the up/down detectors above cannot see.
    install_wedge_watch(node)
    return node

# Backwards-compatible re-exports: engine_server was the single module
# for the whole serving stack before the round-4 decomposition, and
# in-repo callers/tests import these names from here.
from .engine_clerks import (  # noqa: E402,F401
    EngineClerk,
    EngineFleetClerk,
    FirehoseClerk,
    ShardFirehoseClerk,
    EngineShardNetClerk,
    PipelinedClerk,
    PipelinedFleetClerk,
)
from .engine_shard_server import (  # noqa: E402,F401
    EngineShardKVService,
    serve_engine_shardkv,
)
