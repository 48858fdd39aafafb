"""The serving loop's pump cycle, owned once and composed by both
engine services (``EngineKVService``, ``EngineShardKVService``).

A cycle is: flush queued replies -> dispatch a fused tick batch (the
engine-pump thread, engine_pump.py, blocks on the readback) -> complete
it on the loop (``complete_ticks``, the engine's ``after_step``) ->
group fsync and periodic checkpoint -> the owning service's one hook ->
wake the handlers parked on this cycle's end -> re-arm the single pump
timer.  A driver that is not ``fused_eligible()`` (kill switch, reorder
chaos in flight) takes the same cycle with the whole device step inline.

Everything here but the :class:`~.engine_pump.EnginePump` thread runs on
the scheduler loop, so this module stays under graftlint's
blocking-in-callback rule (``engine_pump`` alone is allowlisted).

Tickets, frames and the WAL's synced frontier change at the end of a
cycle and nowhere else, so that is the one event a parked handler waits
for: :meth:`PumpCycle.wait`.
"""

from __future__ import annotations

import functools
import time
from typing import Callable, Optional

from ..engine.core import COMMIT_DEPTH
from ..sim.scheduler import TIMEOUT, Future
from ..utils.knobs import knob_float, knob_int
from . import flightrec
from .engine_pump import PUMP_THREAD_PREFIX, EnginePump
from .realtime import PumpCadence, service_busy

__all__ = ["PumpCycle", "SERVED_TICKS"]

# Ticks a served pump fuses into one program, both services' default:
# the commit path's depth plus the ingest tick, so an entry ingested at
# a batch's first tick commits inside the same program and its update
# is answered at that pump's end, not the next one's.
SERVED_TICKS = COMMIT_DEPTH + 1


class PumpCycle:
    """The pump timer, the pipeline and the cycle-end wake for one
    served engine.

    ``engine`` is the frontier service (``BatchedKV``,
    ``BatchedShardKV``): ``.driver``, ``.pump(n)``, ``.after_step(n)``,
    ``.warm_orphan_sweep()``.
    ``after_step`` and ``warm`` replace the latter two where a service
    binds arguments of its own (the sharded service orchestrates
    migration on every served pump, and not while it is constructed).
    ``on_end`` is the service's one hook: called at the end of every
    cycle, after the group fsync and the cycle-end stamp and before the
    parked handlers wake (so its time is inside ``pump.gap_s``); a
    service prunes its synced WAL-sequence tables there."""

    def __init__(
        self,
        sched,
        engine,
        ticks: int,
        *,
        metrics,
        on_end: Callable[[], None],
        interval: float = 0.002,
        durability=None,  # EngineDurability
        after_step: Optional[Callable[[int], None]] = None,
        warm: Optional[Callable[[int], None]] = None,
    ) -> None:
        self.sched = sched
        self.engine = engine
        self.ticks = ticks
        self.m = metrics
        self._dur = durability
        self._on_end = on_end
        self._after_step = after_step or engine.after_step
        self._cadence = PumpCadence(
            knob_float("MRT_PUMP_IDLE_S", default=interval)
        )
        self._stopped = False
        self._timer = None
        # No-op off the IoScheduler: sim tests drive handlers with the
        # virtual-time Scheduler.
        self._flush_io = getattr(sched, "flush_io", None)
        # The IoScheduler's loop account charges the wake at a cycle's
        # end to the handlers it resumes (``loop.cb.PumpCycle.wake_s``).
        self._run_as = getattr(sched, "run_as", None)
        # Black box: tick boundaries + consensus frontier transitions
        # land in the crash-surviving ring (flightrec.py).  The
        # frontier triple is only recorded when it CHANGES — a quiet
        # pump loop writes one TICK record per pump and nothing else.
        self._frec = flightrec.get_recorder()
        self._last_frontier = (-1, -1, -1)
        # Pump sequencing for the tail plane: the number of the last
        # completed pump and its dispatch stamp (``stages.tick``,
        # ``stages.pump_wait_s``).
        self.seq = 0
        self.t_dispatch = 0.0
        # perf_counter when the last cycle ended (after_pump returned):
        # the next dispatch closes ``pump.gap_s`` against it.
        self.t_end = None
        # Resolved when the cycle in progress ends: what :meth:`wait`
        # parks a handler on.
        self._ended = Future()
        # Asynchronous engine pipeline (engine_pump.py): the loop
        # dispatches fused tick batches and completes them when the
        # dedicated pump thread has fetched the stacked metrics; the
        # synchronous pump stays selectable per pump (kill switch,
        # reorder chaos).  A mesh driver pipelines like a one-chip one:
        # the batch is one program over its devices and the fetch reads
        # each chip's shard back.  Durable servers pin the depth to 1
        # so each checkpoint sees a drained pipeline (EngineDriver.save
        # refuses otherwise).
        self.pipe = None
        self.depth = 1
        driver = engine.driver
        if driver.pipeline_on:
            loop_name = getattr(getattr(sched, "_thread", None), "name", "")
            suffix = (
                loop_name[len("multiraft-loop"):]
                if loop_name.startswith("multiraft-loop") else ""
            )
            self.pipe = EnginePump(sched, name=PUMP_THREAD_PREFIX + suffix)
            if durability is None:
                self.depth = max(1, knob_int("MRT_PIPELINE_DEPTH"))
            if driver.fused_eligible():
                # Warm the fused n-tick program NOW, before the first
                # client byte: its first invocation pays the jit compile
                # on this (loop) thread, and paying it mid-serving stalls
                # the first rate step's tail (measured ~100 ms on the r04
                # sweep's opening step).  The backlog is empty at
                # construction, so this is two liveness ticks.
                (warm or engine.pump)(ticks)
        # The orphan sweep's gather (engine/frontier.py) runs on every
        # 32nd served pump, whichever path pumps: its one program is
        # warmed here too, though nothing is bound yet.
        engine.warm_orphan_sweep()
        sched.call_soon(self._pump_loop)

    # -- what a service and its handlers use ---------------------------------

    def wait(self, until: float, counted: bool = False):
        """Park the calling handler (``yield from``) until the pump
        cycle in progress ends, or until ``until`` on the scheduler's
        clock if no pump ends first (a stalled pump; shutdown's drain,
        which completes ticks without the hook).  False, and no wait,
        once ``until`` has passed.  ``counted``: a parked ``command``
        update's resumptions, ``kv.wait_steps`` (every one) and
        ``kv.wait_timeouts`` (those a deadline caused)."""
        left = until - self.sched.now
        if left <= 0:
            return False
        woke = yield self.sched.with_timeout(self._ended, left)
        if counted:
            self.m.inc("kv.wait_steps")
            if woke is TIMEOUT:
                self.m.inc("kv.wait_timeouts")
        return True

    def stop(self) -> None:
        self._stopped = True
        if self.pipe is not None:
            self.pipe.stop()

    def drain(self) -> None:
        """Complete every in-flight batch synchronously (checkpoint /
        shutdown path): blocks the loop, which is the point — nothing
        else may observe a half-accounted engine."""
        d = self.engine.driver
        while d._inflight:
            p = d._inflight[0]
            d.complete_ticks(p, p.fetch())
            self._after_step(p.n)

    def final_checkpoint(self) -> bool:
        """Graceful-shutdown hook (CLI SIGTERM): fold everything into
        one last checkpoint so the next start skips WAL replay.  False
        when the server is not durable.  The cycle stops first: nothing
        pumps after the last checkpoint, and no pump thread is left
        mid-fetch for the interpreter's exit to tear down (a daemon
        thread unwound inside XLA aborts the process: exit -6)."""
        self.stop()
        if self._dur is None:
            return False
        self.drain()  # driver.save refuses in-flight batches
        self._dur.checkpoint()
        return True

    # -- the cycle (loop thread) ---------------------------------------------

    def _arm_pump(self) -> None:
        """Single-timer discipline: exactly one pending _pump_loop
        timer, re-armed earlier when a completion says there is work."""
        t = self._timer
        if t is not None:
            t.cancel()
        self._timer = self.sched.call_after(
            self._cadence.next_delay(service_busy(self.engine)),
            self._pump_loop,
        )

    def _pump_loop(self) -> None:
        self._timer = None
        if self._stopped:
            return
        d = self.engine.driver
        if self.pipe is None or not d.fused_eligible():
            self._pump_sync()
            return
        # Pipelined path: dispatch a fused batch WITHOUT waiting — the
        # engine-pump thread blocks on the readback and posts
        # _pump_done back here.  The loop is free for wire work while
        # the device computes.
        if len(d._inflight) < self.depth:
            # Push queued replies first (see _pump_sync).
            if self._flush_io is not None:
                self._flush_io()
            cp0 = time.thread_time()
            pending = d.dispatch_ticks(self.ticks)
            pending.t_loop_cpu = time.thread_time() - cp0
            if self.t_end is not None:
                # What the loop did between two cycles: the pump
                # timer's delay, frames, replies, other timers.
                self.m.observe("pump.gap_s", pending.t_dispatch - self.t_end)
            self.pipe.submit(
                pending.fetch, functools.partial(self._pump_done, pending)
            )
        if len(d._inflight) < self.depth:
            self._arm_pump()
        # else the pipeline is full: the completion re-arms
        # (_pump_done), and a timer that only found it full again every
        # hot interval would take loop turns from the sockets.

    def _pump_sync(self) -> None:
        """Synchronous pump (MRT_ENGINE_PIPELINE=0, reorder chaos in
        flight): the whole device step runs on the loop thread."""
        # About to grind for up to several milliseconds: push any
        # queued replies onto the wire first, or a client whose op
        # resolved last tick waits out this whole one before it can
        # pipeline its next frame.
        if self._flush_io is not None:
            self._flush_io()
        t0 = time.perf_counter()
        cp0 = time.thread_time()
        self.engine.pump(self.ticks)
        dt = time.perf_counter() - t0
        self._record_pump(dt, time.thread_time() - cp0)
        self._end_cycle()
        self._arm_pump()

    def _pump_done(self, pending, rec) -> None:
        """Loop-side completion of a dispatched batch (posted by the
        engine-pump thread with the fetched stacked metrics): fold the
        bookkeeping, sweep the frontier, observe, re-arm."""
        if isinstance(rec, BaseException):
            raise rec  # device failure: surface on the owning loop
        d = self.engine.driver
        if pending not in d._inflight:
            # Already drained (final_checkpoint, or a synchronous step
            # that completed it) or torn down.
            if not self._stopped:
                self._arm_pump()
            return
        cp0 = time.thread_time()
        d.complete_ticks(pending, rec)
        self._after_step(pending.n)
        # Wall covers dispatch→completion (the client-visible pump
        # latency); CPU counts only the LOOP-side share — the split the
        # profiler uses to show the loop is no longer device-blocked.
        self._record_pump(
            time.perf_counter() - pending.t_dispatch,
            (time.thread_time() - cp0) + pending.t_loop_cpu,
        )
        self._end_cycle()
        if not self._stopped:
            self._arm_pump()

    def _record_pump(self, dt: float, cdt: float) -> None:
        self.m.inc("pump.count")
        self.m.observe("pump.wall_s", dt)
        # Wall-vs-CPU split: a tick whose wall ≫ CPU is device-bound
        # (the host blocked on the accelerator); wall ≈ CPU is
        # host-bound (binding/resolution burning the loop).  The pump
        # IS the engine stage's CPU (observe.py vocabulary).
        self.m.observe("cpu.engine_s", cdt)
        # Tick id + dispatch stamp (now − wall) let a committing request
        # attribute its parked time to the fused tick that carried it.
        # Unconditional — the flight-ring gate below must not decide
        # whether requests know their tick.
        self.seq += 1
        self.t_dispatch = time.perf_counter() - dt
        fr = self._frec
        if fr is None:
            return
        # Tick boundary + (on change only) the consensus frontier.
        # Everything here is host-side bookkeeping the pump already
        # computed — no device readback is added.
        d = self.engine.driver
        commits = int(d.commits_total)
        fr.record(flightrec.TICK, a=self.seq, b=int(dt * 1e6), c=commits)
        lm = getattr(d, "last_metrics", None) or {}
        frontier = (
            commits, int(lm.get("leaders", -1)), int(lm.get("max_term", -1))
        )
        if frontier != self._last_frontier:
            self._last_frontier = frontier
            fr.record(
                flightrec.STATE, a=frontier[0], b=frontier[1], c=frontier[2]
            )

    def _end_cycle(self) -> None:
        if self._dur is not None:
            self._dur.after_pump()  # group fsync + periodic checkpoint
        self.t_end = time.perf_counter()
        self._on_end()
        # Tickets, failures and the WAL's synced frontier change here
        # and nowhere else, so this is where parked handlers look again:
        # each takes one step inline, after the stamp, so the wake-up is
        # inside ``pump.gap_s``.  The fresh future goes in first: a
        # handler that parks again waits for the NEXT cycle's end.
        ended, self._ended = self._ended, Future()
        if self._run_as is not None:
            self._run_as("PumpCycle.wake", ended.resolve)
        else:
            ended.resolve()
