"""Wall-clock event loop with the sim Scheduler's exact surface.

Every framework component (RaftNode, KVServer, ShardCtrler, clerks) is
written against the virtual-time ``Scheduler`` API: timers via
``call_at/call_after/call_soon``, suspension via ``Future``, blocking
control flow via generator coroutines (``spawn``).  This class provides
the same contract on real time: one event-loop thread owns all callback
execution (so the single-threaded mutation model the sim guarantees by
construction still holds), a monotonic clock replaces virtual ``now``,
and a thread-safe ``post`` lets IO threads (the TCP transport) marshal
completions onto the loop.

This is the deployment analog of the reference's goroutine runtime
(reference: raft/raft.go:51-87) — except there is exactly one mutator
thread, so the reference's mutex discipline (raft/raft.go:22) has no
equivalent to get wrong.
"""

from __future__ import annotations

import heapq
import threading
import time
import types
from typing import Any, Callable, Generator, Optional

from ..sim.scheduler import TIMEOUT, Future, Timer
from ..utils.cpus import usable_cpus
from ..utils.knobs import knob_bool
from .sanitize import get_sanitizer

__all__ = [
    "RealtimeScheduler",
    "IoScheduler",
    "PumpCadence",
    "Backoff",
    "service_busy",
    "LOOP_OWNERS",
]

# Who a timer callback's turn of the loop is charged to, by the class its
# callee belongs to (the first part of its ``__qualname__``): the pump
# cycle, the watches, and ``Future.resolve`` (a sleep or a wait's timeout:
# it resumes a parked coroutine inline).  A coroutine step is
# ``handlers`` whatever its generator; everything else is ``other``.
LOOP_OWNERS = ("pump", "handlers", "watch", "other")
_OWNER_OF_CLASS = {
    "PumpCycle": "pump",
    "WedgeWatch": "watch",
    "OverloadWatch": "watch",
    "Future": "handlers",
}
# A turn of the loop longer than this counts in ``loop.long_turns``.
LONG_TURN_S = 0.1


class Backoff:
    """Bounded exponential backoff with equal jitter, for clerk retry
    loops.  Without it, a fast-failing RPC (connection refused while a
    server restarts, a partitioned minority answering instantly) turns
    the reference retry loop into a hot spin — thousands of doomed
    calls per second hammering the exact process trying to recover.

    ``next_delay()`` draws uniformly from ``[cur/2, cur]`` (equal
    jitter: a floor keeps the loop off the CPU, the random half
    de-synchronizes clerks that failed together), then doubles ``cur``
    up to ``cap``.  ``reset()`` on success re-arms the fast first
    retry."""

    def __init__(
        self,
        base: float = 0.02,
        cap: float = 1.0,
        factor: float = 2.0,
        rng: Optional[Any] = None,
    ) -> None:
        import random

        self.base = base
        self.cap = cap
        self.factor = factor
        self._cur = base
        self._rng = rng if rng is not None else random.Random()

    def next_delay(self) -> float:
        cur = self._cur
        self._cur = min(self.cap, cur * self.factor)
        return cur / 2.0 + self._rng.random() * (cur / 2.0)

    def jittered(self, base: float) -> float:
        """Equal-jitter a caller-supplied delay — a server's
        ``retry_after_s`` hint, a fixed config-wait — WITHOUT advancing
        the doubling state.  The server hands the same hint to every
        clerk it sheds; a deterministic wait would re-synchronize them
        into the next thundering herd."""
        return base / 2.0 + self._rng.random() * (base / 2.0)

    def reset(self) -> None:
        self._cur = self.base


class RealtimeScheduler:
    """Drop-in wall-clock implementation of the sim ``Scheduler`` API.

    ``now`` is seconds on a monotonic clock (an absolute epoch is never
    exposed, matching the sim's relative-time semantics).  All callbacks
    — timer fires, future resolutions, coroutine steps — execute on the
    single loop thread.  External threads interact only through
    :meth:`post` and :meth:`wait`.
    """

    def __init__(self, name: str = "multiraft-loop") -> None:
        self._origin = time.monotonic()
        self._heap: list[tuple[float, int, Timer]] = []
        self._seq = 0
        self._lock = threading.Lock()
        self._wakeup = threading.Condition(self._lock)
        self._stopped = False
        # Runtime sanitizer (MRT_SANITIZE=1): every callback the loop
        # runs goes through its duration-budget shim.  None = off =
        # one `is None` check per dispatch.
        self._san = get_sanitizer()
        # ``name`` is the loop thread's name — the profiler keys CPU
        # attribution by it (profile.py), so multi-node processes pass
        # a per-node suffix ("multiraft-loop/9001") to keep their
        # loops distinguishable in the fleet flame.
        self._thread = threading.Thread(
            target=self._run, name=name, daemon=True
        )
        self._thread.start()

    # -- clock ------------------------------------------------------------

    @property
    def now(self) -> float:
        return time.monotonic() - self._origin

    def on_loop_thread(self) -> bool:
        """True when called from the loop thread (the only thread that
        may touch loop-owned state like RpcNode's reply queue)."""
        return threading.current_thread() is self._thread

    def flush_io(self) -> None:
        """Force any pending IO flush now.  No-op here; IoScheduler
        overrides it.  Long-running timer callbacks (an engine pump
        about to grind for milliseconds) call this first so replies
        already queued don't wait them out."""

    # -- scheduling (sim-compatible) --------------------------------------

    def call_at(self, when: float, fn: Callable, *args: Any) -> Timer:
        timer = Timer(when, fn, args)
        with self._lock:
            self._seq += 1
            heapq.heappush(self._heap, (when, self._seq, timer))
            self._wakeup.notify()
        return timer

    def call_after(self, delay: float, fn: Callable, *args: Any) -> Timer:
        return self.call_at(self.now + delay, fn, *args)

    def call_soon(self, fn: Callable, *args: Any) -> Timer:
        return self.call_at(self.now, fn, *args)

    # ``post`` is the documented thread-safe entry point; internally
    # call_at already locks, so they share one implementation.
    post = call_soon

    # -- futures / coroutines (same semantics as sim Scheduler) -----------

    def sleep(self, delay: float) -> Future:
        fut = Future()
        self.call_after(delay, fut.resolve, None)
        return fut

    def with_timeout(self, fut: Future, timeout: float) -> Future:
        out = Future()
        timer = self.call_after(timeout, out.resolve, TIMEOUT)

        def _done(f: Future) -> None:
            timer.cancel()
            out.resolve(f.value)

        fut.add_done_callback(_done)
        return out

    def spawn(self, gen: Generator) -> Future:
        result = Future()
        if not isinstance(gen, types.GeneratorType):
            result.resolve(gen)
            return result

        def step(send_value: Any) -> None:
            if result.done:  # cancelled from outside (BlockingClerk timeout)
                gen.close()
                return
            try:
                waited = gen.send(send_value)
            except StopIteration as stop:
                result.resolve(stop.value)
                return
            if isinstance(waited, Future):
                # Step inline on resolution — the sim Scheduler's exact
                # semantics (sim/scheduler.py spawn).  Safe because every
                # resolve already runs on the loop thread; posting would
                # add a heap round trip per coroutine step.
                waited.add_done_callback(lambda f: step(f.value))
            elif isinstance(waited, (int, float)):
                self.call_after(float(waited), step, None)
            else:  # pragma: no cover - defensive
                raise TypeError(f"coroutine yielded {waited!r}")

        self.call_soon(step, None)
        return result

    # -- cross-thread waiting ---------------------------------------------

    def wait(self, fut: Future, timeout: Optional[float] = None) -> Any:
        """Block the *calling* (non-loop) thread until ``fut`` resolves.

        Returns the future's value, or :data:`TIMEOUT` on timeout.  The
        external-thread analog of the sim's ``run_until``.

        ``Future`` is not thread-safe (it never needs to be on the loop),
        so the callback is *attached on the loop thread* — the same
        thread every resolve runs on — making the done-check/append
        sequence race-free by construction.
        """
        done = threading.Event()
        box: list[Any] = []

        def _resolved(f: Future) -> None:
            box.append(f.value)
            done.set()

        self.post(lambda: fut.add_done_callback(_resolved))
        if not done.wait(timeout):
            return TIMEOUT
        return box[0]

    def run_call(self, fn: Callable, *args: Any, timeout: float = 30.0) -> Any:
        """Run ``fn(*args)`` on the loop thread and return its result to
        the calling thread; exceptions propagate to the caller instead of
        dying on the loop (construction-time errors must be loud)."""
        fut = Future()

        def _invoke() -> None:
            try:
                fut.resolve((True, fn(*args)))
            except BaseException as e:  # noqa: BLE001 - transported
                fut.resolve((False, e))

        self.post(_invoke)
        out = self.wait(fut, timeout)
        if out is TIMEOUT:
            raise TimeoutError(f"run_call timed out after {timeout}s")
        ok, value = out
        if not ok:
            raise value
        return value

    # -- lifecycle ---------------------------------------------------------

    def stop(self) -> None:
        with self._lock:
            self._stopped = True
            self._wakeup.notify()
        if threading.current_thread() is not self._thread:
            self._thread.join(timeout=5.0)

    def _run(self) -> None:
        while True:
            with self._lock:
                if self._stopped:
                    return
                while True:
                    if not self._heap:
                        self._wakeup.wait()
                        if self._stopped:
                            return
                        continue
                    when, _, timer = self._heap[0]
                    if timer.cancelled:
                        heapq.heappop(self._heap)
                        continue
                    delay = when - self.now
                    if delay <= 0:
                        heapq.heappop(self._heap)
                        break
                    self._wakeup.wait(delay)
                    if self._stopped:
                        return
                fn, args = timer._fn, timer._args
                timer._fn, timer._args = None, ()
            if fn is None:  # cancelled between pop and dispatch
                continue
            try:
                if self._san is not None:
                    self._san.run_callback(fn, *args)
                else:
                    fn(*args)
            except Exception:  # pragma: no cover - keep the loop alive
                import traceback

                traceback.print_exc()


# The closure ``spawn`` schedules for each step of a coroutine: the loop
# charges such a turn to the generator it steps.
_STEP_CODE = next(
    c for c in RealtimeScheduler.spawn.__code__.co_consts
    if isinstance(c, types.CodeType) and c.co_name == "step"
)
_STEP_GEN = _STEP_CODE.co_freevars.index("gen")


class IoScheduler(RealtimeScheduler):
    """A :class:`RealtimeScheduler` whose loop thread is ALSO the IO
    dispatcher: instead of sleeping on a condition variable between
    timers, it blocks in ``io_poll`` (the native transport's inline
    epoll reactor) and handles each event with ``io_handle`` right on
    the loop thread.

    This erases the sim-era thread topology's latency tax.  With a
    separate poller thread, every inbound frame costs two futex
    handoffs (transport → poller condvar, poller → loop ``post``);
    here a frame goes kernel → loop thread → handler inline, so a
    serial RPC round trip crosses exactly one wakeup per process.

    ``io_wake`` must interrupt a blocked ``io_poll`` (it returns
    ``None``); cross-thread ``call_at``/``post``/``stop`` use it in
    place of the condvar notify.  Wakes are level-triggered in the
    transport (an eventfd counter), so a wake that lands before the
    poll starts is not lost.

    ``io_flush`` (optional) runs on the loop thread at two points,
    distinguished by its ``force`` argument.  ``io_flush(True)`` runs
    immediately before every ``io_poll`` — nothing may sit queued while
    the loop blocks.  ``io_flush(False)`` runs after every timer
    callback, and the hook may decline it: under saturation the timer
    heap is never empty (pump ticks requeue faster than they run), so
    the before-poll flush can starve for many milliseconds — a convoy
    where every client waits on replies stuck behind engine compute.
    The soft flush bounds that starvation at one callback, while still
    letting the hook accumulate replies across back-to-back cheap
    callbacks into one vectored write per connection.

    The loop keeps its own account, as three cumulative floats that tile
    the loop thread's wall: ``timer_s`` (a timer callback and the soft
    flush after it), ``io_s`` (the forced flush before a poll and
    ``io_handle`` after it) and ``idle_s`` (blocked in ``io_poll``),
    with ``polls``, the number of polls.  Each turn of the loop is
    charged whole, its heap and lock handling included, to what it ran:
    one clock read per timer callback, three per poll, no histogram on
    this path.  ``Obs.snapshot`` publishes
    them as ``loop.timer_s`` / ``loop.io_s`` / ``loop.idle_s`` /
    ``loop.polls``, so two scrapes say where the loop's time went:
    busy in timers (the pump's phases, coroutine steps), busy with
    sockets, or waiting for either.

    A timer turn is also charged to its callee's account (one dict
    lookup and one float add a turn; the account is resolved once per
    code object, or per generator name for a coroutine step), and each
    account to one of :data:`LOOP_OWNERS`: the accounts tile
    ``timer_s`` exactly, and so do the owners, published as
    ``loop.<owner>_s`` beside ``loop.cb.<qualname>_s``
    (:meth:`loop_account`).  :meth:`run_as` moves a block inside a
    callback to another account: the pump cycle's inline wake of the
    handlers parked on it is theirs.  A turn of any kind over
    :data:`LONG_TURN_S` counts in ``long_turns`` / ``long_turn_s``.

    With a span factory installed (:meth:`trace_with`; the engine
    servers install jax's ``TraceAnnotation``), a profiler session holds
    every timer turn as ``mrt.loop.<owner>`` and a poll's socket work as
    ``mrt.loop.io`` on the loop thread's line, so the line's only holes
    are the blocking ``io_poll``.  With no session a turn pays one flag
    test.
    """

    def __init__(
        self,
        io_poll: Callable[[float], Any],
        io_handle: Callable[[Any], None],
        io_wake: Callable[[], None],
        idle_max: float = 0.2,
        io_flush: Optional[Callable[[bool], None]] = None,
        name: str = "multiraft-loop",
    ) -> None:
        self._io_poll = io_poll
        self._io_handle = io_handle
        self._io_wake = io_wake
        self._io_flush = io_flush
        self._idle_max = idle_max
        self.timer_s = self.io_s = self.idle_s = 0.0
        self.polls = 0
        self.long_turns = 0
        self.long_turn_s = 0.0
        # Account cells ``[seconds, owner, name]``, by code object or
        # generator name (``_accounts``) and by name (``_by_name``: two
        # keys of one name share a cell); ``_cur`` is the running timer
        # callback's, which :meth:`run_as` lends from.
        self._accounts: dict = {}
        self._by_name: dict = {}
        self._cur: Optional[list] = None
        self._span: Optional[Callable[[str], Any]] = None
        self._span_on: Callable[[], bool] = bool
        super().__init__(name=name)

    def trace_with(
        self, span: Callable[[str], Any], enabled: Callable[[], bool]
    ) -> None:
        """Hold the loop's turns on a profiler's line: ``span(name)`` is
        a context manager (jax's ``TraceAnnotation``), made only while
        ``enabled()``.  Any thread, before or after the loop starts."""
        self._span_on = enabled
        self._span = span

    def _cell(self, key: Any, name: str, owner: str) -> list:
        cell = self._by_name.get(name)
        if cell is None:
            cell = self._by_name[name] = [0.0, owner, name]
        self._accounts[key] = cell
        return cell

    def _account_of(self, fn: Callable) -> list:
        """The account of a timer callback off the memo (the slow path:
        a callee seen for the first time, a ``functools.partial``, a
        callable without code)."""
        inner = getattr(fn, "func", fn)  # functools.partial
        code = getattr(inner, "__code__", None)
        cell = self._accounts.get(code) if code is not None else None
        if cell is not None:
            return cell
        name = getattr(inner, "__qualname__", None) or type(inner).__qualname__
        owner = _OWNER_OF_CLASS.get(name.split(".", 1)[0], "other")
        return self._cell(code if code is not None else name, name, owner)

    def run_as(self, name: str, fn: Callable, *args: Any) -> Any:
        """Run ``fn(*args)`` inside the timer callback running now and
        charge its time to the account ``name``, owner ``handlers``, not
        to the callback's: coroutines ``fn`` resumes inline (a pump
        cycle's wake) are the handlers' time.  Loop thread only."""
        cell = self._accounts.get(name) or self._cell(name, name, "handlers")
        tm = self._span("mrt.loop.handlers") if (
            self._span is not None and self._span_on()) else None
        if tm is not None:
            tm.__enter__()
        t0 = time.perf_counter()
        try:
            return fn(*args)
        finally:
            dt = time.perf_counter() - t0
            if tm is not None:
                tm.__exit__(None, None, None)
            lender = self._cur
            if lender is not None:
                lender[0] -= dt
                cell[0] += dt

    def loop_account(self) -> dict:
        """The loop's cumulative account under the names a scrape
        publishes.  Loop thread (``Obs.snapshot`` runs there)."""
        out = {
            "loop.timer_s": self.timer_s,
            "loop.io_s": self.io_s,
            "loop.idle_s": self.idle_s,
            "loop.polls": float(self.polls),
            "loop.long_turns": float(self.long_turns),
            "loop.long_turn_s": self.long_turn_s,
        }
        for owner in LOOP_OWNERS:
            out[f"loop.{owner}_s"] = 0.0
        for secs, owner, name in self._by_name.values():
            out[f"loop.{owner}_s"] += secs
            out[f"loop.cb.{name}_s"] = secs
        return out

    def flush_io(self) -> None:
        """Run the io_flush hook forced, from the loop thread.  The
        entry point for callbacks that KNOW they are about to block the
        loop for a while (engine pump ticks): queued replies leave
        before the grind instead of aging through it."""
        if self._io_flush is not None and self.on_loop_thread():
            self._io_flush(True)

    def call_at(self, when: float, fn: Callable, *args: Any) -> Timer:
        timer = Timer(when, fn, args)
        with self._lock:
            self._seq += 1
            heapq.heappush(self._heap, (when, self._seq, timer))
        # The loop blocks in io_poll, not on the condvar — interrupt it
        # unless we ARE the loop (it re-checks the heap after every
        # callback and IO event anyway, so a self-wake is pure syscall
        # overhead on the hot path).
        if threading.current_thread() is not self._thread:
            self._io_wake()
        return timer

    def stop(self) -> None:
        with self._lock:
            self._stopped = True
        self._io_wake()
        if threading.current_thread() is not self._thread:
            self._thread.join(timeout=5.0)

    def _run(self) -> None:
        t_last = time.perf_counter()  # where the loop's account stands
        while True:
            fn = args = None
            popped = False
            with self._lock:
                if self._stopped:
                    return
                delay = self._idle_max
                while self._heap:
                    when, _, timer = self._heap[0]
                    if timer.cancelled:
                        heapq.heappop(self._heap)
                        continue
                    d = when - self.now
                    if d <= 0:
                        heapq.heappop(self._heap)
                        fn, args = timer._fn, timer._args
                        timer._fn, timer._args = None, ()
                        popped = True
                    else:
                        delay = min(d, self._idle_max)
                    break
            span = self._span
            if span is not None and not self._span_on():
                span = None
            if popped:
                if fn is not None:  # else cancelled between push and pop
                    code = getattr(fn, "__code__", None)
                    if code is _STEP_CODE:
                        key = fn.__closure__[_STEP_GEN].cell_contents.__qualname__
                        cell = self._accounts.get(key) or self._cell(
                            key, key, "handlers")
                    else:
                        cell = self._accounts.get(code) or self._account_of(fn)
                    self._cur = cell
                    tm = span("mrt.loop." + cell[1]) if span else None
                    if tm is not None:
                        tm.__enter__()
                    try:
                        if self._san is not None:
                            self._san.run_callback(fn, *args)
                        else:
                            fn(*args)
                    except Exception:  # pragma: no cover - keep loop alive
                        import traceback

                        traceback.print_exc()
                    # Soft flush after every timer callback: the hook
                    # flushes only replies old enough that waiting out
                    # another (potentially milliseconds-long) pump tick
                    # would hurt, and keeps batching fresh ones.
                    if self._io_flush is not None:
                        try:
                            self._io_flush(False)
                        except Exception:  # pragma: no cover
                            import traceback

                            traceback.print_exc()
                    if tm is not None:
                        tm.__exit__(None, None, None)
                    self._cur = None
                    now = time.perf_counter()
                    dt = now - t_last
                    self.timer_s += dt
                    cell[0] += dt
                    if dt > LONG_TURN_S:
                        self.long_turns += 1
                        self.long_turn_s += dt
                    t_last = now
                continue
            if self._io_flush is not None:
                tm = span("mrt.loop.io") if span else None
                if tm is not None:
                    tm.__enter__()
                try:
                    self._io_flush(True)
                except Exception:  # pragma: no cover - keep the loop alive
                    import traceback

                    traceback.print_exc()
                if tm is not None:
                    tm.__exit__(None, None, None)
            t1 = time.perf_counter()
            ev = self._io_poll(delay)
            t2 = time.perf_counter()
            self.polls += 1
            self.idle_s += t2 - t1
            if ev is not None:
                tm = span("mrt.loop.io") if span else None
                if tm is not None:
                    tm.__enter__()
                try:
                    if self._san is not None:
                        self._san.run_callback(self._io_handle, ev)
                    else:
                        self._io_handle(ev)
                except Exception:  # pragma: no cover - keep the loop alive
                    import traceback

                    traceback.print_exc()
                if tm is not None:
                    tm.__exit__(None, None, None)
            now = time.perf_counter()
            dt = (now - t_last) - (t2 - t1)
            self.io_s += dt
            if dt > LONG_TURN_S:
                self.long_turns += 1
                self.long_turn_s += dt
            t_last = now


class PumpCadence:
    """Adaptive pump scheduling shared by the serving loops: pump HOT
    (a fraction of the idle interval) while client work is in flight,
    idle cadence otherwise.  The fixed-interval loop leaves the pump
    ~half idle under load (measured: the in-process framed ceiling
    rises 28k → 45k ops/s at a fixed hot cadence); the idle interval
    still bounds the steady-state CPU burn, and the hot interval keeps
    a real idle window each cycle so the socket reactor (the
    scheduler's idle wait) continues to run.

    GATED ON CORE COUNT, like the transport's adaptive busy-poll
    (tcp.py MRT_SPIN_US): on a single-CPU box the hot pump steals the
    co-located clients' cycles and the end-to-end number DROPS
    (measured −38% on the 1-core test VM), so single-core hosts keep
    the fixed cadence.  ``MRT_PUMP_HOT=1/0`` overrides."""

    HOT_DIV = 5     # hot interval = interval / HOT_DIV
    HOT_PUMPS = 3   # stay hot this many pumps past the last work

    def __init__(self, interval: float) -> None:
        self.interval = interval
        self.hot_interval = interval / self.HOT_DIV
        self.enabled = knob_bool("MRT_PUMP_HOT",
                                 default=usable_cpus() > 1)
        self._hot = 0

    def next_delay(self, busy: bool) -> float:
        """``busy`` = the service observed in-flight work this pump
        (entries applied, or commands waiting in the backlog)."""
        if not self.enabled:
            return self.interval
        if busy:
            self._hot = self.HOT_PUMPS
            return self.hot_interval
        if self._hot:
            self._hot -= 1
            return self.hot_interval
        return self.interval


def service_busy(svc) -> bool:
    """The serving loops' shared work-pending signal: the last sweep
    applied entries, or submitted commands await ingestion."""
    return bool(svc.last_applied) or bool(svc.driver.backlog.any())
