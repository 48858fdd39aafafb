"""The program's own clocks on the pump cycle and the compiler.

One durable pump cycle (pipeline depth 1) is strictly serial::

    gap | dispatch | handoff | fetch (pump thread: wait | copy) | post
        | complete | apply | sync | checkpoint

:func:`pump_phase` is the one helper the phases that enclose code use:
it times the block into the registry's cumulative ``pump.<phase>_s``
histogram (``Obs.hist`` serves it, two scrapes difference it) and wraps
it in a ``jax.profiler.TraceAnnotation("mrt.pump.<phase>", pump=<n>)``,
so a profiler session holds the same block on the calling thread's line
of the xplane that carries the device's "XLA Ops".  The two clocks are
laid over each other at the end of ``mrt.pump.wait`` (the pump thread's
``block_until_ready`` returning: the host's sight of the device's
completion).  On a v5e, over every pump of a 3 s trace, (the device's
end of ``jit_step_ticks``) - (the end of ``mrt.pump.wait``) has the
median -2.67 ms (quartiles -2.91 / -2.52) at 10,000 groups and -1.48 ms
(-1.54 / -1.42) at 100,000: the device plane's clock runs ~1.5 ms ahead
of the host lines', and where the serving loop is full the pump
thread's return from the wait (the GIL) adds ~1.2 ms.  With no session
on, the annotation is a flag test.  ``gap``,
``handoff`` (the pump thread's wake-up: its condition variable, then
the GIL the loop still holds) and ``post`` (the completion's wait for
the loop) enclose no code: they are waits between stamps, observed
where the stamps meet — ``PumpCycle`` (distributed/pump_cycle.py) and
``EngineDriver.complete_ticks``.

Around the cycle: :func:`count_gc` times the collector, :func:`trace_loop`
puts the serving loop's turns on the profiler's line of its thread
(``IoScheduler`` keeps their account), and :func:`count_compiles` counts
the compiler.

This module lives in ``engine/`` because jax is already imported here:
a pure client node (``distributed/tcp.py``, ``observe.py``) imports
none of it, and so no jax.
"""

from __future__ import annotations

import contextlib
import gc
import threading
import time
from typing import Iterator, Optional

import jax.monitoring
from jax.profiler import TraceAnnotation

from ..utils.metrics import Hist, Metrics

__all__ = [
    "pump_phase", "count_compiles", "count_gc", "trace_loop", "ReadyStages",
]


@contextlib.contextmanager
def pump_phase(
    metrics: Metrics, phase: str, hist: Optional[str] = None
) -> Iterator[None]:
    """Time the block as pump-cycle phase ``phase``: histogram ``hist``
    (default ``pump.<phase>_s``) and a ``mrt.pump.<phase>`` trace
    annotation tagged with the number of pumps completed so far."""
    t0 = time.perf_counter()
    with TraceAnnotation(
        "mrt.pump." + phase, pump=metrics.counters.get("pump.count", 0)
    ):
        try:
            yield
        finally:
            metrics.observe(
                hist or f"pump.{phase}_s", time.perf_counter() - t0
            )


class ReadyStages:
    """Time to ``ready`` by stage, published once as gauges
    ``ready.<stage>_s`` (0.0: the stage did not run).  The first use of
    a program pays its compile or cache load where it falls."""

    def __init__(self, *stages: str) -> None:
        self.secs = dict.fromkeys(stages, 0.0)
        self._t = time.perf_counter()

    def start(self) -> None:
        self._t = time.perf_counter()

    def lap(self, stage: str) -> None:
        """``stage`` ends now; the next one starts."""
        now = time.perf_counter()
        self.secs[stage] = now - self._t
        self._t = now

    def publish(self, metrics: Metrics) -> None:
        for stage, secs in self.secs.items():
            metrics.set(f"ready.{stage}_s", secs)


def trace_loop(sched) -> None:
    """Hold the serving loop's turns on the profiler's line of its
    thread (``IoScheduler.trace_with``): ``mrt.loop.<owner>`` a timer
    turn, ``mrt.loop.io`` a poll's socket work."""
    sched.trace_with(TraceAnnotation, TraceAnnotation.is_enabled)


class _GcClock:
    """One registry's share of :func:`count_gc`'s callback: objects made
    at install and updated in place."""

    __slots__ = ("pause", "counters", "loop")

    def __init__(self, metrics: Metrics, loop) -> None:
        self.pause = metrics.hists.setdefault("gc.pause_s", Hist())
        self.counters = metrics.counters
        for key in ("gc.collections", "gc.gen2"):
            metrics.inc(key, 0)
        metrics.counters.setdefault("loop.gc_s", 0.0)
        self.loop = loop


_gc_clocks: tuple = ()
_gc_start = [0.0, None]  # perf_counter, TraceAnnotation of the collection


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` entry: one for the process, whatever the
    registries.  Collections neither nest nor overlap (the collector
    holds a flag), so one start stamp serves them all.  Nothing here
    makes a key: a collection can start inside ``Metrics.observe``."""
    if phase == "start":
        tm = None
        if TraceAnnotation.is_enabled():
            tm = TraceAnnotation("mrt.gc", gen=info["generation"])
            tm.__enter__()
        _gc_start[1] = tm
        _gc_start[0] = time.perf_counter()
        return
    dt = time.perf_counter() - _gc_start[0]
    tm, _gc_start[1] = _gc_start[1], None
    if tm is not None:
        tm.__exit__(None, None, None)
    gen2 = info["generation"] == 2
    me = threading.get_ident()
    for c in _gc_clocks:
        c.pause.observe(dt)
        counters = c.counters
        counters["gc.collections"] += 1
        if gen2:
            counters["gc.gen2"] += 1
        if c.loop is not None and c.loop.ident == me:
            counters["loop.gc_s"] += dt


def count_gc(metrics: Metrics, loop: Optional[threading.Thread] = None) -> None:
    """Time every collection of the cyclic garbage collector, which
    stops every thread: histogram ``gc.pause_s`` (on whichever thread
    collected), counters ``gc.collections`` and ``gc.gen2``, and
    ``loop.gc_s``, the seconds of those that ran on thread ``loop`` (the
    serving loop's); and, under a profiler session, a
    ``TraceAnnotation("mrt.gc", gen=<generation>)`` on the collecting
    thread's line.  Call it once per registry; a registry whose loop
    has stopped is dropped at the next call."""
    global _gc_clocks
    clock = _GcClock(metrics, loop)
    _gc_clocks = tuple(
        c for c in _gc_clocks if c.loop is None or c.loop.is_alive()
    ) + (clock,)
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def count_compiles(metrics: Metrics) -> None:
    """Count JAX's trace, lower and compile events (``/jax/core/compile*``
    duration events) into ``engine.compiles`` / ``engine.compile_s`` of
    ``metrics``, so a recompile inside a serving window shows by name in
    any scrape.  Call it once per registry: jax keeps a listener for the
    life of the process.  The keys are made here, on the caller's
    thread; the listener runs on whichever thread compiles and only
    updates them in place."""
    metrics.inc("engine.compiles", 0)
    metrics.hists.setdefault("engine.compile_s", Hist())

    def on_duration(event: str, secs: float, **_kw) -> None:
        if event.startswith("/jax/core/compile"):
            metrics.inc("engine.compiles")
            metrics.observe("engine.compile_s", secs)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
