"""The program's own clocks on the pump cycle and the compiler.

One durable pump cycle (pipeline depth 1) is strictly serial::

    gap | dispatch | handoff | fetch (pump thread) | post | complete | apply
        | sync | checkpoint

:func:`pump_phase` is the one helper the phases that enclose code use:
it times the block into the registry's cumulative ``pump.<phase>_s``
histogram (``Obs.hist`` serves it, two scrapes difference it) and wraps
it in a ``jax.profiler.TraceAnnotation("mrt.pump.<phase>", pump=<n>)``,
so a profiler session holds the same block on the calling thread's line
of the xplane that carries the device's "XLA Ops" (on the v5e the device
plane ran about 1.6 ms ahead of the host lines: a program starts before
the call that launched it; whoever lays one over the other takes that
offset from the trace).  With no session on, the annotation is a flag
test.  ``gap``,
``handoff`` (the pump thread's wake-up: its condition variable, then
the GIL the loop still holds) and ``post`` (the completion's wait for
the loop) enclose no code: they are waits between stamps, observed
where the stamps meet — ``PumpCycle`` (distributed/pump_cycle.py) and
``EngineDriver.complete_ticks``.

This module lives in ``engine/`` because jax is already imported here:
a pure client node (``distributed/tcp.py``, ``observe.py``) imports
none of it, and so no jax.
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator, Optional

import jax.monitoring
from jax.profiler import TraceAnnotation

from ..utils.metrics import Hist, Metrics

__all__ = ["pump_phase", "count_compiles", "ReadyStages"]


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
