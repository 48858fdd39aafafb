"""The benchmark's whole hold on the process that owns the chip.

    python chipbench/server_child.py <side-dir> serve-kv --platform tpu ...

Starts one thread that waits for three signals and then calls the
program's own entry point, ``multiraft_tpu.__main__.main(argv)``,
unchanged.  The signals are blocked in every thread and taken with
``sigwait`` by that one: a Python handler runs only when the main thread
wakes, and a signal that the kernel hands to another thread never wakes
a main thread parked in ``Event.wait()`` (seen once in ~70 runs on the
chip: a report that never came).

``SIGUSR1``  ``jax.profiler.start_trace(<side-dir>/trace)``
``SIGUSR2``  ``jax.profiler.stop_trace()``
``SIGHUP``   write ``<side-dir>/report.<n>.json`` (n = 1, 2, ...): peak
             device memory on the fullest chip, and how many times JAX
             traced, lowered or compiled a program so far — two reports
             around a window show whether anything compiled inside it.

The same child serves ``--trace 0`` and ``--trace 1``; an untraced run
just never gets the first two signals.  Only the process that holds the
chip can trace it or read its memory, which is why this is here and not
in the parent.
"""

from __future__ import annotations

import time

_T_START = time.monotonic()  # the interpreter is up; its own start is before this

import json
import os
import signal
import sys
import threading

_state = {"compiles": 0, "reports": 0, "tracing": False}


def _on_duration(event: str, _secs: float, **_kw) -> None:
    if event.startswith("/jax/core/compile"):
        _state["compiles"] += 1


def _report(side: str) -> None:
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    _state["reports"] += 1
    out = {
        "memory_peak_bytes": max(peaks) if peaks else 0,
        "compile_events": _state["compiles"],
    }
    path = os.path.join(side, f"report.{_state['reports']}.json")
    with open(path + ".tmp", "w") as f:
        json.dump(out, f)
    os.replace(path + ".tmp", path)


def _start_trace(side: str) -> None:
    import jax

    if not _state["tracing"]:
        _state["tracing"] = True
        # The device and XLA's own host events; no Python call tracing,
        # which writes millions of events and slows the loop it watches.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(os.path.join(side, "trace"), profiler_options=options)
        open(os.path.join(side, "trace.started"), "w").close()


def _stop_trace(side: str) -> None:
    import jax

    if _state["tracing"]:
        jax.profiler.stop_trace()
        _state["tracing"] = False
        open(os.path.join(side, "trace.stopped"), "w").close()


_ACTIONS = {signal.SIGUSR1: _start_trace, signal.SIGUSR2: _stop_trace,
            signal.SIGHUP: _report}


def _serve_signals(side: str) -> None:
    while True:
        _ACTIONS[signal.sigwait(set(_ACTIONS))](side)


def main(argv) -> int:
    side, rest = argv[0], argv[1:]
    # Before any other thread exists: threads inherit the mask.
    signal.pthread_sigmask(signal.SIG_BLOCK, set(_ACTIONS))
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import jax.monitoring

    t_jax = time.monotonic()
    from multiraft_tpu.__main__ import main as program_main

    # On CLOCK_MONOTONIC, the parent's clock too: where a start's seconds go
    # before the program's own ``ready.*_s`` gauges begin.
    print(f"child clock: start {_T_START:.6f} jax {t_jax:.6f} program {time.monotonic():.6f}",
          file=sys.stderr, flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    threading.Thread(target=_serve_signals, args=(side,), daemon=True,
                     name="chipbench-signals").start()
    return program_main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
