"""Subprocess launch + readiness plumbing and the blocking-clerk
base, shared by the cluster drivers in cluster.py and
engine_cluster.py (their own module so neither imports the other —
the round-4 decomposition must not create an import cycle)."""

from __future__ import annotations

import os
import subprocess
import sys
from typing import Any, List

from ..sim.scheduler import TIMEOUT
from ..utils.knobs import knob_str
from .realtime import RealtimeScheduler
from .tcp import RpcNode

__all__ = [
    "ENGINE_KINDS",
    "server_env",
    "launch_server",
    "check_ready",
    "reserve_ports",
    "BlockingClerkBase",
]


# Server kinds whose process runs the engine tick (and so claims a
# device); the others are pure Python and never touch jax.
ENGINE_KINDS = (
    "engine_kv", "engine_shardkv", "engine_fleet", "split_kv",
    "split_shardkv",
)


def server_env(spec: dict) -> dict:
    """The environment of one server child.  The spec is the one
    source of an engine child's device:

    * ``platform`` becomes the child's ``JAX_PLATFORMS``, and the child
      fails if that backend does not come up.  A chip belongs to one
      process: a second engine child on an occupied chip fails its
      readiness check in seconds, with libtpu's reason.  With no entry
      the child inherits this process's environment, i.e. what JAX
      selects.
    * ``device`` (a local chip index) gives the child that one chip of
      a multi-chip host, through the visibility variables libtpu
      0.0.34 honours (established on a 2x2 v5e host: with these three
      set, each child sees one device and children on different
      indices run side by side).

    The pure-Python kinds never run a tick and stay on the CPU."""
    env = dict(os.environ)
    if spec.get("kind", "kv") not in ENGINE_KINDS:
        env["JAX_PLATFORMS"] = "cpu"
        return env
    if spec.get("platform"):
        env["JAX_PLATFORMS"] = spec["platform"]
    if spec.get("device") is not None:
        env["TPU_VISIBLE_CHIPS"] = str(int(spec["device"]))
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = "1,1,1"
        env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    return env


def launch_server(spec: dict, label: Any) -> subprocess.Popen:
    """Spawn one server subprocess (shared by both cluster drivers):
    env setup (:func:`server_env`), optional MRT_SERVER_LOG_DIR stderr
    capture, Popen."""
    import json

    env = server_env(spec)
    repo_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    log_dir = knob_str("MRT_SERVER_LOG_DIR")
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
        stderr = open(os.path.join(log_dir, f"server-{label}.err"), "a")
    else:
        stderr = subprocess.DEVNULL
    try:
        return subprocess.Popen(
            [sys.executable, "-m", "multiraft_tpu.distributed.cluster",
             json.dumps(spec)],
            stdout=subprocess.PIPE, stderr=stderr, env=env, text=True,
        )
    finally:
        if log_dir:
            stderr.close()


def check_ready(
    proc: subprocess.Popen, label: Any, timeout: float = 120.0
) -> None:
    """Block until the child prints its readiness line, bounded by
    ``timeout`` — a child that starts but hangs before printing (e.g.
    stuck in jax/native-build import) must not wedge the launcher
    forever.  On timeout the child is killed and the failure raised.
    Callers must register ``proc`` for reaping BEFORE calling this — a
    child that fails the check is still a live process."""
    import select
    import time as _time

    deadline = _time.monotonic() + timeout
    buf = ""
    while True:
        remaining = deadline - _time.monotonic()
        if remaining <= 0:
            proc.kill()
            proc.wait()  # reap — crash/restart loops must not pile zombies
            raise RuntimeError(
                f"server {label} produced no readiness line within "
                f"{timeout:.0f}s; killed"
            )
        ready, _, _ = select.select([proc.stdout], [], [], remaining)
        if not ready:
            continue
        chunk = os.read(proc.stdout.fileno(), 4096).decode(
            "utf-8", "replace"
        )
        if chunk == "":
            # EOF: the child is gone.  Reap and report HOW it died —
            # a negative returncode names the signal (a silent SIGKILL
            # reads very differently from a clean exit-1).
            rc = proc.wait()
            raise RuntimeError(
                f"server {label} failed to start (exit {rc}): {buf!r}"
            )
        buf += chunk
        if "\n" in buf:
            line = buf.split("\n", 1)[0]
            if not line.startswith("ready"):
                raise RuntimeError(
                    f"server {label} failed to start: {line!r}"
                )
            return

def reserve_ports(n: int, host: str) -> List[int]:
    import socket

    ports, socks = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind((host, 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


class BlockingClerkBase:
    """Synchronous client facade: drives a generator-coroutine clerk on
    a RealtimeScheduler and blocks the calling thread for the result.
    Subclasses construct ``self._clerk`` (anything with get/put/append
    generator methods)."""

    sched: RealtimeScheduler
    node: RpcNode
    _clerk: Any

    def _run(self, gen, timeout: float) -> Any:
        fut = self.sched.spawn(gen)
        value = self.sched.wait(fut, timeout)
        if value is TIMEOUT:
            # Cancel the abandoned retry loop (resolving the spawn future
            # halts the coroutine at its next step) — otherwise it would
            # spin forever and race the caller's next command on this
            # single-outstanding-op clerk.
            self.sched.post(fut.resolve, TIMEOUT)
            raise TimeoutError("cluster did not answer in time")
        return value

    def get(self, key: str, timeout: float = 30.0) -> str:
        return self._run(self._clerk.get(key), timeout)

    def put(self, key: str, value: str, timeout: float = 30.0) -> None:
        self._run(self._clerk.put(key, value), timeout)

    def append(self, key: str, value: str, timeout: float = 30.0) -> None:
        self._run(self._clerk.append(key, value), timeout)

    def close(self) -> None:
        """Close the RPC node (its scheduler loop stops with it)."""
        self.node.close()

