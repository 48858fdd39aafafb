"""Test configuration.

Correctness tests run on the CPU, on a virtual 8-device mesh so that
multi-chip shardings are exercised without TPU hardware; the chip is
for ``chip_smoke.py`` and ``bench.py``.  ``JAX_PLATFORMS=cpu`` is
exported here so that every subprocess the tests spawn (clusters,
examples, CLI, bench smoke) is on the CPU too, and XLA_FLAGS is set
before jax initialises so the CPU client fans out into 8 devices.
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

import jax

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache, shared with every spawned server
# child: the suite's dominant wall-clock cost was each engine
# subprocess re-jitting the same tick programs (~10-20 s per child,
# dozens of children).  Several children run concurrently and get
# SIGKILLed by crash/chaos tests, hence the atomic writer
# (utils/jaxcache.py).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from multiraft_tpu.utils.jaxcache import enable_compile_cache

enable_compile_cache()

import signal

import pytest

# Per-test wall-clock cap — the reference enforces 120 s per test in
# every harness (raft/config.go:342-347); here it is a pytest-level
# SIGALRM so a wedged test fails loudly instead of stalling the suite.
# Tests that legitimately need longer declare
# @pytest.mark.timeout_s(N).
TEST_TIMEOUT_S = 120


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "timeout_s(n): override the per-test wall-clock cap"
    )


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    cap = TEST_TIMEOUT_S
    m = item.get_closest_marker("timeout_s")
    if m is not None:
        cap = int(m.args[0])

    def on_alarm(signum, frame):
        raise TimeoutError(
            f"test exceeded the {cap}s cap (reference: raft/config.go:"
            "342-347 two-minute rule)"
        )

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(cap)
    try:
        return (yield)
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
