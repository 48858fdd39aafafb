"""chip_smoke.py off the chip; that nothing carries on on the CPU when
the chip it asked for is not there; and the helpers every entry point
shares: a server child's device from its launch spec, where the
compile cache lives, how the native libraries are named."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*args, timeout=300):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=timeout, cwd=ROOT,
    )


def _check_rehearsal(out, legs):
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    last = out.stdout.strip().splitlines()[-1]
    # No result line: a rehearsal ends on its summary, which is not JSON.
    assert last.startswith("summary {")
    summary = json.loads(last[len("summary "):])
    assert "ok" not in summary and summary["rehearsal"] is True
    assert summary["device"]["platform"] == "cpu"
    assert summary["legs"] == legs
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    return summary


def test_rehearsal_runs_every_single_chip_leg_on_the_cpu():
    """Tiny shapes, Pallas interpreted, every check the chip run makes
    — and the output says 'rehearsal', so it cannot pass for one."""
    out = _smoke("--rehearse-cpu", "--legs", "tick,bench,served,served5")
    summary = _check_rehearsal(
        out, {"tick": "ran", "bench": "ran", "served": "ran", "served5": "ran"}
    )
    assert summary["partial"] is True  # mesh4 was not asked for
    assert "parity: pallas == jnp" in out.stdout
    assert "porcupine ok over" in out.stdout
    assert "present exactly once after restart" in out.stdout
    assert "SIGTERM -> final checkpoint, exit 0" in out.stdout
    assert "served5: G=32 x P=5" in out.stdout
    assert "served5: --replicas 3 on the same --data-dir refused" in out.stdout


def test_rehearsal_runs_the_sharded_leg_on_the_cpu():
    """``serve-shardkv --shards 103 --join all`` at 31 replica groups:
    one join, the dict model, a leave and the join back under clerks,
    ``kill -9`` + restart."""
    out = _smoke("--rehearse-cpu", "--legs", "sharded")
    _check_rehearsal(out, {"sharded": "ran"})
    assert "103 shards (crc32), config 1 = ONE join of 31 groups" in out.stdout
    assert "shards a group {4: 10, 3: 21}, shard.slots=103" in out.stdout
    assert "sharded: leave of 3 groups (12 shards change owner)" in out.stdout
    assert "sharded: join of 3 groups (" in out.stdout
    assert "porcupine ok over 48 concurrent" in out.stdout
    assert "after kill -9 + restart: 100 sampled Gets match the model" in out.stdout
    assert "present exactly once after restart" in out.stdout
    assert "SIGTERM -> final checkpoint, exit 0" in out.stdout


@pytest.mark.slow
def test_rehearsal_mesh4_leg_on_four_virtual_devices():
    out = _smoke("--rehearse-cpu", "--legs", "mesh4")
    _check_rehearsal(out, {"mesh4": "ran"})
    assert "consensus state spread over 4 device(s)" in out.stdout
    assert "collective detector trips on a psum" in out.stdout


def test_result_line_has_the_contract_keys_and_no_other():
    """What a full chip run prints last: exactly "ok" and "device", the
    device exactly platform/kind/count, from the line a child printed."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    dev = smoke.note_device(
        "device platform=tpu device_kind=TPU v5 lite devices=1")
    assert json.loads(smoke.result_line(dev)) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }


def test_without_a_tpu_it_fails_with_a_reason_and_no_result():
    """No flag = the chip run.  Here JAX_PLATFORMS=cpu: the first child
    cannot claim a TPU, nothing carries on on the CPU, and no result
    line is printed."""
    out = _smoke(timeout=120)
    assert out.returncode != 0
    reason = out.stderr.strip().splitlines()[-1]
    assert reason.startswith("chip_smoke: FAILED: tick:")
    assert "could not initialise platform tpu" in reason
    assert not any(l.startswith("{") for l in out.stdout.splitlines())


def test_serve_kv_pinned_to_a_missing_platform_exits_nonzero():
    out = subprocess.run(
        [sys.executable, "-m", "multiraft_tpu", "serve-kv",
         "--platform", "tpu", "--groups", "4"],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
    )
    assert out.returncode != 0
    assert "ready" not in out.stdout
    assert "could not initialise platform tpu" in out.stderr


def test_bench_without_a_tpu_fails_unless_the_cpu_is_asked_for():
    env = dict(os.environ)
    env.pop("MULTIRAFT_BENCH_PLATFORM", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py")],
        capture_output=True, text=True, timeout=120, cwd=ROOT, env=env,
    )
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "bench: no TPU" in out.stderr


# -- a server child's device comes from its launch spec ----------------------


def test_server_env_carries_the_spec_into_the_child(monkeypatch):
    from multiraft_tpu.distributed.launch import server_env

    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    # No entry: the engine child inherits the launcher's selection.
    assert server_env({"kind": "engine_kv"})["JAX_PLATFORMS"] == "tpu,cpu"
    env = server_env({"kind": "engine_fleet", "platform": "tpu", "device": 2})
    assert env["JAX_PLATFORMS"] == "tpu"
    assert env["TPU_VISIBLE_CHIPS"] == "2"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    # The pure-Python kinds never run a tick: CPU, whatever the spec.
    env = server_env({"kind": "shardkv", "platform": "tpu", "device": 1})
    assert env["JAX_PLATFORMS"] == "cpu" and "TPU_VISIBLE_CHIPS" not in env


def test_engine_child_without_its_platform_fails_readiness_with_the_reason(
    monkeypatch,
):
    """Asking for the chip and not getting it is an error the launcher
    reports in seconds — not a CPU fallback, not a 300 s hang."""
    import time

    from multiraft_tpu.distributed.engine_cluster import EngineProcessCluster

    monkeypatch.setenv("MRT_ENGINE_PLATFORM", "tpu")
    cluster = EngineProcessCluster(kind="engine_kv", groups=4)
    t0 = time.monotonic()
    try:
        with pytest.raises(RuntimeError, match="could not initialise platform tpu"):
            cluster.start()
    finally:
        cluster.shutdown()
    assert time.monotonic() - t0 < 60.0


# -- the compile cache helper ------------------------------------------------


def _cache_probe(env_value):
    """What a fresh process ends up with after enable_compile_cache()."""
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    code = (
        "import json, os, jax\n"
        "before = jax.config.jax_compilation_cache_dir\n"
        "from multiraft_tpu.utils.jaxcache import enable_compile_cache\n"
        "ret = enable_compile_cache()\n"
        "from jax._src import lru_cache\n"
        "print(json.dumps({'ret': ret, 'before': before,\n"
        "  'after': jax.config.jax_compilation_cache_dir,\n"
        "  'env': os.environ.get('JAX_COMPILATION_CACHE_DIR'),\n"
        "  'atomic': lru_cache.LRUCache._mrt_atomic_put}))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=ROOT, env=env, check=True,
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cache_env_set_means_code_sets_nothing(tmp_path):
    got = _cache_probe(str(tmp_path))
    # jax read the variable itself; the helper changed nothing.
    assert got["before"] == got["after"] == got["ret"] == str(tmp_path)
    assert got["env"] == str(tmp_path)
    assert got["atomic"] is True


def test_cache_env_unset_means_checkout_dot_jax_cache():
    got = _cache_probe(None)
    want = os.path.join(ROOT, ".jax_cache")
    assert got["before"] is None
    assert got["after"] == got["ret"] == want
    assert got["env"] == want  # children inherit the same directory


# -- native libraries are named by what they were built from -----------------


def test_native_library_name_follows_source_and_flags(tmp_path):
    from multiraft_tpu.utils.native_build import build_and_load

    src = tmp_path / "one.cpp"
    so = str(tmp_path / "libone.so")
    src.write_text('extern "C" int answer() { return 1; }\n')
    assert build_and_load(str(src), so).answer() == 1
    first = sorted(p.name for p in tmp_path.glob("libone*.so"))
    assert len(first) == 1 and first[0] != "libone.so"
    # Same source, same flags: the same file, no rebuild.
    mtime = (tmp_path / first[0]).stat().st_mtime_ns
    build_and_load(str(src), so)
    assert (tmp_path / first[0]).stat().st_mtime_ns == mtime
    # A stale binary under the new name's stem is never loaded: change
    # the source and the library that comes back is the new one, even
    # though the old file is NEWER than the source by mtime.
    src.write_text('extern "C" int answer() { return 2; }\n')
    os.utime(src, ns=(0, 0))
    assert build_and_load(str(src), so).answer() == 2
    second = sorted(p.name for p in tmp_path.glob("libone*.so"))
    assert len(second) == 1 and second != first
    # Other flags, another binary.
    build_and_load(str(src), so, extra_flags=["-DX=1"])
    assert sorted(p.name for p in tmp_path.glob("libone*.so")) != second
