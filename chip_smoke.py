"""Chip smoke: the quickest proof that the system still starts on the TPU.

    python chip_smoke.py                 # on a machine with a TPU
    python chip_smoke.py --rehearse-cpu  # tiny shapes on the CPU, to debug
                                         # the script before spending chip time

Drives the system's main paths once, through the entry points a user
calls, at the size BASELINE.json names (10k Raft groups), and checks
that what comes out is right by the repo's own means.  It times
nothing for the record: the seconds it prints are smoke output.

The parent never initialises a JAX backend — a chip belongs to one
process, so every leg that needs it is a child process, run one after
the other.  Each child says which device it holds; unless rehearsing,
anything but a TPU fails the run.  Legs, in order:

``tick``    the consensus tick the servers run (jnp reductions,
            membership on) at G=10,000 x P=3 on the device, and a bit
            parity check of the Pallas kernels (compiled) against the
            jnp path, membership off on both, from one seed.
``bench``   ``python bench.py`` at its own default shapes (10,000 x 3,
            and config5 at 100,000 x 5), Pallas kernels compiled,
            repetitions cut by the variables it already reads.
``served``  ``python -m multiraft_tpu serve-kv --groups 10000`` as a
            child; this process, over real sockets, loads 100,000 keys
            of ~100 B, reads a sample back against a dict model, runs
            concurrent Append/Get clerks on shared keys and checks the
            history with porcupine, checks that the server took the
            fused pump (``pump.fetch_s`` has samples: a silent fall
            back to the synchronous pump fails), then ``kill -9``,
            restart on the same --data-dir, every sampled acknowledged write read back
            exactly once, and a SIGTERM that must exit 0.
``served5`` the same served path with ``--replicas 5`` (BASELINE.json
            config 5's replica count: 50,000 replicas' state), and
            then a start with ``--replicas 3`` on that --data-dir,
            which must refuse by name and serve nothing.
``sharded`` ``serve-shardkv --groups 10000 --shards 33330 --join all``
            (BASELINE.json config 3's shape, 10 shards : 3 groups, at
            9,999 replica groups): the bootstrap is ONE join (config 1,
            3,333 groups of four shards and 6,666 of three, 33,330 live
            slots); 100,000 keys loaded through ``FirehoseClerk`` and
            read back against the plain reference's dict model
            (``harness/shardref.py``); a ``leave`` of 100 groups and
            their ``join`` back under concurrent Append/Get clerks on
            keys of shards that move (porcupine), timed from the
            operation to ``shard.slots`` settled, with the counters each
            grew; ``kill -9``, restart, every acknowledged write read
            back once; SIGTERM exits 0.
``mesh4``   the sharded tick on a 4-device ``groups`` mesh and the same
            served path with ``--mesh-devices 4``.  Runs when the
            chip-holding child reports >= 4 devices; the output says in
            words whether it ran.

Any failed leg makes the exit code non-zero.  A run that passed prints
``summary {"legs": {...}, ..., "claim": null}`` (this script measures
nothing a PR could claim); a full run on the chip then ends stdout with
exactly ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}``, the device as the chip-holding children reported it.
A rehearsal or a ``--legs`` subset never prints that last line, so
neither can pass for the real thing.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
LEGS = ("tick", "bench", "served", "served5", "sharded", "mesh4")
BUDGET_S = 1150.0  # the contract allows 1200 s, compilation included

_T0 = time.monotonic()
_children: List[subprocess.Popen] = []
_claimed: List[Dict[str, Any]] = []  # devices children said they hold
_DEVICE_RE = re.compile(r"platform=(\S+) device_kind=(.*) devices=(\d+)$")


class LegFailed(Exception):
    """One leg's check did not hold; the message is the reason."""


def say(msg: str) -> None:
    print(f"[{time.monotonic() - _T0:7.1f}s] {msg}", flush=True)


def require(cond: bool, why: str) -> None:
    if not cond:
        raise LegFailed(why)


def remaining(cap: float) -> float:
    left = BUDGET_S - (time.monotonic() - _T0)
    require(left > 5.0, f"out of time: {BUDGET_S:.0f}s budget spent")
    return min(cap, left)


# ---------------------------------------------------------------------------
# Sizes: the real ones, and the rehearsal's
# ---------------------------------------------------------------------------


def sizes(rehearse: bool) -> Dict[str, Any]:
    if rehearse:
        return dict(
            G=32, ticks=80, keys=2000, sample=100, served_G=32,
            bench_env=dict(
                MULTIRAFT_BENCH_PLATFORM="cpu",
                MULTIRAFT_BENCH_PALLAS="1",  # interpret mode off the chip
                MULTIRAFT_BENCH_G="16",
                MULTIRAFT_BENCH_CHUNK="40",
                MULTIRAFT_BENCH_SAMPLE="6",
                MULTIRAFT_BENCH_FAULTS="4",
                MULTIRAFT_BENCH_CONFIG5_G="20",
                MULTIRAFT_BENCH_CONFIG5_CHUNK="40",
            ),
        )
    # Shapes are never cut (bench.py's own defaults apply); only
    # repetitions are, below.
    return dict(
        G=10_000, ticks=200, keys=100_000, sample=1000, served_G=10_000,
        bench_env={},
    )


# ---------------------------------------------------------------------------
# Child processes
# ---------------------------------------------------------------------------


def child_env(rehearse: bool, extra: Optional[Dict[str, str]] = None):
    env = dict(os.environ)
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4"
            ).strip()
    env.update(extra or {})
    return env


def run_child(name: str, argv: List[str], env, cap_s: float) -> Dict[str, Any]:
    """Run one chip-holding child to its end, passing its output
    through, and return the JSON object on its last stdout line."""
    say(f"{name}: start {' '.join(argv)}")
    proc = subprocess.Popen(
        [sys.executable, *argv], cwd=ROOT, env=env, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
    )
    _children.append(proc)
    lines: List[str] = []

    def pump() -> None:
        for line in proc.stdout:
            line = line.rstrip("\n")
            lines.append(line)
            note_device(line)
            print(f"    {name}| {line}", flush=True)

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    try:
        rc = proc.wait(timeout=remaining(cap_s))
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        raise LegFailed(f"{name}: still running after its time limit; killed")
    t.join(timeout=10.0)
    require(rc == 0, f"{name}: exit code {rc}: {lines[-1] if lines else ''}")
    last = next((l for l in reversed(lines) if l.startswith("{")), None)
    require(last is not None, f"{name}: printed no JSON result")
    return json.loads(last)


def note_device(text: str) -> Optional[Dict[str, Any]]:
    """Pick up the one line every entry point prints about its device."""
    m = _DEVICE_RE.search(text)
    if m is None:
        return None
    dev = {"platform": m.group(1), "kind": m.group(2), "count": int(m.group(3))}
    _claimed.append(dev)
    return dev


def result_line(dev: Dict[str, Any]) -> str:
    """The last line of a full chip run: these keys and no other."""
    return json.dumps({"ok": True, "device": {
        "platform": str(dev["platform"]), "kind": str(dev["kind"]),
        "count": int(dev["count"]),
    }})


def check_device(name: str, dev: Dict[str, Any], rehearse: bool) -> None:
    say(
        f"{name}: holds platform={dev['platform']} "
        f"device_kind={dev['kind']} devices={dev['count']}"
    )
    if not rehearse:
        require(
            dev["platform"] == "tpu",
            f"{name}: ran on {dev['platform']}, not on a TPU",
        )


# ---------------------------------------------------------------------------
# Leg: tick (this file, as a child that holds the chip)
# ---------------------------------------------------------------------------


def child_claim(rehearse: bool) -> Dict[str, Any]:
    """First thing every ``--child``: claim the device and say which."""
    from multiraft_tpu.utils.device import claim_device, device_line

    try:
        dev = claim_device("" if rehearse else "tpu")
    except RuntimeError as exc:
        sys.exit(f"error: {exc}")
    print(f"device {device_line(dev)}", flush=True)
    return dev


def child_tick(ns) -> int:
    dev = child_claim(ns.rehearse_cpu)
    import dataclasses

    import jax
    import jax.numpy as jnp

    from multiraft_tpu.engine.core import (
        EngineConfig, empty_mailbox, init_state, run_ticks,
    )
    from multiraft_tpu.engine.state_planes import content_fingerprint

    on_tpu = dev["platform"] == "tpu"
    G, N = ns.groups, ns.ticks
    shape = dict(G=G, P=3, L=192, E=48, INGEST=48, HB_TICKS=9)
    key = jax.random.PRNGKey(ns.seed)

    def readback(state) -> Tuple[int, int]:
        leaders = int(jnp.sum((state.role == 2) & state.alive))
        return leaders, int(jnp.sum(jnp.max(state.commit, axis=1)))

    def drive(cfg: EngineConfig, label: str):
        """N loaded ticks from the seed (elections, then commits), then
        N more, timed; returns the content fingerprints."""
        state, inbox = init_state(cfg, key), empty_mailbox(cfg)
        t0 = time.perf_counter()
        state, inbox = run_ticks(cfg, state, inbox, N, cfg.INGEST, key)
        jax.block_until_ready(state.term)
        first = time.perf_counter() - t0
        readback(state)  # compiles the two small reductions
        t0 = time.perf_counter()
        state, inbox = run_ticks(
            cfg, state, inbox, N, cfg.INGEST, jax.random.fold_in(key, 1)
        )
        jax.block_until_ready(state.term)
        again = time.perf_counter() - t0
        # Is block_until_ready a fence?  A value read back after it has
        # returned must find the work done: nothing left to wait for.
        t0 = time.perf_counter()
        leaders, commits = readback(state)
        residual = time.perf_counter() - t0
        print(
            f"{label}: {2 * N} ticks at G={G} P=3: leaders={leaders}/{G} "
            f"commits={commits}; first {N} ticks {first:.1f}s (compile "
            f"incl.), next {N} {again * 1e3 / N:.3f} ms/tick; fence: "
            f"readback after block_until_ready took {residual * 1e3:.1f} ms",
            flush=True,
        )
        assert leaders == G, f"{label}: {leaders}/{G} groups have a leader"
        assert commits > G, f"{label}: commit frontier did not advance"
        assert residual < max(0.5 * again, 0.05), (
            f"{label}: block_until_ready returned after {again:.3f}s but a "
            f"readback then waited {residual:.3f}s: not a fence"
        )
        return content_fingerprint(state), content_fingerprint(inbox)

    # The tick every server runs: jnp reductions, membership planes on.
    served = EngineConfig(**shape)
    assert served.membership_on and not served.use_pallas
    drive(served, "served tick (jnp, membership on)")

    # Pallas vs jnp, membership off on both: bit parity on the device.
    jnp_cfg = EngineConfig(**shape, membership=False)
    pallas_cfg = dataclasses.replace(
        jnp_cfg, use_pallas=True, pallas_interpret=not on_tpu
    )
    want = drive(jnp_cfg, "jnp tick (membership off)")
    got = drive(
        pallas_cfg,
        "pallas tick (%s)" % ("compiled" if on_tpu else "interpret"),
    )
    assert got == want, f"pallas {got} != jnp {want} (state, mailbox)"
    print(f"parity: pallas == jnp, fingerprints {want}", flush=True)
    print(json.dumps({
        "device": dev,
        "pallas": "compiled" if on_tpu else "interpret",
        "fingerprints": list(want),
    }), flush=True)
    return 0


def leg_tick(rehearse: bool, seed: int) -> Dict[str, Any]:
    sz = sizes(rehearse)
    argv = [
        "chip_smoke.py", "--child", "tick", "--groups", str(sz["G"]),
        "--ticks", str(sz["ticks"]), "--seed", str(seed),
    ] + (["--rehearse-cpu"] if rehearse else [])
    out = run_child("tick", argv, child_env(rehearse), 420.0)
    check_device("tick", out["device"], rehearse)
    if not rehearse:
        require(out["pallas"] == "compiled", "tick: Pallas not compiled")
    return out["device"]


# ---------------------------------------------------------------------------
# Leg: bench.py
# ---------------------------------------------------------------------------


def leg_bench(rehearse: bool) -> Dict[str, Any]:
    sz = sizes(rehearse)
    env = child_env(rehearse, {
        # Repetitions cut; shapes are bench.py's own defaults.
        "MULTIRAFT_BENCH_RUNS": "1",
        "MULTIRAFT_BENCH_CHUNKS": "2",
        "MULTIRAFT_BENCH_CONFIG5_CHUNKS": "2",
        **sz["bench_env"],
    })
    rec = run_child("bench", ["bench.py"], env, 600.0)
    check_device("bench", rec["device"], rehearse)
    want = "_cpu" if rehearse else "_tpu"
    require(rec["metric"].endswith(want), f"bench: metric {rec['metric']}")
    require(
        rec["pallas"] == ("interpret" if rehearse else "compiled"),
        f"bench: Pallas kernels {rec['pallas']}",
    )
    require(rec["porcupine"] == "ok", f"bench: porcupine {rec['porcupine']}")
    require(
        rec["latency_unaccounted"] == 0,
        f"bench: {rec['latency_unaccounted']} unaccounted entries",
    )
    c5 = rec.get("config5")
    require(bool(c5) and "error" not in c5, f"bench: config5 {c5}")
    require(c5["latency_unaccounted"] == 0, "bench: config5 unaccounted")
    say(
        f"bench: {rec['metric']} porcupine=ok, "
        f"{rec['latency_entries_measured']:,} entries accounted; config5 "
        f"{c5['groups']}x{c5['peers']} {c5['ms_per_tick']} ms/tick"
    )
    return rec["device"]


# ---------------------------------------------------------------------------
# Leg: served (server child; this process is the client)
# ---------------------------------------------------------------------------

class Server:
    """One ``serve-kv`` (or ``verb``) child on ``data_dir``."""

    def __init__(self, data_dir: str, err_path: str, rehearse: bool,
                 groups: int, mesh: int, seed: int, replicas: int = 3,
                 verb: str = "serve-kv", extra: Tuple[str, ...] = ()) -> None:
        from multiraft_tpu.distributed.launch import reserve_ports

        self.port = reserve_ports(1, "127.0.0.1")[0]
        self.verb = verb
        argv = [
            sys.executable, "-m", "multiraft_tpu", verb,
            "--platform", "cpu" if rehearse else "tpu",
            "--groups", str(groups), "--data-dir", data_dir,
            "--seed", str(seed), "--port", str(self.port), *extra,
        ] + (["--mesh-devices", str(mesh)] if mesh else []) + (
            ["--replicas", str(replicas)] if replicas != 3 else []
        )
        self.err_path = err_path
        self.t_start = time.monotonic()
        with open(err_path, "w") as err:
            self.proc = subprocess.Popen(
                argv, cwd=ROOT, env=child_env(rehearse), text=True,
                stdout=subprocess.PIPE, stderr=err,
            )
        _children.append(self.proc)
        self.ready_s = 0.0

    def stderr_tail(self) -> str:
        with open(self.err_path) as f:
            return " | ".join(f.read().strip().splitlines()[-3:])

    def wait_ready(self, cap_s: float) -> Dict[str, Any]:
        """Block until the readiness line; returns the device the
        server said (on stderr) that it holds."""
        from multiraft_tpu.distributed.launch import check_ready

        try:
            check_ready(self.proc, self.verb, timeout=remaining(cap_s))
        except RuntimeError as exc:
            raise LegFailed(str(exc)) from None
        self.ready_s = time.monotonic() - self.t_start
        with open(self.err_path) as f:
            devs = [d for d in map(note_device, f.read().splitlines()) if d]
        require(bool(devs), f"{self.verb}: printed no device line")
        return devs[-1]

    def kill9(self) -> None:
        self.proc.kill()
        self.proc.wait()

    def terminate(self, cap_s: float) -> int:
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout=remaining(cap_s))
        except subprocess.TimeoutExpired:
            self.kill9()
            raise LegFailed(f"{self.verb}: did not exit on SIGTERM; killed")


def cache_entries() -> int:
    from multiraft_tpu.utils.jaxcache import cache_dir

    try:
        return sum(1 for n in os.listdir(cache_dir()) if n.endswith("-cache"))
    except FileNotFoundError:
        return 0


def leg_served(
    rehearse: bool, seed: int, mesh: int = 0, replicas: int = 3
) -> Dict[str, Any]:
    import random

    from multiraft_tpu.distributed.engine_cluster import BlockingEngineClerk
    from multiraft_tpu.distributed.engine_server import FirehoseClerk
    from multiraft_tpu.distributed.tcp import RpcNode
    from multiraft_tpu.harness import run_clerk_load
    from multiraft_tpu.porcupine.checker import check_operations
    from multiraft_tpu.porcupine.kv import OP_APPEND, kv_model
    from multiraft_tpu.porcupine.model import CheckResult
    from multiraft_tpu.sim.scheduler import TIMEOUT

    name = "served" + (str(replicas) if replicas != 3 else "")
    if mesh:
        name += f"(mesh={mesh})"
    sz = sizes(rehearse)
    G, n_keys, n_sample = sz["served_G"], sz["keys"], sz["sample"]
    rng = random.Random(seed)
    alphabet = "abcdefghijklmnopqrstuvwxyz0123456789"
    model = {
        f"user{i:08d}": "".join(rng.choices(alphabet, k=100))
        for i in range(n_keys)
    }
    sample = rng.sample(sorted(model), n_sample)
    nodes: List[RpcNode] = []

    def run(node: RpcNode, gen, cap_s: float):
        out = node.sched.wait(node.sched.spawn(gen), remaining(cap_s))
        require(out is not TIMEOUT, f"{name}: the server did not answer")
        return out

    def connect(server: Server) -> Tuple[RpcNode, Any]:
        node = RpcNode()
        nodes.append(node)
        return node, node.client_end("127.0.0.1", server.port)

    def snapshot(node: RpcNode, end) -> Dict[str, Any]:
        snap = node.sched.wait(end.call("Obs.snapshot", None), 30.0)
        require(isinstance(snap, dict), f"{name}: Obs.snapshot said {snap!r}")
        return snap["metrics"]

    def read_sample(node: RpcNode, end, what: str) -> None:
        got = run(
            node,
            FirehoseClerk(node.sched, end).run_batch(
                [("Get", k, "") for k in sample], deadline_s=120.0
            ),
            150.0,
        )
        bad = [k for k, v in zip(sample, got) if v != model[k]]
        require(not bad, f"{name}: {what}: {len(bad)} keys differ: {bad[:3]}")
        say(f"{name}: {what}: {len(sample)} sampled Gets match the model")

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        data_dir = os.path.join(tmp, "data")
        mk = lambda i, replicas=replicas: Server(
            data_dir, os.path.join(tmp, f"server{i}.err"), rehearse, G,
            mesh, seed, replicas,
        )
        srv = None
        try:
            # -- first start: cold ------------------------------------
            entries0 = cache_entries()
            srv = mk(1)
            dev = srv.wait_ready(420.0)
            check_device(name, dev, rehearse)
            entries1 = cache_entries()
            say(
                f"{name}: set-up: first start ready in {srv.ready_s:.1f}s, "
                f"compile cache {entries0} -> {entries1} entries"
            )
            node, end = connect(srv)
            info = node.sched.wait(end.call("EngineKV.info", None), 30.0)
            require(isinstance(info, dict), f"{name}: info said {info!r}")
            require(info["G"] == G, f"{name}: serves G={info['G']}")
            require(
                info["P"] == replicas,
                f"{name}: serves P={info['P']}, asked for {replicas}",
            )
            require(
                info["state_devices"] == (mesh or 1),
                f"{name}: state on {info['state_devices']} device(s), "
                f"expected {mesh or 1}",
            )
            say(
                f"{name}: G={G} x P={replicas}, consensus state spread over "
                f"{info['state_devices']} device(s)"
            )
            before = snapshot(node, end)

            # -- load, read back --------------------------------------
            t0 = time.monotonic()
            run(
                node,
                FirehoseClerk(node.sched, end).run_batch(
                    [("Put", k, v) for k, v in model.items()],
                    deadline_s=240.0,
                ),
                300.0,
            )
            say(
                f"{name}: loaded {n_keys} keys x 100 B in "
                f"{time.monotonic() - t0:.1f}s"
            )
            read_sample(node, end, "after load")

            # -- concurrent clerks on shared keys, porcupine ----------
            # Per-op clerks (the `command` RPC): 3 workers alternate
            # uniquely tagged Appends and Gets on two fresh keys.
            shared = ["shared0", "shared1"]
            history = run_clerk_load(
                lambda: BlockingEngineClerk(srv.port), shared,
                n_workers=3, ops_per_worker=12, op_timeout=60.0,
            )
            acked = {
                key: [
                    op.input.value for op in history
                    if op.input.op == OP_APPEND and op.input.key == key
                ]
                for key in shared
            }
            verdict = check_operations(kv_model, history, timeout=60.0)
            require(
                verdict is CheckResult.OK,
                f"{name}: porcupine says {verdict.value} over "
                f"{len(history)} ops",
            )
            say(
                f"{name}: porcupine ok over {len(history)} concurrent "
                f"Append/Get ops from 3 clerks"
            )

            # -- the pump ran, ticks advanced --------------------------
            after = snapshot(node, end)
            require(after.get("pump.count", 0) > 0, f"{name}: pump never ran")
            require(
                after.get("ticks", 0) > before.get("ticks", 0),
                f"{name}: ticks did not advance",
            )
            # The fused, asynchronous pump is what a server runs, on one
            # chip or on a mesh: only it fetches on the pump thread.
            require(
                after.get("pump.fetch_s_count", 0) > 0,
                f"{name}: no pump.fetch_s sample: the server fell back "
                f"to the synchronous pump",
            )
            say(
                f"{name}: pump.count={after['pump.count']} "
                f"pump.fetch_s_count={after['pump.fetch_s_count']} "
                f"ticks {before.get('ticks', 0)} -> {after['ticks']} "
                f"wal.fsyncs={after.get('wal.fsyncs', 'n/a')}"
            )

            # -- kill -9, restart on the same dir: durability ----------
            srv.kill9()
            srv = mk(2)
            dev2 = srv.wait_ready(420.0)
            check_device(f"{name} restart", dev2, rehearse)
            entries2 = cache_entries()
            say(
                f"{name}: set-up: kill -9 + restart ready in "
                f"{srv.ready_s:.1f}s (checkpoint + WAL replay), compile "
                f"cache {entries1} -> {entries2} entries"
            )
            node, end = connect(srv)
            read_sample(node, end, "after kill -9 + restart")
            ck = BlockingEngineClerk(srv.port)
            nodes.append(ck.node)
            for key, tags in acked.items():
                val = ck.get(key, timeout=60.0)
                wrong = [t for t in tags if val.count(t) != 1]
                require(
                    not wrong,
                    f"{name}: acked appends not exactly once in "
                    f"{key}={val!r}: {wrong}",
                )
                require(
                    len(val) == sum(map(len, tags)),
                    f"{name}: {key} holds more than the acked appends",
                )
            ck.append("shared0", "(after)", timeout=60.0)
            val = ck.get("shared0", timeout=60.0)
            require(
                val.endswith("(after)") and val.count("(after)") == 1,
                f"{name}: append after restart reads {val!r}",
            )
            say(
                f"{name}: every acknowledged append present exactly once "
                f"after restart; a new append applied once"
            )

            # -- SIGTERM: final checkpoint, exit 0 ---------------------
            rc = srv.terminate(240.0)
            require(rc == 0, f"{name}: exit {rc} on SIGTERM")
            require(
                os.path.exists(os.path.join(data_dir, "engine.ckpt")),
                f"{name}: no engine.ckpt after SIGTERM",
            )
            say(f"{name}: SIGTERM -> final checkpoint, exit 0")

            # -- another replica count on that dir: refused by name ----
            if replicas != 3:
                srv = mk(3, replicas=3)
                try:
                    rc = srv.proc.wait(timeout=remaining(240.0))
                except subprocess.TimeoutExpired:
                    raise LegFailed(
                        f"{name}: --replicas 3 on a --data-dir written "
                        f"at {replicas} did not refuse"
                    ) from None
                said = srv.stderr_tail()
                require(
                    rc != 0 and f"{replicas} replicas" in said
                    and "asked for 3" in said,
                    f"{name}: --replicas 3 on that --data-dir: exit {rc}",
                )
                require(
                    "ready" not in srv.proc.stdout.read(),
                    f"{name}: the refused server said it was ready",
                )
                say(f"{name}: --replicas 3 on the same --data-dir refused: "
                    f"{said.rsplit(' | ', 1)[-1][:160]}")
        except (LegFailed, TimeoutError) as exc:  # a blocking clerk gave up
            tail = srv.stderr_tail() if srv is not None else ""
            raise LegFailed(f"{exc} [server stderr: {tail}]") from None
        finally:
            for n in nodes:
                n.close()
    return dev


# ---------------------------------------------------------------------------
# Leg: sharded
# ---------------------------------------------------------------------------


def leg_sharded(rehearse: bool, seed: int) -> Dict[str, Any]:
    import random
    from collections import Counter

    from multiraft_tpu.distributed.engine_cluster import BlockingEngineClerk
    from multiraft_tpu.distributed.engine_server import FirehoseClerk
    from multiraft_tpu.distributed.tcp import RpcNode
    from multiraft_tpu.harness import run_clerk_load
    from multiraft_tpu.harness.shardref import ShardRef
    from multiraft_tpu.porcupine.checker import check_operations
    from multiraft_tpu.porcupine.kv import OP_APPEND, kv_model
    from multiraft_tpu.porcupine.model import CheckResult
    from multiraft_tpu.services.shardctrler import ShardSpace
    from multiraft_tpu.sim.scheduler import TIMEOUT

    name, service = "sharded", "EngineShardKV"
    sz = sizes(rehearse)
    G, n_keys, n_sample = sz["served_G"], sz["keys"], sz["sample"]
    n_shards = (G - 1) * 10 // 3       # the source's 10 shards : 3 groups
    leaving = list(range(1, max(3, G // 100) + 1))        # 100 of 9,999
    space = ShardSpace.of(n_shards)
    rng = random.Random(seed)
    alphabet = "abcdefghijklmnopqrstuvwxyz0123456789"
    ref = ShardRef(n_shards, space.shard_of)   # the dict model, by shard
    for i in range(n_keys):
        ref.put(f"user{i:012d}", "".join(rng.choices(alphabet, k=100)))
    model = ref.items()
    sample = rng.sample(sorted(model), n_sample)
    nodes: List[RpcNode] = []

    def run(node: RpcNode, gen, cap_s: float):
        out = node.sched.wait(node.sched.spawn(gen), remaining(cap_s))
        require(out is not TIMEOUT, f"{name}: the server did not answer")
        return out

    def call(node: RpcNode, end, verb: str, args=None, cap_s: float = 60.0):
        out = node.sched.wait(end.call(verb, args), remaining(cap_s))
        require(out is not TIMEOUT and out is not None,
                f"{name}: {verb} said {out!r}")
        return out

    def connect(server: Server) -> Tuple[RpcNode, Any]:
        node = RpcNode()
        nodes.append(node)
        return node, node.client_end("127.0.0.1", server.port)

    def read_sample(node: RpcNode, end, what: str) -> None:
        got = run(
            node,
            FirehoseClerk(node.sched, end, service).run_batch(
                [("Get", k, "") for k in sample], deadline_s=120.0
            ),
            150.0,
        )
        bad = [k for k, v in zip(sample, got) if v != ref.get(k)]
        require(not bad, f"{name}: {what}: {len(bad)} keys differ: {bad[:3]}")
        say(f"{name}: {what}: {len(sample)} sampled Gets match the model")

    def reconfigure(node, end, kind: str, cmd: int, owners: List[int]):
        """One admin operation, then poll until the migration it starts
        is over: every group has applied the config, every shard whose
        owner changed was pulled, inserted, deleted at its old owner and
        confirmed, and the live slots number the shards again.  Returns
        the new config's owners."""
        before = call(node, end, "Obs.snapshot")["metrics"]
        t0 = time.monotonic()
        reply = call(node, end, f"{service}.admin", (kind, leaving, cmd))
        require(reply.err == "OK", f"{name}: {kind} said {reply.err}")
        acked = time.monotonic() - t0
        _, owners_now, groups_now = call(node, end, f"{service}.config")
        shards_moving = sum(1 for a, b in zip(owners, owners_now) if a != b)
        require(
            (set(leaving) <= set(groups_now)) == (kind == "join")
            and shards_moving >= len(leaving),
            f"{name}: {kind}: {shards_moving} shards change owner",
        )
        grew: Dict[str, float] = {}
        while True:
            now = call(node, end, "Obs.snapshot")["metrics"]
            grew = {k: now[k] - before.get(k, 0) for k in sorted(now)
                    if k.startswith("shard.") and not k.endswith(("_p50", "_p99"))
                    and now[k] != before.get(k, 0)}
            if (now["shard.slots"] == n_shards
                    and grew.get("shard.config_applies", 0) == G - 1
                    and grew.get("shard.confirms", 0) == shards_moving):
                break
            require(time.monotonic() - t0 < remaining(300.0),
                    f"{name}: {kind} of {len(leaving)} groups did not settle: {grew}")
            time.sleep(0.25)
        settled = time.monotonic() - t0
        require(grew.get("shard.deletes") == shards_moving
                and grew.get("shard.inserts") == shards_moving,
                f"{name}: {kind}: {grew}")
        say(f"{name}: {kind} of {len(leaving)} groups ({shards_moving} "
            f"shards change owner): acknowledged in {acked:.2f}s, "
            f"shard.slots settled {settled:.2f}s after the operation; grew "
            f"{json.dumps(grew)}")
        return owners_now

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        data_dir = os.path.join(tmp, "data")
        mk = lambda i: Server(
            data_dir, os.path.join(tmp, f"server{i}.err"), rehearse, G, 0,
            seed, verb="serve-shardkv",
            extra=("--shards", str(n_shards), "--join", "all"),
        )
        srv = None
        try:
            srv = mk(1)
            dev = srv.wait_ready(420.0)
            check_device(name, dev, rehearse)
            node, end = connect(srv)
            info = call(node, end, f"{service}.info")
            require(
                (info["G"], info["P"], info["shards"], info["partitioner"])
                == (G, 3, n_shards, "crc32"),
                f"{name}: info said {info}",
            )
            m = call(node, end, "Obs.snapshot")["metrics"]
            require(
                (m["shard.count"], m["shard.slots"], m["shard.config_num"])
                == (n_shards, n_shards, 1),
                f"{name}: after the bootstrap join the gauges read "
                f"{ {k: v for k, v in m.items() if k.startswith('shard.')} }",
            )
            num, owners, groups = call(node, end, f"{service}.config")
            per_group = Counter(Counter(owners).values())
            require(
                num == 1 and len(groups) == G - 1
                and set(per_group) <= {3, 4} and 0 not in owners,
                f"{name}: config {num}: {len(groups)} groups, shards a "
                f"group {dict(per_group)}",
            )
            if G <= 1000:   # small enough for the reference's plain loops
                ref.join(range(1, G))
                require(owners == ref.owner,
                        f"{name}: the owners are not the reference's")
            say(
                f"{name}: first start ready in {srv.ready_s:.1f}s "
                f"(ready.join_s={m['ready.join_s']:.2f}); G={G} x P=3, "
                f"{n_shards} shards ({info['partitioner']}), config 1 = ONE "
                f"join of {G - 1} groups, shards a group {dict(per_group)}, "
                f"shard.slots={int(m['shard.slots'])}"
            )

            # -- load, read back against the dict model ----------------
            t0 = time.monotonic()
            run(
                node,
                FirehoseClerk(node.sched, end, service).run_batch(
                    [("Put", k, v) for k, v in model.items()],
                    deadline_s=240.0,
                ),
                300.0,
            )
            say(f"{name}: loaded {n_keys} keys x 100 B in "
                f"{time.monotonic() - t0:.1f}s")
            read_sample(node, end, "after load")

            # -- leave and join back, under clerk traffic --------------
            # Keys of shards the leaving groups own, so that they move.
            gone = set(leaving)
            moving = [s for s, g in enumerate(owners) if g in gone]
            shared: List[str] = []
            i = 0
            while len(shared) < 2:
                if owners[space.shard_of(f"shared{i}")] in gone:
                    shared.append(f"shared{i}")
                i += 1
            shared.append("shared-at-rest")
            history: List[Any] = []
            worker = threading.Thread(
                target=lambda: history.extend(run_clerk_load(
                    lambda: BlockingEngineClerk(srv.port, service=service),
                    shared, n_workers=3,
                    ops_per_worker=16 if rehearse else 60,
                    op_timeout=120.0,
                )),
                daemon=True,
            )
            worker.start()
            owners2 = reconfigure(node, end, "leave", 2, owners)
            require(not gone & set(owners2),
                    f"{name}: a group that left still owns a shard")
            back = sum(1 for a, b in zip(owners, owners2) if a != b)
            require(back == len(moving),
                    f"{name}: the leave moved {back} shards, not the "
                    f"{len(moving)} the leaving groups held")
            owners3 = reconfigure(node, end, "join", 3, owners2)
            if G <= 1000:
                ref.leave(leaving)
                ref.join(leaving)
                require(owners3 == ref.owner,
                        f"{name}: after the leave and the join back the "
                        f"owners are not the reference's")
            worker.join(remaining(240.0))
            require(not worker.is_alive(), f"{name}: the clerks never finished")
            acked = {
                key: [
                    op.input.value for op in history
                    if op.input.op == OP_APPEND and op.input.key == key
                ]
                for key in shared
            }
            verdict = check_operations(kv_model, history, timeout=60.0)
            require(
                verdict is CheckResult.OK,
                f"{name}: porcupine says {verdict.value} over "
                f"{len(history)} ops",
            )
            say(f"{name}: porcupine ok over {len(history)} concurrent "
                f"Append/Get ops from 3 clerks through both migrations")
            read_sample(node, end, "after the leave and the join back")
            m = call(node, end, "Obs.snapshot")["metrics"]
            require(m["shard.config_num"] == 3 and m["shard.slots"] == n_shards,
                    f"{name}: config {m['shard.config_num']}, "
                    f"{m['shard.slots']} slots")

            # -- kill -9, restart on the same dir ----------------------
            srv.kill9()
            srv = mk(2)
            dev2 = srv.wait_ready(420.0)
            check_device(f"{name} restart", dev2, rehearse)
            say(f"{name}: kill -9 + restart ready in {srv.ready_s:.1f}s "
                f"(checkpoint + WAL replay)")
            node, end = connect(srv)
            m = call(node, end, "Obs.snapshot")["metrics"]
            require(m["shard.config_num"] == 3 and m["shard.slots"] == n_shards,
                    f"{name}: after restart config {m['shard.config_num']}, "
                    f"{m['shard.slots']} slots: a restart re-ran the "
                    f"bootstrap or lost a migration")
            read_sample(node, end, "after kill -9 + restart")
            ck = BlockingEngineClerk(srv.port, service=service)
            nodes.append(ck.node)
            for key, tags in acked.items():
                val = ck.get(key, timeout=60.0)
                wrong = [t for t in tags if val.count(t) != 1]
                require(
                    not wrong and len(val) == sum(map(len, tags)),
                    f"{name}: acked appends not exactly once in "
                    f"{key}={val!r}: {wrong}",
                )
            say(f"{name}: every acknowledged append present exactly once "
                f"after restart")
            rc = srv.terminate(240.0)
            require(rc == 0, f"{name}: exit {rc} on SIGTERM")
            say(f"{name}: SIGTERM -> final checkpoint, exit 0")
        except (LegFailed, TimeoutError) as exc:
            tail = srv.stderr_tail() if srv is not None else ""
            raise LegFailed(f"{exc} [server stderr: {tail}]") from None
        finally:
            for n in nodes:
                n.close()
    return dev


# ---------------------------------------------------------------------------
# Leg: mesh4
# ---------------------------------------------------------------------------


def child_mesh(ns) -> int:
    dev = child_claim(ns.rehearse_cpu)
    if dev["count"] < 4:
        print(json.dumps({"device": dev, "ran": False}), flush=True)
        return 0
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from multiraft_tpu.distributed.engine_wire import make_mesh
    from multiraft_tpu.engine.core import EngineConfig
    from multiraft_tpu.engine.host import EngineDriver
    from multiraft_tpu.engine.mesh import assert_zero_collectives

    G = ns.groups
    mesh = make_mesh(4)
    # The shape serve-kv builds; construction compiles the shard_map
    # tick and asserts its HLO holds no collective.
    d = EngineDriver(EngineConfig(G=G, P=3, L=64, E=8, INGEST=8),
                     seed=ns.seed, mesh=mesh)
    assert d.run_until_quiet_leaders(2000), "mesh tick elected no leaders"

    def spread(x, what: str) -> None:
        shards = x.addressable_shards
        devs = {s.device for s in shards}
        shapes = {tuple(s.data.shape) for s in shards}
        assert devs == set(mesh.devices.flat), f"{what}: on {devs}"
        assert shapes == {(G // 4,) + tuple(x.shape[1:])}, f"{what}: {shapes}"

    spread(d.state.term, "state.term after ticks")
    spread(d.inbox.ar_terms, "inbox.ar_terms after ticks")
    print(
        f"mesh: state.term {d.state.term.shape} held as 4 shards of "
        f"{(G // 4, 3)} on {sorted(str(x) for x in mesh.devices.flat)}",
        flush=True,
    )

    # Does the HLO text search still mean what it says on this backend?
    # A step that does hold a collective must trip it.
    summed = jax.jit(shard_map(
        lambda x: jax.lax.psum(jnp.sum(x), "groups"),
        mesh=mesh, in_specs=P("groups"), out_specs=P(),
    ))
    try:
        assert_zero_collectives(summed, d.state.term)
    except AssertionError as exc:
        print(f"mesh: collective detector trips on a psum: {exc}", flush=True)
    else:
        raise AssertionError(
            "assert_zero_collectives passed a step that holds a psum: "
            "this backend's HLO text names collectives differently"
        )
    print(json.dumps({"device": dev, "ran": True}), flush=True)
    return 0


def leg_mesh4(rehearse: bool, seed: int) -> Tuple[Dict[str, Any], str]:
    sz = sizes(rehearse)
    argv = [
        "chip_smoke.py", "--child", "mesh", "--groups", str(sz["served_G"]),
        "--seed", str(seed),
    ] + (["--rehearse-cpu"] if rehearse else [])
    out = run_child("mesh4", argv, child_env(rehearse), 300.0)
    check_device("mesh4", out["device"], rehearse)
    if not out["ran"]:
        return out["device"], f"not run, {out['device']['count']} device"
    return leg_served(rehearse, seed, mesh=4), "ran"


# ---------------------------------------------------------------------------


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny shapes on the CPU, Pallas interpreted; the "
                         "output is marked 'rehearsal'")
    ap.add_argument("--legs", default=",".join(LEGS),
                    help=f"comma-separated subset of {','.join(LEGS)} "
                         "(a partial run is marked as such)")
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--child", choices=("tick", "mesh"), help=argparse.SUPPRESS)
    ap.add_argument("--groups", type=int, help=argparse.SUPPRESS)
    ap.add_argument("--ticks", type=int, help=argparse.SUPPRESS)
    ns = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "multiraft_tpu")):
        print("chip_smoke: the multiraft_tpu package is not next to this "
              "script; nothing to run", file=sys.stderr)
        return 1
    if ns.child:
        return {"tick": child_tick, "mesh": child_mesh}[ns.child](ns)

    legs = [l for l in ns.legs.split(",") if l]
    unknown = sorted(set(legs) - set(LEGS))
    if unknown:
        ap.error(f"unknown legs {unknown}")
    rehearse = ns.rehearse_cpu
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))

    from multiraft_tpu.distributed.native import native_available as tr_native
    from multiraft_tpu.porcupine.native import native_available as po_native

    say(f"native: transport={tr_native()} porcupine={po_native()} "
        "(built here from the committed .cpp)")
    if not po_native():
        print("chip_smoke: FAILED: the porcupine native core fell back to "
              "Python", file=sys.stderr)
        return 1
    device: Optional[Dict[str, Any]] = None
    report: Dict[str, str] = {}
    failures: List[str] = []
    try:
        for leg in legs:
            try:
                if leg == "tick":
                    device = leg_tick(rehearse, ns.seed)
                elif leg == "bench":
                    device = leg_bench(rehearse)
                elif leg == "served":
                    device = leg_served(rehearse, ns.seed)
                elif leg == "served5":
                    device = leg_served(rehearse, ns.seed, replicas=5)
                elif leg == "sharded":
                    device = leg_sharded(rehearse, ns.seed)
                else:
                    if _claimed and _claimed[-1]["count"] < 4:
                        # An earlier chip-holding child already said how
                        # many devices there are: no child to find out.
                        n = _claimed[-1]["count"]
                        report["mesh4"] = f"not run, {n} device"
                    else:
                        device, report["mesh4"] = leg_mesh4(rehearse, ns.seed)
                    say(f"mesh4: {report['mesh4']}")
                    continue
                report[leg] = "ran"
            except LegFailed as exc:
                say(f"FAILED {exc}")
                failures.append(str(exc))
                report[leg] = "failed"
                if not _claimed:
                    break  # no child ever held a device: stop here
    finally:
        for proc in _children:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        if "jax" in sys.modules:
            from jax._src import xla_bridge

            assert not xla_bridge.backends_are_initialized(), (
                "the chip_smoke parent initialised a JAX backend"
            )
    if failures or device is None:
        print(f"chip_smoke: FAILED: {'; '.join(failures) or 'no leg ran'}",
              file=sys.stderr, flush=True)
        return 1
    summary: Dict[str, Any] = {"device": device, "legs": report}
    if rehearse:
        summary["rehearsal"] = True
    partial = set(legs) != set(LEGS)
    if partial:
        summary["partial"] = True
    summary["seconds"] = round(time.monotonic() - _T0, 1)
    summary["claim"] = None
    print(f"summary {json.dumps(summary)}", flush=True)
    if not rehearse and not partial:
        print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
