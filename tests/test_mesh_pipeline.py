"""The fused, asynchronous pump on a mesh driver (engine/pipeline.py
``sharded_step_ticks``, ``EngineDriver.dispatch_ticks`` /
``complete_ticks`` with a mesh, ``serve-kv --mesh-devices``), on the
virtual CPU devices conftest.py sets.

The reference is the plain one: the serial per-tick loop on ONE device
with no mesh.  Sharding the groups axis over four devices and fusing
the ticks under one ``shard_map`` scan must change placement and
nothing else — state, mailbox, bound payloads and metrics bit for bit,
clean and under faults — and the compiled program holds no collective.
On top of that: a durable mesh server restores onto its mesh and reads
every acknowledged write back, and the CLI's ``--mesh-devices 4`` serves
a linearizable store over sockets.  (That a mesh server takes the
pipelined branch and that its phases tile is ``test_pump_phases.py``'s
``mesh4`` cases.)
"""

from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jax.sharding import Mesh  # noqa: E402

from multiraft_tpu.engine.core import (  # noqa: E402
    SCALAR_METRIC_KEYS,
    EngineConfig,
)
from multiraft_tpu.engine.host import EngineDriver  # noqa: E402
from multiraft_tpu.engine.mesh import assert_zero_collectives  # noqa: E402
from multiraft_tpu.engine.pipeline import sharded_step_ticks  # noqa: E402
from multiraft_tpu.engine.state_planes import content_fingerprint  # noqa: E402

CFG = EngineConfig(G=16, P=3, L=32, E=4, INGEST=4)
DEVICES = 4


@pytest.fixture(scope="module")
def mesh():
    devs = jax.devices()
    if len(devs) < DEVICES:
        pytest.skip(f"need {DEVICES} devices, have {len(devs)}")
    return Mesh(np.array(devs[:DEVICES]), axis_names=("groups",))


def assert_same_world(a: EngineDriver, b: EngineDriver, when) -> None:
    assert content_fingerprint(a.state) == content_fingerprint(b.state), when
    assert content_fingerprint(a.inbox) == content_fingerprint(b.inbox), when
    assert a.tick == b.tick
    assert a.backlog.tolist() == b.backlog.tolist(), when
    assert a.payloads == b.payloads, when
    assert a.commits_total == b.commits_total, when
    for k in b.last_metrics:
        assert np.array_equal(
            np.asarray(a.last_metrics[k]), np.asarray(b.last_metrics[k])
        ), (k, when)


def faults(kind: str, rnd: int, drivers) -> None:
    for d in drivers:
        if kind == "drop":
            d.drop_prob = 0.15 if 2 <= rnd < 9 else 0.0
        elif kind == "edges":
            # One replica cut off and healed; one directed edge down for
            # good, in a group that lives on another device.
            if rnd == 3:
                d.partition_replica(1, 2, False)
                d.set_edge(13, 0, 1, False)
            if rnd == 8:
                d.partition_replica(1, 2, True)


@pytest.mark.parametrize("kind, replicas", [
    pytest.param("clean", 3, id="clean"),
    pytest.param("drop", 3, id="drop"),
    pytest.param("edges", 3, id="edges"),
    # five replicas a group: the [G,P,P] planes shard like the [G,P] ones
    pytest.param("clean", 5, id="clean-p5"),
    pytest.param("edges", 5, id="edges-p5"),
])
def test_fused_sharded_pump_is_the_single_device_serial_loop(
    mesh, kind, replicas
):
    """Same seed, same submissions, same faults: the four-device fused
    scan against ``_step_serial`` on one device, pump after pump."""
    cfg = dataclasses.replace(CFG, P=replicas)
    sharded = EngineDriver(cfg, seed=7, mesh=mesh)
    plain = EngineDriver(cfg, seed=7)
    plain._pipeline_on = False
    assert sharded.fused_eligible() and not plain.fused_eligible()
    rng = np.random.default_rng(13)
    for rnd in range(14):
        for g in range(cfg.G):
            for j in range(int(rng.integers(0, 6))):
                for d in (sharded, plain):
                    d.start(g, ("cmd", rnd, g, j))
        faults(kind, rnd, (sharded, plain))
        n = int(rng.integers(2, 6))
        sharded.step(n)
        plain._step_serial(n)
        assert_same_world(sharded, plain, (kind, rnd))
    assert sharded.commits_total > 5 * cfg.G and len(sharded.payloads) > 100
    # The state never left the mesh, and the readback was per device.
    assert len(sharded.state.term.addressable_shards) == DEVICES
    c = sharded.metrics.counters
    assert c["pump.readback_copies"] == 14 * DEVICES  # one buffer a chip
    for k in SCALAR_METRIC_KEYS:
        assert np.ndim(sharded.last_metrics[k]) == 0, k


def test_a_mesh_driver_may_change_between_the_fused_and_the_serial_loop(mesh):
    """Reorder chaos (or the kill switch) sends a mesh driver to its
    serial ``shard_map`` tick for a while: both loops draw the same
    timer jitter and drops for a group, whichever device holds it."""
    a = EngineDriver(CFG, seed=3, mesh=mesh)
    b = EngineDriver(CFG, seed=3, mesh=mesh)
    b._pipeline_on = False
    a.drop_prob = b.drop_prob = 0.1
    for rnd in range(8):
        for g in range(0, CFG.G, 3):
            a.start(g, (rnd, g))
            b.start(g, (rnd, g))
        a.step(3)
        b.step(3)
        assert_same_world(a, b, rnd)


def test_overlapped_dispatch_on_a_mesh_never_ingests_twice(mesh):
    """Depth 2: the second batch's backlog is the host's less what the
    first, still in flight, accepted — subtracted shard by shard."""
    sharded = EngineDriver(CFG, seed=5, mesh=mesh)
    plain = EngineDriver(CFG, seed=5)
    plain._pipeline_on = False
    for d in (sharded, plain):
        d.step(40)  # leaders
        for g in range(CFG.G):
            for j in range(6):
                d.start(g, (g, j))
    p1 = sharded.dispatch_ticks(2)
    p2 = sharded.dispatch_ticks(2)
    assert len(p1.accepts_dev.addressable_shards) == DEVICES
    sharded.complete_ticks(p1, p1.fetch())
    sharded.complete_ticks(p2, p2.fetch())
    plain._step_serial(4)
    assert_same_world(sharded, plain, "depth 2")


@pytest.mark.parametrize("n", [1, 2])
@pytest.mark.parametrize("faults", ["clean", "drop", "edges"])
def test_mesh_fetch_unpacks_the_record_the_scan_produced(mesh, faults, n):
    """Each chip packs its share of the record into its shard of one
    buffer: ``fetch`` copies one buffer a chip and hands
    ``complete_ticks`` the per-field record the sharded scan stacked —
    ``[n, G]`` fields, ``[n, DEVICES]`` scalar lanes, same dtypes and
    values, as many bytes — and ``accepts_dev`` is its ``accepted``
    summed over ticks, still sharded on the groups axis."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from multiraft_tpu.engine.core import METRIC_KEYS
    from multiraft_tpu.engine.mesh import (
        INBOX_SPECS, STATE_SPECS, local_cfg, local_shard,
    )
    from multiraft_tpu.engine.pipeline import _scan_ticks

    d = EngineDriver(CFG, seed=5, mesh=mesh)
    d.step(40)  # leaders
    rng = np.random.default_rng(n)
    for g in range(CFG.G):
        for j in range(int(rng.integers(1, 9))):
            d.start(g, (g, j))
    if faults == "drop":
        d.drop_prob = 0.3
    elif faults == "edges":
        d.partition_replica(1, 0, False)
        d.set_edge(13, 0, 1, False)
    with_drop, with_edges = d.drop_prob > 0.0, not bool(d.edge_up.all())
    edge = d._edge_mask() if with_edges else np.zeros((), np.bool_)

    # The reference: the same scan under shard_map, its record per field
    # (scalars as one lane a device), not donated.
    lcfg = local_cfg(CFG, mesh)

    def scan(state, inbox, backlog, drop_prob, edge_mask, tick0, key):
        rec = _scan_ticks(
            lcfg, state, inbox, n, with_drop, with_edges, backlog,
            drop_prob, edge_mask, tick0, key, local_shard(CFG, lcfg),
        )[3]
        return {k: (v[:, None] if v.ndim == 1 else v) for k, v in rec.items()}

    groups, whole = P("groups"), P()
    ref = jax.jit(shard_map(
        scan, mesh=mesh,
        in_specs=(STATE_SPECS, INBOX_SPECS, groups, whole,
                  groups if with_edges else whole, whole, whole),
        out_specs={k: P(None, "groups") for k in METRIC_KEYS},
    ))(
        d.state, d.inbox,
        jax.device_put(d.backlog.astype(np.int32), d._groups_sharding),
        np.float32(d.drop_prob), edge, np.int32(d.tick), d.key,
    )
    ref = {k: np.asarray(v) for k, v in ref.items()}
    assert ref["accepted"].any()

    p = d.dispatch_ticks(n)
    assert len(p.buf.addressable_shards) == DEVICES
    assert len(p.accepts_dev.addressable_shards) == DEVICES
    got = p.fetch()
    assert list(got) == list(METRIC_KEYS)
    for k in METRIC_KEYS:
        want = (n, DEVICES) if k in SCALAR_METRIC_KEYS else (n, CFG.G)
        assert got[k].shape == ref[k].shape == want, k
        assert got[k].dtype == ref[k].dtype, k
        assert np.array_equal(got[k], ref[k]), k
    assert np.array_equal(np.asarray(p.accepts_dev), ref["accepted"].sum(0))
    assert p.ncopies == DEVICES
    assert p.nbytes == sum(v.nbytes for v in ref.values())
    d.complete_ticks(p, got)


@pytest.mark.parametrize("with_faults", [False, True], ids=["clean", "faults"])
def test_fused_sharded_program_holds_no_collective(mesh, with_faults):
    d = EngineDriver(CFG, seed=1, mesh=mesh, check_zero_collectives=False)
    if with_faults:
        d.set_edge(2, 0, 1, False)
        edge = d._edge_mask()
    else:
        edge = np.zeros((), np.bool_)
    hlo = assert_zero_collectives(
        sharded_step_ticks(CFG, mesh, 2, with_faults, with_faults),
        d.state, d.inbox, jax.device_put(
            np.zeros(CFG.G, np.int32), d._groups_sharding
        ),
        np.float32(0.1), edge, np.int32(0), d.key,
    )
    assert "while" in hlo  # the scan is in there


# -- the served store -------------------------------------------------------


def _serve(tmp_path, **kw):
    from multiraft_tpu.distributed.engine_server import serve_engine_kv

    return serve_engine_kv(
        port=0, G=8, data_dir=str(tmp_path), mesh_devices=DEVICES, **kw
    )


@pytest.mark.timeout_s(300)
@pytest.mark.parametrize("replicas", [3, 5], ids=["p3", "p5"])
def test_mesh_server_restores_onto_its_mesh_and_keeps_every_acked_write(
    mesh, tmp_path, replicas
):
    """Checkpoint, kill, ``restore(mesh=)`` under the fused pump: what a
    dict says after the acknowledged operations is what the restarted
    server reads back, from the checkpoint and from the WAL's tail."""
    from multiraft_tpu.distributed.engine_server import EngineClerk
    from multiraft_tpu.distributed.tcp import RpcNode
    from multiraft_tpu.sim.scheduler import TIMEOUT

    def run(client, gen):
        out = client.sched.wait(client.sched.spawn(gen), 60.0)
        assert out is not TIMEOUT
        return out

    model = {}
    node = _serve(tmp_path, checkpoint_every_s=0.5, replicas=replicas)
    client = RpcNode()
    try:
        svc = node.engine_service
        assert svc.kv.driver.mesh is not None and svc.kv.driver.fused_eligible()
        assert svc.kv.driver.cfg.P == replicas
        ck = EngineClerk(client.sched, client.client_end("127.0.0.1", node.port))
        saves = lambda: node.obs.metrics.hists["ckpt.save_s"].count
        for i in range(30):
            key = f"{chr(97 + i % 11)}k"  # eleven first letters: many groups
            if i % 3:
                run(client, ck.append(key, f"[{i}]"))
                model[key] = model.get(key, "") + f"[{i}]"
            else:
                run(client, ck.put(key, f"<{i}>"))
                model[key] = f"<{i}>"
            if i == 20:  # the rest is in the WAL only
                before = saves()
                deadline = time.monotonic() + 30
                while saves() == before and time.monotonic() < deadline:
                    time.sleep(0.05)
                assert saves() > before, "no checkpoint under the fused pump"
        assert node.obs.metrics.hists["pump.fetch_s"].count > 0
    finally:
        # kill: no final checkpoint, the pump just stops.
        client.close()
        node.sched.run_call(node.engine_service.stop, timeout=30)
        node.close()

    node = _serve(tmp_path, checkpoint_every_s=3600.0, replicas=replicas)
    client = RpcNode()
    try:
        m = node.obs.metrics
        assert m.counters["engine.restores"] == 1
        d = node.engine_service.kv.driver
        assert d.cfg.P == replicas
        # every [G,P,P] plane came back split over the mesh too
        assert node.sched.run_call(
            lambda: len(d.state.match_idx.addressable_shards), timeout=30
        ) == DEVICES
        # on the loop: the pump donates the state it steps
        assert node.sched.run_call(
            lambda: len(d.state.term.addressable_shards), timeout=30
        ) == DEVICES
        ck = EngineClerk(client.sched, client.client_end("127.0.0.1", node.port))
        for key, want in model.items():
            assert run(client, ck.get(key)) == want, key
        run(client, ck.append("ak", "(after)"))
        assert run(client, ck.get("ak")) == model["ak"] + "(after)"
        assert m.hists["pump.fetch_s"].count > 0  # still the fused pump
    finally:
        client.close()
        node.sched.run_call(node.engine_service.stop, timeout=30)
        node.close()


def _wait_ready(proc, timeout=240.0) -> int:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if line.startswith("ready "):
            return int(line.split()[1])
        if not line and proc.poll() is not None:
            break
    raise AssertionError(f"serve-kv gave no readiness line (exit={proc.poll()})")


@pytest.mark.timeout_s(420)
def test_cli_mesh_devices_4_serves_a_linearizable_store(tmp_path):
    """``serve-kv --mesh-devices 4`` through the normal entry point, over
    sockets: a dict model for what was acknowledged, porcupine for the
    concurrent history, and the scrape says it was the fused pump."""
    from multiraft_tpu.distributed.engine_cluster import BlockingEngineClerk
    from multiraft_tpu.distributed.tcp import RpcNode
    from multiraft_tpu.harness import run_clerk_load
    from multiraft_tpu.porcupine.checker import check_operations
    from multiraft_tpu.porcupine.kv import kv_model
    from multiraft_tpu.porcupine.model import CheckResult

    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-m", "multiraft_tpu", "serve-kv", "--groups", "16",
         "--mesh-devices", "4", "--data-dir", str(tmp_path / "mesh4")],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env, text=True,
    )
    node = None
    try:
        port = _wait_ready(proc)
        ck = BlockingEngineClerk(port)
        try:
            model = {f"{chr(97 + i)}-key": f"value{i}" for i in range(20)}
            for k, v in model.items():
                ck.put(k, v, timeout=60.0)
            for k in list(model)[::2]:
                ck.append(k, "+", timeout=60.0)
                model[k] += "+"
            for k, v in model.items():
                assert ck.get(k, timeout=60.0) == v, k
        finally:
            ck.close()
        history = run_clerk_load(
            lambda: BlockingEngineClerk(port), ["shared0", "shared1"],
            n_workers=3, ops_per_worker=9, op_timeout=60.0,
        )
        assert check_operations(kv_model, history, timeout=60.0) is CheckResult.OK
        node = RpcNode()
        end = node.client_end("127.0.0.1", port)
        snap = node.sched.wait(end.call("Obs.snapshot", None), 60.0)["metrics"]
        assert snap["engine.mesh_devices"] == 4
        assert snap["pump.fetch_s_count"] > 0, "the mesh server pumped synchronously"
        assert snap["pump.readback_copies"] == 4 * snap["pump.fetch_s_count"]
        info = node.sched.wait(end.call("EngineKV.info", None), 60.0)
        assert info["state_devices"] == 4
    finally:
        if node is not None:
            node.close()
        proc.kill()
        proc.wait()
