"""``serve-kv --replicas N``: the served store at the replica count its
deployment states (BASELINE.json config 5 says five).

The reference is plain and independent of the engine: a dict for what
the acknowledged operations leave behind, the repo's porcupine for the
concurrent history, and numpy arithmetic over the state planes
(``EngineDriver.rows_of``) for who holds an acknowledged index.  Every
case that P=3 has always passed runs at P=5 too: the same entry point,
pump, WAL and checkpoint.  What is new at five is the guarantee: a group
keeps serving with two replicas down, stops (and acknowledges nothing)
with three down, and loses no acknowledged write either way.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import multiraft_tpu.__main__ as cli  # noqa: E402
from multiraft_tpu.distributed.engine_server import (  # noqa: E402
    EngineClerk,
    serve_engine_kv,
)
from multiraft_tpu.distributed.engine_wire import route_group  # noqa: E402
from multiraft_tpu.distributed.tcp import RpcNode  # noqa: E402
from multiraft_tpu.engine.core import LEADER  # noqa: E402
from multiraft_tpu.porcupine.checker import check_operations  # noqa: E402
from multiraft_tpu.porcupine.kv import (  # noqa: E402
    OP_APPEND,
    OP_GET,
    OP_PUT,
    KvInput,
    KvOutput,
    kv_model,
)
from multiraft_tpu.porcupine.model import CheckResult, Operation  # noqa: E402
from multiraft_tpu.sim.scheduler import TIMEOUT  # noqa: E402

REPLICAS = pytest.mark.parametrize("replicas", [3, 5], ids=["p3", "p5"])
G = 16
PLANES = ("role", "alive", "term", "commit", "base", "log_len", "log_term")


def _stop(node) -> None:
    """Kill, not shut down: no final checkpoint, the pump just stops."""
    node.sched.run_call(node.engine_service.stop, timeout=30)
    node.close()


def _run(client, gen, cap_s=60.0):
    out = client.sched.wait(client.sched.spawn(gen), cap_s)
    assert out is not TIMEOUT
    return out


@pytest.fixture
def client():
    node = RpcNode()
    try:
        yield node
    finally:
        node.close()


def _clerk(client, node) -> EngineClerk:
    return EngineClerk(client.sched, client.client_end("127.0.0.1", node.port))


def _key_in_group(g: int, groups: int, tag: str) -> str:
    return next(
        k for i in range(10_000)
        if route_group(k := f"{tag}{i}", groups) == g
    )


# ---------------------------------------------------------------------------
# The store, against the dict model and porcupine
# ---------------------------------------------------------------------------


@REPLICAS
@pytest.mark.timeout_s(300)
def test_served_mix_answers_as_the_dict_model_and_is_linearizable(
    replicas, tmp_path, client
):
    """A seeded mix of Put / Append / Get from concurrent clerks over
    real sockets.  Each clerk's own keys are written by it alone, so a
    dict says what every one of its reads returns; the keys all clerks
    share are judged by porcupine, with the rest of the history."""
    node = serve_engine_kv(
        port=0, G=G, data_dir=str(tmp_path), checkpoint_every_s=0.5,
        replicas=replicas,
    )
    history: list = []
    wrong: list = []

    def clerk_loop(wid: int):
        rng = np.random.default_rng([replicas, wid, 1009])
        ck = _clerk(client, node)
        own = [f"{chr(97 + wid)}{i}-own" for i in range(5)]
        shared = [f"shared{i}" for i in range(3)]
        model: dict = {}
        for j in range(40):
            mine = rng.random() < 0.6
            key = str(rng.choice(own if mine else shared))
            draw = rng.random()
            t0 = time.monotonic()
            if draw < 0.4:
                got = yield from ck.get(key)
                inp, out = KvInput(op=OP_GET, key=key), KvOutput(value=got)
                if mine and got != model.get(key, ""):
                    wrong.append((wid, j, key, got, model.get(key, "")))
            elif draw < 0.7:
                value = f"<{wid}.{j}>"
                yield from ck.put(key, value)
                model[key] = value
                inp, out = KvInput(op=OP_PUT, key=key, value=value), KvOutput()
            else:
                value = f"[{wid}.{j}]"
                yield from ck.append(key, value)
                model[key] = model.get(key, "") + value
                inp = KvInput(op=OP_APPEND, key=key, value=value)
                out = KvOutput()
            history.append(Operation(
                client_id=ck.client_id, input=inp, call=t0, output=out,
                ret=time.monotonic(),
            ))
        return model

    try:
        futs = [client.sched.spawn(clerk_loop(w)) for w in range(4)]
        models = [client.sched.wait(f, 240.0) for f in futs]
        assert TIMEOUT not in models
        assert not wrong, wrong[:3]
        assert len(history) == 160
        assert check_operations(kv_model, history, timeout=60.0) is CheckResult.OK
        # ... and after the dust settles every own key reads as its dict
        ck = _clerk(client, node)
        for model in models:
            for key, want in model.items():
                if key.endswith("-own"):
                    assert _run(client, ck.get(key)) == want, key
        info = client.sched.wait(
            client.client_end("127.0.0.1", node.port).call("EngineKV.info", None),
            30.0,
        )
        assert info["G"] == G and info["P"] == replicas
        assert node.obs.metrics.hists["ckpt.save_s"].count >= 1
        assert node.obs.metrics.hists["pump.fetch_s"].count > 0  # the fused pump
    finally:
        _stop(node)


# ---------------------------------------------------------------------------
# The guarantee: a group of 2f+1 serves with f down, and only then
# ---------------------------------------------------------------------------


def _holders(rows, index: int) -> int:
    """How many replicas of the one group in ``rows`` hold log index
    ``index`` with the entry the leader's log has there: numpy over the
    planes, nothing of the engine's own quorum code."""
    L = rows["log_term"].shape[-1]
    last = rows["base"][0] + rows["log_len"][0]
    lead = int(np.argmax((rows["role"][0] == LEADER) & rows["alive"][0]))
    assert rows["base"][0][lead] < index <= last[lead]
    want = rows["log_term"][0][lead][index % L]
    has = (rows["base"][0] < index) & (index <= last) & (
        rows["log_term"][0][:, index % L] == want
    )
    # A replica that compacted past ``index`` held it when it did.
    return int((has | (rows["base"][0] >= index)).sum())


@REPLICAS
@pytest.mark.timeout_s(300)
def test_a_group_serves_with_a_minority_down_and_stops_with_a_majority_down(
    replicas, tmp_path, client
):
    f = replicas // 2
    node = serve_engine_kv(port=0, G=8, data_dir=str(tmp_path), replicas=replicas)
    svc = node.engine_service
    driver = svc.kv.driver
    g = 5
    key, other = _key_in_group(g, 8, "q"), _key_in_group(2, 8, "o")
    on_loop = lambda fn: node.sched.run_call(fn, timeout=60)
    rows = lambda: on_loop(lambda: driver.rows_of(PLANES, [g]))
    applied = lambda: on_loop(lambda: int(svc.kv.applied_upto[g]))

    def take_down(n: int) -> list:
        """The leader first: the survivors have to elect."""
        def fn():
            st = driver.rows_of(("role", "alive"), [g])
            up = np.flatnonzero(st["alive"][0])
            lead = (st["role"][0] == LEADER) & st["alive"][0]
            order = sorted(up, key=lambda p: not lead[p])[:n]
            for p in order:
                driver.set_alive(g, int(p), False)
            return [int(p) for p in order]
        return on_loop(fn)

    try:
        ck = _clerk(client, node)
        model = {}
        for i in range(3):
            _run(client, ck.append(key, f"[a{i}]"))
            model[key] = model.get(key, "") + f"[a{i}]"
        assert _holders(rows(), applied()) >= f + 1

        # f down (the leader among them): still acknowledged, and what is
        # acknowledged is held by a majority of ALL the replicas.
        down = take_down(f)
        assert len(down) == f
        for i in range(4):
            _run(client, ck.append(key, f"[b{i}]"))
            model[key] += f"[b{i}]"
            r = rows()
            assert int(r["alive"][0].sum()) == replicas - f
            assert _holders(r, applied()) >= f + 1
        assert _run(client, ck.get(key)) == model[key]

        # f + 1 down: nothing is acknowledged, the commit index stands.
        down += take_down(1)
        commit0 = int(rows()["commit"][0].max())
        stuck = client.sched.spawn(ck.append(key, "[c]"))
        assert client.sched.wait(stuck, 3.0) is TIMEOUT
        r = rows()
        assert int(r["alive"][0].sum()) == f
        assert int(r["commit"][0].max()) == commit0
        # ... in that group alone
        _run(client, _clerk(client, node).put(other, "elsewhere"))

        # One comes back: the clerk's retry lands once, nothing was lost.
        on_loop(lambda: driver.set_alive(g, down[-1], True))
        assert client.sched.wait(stuck, 60.0) is not TIMEOUT
        model[key] += "[c]"
        assert _run(client, ck.get(key)) == model[key]
        assert _holders(rows(), applied()) >= f + 1
        assert _run(client, ck.get(other)) == "elsewhere"
    finally:
        _stop(node)


# ---------------------------------------------------------------------------
# Durability, and the refusal
# ---------------------------------------------------------------------------


def _write_some(client, node, n=24, wait_checkpoint_at=16) -> dict:
    ck = _clerk(client, node)
    model: dict = {}
    saves = lambda: node.obs.metrics.hists["ckpt.save_s"].count
    for i in range(n):
        key = f"{chr(97 + i % 9)}k"
        if i % 3:
            _run(client, ck.append(key, f"[{i}]"))
            model[key] = model.get(key, "") + f"[{i}]"
        else:
            _run(client, ck.put(key, f"<{i}>"))
            model[key] = f"<{i}>"
        if i == wait_checkpoint_at:  # the rest is in the WAL only
            before, deadline = saves(), time.monotonic() + 30
            while saves() == before and time.monotonic() < deadline:
                time.sleep(0.05)
            assert saves() > before
    return model


@REPLICAS
@pytest.mark.timeout_s(300)
def test_a_hard_stop_and_a_restart_at_the_same_count_keep_every_acked_write(
    replicas, tmp_path, client
):
    node = serve_engine_kv(
        port=0, G=8, data_dir=str(tmp_path), checkpoint_every_s=0.5,
        replicas=replicas,
    )
    try:
        model = _write_some(client, node)
    finally:
        _stop(node)
    node = serve_engine_kv(
        port=0, G=8, data_dir=str(tmp_path), checkpoint_every_s=3600.0,
        replicas=replicas,
    )
    try:
        assert node.obs.metrics.counters["engine.restores"] == 1
        assert node.engine_service.kv.driver.cfg.P == replicas
        ck = _clerk(client, node)
        for key, want in model.items():
            assert _run(client, ck.get(key)) == want, key  # once, not twice
        _run(client, ck.append("ak", "(after)"))
        assert _run(client, ck.get("ak")) == model["ak"] + "(after)"
    finally:
        _stop(node)


@pytest.mark.parametrize("wrote, asked", [(5, 3), (3, 5)], ids=["5-as-3", "3-as-5"])
@pytest.mark.timeout_s(300)
def test_a_data_dir_of_another_replica_count_is_refused_by_name(
    wrote, asked, tmp_path, client
):
    node = serve_engine_kv(port=0, G=4, data_dir=str(tmp_path), replicas=wrote)
    try:
        _run(client, _clerk(client, node).put("k", "v"))
    finally:
        _stop(node)
    with pytest.raises(ValueError) as exc:
        serve_engine_kv(port=0, G=4, data_dir=str(tmp_path), replicas=asked)
    said = str(exc.value)
    assert f"{wrote} replicas" in said and f"asked for {asked}" in said
    assert f"--replicas {wrote}" in said
    # ... and the data is untouched: the right count still serves it
    node = serve_engine_kv(port=0, G=4, data_dir=str(tmp_path), replicas=wrote)
    try:
        assert _run(client, _clerk(client, node).get("k")) == "v"
    finally:
        _stop(node)


# ---------------------------------------------------------------------------
# The flag
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("argv, want", [
    (["serve-kv"], 3),
    (["serve-kv", "--replicas", "5"], 5),
    (["serve-shardkv", "--join", "1"], 3),
    (["serve-shardkv", "--join", "1", "--replicas", "5"], 5),
], ids=["kv-default", "kv-5", "shardkv-default", "shardkv-5"])
@pytest.mark.timeout_s(300)
def test_the_flag_reaches_the_engine_config(argv, want, monkeypatch, client):
    """``main()`` itself, with the serving scaffold's wait taken out."""
    seen = {}

    def build_and_look(_args, build):
        node = build()
        try:
            svc = node.engine_service
            driver = (svc.kv if hasattr(svc, "kv") else svc.skv).driver
            seen["P"] = driver.cfg.P
            seen["quorum"] = driver.cfg.quorum
            if argv[0] == "serve-kv":
                end = client.client_end("127.0.0.1", node.port)
                seen["info"] = client.sched.wait(end.call("EngineKV.info", None), 30)
        finally:
            _stop(node)
        return 0

    monkeypatch.setattr(cli, "_serve_forever", build_and_look)
    assert cli.main([*argv, "--groups", "4"]) == 0
    assert seen["P"] == want and seen["quorum"] == want // 2 + 1
    if "info" in seen:
        assert seen["info"]["P"] == want and seen["info"]["G"] == 4


@pytest.mark.parametrize("bad", ["4", "1", "2", "0", "-5", "five"])
def test_an_even_or_too_small_count_is_an_argparse_error_that_says_why(
    bad, capsys
):
    with pytest.raises(SystemExit) as exc:
        cli.main(["serve-kv", f"--replicas={bad}"])
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert "--replicas" in err
    if bad != "five":
        assert "odd and at least 3" in err


def test_the_upper_limit_is_the_engine_configs_own():
    """One place: the i32 voter bitmask's (``EngineConfig``), which the
    CLI reports as an error, not a traceback."""
    from multiraft_tpu.engine.core import EngineConfig

    assert cli._replicas("29") == 29
    with pytest.raises(ValueError, match="P <= 30"):
        EngineConfig(G=1, P=cli._replicas("31"), membership=True)


@pytest.mark.timeout_s(300)
def test_the_cli_refuses_a_mismatched_data_dir_and_exits_nonzero(
    tmp_path, client
):
    node = serve_engine_kv(port=0, G=4, data_dir=str(tmp_path), replicas=5)
    _stop(node)
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "multiraft_tpu", "serve-kv", "--groups", "4",
         "--data-dir", str(tmp_path)],
        env=env, text=True, capture_output=True, timeout=240,
    )
    assert out.returncode == 1
    assert "ready" not in out.stdout
    last = out.stderr.strip().splitlines()[-1]
    assert last.startswith("error: ") and "Traceback" not in out.stderr
    assert "5 replicas" in last and "asked for 3" in last


# ---------------------------------------------------------------------------
# What a scrape says of the deployment
# ---------------------------------------------------------------------------


@REPLICAS
@pytest.mark.timeout_s(300)
def test_a_scrape_says_how_many_replicas_the_ticks_advanced(
    replicas, tmp_path, client
):
    """``engine.replica_ticks`` / ``ticks`` = G x P between any two
    scrapes, gauge ``engine.replicas`` = P; ``Obs.snapshot``'s per-group
    columns and the wedge watch run against the same server."""
    node = serve_engine_kv(port=0, G=G, data_dir=str(tmp_path), replicas=replicas)
    try:
        end = client.client_end("127.0.0.1", node.port)
        scrape = lambda: client.sched.wait(end.call("Obs.snapshot", None), 30.0)
        a = scrape()["metrics"]
        _run(client, _clerk(client, node).put("k", "v"))
        time.sleep(0.6)  # two scrapes of the wedge watch (0.25 s)
        snap = scrape()
        b = snap["metrics"]
        ticks = b["ticks"] - a["ticks"]
        assert ticks > 0
        assert b["engine.replica_ticks"] - a["engine.replica_ticks"] == (
            ticks * G * replicas
        )
        assert b["engine.replica_ticks"] == b["ticks"] * G * replicas
        assert b["engine.replicas"] == replicas
        groups = snap["groups"]
        assert groups["G"] == G and len(groups["leader"]) == G
        assert all(0 <= p < replicas for p in groups["leader"])
        assert all(len(row) == replicas for row in groups["replica_alive"])
        assert b["wedge.check_s_count"] >= 2 and not node.wedge_watch.wedged
        assert b.get("wedge.watch_errors", 0) == 0 and b.get("wedge.trips", 0) == 0
    finally:
        _stop(node)
