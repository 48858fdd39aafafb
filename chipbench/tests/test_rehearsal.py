"""A rehearsal on the CPU prints, last, a line with the contract's keys
and no other (but the mark that says it is a rehearsal, not a result):
for a closed-loop cell, for the open-loop one and for one whose server
is killed and started again inside the window.  Then the rest of a run
with the timed path broken underneath: ``correct`` comes out false; a
restart that loses the WAL comes out not correct; and a generator that
cannot offer its schedule gives no result."""

import copy
import json
import os
import subprocess
import sys

import pytest

import manifest

CELLS = [w["name"] for w in manifest.manifest()["workloads"]]
CELL = CELLS[0]
OPEN = [c for c in CELLS if manifest.cell(c)["traffic"]["loop"] == "open"]
CRASH = [c for c in CELLS if manifest.kill_at(manifest.cell(c)["traffic"], 50.0) is not None]
RECONFIG = [c for c in CELLS if manifest.admin_calls(manifest.cell(c)["traffic"], 50.0)]
MAY_BE_ZERO = {"admit.shed_share", "reconfig.wrong_group_share"}   # shares of the calls
RECOVERY = {f"recover.{part}_s" for part in
            ("outage", "exit", "start", "backend", "restore", "warm", "replay", "checkpoint",
             "reconnect")}
SEED = str(2 ** 31 + 5)


def run_directory(out):
    """The directory a run names on its standard output."""
    return [l.split("run directory ", 1)[1] for l in out.splitlines() if "] run directory " in l][0]


def check_line(line, cell_name, trace, out):
    assert line.pop("rehearsal") is True
    want = {"correct", "attempted", "failed", "metrics", "device", "compared"}
    assert set(line) == (want | {"breakdown"} if trace else want)
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["compared"] and all(c == {"value": 0, "limit": 0} for c in line["compared"].values())
    cell = manifest.cell(cell_name)  # the cell's own lists: tails are opt-in
    names = {m["name"] for m in cell["layers" if trace else "end_to_end"]}
    assert set(line["metrics"]) <= names
    if not trace:
        assert set(line["metrics"]) == names
    for name, m in line["metrics"].items():   # a share of the calls may be 0 %: none was shed
        assert set(m) == {"value", "unit"} and (m["value"] > 0 or name in MAY_BE_ZERO), name
    device = {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(line["device"]) == (device | {"busy_s", "window_s"} if trace else device)
    assert not os.path.exists(run_directory(out))   # the run removed what it wrote


@pytest.mark.parametrize("cell,trace", [(CELL, 0), (CELL, 1)] + [(c, 1) for c in OPEN]
                         + [(OPEN[0], 0)] + [(c, t) for c in CRASH for t in (0, 1)])
def test_rehearsal_prints_the_contract_line(cell, trace):
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "run.py"), "--workload", cell,
         "--seed", SEED, "--seconds", "4", "--trace", str(trace), "--rehearse-cpu"],
        cwd=manifest.ROOT, text=True, capture_output=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    # every number compared stands beside its limit, last on stderr too
    assert out.stderr.strip().splitlines()[-1].startswith("compared ")
    check_line(line, cell, trace, out.stdout)
    if cell in OPEN:
        assert "ms from due:" in out.stdout and "generator: " in out.stdout
        if trace:   # what the generator says of itself is there to be read
            assert {"client.late_p99_ms", "client.inflight_p95", "client.inflight_end"} \
                <= set(line["metrics"])
    if cell in CRASH:
        assert "recovery: recover_s " in out.stdout and "check across the kill: " in out.stdout
        assert line["compared"]["acked_before_kill_lost"] == {"value": 0, "limit": 0}
        if trace:   # the restarted server's stages and the clerks' re-dial
            assert RECOVERY <= set(line["metrics"])


def test_no_tpu_no_result():
    """Without --rehearse-cpu and without a TPU: exit non-zero, no line."""
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "2", "--trace", "0"],
        cwd=manifest.ROOT, text=True, capture_output=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode != 0
    assert not any(l.startswith("{") for l in out.stdout.splitlines())


# -- the rest of a run, in this process, with something changed underneath ----


def rehearse(monkeypatch, capfd, cell_name, change_cell=None):
    import run

    cell = copy.deepcopy(manifest.cell(cell_name))
    if change_cell:
        change_cell(cell)
    monkeypatch.setattr(run.manifest, "cell", lambda name: cell)
    rc = run.main(["--workload", cell_name, "--seed", SEED, "--seconds", "4", "--trace", "0",
                   "--rehearse-cpu"])
    out, err = capfd.readouterr()
    lines = [json.loads(l) for l in out.splitlines() if l.startswith("{")]
    return rc, lines, out, err


def test_the_service_name_comes_from_the_configuration(monkeypatch, capfd):
    def spell_it_out(cell):
        cell["config"]["service"] = "EngineKV"
    rc, lines, _out, err = rehearse(monkeypatch, capfd, OPEN[0], spell_it_out)
    assert rc == 0 and lines[-1]["correct"] is True, err[-1000:]

    def another(cell):
        cell["config"]["service"] = "NoSuchKV"
    rc, lines, _out, err = rehearse(monkeypatch, capfd, OPEN[0], another)
    assert rc == 1 and not lines and "NoSuchKV.info" in err


def test_a_generator_that_cannot_offer_its_schedule_gives_no_result(monkeypatch, capfd):
    def starve(cell):     # one session: an arrival while it is busy (~10 ms an update) waits
        cell["traffic"]["rehearse_cpu"] = {"rate_ops_s": 60, "sessions": 1}
    rc, lines, out, err = rehearse(monkeypatch, capfd, OPEN[0], starve)
    assert rc == 1 and not lines
    assert "generator: " in out and "error: the generator did not offer its schedule" in err


@pytest.mark.parametrize("cell", [OPEN[0]] + CRASH + RECONFIG)
@pytest.mark.parametrize("fault", ["answer_altered", "update_acknowledged_and_never_sent"])
def test_a_broken_timed_path_comes_out_not_correct(monkeypatch, capfd, fault, cell):
    from multiraft_tpu.distributed.engine_clerks import EngineClerk
    from traffic import TAG

    seen = {"n": 0}
    if fault == "answer_altered":
        real_get = EngineClerk.get

        def get(self, key):        # one read in twenty names a write nobody made
            v = yield from real_get(self, key)
            seen["n"] += 1
            if seen["n"] % 20 == 0:
                v = f"{(int(v[:TAG]) + 1) % 10 ** TAG:0{TAG}d}" + v[TAG:]
            return v
        monkeypatch.setattr(EngineClerk, "get", get)
    else:
        real_put = EngineClerk.put

        def put(self, key, value):  # one update in five is acknowledged by nobody but the clerk
            seen["n"] += 1
            if seen["n"] % 5 == 0:
                return ""
            return (yield from real_put(self, key, value))
        monkeypatch.setattr(EngineClerk, "put", put)
    rc, lines, _out, err = rehearse(monkeypatch, capfd, cell)
    assert rc == 0 and lines[-1]["correct"] is False, err[-1000:]
    failed = {k for k, c in lines[-1]["compared"].items() if c["value"] > c["limit"]}
    assert failed and "compared " in err
    if fault == "answer_altered":
        assert failed & {"reads_of_values_nobody_wrote", "reads_from_the_future"}
    else:
        assert failed & {"stale_reads", "acked_updates_not_in_wal", "keys_read_back_wrong"}


def lose_the_wal(restart):
    """The control of a cell with a kill: the server starts again on its
    data directory without the WAL, as one that acknowledged updates
    before they reached the disk would come back.  ``restart`` is
    ``run.Server.restart``; this returns its replacement."""
    def restart_without_the_wal(server):
        server.kill()
        data = server.argv[server.argv.index("--data-dir") + 1]
        os.remove(os.path.join(data, "ops.wal"))
        restart(server)
    return restart_without_the_wal


@pytest.mark.parametrize("cell", CRASH)
def test_a_restart_that_loses_the_wal_comes_out_not_correct(monkeypatch, capfd, cell):
    import run

    monkeypatch.setattr(run.Server, "restart", lose_the_wal(run.Server.restart))
    rc, lines, _out, err = rehearse(monkeypatch, capfd, cell)
    assert rc == 0 and lines[-1]["correct"] is False, err[-1000:]
    assert lines[-1]["compared"]["acked_before_kill_lost"]["value"] > 0
