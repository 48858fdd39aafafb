"""The sharded cell's rehearsal on the CPU: ``serve-shardkv`` behind
``EngineShardKV`` prints the contract's line traced and untraced, every
per-layer metric the manifest lists for the cell is in the traced line
but the one only a device trace gives, and an altered answer comes out
``correct`` false through the sharded service too."""

import json
import os
import subprocess
import sys

import pytest

import manifest
from test_rehearsal import SEED, check_line, rehearse

CELL = "shardkv10k.ycsb-a"


@pytest.mark.parametrize("trace", [0, 1])
def test_the_sharded_cell_rehearses_to_the_contract_line(trace):
    # 12 s: the window holds the server's first checkpoint (30 s after
    # `ready`, the window 20 s after it), which the cell's ckpt.* metrics
    # read, however soon the profiler's stop returns
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "run.py"), "--workload", CELL,
         "--seed", SEED, "--seconds", "12", "--trace", str(trace), "--rehearse-cpu"],
        cwd=manifest.ROOT, text=True, capture_output=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    if trace:
        listed = {m["name"] for m in manifest.cell(CELL)["layers"]}
        assert listed - set(line["metrics"]) == {"tick_ms"}   # the CPU has no device plane
        # a settled config: the sweep visits no group, and that reads 0, not nothing
        assert line["metrics"].pop("shard.orchestrate_groups") == {"value": 0.0, "unit": "%"}
        assert 0 < line["metrics"]["shard.orchestrate_ms"]["value"] < 0.1
    check_line(line, CELL, trace, out.stdout)


def test_the_cell_is_the_sharded_service_at_the_sources_ratio():
    cfg = manifest.cell(CELL)["config"]
    assert cfg["service"] == "EngineShardKV" and cfg["serve"][0] == "serve-shardkv"
    assert cfg["serve"][cfg["serve"].index("--join") + 1] == "all"   # whatever --groups is
    assert str(cfg["shards"]) == cfg["serve"][cfg["serve"].index("--shards") + 1]
    assert cfg["shards"] * 3 == (cfg["groups"] - 1) * 10             # 10 shards : 3 groups


def test_an_altered_answer_comes_out_not_correct_through_the_sharded_service(
        monkeypatch, capfd):
    from multiraft_tpu.distributed.engine_clerks import EngineClerk
    from traffic import TAG

    seen = {"n": 0, "services": set()}
    real_get = EngineClerk.get

    def get(self, key):        # one read in twenty names a write nobody made
        v = yield from real_get(self, key)
        seen["n"] += 1
        seen["services"].add(self.service)
        if seen["n"] % 20 == 0:
            v = f"{(int(v[:TAG]) + 1) % 10 ** TAG:0{TAG}d}" + v[TAG:]
        return v
    monkeypatch.setattr(EngineClerk, "get", get)
    rc, lines, _out, err = rehearse(monkeypatch, capfd, CELL)
    assert rc == 0 and lines[-1]["correct"] is False, err[-1000:]
    assert seen["services"] == {"EngineShardKV"}
    failed = {k for k, c in lines[-1]["compared"].items() if c["value"] > c["limit"]}
    assert failed & {"reads_of_values_nobody_wrote", "reads_from_the_future"}
