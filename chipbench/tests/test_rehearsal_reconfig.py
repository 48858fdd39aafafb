"""The reconfiguration cell's rehearsal on the CPU (G = 64, 33,330
shards): a leave of every third replica group and their join back
inside the window both settle, the line carries every ``reconfig.*``
metric traced and every comparison at 0, and the owners after each call
are the program's own plain reference's (``harness/shardref.py``) as
well as the benchmark's.  Then the two controls: a run whose join back
is skipped, and one whose read-back answers a value overwritten before
the window's end, come out ``correct`` false."""

import json
import os
import subprocess
import sys
import zlib

import numpy as np
import pytest

import admin
import manifest
from test_rehearsal import SEED, check_line, rehearse

CELL = "shardkv10k.reconfig"
RECONFIG = {"reconfig.ack_s", "reconfig.leave_settle_s", "reconfig.join_settle_s",
            "reconfig.shards_moved", "reconfig.wrong_group_share"}
OWNERSHIP = {"configs_not_one_a_call", "owners_not_the_references", "leavers_still_owning",
             "shards_moved_not_the_leavers", "groups_off_even_share", "inserts_not_shards_moved",
             "deletes_not_shards_moved", "confirms_not_shards_moved", "moved_keys_lost"}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_reconfig_cell_rehearses_to_the_contract_line(trace):
    # 12 s: the window holds the server's first checkpoint (30 s after
    # `ready`, the window 20 s after it), which the cell's ckpt.* metrics read
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "run.py"), "--workload", CELL,
         "--seed", SEED, "--seconds", "12", "--trace", str(trace), "--rehearse-cpu"],
        cwd=manifest.ROOT, text=True, capture_output=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    legs = [l for l in out.stdout.splitlines() if "] reconfig: " in l]
    assert [l.split("reconfig: ")[1].split()[0] for l in legs] == ["leave", "join"]
    assert all(" settle_s " in l for l in legs)
    assert OWNERSHIP <= set(line["compared"])
    if trace:
        listed = {m["name"] for m in manifest.cell(CELL)["layers"]}
        assert listed - set(line["metrics"]) == {"tick_ms"}   # the CPU has no device plane
        assert RECONFIG <= listed
        assert line["metrics"]["reconfig.shards_moved"]["value"] == 11110   # 21 of 63 groups
        assert line["metrics"]["shard.orchestrate_groups"]["value"] > 0    # the active path
    check_line(line, CELL, trace, out.stdout)


def keep_legs(monkeypatch):
    """Records every leg the admin calls made: ``legs`` fills as they end."""
    legs = []
    finish = admin.AdminCalls.finish

    def kept(self, cap_s):
        legs.extend(finish(self, cap_s))
        return legs
    monkeypatch.setattr(admin.AdminCalls, "finish", kept)
    return legs


def test_owners_are_the_programs_plain_reference_after_each_leg(monkeypatch, capfd):
    legs = keep_legs(monkeypatch)
    rc, lines, _out, err = rehearse(monkeypatch, capfd, CELL)
    assert rc == 0 and lines[-1]["correct"] is True, err[-1000:]
    from multiraft_tpu.harness.shardref import ShardRef   # on the path once run.py is imported

    cfg = manifest.cell(CELL)["config"]
    groups = range(1, cfg["rehearse_cpu"]["groups"])
    ref = ShardRef(cfg["shards"], shard_of=None)
    ref.join(groups)
    assert list(legs[0]["config0"]["owners"]) == ref.owner
    assert [leg["op"] for leg in legs] == ["leave", "join"]
    for leg in legs:
        getattr(ref, leg["op"])(leg["gids"])
        assert list(leg["config1"]["owners"]) == ref.owner, leg["op"]
    assert len(legs[0]["moved"]) == 11110


def skip_the_join(monkeypatch):
    """The first control: the join back is never sent, and the harness is
    told ``OK``."""
    send = admin.AdminCalls.send

    def send_but_no_join(self, op, gids, cmd):
        return "OK" if op == "join" else send(self, op, gids, cmd)
    monkeypatch.setattr(admin.AdminCalls, "send", send_but_no_join)


def read_back_overwritten_values(monkeypatch, records):
    """The second control: the read-back after the window answers each
    updated key with its loaded value, which an acknowledged update
    overwrote before the window's end, as a migration that shipped a
    shard's older copy would serve it.  ``records`` is the run's
    ``traffic.Records``."""
    import run
    from traffic import LOADER

    firehose = run.Client.firehose

    def overwritten(self, ops, cap_s):
        got = firehose(self, ops, cap_s)
        if all(op == "Get" for op, _k, _v in ops):
            got = [records.value(LOADER, int(k[4:])) for _op, k, _v in ops]
        return got
    monkeypatch.setattr(run.Client, "firehose", overwritten)


def test_a_skipped_join_back_comes_out_not_correct(monkeypatch, capfd):
    skip_the_join(monkeypatch)
    rc, lines, _out, err = rehearse(monkeypatch, capfd, CELL)
    assert rc == 0 and lines[-1]["correct"] is False, err[-1000:]
    failed = {k for k, c in lines[-1]["compared"].items() if c["value"] > c["limit"]}
    assert {"configs_not_one_a_call", "owners_not_the_references",
            "groups_off_even_share"} <= failed, failed
    # 21 groups hold no shard, the other 42 hold 793-794 where 529-530 was due
    assert lines[-1]["compared"]["groups_off_even_share"]["value"] == 63


def test_a_read_back_of_overwritten_values_comes_out_not_correct(monkeypatch, capfd):
    import traffic

    cfg = dict(manifest.cell(CELL)["config"], recordcount=manifest.cell(CELL)["config"][
        "rehearse_cpu"]["recordcount"])
    read_back_overwritten_values(monkeypatch, traffic.Records(cfg, int(SEED)))
    rc, lines, _out, err = rehearse(monkeypatch, capfd, CELL)
    assert rc == 0 and lines[-1]["correct"] is False, err[-1000:]
    compared = lines[-1]["compared"]
    assert compared["moved_keys_lost"]["value"] > 0 and compared["stale_reads"]["value"] > 0
    assert all(compared[k]["value"] == 0 for k in OWNERSHIP - {"moved_keys_lost"})


def test_the_moved_keys_are_every_key_of_the_shards_that_moved():
    import traffic

    rule = admin.ShardSettle("EngineShardKV", {"groups": 64, "shards": 10})
    records = traffic.Records({"recordcount": 200, "fieldcount": 10, "fieldlength": 100}, 1)
    legs = [{"moved": np.array([2, 7])}, {"moved": np.array([7, 9])}]
    keys = rule.read_back(legs, records)
    want = [i for i, k in enumerate(records.keys) if zlib.crc32(k.encode()) % 10 in (2, 7, 9)]
    assert keys.tolist() == want
