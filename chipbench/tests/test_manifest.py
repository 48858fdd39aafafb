"""BENCHMARK.json and the files under chipbench/ agree by name."""

import json
import os
import re

import pytest

import layers
import manifest

MAN = manifest.manifest()
with open(os.path.join(manifest.ROOT, "multiraft_tpu", "__main__.py")) as _f:
    # the program's own subcommands, read and not imported: no jax here
    SUBCOMMANDS = set(re.findall(r'add_parser\(\s*"([\w-]+)"', _f.read()))


def test_every_cell_resolves_to_its_files():
    for w in MAN["workloads"]:
        cell = manifest.cell(w["name"])
        assert cell["config"]["serve"][0] in SUBCOMMANDS
        assert cell["traffic"]["loop"] in ("closed", "open")
        e2e = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell["layers"], "every cell reports a per-layer metric"
        assert w["chips"] in (1, 4)
        manifest.kill_at(cell["traffic"], MAN["run_seconds"])   # a schedule that is built
        if cell["traffic"].get("rehearse_cpu", {}).get("faults"):
            manifest.kill_at(cell["traffic"]["rehearse_cpu"], MAN["run_seconds"])


def test_configs_are_files_under_paths_and_state_what_the_manifest_says():
    for c in MAN["configs"]:
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        with open(os.path.join(manifest.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert str(cfg["groups"]) in cfg["serve"]
        assert cfg["fieldcount"] * cfg["fieldlength"] == 1000  # YCSB's record, never cut
        assert any(c["name"] == w["config"] for w in MAN["workloads"])


def test_layer_metrics_have_a_known_reader_and_move_an_end_to_end_metric():
    e2e = {m["name"] for m in MAN["end_to_end"]}
    for m in MAN["per_layer"]:
        with open(os.path.join(manifest.HERE, "layers", m["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"]["kind"] in layers.READERS
        assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]
        assert m["moves"] in e2e and m["moves"] != "setup_s"


def test_a_layer_metric_is_only_where_the_metric_it_moves_is_reported():
    for w in MAN["workloads"]:
        cell = manifest.cell(w["name"])
        reported = {m["name"] for m in cell["end_to_end"]}
        for spec in cell["layers"]:
            assert spec["moves"] in reported, (w["name"], spec["name"])


# Every cell carries these two; every other end-to-end metric is opt-in:
# a cell joins its list only where its own two sets of runs let the bound
# resolve (README.md, "Add a cell").
IN_EVERY_CELL = {"update_p50_ms", "setup_s"}


def test_every_end_to_end_metric_but_the_two_every_cell_carries_is_opt_in():
    for m in MAN["end_to_end"]:
        assert ("workloads" not in m) == (m["name"] in IN_EVERY_CELL), m["name"]


def test_a_workloads_list_is_not_empty_and_names_cells():
    cells = {w["name"] for w in MAN["workloads"]}
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        if "workloads" in m:
            assert m["workloads"], m["name"]
            assert len(set(m["workloads"])) == len(m["workloads"]), m["name"]
            assert set(m["workloads"]) <= cells, m["name"]


def test_a_layer_metric_for_every_cell_moves_a_metric_of_every_cell():
    listed = {m["name"] for m in MAN["end_to_end"] if "workloads" in m}
    for m in MAN["per_layer"]:
        if "workloads" not in m:
            assert m["moves"] not in listed, m["name"]


def test_a_layer_metric_that_moves_a_listed_metric_lists_the_same_cells():
    """README "Add a cell", rule 5: a cell joins a per-layer metric exactly
    where it joined the listed end-to-end metric that the layer metric moves."""
    lists = {m["name"]: set(m["workloads"]) for m in MAN["end_to_end"] if "workloads" in m}
    for m in MAN["per_layer"]:
        if m["moves"] in lists:
            assert set(m.get("workloads", ())) == lists[m["moves"]], m["name"]


def test_the_outage_is_read_exactly_where_the_server_is_killed():
    for w in MAN["workloads"]:
        cell = manifest.cell(w["name"])
        killed = manifest.kill_at(cell["traffic"], MAN["run_seconds"]) is not None
        assert killed == ("recover.outage_s" in {m["name"] for m in cell["layers"]}), w["name"]


def test_a_fault_schedule_is_one_kill_inside_the_window():
    assert manifest.kill_at({"loop": "closed"}, 50.0) is None
    assert manifest.kill_at({"faults": [{"kind": "kill", "at_s": 20}]}, 50.0) == 20.0
    for faults in (
        [{"kind": "call", "at_s": 20}],              # a kind that is not built
        [{"kind": "kill", "at_s": 20}] * 2,
        [{"kind": "kill"}],
        [{"kind": "kill", "at_s": 20, "method": "leave"}],
        [{"kind": "kill", "at_s": 50}],              # not inside a 50 s window
        [{"kind": "kill", "at_s": 0}],
    ):
        with pytest.raises(manifest.ManifestError):
            manifest.kill_at({"faults": faults}, 50.0)


def test_admin_calls_are_in_time_order_and_never_beside_a_kill():
    assert manifest.admin_calls({"loop": "closed"}, 50.0) == []
    assert manifest.admin_calls({"faults": [{"kind": "kill", "at_s": 20}]}, 50.0) == []
    leave = {"kind": "admin", "at_s": 14, "op": "leave", "gids": {"every": 3}}
    join = dict(leave, at_s=30, op="join")
    calls = {"faults": [leave, join]}
    assert manifest.admin_calls(calls, 50.0) == [leave, join]
    assert manifest.kill_at(calls, 50.0) is None
    for faults in (
        [join, leave],                               # out of time order
        [leave, dict(join, at_s=14)],
        [leave, {"kind": "kill", "at_s": 20}],       # the two kinds mixed
        [dict(leave, gids={"every": 0})],
        [dict(leave, gids=[3, 6])],
        [{k: v for k, v in leave.items() if k != "op"}],
        [dict(leave, at_s=50)],
    ):
        with pytest.raises(manifest.ManifestError):
            manifest.kill_at({"faults": faults}, 50.0)
        with pytest.raises(manifest.ManifestError):
            manifest.admin_calls({"faults": faults}, 50.0)


def test_an_admin_op_is_one_the_service_takes(monkeypatch):
    assert set(manifest.admin_ops("EngineShardKV")) >= {"join", "leave"}
    assert manifest.admin_ops("NoSuchKV") == ()
    assert manifest.gids({"every": 3}, 10000) == list(range(3, 10000, 3))
    assert len(manifest.gids({"every": 3}, 64)) == 21
    man = manifest.manifest()
    cell = [w for w in man["workloads"] if w["traffic"] == "ycsb-a.reconfig"][0]["name"]
    real = manifest._load

    def unknown_op(path):
        out = real(path)
        for f in out.get("faults", ()):
            if f["kind"] == "admin":
                f["op"] = "drain"
        return out
    monkeypatch.setattr(manifest, "_load", unknown_op)
    with pytest.raises(manifest.ManifestError, match="drain"):
        manifest.cell(cell)


def test_the_reconfig_metrics_are_read_exactly_where_admin_calls_are():
    for w in MAN["workloads"]:
        cell = manifest.cell(w["name"])
        calls = manifest.admin_calls(cell["traffic"], MAN["run_seconds"])
        named = {m["name"] for m in cell["layers"] if m["name"].startswith("reconfig.")}
        assert bool(calls) == bool(named), w["name"]


def test_unknown_workload_is_an_error():
    with pytest.raises(manifest.ManifestError):
        manifest.cell("no-such-cell")
