"""The trace reduction, on a hand-made trace whose answers are plain
arithmetic, and on a recording from the chip (the first 40 ms of the
device planes of a traced ``kv10k.ycsb-a`` run, my chip run of PR 24,
kept as plain intervals: ``trace_reduce.py --dump-json``)."""

import json
import os

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6  # ns


def test_hand_made_trace():
    ops = [
        (0 * MS, 10 * MS, "%while.1"),          # encloses the next two
        (1 * MS, 4 * MS, "%fusion.1"),
        (5 * MS, 9 * MS, "%fusion.2"),
        (30 * MS, 31 * MS, "%add.1"),
        (60 * MS, 70 * MS, "%while.1"),
        (61 * MS, 64 * MS, "%fusion.1"),
    ]
    modules = [
        (0 * MS, 10 * MS, "jit_step_ticks(42)"),
        (30 * MS, 32 * MS, "jit_add(7)"),       # 1 ms inside it runs nothing
        (60 * MS, 70 * MS, "jit_step_ticks(42)"),
    ]
    out = tr.reduce({"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules}},
                    "jit_step_ticks")
    assert out["window_s"] == pytest.approx(0.070)
    assert out["busy_s"] == pytest.approx(0.021)            # 10 + 1 + 10 ms
    assert out["metrics"] == {"program_s": pytest.approx(0.020), "programs": 2}
    own = dict(out["breakdown"]["device_ops"])
    assert own["%while.1"] == pytest.approx(0.010)          # 20 ms less its body's 10
    assert own["%fusion.1"] == pytest.approx(0.006)
    assert own["%fusion.2"] == pytest.approx(0.004)
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps["after jit_step_ticks before jit_add"] == pytest.approx(0.020)
    assert gaps["after jit_add before jit_step_ticks"] == pytest.approx(0.028)
    assert gaps["inside a program, between its operations"] == pytest.approx(0.001)
    # busy + every gap = the window
    assert out["busy_s"] + sum(gaps.values()) == pytest.approx(out["window_s"])


def test_two_devices_are_averaged():
    one = {"XLA Ops": [(0, 10 * MS, "%a")], "XLA Modules": [(0, 10 * MS, "jit_f(1)")]}
    two = {"XLA Ops": [(0, 30 * MS, "%a")], "XLA Modules": [(0, 30 * MS, "jit_f(1)")]}
    out = tr.reduce({"/device:TPU:0": one, "/device:TPU:1": two}, "jit_f")
    assert out["busy_s"] == pytest.approx(0.020) and out["devices"] == 2
    assert out["metrics"]["programs"] == 1 and out["metrics"]["program_s"] == pytest.approx(0.020)


def test_no_device_plane_is_an_error():
    with pytest.raises(SystemExit):
        tr.reduce({}, "jit_step_ticks")


def test_names():
    assert tr.short_name("%fusion.3 = s32[10000,3]{0,1:T(4,128)} fusion(%p)") == "%fusion.3"
    assert tr.base_name("jit_step_ticks(2414905637756280514)") == "jit_step_ticks"


def test_recorded_trace_from_the_chip():
    with open(os.path.join(HERE, "trace_kv10k_ycsb-a_40ms.json")) as f:
        raw = json.load(f)
    planes = {p: {l: [tuple(e) for e in evs] for l, evs in ls.items()}
              for p, ls in raw.items()}
    out = tr.reduce(planes, "jit_step_ticks")
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(0.034304038)
    assert out["busy_s"] == pytest.approx(0.004606755)       # 13.4 % busy
    assert out["metrics"]["programs"] == 3
    assert out["metrics"]["program_s"] == pytest.approx(0.004614799)  # 1.54 ms a two-tick program
    top = out["breakdown"]["device_ops"][0]
    assert top[0] == "%select_reduce_fusion.22" and top[1] == pytest.approx(0.000639133)
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps["after jit__reduce_sum before jit_add"] == pytest.approx(0.014099944)
    assert out["busy_s"] + sum(gaps.values()) == pytest.approx(out["window_s"], rel=1e-3)
