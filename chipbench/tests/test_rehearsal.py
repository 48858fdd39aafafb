"""A rehearsal on the CPU prints, last, a line with the contract's keys
and no other (but the mark that says it is a rehearsal, not a result)."""

import json
import os
import subprocess
import sys

import pytest

import manifest

CELL = manifest.manifest()["workloads"][0]["name"]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contract_line(trace):
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 5), "--seconds", "4", "--trace", str(trace),
         "--rehearse-cpu"],
        cwd=manifest.ROOT, text=True, capture_output=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line.pop("rehearsal") is True
    want = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line) == (want | {"breakdown"} if trace else want)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    cell = manifest.cell(CELL)  # the cell's own lists: tails are opt-in
    names = {m["name"] for m in cell["layers" if trace else "end_to_end"]}
    assert set(line["metrics"]) <= names
    if not trace:
        assert set(line["metrics"]) == names
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and m["value"] > 0, name
    device = {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(line["device"]) == (device | {"busy_s", "window_s"} if trace else device)
    assert not os.path.exists(os.path.join(manifest.ROOT, ".chipbench_run", CELL))


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
