"""Keep examples/ runnable: each script is executed as a subprocess
the way a user would run it (fresh interpreter, no pytest fixtures).

All examples LAUNCH together (module-scoped) and each test merely
awaits its own — the scripts are independent process trees with real
idle phases (server readiness polls, pump cadences), so concurrent
execution overlaps their waits and cuts the wall-clock several-fold
while per-example pass/fail reporting stays intact."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = sorted(
    f for f in os.listdir(os.path.join(ROOT, "examples")) if f.endswith(".py")
)

# Launched once, all concurrently, on first use (the scripts are
# independent process trees; overlapping their readiness polls and
# pump-cadence idle cuts the module's wall-clock vs serial runs).
# Output goes to temp FILES, not pipes — nothing drains a pipe until
# the script's own test runs, and a chatty example would block on the
# ~64 KiB pipe capacity, silently serializing the launch.
_PROCS: dict = {}


def launch(scripts) -> dict:
    import tempfile

    # conftest exports JAX_PLATFORMS=cpu: examples (and the server
    # children they spawn) inherit it.
    env = dict(os.environ)
    for script in scripts:
        if script in _PROCS:
            continue
        out = tempfile.TemporaryFile(mode="w+")
        errf = tempfile.TemporaryFile(mode="w+")
        proc = subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "examples", script)],
            stdout=out,
            stderr=errf,
            text=True,
            cwd=ROOT,
            env=env,
        )
        _PROCS[script] = (proc, out, errf)
    return _PROCS


@pytest.fixture(scope="module")
def running_examples(request):
    # Launch only the examples this run SELECTED (pytest -k one_script
    # must not fan out all 13 process trees).
    wanted = {
        item.callspec.params["script"]
        for item in request.session.items
        if getattr(item, "callspec", None) is not None
        and "script" in item.callspec.params
        and item.function.__name__ == "test_example_runs"
    }
    yield launch(sorted(wanted) or EXAMPLES)
    for proc, out, errf in _PROCS.values():
        if proc.poll() is None:
            proc.kill()
        out.close()
        errf.close()


@pytest.mark.timeout_s(420)
@pytest.mark.parametrize("script", EXAMPLES)
def test_example_runs(script, running_examples):
    proc, out, errf = running_examples[script]
    try:
        proc.wait(timeout=400)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        errf.seek(0)
        raise AssertionError(f"{script} timed out:\n{errf.read()[-2000:]}")
    out.seek(0)
    errf.seek(0)
    stdout, stderr = out.read(), errf.read()
    assert proc.returncode == 0, (
        f"{script} failed:\n{stdout[-2000:]}\n{stderr[-2000:]}"
    )
