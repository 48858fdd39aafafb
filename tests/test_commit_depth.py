"""The commit path's depth, which sets the served ticks a pump.

``core.COMMIT_DEPTH`` is read off the tick's phase order: a leader
ingests an entry in phase 5b of tick t, followers append it in phase 3
of t+1, and the leader's phase 4 commits it at t+2.  The served pump
(``pump_cycle.SERVED_TICKS``) fuses one tick more than that, so an
entry ingested at a batch's first tick commits inside the same
program.  A change to the phase order that moves the depth fails here,
on the CPU, for three and five replicas, on one device and on a mesh.
"""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jax.sharding import Mesh  # noqa: E402

from multiraft_tpu.distributed.pump_cycle import SERVED_TICKS  # noqa: E402
from multiraft_tpu.engine.core import COMMIT_DEPTH, EngineConfig  # noqa: E402
from multiraft_tpu.engine.host import EngineDriver  # noqa: E402

G = 8
GROUP = 5
DEVICES = 4


def _mesh():
    devs = jax.devices()
    if len(devs) < DEVICES:
        pytest.skip(f"need {DEVICES} devices, have {len(devs)}")
    return Mesh(np.array(devs[:DEVICES]), axis_names=("groups",))


def _batch(P: int, on_mesh: bool, n: int):
    """A quiet cluster given one command for ``GROUP``, then one fused
    batch of ``n`` ticks: the batch's record, the index the command was
    ingested at, and the driver's counters."""
    d = EngineDriver(
        EngineConfig(G=G, P=P, L=32, E=4, INGEST=4), seed=11,
        mesh=_mesh() if on_mesh else None,
    )
    assert d.run_until_quiet_leaders(2000)
    d.start(GROUP, "cmd")
    p = d.dispatch_ticks(n)
    rec = p.fetch()
    d.complete_ticks(p, rec)
    # Ingested at the batch's first tick, and nowhere else.
    assert rec["accepted"][:, GROUP].tolist() == [1] + [0] * (n - 1)
    assert int(rec["accepted"].sum()) == 1
    return rec, int(rec["start_index"][0, GROUP]) + 1, d.metrics.counters


@pytest.mark.parametrize("on_mesh", [False, True], ids=["one-device", "mesh"])
@pytest.mark.parametrize("P", [3, 5])
def test_an_entry_ingested_at_a_batchs_first_tick_commits_inside_it(P, on_mesh):
    assert SERVED_TICKS == COMMIT_DEPTH + 1
    rec, idx, c = _batch(P, on_mesh, SERVED_TICKS)
    commit = rec["commit_index"][:, GROUP]
    # Not before COMMIT_DEPTH ticks after the ingest, and then at once.
    assert commit[COMMIT_DEPTH - 1] < idx <= commit[COMMIT_DEPTH]
    assert commit[-1] >= idx
    assert c["pump.ingested"] == 1 and c["pump.committed_in_batch"] == 1


@pytest.mark.parametrize("on_mesh", [False, True], ids=["one-device", "mesh"])
@pytest.mark.parametrize("P", [3, 5])
def test_a_batch_one_tick_shorter_does_not_commit_it(P, on_mesh):
    rec, idx, c = _batch(P, on_mesh, SERVED_TICKS - 1)
    assert rec["commit_index"][-1, GROUP] < idx
    assert c["pump.ingested"] == 1 and c["pump.committed_in_batch"] == 0
