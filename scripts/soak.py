"""Long-running soak: the full fault cocktail against the batched
engine with every Raft safety invariant asserted on every tick, until
the time budget expires.

    python scripts/soak.py [minutes] [--prevote] [--seed N]

Rotates through fault regimes (calm, lossy, reordering, churn,
partitions, everything-at-once) while a client firehose runs; prints a
line per regime and a final summary. Exit code 0 = no invariant ever
violated. This is the open-ended form of tests/test_engine_fuzz.py —
run it for hours before a release.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> int:
    minutes = 10.0
    prevote = "--prevote" in sys.argv
    seed = 0
    argv = sys.argv[1:]
    if "--seed" in argv:
        i = argv.index("--seed")
        if i + 1 >= len(argv):
            print("--seed requires a value", file=sys.stderr)
            return 2
        seed = int(argv[i + 1])
        del argv[i : i + 2]  # the value must not count as a positional
    args = [a for a in argv if not a.startswith("--")]
    if args:
        minutes = float(args[0])

    from multiraft_tpu.utils.device import claim_device, device_line

    # Runs on what JAX selects (JAX_PLATFORMS), and says which.
    print(f"soak: {device_line(claim_device())}", flush=True)

    from multiraft_tpu.engine.core import EngineConfig
    from multiraft_tpu.engine.host import EngineDriver
    from multiraft_tpu.engine.invariants import InvariantMonitor

    cfg = EngineConfig(G=8, P=3, L=32, E=4, INGEST=4, prevote=prevote)
    d = EngineDriver(cfg, seed=seed)
    mon = InvariantMonitor(d)
    rng = np.random.default_rng(seed + 777)

    REGIMES = [
        ("calm", dict(drop=0.0, reorder=0.0, p_crash=0.0, p_cut=0.0)),
        ("lossy", dict(drop=0.2, reorder=0.0, p_crash=0.0, p_cut=0.0)),
        ("reordering", dict(drop=0.1, reorder=2 / 3, p_crash=0.0, p_cut=0.0)),
        ("churn", dict(drop=0.0, reorder=0.0, p_crash=0.04, p_cut=0.0)),
        ("partitions", dict(drop=0.0, reorder=0.0, p_crash=0.0, p_cut=0.04)),
        ("cocktail", dict(drop=0.15, reorder=0.5, p_crash=0.03, p_cut=0.03)),
    ]

    deadline = time.time() + minutes * 60
    dead: set = set()
    cut: set = set()
    total_ticks = 0
    regime_i = 0
    print(f"soak: {minutes:.0f} min, G={cfg.G} P={cfg.P} prevote={prevote}")
    while time.time() < deadline:
        name, r = REGIMES[regime_i % len(REGIMES)]
        regime_i += 1
        d.drop_prob = r["drop"]
        d.set_reorder(r["reorder"])
        t0 = time.time()
        c0 = d.commits_total
        ticks = 0
        while time.time() - t0 < 20 and time.time() < deadline:
            if rng.random() < r["p_crash"]:
                g, p = int(rng.integers(cfg.G)), int(rng.integers(cfg.P))
                if (g, p) not in dead:
                    d.set_alive(g, p, False)
                    dead.add((g, p))
            if dead and rng.random() < 0.3:
                g, p = list(dead)[int(rng.integers(len(dead)))]
                d.restart_replica(g, p)
                mon.note_restart(g, p)
                dead.discard((g, p))
            if rng.random() < r["p_cut"]:
                g, p = int(rng.integers(cfg.G)), int(rng.integers(cfg.P))
                if (g, p) not in cut:
                    d.partition_replica(g, p, False)
                    cut.add((g, p))
            if cut and rng.random() < 0.3:
                g, p = list(cut)[int(rng.integers(len(cut)))]
                d.partition_replica(g, p, True)
                cut.discard((g, p))
            if rng.random() < 0.6:
                # start_bulk: no per-command payload binding (the soak
                # never applies payloads, so start() entries would
                # accumulate in driver.payloads forever).
                counts = np.zeros(cfg.G, np.int64)
                counts[int(rng.integers(cfg.G))] = 1
                d.start_bulk(counts)
            d.step()
            mon.observe()
            ticks += 1
        total_ticks += ticks
        # Bound memory for hours-long runs: drop monitor records below
        # the cluster-wide snapshot floor (they are unverifiable — no
        # replica still holds those ring slots).
        mon.prune_below_snapshot_floor()
        print(
            f"soak[{name:>10}]: {ticks} ticks, "
            f"+{d.commits_total - c0} commits, "
            f"dead={len(dead)} cut={len(cut)}",
            flush=True,
        )
    # Heal and verify final progress.
    d.drop_prob = 0.0
    d.set_reorder(0.0)
    for g, p in list(dead):
        d.restart_replica(g, p)
        mon.note_restart(g, p)
    for g, p in list(cut):
        d.partition_replica(g, p, True)
    before = d.commits_total
    d.start_bulk(np.ones(cfg.G, np.int64))
    for _ in range(400):
        d.step()
        mon.observe()
        if d.commits_total >= before + cfg.G:
            break
    assert d.commits_total >= before + cfg.G, "no progress after heal"
    for g in range(cfg.G):
        d.check_log_matching(g)
    print(
        f"soak OK: {total_ticks} ticks, {d.commits_total} commits, "
        f"all invariants held on every tick"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
