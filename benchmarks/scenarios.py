"""Scenario benchmarks: the stress rig of SURVEY §7.2 step 8.

``bench.py`` at the repo root is the headline number (steady-state
commits/sec).  This module measures the *hard* regimes the reference's
test gates imply (leader churn, InstallSnapshot storms after laggard
recovery, skewed shard load, group-count scaling), each as one JSON
line on stdout:

    python -m benchmarks.scenarios churn
    python -m benchmarks.scenarios snapstorm
    python -m benchmarks.scenarios skew
    python -m benchmarks.scenarios sweep
    python -m benchmarks.scenarios all

Shapes default to the bench config (G=10k x P=3) and scale down via
MULTIRAFT_BENCH_G / MULTIRAFT_BENCH_CHUNK for smoke runs.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time
from typing import Dict

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _cfg(G=None, P=None, L=192, E=48, ingest=48):
    """Defaults match bench.py's measured sweet spot (E=INGEST=48,
    L=192, re-tuned round 4 after the phase fusion — see the
    operating-point note there; E multiples of 32 collapse).  P
    comes from MULTIRAFT_BENCH_P so every scenario is
    peer-count-generic."""
    from multiraft_tpu.engine.core import EngineConfig

    G = G or int(os.environ.get("MULTIRAFT_BENCH_G", "10000"))
    P = P or int(os.environ.get("MULTIRAFT_BENCH_P", "3"))
    return EngineConfig(G=G, P=P, L=L, E=E, INGEST=ingest, HB_TICKS=9)


def _chunk() -> int:
    return int(os.environ.get("MULTIRAFT_BENCH_CHUNK", "200"))


@functools.cache
def _run_ticks_vec(cfg, n_ticks):
    """Like core.run_ticks but with a per-group ingest *vector* (the
    skewed-firehose path)."""
    import jax
    import jax.numpy as jnp

    from multiraft_tpu.engine.core import tick_impl

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def run(state, inbox, new_cmds, key):
        def body(carry, i):
            st, mb = carry
            st, mb, _ = tick_impl(cfg, st, mb, new_cmds, jax.random.fold_in(key, i))
            return (st, mb), None

        (state, inbox), _ = jax.lax.scan(
            body, (state, inbox), jnp.arange(n_ticks, dtype=jnp.int32)
        )
        return state, inbox

    return run


def _boot(cfg, seed=7):
    """Elect leaders everywhere; returns (state, inbox)."""
    import jax
    import jax.numpy as jnp

    from multiraft_tpu.engine.core import empty_mailbox, init_state, run_ticks

    key = jax.random.PRNGKey(seed)
    state = init_state(cfg, jax.random.fold_in(key, 0))
    inbox = empty_mailbox(cfg)
    state, inbox = run_ticks(cfg, state, inbox, _chunk(), 0, key)
    jax.block_until_ready(state.term)
    leaders = int(jnp.sum((state.role == 2) & state.alive))
    log(f"boot: leaders={leaders}/{cfg.G}")
    return state, inbox, key


def _commits(state) -> np.ndarray:
    return np.asarray(state.commit).max(axis=1).astype(np.int64)


def _emit(metric: str, value: float, unit: str, baseline: float,
          **extra) -> Dict:
    rec = {
        "metric": metric,
        "value": round(value, 1),
        "unit": unit,
        "vs_baseline": round(value / baseline, 3) if baseline else None,
        **extra,
    }
    print(json.dumps(rec), flush=True)
    return rec


def bench_churn() -> Dict:
    """Sustained throughput while a slice of leaders is killed every
    chunk (the batched form of the reference's leader-failure churn,
    raft/test_test.go:957-1107).  Kills 10% of groups' leaders each
    round, revives the previous victims."""
    import jax
    import jax.numpy as jnp

    from multiraft_tpu.engine.core import run_ticks

    cfg = _cfg()
    state, inbox, key = _boot(cfg)
    CHUNK = _chunk()
    ROUNDS = int(os.environ.get("MULTIRAFT_BENCH_CHUNKS", "5"))
    kill_n = max(1, cfg.G // 10)
    rng = np.random.default_rng(0)
    # Warm the loaded-variant compile before timing.
    state, inbox = run_ticks(cfg, state, inbox, CHUNK, cfg.INGEST,
                             jax.random.fold_in(key, 1))
    jax.block_until_ready(state.term)

    c0 = _commits(state)
    prev_victims = None
    t0 = time.perf_counter()
    for r in range(ROUNDS):
        role = np.asarray(state.role)
        alive = np.asarray(state.alive)
        leaders = ((role == 2) & alive).argmax(axis=1)
        victims = rng.choice(cfg.G, size=kill_n, replace=False)
        alive_mask = jnp.asarray(alive)
        if prev_victims is not None:
            g, p = prev_victims
            alive_mask = alive_mask.at[g, p].set(True)
        alive_mask = alive_mask.at[victims, leaders[victims]].set(False)
        state = state._replace(alive=alive_mask)
        prev_victims = (victims, leaders[victims])
        state, inbox = run_ticks(cfg, state, inbox, CHUNK, cfg.INGEST,
                                 jax.random.fold_in(key, 100 + r))
        jax.block_until_ready(state.term)
        log(f"churn round {r+1}/{ROUNDS}: killed {kill_n} leaders")
    elapsed = time.perf_counter() - t0
    commits = int((_commits(state) - c0).sum())
    return _emit(
        "commits_per_sec_under_leader_churn",
        commits / elapsed,
        "commits/s",
        1_000_000.0,
        groups=cfg.G,
        killed_per_round=kill_n,
    )


def bench_snapstorm() -> Dict:
    """InstallSnapshot storm: one follower per group is dead while the
    log advances past the ring capacity, then every group fast-forwards
    its laggard at once (reference: raft 2D snapshot tests at scale).
    Metric: entries fast-forwarded per second during recovery."""
    import jax
    import jax.numpy as jnp

    from multiraft_tpu.engine.core import run_ticks

    # Small ring so laggards overflow it quickly (and E+INGEST+2 < L).
    cfg = _cfg(L=32, E=8, ingest=8)
    state, inbox, key = _boot(cfg)
    CHUNK = _chunk()
    # Kill one non-leader per group (P-generic: pick the highest
    # replica id that is not the leader).
    role = np.asarray(state.role)
    alive = np.asarray(state.alive)
    leaders = ((role == 2) & alive).argmax(axis=1)
    victim = np.where(leaders != cfg.P - 1, cfg.P - 1, cfg.P - 2)
    state = state._replace(
        alive=state.alive.at[np.arange(cfg.G), victim].set(False)
    )
    # Outrun the ring: advance well past L entries while laggard sleeps.
    rounds = 0
    while True:
        state, inbox = run_ticks(cfg, state, inbox, CHUNK, cfg.INGEST,
                                 jax.random.fold_in(key, 200 + rounds))
        jax.block_until_ready(state.term)
        rounds += 1
        lag = _commits(state) - np.asarray(state.commit)[
            np.arange(cfg.G), victim
        ]
        if (lag > cfg.L).all() or rounds >= 50:
            break
    lag_before = _commits(state) - np.asarray(state.commit)[
        np.arange(cfg.G), victim
    ]
    log(f"snapstorm: median lag at revival {int(np.median(lag_before))} entries")
    # Revive everyone at once: the storm. No new load during recovery.
    state = state._replace(alive=jnp.ones((cfg.G, cfg.P), bool))
    t0 = time.perf_counter()
    ticks = 0
    while ticks < 50 * CHUNK:
        state, inbox = run_ticks(cfg, state, inbox, CHUNK, 0,
                                 jax.random.fold_in(key, 300 + ticks))
        jax.block_until_ready(state.term)
        ticks += CHUNK
        commit = np.asarray(state.commit)
        caught = (commit[np.arange(cfg.G), victim] >= _commits(state)).mean()
        if caught == 1.0:
            break
    elapsed = time.perf_counter() - t0
    bases = np.asarray(state.base)[np.arange(cfg.G), victim]
    assert (bases > 0).mean() > 0.9, "snapshot fast-forward path not exercised"
    total_ff = int(lag_before.sum())
    return _emit(
        "snapshot_fastforward_entries_per_sec",
        total_ff / elapsed,
        "entries/s",
        0,
        groups=cfg.G,
        recovery_ticks=ticks,
        caught_up_frac=float(
            (np.asarray(state.commit)[np.arange(cfg.G), victim]
             >= _commits(state)).mean()
        ),
    )


def bench_skew() -> Dict:
    """Skewed shard load (step 8): 10% hot groups ingest at full rate,
    the rest trickle — the regime shard rebalancing exists for."""
    import jax
    import jax.numpy as jnp

    cfg = _cfg()
    state, inbox, key = _boot(cfg)
    CHUNK = _chunk()
    ROUNDS = int(os.environ.get("MULTIRAFT_BENCH_CHUNKS", "5"))
    hot = cfg.G // 10
    new_cmds = np.ones(cfg.G, np.int32)
    new_cmds[:hot] = cfg.INGEST
    new_cmds = jnp.asarray(new_cmds)
    run = _run_ticks_vec(cfg, CHUNK)
    state, inbox = run(state, inbox, new_cmds, jax.random.fold_in(key, 1))
    jax.block_until_ready(state.term)
    c0 = _commits(state)
    t0 = time.perf_counter()
    for r in range(ROUNDS):
        state, inbox = run(state, inbox, new_cmds,
                           jax.random.fold_in(key, 400 + r))
        jax.block_until_ready(state.term)
    elapsed = time.perf_counter() - t0
    delta = _commits(state) - c0
    hot_rate = delta[:hot].sum() / elapsed
    cold_rate = delta[hot:].sum() / elapsed
    return _emit(
        "commits_per_sec_skewed_load",
        (hot_rate + cold_rate),
        "commits/s",
        1_000_000.0,
        groups=cfg.G,
        hot_groups=hot,
        hot_commits_per_sec=round(float(hot_rate), 1),
        cold_commits_per_sec=round(float(cold_rate), 1),
    )


def bench_sweep() -> Dict:
    """(G, P) scaling sweep: commits/sec at G = 1k/10k (and 100k with
    MULTIRAFT_BENCH_SWEEP_MAX=100000) for every peer count in
    MULTIRAFT_BENCH_SWEEP_P (default "3"; "3,5" reproduces
    BENCHMARKS.md's full table incl. config #5 100k x 5) on one chip."""
    import jax

    from multiraft_tpu.engine.core import run_ticks

    CHUNK = _chunk()
    ROUNDS = int(os.environ.get("MULTIRAFT_BENCH_CHUNKS", "3"))
    gmax = int(os.environ.get("MULTIRAFT_BENCH_SWEEP_MAX", "10000"))
    peer_counts = [
        int(p)
        for p in os.environ.get("MULTIRAFT_BENCH_SWEEP_P", "3").split(",")
    ]
    points = {}
    for P in peer_counts:
        for G in [g for g in (1000, 10000, 100000) if g <= gmax]:
            # Per-scale operating point (measured, not modeled — the
            # round-3 roofline showed the tick is NOT bandwidth-bound):
            # at 100k groups a leaner ring wins; at <=10k the round-4
            # retune (48/192, _cfg's default) follows the fused tick's
            # envelope — see BENCHMARKS.md "Roofline".
            cfg = (
                _cfg(G=G, P=P, L=112, E=28, ingest=28)
                if G >= 100000
                else _cfg(G=G, P=P)
            )
            state, inbox, key = _boot(cfg)
            state, inbox = run_ticks(cfg, state, inbox, CHUNK, cfg.INGEST,
                                     jax.random.fold_in(key, 1))
            jax.block_until_ready(state.term)
            c0 = _commits(state)
            t0 = time.perf_counter()
            for r in range(ROUNDS):
                state, inbox = run_ticks(cfg, state, inbox, CHUNK,
                                         cfg.INGEST,
                                         jax.random.fold_in(key, 500 + r))
                jax.block_until_ready(state.term)
            elapsed = time.perf_counter() - t0
            rate = int((_commits(state) - c0).sum()) / elapsed
            points[f"G={G},P={P}"] = round(rate, 1)
            log(f"sweep G={G} P={P}: {rate:,.0f} commits/s")
    best = max(points.values())
    return _emit(
        "commits_per_sec_scaling_sweep",
        best,
        "commits/s",
        1_000_000.0,
        points=points,
    )


SCENARIOS = {
    "churn": bench_churn,
    "snapstorm": bench_snapstorm,
    "skew": bench_skew,
    "sweep": bench_sweep,
}


def main(argv) -> None:
    from multiraft_tpu.utils.device import claim_device, device_line

    log(f"scenarios: {device_line(claim_device())}")
    which = argv[1] if len(argv) > 1 else "all"
    names = list(SCENARIOS) if which == "all" else [which]
    for n in names:
        log(f"=== scenario: {n} ===")
        SCENARIOS[n]()


if __name__ == "__main__":
    main(sys.argv)
