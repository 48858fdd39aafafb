"""Roofline accounting for the engine tick — is 212M commits/s HBM-
bound, and is the next 2x available?

Two parts (BENCHMARKS.md "Roofline" section reports both):

* ARITHMETIC: bytes touched per tick from the tensor shapes.  The
  dominant arrays at the bench shape (G=10k, P=3) are the log ring
  ``log_term [G,P,L] i32`` and the append-channel mailbox
  ``ar_terms [G,P,P,E] i32`` (+ 15 [G,P,P] lane fields per edge and
  11 [G,P] sender lanes, ``core.SENDER_LANES``).  The tick
  reads state+inbox and writes state+outbox; ring reads appear in
  several phases, so a fusion-count multiplier is reported as a range.

* EXPERIMENT: measured ms/tick across L (ring capacity) and E/INGEST
  sweeps at fixed G.  If tick time tracks the L-dependent byte count,
  the tick is bandwidth-bound and narrower dtypes / ring packing buy
  the next step; if it is flat in L, the ceiling is elsewhere
  (fusion/launch overhead, serial phase chains).

Usage:  python -m benchmarks.roofline            # sweep, JSON lines
"""

from __future__ import annotations

import json
import sys
import time

# Peak HBM bandwidth in bytes/s, keyed by jax's ``device_kind``.  A
# device that is not here is an error, not a default: a roofline share
# against another chip's peak is not a number.
# Source: Google Cloud documentation, "TPU v5e" system architecture
# (16 GB HBM2e per chip at 819 GB/s).
HBM_PEAK_BYTES_PER_S = {
    "TPU v5 lite": 819e9,
}


def bytes_per_tick(G: int, P: int, L: int, E: int, passes_log: float = 2.0):
    """Shape-derived traffic estimate (bytes) for one tick: every
    state/mailbox tensor read once + written once, with the log ring
    counted ``passes_log`` times on the read side (ring reads appear
    in the vote, append-handle, and append-send phases; XLA fuses some
    but not all into one pass)."""
    i32 = 4
    log = G * P * L * i32
    ar_terms = G * P * P * E * i32
    # vr/vp/ar/ap scalar lane fields: per edge, and per sender
    lanes = 15 * G * P * P * i32 + 11 * G * P * i32
    gp = 14 * G * P * i32        # term/vote/role/commit/... columns
    gpp = 3 * G * P * P * i32    # next/match/votes
    state = log + gp + gpp
    mailbox = ar_terms + lanes
    # read state (+extra log passes) + read inbox + write state + write outbox
    return (state + (passes_log - 1) * log) + mailbox + state + mailbox


def measure(cfg, n_ticks: int = 200, reps: int = 3):
    """(best_s_per_tick, commits_per_sec_at_best).  Each timed chunk
    ends in a scalar COMMIT READBACK: a value the host reads cannot
    arrive before the scan has finished, and the assertion on it is the
    proof that the chunk really committed work.  ``block_until_ready``
    is an equally good fence on the installed jax/libtpu —
    chip_smoke.py's tick leg checks on every run that a readback
    issued after it returns finds nothing left to wait for."""
    import jax
    import jax.numpy as jnp

    from multiraft_tpu.engine.core import (
        empty_mailbox,
        init_state,
        run_ticks,
    )

    def commits(st):
        return int(jnp.max(st.commit, axis=1).sum())  # forces the sync

    key = jax.random.PRNGKey(5)
    state = init_state(cfg, key)
    inbox = empty_mailbox(cfg)
    state, inbox = run_ticks(cfg, state, inbox, n_ticks, 0, key)  # elect+compile
    state, inbox = run_ticks(
        cfg, state, inbox, n_ticks, cfg.INGEST, jax.random.fold_in(key, 1)
    )  # compile loaded + fill
    c0 = commits(state)
    best = float("inf")
    rate = 0.0
    for r in range(reps):
        t0 = time.perf_counter()
        state, inbox = run_ticks(
            cfg, state, inbox, n_ticks, cfg.INGEST, jax.random.fold_in(key, 2 + r)
        )
        c1 = commits(state)
        dt = time.perf_counter() - t0
        assert c1 > c0, "no commits in a timed chunk — measurement invalid"
        if dt / n_ticks < best:
            best = dt / n_ticks
            rate = (c1 - c0) / dt
        c0 = c1
    return best, rate


def main(argv) -> None:
    from multiraft_tpu.engine.core import EngineConfig
    from multiraft_tpu.utils.device import claim_device

    G = int(argv[1]) if len(argv) > 1 else 10_000
    dev = claim_device()
    platform = dev["platform"]
    HBM = HBM_PEAK_BYTES_PER_S.get(dev["kind"])
    if HBM is None:
        sys.exit(
            f"roofline: no HBM peak on record for device_kind "
            f"{dev['kind']!r} (platform {platform}); add it to "
            "HBM_PEAK_BYTES_PER_S with its source"
        )

    sweeps = [
        # L sweep at fixed E/INGEST: bandwidth-bound <=> time tracks L.
        dict(L=48, E=8, INGEST=8),
        dict(L=64, E=8, INGEST=8),
        dict(L=112, E=8, INGEST=8),
        dict(L=224, E=8, INGEST=8),
        # operating points: the bench's 28/112 vs neighbors — maps the
        # E-cliff (32/128 doubles tick time for +11% bytes: a compile/
        # shape cliff, not bandwidth).
        dict(L=80, E=20, INGEST=20),
        dict(L=96, E=24, INGEST=24),
        dict(L=112, E=28, INGEST=28),
        dict(L=120, E=30, INGEST=30),
        dict(L=128, E=32, INGEST=32),
    ]
    for s in sweeps:
        cfg = EngineConfig(
            G=G, P=3, HB_TICKS=9,
            use_pallas=(platform == "tpu"), **s,
        )
        per_tick, commits_s = measure(cfg)
        ms = per_tick * 1e3
        b2 = bytes_per_tick(G, 3, s["L"], s["E"], passes_log=2.0)
        print(
            json.dumps({
                "G": G, **s, "platform": platform,
                "device_kind": dev["kind"],
                "ms_per_tick": round(ms, 4),
                "commits_per_sec": round(commits_s, 0),
                "bytes_per_tick_est": b2,
                "est_GBps": round(b2 / (ms * 1e-3) / 1e9, 1),
                "frac_hbm_roofline": round(b2 / (ms * 1e-3) / HBM, 3),
                "bracket_1x_3x_GBps": [
                    round(bytes_per_tick(G, 3, s["L"], s["E"], p)
                          / (ms * 1e-3) / 1e9, 1)
                    for p in (1.0, 3.0)
                ],
            }),
            flush=True,
        )


if __name__ == "__main__":
    main(sys.argv)
