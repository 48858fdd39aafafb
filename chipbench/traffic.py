"""The one general traffic generator: a mix file's parameters and a seed
in, every client's whole operation sequence out — before the window, so
the window spends the client's CPU on the wire and not on the dice.

A mix (``chipbench/traffic/<name>.json``) gives ``loop`` ("closed" with
``clients``, or "open" with ``rate_ops_s``, ``sessions`` and optionally
``burst``: :func:`arrivals`), the shares
``read`` and ``update``, ``distribution`` ("zipfian" or "uniform") with
``theta``, and ``path`` ("command": one ``<service>.command`` RPC per
operation).  Every seed draws from the same distribution over the same
number of records: a seed changes which keys are hot, the order of
operations and, in an open loop, the instants they are due, never the
amount of work.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

TAG = 10                # digits at the head of every value: who wrote it
LOADER = 99             # the "client" that wrote the loaded records
CYCLE_S = 1.0           # an open loop's bursts come once a cycle (arrivals)
_ALPHABET = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz0123456789", np.uint8)


def zipf_cdf(n: int, theta: float) -> np.ndarray:
    """CDF over ranks 0..n-1 with P(rank r) proportional to 1/(r+1)**theta
    (YCSB's zipfian with constant ``theta``; rank 0 is the hottest)."""
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** theta
    cdf = np.cumsum(w)
    return cdf / cdf[-1]


def code(client: int, n: int) -> int:
    """The number a value's tag spells: ``client`` wrote it as its
    ``n``-th operation (for the loader, ``n`` is the record)."""
    return client * 10 ** (TAG - 2) + n


class Records:
    """``recordcount`` records of ``valuebytes`` bytes, made from the seed.

    A value is its tag (``TAG`` digits), ``|``, then a slice of one
    seeded pool of letters and digits, so any value ever written can be
    rebuilt from its tag alone when the check wants all its bytes."""

    def __init__(self, config: Dict[str, Any], seed: int) -> None:
        self.n = int(config["recordcount"])
        self.valuebytes = int(config["fieldcount"]) * int(config["fieldlength"])
        self.body = self.valuebytes - TAG - 1
        rng = np.random.default_rng([seed, 1])
        self.pool_len = 1 << 20
        pool = _ALPHABET[rng.integers(0, len(_ALPHABET), self.pool_len + self.body)]
        self.pool = pool.tobytes().decode("ascii")
        # YCSB scrambles ranks over the key space: the hot keys are not
        # neighbours, and here not in neighbouring groups either.
        self.key_of_rank = rng.permutation(self.n)
        self.keys = [f"user{i:012d}" for i in range(self.n)]

    def value(self, client: int, n: int) -> str:
        c = code(client, n)
        off = (c * 2654435761) % self.pool_len
        return f"{c:0{TAG}d}|" + self.pool[off:off + self.body]

    def value_of_code(self, c: int) -> str:
        return self.value(c // 10 ** (TAG - 2), c % 10 ** (TAG - 2))

    def load_ops(self):
        return [("Put", k, self.value(LOADER, i)) for i, k in enumerate(self.keys)]


def sequences(traffic: Dict[str, Any], records: Records, seed: int,
              ops_per_client: int, clients: Optional[int] = None):
    """``(is_update, key_index)``: two ``[clients, ops_per_client]``
    arrays, client ``c``'s ``n``-th operation at ``[c, n]``.  A closed
    mix says how many ``clients``; an open loop gives the rows of its
    record (``loadgen.OpenLoop.ROWS``)."""
    clients = int(traffic["clients"] if clients is None else clients)
    read, update = float(traffic["read"]), float(traffic["update"])
    if abs(read + update - 1.0) > 1e-9:
        raise ValueError("traffic: read + update must be 1")
    rng = np.random.default_rng([seed, 2])
    shape = (clients, ops_per_client)
    if traffic["distribution"] == "zipfian":
        cdf = zipf_cdf(records.n, float(traffic["theta"]))
        rank = np.searchsorted(cdf, rng.random(shape), side="right")
        rank = np.minimum(rank, records.n - 1)
    elif traffic["distribution"] == "uniform":
        rank = rng.integers(0, records.n, shape)
    else:
        raise ValueError(f"traffic: distribution {traffic['distribution']!r}")
    is_update = rng.random(shape) < update
    return is_update, records.key_of_rank[rank].astype(np.int64)


def arrivals(traffic: Dict[str, Any], seed: int, duration_s: float) -> np.ndarray:
    """When every operation of an open loop is due, in seconds after the
    loop starts: sorted, inside ``[0, duration_s)``.

    ``rate_ops_s`` is the mean offered rate.  Time is cut into cycles of
    ``CYCLE_S``; with ``burst`` = ``{"factor", "duty"}`` the first
    ``duty`` of every cycle is offered ``factor`` x the mean and the rest
    ``(1 - factor x duty) / (1 - duty)`` x the mean, so the mean stays
    ``rate_ops_s`` (the shape of ``benchmarks/openloop.py``'s ``bursty``
    mode, restated).  Each stretch of constant rate gets the
    whole number of arrivals its rate gives it (the cumulative count,
    rounded), placed as sorted uniforms: a Poisson process conditioned
    on its count in every stretch.  So a seed (stream ``[seed, 4]``)
    moves the instants and never the amount of work: every seed offers
    the same number of operations in every phase of every cycle.
    """
    rate, cycle = float(traffic["rate_ops_s"]), CYCLE_S
    burst = traffic.get("burst") or {"factor": 1.0, "duty": 1.0}
    factor, duty = float(burst["factor"]), float(burst["duty"])
    if not (rate > 0 and 0 < duty <= 1 and 0 < factor * duty <= 1):
        raise ValueError(f"traffic: rate {rate}, burst {burst}: nothing left for the rest of a cycle")
    # The edges of the stretches, and the rate of each as a multiple of the mean.
    starts = np.arange(0.0, duration_s, cycle)
    edges = np.unique(np.minimum(
        np.concatenate([starts, starts + duty * cycle, [duration_s]]), duration_s))
    in_burst = np.mod(edges[:-1], cycle) < duty * cycle - 1e-9
    level = np.where(in_burst, factor, (1.0 - factor * duty) / max(1.0 - duty, 1e-12))
    due_by_edge = np.concatenate([[0.0], np.cumsum(rate * level * np.diff(edges))])
    counts = np.diff(np.round(due_by_edge).astype(np.int64))
    stretch = np.repeat(np.arange(len(counts)), counts)
    u = np.random.default_rng([seed, 4]).random(len(stretch))
    return np.sort(edges[stretch] + u * np.diff(edges)[stretch])
