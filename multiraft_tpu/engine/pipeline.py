"""Fused multi-tick device pipeline for the serving path.

The serial :meth:`EngineDriver.step` loop pays two host round-trips per
tick whenever commands are in flight: the ``np.minimum`` backlog clip
that builds ``new_cmds`` (host → device), and the accepted/starts/terms
readback that binds payloads (device → host).  At serving shapes the
readback dominates the pump — LOADCURVE_r03 measured ``host.step`` at
538 µs/op against 29 µs/op for ingress decode.

:func:`step_ticks` removes both: one ``lax.scan`` advances
``ticks_per_pump`` ticks entirely on device, carrying the backlog
decrement in the scan carry (``new_cmds`` is recomputed per tick from
the carried backlog, so accepted commands are never re-ingested), and
stacking the per-tick metrics so the host syncs ONCE per pump and
replays the payload binding from the stacked record.  The fault model
rides inside the scan: per-tick drop masks (same ``fold_in(tick_key,
0xFA)`` stream as the serial loop) and the partition edge mask, so a
chaos run fuses identically to a clean one.  Host-side reorder
(`_apply_reorder`) is inherently unfusable — drivers with reordering
in flight fall back to the serial loop (see
``EngineDriver.fused_eligible``).  A mesh driver runs the same scan
under ``shard_map`` (:func:`sharded_step_ticks`): each device advances
its share of the groups, no collective, and the host sums the scalar
records' per-device lanes.

Bit-parity with the serial loop is a hard contract
(tests/test_engine_pipeline.py pins it via the state_planes content
fingerprints): same keys (``fold_in(key, tick0 + 1 + i)`` reproduces
the serial per-tick fold), same ingest clip, same decrement order.

:class:`PendingTicks` is the dispatch/complete split on top of it: the
scheduler loop dispatches a batch without waiting (JAX async dispatch
makes the returned arrays futures), a dedicated pump thread blocks in
:meth:`PendingTicks.fetch`, and the loop folds the fetched record back
in :meth:`EngineDriver.complete_ticks` — so socket I/O, decode and
acks proceed during device compute (distributed/engine_pump.py).

The record leaves the device as ONE flat ``int32`` buffer per chip
(:func:`_pack`), not seven arrays: each ``np.asarray`` of a device
buffer is a latency-bound copy (0.4-0.8 ms on a v5e whether it moves
320 KB or 3.2 MB; PERF.md §6, PR 39), so ``fetch`` pays one copy per
chip (seven copies started at once, ``jax.device_get``, measured 0.2-0.5
ms a pump slower).  Layout, per chip and tick-major: the three scalar lanes
``commits``, ``leaders``, ``max_term`` (``[n]`` each), then the four
per-group fields ``accepted``, ``start_index``, ``accept_term``,
``commit_index`` (``[n, G]`` each, raveled; ``G / n_devices`` groups a
chip on a mesh) — ``METRIC_KEYS`` order.  :func:`unpack_record` turns
the fetched buffer back into the per-field record
``complete_ticks`` reads.
"""

from __future__ import annotations

import functools
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from jax import shard_map
from jax.sharding import PartitionSpec as P

from .core import (
    METRIC_KEYS,
    SCALAR_METRIC_KEYS,
    SENDER_LANES,
    EngineConfig,
    EngineState,
    Mailbox,
    tick_impl,
)
from .host import drop_messages, mask_active
from .mesh import INBOX_SPECS, STATE_SPECS, local_cfg, local_shard

__all__ = ["step_ticks", "sharded_step_ticks", "unpack_record", "PendingTicks"]

GROUP_METRIC_KEYS = METRIC_KEYS[len(SCALAR_METRIC_KEYS):]


def _scan_ticks(
    cfg, state, inbox, n_ticks, with_drop, with_edges,
    backlog, drop_prob, edge_mask, tick0, key, shard=None,
):
    """The scan both programs below run: on the whole groups axis
    (:func:`step_ticks`) or, with ``shard``, on one device's share of
    it (:func:`sharded_step_ticks`)."""

    def body(carry, i):
        st, mb, bl = carry
        # Parity with the serial loop: it increments the host tick
        # FIRST, then folds — tick i of this batch is tick0 + 1 + i.
        tick_key = jax.random.fold_in(key, tick0 + 1 + i)
        new_cmds = jnp.minimum(bl, jnp.int32(cfg.INGEST))
        st, mb, m = tick_impl(cfg, st, mb, new_cmds, tick_key, shard)
        if with_drop:
            mb = drop_messages(
                mb, jax.random.fold_in(tick_key, 0xFA), drop_prob, cfg, shard
            )
        if with_edges:
            mb = mask_active(mb, lambda _, a: a & edge_mask)
        bl = bl - m["accepted"]
        return (st, mb, bl), m

    carry, first = (state, inbox, backlog), 0
    if any(getattr(inbox, f).ndim != 2 for f in SENDER_LANES):
        # A host path left a sender lane per edge (reorder, split
        # staging, an old checkpoint): the first tick reads that form
        # outside the scan, so the carry keeps the [G, src] form the
        # tick writes.
        carry, m0 = body(carry, jnp.int32(0))
        first = 1
    (state, inbox, backlog), rec = jax.lax.scan(
        body, carry, jnp.arange(first, n_ticks, dtype=jnp.int32)
    )
    if first:
        rec = {k: jnp.concatenate([m0[k][None], v]) for k, v in rec.items()}
    return state, inbox, backlog, rec


def _pack(rec):
    """The stacked record as the one buffer ``fetch`` copies, plus the
    batch's accepted commands a group (``accepts``, i32[G]: what a
    later dispatch subtracts from the backlog it ships) — both made in
    the program that ran the scan, so no program of their own runs."""
    buf = jnp.concatenate([rec[k].reshape(-1) for k in METRIC_KEYS])
    return buf, jnp.sum(rec["accepted"], axis=0)


def unpack_record(
    buf: np.ndarray, n: int, shards: int, lanes: bool
) -> Dict[str, np.ndarray]:
    """A fetched buffer of :func:`_pack`'s layout as the per-field
    record: ``[n]`` scalars and ``[n, G]`` fields, or on a mesh
    (``lanes``) ``[n, shards]`` scalar lanes, one a device.  Views of
    ``buf`` on one device; a mesh's fields are its ``shards`` chips'
    group ranges side by side, which one reshape copies into ``[n, G]``."""
    rows = buf.reshape(shards, -1)
    head = len(SCALAR_METRIC_KEYS) * n
    scalars = rows[:, :head].reshape(shards, len(SCALAR_METRIC_KEYS), n)
    fields = rows[:, head:].reshape(shards, len(GROUP_METRIC_KEYS), n, -1)
    rec = {}
    for i, k in enumerate(SCALAR_METRIC_KEYS):
        rec[k] = scalars[:, i].T if lanes else scalars[0, i]
    for i, k in enumerate(GROUP_METRIC_KEYS):
        rec[k] = fields[:, i].transpose(1, 0, 2).reshape(n, -1)
    return rec


@functools.partial(
    jax.jit, static_argnums=(0, 3, 4, 5), donate_argnums=(1, 2)
)
def step_ticks(
    cfg: EngineConfig,
    state: EngineState,
    inbox: Mailbox,
    n_ticks: int,
    with_drop: bool,
    with_edges: bool,
    backlog: jnp.ndarray,  # i32[G]: host backlog (clipped), scan carry
    drop_prob: jnp.ndarray,  # f32 scalar (unused when not with_drop)
    edge_mask: jnp.ndarray,  # bool[G,P,P]; dummy when not with_edges
    tick0: jnp.ndarray,  # i32 scalar: host tick BEFORE this batch
    key: jax.Array,
):
    """``n_ticks`` consensus rounds fused under one scan, with the
    backlog/new_cmds computation in the carry and every per-tick metric
    stacked (``rec[k]`` has a leading ``[n_ticks]`` axis) and packed
    into one buffer (:func:`_pack`, :func:`unpack_record`).

    Returns ``(state, inbox, backlog_left, buf, accepts)``.
    ``with_drop`` / ``with_edges`` are static so the clean path
    compiles none of the fault machinery; ``tick0`` and ``backlog`` are
    device values so a moving tick counter never retraces."""
    state, inbox, backlog, rec = _scan_ticks(
        cfg, state, inbox, n_ticks, with_drop, with_edges,
        backlog, drop_prob, edge_mask, tick0, key,
    )
    return (state, inbox, backlog) + _pack(rec)


@functools.lru_cache(maxsize=None)
def sharded_step_ticks(
    cfg: EngineConfig, mesh, n_ticks: int, with_drop: bool, with_edges: bool
):
    """:func:`step_ticks` for a mesh driver (engine/mesh.py's recipe):
    under ``shard_map`` each device scans its ``G / n`` groups for
    ``n_ticks``, zero collectives.  Returns the jitted
    ``run(state, inbox, backlog, drop_prob, edge_mask, tick0, key)``
    with the same results, where each device packs its own share of the
    record (its scalar lanes and its ``G / n`` groups, :func:`_pack`'s
    layout) into one global buffer sharded on the groups axis — one
    buffer, so one copy, a chip — and ``accepts`` keeps the groups
    sharding.  The scalar lanes are not ``psum``-ed: the host sums them
    (``EngineDriver.complete_ticks``).  Bit-equal to :func:`step_ticks`
    on one device: the random draws are the unsharded ones' rows
    (``core.shard_rows``)."""
    lcfg = local_cfg(cfg, mesh)

    def step_ticks_mesh(state, inbox, backlog, drop_prob, edge_mask, tick0, key):
        state, inbox, backlog, rec = _scan_ticks(
            lcfg, state, inbox, n_ticks, with_drop, with_edges,
            backlog, drop_prob, edge_mask, tick0, key,
            local_shard(cfg, lcfg),
        )
        return (state, inbox, backlog) + _pack(rec)

    groups, whole = P("groups"), P()
    return jax.jit(
        shard_map(
            step_ticks_mesh,
            mesh=mesh,
            in_specs=(
                STATE_SPECS, INBOX_SPECS, groups, whole,
                groups if with_edges else whole, whole, whole,
            ),
            out_specs=(STATE_SPECS, INBOX_SPECS, groups, groups, groups),
        ),
        donate_argnums=(0, 1),
    )


class PendingTicks:
    """A dispatched, not-yet-completed fused tick batch.

    Created by :meth:`EngineDriver.dispatch_ticks` (scheduler loop,
    non-blocking); :meth:`fetch` blocks until the packed record (``buf``,
    one flat ``int32`` buffer a chip, :func:`_pack`) is on host and is
    the ONE call safe to run off the loop thread (the engine-pump
    thread's whole job); the result then goes back to the loop for
    :meth:`EngineDriver.complete_ticks`.

    ``accepts_dev`` stays on device: later dispatches subtract it from
    the host backlog so an in-flight batch's accepted commands are
    never re-ingested (the pipeline-depth ≥ 2 double-ingest hazard).
    """

    __slots__ = (
        "n", "tick0", "buf", "accepts_dev", "t_dispatch", "t_loop_cpu",
        "pump", "mesh_devices", "t_dispatched", "t_fetch", "t_ready",
        "t_fetched", "nbytes", "ncopies",
    )

    def __init__(
        self,
        n: int,
        tick0: int,
        buf: jnp.ndarray,
        accepts_dev: jnp.ndarray,
        t_dispatch: float,
        pump: int = 0,
        mesh_devices: int = 0,
    ) -> None:
        self.n = n
        self.tick0 = tick0
        self.buf = buf
        self.accepts_dev = accepts_dev
        self.t_dispatch = t_dispatch
        self.pump = pump  # pumps completed at dispatch: the trace tag
        # The mesh driver's device count, each holding one shard of
        # ``buf`` and one scalar lane of the record; 0 without a mesh.
        self.mesh_devices = mesh_devices
        # Loop-side CPU the dispatch burned (the serving loop's share
        # of this pump; completion adds its own) — set by the caller.
        self.t_loop_cpu = 0.0

    def fetch(self) -> Dict[str, np.ndarray]:
        """Block until the batch's packed record is host-resident and
        return it per field (:func:`unpack_record`).  Pure device wait +
        copy: touches no driver state, so it is safe off the scheduler
        loop by construction.  It stamps its own entry, the device's
        completion, its return, the bytes it brought over and the device
        buffers it copied them from (one a chip: a mesh driver's buffer
        is read back shard by shard) on the batch; ``complete_ticks``
        turns those into ``pump.handoff_s`` (since ``dispatch_ticks``
        returned), ``pump.fetch_s`` = ``pump.wait_s`` (the device) +
        ``pump.copy_s`` (the copy and the unpacking), ``pump.post_s``,
        ``pump.readback_bytes`` and ``pump.readback_copies`` on the loop."""
        self.t_fetch = time.perf_counter()
        shards = max(self.mesh_devices, 1)
        with TraceAnnotation("mrt.pump.fetch", pump=self.pump):
            # The copy is queued behind the device's work first, as
            # ``np.asarray`` alone queues it: issued only after the wait
            # it cost 0.45 ms a pump at 10k groups (v5e).
            self.buf.copy_to_host_async()
            # The device's completion, as the host sees it: the end of
            # ``mrt.pump.wait`` is what lays the device plane's clock
            # over the host lines' (engine/instrument.py).
            with TraceAnnotation("mrt.pump.wait", pump=self.pump):
                self.buf.block_until_ready()
            self.t_ready = time.perf_counter()
            with TraceAnnotation("mrt.pump.copy", pump=self.pump):
                flat = np.asarray(self.buf)
                out = unpack_record(flat, self.n, shards, self.mesh_devices > 0)
        self.nbytes = flat.nbytes
        self.ncopies = shards
        self.t_fetched = time.perf_counter()
        return out
