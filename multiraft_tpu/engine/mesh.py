"""Multi-chip sharding for the batched engine — the production mesh
recipe, shared by :class:`EngineDriver`, ``bench.py``, and
``__graft_entry__.dryrun_multichip``.

The groups axis is embarrassingly parallel (consensus traffic never
crosses a group boundary — SURVEY §2.2), so the whole engine shards
over a 1-D ``Mesh`` named ``"groups"`` with **zero collectives** in the
compiled step.  Two properties make that work:

* every per-group tensor (leading dim ``G``) gets
  ``PartitionSpec("groups")``; scalars/keys are replicated;
* the step runs under ``jax.shard_map``, so the steady-state fast-path
  ``lax.cond`` predicates (global reductions in ``tick_impl``) evaluate
  *per device* — under plain GSPMD jit they would lower to scalar
  all-reduces (measured: 2 all-reduces/tick).

Scalar metrics are returned as per-device lanes (shape ``[n_devices]``,
sharded) instead of ``psum``-ed, keeping the zero-collective guarantee;
hosts sum them lazily.

Cross-host placement note: a (groups-sharded) mesh spanning hosts puts
disjoint group ranges on each host's chips; chip↔chip traffic is zero
for consensus, and client routing to the owning host is the transport
layer's job (``distributed/``).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .core import (
    METRIC_KEYS,
    TRACE_KEYS,
    EngineConfig,
    EngineState,
    Mailbox,
    tick_impl,
)

__all__ = [
    "group_pspec",
    "shard_arrays",
    "STATE_SPECS",
    "INBOX_SPECS",
    "local_cfg",
    "local_shard",
    "make_sharded_tick",
    "make_sharded_run_ticks",
    "assert_zero_collectives",
]

# Collective ops that must never appear in the compiled consensus step.
_COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
                "reduce-scatter", "all-to-all")


def group_pspec(cfg: EngineConfig, x) -> P:
    """PartitionSpec for one engine array: shard the leading axis iff
    it is the groups axis; everything else is replicated."""
    sharded = getattr(x, "ndim", 0) >= 1 and x.shape and x.shape[0] == cfg.G
    return P("groups") if sharded else P()


def shard_arrays(cfg: EngineConfig, mesh: Mesh, tree):
    """``device_put`` a state/mailbox pytree with the groups axis split
    over the mesh."""
    put = lambda x: jax.device_put(
        x, NamedSharding(mesh, group_pspec(cfg, x))
    )
    return jax.tree.map(put, tree)


# State/mailbox fields shard on their leading (groups) axis; the tick
# counter is a replicated scalar.
STATE_SPECS = EngineState(
    **{
        f: (P() if f == "tick_no" else P("groups"))
        for f in EngineState._fields
    }
)
INBOX_SPECS = Mailbox(**{f: P("groups") for f in Mailbox._fields})


def local_cfg(cfg: EngineConfig, mesh: Mesh) -> EngineConfig:
    """The config of one device's share of the groups."""
    n = mesh.devices.size
    if cfg.G % n != 0:
        raise ValueError(
            f"G={cfg.G} must divide evenly over {n} mesh devices"
        )
    return dataclasses.replace(cfg, G=cfg.G // n)


def local_shard(cfg: EngineConfig, lcfg: EngineConfig):
    """``(G_total, row0)`` of the calling device, inside ``shard_map``:
    what ``core.shard_rows`` needs to give a device's groups the random
    draws the unsharded tick would give them (a group's device is its
    index range)."""
    return cfg.G, jax.lax.axis_index("groups") * lcfg.G


def make_sharded_tick(
    cfg: EngineConfig, mesh: Mesh
) -> Callable[[EngineState, Mailbox, jnp.ndarray, jax.Array], Tuple]:
    """The full engine tick under ``shard_map``: each device advances
    its local slice of groups.  Returns a jitted
    ``step(state, inbox, new_cmds, key) -> (state, outbox, metrics)``
    where scalar metrics come back as per-device lanes (sum on host).
    Per-group metric vectors keep their global [G] shape."""
    lcfg = local_cfg(cfg, mesh)

    def local_step(state, inbox, new_cmds, key):
        st, mb, m = tick_impl(
            lcfg, state, inbox, new_cmds, key, local_shard(cfg, lcfg)
        )
        # Scalars become one lane per device (out_spec "groups" then
        # concatenates them) — no psum, zero collectives.
        m = {
            k: (v[None] if v.ndim == 0 else v) for k, v in m.items()
        }
        return st, mb, m

    metric_specs = {k: P("groups") for k in METRIC_KEYS}
    return jax.jit(
        shard_map(
            local_step,
            mesh=mesh,
            in_specs=(STATE_SPECS, INBOX_SPECS, P("groups"), P()),
            out_specs=(STATE_SPECS, INBOX_SPECS, metric_specs),
        )
    )


def make_sharded_run_ticks(
    cfg: EngineConfig, mesh: Mesh, n_ticks: int, ingest_per_tick: int
):
    """Device-resident multi-tick loop (the bench path) under the same
    shard_map recipe: ``lax.scan`` of the local tick per device, zero
    host round-trips and zero collectives.  Returns a jitted
    ``run(state, inbox, key) -> (state, inbox)``."""
    lcfg = local_cfg(cfg, mesh)

    def local_run(state, inbox, key):
        new_cmds = jnp.full((lcfg.G,), ingest_per_tick, jnp.int32)

        def body(carry, i):
            st, mb = carry
            st, mb, _ = tick_impl(lcfg, st, mb, new_cmds, jax.random.fold_in(key, i))
            return (st, mb), None

        (state, inbox), _ = jax.lax.scan(
            body, (state, inbox), jnp.arange(n_ticks, dtype=jnp.int32)
        )
        return state, inbox

    return jax.jit(
        shard_map(
            local_run,
            mesh=mesh,
            in_specs=(STATE_SPECS, INBOX_SPECS, P()),
            out_specs=(STATE_SPECS, INBOX_SPECS),
        )
    )


def make_sharded_run_ticks_traced(
    cfg: EngineConfig, mesh: Mesh, n_ticks: int, ingest_per_tick: int
):
    """``make_sharded_run_ticks`` + the per-tick trace records of
    ``core.run_ticks_traced`` (frontiers/accept terms, [n_ticks, G]
    sharded on the groups axis) — the bench's verified mode on a mesh,
    same zero-collective recipe."""
    lcfg = local_cfg(cfg, mesh)

    def local_run(state, inbox, key):
        from .core import make_traced_body

        new_cmds = jnp.full((lcfg.G,), ingest_per_tick, jnp.int32)
        body = make_traced_body(lcfg, new_cmds, key)
        (state, inbox), rec = jax.lax.scan(
            body, (state, inbox), jnp.arange(n_ticks, dtype=jnp.int32)
        )
        return state, inbox, rec

    rec_specs = {k: P(None, "groups") for k in TRACE_KEYS}
    return jax.jit(
        shard_map(
            local_run,
            mesh=mesh,
            in_specs=(STATE_SPECS, INBOX_SPECS, P()),
            out_specs=(STATE_SPECS, INBOX_SPECS, rec_specs),
        )
    )


def assert_zero_collectives(jitted, *example_args) -> str:
    """Compile ``jitted`` for the example args and assert the optimized
    HLO contains no cross-device collectives (the linear-scaling
    guarantee).  Returns the HLO text for further inspection."""
    hlo = jitted.lower(*example_args).compile().as_text()
    for coll in _COLLECTIVES:
        assert coll not in hlo, (
            f"unexpected {coll} in sharded engine step — the groups "
            f"axis must stay embarrassingly parallel"
        )
    return hlo
