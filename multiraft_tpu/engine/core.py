"""The batched TPU consensus engine — multi-Raft as one jitted tick.

This is the TPU-native inversion of the reference's runtime: instead of
3+2(n−1) goroutines per Raft instance (reference: raft/raft.go:51-87),
*every replica of every group* lives in struct-of-arrays state tensors
with a leading ``(G, P)`` = (groups, peers) axis, and one pure
``tick(state, inbox, ...) → (state, outbox, metrics)`` function advances
them all synchronously.  RPCs are dense per-edge mailboxes
``[G, src, dst]``; the labrpc fault model becomes masks (drop,
partition) applied between outbox and inbox (SURVEY §2.2, §5.8).

Per-phase mapping to the reference:

* vote request/reply handling  — raft/raft_election.go:4-77
* append request handling incl. conflict backoff
                               — raft/raft_append_entry.go:108-162
* reply processing + quorum commit advance (the north-star kernel)
                               — raft/raft_append_entry.go:66-105
* snapshot fast-forward        — raft/raft_snapshot.go:15-54 (the
  ``snap`` flag compresses InstallSnapshot into the append channel;
  snapshot *data* lives host-side keyed by (group, index))

Deliberate divergences (documented):

* Conflict backoff jumps straight to ``min(prev, commit+1)`` — the
  follower's committed prefix provably matches the leader, so
  repositioning takes O(1) round trips instead of the reference's
  term-scan (raft/raft_append_entry.go:136-143); data catch-up then
  streams at ``E`` entries per message.
* Election timeouts are integer ticks with per-replica jitter drawn
  from a counter-based PRNG (replaces the reference's wall-clock reseed
  quirk, raft/raft.go:46-50).
* Logs are fixed-capacity rings with ``base`` rebase; compaction
  advances ``base`` over the applied prefix automatically (the
  service-driven Snapshot() of the reference becomes a frontier the
  host reads).

Sharding: every tensor is independent along G, so the whole engine
shards over a ``Mesh`` 'groups' axis with zero collectives (use
``jax.shard_map`` so the steady-state fast-path conds evaluate
per-device — under plain GSPMD jit their global predicates lower to
scalar all-reduces; see ``__graft_entry__.dryrun_multichip``) — consensus
*within* a group never crosses a shard boundary.  (Cross-host traffic
only appears when a logical group spans hosts, which the transport
layer handles, not the kernel.)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.knobs import knob_bool

__all__ = [
    "EngineConfig", "EngineState", "Mailbox", "SENDER_LANES", "init_state",
    "empty_mailbox", "per_edge", "tick", "METRIC_KEYS", "SCALAR_METRIC_KEYS",
    "COMMIT_DEPTH",
]

FOLLOWER, CANDIDATE, LEADER = 0, 1, 2

# Ticks from a leader's ingest of an entry to its ``commit_index``
# covering it, read off the phase order of ``_tick_phases``: the leader
# appends in phase 5b and sends in 5c of tick t; followers append and
# reply in phase 3 of t+1; the leader counts the replies and advances
# its commit in phase 4 of t+2.  tests/test_commit_depth.py pins it.
COMMIT_DEPTH = 2


def prevote_default() -> bool:
    """PreVote election mode, ON unless ``MRT_PREVOTE=0`` (kill switch).
    Read at EngineConfig construction, so the legacy arm of the CI A/B
    matrix flips it per-process without touching call sites."""
    return knob_bool("MRT_PREVOTE")


def check_quorum_default() -> bool:
    """Check-quorum leader self-demotion, ON unless
    ``MRT_CHECK_QUORUM=0`` (kill switch, paired with MRT_PREVOTE)."""
    return knob_bool("MRT_CHECK_QUORUM")


def membership_default() -> bool:
    """Joint-consensus membership change, ON unless ``MRT_MEMBERSHIP=0``
    (kill switch).  With every group at its full static peer set the
    masked dual-quorum reductions are value-identical to the legacy
    single-quorum ones (see the math note on EngineConfig.membership),
    so default-on changes no behavior until a config entry lands."""
    return knob_bool("MRT_MEMBERSHIP")

# The tick's metrics schema — single source of truth for the mesh
# path's out_specs (engine/mesh.py) and the host's per-device scalar
# reduction (engine/host.py).  SCALAR keys are cluster-wide scalars
# (per-device lanes under a mesh); the rest are per-group [G] vectors.
SCALAR_METRIC_KEYS = ("commits", "leaders", "max_term")
METRIC_KEYS = SCALAR_METRIC_KEYS + (
    "accepted", "start_index", "accept_term", "commit_index",
)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static shape/timing parameters (hashable: passed as a jit static).

    Timing is in ticks; with the reference's wall-clock mapping of
    10 ms/tick these defaults reproduce its 90 ms heartbeat and
    300–600 ms election window (reference: raft/raft.go:42-50).  The
    bench shrinks the tick period to whatever the chip sustains.
    """

    G: int = 8  # groups
    P: int = 3  # peers per group
    L: int = 64  # log ring capacity per replica
    E: int = 8  # max entries per append message
    INGEST: int = 8  # max Start() commands accepted per group per tick
    HB_TICKS: int = 9
    ELECT_MIN: int = 30
    ELECT_MAX: int = 60
    # Pallas kernels for vote tally + quorum commit (the north-star
    # ops); interpret=True runs them under the Pallas interpreter on
    # non-TPU backends (parity/testing path).
    use_pallas: bool = False
    pallas_interpret: bool = False
    # Host-paced compaction (split-group mode, engine/split.py): the
    # tick stops auto-advancing `applied` to `commit`, leaving the host
    # to raise it as its state machine actually applies — so ring
    # compaction can never pass an index whose entry term the host
    # still needs (payload term-arbitration reads it from the ring).
    # Off for the throughput path: device-paced applied keeps the ring
    # compacting without host round-trips.
    host_paced_compaction: bool = False
    # PreVote (etcd/TiKV-style, beyond the reference): an election
    # timeout launches a NON-BINDING prevote round at term+1 first;
    # only a prevote quorum promotes to a real candidacy.  Voters that
    # heard a live leader within ELECT_MIN ticks refuse, so a replica
    # rejoining from a partition cannot depose a healthy leader by
    # term inflation.  Default ON; ``MRT_PREVOTE=0`` restores the
    # reference-faithful legacy elections (the CI A/B's second arm).
    prevote: bool = dataclasses.field(default_factory=prevote_default)
    # Check-quorum (etcd CheckQuorum analog): a leader that has not
    # heard an append reply from a quorum within ELECT_MAX ticks
    # demotes itself to follower AT ITS OWN TERM — a quorum-severed
    # leader releases its groups instead of wedging them while clerk
    # traffic piles into a log that can never commit.  The demotion
    # keeps ``voted_for`` (clearing it would allow a second same-term
    # grant and break election safety).  Default ON;
    # ``MRT_CHECK_QUORUM=0`` is the kill switch.
    check_quorum: bool = dataclasses.field(
        default_factory=check_quorum_default
    )
    # Joint-consensus membership change (Raft §6 / thesis §4.3): per-
    # replica config views as voter BITMASKS (``voters_old`` /
    # ``voters_new``, i32 bit p = peer p votes) plus a ``joint`` flag.
    # While joint, vote tallying, quorum-median commit advance and
    # check-quorum stepdown each require BOTH quorums (two masked
    # reductions).  Config entries take effect ON APPEND (not commit):
    # a replica always reasons with the latest config in its log.
    # Math note: with a full mask (the init state) the masked reduction
    # needs ``P//2+1`` of ``P`` voters and ignores no lanes — exactly
    # the legacy ``cfg.quorum`` single-quorum math, so membership=True
    # is a no-op until the first config entry.  The Pallas tally/commit
    # kernels are mask-unaware, so masked math runs only on the jnp
    # path: ``membership_on`` is gated off under ``use_pallas`` and the
    # host admin ops refuse to start a reconfig there.
    membership: bool = dataclasses.field(
        default_factory=membership_default
    )

    def __post_init__(self) -> None:
        # The ring-log algebra requires headroom: vectorized scatters
        # assume message slots are distinct mod L, and the capacity /
        # compaction thresholds assume an E+INGEST+2 reserve.
        if self.L <= self.E + self.INGEST + 2:
            raise ValueError(
                f"EngineConfig: L={self.L} must exceed "
                f"E+INGEST+2={self.E + self.INGEST + 2}"
            )
        if self.P < 1 or self.G < 1 or self.E < 1:
            raise ValueError("EngineConfig: G, P, E must be >= 1")
        if self.ELECT_MIN >= self.ELECT_MAX or self.HB_TICKS < 1:
            raise ValueError("EngineConfig: bad timing parameters")
        if self.membership and self.P > 30:
            # Voter sets are i32 bitmasks; bit 31 is the sign bit.
            raise ValueError(
                f"EngineConfig: membership mode supports P <= 30 "
                f"(i32 voter bitmasks), got P={self.P}"
            )

    @property
    def quorum(self) -> int:
        return self.P // 2 + 1

    @property
    def membership_on(self) -> bool:
        """Membership machinery active in the tick: requires the jnp
        reduction path (the Pallas kernels are mask-unaware)."""
        return self.membership and not self.use_pallas

    @property
    def full_voters(self) -> int:
        """The all-peers voter bitmask (the init config)."""
        return (1 << self.P) - 1


class EngineState(NamedTuple):
    """Struct-of-arrays Raft state, leading axes (G, P)."""

    tick_no: jnp.ndarray  # i32 scalar
    term: jnp.ndarray  # i32[G,P]
    voted_for: jnp.ndarray  # i32[G,P] (-1 = none)
    role: jnp.ndarray  # i32[G,P]
    commit: jnp.ndarray  # i32[G,P]
    applied: jnp.ndarray  # i32[G,P]
    base: jnp.ndarray  # i32[G,P] snapshot index (log ring floor)
    base_term: jnp.ndarray  # i32[G,P]
    log_len: jnp.ndarray  # i32[G,P] entries above base
    log_term: jnp.ndarray  # i32[G,P,L] ring: abs index i at slot i % L
    next_idx: jnp.ndarray  # i32[G,P,P] leader p's next for peer q
    match_idx: jnp.ndarray  # i32[G,P,P]
    votes: jnp.ndarray  # bool[G,P,P] candidate p's votes from q
    elect_dl: jnp.ndarray  # i32[G,P] election deadline tick
    hb_due: jnp.ndarray  # i32[G,P] next heartbeat tick
    alive: jnp.ndarray  # bool[G,P] fault-injection: replica up
    pre_votes: jnp.ndarray  # bool[G,P,P] prevote grants (prevote mode)
    last_heard: jnp.ndarray  # i32[G,P] last tick a leader was heard
    last_ack: jnp.ndarray  # i32[G,P,P] leader p: last ack tick from q
    # Membership (joint consensus): each replica's VIEW of its group's
    # config — voter bitmasks, the joint flag, a monotone config epoch
    # and the log index of the latest config entry.  Equal old/new
    # masks outside the joint phase (the invariant that makes the
    # dual-quorum reductions branchless).
    voters_old: jnp.ndarray  # i32[G,P] bitmask: C_old voters
    voters_new: jnp.ndarray  # i32[G,P] bitmask: C_new voters
    joint: jnp.ndarray  # bool[G,P] in the C_old,new transition
    cfg_epoch: jnp.ndarray  # i32[G,P] config generation counter
    cfg_idx: jnp.ndarray  # i32[G,P] log index of the latest cfg entry


class Mailbox(NamedTuple):
    """Dense messages, ``[G, src, dst]`` (+ trailing dims) per edge.

    The :data:`SENDER_LANES` hold a value of the sender's alone (its
    term, commit, last log position, config view), the same for every
    destination, so the tick writes them once per sender, ``[G, src]``:
    a ``[G, P, P]`` copy is a kernel of its own on every tick, since the
    mailbox is the carry of the fused scan (engine/pipeline.py).  A
    receiver broadcasts such a lane over destinations inside its own
    fusion.  A host path that rewrites one edge of a sender lane
    (reorder redelivery, split staging) first expands it to
    ``[G, src, dst]``; the tick reads either form, and writes
    ``[G, src]`` again."""

    # RequestVote (reference: raft/raft_rpc.go RequestVote args/reply);
    # the ``pre`` bits mark non-binding PreVote rounds.
    vr_active: jnp.ndarray  # bool[G,P,P]
    vr_term: jnp.ndarray  # i32[G,P] (sender lane)
    vr_last_idx: jnp.ndarray  # i32[G,P] (sender lane)
    vr_last_term: jnp.ndarray  # i32[G,P] (sender lane)
    vr_pre: jnp.ndarray  # bool[G,P,P]
    vp_active: jnp.ndarray  # bool[G,P,P]  src=voter, dst=candidate
    vp_term: jnp.ndarray  # i32[G,P,P]
    vp_granted: jnp.ndarray  # bool[G,P,P]
    vp_pre: jnp.ndarray  # bool[G,P,P]
    # AppendEntries / InstallSnapshot (snap flag)
    ar_active: jnp.ndarray  # bool[G,P,P]
    ar_term: jnp.ndarray  # i32[G,P] (sender lane)
    ar_prev_idx: jnp.ndarray  # i32[G,P,P]
    ar_prev_term: jnp.ndarray  # i32[G,P,P]
    ar_n: jnp.ndarray  # i32[G,P,P] entries carried (<= E)
    ar_terms: jnp.ndarray  # i32[G,P,P,E]
    ar_commit: jnp.ndarray  # i32[G,P] (sender lane) leader commit
    ar_snap: jnp.ndarray  # bool[G,P,P] InstallSnapshot fast-forward
    ap_active: jnp.ndarray  # bool[G,P,P]  src=follower, dst=leader
    ap_term: jnp.ndarray  # i32[G,P] (sender lane)
    ap_success: jnp.ndarray  # bool[G,P,P]
    ap_match: jnp.ndarray  # i32[G,P,P]
    ap_conflict: jnp.ndarray  # i32[G,P,P]
    # Leader config view, carried with every append: a follower whose
    # log provably covers ``ar_cfg_idx`` mirrors the leader's view
    # (effect-on-append without per-entry payload plumbing — see the
    # phase-3 adoption note in tick_impl).
    ar_cfg_epoch: jnp.ndarray  # i32[G,P] (sender lane)
    ar_cfg_idx: jnp.ndarray  # i32[G,P] (sender lane)
    ar_cfg_old: jnp.ndarray  # i32[G,P] (sender lane) voter bitmask
    ar_cfg_new: jnp.ndarray  # i32[G,P] (sender lane) voter bitmask
    ar_cfg_joint: jnp.ndarray  # bool[G,P] (sender lane)


# The Mailbox fields whose value is the sender's alone: stored [G, src]
# (see the Mailbox docstring).
SENDER_LANES = (
    "vr_term", "vr_last_idx", "vr_last_term",
    "ar_term", "ar_commit", "ap_term",
    "ar_cfg_epoch", "ar_cfg_idx", "ar_cfg_old", "ar_cfg_new", "ar_cfg_joint",
)


def per_edge(lane):
    """A mailbox lane in the per-edge form ``[G, src, dst]``: a sender
    lane ``[G, src]`` broadcast over destinations (a writable copy for
    a numpy lane), any other lane as it is.  What a path that writes
    one edge of a sender lane expands it with first."""
    if lane.ndim != 2:
        return lane
    G, P = lane.shape
    if isinstance(lane, np.ndarray):
        return np.broadcast_to(lane[:, :, None], (G, P, P)).copy()
    return jnp.broadcast_to(lane[:, :, None], (G, P, P))


def init_state(cfg: EngineConfig, key: jax.Array) -> EngineState:
    G, P, L = cfg.G, cfg.P, cfg.L
    z = lambda *s: jnp.zeros(s, jnp.int32)
    deadlines = jax.random.randint(
        key, (G, P), cfg.ELECT_MIN, cfg.ELECT_MAX, dtype=jnp.int32
    )
    return EngineState(
        tick_no=jnp.int32(0),
        term=z(G, P),
        voted_for=jnp.full((G, P), -1, jnp.int32),
        role=z(G, P),
        commit=z(G, P),
        applied=z(G, P),
        base=z(G, P),
        base_term=z(G, P),
        log_len=z(G, P),
        log_term=z(G, P, L),
        next_idx=jnp.ones((G, P, P), jnp.int32),
        match_idx=z(G, P, P),
        votes=jnp.zeros((G, P, P), bool),
        elect_dl=deadlines,
        hb_due=z(G, P),
        alive=jnp.ones((G, P), bool),
        pre_votes=jnp.zeros((G, P, P), bool),
        last_heard=z(G, P),
        last_ack=z(G, P, P),
        voters_old=jnp.full((G, P), cfg.full_voters, jnp.int32),
        voters_new=jnp.full((G, P), cfg.full_voters, jnp.int32),
        joint=jnp.zeros((G, P), bool),
        cfg_epoch=z(G, P),
        cfg_idx=z(G, P),
    )


def empty_mailbox(cfg: EngineConfig) -> Mailbox:
    G, P, E = cfg.G, cfg.P, cfg.E
    b = lambda *s: jnp.zeros(s, bool)
    z = lambda *s: jnp.zeros(s, jnp.int32)
    return Mailbox(
        vr_active=b(G, P, P), vr_term=z(G, P),
        vr_last_idx=z(G, P), vr_last_term=z(G, P),
        vr_pre=b(G, P, P),
        vp_active=b(G, P, P), vp_term=z(G, P, P), vp_granted=b(G, P, P),
        vp_pre=b(G, P, P),
        ar_active=b(G, P, P), ar_term=z(G, P),
        ar_prev_idx=z(G, P, P), ar_prev_term=z(G, P, P),
        ar_n=z(G, P, P), ar_terms=z(G, P, P, E), ar_commit=z(G, P),
        ar_snap=b(G, P, P),
        ap_active=b(G, P, P), ap_term=z(G, P), ap_success=b(G, P, P),
        ap_match=z(G, P, P), ap_conflict=z(G, P, P),
        ar_cfg_epoch=z(G, P), ar_cfg_idx=z(G, P),
        ar_cfg_old=z(G, P), ar_cfg_new=z(G, P),
        ar_cfg_joint=b(G, P),
    )


# ---------------------------------------------------------------------------
# Ring-log helpers (the device mirror of raft/raft_log.go's index algebra)
#
# TPU-critical: computed-index gather/scatter on the minor axis are
# catastrophically slow on TPU (measured ~8-17 ms per op at the bench
# shapes vs ~0.05 ms for a fused pass).  Every ring access is therefore
# expressed as compare+select+reduce over the static L axis — XLA fuses
# the on-the-fly one-hot into a single vectorized pass, so the (…,K,L)
# intermediate never reaches HBM.
# ---------------------------------------------------------------------------


def _ring_read(log: jnp.ndarray, idx: jnp.ndarray, L: int) -> jnp.ndarray:
    """Gather ``log[..., idx mod L]`` without a gather op.

    ``log``: [..., L]; ``idx``: [..., K] absolute indices (broadcastable
    prefix). Returns [..., K].  Slots outside the ring window read
    whatever the ring holds — callers mask validity, as with the gather
    formulation.
    """
    slot = jnp.mod(idx, L)  # [..., K]
    lanes = jnp.arange(L, dtype=slot.dtype)
    onehot = slot[..., None] == lanes  # [..., K, L] (fused, never stored)
    return jnp.sum(jnp.where(onehot, log[..., None, :], 0), axis=-1)


def _ring_write(
    log: jnp.ndarray,
    start: jnp.ndarray,
    vals: jnp.ndarray,
    n: jnp.ndarray,
    L: int,
) -> jnp.ndarray:
    """Write ``vals[..., e] → slot (start+e) mod L`` for ``e < n``,
    scatter-free.

    ``log``: [..., L]; ``start``: [...] first absolute index written;
    ``vals``: [..., E]; ``n``: [...] entries to write (≤ E ≤ L, so each
    written slot is hit by at most one message entry).
    """
    E = vals.shape[-1]
    lanes = jnp.arange(L, dtype=start.dtype)
    # Which message entry lands on lane l (unique since E <= L).
    e_l = jnp.mod(lanes - start[..., None], L)  # [..., L]
    hit = e_l < n[..., None]  # [..., L]
    ei = jnp.arange(E, dtype=start.dtype)
    v = jnp.sum(
        jnp.where(e_l[..., None] == ei, vals[..., None, :], 0), axis=-1
    )  # [..., L] (fused)
    return jnp.where(hit, v, log)


def _sort_cols(x: jnp.ndarray) -> list:
    """Ascending sort along the (static, small) last axis via an
    unrolled compare-swap network — ``jnp.sort`` costs ~1.6 ms at bench
    shapes where this is a handful of fused min/max passes.  Returns
    the sorted columns as a list of [...] arrays."""
    cols = [x[..., i] for i in range(x.shape[-1])]
    n = len(cols)
    for i in range(n):
        for j in range(n - 1 - i):
            a, b = cols[j], cols[j + 1]
            cols[j], cols[j + 1] = jnp.minimum(a, b), jnp.maximum(a, b)
    return cols


def _kth_smallest(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """k-th smallest (0-based) along the last axis (see _sort_cols)."""
    return _sort_cols(x)[k]


def _voter_lanes(bits: jnp.ndarray, P: int) -> jnp.ndarray:
    """Expand an i32 voter bitmask [...] to a bool lane mask [..., P]."""
    qi = jnp.arange(P, dtype=jnp.int32)
    return ((bits[..., None] >> qi) & 1) == 1


def _quorum_met(grants: jnp.ndarray, bits: jnp.ndarray, P: int) -> jnp.ndarray:
    """Does ``grants`` (bool[..., P]) contain a majority of the voters
    named by ``bits`` (i32 bitmask [...])?  The masked generalization of
    ``count >= cfg.quorum``: with a full mask it needs P//2+1 of P."""
    lanes = _voter_lanes(bits, P)
    n = jnp.sum((grants & lanes).astype(jnp.int32), axis=-1)
    need = jnp.sum(lanes.astype(jnp.int32), axis=-1) // 2 + 1
    return n >= need


def _quorum_kth(vals: jnp.ndarray, bits: jnp.ndarray, P: int) -> jnp.ndarray:
    """Largest v such that a majority of the voters in ``bits`` have
    ``vals >= v`` — the masked, dynamic-quorum generalization of
    ``_kth_smallest(vals, P - quorum)``.  Non-voter lanes are pushed
    below every real value (sentinel -1), so the top ``count(bits)``
    sorted columns are exactly the voters and the majority-th largest
    overall equals the majority-th largest among voters."""
    lanes = _voter_lanes(bits, P)
    need = jnp.sum(lanes.astype(jnp.int32), axis=-1) // 2 + 1  # [...]
    cols = _sort_cols(jnp.where(lanes, vals, -1))
    k = P - need  # dynamic per-element index into the ascending sort
    out = cols[0]
    for i in range(1, P):
        out = jnp.where(k == i, cols[i], out)
    return out


def _term_at(cfg: EngineConfig, state: EngineState, idx: jnp.ndarray) -> jnp.ndarray:
    """Term of absolute index ``idx`` per replica; idx shape [G,P].
    idx == base → base_term; out-of-window reads return 0 (callers mask)."""
    gathered = _ring_read(state.log_term, idx[..., None], cfg.L)[..., 0]
    return jnp.where(idx == state.base, state.base_term, gathered)


def _last_index(state: EngineState) -> jnp.ndarray:
    return state.base + state.log_len


def _step_down(
    cfg: EngineConfig,
    state: EngineState,
    higher: jnp.ndarray,
    m_term: jnp.ndarray,
    clear_vote: bool = True,
) -> EngineState:
    """Observe a higher term: adopt it, clear the vote, drop to
    follower (reference: the term-check prologue of every RPC handler).
    In prevote mode a term bump also invalidates any prevote round in
    flight — its grants were collected at a now-stale term.

    ``clear_vote=False`` is the check-quorum entry: the demotion
    happens AT THE LEADER'S OWN TERM, where the vote must survive —
    the leader voted for itself at this term, and releasing that vote
    would let a concurrent same-term candidate collect a second grant
    from this replica (two leaders at one term)."""
    kw = dict(
        term=jnp.where(higher, m_term, state.term),
        role=jnp.where(higher, FOLLOWER, state.role),
    )
    if clear_vote:
        kw["voted_for"] = jnp.where(higher, -1, state.voted_for)
    if cfg.prevote:
        kw["pre_votes"] = jnp.where(
            higher[..., None], False, state.pre_votes
        )
    return state._replace(**kw)


# ---------------------------------------------------------------------------
# The tick
# ---------------------------------------------------------------------------


class _PhaseScope:
    """``jax.named_scope`` for the consecutive phases of one long
    function without indenting them: calling it leaves the phase before
    and enters the named one, so every operation traced in between
    carries the phase in its metadata (``op_name`` in the HLO, ``tf_op``
    in a device trace).  Metadata only: the compiled program and the
    compile-cache key are the same with and without."""

    def __init__(self) -> None:
        self._open = None

    def __call__(self, name: str) -> None:
        self.close()
        self._open = jax.named_scope(name)
        self._open.__enter__()

    def close(self) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


def tick_impl(
    cfg: EngineConfig,
    state: EngineState,
    inbox: Mailbox,
    new_cmds: jnp.ndarray,  # i32[G]: Start() firehose, appended at leaders
    key: jax.Array,
    shard: Optional[Tuple[int, jnp.ndarray]] = None,
) -> Tuple[EngineState, Mailbox, Dict[str, jnp.ndarray]]:
    """``shard``: set by the ``shard_map`` callers (engine/mesh.py),
    whose ``cfg.G`` is one device's share of the groups — see
    :func:`shard_rows`."""
    scope = _PhaseScope()
    try:
        return _tick_phases(cfg, state, inbox, new_cmds, key, scope, shard)
    finally:
        scope.close()  # a trace that raised leaves no scope behind


def shard_rows(draw, G: int, shard: Optional[Tuple[int, jnp.ndarray]]):
    """``draw(rows)``, a per-group random draw with leading axis
    ``rows``, for the ``G`` groups of this program.  Unsharded
    (``shard`` None) that is ``draw(G)``.  Under ``shard_map`` every
    device holds the same key, so ``draw(G)`` would hand each device's
    groups the draw of groups ``0..G-1``; ``shard = (G_total, row0)``
    takes rows ``row0..row0+G`` of the whole driver's draw instead, and
    a sharded run is bit-equal to the unsharded one.  The price is the
    whole draw on every device (the TPU compiler does not push the
    slice into the counter-based generator): elementwise work on
    ``G_total`` rows, small beside a tick."""
    if shard is None:
        return draw(G)
    total, row0 = shard
    return jax.lax.dynamic_slice_in_dim(draw(total), row0, G)


def _tick_phases(
    cfg: EngineConfig,
    state: EngineState,
    inbox: Mailbox,
    new_cmds: jnp.ndarray,
    key: jax.Array,
    scope: _PhaseScope,
    shard: Optional[Tuple[int, jnp.ndarray]] = None,
) -> Tuple[EngineState, Mailbox, Dict[str, jnp.ndarray]]:
    G, P, L, E = cfg.G, cfg.P, cfg.L, cfg.E
    out = empty_mailbox(cfg)
    now = state.tick_no + 1
    commit_before = state.commit

    pi = jnp.arange(P)[None, :]  # [1,P] replica index grid

    # One jitter draw per tick, shared by every timer reset in this
    # tick: per-draw PRNG costs ~150 us at bench shapes, and within a
    # single tick the resets are interchangeable — cross-tick
    # desynchronization (what liveness needs) comes from folding the
    # key per tick.
    jitter = shard_rows(
        lambda rows: jax.random.randint(
            jax.random.fold_in(key, 7), (rows, P),
            cfg.ELECT_MIN, cfg.ELECT_MAX, dtype=jnp.int32,
        ),
        G, shard,
    )

    scope("tick.1_votes")
    # ---- 1. vote requests (reference: raft/raft_election.go:54-77) ----
    # All candidates arbitrated in ONE pass (fused r04: the per-src
    # loop emitted P dependent kernel chains; the roofline showed the
    # tick is launch-bound, not bandwidth-bound).  Semantics: the voter
    # first adopts the max incoming term (one step-down covers every
    # request), then grants at most one vote — to ``voted_for`` if
    # already bound, else to the LOWEST-index eligible candidate, which
    # is exactly the old loop's order.  Requests below the adopted term
    # are refused; the old loop could grant them when they arrived
    # "first", but that is just a different message interleaving, and
    # Raft is ordering-robust (the mailbox is at-most-once).  PreVote
    # requests (vr_pre lanes) stay non-binding: no step-down, no
    # voted_for, no timer reset.
    # View [G, voter(dst), cand(src)] — matches out.vp's [G,src,dst].
    vT = lambda x: jnp.swapaxes(x, 1, 2)
    # A sender lane's view (Mailbox, SENDER_LANES): stored [G, src], it
    # broadcasts over dst here, inside the reading fusion; expanded to
    # [G, src, dst] by a host path, it reads as any lane does.
    vS = lambda x: x[:, None, :] if x.ndim == 2 else vT(x)
    arrived = vT(inbox.vr_active) & state.alive[:, :, None]
    is_pre = vT(inbox.vr_pre)
    active = arrived & ~is_pre
    m_term = vS(inbox.vr_term)
    higher_lane = active & (m_term > state.term[..., None])
    adopt = jnp.max(jnp.where(higher_lane, m_term, -1), axis=2)
    state = _step_down(cfg, state, jnp.any(higher_lane, axis=2), adopt)
    last_idx = _last_index(state)
    last_term = _term_at(cfg, state, last_idx)
    up_to_date = (vS(inbox.vr_last_term) > last_term[..., None]) | (
        (vS(inbox.vr_last_term) == last_term[..., None])
        & (vS(inbox.vr_last_idx) >= last_idx[..., None])
    )
    eligible = active & (m_term == state.term[..., None]) & up_to_date
    cand_ids = jnp.arange(P, dtype=jnp.int32)
    bound = state.voted_for != -1  # [G,P] at voter
    cand_ok = eligible & jnp.where(
        bound[..., None], cand_ids == state.voted_for[..., None], True
    )
    winner = jnp.min(jnp.where(cand_ok, cand_ids, P), axis=2)  # [G,P]
    grant = cand_ok & (cand_ids == winner[..., None])  # ≤1 true per voter
    grant_any = winner < P
    state = state._replace(
        voted_for=jnp.where(grant_any, winner, state.voted_for),
        elect_dl=jnp.where(grant_any, now + jitter, state.elect_dl),
        last_heard=jnp.where(grant_any, now, state.last_heard),
    )
    if cfg.prevote:
        pre_act = arrived & is_pre
        # Grant iff the proposed term would win AND the log is up
        # to date AND this voter has not heard a live leader within
        # ELECT_MIN ticks (the disruption guard).  A LEADER never
        # grants: it is in-lease by definition (its own last_heard
        # is not refreshed while leading — etcd refuses likewise).
        lease_expired = (now - state.last_heard) >= cfg.ELECT_MIN
        grant_pre = (
            pre_act
            & (state.role != LEADER)[..., None]
            & (m_term > state.term[..., None])
            & lease_expired[..., None]
            & up_to_date
        )
    else:
        pre_act = jnp.zeros_like(active)
        grant_pre = pre_act
    # Reply lanes [G, voter, cand] ARE out.vp's [G, src, dst] layout.
    # A src sends either a real or a pre request per tick, so the lanes
    # are disjoint; merge into one write.  A GRANTED pre reply echoes
    # the proposed term (the tally matches on it); a REFUSED pre reply
    # carries the voter's actual term, so a candidate probing a
    # partition-stale term learns the real one and steps down (sim
    # parity: node.py _on_prevote_reply; etcd does the same).
    out = out._replace(
        vp_active=active | pre_act,
        vp_pre=pre_act,
        vp_term=jnp.where(
            pre_act & grant_pre,
            m_term,
            jnp.broadcast_to(state.term[..., None], (G, P, P)),
        ),
        vp_granted=jnp.where(pre_act, grant_pre, grant),
    )

    scope("tick.2_tally")
    # ---- 2. vote replies → tally → leadership
    # (reference: raft/raft_election.go:27-49) ----
    # Replies commute: the tally is an OR per voter slot and step-down
    # adopts the max reply term, so the whole phase is one elementwise
    # pass over the [G, cand(dst), voter(src)] view (fused r04; the old
    # per-src loop serialized P dependent chains for an order-invariant
    # reduction).
    arrived = vT(inbox.vp_active) & state.alive[:, :, None]
    reply_pre = vT(inbox.vp_pre)
    active = arrived & ~reply_pre
    m_term = vT(inbox.vp_term)
    granted = vT(inbox.vp_granted)
    higher_lane = active & (m_term > state.term[..., None])
    if cfg.prevote:
        # A refused pre reply carries the voter's actual term (see
        # phase 1): adopt a higher one just like the sim does —
        # without this, a candidate never learns a voter's real
        # term from a prevote refusal (liveness lag).
        higher_lane = higher_lane | (
            arrived & reply_pre & ~granted & (m_term > state.term[..., None])
        )
    adopt = jnp.max(jnp.where(higher_lane, m_term, -1), axis=2)
    state = _step_down(cfg, state, jnp.any(higher_lane, axis=2), adopt)
    good = (
        active
        & (state.role == CANDIDATE)[..., None]
        & (m_term == state.term[..., None])
        & granted
    )
    state = state._replace(votes=state.votes | good)
    if cfg.prevote:
        # Pre replies echo the proposed term (our term+1); stale
        # rounds (term moved on) are discarded.
        good_pre = (
            arrived
            & reply_pre
            & (m_term == state.term[..., None] + 1)
            & granted
        )
        state = state._replace(pre_votes=state.pre_votes | good_pre)

    if cfg.prevote:
        # Prevote quorum → promote to a REAL candidacy (the only place
        # a term bump happens in prevote mode).  The real vote requests
        # go out in phase 5 via ``promote``.
        diag = jnp.arange(P)[None, :, None] == jnp.arange(P)[None, None, :]
        if cfg.membership_on:
            # Joint phase: a prevote round wins only with BOTH quorums
            # (equal masks outside joint make this the single-quorum
            # check).  A candidate tallies against its OWN config view
            # — the latest config in its log, per effect-on-append.
            promote = (
                state.alive
                & (state.role != LEADER)
                & _quorum_met(state.pre_votes, state.voters_old, P)
                & _quorum_met(state.pre_votes, state.voters_new, P)
            )
        else:
            n_pre = jnp.sum(state.pre_votes, axis=-1)  # [G,P]
            promote = (
                state.alive & (state.role != LEADER) & (n_pre >= cfg.quorum)
            )
        state = state._replace(
            term=jnp.where(promote, state.term + 1, state.term),
            role=jnp.where(promote, CANDIDATE, state.role),
            voted_for=jnp.where(promote, pi, state.voted_for),
            votes=jnp.where(promote[..., None], diag, state.votes),
            pre_votes=jnp.where(promote[..., None], False, state.pre_votes),
            elect_dl=jnp.where(promote, now + jitter, state.elect_dl),
        )
    else:
        promote = None
    if cfg.membership_on:
        # Leadership needs a majority of C_old AND (while joint) of
        # C_new — the two masked tallies that make a config change safe
        # against a disjoint-quorum double election (Raft §6).
        become_leader = (
            (state.role == CANDIDATE)
            & state.alive
            & _quorum_met(state.votes, state.voters_old, P)
            & _quorum_met(state.votes, state.voters_new, P)
        )
    elif cfg.use_pallas:
        from .pallas_ops import vote_tally_pallas

        become_leader = vote_tally_pallas(
            state.votes,
            state.role,
            state.alive,
            cfg.quorum,
            interpret=cfg.pallas_interpret,
        )
    else:
        n_votes = jnp.sum(state.votes, axis=-1)  # [G,P]
        become_leader = (
            (state.role == CANDIDATE) & state.alive & (n_votes >= cfg.quorum)
        )
    if cfg.membership_on:
        # A leader elected while a config change is pending appends a
        # NO-OP at its own term (Raft thesis §6.4 / §3.6.2): the joint
        # or exit entry it inherited carries an older term, and the
        # current-term commit guard would otherwise stall the
        # transition forever on an idle group.  Gated on a pending
        # change so steady-state elections stay entry-free.
        noop = (
            become_leader
            & (state.joint | (state.cfg_idx > state.commit))
            & ((L - 2 - E - state.log_len) >= 1)
        )
        noop_idx = _last_index(state) + 1
        lanes_no = jnp.arange(L, dtype=jnp.int32)
        hit_no = (
            jnp.mod(lanes_no - noop_idx[..., None], L) == 0
        ) & noop[..., None]
        state = state._replace(
            log_term=jnp.where(hit_no, state.term[..., None], state.log_term),
            log_len=state.log_len + noop.astype(jnp.int32),
        )
    last_idx = _last_index(state)
    state = state._replace(
        role=jnp.where(become_leader, LEADER, state.role),
        next_idx=jnp.where(
            become_leader[..., None], (last_idx + 1)[..., None], state.next_idx
        ),
        match_idx=jnp.where(
            become_leader[..., None],
            jnp.where(pi[None] == pi[..., None], last_idx[..., None], 0),
            state.match_idx,
        ),
        hb_due=jnp.where(become_leader, now, state.hb_due),  # immediate HB
    )
    if cfg.check_quorum:
        # A fresh leader starts its check-quorum clock NOW: every peer
        # counts as just-heard, so the demotion below cannot fire off
        # acks owed to a previous reign.
        state = state._replace(
            last_ack=jnp.where(
                become_leader[..., None], now, state.last_ack
            )
        )

    scope("tick.3_append")
    # ---- 3. append requests (reference: raft/raft_append_entry.go:108-162) ----
    # One arbitrated pass (fused r04).  Distinct leaders always carry
    # distinct terms (election safety — a replica's appends all carry
    # terms at which IT led), so per destination at most one incoming
    # append is current: pick the max-term message (tie → lowest src,
    # the old loop's order) as the winner and process exactly it; every
    # other active message is answered with a failure reply carrying
    # our post-adoption term, which is what the old loop did for stale
    # messages and is equivalent to an at-most-once drop for the rare
    # lower-term-processed-first interleaving.
    act_in = vT(inbox.ar_active) & state.alive[:, :, None]  # [G,dst,src]
    m_term_all = vS(inbox.ar_term)
    term_key = jnp.where(act_in, m_term_all, -1)
    max_term_in = jnp.max(term_key, axis=2)  # [G,dst]
    is_max = act_in & (term_key == max_term_in[..., None])
    src_ids = jnp.arange(P, dtype=jnp.int32)
    win_src = jnp.min(jnp.where(is_max, src_ids, P), axis=2)  # [G,dst]
    sel = src_ids == win_src[..., None]  # [G,dst,src] one-hot (or none)
    pick = lambda x: jnp.sum(jnp.where(sel, vS(x), 0), axis=2)
    active = win_src < P  # [G,P] a message arrived at dst
    m_term = pick(inbox.ar_term)
    stale = active & (m_term < state.term)
    ok = active & ~stale
    # Accept leadership: step down, reset election timer.
    higher = ok & (m_term > state.term)
    state = state._replace(
        term=jnp.where(higher, m_term, state.term),
        voted_for=jnp.where(higher, -1, state.voted_for),
        role=jnp.where(ok, FOLLOWER, state.role),
    )
    state = state._replace(
        elect_dl=jnp.where(ok, now + jitter, state.elect_dl),
        last_heard=jnp.where(ok, now, state.last_heard),
    )
    if cfg.prevote:
        # Hearing a live leader ABORTS any in-flight prevote round:
        # grants collected during the leader's hiccup must not
        # promote one tick after we acknowledged it (etcd aborts
        # its campaign on MsgApp/MsgHeartbeat the same way).
        state = state._replace(
            pre_votes=jnp.where(ok[..., None], False, state.pre_votes)
        )

    prev = pick(inbox.ar_prev_idx)
    prev_t = pick(inbox.ar_prev_term)
    n_ent = pick(inbox.ar_n)
    snap = jnp.any(sel & vT(inbox.ar_snap), axis=2)

    # InstallSnapshot fast-forward (reference: raft/raft_snapshot.go:15-54).
    do_snap = ok & snap & (prev > state.commit)
    state = state._replace(
        base=jnp.where(do_snap, prev, state.base),
        base_term=jnp.where(do_snap, prev_t, state.base_term),
        log_len=jnp.where(do_snap, 0, state.log_len),
        commit=jnp.where(do_snap, prev, state.commit),
        applied=jnp.where(do_snap, prev, state.applied),
    )
    snap_handled = ok & snap

    # last AFTER any snapshot rebase so non-append rows keep a
    # consistent (base, len) pair.
    last = _last_index(state)
    apn = ok & ~snap
    in_window = (prev >= state.base) & (prev <= last)
    match = apn & in_window & (_term_at(cfg, state, prev) == prev_t)

    # Write entries prev+1..prev+n, truncating only at a genuine
    # conflict (reference: raft/raft_append_entry.go:146-155).
    # Scatter-free ring write (see _ring_write): slots within one
    # message are distinct mod L (E < L), so the lane mapping is
    # exact.
    ei = jnp.arange(E)  # [E]
    idx = prev[..., None] + 1 + ei  # [G,P,E]
    in_msg = match[..., None] & (ei < n_ent[..., None])
    # Winner's entry terms: [G,dst,src,E] selected down to [G,dst,E].
    incoming = jnp.sum(
        jnp.where(
            sel[..., None], jnp.swapaxes(inbox.ar_terms, 1, 2), 0
        ),
        axis=2,
    )
    exists = idx <= last[..., None]
    overlap = in_msg & exists
    # Steady-state skip: appends land strictly past ``last`` (no
    # overlap with existing entries), so the conflict-check ring
    # read has nothing to compare — elide it under a runtime cond.
    conflict_any = jax.lax.cond(
        jnp.any(overlap),
        lambda _: jnp.any(
            overlap
            & (_ring_read(state.log_term, idx, L) != incoming),
            axis=-1,
        ),
        # zeros_like(match), not zeros((G,P)): under shard_map's
        # rep-tracking both branches must vary over the mesh axis.
        lambda _: jnp.zeros_like(match),
        None,
    )  # [G,P]
    log = _ring_write(
        state.log_term, prev + 1, incoming,
        jnp.where(match, n_ent, 0), L,
    )
    state = state._replace(log_term=log)
    msg_last = prev + n_ent
    new_last = jnp.where(
        match,
        jnp.where(conflict_any, msg_last, jnp.maximum(last, msg_last)),
        last,
    )
    state = state._replace(log_len=new_last - state.base)
    # Follower commit (reference: raft/raft_append_entry.go:157-160).
    new_commit = jnp.minimum(pick(inbox.ar_commit), msg_last)
    state = state._replace(
        commit=jnp.where(
            match & (new_commit > state.commit), new_commit, state.commit
        )
    )

    if cfg.membership_on:
        # Config mirroring (effect-on-append without per-entry payload
        # plumbing — a deliberate divergence from entry-parse Raft): a
        # follower adopts the leader's whole config view when a
        # successful append proves its log COVERS the leader's latest
        # config entry (``cfg_idx <= prev + n``: log matching then
        # guarantees the entry at cfg_idx is the leader's).  Truncation
        # rollback falls out for free — a new leader with an older
        # config re-mirrors its view the same way.  A snapshot
        # fast-forward adopts unconditionally: config is part of
        # snapshot state (reference: raft/raft_snapshot.go InstallSnapshot
        # carries the config in etcd/thesis Raft).
        m_cfg_idx = pick(inbox.ar_cfg_idx)
        covered = m_cfg_idx <= (prev + n_ent)
        adopt_cfg = (match & covered) | do_snap
        m_joint = jnp.any(sel & vS(inbox.ar_cfg_joint), axis=2)
        state = state._replace(
            voters_old=jnp.where(
                adopt_cfg, pick(inbox.ar_cfg_old), state.voters_old
            ),
            voters_new=jnp.where(
                adopt_cfg, pick(inbox.ar_cfg_new), state.voters_new
            ),
            joint=jnp.where(adopt_cfg, m_joint, state.joint),
            cfg_epoch=jnp.where(
                adopt_cfg, pick(inbox.ar_cfg_epoch), state.cfg_epoch
            ),
            cfg_idx=jnp.where(adopt_cfg, m_cfg_idx, state.cfg_idx),
        )

    # Replies go to EVERY active sender ([G,dst,src] is out.ap's
    # [G,src,dst] layout: the replier is out's src).  Only the winner
    # can succeed; losers get failure + our current term, and their
    # per-message msg_last / conflict hints are computed elementwise.
    prev_all = vT(inbox.ar_prev_idx)
    msg_last_all = prev_all + vT(inbox.ar_n)
    # Conflict backoff: the committed prefix always matches, so
    # reposition to min(prev, commit+1) in one round (divergence
    # from the reference's term scan — see module docstring).
    conflict_all = jnp.minimum(prev_all, state.commit[..., None] + 1)
    success = match | snap_handled  # [G,P] winner outcome
    reply_match_w = jnp.where(snap_handled, prev, msg_last)
    out = out._replace(
        ap_active=act_in,
        ap_term=state.term,
        ap_success=sel & success[..., None],
        ap_match=jnp.where(sel, reply_match_w[..., None], msg_last_all),
        ap_conflict=conflict_all,
    )

    scope("tick.4_replies_commit")
    # ---- 4. append replies + quorum commit advance
    # (reference: raft/raft_append_entry.go:66-105 — the north-star) ----
    # Replies commute: each src's reply touches only its own
    # match/next slot and step-down adopts the max reply term, so the
    # whole phase is one elementwise pass over the
    # [G, leader(dst), src] view (fused r04).
    active = vT(inbox.ap_active) & state.alive[:, :, None]
    m_term = vS(inbox.ap_term)
    higher_lane = active & (m_term > state.term[..., None])
    adopt = jnp.max(jnp.where(higher_lane, m_term, -1), axis=2)
    state = _step_down(cfg, state, jnp.any(higher_lane, axis=2), adopt)
    good = (
        active
        & (state.role == LEADER)[..., None]
        & (m_term == state.term[..., None])
    )
    if cfg.check_quorum:
        # Any current-term reply — success OR conflict — proves the
        # peer is reachable and acknowledges this leadership; both
        # refresh the leader's per-peer last-ack clock.
        state = state._replace(
            last_ack=jnp.where(good, now, state.last_ack)
        )
    succ = good & vT(inbox.ap_success)
    fail = good & ~vT(inbox.ap_success)
    new_match = jnp.maximum(state.match_idx, vT(inbox.ap_match))
    state = state._replace(
        match_idx=jnp.where(succ, new_match, state.match_idx),
        next_idx=jnp.where(
            succ,
            # max(): appends are pipelined (next_idx advances
            # optimistically at send, phase 5c), so an ack for
            # batch k must not rewind past batches k+1... already
            # in flight.
            jnp.maximum(state.next_idx, new_match + 1),
            jnp.where(
                fail,
                # Floor at match_idx+1: a reordered stale
                # failure must not rewind below what this
                # follower has already acked.
                jnp.maximum(
                    jnp.clip(vT(inbox.ap_conflict), 1, None),
                    state.match_idx + 1,
                ),
                state.next_idx,
            ),
        ),
    )

    last_idx = _last_index(state)
    is_leader = (state.role == LEADER) & state.alive
    # Self always matches its own last entry.
    own = pi[None] == pi[..., None]  # [1,P,P] diag mask
    eff_match = jnp.where(own, last_idx[..., None], state.match_idx)
    if cfg.membership_on:
        # Joint commit rule: an index is committed only when a majority
        # of C_old AND a majority of C_new have matched it — the min of
        # the two masked quorum medians (equal outside joint).  A
        # leader REMOVED by the in-flight config still advances commit
        # here: the medians run over the voters' match columns, not the
        # leader's own lane, so it can commit the very entry that
        # removes it (Raft thesis §4.2.2).
        q_old = _quorum_kth(eff_match, state.voters_old, P)
        q_new = _quorum_kth(eff_match, state.voters_new, P)
        quorum_idx = jnp.minimum(q_old, q_new)
        # Current-term guard (reference: raft/raft_append_entry.go:98).
        guard = _term_at(cfg, state, quorum_idx) == state.term
        new_commit = jnp.where(
            is_leader & guard,
            jnp.maximum(state.commit, quorum_idx),
            state.commit,
        )
    elif cfg.use_pallas:
        from .pallas_ops import quorum_commit_pallas

        new_commit = quorum_commit_pallas(
            eff_match,
            state.term,
            state.commit,
            state.base,
            state.base_term,
            state.log_term,
            is_leader,
            cfg.quorum,
            interpret=cfg.pallas_interpret,
        )
    else:
        # k-th smallest via fused compare-swap network (jnp.sort on the
        # P axis costs ~1.6 ms at bench shapes).
        quorum_idx = _kth_smallest(eff_match, P - cfg.quorum)  # the median
        # Current-term guard (reference: raft/raft_append_entry.go:98).
        guard = _term_at(cfg, state, quorum_idx) == state.term
        new_commit = jnp.where(
            is_leader & guard,
            jnp.maximum(state.commit, quorum_idx),
            state.commit,
        )
    state = state._replace(commit=new_commit)

    scope("tick.4b_check_quorum")
    # ---- 4b. check-quorum: quorum-severed leaders release their
    # groups (etcd CheckQuorum analog; beyond the reference) ----
    if cfg.check_quorum:
        # Quorum-heard tick: the (P-quorum)-th smallest effective ack
        # (self slot = now) has ``quorum`` elements at or above it, so
        # it is the newest tick at which a full quorum had acked.
        eff_ack = jnp.where(own, now, state.last_ack)  # [G,P,P]
        if cfg.membership_on:
            # Joint check-quorum: the leader must be hearing BOTH
            # quorums — losing either one means it can no longer
            # commit, so it releases the group.  Learner acks are
            # masked out: a caught-up learner must never keep a
            # voter-severed leader alive.
            q_heard = jnp.minimum(
                _quorum_kth(eff_ack, state.voters_old, P),
                _quorum_kth(eff_ack, state.voters_new, P),
            )
        else:
            q_heard = _kth_smallest(eff_ack, P - cfg.quorum)  # [G,P]
        demote = (
            (state.role == LEADER)
            & state.alive
            & ((now - q_heard) >= cfg.ELECT_MAX)
        )
        state = _step_down(
            cfg, state, demote, state.term, clear_vote=False
        )
        # Full randomized backoff before the deposed leader campaigns:
        # while severed its prevotes cannot win anyway, and on heal the
        # surviving side's leader should not be raced immediately.
        state = state._replace(
            elect_dl=jnp.where(demote, now + jitter, state.elect_dl)
        )

    scope("tick.4c_membership")
    # ---- 4c. membership: a leader removed by a COMPLETED config
    # change steps down once the removing entry commits (Raft thesis
    # §4.2.2: it keeps leading — and committing — up to that point) ----
    if cfg.membership_on:
        self_voter = (
            ((state.voters_old | state.voters_new) >> pi) & 1
        ) == 1  # [G,P]
        removed = (
            (state.role == LEADER)
            & state.alive
            & ~state.joint
            & ~self_voter
            & (state.commit >= state.cfg_idx)
        )
        # Own-term demotion, like check-quorum: no higher term was
        # observed, so the vote must survive.
        state = _step_down(cfg, state, removed, state.term, clear_vote=False)
        state = state._replace(
            elect_dl=jnp.where(removed, now + jitter, state.elect_dl)
        )

    scope("tick.5_timers")
    # ---- 5. timers: elections (reference: raft/raft.go:106-125) ----
    timeout = state.alive & (now >= state.elect_dl) & (state.role != LEADER)
    if cfg.membership_on:
        # Non-voters (learners, removed peers) never campaign: their
        # own config view excludes them from both voter sets.  They
        # still GRANT votes — eligibility is the candidate's config,
        # tallied under the candidate's masks above.
        member = (((state.voters_old | state.voters_new) >> pi) & 1) == 1
        timeout = timeout & member
    if not cfg.prevote:
        state = state._replace(
            term=jnp.where(timeout, state.term + 1, state.term),
            role=jnp.where(timeout, CANDIDATE, state.role),
            voted_for=jnp.where(timeout, pi, state.voted_for),
            votes=jnp.where(timeout[..., None], own[0][None], state.votes),
            elect_dl=jnp.where(timeout, now + jitter, state.elect_dl),
        )
        send_real = timeout
        send_pre = jnp.zeros_like(timeout)
    else:
        # Timeout launches a fresh NON-BINDING prevote round: grant
        # ourselves, ask peers at term+1, reset the retry window.  No
        # term bump, no role change — promotion happened in phase 2.
        state = state._replace(
            pre_votes=jnp.where(timeout[..., None], own[0][None],
                                state.pre_votes),
            elect_dl=jnp.where(timeout, now + jitter, state.elect_dl),
        )
        # Phase-2 promotions announce immediately — unless a later
        # phase (3/4) already deposed the fresh candidate on a
        # higher-term message: a FOLLOWER must not broadcast real
        # RequestVote (voters would burn voted_for for a node that can
        # never tally them).
        send_real = promote & (state.role == CANDIDATE)
        send_pre = timeout  # disjoint: promote reset elect_dl this tick
    last_idx = _last_index(state)
    last_term = _term_at(cfg, state, last_idx)
    # Vote requests to every peer (dst masked to alive senders; self slot
    # excluded).
    sending = send_real | send_pre
    vr_act = sending[:, :, None] & ~own & state.alive[:, :, None]
    vr_term_per = jnp.where(send_pre, state.term + 1, state.term)
    out = out._replace(
        vr_active=vr_act,
        vr_term=vr_term_per,
        vr_last_idx=last_idx,
        vr_last_term=last_term,
        vr_pre=send_pre[:, :, None] & vr_act,
    )

    scope("tick.5a_membership")
    # ---- 5a-bis. membership: joint auto-exit.  A leader whose
    # C_old,new entry has COMMITTED appends the C_new exit entry
    # in-tick (no host round-trip in the transition's critical path)
    # and adopts it immediately — effect-on-append collapses old to
    # new, ending the dual-quorum phase.  Placed before ingest so the
    # capacity accounting and ``last_idx`` the firehose sees already
    # include the exit entry. ----
    if cfg.membership_on:
        last_idx = _last_index(state)
        can_exit = (
            (state.role == LEADER)
            & state.alive
            & state.joint
            & (state.commit >= state.cfg_idx)
            & ((L - 2 - E - state.log_len) >= 1)
        )
        exit_idx = last_idx + 1
        lanes_cfg = jnp.arange(L, dtype=jnp.int32)
        hit_cfg = (
            jnp.mod(lanes_cfg - exit_idx[..., None], L) == 0
        ) & can_exit[..., None]
        state = state._replace(
            log_term=jnp.where(
                hit_cfg, state.term[..., None], state.log_term
            ),
            log_len=state.log_len + can_exit.astype(jnp.int32),
            voters_old=jnp.where(
                can_exit, state.voters_new, state.voters_old
            ),
            joint=jnp.where(can_exit, False, state.joint),
            cfg_epoch=jnp.where(
                can_exit, state.cfg_epoch + 1, state.cfg_epoch
            ),
            cfg_idx=jnp.where(can_exit, exit_idx, state.cfg_idx),
        )

    scope("tick.5b_ingest")
    # ---- 5b. Start() ingestion: leaders append the firehose ----
    # Only the leader at the group's max alive term ingests: a zombie
    # leader (older term, still alive under message loss) can never
    # commit what it accepts, and letting it accept would corrupt the
    # per-group accepted/start_index payload-binding metrics (there is
    # exactly one leader per term by election safety).
    is_leader = (state.role == LEADER) & state.alive  # [G,P]
    group_max_term = jnp.max(
        jnp.where(state.alive, state.term, -1), axis=1, keepdims=True
    )
    is_leader = is_leader & (state.term == group_max_term)
    capacity = jnp.maximum(L - 2 - cfg.E - state.log_len, 0)
    want = jnp.minimum(new_cmds[:, None], cfg.INGEST)  # [G,P]
    accept = jnp.where(is_leader, jnp.minimum(want, capacity), 0)
    last_idx = _last_index(state)
    # Scatter-free lane write: every ingested entry carries the leader's
    # current term, so the per-lane value is just ``term`` — no inner
    # entry gather needed at all.
    lanes = jnp.arange(L, dtype=jnp.int32)
    e_l = jnp.mod(lanes - (last_idx[..., None] + 1), L)  # [G,P,L]
    hit = e_l < accept[..., None]
    log = jnp.where(hit, state.term[..., None], state.log_term)
    state = state._replace(log_term=log, log_len=state.log_len + accept)
    # Group accepted count (for host payload binding): the max-term
    # gate above guarantees at most one accepting replica per group,
    # so sum exactly collapses the P axis.
    accepted_per_group = jnp.sum(accept, axis=1)  # i32[G]
    start_index = jnp.sum(jnp.where(accept > 0, last_idx, 0), axis=1)

    scope("tick.5c_sends")
    # ---- 5c. append sends: heartbeat + lag repair
    # (reference: raft/raft_append_entry.go:4-65; heartbeats are full
    # appends carrying missing suffix) ----
    last_idx = _last_index(state)
    is_leader = (state.role == LEADER) & state.alive
    hb_fire = is_leader & (now >= state.hb_due)
    lag = state.next_idx <= last_idx[:, :, None]  # [G,P,P] dst lags
    send = (hb_fire[:, :, None] | (is_leader[:, :, None] & lag)) & ~own
    send = send & state.alive[:, :, None]
    prev = state.next_idx - 1  # [G,P,P] per (leader, dst)
    need_snap = prev < state.base[:, :, None]
    prev = jnp.where(need_snap, state.base[:, :, None], prev)
    # prev term per (g, p, dst): scatter-free read from sender's ring.
    prev_term = _ring_read(state.log_term, prev, L)  # [G,P,P]
    prev_term = jnp.where(
        prev == state.base[:, :, None], state.base_term[:, :, None], prev_term
    )
    n_send = jnp.where(
        need_snap, 0, jnp.clip(last_idx[:, :, None] - prev, 0, E)
    )
    # Outgoing suffix terms.  Fast path: log terms are monotone
    # non-decreasing and bounded by the sender's own term, so when
    # ``term_at(prev+1) == term`` the ENTIRE suffix carries the current
    # term — the [G,P,P,E]xL bulk ring read (the dominant op of the
    # steady-state tick) collapses to a broadcast.  The check itself is
    # an E-times-cheaper [G,P,P]xL read, and lagging/faulted cases fall
    # back to the exact full read under a runtime cond.
    first_term = _ring_read(state.log_term, prev + 1, L)  # [G,P,P]
    uniform = ~send | (n_send == 0) | (first_term == state.term[:, :, None])

    def _suffix_full(_):
        send_idx = prev[..., None] + 1 + jnp.arange(E)  # [G,P,P,E]
        return _ring_read(
            state.log_term, send_idx.reshape(G, P, P * E), L
        ).reshape(G, P, P, E)

    def _suffix_uniform(_):
        return jnp.broadcast_to(state.term[:, :, None, None], (G, P, P, E))

    t = jax.lax.cond(jnp.all(uniform), _suffix_uniform, _suffix_full, None)
    ar_terms = jnp.where(jnp.arange(E) < n_send[..., None], t, 0)
    out = out._replace(
        ar_active=send,
        ar_term=state.term,
        ar_prev_idx=prev,
        ar_prev_term=prev_term,
        ar_n=n_send,
        ar_terms=ar_terms,
        ar_commit=state.commit,
        ar_snap=need_snap & send,
        # Leader config view rides every append (phase-3 mirroring).
        ar_cfg_epoch=state.cfg_epoch,
        ar_cfg_idx=state.cfg_idx,
        ar_cfg_old=state.voters_old,
        ar_cfg_new=state.voters_new,
        ar_cfg_joint=state.joint,
    )
    state = state._replace(
        hb_due=jnp.where(hb_fire, now + cfg.HB_TICKS, state.hb_due),
        # Pipelined replication: advance next_idx at send time instead
        # of waiting the 2-tick ack RTT, so a fresh E-batch streams
        # every tick (2x steady-state throughput).  A dropped batch
        # self-heals: the follower's failure reply repositions next_idx
        # via the conflict backoff above.  (The reference replicator is
        # one-at-a-time per peer, raft/raft_append_entry.go:20-65 — a
        # deliberate divergence.)
        next_idx=jnp.where(send, state.next_idx + n_send, state.next_idx),
    )

    scope("tick.6_apply_compact")
    # ---- 6. apply frontier + ring compaction ----
    if not cfg.host_paced_compaction:
        state = state._replace(
            applied=jnp.maximum(state.applied, state.commit)
        )
    # Compact when headroom shrinks: advance base over the applied
    # prefix (device analog of service-driven Snapshot(),
    # reference: raft/raft_snapshot.go:3-13).
    headroom = L - state.log_len
    need = headroom < (cfg.E + cfg.INGEST + 2)
    target = jnp.minimum(state.applied, _last_index(state))
    new_base = jnp.where(need, jnp.maximum(state.base, target), state.base)
    new_base_term = _term_at(cfg, state, new_base)
    state = state._replace(
        log_len=_last_index(state) - new_base,
        base=new_base,
        base_term=new_base_term,
    )

    state = state._replace(tick_no=now)

    scope("tick.7_metrics")
    leader_commit_delta = jnp.where(
        (state.role == LEADER) & state.alive,
        state.commit - commit_before,
        0,
    )
    metrics = {
        "commits": jnp.sum(jnp.maximum(leader_commit_delta, 0)),
        "leaders": jnp.sum((state.role == LEADER) & state.alive),
        "max_term": jnp.max(state.term),
        "accepted": accepted_per_group,
        "start_index": start_index,
        # Term the accepted entries carry (the acceptor is the unique
        # max-term alive leader, so the sum collapses the P axis) —
        # lets the host bind payloads to (index, term), which is
        # unambiguous where index alone is not (conformance rig).
        "accept_term": jnp.sum(jnp.where(accept > 0, state.term, 0), axis=1),
        "commit_index": jnp.max(state.commit, axis=1),  # i32[G]
    }
    assert set(metrics) == set(METRIC_KEYS), (
        "tick metrics drifted from METRIC_KEYS — update core.py's "
        "constants (mesh.py and host.py derive their specs from them)"
    )
    return state, out, metrics


tick = functools.partial(jax.jit, static_argnums=0, donate_argnums=(1,))(
    tick_impl
)

# Per-tick record fields the traced bench loop stacks (i32[n_ticks, G]
# each): the ingest/commit frontiers and accept terms from which the
# bench reconstructs per-entry commit latency (measured, not modeled)
# and per-sampled-group operation histories for porcupine.
TRACE_KEYS = ("ing_hi", "accepted", "accept_term", "commit")


def make_traced_body(cfg: EngineConfig, new_cmds: jnp.ndarray, key: jax.Array):
    """The traced scan body shared by :func:`run_ticks_traced` and the
    mesh variant (engine/mesh.py) — one place derives the TRACE_KEYS
    record from the tick metrics, so the two bench paths can never
    desynchronize."""

    def body(carry, i):
        st, mb = carry
        st, mb, m = tick_impl(cfg, st, mb, new_cmds, jax.random.fold_in(key, i))
        rec = {
            # Last index after this tick's ingest at the accepting
            # leader; 0 on no-accept ticks (host takes a running max).
            "ing_hi": m["start_index"] + m["accepted"],
            "accepted": m["accepted"],
            "accept_term": m["accept_term"],
            "commit": m["commit_index"],
        }
        return (st, mb), rec

    return body


@functools.partial(jax.jit, static_argnums=(0, 3, 4), donate_argnums=(1, 2))
def run_ticks(
    cfg: EngineConfig,
    state: EngineState,
    inbox: Mailbox,
    n_ticks: int,
    ingest_per_tick: int,
    key: jax.Array,
) -> Tuple[EngineState, Mailbox]:
    """Device-resident multi-tick loop for the bench path: ``n_ticks``
    consensus rounds under one ``lax.scan`` with a constant Start()
    firehose — zero host round-trips between ticks (the whole point of
    the batched design: SURVEY §7.1's global synchronous tick loop).

    Committed-entry totals are exact from state alone:
    ``sum_g max_p commit[g,p]`` before vs after."""
    new_cmds = jnp.full((cfg.G,), ingest_per_tick, jnp.int32)

    def body(carry, i):
        st, mb = carry
        k = jax.random.fold_in(key, i)
        st, mb, _ = tick_impl(cfg, st, mb, new_cmds, k)
        return (st, mb), None

    (state, inbox), _ = jax.lax.scan(
        body, (state, inbox), jnp.arange(n_ticks, dtype=jnp.int32)
    )
    return state, inbox


@functools.partial(jax.jit, static_argnums=(0, 3, 4), donate_argnums=(1, 2))
def run_ticks_traced(
    cfg: EngineConfig,
    state: EngineState,
    inbox: Mailbox,
    n_ticks: int,
    ingest_per_tick: int,
    key: jax.Array,
) -> Tuple[EngineState, Mailbox, Dict[str, jnp.ndarray]]:
    """:func:`run_ticks` plus a per-tick record of the per-group
    ingest/commit frontiers and accept terms (``TRACE_KEYS``, each
    i32[n_ticks, G]) — the raw material for the bench's MEASURED
    commit-latency distribution and its porcupine verification of
    sampled groups (reconstructing each sampled group's operation
    history from what the device actually did, kvraft-style post-hoc
    checking of the flagship run; reference: kvraft test harness
    porcupine pass over the real op history).

    Still device-resident and scan-fused: the records are four [G]
    vectors appended to HBM per tick — noise against the tick's own
    traffic (the bench gates on <=2% throughput cost vs the untraced
    loop)."""
    new_cmds = jnp.full((cfg.G,), ingest_per_tick, jnp.int32)
    body = make_traced_body(cfg, new_cmds, key)
    (state, inbox), rec = jax.lax.scan(
        body, (state, inbox), jnp.arange(n_ticks, dtype=jnp.int32)
    )
    return state, inbox, rec


@functools.partial(jax.jit, static_argnums=(0, 3), donate_argnums=(1, 2))
def run_ticks_traced_vec(
    cfg: EngineConfig,
    state: EngineState,
    inbox: Mailbox,
    n_ticks: int,
    new_cmds: jnp.ndarray,
    key: jax.Array,
) -> Tuple[EngineState, Mailbox, Dict[str, jnp.ndarray]]:
    """:func:`run_ticks_traced` with a per-group ingest VECTOR — the
    skewed-firehose form (10% hot groups at full rate, the rest
    trickling) the config-#5 capture drives (BASELINE.json configs[4]:
    churn + snapshot storm + skewed shard load at 100k x 5)."""
    body = make_traced_body(cfg, new_cmds, key)
    (state, inbox), rec = jax.lax.scan(
        body, (state, inbox), jnp.arange(n_ticks, dtype=jnp.int32)
    )
    return state, inbox, rec
