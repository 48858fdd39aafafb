"""Typed registry of every ``MRT_*`` environment knob.

Every runtime tunable the deployment plane reads from the environment
is declared ONCE here — name, type, default, owning module and a doc
line — and read through the typed accessors (:func:`knob_str`,
:func:`knob_int`, :func:`knob_float`, :func:`knob_bool`).  graftlint's
``env-knob`` rule makes a raw ``os.environ`` read of an ``MRT_*`` name
anywhere else in the package a finding, and an accessor call with an
undeclared name a finding, so a knob cannot ship half-registered: the
table is what generates ``docs/KNOBS.md`` and what the CI drift gate
checks doc/workflow mentions against.

Semantics (canonical across every knob — historical call sites had
four different bool spellings, now unified):

* ``bool`` — set-and-not-falsey is ON; ``"" / 0 / false / no / off``
  (case-insensitive) are OFF; unset means the declared default.
* ``int`` / ``float`` — parsed; unset, empty or unparsable values fall
  back to the default (a typo'd knob must not crash a server at
  import, matching the old ``_env_f`` helpers).
* ``str`` — unset or empty means the default (``None`` for "feature
  off" path knobs like ``MRT_TRACE_DIR``).

A default of ``None`` marks a DYNAMIC knob: the declared default
depends on the host (CPU count, sibling knob) and the call site must
pass ``default=``.

CLI:

    python -m multiraft_tpu.utils.knobs --write   # regenerate docs/KNOBS.md
    python -m multiraft_tpu.utils.knobs --check   # CI drift gate
"""

from __future__ import annotations

import os
import re
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional, Tuple

__all__ = [
    "Knob",
    "KNOBS",
    "knob_str",
    "knob_int",
    "knob_float",
    "knob_bool",
    "render_doc",
    "doc_drift",
]


@dataclass(frozen=True)
class Knob:
    """One declared environment knob."""

    name: str
    type: str  # "str" | "int" | "float" | "bool"
    default: object  # None = dynamic (call site supplies default=)
    module: str  # owning module, dotted under multiraft_tpu
    doc: str  # one-line effect description


# The whole registry.  Keep sorted by (module, name) — the generated
# doc table follows this order and the round-trip test pins it.
KNOBS: Tuple[Knob, ...] = (
    # -- analysis.postmortem ------------------------------------------------
    Knob("MRT_CPUSAT_PERMILLE", "int", 850, "analysis.postmortem",
         "PROF breadcrumb CPU-busy permille at/above which the doctor "
         "calls a stall window cpu_saturation rather than "
         "queueing_collapse."),
    # -- distributed.admission ---------------------------------------------
    Knob("MRT_ADMISSION", "bool", True, "distributed.admission",
         "Kill switch for token-bucket admission control at the "
         "dispatch layer (off = every request admitted)."),
    Knob("MRT_ADMIT_BURST", "float", None, "distributed.admission",
         "Admission bucket burst size in ops (dynamic default: "
         "MRT_ADMIT_RATE / 8, ~125 ms of headroom at the rate)."),
    Knob("MRT_ADMIT_INFLIGHT", "int", 512, "distributed.admission",
         "Per-connection cap on dispatched-but-unreplied requests "
         "before admission sheds with retry_after_s."),
    Knob("MRT_ADMIT_RATE", "float", 1600.0, "distributed.admission",
         "Global admitted ops/s for the node's token bucket (~0.8x "
         "the measured knee of the load curve)."),
    Knob("MRT_ADMIT_RETRY_S", "float", 0.05, "distributed.admission",
         "Base retry-after hint handed to shed clients (scaled up "
         "with brownout level)."),
    Knob("MRT_ADMIT_SESSION_RATE", "float", None, "distributed.admission",
         "Per-session admitted ops/s (dynamic default: MRT_ADMIT_RATE, "
         "i.e. no per-session throttling below the global bucket)."),
    Knob("MRT_BROWNOUT_FACTORS", "str", "", "distributed.admission",
         "CSV of shed factors per brownout state overriding the "
         "built-in healthy/shedding/brownout ladder."),
    # -- distributed.cluster ------------------------------------------------
    Knob("MRT_DEBUG", "bool", False, "distributed.cluster",
         "Verbose harness/cluster debug logging to stderr."),
    # -- distributed.engine_cluster ------------------------------------------
    Knob("MRT_ENGINE_PLATFORM", "str", "", "distributed.engine_cluster",
         "JAX platform (cpu/tpu) engine-cluster launches write into "
         "each server spec: it becomes the child's JAX_PLATFORMS and "
         "the child fails if that backend does not come up. Empty = "
         "no pin, the child runs on what JAX selects from the "
         "launcher's environment."),
    # -- distributed.pump_cycle ----------------------------------------------
    Knob("MRT_PIPELINE_DEPTH", "int", 2, "distributed.pump_cycle",
         "In-flight fused tick batches the pipelined pump keeps "
         "dispatched (overlaps host bookkeeping with device compute); "
         "durable servers pin it to 1 so every checkpoint sees a "
         "drained pipeline."),
    Knob("MRT_PUMP_IDLE_S", "float", 0.002, "distributed.pump_cycle",
         "Idle engine-pump cadence in seconds (the adaptive cadence's "
         "slow interval when no traffic is flowing)."),
    # -- distributed.flightrec ----------------------------------------------
    Knob("MRT_FLIGHTREC_DIR", "str", None, "distributed.flightrec",
         "Directory for the crash-safe flight-recorder rings; unset "
         "disables the recorder entirely."),
    Knob("MRT_FLIGHTREC_SLOTS", "int", 8192, "distributed.flightrec",
         "Ring capacity in fixed-width event slots per process."),
    # -- distributed.launch -------------------------------------------------
    Knob("MRT_SERVER_LOG_DIR", "str", None, "distributed.launch",
         "Directory for per-server-child stdout/stderr capture files; "
         "unset inherits the parent's streams."),
    # -- distributed.observe ------------------------------------------------
    Knob("MRT_OBS_MAX_EVENTS", "int", 50000, "distributed.observe",
         "Bound on buffered observability events per process before "
         "the oldest are dropped."),
    Knob("MRT_STAGECLOCK", "bool", True, "distributed.observe",
         "Per-stage serving-path CPU segment accounting (the "
         "cpu_*_us_per_op loadcurve columns); off removes the clocks."),
    # -- distributed.overload -----------------------------------------------
    Knob("MRT_BROWNOUT_DOWN", "int", 8, "distributed.overload",
         "Consecutive clean overload-watch ticks required to "
         "de-escalate one brownout level."),
    Knob("MRT_BROWNOUT_UP", "int", 2, "distributed.overload",
         "Consecutive tripping overload-watch ticks required to "
         "escalate one brownout level."),
    Knob("MRT_OVERLOAD_BACKLOG", "float", 4096.0, "distributed.overload",
         "Engine dispatch backlog depth the overload watch treats as "
         "a trip."),
    Knob("MRT_OVERLOAD_INTERVAL", "float", 0.25, "distributed.overload",
         "Overload watch period in seconds."),
    Knob("MRT_OVERLOAD_P99_MS", "float", 100.0, "distributed.overload",
         "Per-stage p99 latency bound in ms; a window past it trips "
         "the brownout machine."),
    Knob("MRT_OVERLOAD_REPLYQ", "float", 1024.0, "distributed.overload",
         "Queued-replies depth the overload watch treats as a trip."),
    Knob("MRT_OVERLOAD_WAL", "float", 4096.0, "distributed.overload",
         "Appended-but-unsynced WAL record count the overload watch "
         "treats as a trip."),
    Knob("MRT_OVERLOAD_WATCH", "bool", True, "distributed.overload",
         "Kill switch for the overload watch / brownout controller."),
    # -- distributed.placement ----------------------------------------------
    Knob("MRT_PLACE_COOLDOWN_S", "float", 5.0, "distributed.placement",
         "Minimum seconds between planner migration decisions "
         "(anti-thrash)."),
    Knob("MRT_PLACE_DEAD_S", "float", 3.0, "distributed.placement",
         "Seconds without a heartbeat scrape before a process is "
         "declared dead and its groups re-placed."),
    Knob("MRT_PLACE_MAX_MOVES", "int", 1, "distributed.placement",
         "Max group migrations per planner decision."),
    Knob("MRT_PLACE_MIN_GAIN", "float", 0.25, "distributed.placement",
         "Minimum fractional load-spread improvement before the "
         "planner bothers moving a group."),
    Knob("MRT_PLACE_REPLACE", "bool", True, "distributed.placement",
         "Kill switch for automated dead-voter replacement via joint "
         "consensus."),
    Knob("MRT_PLACE_REPLACE_DEADLINE_S", "float", 30.0,
         "distributed.placement",
         "Seconds a group may sit on a reduced quorum before the "
         "doctor flags the replacement leg as stuck."),
    Knob("MRT_PLACE_SCRAPE_S", "float", 0.5, "distributed.placement",
         "Placement controller metric-scrape period in seconds."),
    # -- distributed.profile ------------------------------------------------
    Knob("MRT_PROFILE", "bool", True, "distributed.profile",
         "Continuous stack-sampling profiler, default on within its "
         "measured <2% budget."),
    Knob("MRT_PROFILE_DEPTH", "int", 48, "distributed.profile",
         "Max frames kept per sampled stack."),
    Knob("MRT_PROFILE_HZ", "float", None, "distributed.profile",
         "Sampling rate override (dynamic default: 67 Hz with spare "
         "cores, 19 Hz on a 1-CPU host)."),
    Knob("MRT_PROFILE_MAX_STACKS", "int", 5000, "distributed.profile",
         "Distinct-stack table cap; overflow folds into a sentinel "
         "frame."),
    # -- distributed.realtime -----------------------------------------------
    Knob("MRT_PUMP_HOT", "bool", None, "distributed.realtime",
         "Hot engine pump (spin between ticks instead of sleeping); "
         "dynamic default: on with spare cores, off on a 1-CPU host."),
    # -- distributed.sanitize -----------------------------------------------
    Knob("MRT_SANITIZE", "bool", False, "distributed.sanitize",
         "Runtime invariant sanitizer (deep frame/state checks on the "
         "serving path); default off for speed."),
    Knob("MRT_SANITIZE_CB_BUDGET_MS", "float", 250.0,
         "distributed.sanitize",
         "Callback wall-clock budget in ms before the sanitizer "
         "records an overrun."),
    Knob("MRT_SANITIZE_STRICT", "bool", False, "distributed.sanitize",
         "Escalate sanitizer findings from flight-record events to "
         "raised exceptions."),
    # -- distributed.stateplane ---------------------------------------------
    Knob("MRT_SHIP_SYNC", "bool", False, "distributed.stateplane",
         "Acks gate on state shipment (zero acknowledged-write loss; "
         "the durable chaos gate runs with this on)."),
    Knob("MRT_SHIP_TAIL_CAP", "int", 512, "distributed.stateplane",
         "Re-snapshot early once the unshipped tail exceeds this many "
         "records (bounds standby replay time)."),
    Knob("MRT_SHIP_WINDOW_S", "float", 5.0, "distributed.stateplane",
         "Snapshot shipment cadence; the bound on data loss when "
         "async shipping races a death."),
    # -- distributed.tail ---------------------------------------------------
    Knob("MRT_TAIL", "bool", True, "distributed.tail",
         "Per-request lifecycle exemplars with tail-based sampling "
         "(the Obs.tail plane); off removes the per-request record."),
    Knob("MRT_TAIL_RESERVOIR", "int", 64, "distributed.tail",
         "Reservoir size for NORMAL (under-SLO) request exemplars "
         "kept per drain window."),
    Knob("MRT_TAIL_SLO_CAP", "int", 4096, "distributed.tail",
         "Hard bound on guaranteed over-SLO exemplars held between "
         "drains; overflow is counted, not stored."),
    Knob("MRT_TAIL_SLO_MS", "float", 250.0, "distributed.tail",
         "Total-latency SLO in ms; every request over it is retained "
         "verbatim (up to MRT_TAIL_SLO_CAP) until the next Obs.tail "
         "drain and breadcrumbed into the flight ring."),
    Knob("MRT_TAIL_TOPK", "int", 16, "distributed.tail",
         "Windowed top-k: the k slowest requests since the last drain "
         "are retained even when under the SLO."),
    # -- distributed.tcp ----------------------------------------------------
    Knob("MRT_DEBUG_RPC", "bool", False, "distributed.tcp",
         "Per-frame RPC debug logging on the wire path."),
    Knob("MRT_REPLY_Q_CAP", "int", 4096, "distributed.tcp",
         "Bound on queued unsent replies per connection before "
         "backpressure engages."),
    Knob("MRT_SPIN_US", "int", None, "distributed.tcp",
         "Epoll busy-poll spin budget in microseconds (dynamic "
         "default: CPU-count dependent)."),
    Knob("MRT_TRACE_DIR", "str", None, "distributed.tcp",
         "Directory for per-node Chrome-trace span capture; unset "
         "disables tracing."),
    Knob("MRT_WIRE_LEGACY", "bool", False, "distributed.tcp",
         "Speak the pre-capability legacy wire dialect (no hello "
         "capability negotiation) for interop tests."),
    # -- distributed.wedge --------------------------------------------------
    Knob("MRT_WEDGE_INTERVAL", "float", 0.25, "distributed.wedge",
         "Wedge watchdog check period in seconds."),
    Knob("MRT_WEDGE_TICKS", "int", 8, "distributed.wedge",
         "Consecutive no-progress checks before a group is declared "
         "wedged and flight-recorded."),
    Knob("MRT_WEDGE_WATCH", "bool", True, "distributed.wedge",
         "Kill switch for the wedge watchdog."),
    # -- engine.core --------------------------------------------------------
    Knob("MRT_CHECK_QUORUM", "bool", True, "engine.core",
         "Check-quorum leader self-demotion (kill switch, paired "
         "with MRT_PREVOTE for the CI A/B matrix)."),
    Knob("MRT_MEMBERSHIP", "bool", True, "engine.core",
         "Joint-consensus membership change support (kill switch)."),
    Knob("MRT_PREVOTE", "bool", True, "engine.core",
         "PreVote election mode (kill switch for the legacy CI arm)."),
    # -- engine.host --------------------------------------------------------
    Knob("MRT_ENGINE_PIPELINE", "bool", True, "engine.host",
         "Asynchronous engine pipeline: fused multi-tick device scan "
         "plus a dedicated pump thread; 0 restores the serial per-tick "
         "step and the synchronous pump loop for clean A/B."),
    # -- harness.nemesis ----------------------------------------------------
    Knob("MRT_POSTMORTEM_DIR", "str", None, "harness.nemesis",
         "Directory where a failed chaos run drops its evidence "
         "bundle for the postmortem doctor."),
)

_BY_NAME = {k.name: k for k in KNOBS}

# Canonical falsey spellings for bool knobs (case-insensitive).
_FALSEY = ("", "0", "false", "no", "off")


def _declared(name: str, expect: str) -> Knob:
    k = _BY_NAME.get(name)
    if k is None:
        raise KeyError(
            f"undeclared env knob {name!r}: add it to KNOBS in "
            f"multiraft_tpu/utils/knobs.py"
        )
    if k.type != expect:
        raise TypeError(
            f"env knob {name} is declared {k.type!r}, read as {expect!r}"
        )
    return k


def knob_str(name: str, default: Optional[str] = None) -> Optional[str]:
    """Declared string knob; unset/empty → ``default`` if given, else
    the declared default."""
    k = _declared(name, "str")
    raw = os.environ.get(name)
    if raw:
        return raw
    return default if default is not None else k.default  # type: ignore[return-value]


def knob_int(name: str, default: Optional[int] = None) -> int:
    k = _declared(name, "int")
    fallback = default if default is not None else k.default
    if fallback is None:
        raise TypeError(f"dynamic knob {name} needs an explicit default=")
    raw = os.environ.get(name, "")
    try:
        return int(raw) if raw else int(fallback)  # type: ignore[arg-type]
    except ValueError:
        return int(fallback)  # type: ignore[arg-type]


def knob_float(name: str, default: Optional[float] = None) -> float:
    k = _declared(name, "float")
    fallback = default if default is not None else k.default
    if fallback is None:
        raise TypeError(f"dynamic knob {name} needs an explicit default=")
    raw = os.environ.get(name, "")
    try:
        return float(raw) if raw else float(fallback)  # type: ignore[arg-type]
    except ValueError:
        return float(fallback)  # type: ignore[arg-type]


def knob_bool(name: str, default: Optional[bool] = None) -> bool:
    k = _declared(name, "bool")
    raw = os.environ.get(name)
    if raw is None:
        if default is not None:
            return bool(default)
        return bool(k.default)
    return raw.strip().lower() not in _FALSEY


# ---------------------------------------------------------------------------
# docs/KNOBS.md generation + drift gate
# ---------------------------------------------------------------------------

_REPO_ROOT = Path(__file__).resolve().parents[2]
_DOC_PATH = _REPO_ROOT / "docs" / "KNOBS.md"

# Surfaces the drift gate scans for MRT_* mentions: every token must
# name a declared knob (a trailing-underscore token like MRT_PLACE_*
# is a prefix mention and must match at least one declared knob).
_SCAN_GLOBS = ("README.md", "docs/*.md", ".github/workflows/*.yml")
_TOKEN = re.compile(r"MRT_[A-Z0-9_]+")


def _fmt_default(k: Knob) -> str:
    if k.default is None:
        return "(dynamic)" if k.type != "str" else "(unset)"
    if k.type == "bool":
        return "on" if k.default else "off"
    if k.type == "str":
        return f'`"{k.default}"`' if k.default != "" else '`""`'
    return f"`{k.default}`"


def render_doc() -> str:
    """The full docs/KNOBS.md content from the declared table."""
    lines = [
        "# Environment knobs",
        "",
        "Every `MRT_*` runtime tunable, generated from the declared",
        "registry in `multiraft_tpu/utils/knobs.py` — do not edit by",
        "hand; regenerate with `python -m multiraft_tpu.utils.knobs",
        "--write`.  CI (`scripts/check.py`) fails when this file is",
        "stale or when a doc/workflow mentions an undeclared knob.",
        "",
        "Bool knobs: set-and-not-falsey is on; `\"\"`/`0`/`false`/`no`/",
        "`off` are off; unset means the default.  `(dynamic)` defaults",
        "depend on the host (CPU count or a sibling knob) — the doc",
        "line says which.",
        "",
        "| Knob | Type | Default | Owning module | Effect |",
        "|---|---|---|---|---|",
    ]
    for k in KNOBS:
        lines.append(
            f"| `{k.name}` | {k.type} | {_fmt_default(k)} | "
            f"`multiraft_tpu/{k.module.replace('.', '/')}.py` | {k.doc} |"
        )
    lines.append("")
    return "\n".join(lines)


def doc_drift(repo_root: Optional[Path] = None) -> List[str]:
    """Drift problems: stale generated doc, or an MRT_* token in a doc
    or workflow surface that names no declared knob."""
    root = Path(repo_root) if repo_root is not None else _REPO_ROOT
    problems: List[str] = []
    doc = root / "docs" / "KNOBS.md"
    if not doc.exists():
        problems.append(f"{doc}: missing (run --write)")
    elif doc.read_text(encoding="utf-8") != render_doc():
        problems.append(f"{doc}: stale vs. the declared KNOBS table "
                        f"(run --write)")
    declared = set(_BY_NAME)
    for pattern in _SCAN_GLOBS:
        for f in sorted(root.glob(pattern)):
            for i, line in enumerate(
                f.read_text(encoding="utf-8").splitlines(), start=1
            ):
                for tok in _TOKEN.findall(line):
                    if tok in declared:
                        continue
                    if tok.endswith("_"):
                        # Prefix mention ("MRT_PLACE_*"): fine while
                        # at least one declared knob carries it.
                        if any(n.startswith(tok) for n in declared):
                            continue
                    problems.append(
                        f"{f.relative_to(root)}:{i}: mentions "
                        f"undeclared knob {tok}"
                    )
    return problems


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="multiraft_tpu.utils.knobs")
    g = ap.add_mutually_exclusive_group(required=True)
    g.add_argument("--write", action="store_true",
                   help="regenerate docs/KNOBS.md")
    g.add_argument("--check", action="store_true",
                   help="fail on generated-doc staleness or undeclared "
                        "knob mentions")
    ns = ap.parse_args(argv)
    if ns.write:
        _DOC_PATH.parent.mkdir(parents=True, exist_ok=True)
        _DOC_PATH.write_text(render_doc(), encoding="utf-8")
        print(f"knobs: wrote {_DOC_PATH} ({len(KNOBS)} knobs)")
        return 0
    problems = doc_drift()
    for p in problems:
        print(f"knobs: {p}", file=sys.stderr)
    if problems:
        print(f"knobs: {len(problems)} drift problem(s)", file=sys.stderr)
        return 1
    print(f"knobs: clean ({len(KNOBS)} knobs declared)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
