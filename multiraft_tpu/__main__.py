"""Command-line entry: serve engines and talk to them.

The reference ships as a Go library driven by `go test`; this
framework additionally deploys.  The CLI wraps the server entrypoints
(`distributed.engine_server`) and a one-shot client so an operator can
stand up a chip-owning KV service and poke it without writing code:

    python -m multiraft_tpu serve-kv --port 7000 --groups 64 \
        --data-dir /var/lib/mrt --platform tpu
    python -m multiraft_tpu kv put  --addr 127.0.0.1:7000 greeting hello
    python -m multiraft_tpu kv get  --addr 127.0.0.1:7000 greeting

Sharded/fleet serving uses the same flags plus --gids/--peer; process
supervision (restart-on-crash, placement) belongs to the operator's
init system — a restarted durable server recovers from --data-dir.
"""

from __future__ import annotations

import argparse
import sys


def _serve_forever(args, build) -> int:
    """Shared serve scaffold: claim the device, build the node, print
    the readiness line, park the main thread.

    ``--platform`` is a pin that fails when JAX cannot bring that
    backend up; without it the server runs on what JAX selects.  Either
    way one stderr line at readiness names the device.

    SIGTERM/SIGINT shut down gracefully: a durable server writes a
    final checkpoint (rotating the WAL away), so the next start
    recovers instantly instead of replaying — kill -9 remains the
    crash path and recovers via WAL replay."""
    import signal
    import threading
    import time

    from .utils.device import claim_device, device_line

    t0 = time.perf_counter()
    try:
        dev = claim_device(args.platform or "")
    except RuntimeError as exc:
        print(f"error: {exc}", file=sys.stderr, flush=True)
        return 1
    backend_s = time.perf_counter() - t0  # import jax to the first device
    try:
        node = build()
    except ValueError as exc:
        # What the flags ask for cannot be served: a shape EngineConfig
        # refuses, or a --data-dir written at another replica count or
        # mesh size.
        print(f"error: {exc}", file=sys.stderr, flush=True)
        return 1
    # Time to ready by stage: gauges in every Obs.snapshot, and one line.
    stages = f"backend={backend_s:.3f}" + "".join(
        f" {name[len('ready.'):-len('_s')]}={secs:.3f}"
        for name, secs in node.obs.metrics.gauges.items()
        if name.startswith("ready.")
    )
    node.obs.metrics.set("ready.backend_s", backend_s)
    stop = threading.Event()

    def _on_signal(*_):
        if stop.is_set():
            # Second signal: the graceful path is wedged (e.g. a stalled
            # device mid-checkpoint) — force-exit like the pre-handler
            # behavior instead of sitting out the run_call timeout.
            import os as _os

            _os._exit(130)
        stop.set()

    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, _on_signal)
    print(f"ready-in seconds: {stages}", file=sys.stderr, flush=True)
    print(f"device {device_line(dev)}", file=sys.stderr, flush=True)
    print(f"ready {node.port}", flush=True)
    stop.wait()
    svc = getattr(node, "engine_service", None)
    if svc is not None:
        # On the loop thread: checkpoint at a tick boundary, not mid-pump.
        node.sched.run_call(svc.final_checkpoint, timeout=600.0)
    node.close()
    return 0


def _cmd_serve_kv(args) -> int:
    def build():
        from .distributed.engine_server import serve_engine_kv

        return serve_engine_kv(
            port=args.port,
            G=args.groups,
            host=args.host,
            seed=args.seed,
            data_dir=args.data_dir,
            checkpoint_every_s=args.checkpoint_every,
            mesh_devices=args.mesh_devices,
            replicas=args.replicas,
        )

    return _serve_forever(args, build)


def _cmd_serve_shardkv(args) -> int:
    def build():
        from .distributed.engine_server import serve_engine_shardkv

        peer_addrs = {}
        for spec in args.peer or []:
            gid, addr = spec.split("=", 1)
            h, p = addr.rsplit(":", 1)
            peer_addrs[int(gid)] = (h, int(p))
        gids = [int(g) for g in args.gids.split(",")] if args.gids else None
        hosted = gids if gids is not None else list(range(1, args.groups))
        return serve_engine_shardkv(
            port=args.port,
            G=args.groups,
            host=args.host,
            seed=args.seed,
            join_gids=_gid_list(args.join, hosted) if args.join else None,
            shards=args.shards,
            gids=gids,
            peer_addrs=peer_addrs or None,
            data_dir=args.data_dir,
            checkpoint_every_s=args.checkpoint_every,
            mesh_devices=args.mesh_devices,
            replicas=args.replicas,
        )

    return _serve_forever(args, build)


def _cmd_kv(args) -> int:
    from .distributed.engine_server import EngineClerk
    from .distributed.tcp import RpcNode
    from .sim.scheduler import TIMEOUT

    if args.op != "get" and args.value is None:
        # Silently writing "" on a forgotten value would be data
        # destruction with exit code 0.
        print(f"error: kv {args.op} requires a VALUE", file=sys.stderr)
        return 2
    h, p = args.addr.rsplit(":", 1)
    node = RpcNode()
    try:
        end = node.client_end(h, int(p))
        ck = EngineClerk(node.sched, end, service=args.service)
        if args.op == "get":
            gen = ck.get(args.key)
        elif args.op == "put":
            gen = ck.put(args.key, args.value)
        else:
            gen = ck.append(args.key, args.value)
        out = node.sched.wait(node.sched.spawn(gen), args.timeout)
        if out is TIMEOUT:
            print("error: server did not answer", file=sys.stderr)
            return 1
        if args.op == "get":
            print(out)
        return 0
    finally:
        node.close()


def _replicas(text: str) -> int:
    """``--replicas``: a group commits with a majority, so an even count
    buys no failure the odd count below it does not survive, and fewer
    than 3 survive none.  The upper limit is ``EngineConfig``'s."""
    n = int(text)
    if n < 3 or n % 2 == 0:
        raise argparse.ArgumentTypeError(
            f"{n}: replicas a group must be odd and at least 3 (a group "
            f"of 2f+1 survives f failed replicas; an even count adds "
            f"none)"
        )
    return n


def _gid_list(text: str, hosted) -> list:
    """``--join``: ``all`` (every replica group this server hosts,
    whatever ``--groups`` is), or a comma list of gids and ranges
    (``1,2,7-9``; a range includes both ends)."""
    if text == "all":
        return list(hosted)
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def _add_serve_flags(p: argparse.ArgumentParser) -> None:
    p.add_argument("--port", type=int, default=0,
                   help="listen port (0 = ephemeral, printed on ready)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--groups", type=int, default=64,
                   help="engine consensus groups (G)")
    p.add_argument("--replicas", type=_replicas, default=3, metavar="N",
                   help="replicas a group (odd, >= 3; default 3): a group "
                        "keeps serving with N//2 of them down.  A "
                        "--data-dir written at another N is refused")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--data-dir", default=None,
                   help="enable durability (checkpoints + WAL) here")
    p.add_argument("--checkpoint-every", type=float, default=30.0,
                   metavar="SECONDS")
    p.add_argument("--mesh-devices", type=int, default=0,
                   help="run the tick over this many local chips")
    p.add_argument("--platform", default=None, choices=("cpu", "tpu"),
                   help="pin the jax backend and fail if it does not come "
                        "up (tpu = own the chip); default: what JAX selects")


def main(argv=None) -> int:
    top = argparse.ArgumentParser(prog="multiraft_tpu", description=__doc__)
    sub = top.add_subparsers(dest="cmd", required=True)

    s1 = sub.add_parser("serve-kv", help="chip-owning engine KV server")
    _add_serve_flags(s1)
    s1.set_defaults(fn=_cmd_serve_kv)

    s2 = sub.add_parser("serve-shardkv",
                        help="sharded engine server (standalone or fleet)")
    _add_serve_flags(s2)
    s2.add_argument("--join", default=None, metavar="all|GID,LO-HI",
                    help="bootstrap-join these gids in ONE join operation "
                         "(config 1) before readiness: `all` = every "
                         "replica group this server hosts, or a comma list "
                         "of gids and ranges (1,2,7-9)")
    s2.add_argument("--shards", type=int, default=10, metavar="N",
                    help="the shard space (default 10, the reference's, "
                         "keyed by a key's first byte); any other N hashes "
                         "the whole key (crc32 %% N).  A --data-dir written "
                         "at another N is refused")
    s2.add_argument("--gids", default=None, metavar="GID,GID",
                    help="fleet mode: the global gids THIS process hosts")
    s2.add_argument("--peer", action="append", metavar="GID=HOST:PORT",
                    help="fleet mode: owner address of a remote gid")
    s2.set_defaults(fn=_cmd_serve_shardkv)

    s3 = sub.add_parser("kv", help="one-shot client op")
    s3.add_argument("op", choices=("get", "put", "append"))
    s3.add_argument("key")
    s3.add_argument("value", nargs="?", default=None)
    s3.add_argument("--addr", required=True, metavar="HOST:PORT")
    s3.add_argument("--service", default="EngineKV",
                    choices=("EngineKV", "EngineShardKV"))
    s3.add_argument("--timeout", type=float, default=30.0)
    s3.set_defaults(fn=_cmd_kv)

    args = top.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
