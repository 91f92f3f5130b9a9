"""CLI entry point: ``python -m repro.serve`` — boot the warm daemon.

Constructs one :class:`repro.api.Session` (optionally preloading
campaign specs so their plans are parsed and sliced before the first
request), binds the localhost HTTP server, installs SIGTERM/SIGINT
drain handlers, and serves until drained::

    python -m repro.serve --port 8733 --cache .cache/hcr.jsonl \\
        --preload specs/fig10_gemm.json

``--port 0`` binds an ephemeral port (the chosen URL is printed on the
first line of stdout, so scripts can scrape it).  See
``docs/serving.md`` for the endpoint reference and
``repro.serve.client`` / ``examples/serve_client.py`` for clients.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from .server import DEFAULT_PORT


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.serve",
        description="Warm prediction daemon: one resident Session "
                    "(plans + (H, C, R) cache) serving predict/campaign/"
                    "report over localhost HTTP.")
    ap.add_argument("--host", default="127.0.0.1",
                    help="bind address (default 127.0.0.1; keep it local)")
    ap.add_argument("--port", type=int, default=DEFAULT_PORT,
                    help=f"TCP port (default {DEFAULT_PORT}; 0 = ephemeral)")
    ap.add_argument("--cache", default=None, metavar="PATH",
                    help="persistent (H, C, R) store backing every "
                         "request (default: in-memory only)")
    ap.add_argument("--systems", action="append", default=[],
                    metavar="PATH",
                    help="extra system-catalog file/dir (repeatable)")
    ap.add_argument("--preload", action="append", default=[],
                    metavar="SPEC",
                    help="campaign/suite spec whose workloads are parsed "
                         "and planned at boot (repeatable)")
    ap.add_argument("--drain-timeout", type=float, default=60.0,
                    metavar="S", help="max seconds to wait for in-flight "
                                      "requests on shutdown (default 60)")
    ap.add_argument("--workers", type=int, default=1, metavar="N",
                    help="N > 1 boots a supervised worker fleet behind "
                         "this listener instead of a single daemon "
                         "(see docs/robustness.md)")
    ap.add_argument("--fault-plan", default=None, metavar="PLAN",
                    help="TEST ONLY: seeded fault-injection plan (path "
                         "or inline JSON) activated in the daemon / its "
                         "workers — see repro.serve.faults")
    ap.add_argument("--verbose", action="store_true",
                    help="log every request to stderr")
    args = ap.parse_args(argv)

    if args.workers > 1:
        # the fleet ships work to separate worker *processes*: refuse to
        # boot if any registered backend class could not cross that
        # boundary (same check the process-pool campaign executor makes)
        errs = _portability_errors()
        if errs:
            for e in errs:
                print(f"error: {e}", file=sys.stderr)
            return 2
        from .fleet import FleetSupervisor
        fleet = FleetSupervisor(
            workers=args.workers, cache_path=args.cache,
            systems=tuple(args.systems), preload=tuple(args.preload),
            host=args.host, port=args.port, fault_plan=args.fault_plan,
            verbose=args.verbose)
        fleet.install_signal_handlers()
        fleet.start()       # workers + monitor + front listener thread
        # first stdout line is machine-readable: scripts scrape the URL
        print(json.dumps({"url": fleet.url, "pid": os.getpid(),
                          "workers": args.workers}), flush=True)
        while not fleet.stopped.wait(0.2):   # main thread: signals only
            pass
        return 0

    if args.fault_plan:
        from . import faults
        os.environ[faults.ENV_PLAN] = args.fault_plan

    from ..launch.compile_cache import enable_compile_cache
    from .server import PredictionServer, PredictionService
    enable_compile_cache()
    service = PredictionService(cache_path=args.cache,
                                systems=tuple(args.systems))
    for err in _portability_errors(service):
        # a single daemon serves in-process by default, but a client may
        # still request executor='process' — warn loudly at boot instead
        # of failing at request time
        print(f"warning: {err}", file=sys.stderr)
    for spec in args.preload:
        info = service.preload(spec)
        print(f"preloaded {spec}: {len(info['workloads'])} workloads, "
              f"{info['plans_built']} plans", file=sys.stderr)
    server = PredictionServer(service, host=args.host, port=args.port,
                              drain_timeout_s=args.drain_timeout,
                              verbose=args.verbose)
    # first stdout line is machine-readable: scripts scrape the URL
    print(json.dumps({"url": server.url, "pid": os.getpid()}), flush=True)
    server.install_signal_handlers()
    server.serve_forever()
    return 0


def _portability_errors(service=None) -> list[str]:
    """Boot check: every registered backend class must be importable at
    module level to cross a worker-process boundary (fleet workers, the
    process-pool campaign executor).  Checks the service's session
    registries when given one, else the global vocabularies."""
    if service is not None:
        regs = [service.session.estimators, service.session.topologies]
    else:
        from ..core.registry import ESTIMATORS, TOPOLOGIES
        regs = [ESTIMATORS, TOPOLOGIES]
    errs: list[str] = []
    for reg in regs:
        r = reg
        while r is not None:            # scoped session registries chain
            errs.extend(r.portability_errors())
            r = r.parent
    return errs


if __name__ == "__main__":
    raise SystemExit(main())
