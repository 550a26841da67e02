"""``python -m repro.service`` — drain a queue, or run the serve daemon.

One-shot drain (the original mode)::

    python -m repro.service queue.jsonl [--out responses.jsonl]
        [--fake-devices N] [--mesh data=2,model=4] [--state-cache PATH]
        [--max-batch-rows N] [--max-wait-rounds N] [--fairness-rows N]
        [--quota-rows N] [--engine-retries N]
        [--metrics-dir DIR] [--trace FILE]

Each input line is a wire-schema request (see ``wire.py``); one response
line is written per input line, in queue order, streamed/flushed as each
completes.  Malformed lines get structured ``error`` responses instead of
aborting the drain.

Daemon mode::

    python -m repro.service serve --intake DIR [--out responses.jsonl]
        [--state-cache PATH] [--poll 0.25] [--idle-exit-rounds N]
        [--max-line-bytes N] [...same service knobs as above...]
        [--metrics-dir DIR] [--trace FILE]

Both modes accept ``--metrics-dir`` (atomic ``metrics.json`` +
``metrics.prom`` snapshots of the live registry: paper observables per
pass, service health, daemon phase timing) and ``--trace`` (Chrome-trace
JSON, one span per coalesced pass annotated with its CompatKey, row
counts, and cache provenance).  Render/validate either with
``python -m repro.obs summarize [--check]``.  Telemetry is strictly
off-path: responses are bit-identical with or without these flags.

Watches DIR for ``*.jsonl`` request files, serves continuously (arrivals
batched per scheduler round, per-requester quotas on top of the Eq. (3)
fairness window), renames processed files to ``*.done``, and appends
responses as they complete.  SIGTERM/SIGINT flush in-flight work and exit
cleanly; see ``daemon.py``.

``--fake-devices`` forces an N-device CPU platform (for
``backend="sharded"`` requests on a development host) and therefore must
be applied *before* JAX loads — which is why this module parses arguments
before importing the service and the package ``__init__`` is lazy.  If JAX
is somehow already imported the flag fails loudly instead of silently
no-opping.

Compiled programs persist across runs in JAX's compilation cache:
``JAX_COMPILATION_CACHE_DIR`` where set, else ``.jax_cache`` in the
checkout (``repro.compile_cache``).
"""
from __future__ import annotations

import argparse
import os
import sys

from .. import compile_cache


def _parse_mesh(text: str) -> list[tuple[str, int]]:
    out = []
    for part in text.split(","):
        name, _, size = part.partition("=")
        if not size:
            raise argparse.ArgumentTypeError(
                f"mesh axis {part!r} is not name=size")
        out.append((name.strip(), int(size)))
    return out


def _add_service_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--fake-devices", type=int, default=0, metavar="N",
                    help="force an N-device CPU platform (sharded requests "
                         "on a dev host); must run before JAX imports")
    ap.add_argument("--mesh", type=_parse_mesh, default=None,
                    metavar="data=2,model=4",
                    help="device mesh for backend='sharded' requests")
    ap.add_argument("--state-cache", default=None, metavar="PATH",
                    help="persist/restore the burned-state cache here "
                         "(npz; survives process restarts)")
    ap.add_argument("--max-batch-rows", type=int, default=4096)
    ap.add_argument("--max-wait-rounds", type=int, default=0)
    ap.add_argument("--fairness-rows", type=float, default=float("inf"),
                    help="Eq. (3) window over cumulative served rows "
                         "(laggard = GVT); inf disables")
    ap.add_argument("--quota-rows", type=float, default=float("inf"),
                    help="per-requester row budget per scheduling round "
                         "(tenant-layer Delta); inf disables")
    ap.add_argument("--engine-retries", type=int, default=0,
                    help="capped-backoff retries per failing device pass "
                         "before the per-request error response")
    ap.add_argument("--state-cache-rows", type=int, default=65536,
                    help="LRU bound of the burned-state cache, in rows")
    ap.add_argument("--metrics-dir", default=None, metavar="DIR",
                    help="write atomic metrics.json/metrics.prom snapshots "
                         "here (live paper observables + service health; "
                         "see repro.obs)")
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="record a Chrome-trace/Perfetto JSON here (one "
                         "span per coalesced pass, CompatKey-annotated)")


def _apply_fake_devices(args) -> int:
    """Set XLA_FLAGS for --fake-devices; error loudly if JAX beat us."""
    if not args.fake_devices:
        return 0
    if "jax" in sys.modules:
        print("error: --fake-devices must take effect before JAX is "
              "imported, but 'jax' is already in sys.modules — the flag "
              "would silently do nothing.  Run this CLI in a fresh "
              "process, or export XLA_FLAGS="
              f"--xla_force_host_platform_device_count={args.fake_devices} "
              "before starting Python.", file=sys.stderr)
        return 2
    flag = f"--xla_force_host_platform_device_count={args.fake_devices}"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
    return 0


def _build_mesh(args):
    """The device mesh for --mesh, or an error-message string."""
    if not args.mesh:
        return None
    import jax
    import numpy as np
    from jax.sharding import Mesh
    names = [n for n, _ in args.mesh]
    sizes = [s for _, s in args.mesh]
    n_dev = int(np.prod(sizes))
    if len(jax.devices()) < n_dev:
        return f"mesh needs {n_dev} devices, have {len(jax.devices())}"
    devs = np.asarray(jax.devices()[:n_dev]).reshape(sizes)
    return Mesh(devs, tuple(names))


def _build_telemetry(args):
    """A ``repro.obs.Telemetry`` bundle when either flag asks for one."""
    if not (args.metrics_dir or args.trace):
        return None
    from ..obs import Telemetry, TraceRecorder
    return Telemetry(tracer=TraceRecorder() if args.trace else None)


def _build_service(args, telemetry=None):
    from .api import SweepService
    mesh = _build_mesh(args)
    if isinstance(mesh, str):
        print(f"error: {mesh}", file=sys.stderr)
        return None
    return SweepService(mesh=mesh,
                        max_batch_rows=args.max_batch_rows,
                        max_wait_rounds=args.max_wait_rounds,
                        fairness_rows=args.fairness_rows,
                        quota_rows=args.quota_rows,
                        engine_retries=args.engine_retries,
                        state_cache_rows=args.state_cache_rows,
                        telemetry=telemetry)


def _summary(stats) -> str:
    return (f"served {stats.n_requests} request(s): "
            f"{stats.n_deduped} deduped, {stats.n_errors} error(s), "
            f"{stats.n_passes} coalesced pass(es), "
            f"{stats.rows_computed} rows computed, "
            f"{stats.rows_from_state_cache} rows from state cache, "
            f"{stats.engine_row_steps} engine row-steps; state cache "
            f"{stats.state_cache_hits} hit(s) / "
            f"{stats.state_cache_misses} miss(es) / "
            f"{stats.state_cache_evictions} eviction(s)")


def _main_drain(argv) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.service",
        description="Drain a JSONL window-sweep request queue "
                    "(or: `serve` for daemon mode).")
    ap.add_argument("queue", help="JSONL file of wire-schema requests")
    ap.add_argument("--out", default=None,
                    help="responses JSONL path (default: stdout)")
    _add_service_args(ap)
    args = ap.parse_args(argv)

    if _apply_fake_devices(args):
        return 2
    compile_cache.enable()

    # deferred so --fake-devices lands before the first JAX import
    from .wire import serve_queue

    tel = _build_telemetry(args)
    service = _build_service(args, telemetry=tel)
    if service is None:
        return 2
    if args.state_cache and os.path.exists(args.state_cache):
        service.state_cache.load(args.state_cache)
    if tel is not None and tel.tracer is not None:
        from ..obs import set_tracer
        set_tracer(tel.tracer)     # library-level spans join the trace
    if args.out:
        with open(args.out, "w") as fh:
            stats = serve_queue(args.queue, fh, service=service)
    else:
        stats = serve_queue(args.queue, sys.stdout, service=service)
    if args.state_cache and service.state_cache.dirty:
        service.state_cache.save(args.state_cache)
    if tel is not None:
        if args.metrics_dir:
            from ..obs import write_snapshot
            write_snapshot(tel.registry, args.metrics_dir)
        if args.trace:
            tel.tracer.save(args.trace)
    print(_summary(stats), file=sys.stderr)
    return 0


def _main_serve(argv) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.service serve",
        description="Long-running watch-directory sweep-service daemon.")
    ap.add_argument("--intake", required=True, metavar="DIR",
                    help="directory watched for *.jsonl request files "
                         "(processed files are renamed to *.done)")
    ap.add_argument("--out", default="responses.jsonl",
                    help="responses JSONL, append mode (default: "
                         "responses.jsonl)")
    ap.add_argument("--poll", type=float, default=0.25, metavar="SECONDS",
                    help="idle poll interval")
    ap.add_argument("--idle-exit-rounds", type=int, default=None,
                    metavar="N",
                    help="exit cleanly after N consecutive idle rounds "
                         "(default: run until SIGTERM)")
    ap.add_argument("--max-rounds", type=int, default=None, metavar="N",
                    help="hard cap on serve rounds (tests/smoke)")
    ap.add_argument("--max-line-bytes", type=int, default=None, metavar="N",
                    help="intake cap per request line (default 1 MiB); "
                         "longer lines get structured oversize errors")
    ap.add_argument("--max-files-per-round", type=int, default=None,
                    metavar="N",
                    help="intake meter: at most N request files per round")
    ap.add_argument("--crash-after-passes", type=int, default=None,
                    help=argparse.SUPPRESS)   # fault injection (tests)
    _add_service_args(ap)
    args = ap.parse_args(argv)

    if _apply_fake_devices(args):
        return 2
    compile_cache.enable()

    from .daemon import DaemonConfig, serve_daemon
    from .wire import DEFAULT_MAX_LINE_BYTES

    tel = _build_telemetry(args)
    service = _build_service(args, telemetry=tel)
    if service is None:
        return 2
    if tel is not None and tel.tracer is not None:
        from ..obs import set_tracer
        set_tracer(tel.tracer)     # library-level spans join the trace
    cfg = DaemonConfig(
        intake_dir=args.intake, out_path=args.out,
        state_cache_path=args.state_cache,
        poll_interval_s=args.poll,
        max_line_bytes=(DEFAULT_MAX_LINE_BYTES if args.max_line_bytes is None
                        else args.max_line_bytes),
        max_files_per_round=args.max_files_per_round,
        idle_exit_rounds=args.idle_exit_rounds,
        max_rounds=args.max_rounds,
        crash_after_passes=args.crash_after_passes,
        metrics_dir=args.metrics_dir,
        trace_path=args.trace)
    stats = serve_daemon(cfg, service=service)
    print(_summary(stats), file=sys.stderr)
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "serve":
        return _main_serve(argv[1:])
    return _main_drain(argv)


if __name__ == "__main__":
    sys.exit(main())
