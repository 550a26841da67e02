"""One run of one benchmark cell on the served path of the sweep service.

Everything a cell is made of is data, found by name:

* ``workloads/<cell>.json``: the cell's ``config``, ``traffic``, ``chips``
  and its output ``check`` (requests sampled, limits);
* ``configs/<config>.json``: the deployment (ring, Δ grid, backend, mesh),
  with two optional keys: ``sweep_fields``, further ``WindowSweep`` fields
  that every request carries (``traffic.spec_for``), and ``reference``, the
  name of its plain-reference module (default ``reference``);
* ``<reference>.py``: a plain reference, which declares the
  ``sweep_fields`` keys (``SWEEP_FIELDS``) and ``window`` values
  (``WINDOWS``) it implements; the contract is in ``compare.py``;
* ``traffic/<mix>.json``: the mix's parameters, read by ``traffic.py``;
* ``metrics/<metric>.py``: one reader per metric, ``read(run)``, which
  returns a number or None where it finds nothing to read;
* ``BENCHMARK.json`` at the root: which metrics each cell reports.

A configuration whose reference does not declare its ``window`` or one of
its ``sweep_fields`` is refused before anything is served.

A run: check the platform, build the service, serve the mix's warm-up
requests (every pass shape the mix can produce), then serve the mix for
``seconds`` in the daemon's round order (intake, ``flush_ready``,
``step(force=False)``), with every request and response through the wire
codec.  Requests due in the window are drained after it.  Then read the
device's peak memory, free the service, and compare a sample of the
answered requests with the configuration's plain reference.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

from . import compare, trace_reduce, traffic
from .traffic import BenchError

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
#: the program's compile cache, at a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass(frozen=True)
class Layout:
    """Where a run finds its data files and the metric declarations."""

    data: pathlib.Path = HERE
    benchmark: pathlib.Path = ROOT / "BENCHMARK.json"

    def read(self, kind: str, name: str) -> dict:
        path = self.data / kind / f"{name}.json"
        if not path.is_file():
            raise BenchError(f"no {kind[:-1]} named {name!r} ({path})")
        return json.loads(path.read_text())

    def reader(self, metric: str):
        path = self.data / "metrics" / f"{metric}.py"
        if not path.is_file():
            raise BenchError(f"no reader for metric {metric!r} ({path})")
        return _load(path, "chipbench_metric_" + metric.replace(".", "_")).read

    def reference(self, config: dict):
        """The plain-reference module ``config`` names, checked against it."""
        name = config.get("reference", "reference")
        path = self.data / f"{name}.py"
        if not name.isidentifier() or not path.is_file():
            raise BenchError(f"no reference module named {name!r} ({path})")
        ref = _load(path, "chipbench_reference_" + name)
        windows = getattr(ref, "WINDOWS", ())
        fields = getattr(ref, "SWEEP_FIELDS", ())
        of = f"of config {config.get('name')!r}"
        if config["window"] not in windows:
            raise BenchError(
                f"reference {name!r} implements window {windows}, not "
                f"window {config['window']!r} {of}")
        for key in config.get("sweep_fields", {}):
            if key not in fields:
                raise BenchError(
                    f"reference {name!r} does not implement sweep_fields "
                    f"key {key!r} {of} (it declares {fields})")
        return ref

    def metrics(self, cell: str, traced: bool) -> list[dict]:
        """The cell's end-to-end metrics, or with ``traced`` its per-layer."""
        bench = json.loads(self.benchmark.read_text())
        group = bench["per_layer" if traced else "end_to_end"]
        return [m for m in group if cell in m.get("workloads", [cell])]


def _load(path: pathlib.Path, module: str):
    spec = importlib.util.spec_from_file_location(module, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Served:
    """One request of the window and what became of it."""

    req: traffic.Request
    submitted: float | None = None
    answered: float | None = None
    response: object = None

    @property
    def ok(self) -> bool:
        return self.answered is not None and self.response.error is None


@dataclasses.dataclass
class Run:
    """What the metric readers read; times are seconds from window start."""

    cell: str
    config: dict
    mix: dict
    chips: int
    seconds: float
    setup_s: float
    served: list
    window_end: float
    stats: dict                 # ServiceStats over the window and its drain
    compiles: int               # executables built or loaded in the window
    trace: object = None        # trace_reduce.Trace of a traced run

    @property
    def devices(self) -> list[int]:
        return list(range(self.chips))


def check_platform(chips: int):
    """The devices to run on; refuses anything but enough TPU chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found platform {devs[0].platform!r} "
                         f"({devs[0].device_kind}, {len(devs)} device(s))")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)} {devs[0].device_kind}")
    peaks(devs[0].device_kind)
    return devs


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; unknown kinds refused."""
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def enable_compile_cache() -> str:
    """JAX's persistent cache at the checkout's fixed path, for every entry."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    from repro import compile_cache
    where = compile_cache.enable()
    import jax
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return where


def make_service(config: dict):
    from repro.service.api import SweepService
    mesh = None
    if config.get("mesh"):
        from repro.compat import make_mesh
        axes = config["mesh"]
        mesh = make_mesh(tuple(axes.values()), tuple(axes))
    return SweepService(mesh=mesh)


def _window_sweep(spec: dict):
    from repro.experiments.sweep import WindowSweep
    return WindowSweep(**spec)


class Client:
    """Serves requests through the wire codec in the daemon's round order."""

    def __init__(self, service, clock, source=None):
        from repro.service import wire
        self.wire, self.service, self.clock = wire, service, clock
        self.source = source
        self.waiting: dict[str, list[Served]] = {}
        self.served: list[Served] = []
        service.on_response = self._on_response

    def _on_response(self, resp) -> None:
        now = self.clock()
        doc = json.loads(json.dumps(self.wire.encode_response(resp)))
        decoded = self.wire.decode_response(doc)
        for s in self.waiting.pop(resp.request_id, []):
            s.answered, s.response = now, decoded
            if self.source is not None:
                self.source.answered(s.req, now)

    def submit(self, req: traffic.Request) -> Served:
        line = json.dumps(self.wire.encode_request(
            _window_sweep(req.spec), req.requester))
        spec, who = self.wire.decode_request(json.loads(line))
        s = Served(req, submitted=self.clock())
        rid = self.service.submit(spec, who).request_id
        self.waiting.setdefault(rid, []).append(s)
        self.served.append(s)
        return s

    def serve_all(self, reqs) -> None:
        """Submit ``reqs`` and serve them to the end (warm-up)."""
        for r in reqs:
            self.submit(r)
        while self.waiting:
            self.service.flush_ready()
            if not self.service.step(force=False) and self.waiting:
                self.service.step(force=True)

    def run_window(self, annotate) -> None:
        """Serve the source's requests until every one due is answered."""
        src, service = self.source, self.service
        while True:
            with annotate("intake"):
                for r in src.take_due(self.clock()):
                    self.submit(r)
            with annotate("flush_ready"):
                service.flush_ready()
            with annotate("service.step"):
                n = service.step(force=False)
            if n:
                continue
            nxt = src.next_due()
            if self.waiting:
                if not service.scheduler.n_pending:
                    raise BenchError(f"{len(self.waiting)} requests wait "
                                     f"with no work pending")
                continue
            if nxt is None:
                return
            wait = nxt - self.clock()
            if wait > 0:
                with annotate("generator_wait"):
                    time.sleep(wait)


def memory_peak(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, layout: Layout = Layout(),
             require_tpu: bool = True, keep_trace: str | None = None) -> dict:
    """One run of ``cell``; returns the result line as a dict.

    ``require_tpu=False`` skips the platform check and the compile cache
    (the tests drive the rest of a run on the CPU).
    """
    spec = layout.read("workloads", cell)
    config = layout.read("configs", spec["config"])
    mix = layout.read("traffic", spec["traffic"])
    chips = int(spec["chips"])
    readers = [(m, layout.reader(m["name"]))
               for m in layout.metrics(cell, trace)]
    ref = layout.reference(config)
    traffic.spec_for(config, mix["request"], 0)   # refuses clashing fields
    import jax
    if require_tpu:
        devs = check_platform(chips)
        log(f"cell {cell}: {devs[0].device_kind} x{len(devs)}, compile "
            f"cache {enable_compile_cache()}")
    else:
        devs = jax.devices()
    with compile_events() as compile_times:
        run, peak = measure(cell, config, mix, chips, seed, seconds, trace,
                            t_start, keep_trace, compile_times)
    metrics = {}
    for m, read in readers:
        value = read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    failed = sum(1 for s in run.served if not s.ok)
    log(f"window {seconds:g} s: {len(run.served)} requests, {failed} failed, "
        f"last answer at {run.window_end:.3f} s, stats {run.stats}")
    checks = compare.check(run.served, config, spec["check"], seed, ref)
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())
    out = {"correct": correct, "attempted": len(run.served),
           "failed": failed, "metrics": metrics,
           "device": {"platform": devs[0].platform,
                      "kind": devs[0].device_kind, "count": chips,
                      "memory_peak_bytes": peak}}
    if run.trace is not None:
        out["device"]["busy_s"] = trace_reduce.busy_s(run.trace, run.devices)
        out["device"]["window_s"] = run.trace.window_s
        out["breakdown"] = {
            "device_ops": trace_reduce.top_ops(run.trace, run.devices),
            "idle_gaps": trace_reduce.idle_gaps(run.trace, run.devices[0])}
    out["checks"] = checks
    return out


@contextlib.contextmanager
def compile_events():
    """Times at which executables were built or loaded, while open."""
    import jax
    times: list[float] = []

    def on_duration(event, duration, **kw):
        if event == BACKEND_COMPILE:
            times.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield times
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


def warm_up(config: dict, mix: dict, clock) -> None:
    """Serve the mix's warm-up requests on a scratch service, then free it."""
    warm = Client(make_service(config), clock)
    for batch in traffic.warmup_batches(mix, config):
        warm.serve_all(batch)
    del warm
    gc.collect()


def measure(cell, config, mix, chips, seed, seconds, trace, t_start,
            keep_trace, compile_times) -> tuple[Run, int | None]:
    """Warm up, serve the window, and free the service.

    Returns the run and the peak device memory, read before the service's
    state is freed and before any reference runs.
    """
    import jax
    clock0 = [0.0]

    def clock():
        return time.perf_counter() - clock0[0]

    warm_up(config, mix, clock)
    service = make_service(config)
    client = Client(service, clock, traffic.Source(mix, config, seed,
                                                   seconds))
    stats0 = service.stats.snapshot()
    if trace:
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(tdir)
        annotate = jax.profiler.TraceAnnotation
    else:
        def annotate(name):
            return contextlib.nullcontext()
    clock0[0] = time.perf_counter()
    setup_s = clock0[0] - t_start
    with annotate("window"):
        client.run_window(annotate)
    n_compiles = sum(1 for t in compile_times if t >= clock0[0])
    window_end = max((s.answered for s in client.served
                      if s.answered is not None), default=clock())
    tr = None
    if trace:
        jax.profiler.stop_trace()
        path = trace_reduce.find_xplane(tdir)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(path, keep_trace)
        tr = trace_reduce.load(path)
        shutil.rmtree(tdir, ignore_errors=True)
    run = Run(cell=cell, config=config, mix=mix, chips=chips,
              seconds=seconds, setup_s=setup_s, served=client.served,
              window_end=window_end,
              stats=service.stats.diff(stats0).as_dict(),
              compiles=n_compiles, trace=tr)
    peak = memory_peak(jax.devices()[:chips])
    del client, service
    gc.collect()
    return run, peak
