"""Telemetry tests: registry semantics, goldens, off-path bit-identity.

The contract under test (``repro.obs``, ISSUE 10): instrumentation is
strictly off-path — it observes host-side values the instrumented code
already materialized, so telemetry-on responses are bit-identical to
telemetry-off responses (checked single-device in-process and on an
8-fake-device mesh in a subprocess).  Exposition is deterministic: the
Prometheus text and Chrome-trace JSON renderings are golden-filed under a
fixed clock and re-render byte-identically.  ``python -m repro.obs
summarize --check`` (the CI gate) accepts what the daemon writes and
rejects empty snapshots, missing paper observables, and non-nesting
spans.
"""
import itertools
import json
import math
import os
import subprocess
import sys
import textwrap

import pytest

from repro.obs import (MetricsRegistry, ProfilerRecorder, Telemetry,
                       TraceRecorder, current_tracer, set_tracer, span,
                       to_prometheus, write_snapshot)
from repro.obs.summarize import (REQUIRED_SERVICE_SERIES, check_metrics,
                                 check_trace, load_any)
from repro.obs.summarize import main as summarize_main

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the shared single-device pass shape of the service telemetry tests
COMMON = dict(Ls=(16,), n_vs=(2,), replicas=4, n_steps=32, burn_in=16,
              backend="pallas_multistep", k_fuse=8)


# ---------------------------------------------------------------------------
# metrics core
# ---------------------------------------------------------------------------


def test_counter_monotone():
    reg = MetricsRegistry()
    c = reg.counter("c", "help text")
    c.inc()
    c.inc(2.5, requester="alice")
    assert c.value() == 1.0
    assert c.value(requester="alice") == 2.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_counter_set_total_mirrors_external_ledger():
    # the service syncs ServiceStats fields via set_total: monotone, and a
    # regression (ledger went backwards) is a loud programming error
    reg = MetricsRegistry()
    c = reg.counter("c")
    c.set_total(5)
    c.set_total(5)
    c.set_total(9)
    assert c.value() == 9.0
    with pytest.raises(ValueError):
        c.set_total(3)


def test_gauge_goes_both_ways():
    g = MetricsRegistry().gauge("g")
    g.set(4.0)
    g.set(1.5)
    assert g.value() == 1.5
    assert g.value(other="labels") == 0.0


def test_histogram_counts_and_validation():
    reg = MetricsRegistry()
    h = reg.histogram("h", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 3.0, 100.0):
        h.observe(v)
    (series,) = h.series.values()
    assert series["counts"] == [2, 0, 1, 1]      # le=1 is inclusive
    assert series["count"] == 4 == h.count()
    assert series["sum"] == pytest.approx(104.5)
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=())
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=(1.0, 1.0))    # duplicate bound


def test_registry_get_or_create_and_kind_clash():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    assert len(reg) == 1


def test_series_materialize_on_first_update_only():
    # "series present in a snapshot" must mean the instrumented path ran —
    # merely creating instruments exposes nothing
    reg = MetricsRegistry(clock=lambda: 0.0)
    reg.counter("never_used")
    reg.histogram("never_observed")
    assert reg.snapshot()["series"] == []
    assert to_prometheus(reg) == ""


# ---------------------------------------------------------------------------
# exposition goldens (fixed clock -> byte-stable)
# ---------------------------------------------------------------------------


def _golden_registry() -> MetricsRegistry:
    reg = MetricsRegistry(clock=lambda: 1700000000.0)
    req = reg.counter("repro_service_requests", "wire requests accepted")
    req.inc(5)
    served = reg.counter("repro_service_served_rows",
                         "rows returned, by requester", unit="rows")
    served.inc(8, requester="alice")
    served.inc(4, requester="bob")
    reg.gauge("repro_service_coalescing_ratio",
              "rows requested / rows computed").set(1.5)
    u = reg.histogram("repro_pass_u", "per-pass mean utilization",
                      buckets=(0.25, 0.5, 1.0))
    u.observe(0.125)
    u.observe(0.75)
    reg.histogram("repro_pass_w2", "per-pass mean squared width",
                  unit="tau^2", buckets=(1.0, 4.0, 16.0)).observe(2.5)
    reg.histogram("repro_pass_window_occupancy", "spread / Delta",
                  buckets=(0.5, 1.0)).observe(0.8)
    return reg


def test_prometheus_golden():
    text = to_prometheus(_golden_registry())
    with open(os.path.join(GOLDEN, "obs_metrics.prom")) as fh:
        assert text == fh.read()
    # deterministic: re-rendering an unchanged registry is byte-identical
    assert to_prometheus(_golden_registry()) == text


def test_prometheus_label_escaping():
    reg = MetricsRegistry()
    reg.counter("c").inc(1, path='a"b\\c\nd')
    line = to_prometheus(reg).splitlines()[-1]
    assert line == 'c{path="a\\"b\\\\c\\nd"} 1'


def _step_clock(step=1.0):
    counter = itertools.count()
    return lambda: step * next(counter)


def _golden_tracer() -> TraceRecorder:
    tr = TraceRecorder(clock=_step_clock(), pid=1)   # ticks 0, 1, 2, ... s
    with tr.span("round", cat="daemon", args={"round": 1}):
        with tr.span("pass", cat="service") as sp:
            sp.args.update(n_rows=12, rows_burned=12, rows_from_cache=0)
        with tr.span("reduce"):
            pass
    return tr


def test_trace_golden(tmp_path):
    path = tmp_path / "trace.json"
    _golden_tracer().save(path)
    with open(path) as fh, \
            open(os.path.join(GOLDEN, "obs_trace.json")) as golden:
        assert fh.read() == golden.read()
    assert check_trace(load_any(path)[1]) == []


def test_trace_span_error_annotation():
    tr = TraceRecorder(clock=_step_clock(), pid=1)
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    (ev,) = tr.events
    assert ev["args"]["error"] == "RuntimeError"


def test_ambient_tracer_helper():
    assert current_tracer() is None
    with span("nothing") as sp:       # no tracer installed: yields None
        assert sp is None
    tr = TraceRecorder()
    prev = set_tracer(tr)
    try:
        assert prev is None
        assert current_tracer() is tr
        with span("real") as sp:
            assert sp is not None
        assert [e["name"] for e in tr.events] == ["real"]
    finally:
        set_tracer(prev)
    assert current_tracer() is None


def test_profiler_recorder_yields_a_span_and_propagates_errors():
    pytest.importorskip("jax")
    rec = ProfilerRecorder()
    with rec.span("phase", args={"rows": 4}) as sp:
        sp.args["more"] = 1            # callers may annotate, as with Chrome
    assert sp.name == "phase" and sp.args == {"rows": 4, "more": 1}
    with pytest.raises(RuntimeError):
        with rec.span("boom"):
            raise RuntimeError("x")


def test_repro_obs_imports_without_jax_and_spans_default_to_null():
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; import repro.obs; from repro.obs import "
         "ProfilerRecorder; print('jax' in sys.modules)"],
        capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH="src"), cwd=REPO)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"
    from repro.obs.trace import _NULL
    assert span("anything") is _NULL
    pytest.importorskip("jax")
    from repro.service import SweepService
    assert SweepService()._phase("pass", lambda: 1 / 0) is _NULL


# ---------------------------------------------------------------------------
# snapshot files
# ---------------------------------------------------------------------------


def test_write_snapshot_atomic_pair(tmp_path):
    reg = _golden_registry()
    snap = write_snapshot(reg, tmp_path / "metrics")
    d = tmp_path / "metrics"
    assert sorted(os.listdir(d)) == ["metrics.json", "metrics.prom"]
    assert (d / "metrics.prom").read_text() == to_prometheus(reg)
    assert json.loads((d / "metrics.json").read_text()) == snap
    kind, loaded = load_any(d)                   # dir resolves to the json
    assert kind == "metrics" and loaded == snap


# ---------------------------------------------------------------------------
# summarize --check: the CI gate
# ---------------------------------------------------------------------------


def test_check_rejects_empty_and_missing_observables():
    assert check_metrics({"series": []}) == ["metrics snapshot has no series"]
    # a service-produced snapshot (any repro_service_*) must carry the live
    # paper observables with >=1 observation each
    reg = MetricsRegistry(clock=lambda: 0.0)
    reg.counter("repro_service_requests").inc(1)
    problems = check_metrics(reg.snapshot())
    assert len(problems) == len(REQUIRED_SERVICE_SERIES)
    for req, p in zip(REQUIRED_SERVICE_SERIES, sorted(problems)):
        assert req in p
    # a non-service snapshot (e.g. bench-only) has no such requirement
    reg2 = MetricsRegistry(clock=lambda: 0.0)
    reg2.counter("repro_bench_calls").inc(1)
    assert check_metrics(reg2.snapshot()) == []


def test_check_rejects_inconsistent_histogram():
    snap = {"series": [{"name": "h", "type": "histogram",
                        "buckets": [1.0], "counts": [1, 0], "count": 3,
                        "sum": 0.5}]}
    (p,) = check_metrics(snap)
    assert "counts sum" in p


def test_check_rejects_non_nesting_spans():
    base = {"cat": "t", "ph": "X", "pid": 1, "tid": 1}
    ok = {"traceEvents": [dict(base, name="outer", ts=0, dur=10),
                          dict(base, name="inner", ts=2, dur=3),
                          dict(base, name="later", ts=20, dur=5)]}
    assert check_trace(ok) == []
    bad = {"traceEvents": [dict(base, name="a", ts=0, dur=10),
                           dict(base, name="b", ts=5, dur=10)]}
    (p,) = check_trace(bad)
    assert "without nesting" in p
    assert check_trace({"traceEvents": []}) \
        == ["trace has no complete ('X') spans"]
    # other lanes are independent: the same overlap on two tids is fine
    two_lanes = {"traceEvents": [dict(base, name="a", ts=0, dur=10),
                                 dict(base, name="b", ts=5, dur=10,
                                      tid=2)]}
    assert check_trace(two_lanes) == []


def test_summarize_cli_roundtrip(tmp_path, capsys):
    mdir = tmp_path / "metrics"
    write_snapshot(_golden_registry(), mdir)
    tpath = tmp_path / "trace.json"
    _golden_tracer().save(tpath)
    assert summarize_main(["summarize", "--check", str(mdir),
                           str(tpath)]) == 0
    out = capsys.readouterr().out
    assert out.count("check ok") == 2
    assert "repro_pass_u" in out and "round" in out
    # an empty snapshot fails the gate
    empty = tmp_path / "empty"
    write_snapshot(MetricsRegistry(clock=lambda: 0.0), empty)
    assert summarize_main(["summarize", "--check", str(empty)]) == 1


# ---------------------------------------------------------------------------
# service integration: off-path bit-identity + live observables
# ---------------------------------------------------------------------------


def _serve_once(telemetry):
    from repro.experiments import WindowSweep
    from repro.service import SweepService
    spec = WindowSweep(deltas=(2.0, 4.0, math.inf), **COMMON)
    svc = SweepService(telemetry=telemetry)
    svc.submit(spec, requester="alice")
    (resp,) = svc.drain()
    assert resp.error is None
    return resp.result


def test_service_telemetry_is_off_path_bit_identical():
    pytest.importorskip("jax")
    tel = Telemetry(tracer=TraceRecorder())
    with_tel = _serve_once(tel)
    without = _serve_once(None)
    # float-equal records, not allclose: telemetry must not perturb results
    assert with_tel.records == without.records

    # live observables materialized: one histogram observation per pass
    snap = tel.registry.snapshot()
    assert check_metrics(snap) == []
    by_name = {}
    for s in snap["series"]:
        by_name.setdefault(s["name"], []).append(s)
    for req in ("repro_pass_u", "repro_pass_w2",
                "repro_pass_window_occupancy"):
        assert sum(s["count"] for s in by_name[req]) >= 1, req
    (served,) = by_name["repro_service_served_rows"]
    assert served["labels"] == {"requester": "alice"}

    # exactly one "pass" span, annotated with its CompatKey + provenance
    passes = [e for e in tel.tracer.events if e["name"] == "pass"]
    assert len(passes) == 1
    args = passes[0]["args"]
    assert args["L"] == 16 and args["n_v"] == 2
    assert args["backend"] == COMMON["backend"]
    assert args["n_rows"] == 3 * COMMON["replicas"]
    assert args["rows_burned"] + args["rows_from_cache"] == args["n_rows"]
    assert args["requesters"] == ["alice"]
    assert check_trace(tel.tracer.to_dict()) == []


def test_service_stats_snapshot_diff():
    pytest.importorskip("jax")
    from repro.service.api import ServiceStats
    a = ServiceStats()
    a.n_requests, a.rows_computed = 3, 100
    snap = a.snapshot()
    a.n_requests, a.rows_computed = 5, 160
    d = a.diff(snap)
    assert (d.n_requests, d.rows_computed) == (2, 60)
    assert d.n_errors == 0
    assert snap.n_requests == 3            # snapshot is an isolated copy


def test_daemon_writes_snapshots_and_trace(tmp_path):
    pytest.importorskip("jax")
    from repro.experiments import WindowSweep
    from repro.service.daemon import DaemonConfig, serve_daemon
    from repro.service.wire import encode_request

    intake = tmp_path / "intake"
    intake.mkdir()
    spec = WindowSweep(deltas=(2.0, 4.0), **COMMON)
    (intake / "a.jsonl").write_text(
        json.dumps(encode_request(spec, "alice")) + "\n")
    cfg = DaemonConfig(intake_dir=str(intake),
                       out_path=str(tmp_path / "responses.jsonl"),
                       poll_interval_s=0.01, idle_exit_rounds=2,
                       metrics_dir=str(tmp_path / "metrics"),
                       trace_path=str(tmp_path / "trace.json"))
    lines = []
    stats = serve_daemon(cfg, log=lines.append)
    assert stats.n_requests == 1 and stats.n_errors == 0

    # per-round delta logging (satellite a): rates, not lifetime totals
    round_lines = [ln for ln in lines if ln.startswith("round ")]
    assert any("+1 request(s)" in ln and "1 pass(es)" in ln
               for ln in round_lines)

    # exposition: snapshot pair + trace on disk, and the CI gate passes
    mdir = tmp_path / "metrics"
    assert sorted(os.listdir(mdir)) == ["metrics.json", "metrics.prom"]
    assert summarize_main(["summarize", "--check", str(mdir),
                           str(tmp_path / "trace.json")]) == 0
    prom = (mdir / "metrics.prom").read_text()
    for name in (*REQUIRED_SERVICE_SERIES, "repro_daemon_rounds",
                 "repro_daemon_phase_seconds", "repro_service_queue_depth",
                 "repro_service_phase_seconds"):
        assert name in prom, name

    trace = json.loads((tmp_path / "trace.json").read_text())
    names = [e["name"] for e in trace["traceEvents"]]
    assert names.count("pass") == stats.n_passes == 1
    rounds = [e for e in trace["traceEvents"] if e["name"] == "round"]
    assert rounds and rounds[0]["args"]["n_passes"] == 1


def test_sweep_emits_phase_spans_under_ambient_tracer():
    pytest.importorskip("jax")
    from repro.experiments import WindowSweep, run_window_sweep
    spec = WindowSweep(deltas=(2.0,), **COMMON)
    baseline = run_window_sweep(spec)           # untraced
    tr = TraceRecorder()
    prev = set_tracer(tr)
    try:
        traced = run_window_sweep(spec)
    finally:
        set_tracer(prev)
    assert traced.records == baseline.records   # tracing is off-path too
    names = [e["name"] for e in tr.events]
    assert names.count("burn") == 1
    assert names.count("measure") == 1
    assert names.count("reduce") == 1
    (burn,) = [e for e in tr.events if e["name"] == "burn"]
    assert burn["args"]["rows"] == spec.n_trajectories
    assert burn["args"]["steps"] == COMMON["burn_in"]
    assert check_trace(tr.to_dict()) == []


# ---------------------------------------------------------------------------
# service phase spans: one route to either sink, on the device's clock
# ---------------------------------------------------------------------------

#: each span the service opens, and the span it opens inside (None: none)
SERVICE_SPANS = {
    "service.schedule": None, "pass": None, "service.flush": None,
    "pass.state": "pass", "pass.measure": "pass",
    "pass.stats.fetch": "pass", "pass.stats.reduce": "pass",
    "pass.state.lookup": "pass.state", "pass.burn": "pass.state",
    "pass.state.fetch": "pass.state", "pass.state.put": "pass.state",
    "pass.state.splice": "pass.state", "pass.state.upload": "pass.state",
}


def _serve_streaming(telemetry=None):
    """Two requests served through a streaming sink, so ``service.flush``
    runs: a fresh one, whose burned state goes straight to the measurement,
    then a follow-up that partly hits the state cache, so the host splice
    and upload run too.  Returns both responses' records."""
    from repro.experiments import WindowSweep
    from repro.service import SweepService
    svc = SweepService(telemetry=telemetry)
    got = []
    svc.on_response = got.append
    for deltas in ((2.0, 4.0, math.inf), (2.0, 8.0)):
        svc.submit(WindowSweep(deltas=deltas, **COMMON), requester="alice")
        svc.drain()
    assert [r.error for r in got] == [None, None]
    assert 0 < svc.stats.rows_from_state_cache < svc.stats.rows_computed
    return [r.result.records for r in got]


def test_profiler_sink_puts_service_spans_on_the_host_plane(tmp_path):
    jax = pytest.importorskip("jax")
    from jax.profiler import ProfileData
    baseline = _serve_streaming()
    prev = set_tracer(ProfilerRecorder())
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            traced = _serve_streaming()
        finally:
            jax.profiler.stop_trace()
    finally:
        set_tracer(prev)
    assert traced == baseline
    (path,) = (tmp_path / "plugins" / "profile").glob("*/*.xplane.pb")
    data = ProfileData.from_serialized_xspace(path.read_bytes())
    spans: dict[str, list] = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                assert not e.name.startswith("pass#"), e.name   # no args
                if e.name in SERVICE_SPANS:
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    assert set(spans) == set(SERVICE_SPANS)
    for name, parent in SERVICE_SPANS.items():
        for s, e in spans[name]:
            if parent is None and name != "pass":
                assert not any(ps <= s and e <= pe
                               for ps, pe in spans["pass"]), name
            elif parent is not None:
                assert any(ps <= s and e <= pe
                           for ps, pe in spans[parent]), (name, parent)


def test_service_responses_are_bit_identical_under_either_sink():
    pytest.importorskip("jax")
    baseline = _serve_streaming()
    chrome = TraceRecorder()
    for sink in (chrome, ProfilerRecorder()):
        prev = set_tracer(sink)
        try:
            traced = _serve_streaming()
        finally:
            set_tracer(prev)
        assert traced == baseline, type(sink).__name__
    assert {e["name"] for e in chrome.events} == set(SERVICE_SPANS)
    assert check_trace(chrome.to_dict()) == []


def test_phase_seconds_are_labelled_by_span_name():
    pytest.importorskip("jax")
    tel = Telemetry(tracer=TraceRecorder())
    _serve_streaming(tel)
    names = [e["name"] for e in tel.tracer.events]
    phases = {s["labels"]["phase"]: s["count"]
              for s in tel.registry.snapshot()["series"]
              if s["name"] == "repro_service_phase_seconds"}
    assert phases == {n: names.count(n) for n in SERVICE_SPANS}


_TRACES_SCRIPT = textwrap.dedent("""
    import json, math
    from repro.experiments import WindowSweep
    from repro.obs import Telemetry
    from repro.service import SweepService

    tel = Telemetry()
    svc = SweepService(telemetry=tel)
    counts = []
    for seed in (1, 2, 3):
        before = svc.stats.n_traces
        svc.submit(WindowSweep(Ls=(16,), n_vs=(2,), deltas=(2.0, math.inf),
                               replicas=2, n_steps=16, burn_in=8,
                               backend="pallas_multistep", k_fuse=8,
                               seed=seed))
        (resp,) = svc.drain()
        assert resp.error is None
        counts.append(svc.stats.n_traces - before)
    (mirror,) = [s["value"] for s in tel.registry.snapshot()["series"]
                 if s["name"] == "repro_service_jaxpr_traces"]
    print(json.dumps({"counts": counts, "mirror": mirror,
                      "total": svc.stats.n_traces}))
""")


def test_n_traces_counts_jit_misses_and_none_on_a_repeated_shape():
    # a fresh interpreter: what earlier tests left in JAX's caches must not
    # decide whether this pass shape has been traced before
    pytest.importorskip("jax")
    out = subprocess.run([sys.executable, "-c", _TRACES_SCRIPT],
                         capture_output=True, text=True,
                         env=dict(os.environ, PYTHONPATH="src"), cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    first, *repeats = res["counts"]
    assert first > 0                 # the first pass traced its programs
    assert repeats == [0, 0]         # the same shapes again: no new trace
    assert res["mirror"] == res["total"] == first


# ---------------------------------------------------------------------------
# sharded mesh: bit-identity holds under telemetry on 8 fake devices
# ---------------------------------------------------------------------------

_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json, math
    from repro.compat import make_mesh
    from repro.experiments import WindowSweep
    from repro.obs import Telemetry, TraceRecorder
    from repro.obs.summarize import check_metrics, check_trace
    from repro.service import SweepService

    def same(xs, ys):
        # float-equal, except the sharded backend's wa is NaN by contract
        # (see test_sharded_sweep) and NaN != NaN under dataclass equality
        def eq(x, y):
            if isinstance(x, float) and math.isnan(x):
                return isinstance(y, float) and math.isnan(y)
            return x == y
        return len(xs) == len(ys) and all(
            all(eq(a, b) for a, b in zip(dataclasses.astuple(x),
                                         dataclasses.astuple(y)))
            for x, y in zip(xs, ys))

    spec = WindowSweep(Ls=(16,), n_vs=(2,), deltas=(1.0, 2.0, 4.0, math.inf),
                       replicas=4, n_steps=16, burn_in=8,
                       backend="sharded", k_fuse=4)

    def serve(telemetry):
        svc = SweepService(mesh=make_mesh((2, 4), ("data", "model")),
                           telemetry=telemetry)
        svc.submit(spec, requester="alice")
        (resp,) = svc.drain()
        assert resp.error is None, resp.error
        return resp.result

    tel = Telemetry(tracer=TraceRecorder())
    with_tel = serve(tel)
    without = serve(None)
    passes = [e for e in tel.tracer.events if e["name"] == "pass"]
    print(json.dumps({
        "bit_identical": same(with_tel.records, without.records),
        "metrics_ok": check_metrics(tel.registry.snapshot()) == [],
        "trace_ok": check_trace(tel.tracer.to_dict()) == [],
        "n_pass_spans": len(passes),
        "pad": passes[0]["args"].get("n_pad", 0) if passes else -1,
    }))
""")


@pytest.mark.distributed
def test_sharded_service_telemetry_bit_identical():
    pytest.importorskip("jax")
    env = dict(os.environ, PYTHONPATH="src")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                         capture_output=True, text=True, env=env, cwd=repo)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["bit_identical"]
    assert res["metrics_ok"] and res["trace_ok"]
    assert res["n_pass_spans"] == 1


# ---------------------------------------------------------------------------
# sharded mesh, 4 fake devices: either sink is off-path; retraces counted
# ---------------------------------------------------------------------------

_SINKS_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses, json, math, tempfile
    import jax
    from repro.compat import make_mesh
    from repro.experiments import WindowSweep
    from repro.obs import ProfilerRecorder, TraceRecorder, set_tracer
    from repro.service import SweepService

    mesh = make_mesh((1, 4), ("data", "model"))

    def serve(seed):
        svc = SweepService(mesh=mesh)
        svc.submit(WindowSweep(Ls=(16,), n_vs=(2,),
                               deltas=(1.0, 2.0, math.inf), replicas=4,
                               n_steps=16, burn_in=8, backend="sharded",
                               k_fuse=4, seed=seed))
        (resp,) = svc.drain()
        assert resp.error is None, resp.error
        # wa is NaN on the sharded backend by contract; NaN != NaN
        recs = [dataclasses.replace(r, wa=0.0) for r in resp.result.records]
        return recs, svc.stats.n_traces

    plain, cold = serve(1)
    _, warm = serve(2)
    out = {"cold_traces": cold, "warm_traces": warm}
    for name, sink in (("chrome", TraceRecorder()),
                       ("profiler", ProfilerRecorder())):
        prev = set_tracer(sink)
        try:
            with tempfile.TemporaryDirectory() as d:
                jax.profiler.start_trace(d)
                try:
                    recs, traces = serve(1)
                finally:
                    jax.profiler.stop_trace()
        finally:
            set_tracer(prev)
        out[name] = recs == plain
        out[name + "_traces"] = traces
    print(json.dumps(out))
""")


@pytest.mark.distributed
def test_sharded_service_under_either_sink_and_its_traces():
    pytest.importorskip("jax")
    env = dict(os.environ, PYTHONPATH="src")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", _SINKS_MESH_SCRIPT],
                         capture_output=True, text=True, env=env, cwd=repo)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["chrome"] and res["profiler"]
    # the sharded runtime wraps a fresh jax.jit around each call, so a pass
    # whose shapes it has run before still traces burn and measurement
    # again: today 220 jaxpr traces per pass on this mesh and spec
    assert res["cold_traces"] > res["warm_traces"]
    assert res["warm_traces"] == 220
    assert res["chrome_traces"] == res["profiler_traces"] == 220
