"""Device idle time split by the program's own spans (``idle_split.py``).

The synthetic trace nests the sweep service's spans inside the harness's
``service.step`` on a host plane beside one TPU plane, with the times
below (microseconds from the window's start).  The device is idle in
[1, 5], [6, 9] and [14, 20]: 13 of 20 us.
"""
import math

import numpy as np
import pytest

from chipbench_tiny import run_tiny, tiny_layout
from benchmarks.chip import harness, idle_split, trace_reduce
from benchmarks.chip.metrics_common import idle_pct

OPS = [("fusion.1", 0, 1), ("burn.2", 5, 6), ("measure.3", 9, 14)]
HOST = [("window", 0, 20), ("service.step", 1, 19), ("flush_ready", 19, 20),
        ("service.schedule", 1, 2), ("pass", 2, 18), ("pass.state", 2, 8),
        ("pass.burn", 2, 4), ("pass.state.fetch", 4, 7),
        ("pass.measure", 8, 10), ("pass.stats.fetch", 10, 15),
        ("pass.stats.reduce", 15, 17), ("service.flush", 18, 19)]
#: innermost label of each idle stretch, and its microseconds
WANT = {"pass.burn": 2, "pass.state.fetch": 2, "pass.state": 1,
        "pass.measure": 1, "pass.stats.fetch": 1, "pass.stats.reduce": 2,
        "service.flush": 1, "service.schedule": 1, "pass": 1,
        "flush_ready": 1}


def _line(name, events, names):
    evs = " ".join(
        f"events {{ metadata_id: {names.index(n) + 1} "
        f"offset_ps: {s * 1_000_000} duration_ps: {(e - s) * 1_000_000} }}"
        for n, s, e in events)
    meta = " ".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                    f'name: "{n}" }} }}' for i, n in enumerate(names))
    return (f'lines {{ id: 1 name: "{name}" timestamp_ns: 1000 {evs} }} '
            f'{meta}')


def _xspace():
    ops = sorted({n for n, _, _ in OPS})
    host = sorted({n for n, _, _ in HOST})
    return (f'planes {{ id: 1 name: "/device:TPU:0" '
            f'{_line("XLA Ops", OPS, ops)} }} '
            f'planes {{ id: 2 name: "/host:CPU" '
            f'{_line("python", HOST, host)} }}')


@pytest.fixture
def traced(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(_xspace()))
    return str(path)


def test_program_spans_are_kept_beside_the_harness_spans(traced):
    tr = trace_reduce.load(traced)
    # the harness's reduction is unchanged: its own spans only
    assert [n for n, _, _ in tr.host] == ["service.step", "flush_ready"]
    spans = idle_split.program_spans(traced)
    assert sorted(n for n, _, _ in spans) == sorted(
        n for n, _, _ in HOST if n in idle_split.PROGRAM_SPANS)
    (burn,) = [s for s in spans if s[0] == "pass.burn"]
    assert burn[1:] == (pytest.approx(2e-6), pytest.approx(4e-6))


def test_idle_goes_to_the_innermost_span_and_the_shares_add_up(traced):
    tr = trace_reduce.load(traced)
    spans = idle_split.program_spans(traced)
    split = idle_split.idle_by_span(tr, spans, [0])
    assert {k: v * 1e6 for k, v in split} == {
        k: pytest.approx(v) for k, v in WANT.items()}
    out = idle_split.idle_shares(tr, spans, [0], answered=1)
    pct = out["shares"]
    assert pct["engine.idle_pct.dispatch"] == pytest.approx(15.0)
    assert pct["service.idle_pct.state"] == pytest.approx(15.0)
    assert pct["service.idle_pct.stats"] == pytest.approx(20.0)
    assert pct["other"] == pytest.approx(15.0)
    run = harness.Run(cell="c", config={}, mix={}, chips=1, seconds=1.0,
                      setup_s=0.0, served=[], window_end=1.0, stats={},
                      compiles=0, trace=tr)
    assert idle_pct(run) == pytest.approx(65.0)
    assert sum(pct.values()) == pytest.approx(idle_pct(run))
    # idle under bare "pass" or a harness span is not under a named phase
    assert out["named_pct_of_idle"] == pytest.approx(100 * 11 / 13)


def test_a_window_with_answers_but_no_program_span_is_an_error(traced):
    tr = trace_reduce.load(traced)
    with pytest.raises(ValueError, match="none of the program's spans"):
        idle_split.idle_shares(tr, [], [0], answered=3)
    # no answers, no spans: everything idle is the harness's or nobody's
    out = idle_split.idle_shares(tr, [], [0], answered=0)
    assert out["shares"]["other"] == pytest.approx(65.0)
    assert dict(out["idle_by_span"]) == {
        "service.step": pytest.approx(12e-6),
        "flush_ready": pytest.approx(1e-6)}


def test_the_catalogue_is_what_the_service_emits():
    from repro.experiments import WindowSweep
    from repro.obs import TraceRecorder, set_tracer
    from repro.service import SweepService
    svc = SweepService()
    got = []
    svc.on_response = got.append
    tr = TraceRecorder()
    prev = set_tracer(tr)
    try:
        svc.submit(WindowSweep(Ls=(16,), n_vs=(2,), deltas=(2.0, math.inf),
                               replicas=2, n_steps=16, burn_in=8,
                               backend="pallas_multistep", k_fuse=8))
        svc.drain()
    finally:
        set_tracer(prev)
    assert len(got) == 1 and got[0].error is None
    assert {e["name"] for e in tr.events} == set(idle_split.PROGRAM_SPANS)


def _served(ok):
    return harness.Served(None, submitted=0.0, answered=1.0 if ok else None,
                          response=type("R", (), {"error": None})())


def test_traces_per_request_reads_the_counter_over_answered_requests():
    read = harness.Layout().reader("engine.traces_per_request")

    def run(stats, served):
        return harness.Run(cell="c", config={}, mix={}, chips=1,
                           seconds=1.0, setup_s=0.0, served=served,
                           window_end=1.0, stats=stats, compiles=0)

    served = [_served(True), _served(True), _served(True), _served(False)]
    assert read(run({"n_traces": 6}, served)) == pytest.approx(2.0)
    assert read(run({"n_traces": 0}, served)) == 0.0
    # a program that counts no traces, or a window that answered nothing
    assert read(run({}, served)) is None
    assert read(run({"n_traces": 4}, [_served(False)])) is None


def test_the_harness_reads_traces_per_request_in_a_traced_run(tmp_path):
    out = run_tiny(tiny_layout(tmp_path), "t.study", seconds=1.5,
                   trace=True)
    assert out["correct"] is True
    # how many traces a warmed-up pass makes is pinned in tests/test_obs.py,
    # in a fresh interpreter; here the metric reaches the result line
    m = out["metrics"]["engine.traces_per_request"]
    assert m["unit"] == "traces/request" and m["value"] >= 0.0


def test_idle_split_run_records_the_program_spans_on_the_cpu(tmp_path):
    layout = tiny_layout(tmp_path)
    out = idle_split.run("t.study", 2**31 + 99, 1.5, True, t_start=0.0,
                         layout=layout, require_tpu=False)
    assert out["n_program_spans"] > 0 and out["answered"] >= 1
    assert out["metrics"]["engine.traces_per_request"] == pytest.approx(
        out["stats"]["n_traces"] / out["answered"])
    assert out["metrics"]["pe_steps_per_s"] > 0
    plain = idle_split.run("t.study", 2**31 + 99, 1.0, False, t_start=0.0,
                           layout=layout, require_tpu=False)
    assert plain["n_program_spans"] == 0
    assert "device.idle_pct.rate" not in plain["metrics"]
    assert np.isfinite(plain["metrics"]["pe_steps_per_s"])
