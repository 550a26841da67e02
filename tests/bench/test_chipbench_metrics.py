"""The chip benchmark's metric arithmetic on known inputs."""
import types

import pytest

from chipbench_tiny import DATA  # noqa: F401  (puts the repo on sys.path)
from benchmarks.chip import harness, trace_reduce, traffic
from benchmarks.chip.metrics_common import FAILED_S

LAYOUT = harness.Layout()
STATS = dict(n_requests=0, n_deduped=0, n_passes=0, n_engine_calls=0,
             n_errors=0, n_retries=0, rows_requested=0, rows_computed=0,
             rows_burned=0, rows_from_state_cache=0, engine_row_steps=0,
             state_cache_hits=0, state_cache_misses=0,
             state_cache_evictions=0)


def _served(due, answered, rows=160, steps=(1024, 512), error=None):
    spec = dict(Ls=(10_000,), n_vs=(10,), deltas=(1.0,) * (rows // 32),
                replicas=32, burn_in=steps[0], n_steps=steps[1])
    resp = types.SimpleNamespace(error=error)
    return harness.Served(traffic.Request(due, "t", spec),
                          submitted=due, answered=answered, response=resp)


def _run(served, window_end, stats=None, trace=None, chips=1, **kw):
    return harness.Run(cell="c", config={"L": 10_000}, mix={}, chips=chips,
                       seconds=10.0, setup_s=kw.get("setup_s", 12.5),
                       served=served, window_end=window_end,
                       stats=dict(STATS, **(stats or {})),
                       compiles=kw.get("compiles", 0), trace=trace)


def read(name, run):
    return LAYOUT.reader(name)(run)


def test_rate_is_work_over_time_to_the_last_answer():
    served = [_served(0.0, 4.0), _served(4.0, 8.0), _served(8.0, 12.5)]
    per = 160 * 10_000 * 1536
    assert read("pe_steps_per_s", _run(served, 12.5)) == pytest.approx(
        3 * per / 12.5)


def test_rate_leaves_out_failed_requests_and_reads_nothing_without_work():
    served = [_served(0.0, 5.0), _served(5.0, 10.0, error={"code": "x"})]
    assert read("pe_steps_per_s", _run(served, 10.0)) == pytest.approx(
        160 * 10_000 * 1536 / 10.0)
    assert read("pe_steps_per_s", _run([], 0.0)) is None


def test_latency_percentiles_over_every_request_due_in_the_window():
    # latencies 1..20 s, answered after the window closed for the late ones
    served = [_served(float(i), float(i) + 1 + i) for i in range(20)]
    run = _run(served, 60.0)
    assert read("request_p50_s", run) == pytest.approx(10.5)
    assert read("request_p95_s", run) == pytest.approx(19.05)


def test_a_failed_or_unanswered_request_lies_beyond_any_limit():
    served = [_served(0.0, 0.5) for _ in range(19)]
    served.append(_served(1.0, None))
    run = _run(served, 2.0)
    assert read("request_p95_s", run) > 1e3
    assert read("request_p50_s", run) == pytest.approx(0.5)
    served[-1] = _served(1.0, 1.5, error={"code": "engine"})
    assert read("request_p95_s", _run(served, 2.0)) > 1e3
    assert FAILED_S > 1e3


def test_service_stats_diff_feeds_scheduler_and_cache_metrics():
    from repro.service.api import ServiceStats
    before = ServiceStats(rows_requested=100, rows_computed=80,
                          state_cache_hits=10, state_cache_misses=30)
    after = ServiceStats(rows_requested=460, rows_computed=360,
                         state_cache_hits=130, state_cache_misses=190)
    run = _run([], 1.0, stats=after.diff(before).as_dict())
    assert read("sched.coalescing_ratio", run) == pytest.approx(360 / 280)
    assert read("cache.state_hit_pct", run) == pytest.approx(
        100 * 120 / 280)
    assert read("sched.coalescing_ratio", _run([], 1.0)) is None
    assert read("cache.state_hit_pct", _run([], 1.0)) is None


def test_setup_and_compiles_are_read_as_recorded():
    run = _run([], 1.0, setup_s=17.25, compiles=2)
    assert read("setup_s", run) == 17.25
    assert read("engine.compiles", run) == 2


def _trace(window_s, ops, host=()):
    conv = {d: ([n for n, _, _ in evs],
                __import__("numpy").array([s for _, s, _ in evs], float),
                __import__("numpy").array([e for _, _, e in evs], float))
            for d, evs in ops.items()}
    return trace_reduce.Trace(window_s=window_s, ops=conv, host=list(host))


def test_idle_share_kernel_time_and_exposed_collectives_from_a_trace():
    tr = _trace(10.0, {
        0: [("pdes_multistep_counter.1", 0.0, 4.0), ("fusion.2", 3.0, 5.0),
            ("all-reduce.3", 6.0, 7.0)],
        1: [("pdes_multistep_counter.1", 0.0, 2.0),
            ("collective-permute-done", 2.0, 4.0), ("fusion.2", 3.0, 6.0)]})
    run = _run([], 10.0, stats={"engine_row_steps": 1000}, trace=tr, chips=2)
    # busy: dev0 [0,5]+[6,7] = 6 s, dev1 [0,6] = 6 s -> idle 40 %
    assert read("device.idle_pct.rate", run) == pytest.approx(40.0)
    assert read("device.idle_pct.latency", run) == pytest.approx(40.0)
    # kernel: 4 + 2 s over 1000 row-steps x 10^4 PEs
    assert read("kernel.ps_per_pe_step", run) == pytest.approx(
        6.0 / 1e7 * 1e12)
    # exposed collective: dev0 1 s, dev1 [2,3] = 1 s -> 10 % of the window
    assert read("mesh.exposed_collective_pct", run) == pytest.approx(10.0)


def test_trace_metrics_read_nothing_without_a_trace_or_their_events():
    run = _run([], 10.0, stats={"engine_row_steps": 1000})
    for name in ("device.idle_pct.rate", "kernel.ps_per_pe_step",
                 "mesh.exposed_collective_pct"):
        assert read(name, run) is None
    tr = _trace(10.0, {0: [("fusion.1", 0.0, 1.0)]})
    run = _run([], 10.0, stats={"engine_row_steps": 1000}, trace=tr)
    # ops ran but none is the kernel's: its name changed, which must show
    with pytest.raises(ValueError, match="pdes_multistep"):
        read("kernel.ps_per_pe_step", run)
    # ops ran but no collective was left exposed: its best value, not nothing
    assert read("mesh.exposed_collective_pct", run) == 0.0
    run = _run([], 10.0, trace=_trace(10.0, {}))
    assert read("mesh.exposed_collective_pct", run) is None
