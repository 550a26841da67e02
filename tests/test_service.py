"""Sweep-service tests: coalesced bit-identity, dedup, cache, scheduling.

The tentpole contract (``repro.service``): a coalesced device pass must
return, for every request, exactly the rows a direct ``run_window_sweep``
of that request's spec would return — float-equal records, not allclose.
The single-device gate runs in-process (three overlapping requests share
one pass); the sharded gate runs in one subprocess with 8 fake CPU devices
(same pattern as tests/test_sharded_sweep.py).  Around the gate: scheduler
units (compat keying, Δ-grid union packing, admission, Eq. (3) fairness),
the burned-state LRU, the wire schema + ``python -m repro.service`` CLI,
and the golden-section Δ* refiner that drives the service adaptively.
"""
import dataclasses
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.experiments import (WindowSweep, refine_optimal_window,
                               optimal_windows, run_window_sweep)
from repro.experiments.sweep import spec_from_dict, spec_to_dict
from repro.service import (BatchScheduler, CompatKey, GridJob, StateCache,
                           SweepService, canonicalize_spec, decode_request,
                           decode_response, encode_request, encode_response,
                           spec_fingerprint, window_admission)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the shared single-device pass shape of the coalescing tests
COMMON = dict(Ls=(16,), n_vs=(2,), replicas=4, n_steps=32, burn_in=16,
              backend="pallas_multistep", k_fuse=8)


def _key(**kw) -> CompatKey:
    base = dict(L=16, n_v=2, backend="reference", window="exact", k_fuse=8,
                rd_mode=False, border_both=False, seed=0, burn=16, n_steps=32)
    base.update(kw)
    return CompatKey(**base)


def _job(requester, seq, rows, key=None) -> GridJob:
    deltas = tuple(dict.fromkeys(d for _, d in rows))
    return GridJob(fp=f"fp-{requester}-{seq}", requester=requester, seq=seq,
                   key=key or _key(), rows=tuple(rows), deltas=deltas,
                   replicas=len(rows) // len(deltas), steady_frac=0.5)


# ---------------------------------------------------------------------------
# Eq. (3) as an admission predicate + compat keying
# ---------------------------------------------------------------------------


def test_window_admission_is_eq3():
    # tau <= delta + gvt, exactly the moving-window rule
    assert window_admission(5.0, 2.0, 4.0) is True
    assert window_admission(6.0, 2.0, 4.0) is True      # boundary included
    assert window_admission(6.1, 2.0, 4.0) is False
    assert window_admission(10, math.inf, 0) is True    # inf disables
    out = window_admission(np.array([1.0, 6.0, 7.0]), 2.0, 4.0)
    assert out.tolist() == [True, True, False]


def test_compat_stream_key_drops_n_steps():
    a, b = _key(n_steps=32), _key(n_steps=64)
    assert a != b                      # cannot share a pass...
    assert a.stream_key == b.stream_key   # ...but share burned-in states


def test_canonicalize_and_fingerprint():
    s1 = WindowSweep(Ls=[16], n_vs=(2,), deltas=[2, 4.0], **{
        k: v for k, v in COMMON.items() if k not in ("Ls", "n_vs")})
    s2 = WindowSweep(Ls=(16,), n_vs=[2], deltas=(2.0, 4.0), **{
        k: v for k, v in COMMON.items() if k not in ("Ls", "n_vs")})
    assert canonicalize_spec(s1) == canonicalize_spec(s2)
    assert spec_fingerprint(s1) == spec_fingerprint(s2)
    s3 = dataclasses.replace(s2, seed=1)
    assert spec_fingerprint(s3) != spec_fingerprint(s2)


def test_request_id_is_deterministic_and_idempotent():
    svc = SweepService()
    spec = WindowSweep(deltas=(2.0, 4.0), **COMMON)
    r1 = svc.submit(spec, requester="alice")
    r2 = svc.submit(spec, requester="alice")   # resubmission: same request
    r3 = svc.submit(spec, requester="bob")
    assert r1.request_id == r2.request_id
    assert r1.request_id != r3.request_id
    assert r1.fingerprint == r3.fingerprint    # same computation though
    assert svc.stats.n_requests == 2           # resubmission not re-counted


# ---------------------------------------------------------------------------
# scheduler: union packing, admission control, fairness
# ---------------------------------------------------------------------------


def test_pack_unions_shared_rows_and_slices_per_job():
    sched = BatchScheduler()
    a = _job("alice", 0, [(0, 2.0), (1, 2.0), (0, 4.0), (1, 4.0)])
    b = _job("bob", 1, [(0, 4.0), (1, 4.0), (0, 8.0), (1, 8.0)])
    sched.enqueue(a)
    sched.enqueue(b)
    (p,) = sched.take(force=True)
    assert sched.n_pending == 0
    # shared (trial, 4.0) rows computed once: 4 + 4 - 2 union rows
    assert p.n_rows == 6
    for job, cols in zip(p.jobs, p.cols):
        assert tuple(p.rows[c] for c in cols) == job.rows


def test_incompatible_keys_never_share_a_pass():
    sched = BatchScheduler()
    sched.enqueue(_job("alice", 0, [(0, 2.0)], key=_key(n_steps=32)))
    sched.enqueue(_job("bob", 1, [(0, 2.0)], key=_key(n_steps=64)))
    passes = sched.take(force=True)
    assert len(passes) == 2
    assert {p.key.n_steps for p in passes} == {32, 64}


def test_max_batch_rows_splits_job_granularly():
    sched = BatchScheduler(max_batch_rows=3)
    sched.enqueue(_job("a", 0, [(0, 1.0), (1, 1.0)]))
    sched.enqueue(_job("b", 1, [(2, 1.0), (3, 1.0)]))
    passes = sched.take(force=True)
    assert [p.n_rows for p in passes] == [2, 2]


def test_max_wait_rounds_holds_then_releases():
    sched = BatchScheduler(max_wait_rounds=2)
    sched.enqueue(_job("a", 0, [(0, 1.0)]))
    assert sched.take() == []          # round 1: held, accumulating
    assert sched.take() == []          # round 2: held
    assert len(sched.take()) == 1      # waited out: released
    sched.enqueue(_job("a", 1, [(0, 1.0)]))
    assert len(sched.take(force=True)) == 1   # force overrides the wait


def test_fairness_window_throttles_served_requesters():
    sched = BatchScheduler(fairness_rows=4)
    sched.enqueue(_job("greedy", 0, [(0, 1.0)]))
    sched.enqueue(_job("starved", 1, [(1, 1.0)]))
    served = {"greedy": 10, "starved": 0}   # gvt = 0, window = 4
    (p,) = sched.take(served)
    assert [j.requester for j in p.jobs] == ["starved"]
    (p,) = sched.take(served, force=True)   # drain serves everyone
    assert [j.requester for j in p.jobs] == ["greedy"]


# ---------------------------------------------------------------------------
# burned-state LRU
# ---------------------------------------------------------------------------


def test_state_cache_lru_and_counters():
    cache = StateCache(max_rows=2)
    tau = np.zeros(4, np.float32)
    cache.put("a", tau, 0.0, 0.0)
    cache.put("b", tau, 1.0, 0.0)
    assert cache.get("a") is not None   # refreshes a
    cache.put("c", tau, 2.0, 0.0)       # evicts b (LRU)
    assert cache.get("b") is None
    assert cache.get("a") is not None and cache.get("c") is not None
    assert cache.misses == 1 and cache.hits == 3


# ---------------------------------------------------------------------------
# the bit-identity gate: coalesced == direct, float-equal
# ---------------------------------------------------------------------------


def test_coalesced_pass_bit_identical_to_direct_runs():
    """Three overlapping requests share one device pass; every response is
    float-equal to a standalone ``run_window_sweep`` of its spec."""
    specs = {
        "alice": WindowSweep(deltas=(2.0, 4.0, math.inf), **COMMON),
        "bob": WindowSweep(deltas=(2.0, 4.0), **COMMON),
        "carol": WindowSweep(deltas=(1.0, 4.0, 8.0), **COMMON),
    }
    svc = SweepService()
    for who, spec in specs.items():
        svc.submit(spec, requester=who)
    responses = svc.drain()
    assert svc.stats.n_passes == 1          # one coalesced pass served all
    assert svc.stats.rows_computed < sum(
        s.n_trajectories for s in specs.values())   # shared rows dedup'd
    for resp in responses:
        direct = run_window_sweep(resp.spec)
        assert resp.result.records == direct.records, resp.requester


def test_dedup_identical_specs_no_recompute():
    spec = WindowSweep(deltas=(2.0, 4.0), **COMMON)
    svc = SweepService()
    svc.submit(spec, requester="alice")
    svc.submit(spec, requester="bob")       # in-flight dedup
    r1, r2 = svc.drain()
    assert not r1.cached and r2.cached
    assert r1.result.records == r2.result.records
    assert svc.stats.n_passes == 1
    assert svc.stats.rows_computed == spec.n_trajectories
    svc.submit(spec, requester="carol")     # post-drain dedup: result cache
    (r3,) = svc.drain()
    assert r3.cached and r3.result.records == r1.result.records
    assert svc.stats.n_passes == 1          # still exactly one pass ever
    assert svc.stats.n_deduped == 2


def test_state_cache_reuse_does_not_perturb_results():
    """A later request sharing the stream prefix pulls burned-in rows from
    the cache; its records stay bit-identical to an uncached direct run."""
    first = WindowSweep(deltas=(2.0, 4.0), **COMMON)
    longer = dataclasses.replace(first, n_steps=64)
    svc = SweepService()
    svc.submit(first, requester="alice")
    svc.drain()
    assert svc.stats.rows_from_state_cache == 0
    svc.submit(longer, requester="alice")
    (resp,) = svc.drain()
    assert svc.stats.rows_from_state_cache == first.n_trajectories
    direct = run_window_sweep(longer)
    assert resp.result.records == direct.records


def test_partial_state_cache_overlap_bit_identical():
    """A pass mixing cached and freshly-burned rows (the splice path in
    ``_burned_state``) still reproduces the direct run exactly."""
    svc = SweepService()
    svc.submit(WindowSweep(deltas=(2.0,), **COMMON), requester="alice")
    svc.drain()
    mixed = WindowSweep(deltas=(2.0, 8.0), **COMMON)   # one Δ cached, one not
    svc.submit(mixed, requester="alice")
    (resp,) = svc.drain()
    assert 0 < svc.stats.rows_from_state_cache < mixed.n_trajectories
    assert resp.result.records == run_window_sweep(mixed).records


# ---------------------------------------------------------------------------
# burned state kept on the device: no row hit the cache
# ---------------------------------------------------------------------------


def _rec_eq(a, b):
    """Float-equal records; the sharded backend's ``wa`` is NaN by contract
    and NaN != NaN under dataclass equality."""
    da, db = dataclasses.asdict(a), dataclasses.asdict(b)
    return all(v == db[k] or (isinstance(v, float) and math.isnan(v)
                              and math.isnan(db[k]))
               for k, v in da.items())


def state_on_device_checks(mesh=None) -> dict:
    """Serve a pass no row of which is cached, then one that partly hits
    the cache; every entry of the returned dict should be True.

    On a mesh the first pass has a pad row (9 rows over an ensemble extent
    of 2), which now starts the measurement burned instead of zeroed."""
    from repro.core import PDESConfig, PDESEngine
    backend = "pallas_multistep" if mesh is None else "sharded"
    common = dict(COMMON, replicas=3, backend=backend)
    fresh = WindowSweep(deltas=(2.0, 4.0, math.inf), **common)
    follow = WindowSweep(deltas=(2.0, 8.0), **common)   # 2.0 rows cached
    svc = SweepService(mesh=mesh)
    svc.submit(fresh, requester="alice")
    (r1,) = svc.drain()
    n = fresh.n_trajectories
    out = {
        "fresh_bit_identical": all(_rec_eq(x, y) for x, y in zip(
            r1.result.records,
            run_window_sweep(fresh, mesh=mesh).records)),
        "fresh_on_device": (svc.stats.rows_state_on_device
                            == svc.stats.rows_computed == n),
    }

    # the cache holds what a direct burn-in of those rows gives
    eng = PDESEngine(PDESConfig(L=16, n_v=2, delta=math.inf),
                     backend=backend, k_fuse=COMMON["k_fuse"], mesh=mesh)
    rows = [(w * fresh.replicas + r, d) for w, d in enumerate(fresh.deltas)
            for r in range(fresh.replicas)]
    pad = 0 if mesh is None else -n % mesh.shape["data"]
    trials = [t for t, _ in rows] + [-1 - i for i in range(pad)]
    deltas = [d for _, d in rows] + [math.inf] * pad
    burned = eng.burn_in(eng.init(n + pad), fresh.seed, fresh.burn_in,
                         deltas=jax.numpy.asarray(deltas, np.float32),
                         trial_base=jax.numpy.asarray(trials, np.int32))
    skey = CompatKey(L=16, n_v=2, backend=backend, window="exact",
                     k_fuse=COMMON["k_fuse"], rd_mode=False,
                     border_both=False, seed=fresh.seed,
                     burn=fresh.burn_in, n_steps=fresh.n_steps).stream_key
    host = [np.asarray(a) for a in
            (burned.tau, burned.offset, burned.offset_comp)]
    out["cache_is_direct_burn"] = len(svc.state_cache) == n and all(
        all(np.array_equal(c, a[i]) for c, a in
            zip(svc.state_cache.get(skey + row), host))
        for i, row in enumerate(rows))

    on_device = svc.stats.rows_state_on_device
    svc.submit(follow, requester="alice")
    (r2,) = svc.drain()
    out["partial_bit_identical"] = all(_rec_eq(x, y) for x, y in zip(
        r2.result.records, run_window_sweep(follow, mesh=mesh).records))
    out["partial_through_host"] = (
        0 < svc.stats.rows_from_state_cache < follow.n_trajectories
        and svc.stats.rows_state_on_device == on_device)
    return out


STATE_ON_DEVICE_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
sys.path.insert(0, "tests")
from repro.compat import make_mesh
from test_service import state_on_device_checks
print(json.dumps(state_on_device_checks(make_mesh((2, 4), ("data", "model")))))
"""


@pytest.mark.parametrize("where", [
    "one_device", pytest.param("sharded", marks=pytest.mark.distributed)])
def test_all_miss_pass_keeps_the_burned_state_on_device(where):
    """A pass no row of which hit the cache measures the burn's output as
    it stands: bit-identical to a direct run, counted in
    ``rows_state_on_device``, with the cache filled behind the measurement
    exactly as a direct burn-in gives; a partial hit keeps the splice."""
    if where == "one_device":
        results = state_on_device_checks()
    else:
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        env.pop("XLA_FLAGS", None)
        out = subprocess.run([sys.executable, "-c", STATE_ON_DEVICE_SCRIPT],
                             capture_output=True, text=True, env=env,
                             cwd=REPO)
        assert out.returncode == 0, out.stderr[-4000:]
        results = json.loads(out.stdout.strip().splitlines()[-1])
    assert results == {k: True for k in results}, results


def test_failed_measurement_still_fills_the_state_cache(monkeypatch):
    """The cache fill runs behind the measurement, and also when the
    measurement raises: the retry finds every burned row in the cache."""
    from repro.core.engine import PDESEngine
    real_run = PDESEngine.run
    calls = []

    def flaky_run(self, *args, **kwargs):
        calls.append(None)
        if len(calls) == 1:
            raise RuntimeError("measurement fault")
        return real_run(self, *args, **kwargs)

    monkeypatch.setattr(PDESEngine, "run", flaky_run)
    spec = WindowSweep(deltas=(2.0, 4.0), **COMMON)
    n = spec.n_trajectories
    svc = SweepService(engine_retries=1, retry_base_s=0.0)
    svc.submit(spec, requester="alice")
    (resp,) = svc.drain()
    assert resp.error is None and svc.stats.n_retries == 1
    assert svc.stats.rows_burned == n            # burned before the fault
    assert svc.stats.state_cache_hits == n       # the retry hit every row
    assert svc.stats.rows_from_state_cache == n
    assert svc.stats.rows_state_on_device == 0   # ...through the host splice
    assert resp.result.records == run_window_sweep(spec).records


# ---------------------------------------------------------------------------
# sharded gate: coalesced mesh pass == direct sharded sweep (subprocess)
# ---------------------------------------------------------------------------

SHARDED_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json, math
import numpy as np
import jax
from repro.compat import make_mesh
from repro.experiments.sweep import WindowSweep, run_window_sweep
from repro.service import SweepService

def rec_eq(a, b):
    da, db = dataclasses.asdict(a), dataclasses.asdict(b)
    return all(v == db[k] or (isinstance(v, float) and math.isnan(v)
                              and math.isnan(db[k]))
               for k, v in da.items())

results = {}
mesh = make_mesh((2, 4), ("data", "model"))
common = dict(Ls=(16,), n_vs=(2,), replicas=3, n_steps=32, burn_in=16,
              backend="sharded")
specs = {"alice": WindowSweep(deltas=(2.0, 4.0, math.inf), **common),
         "bob": WindowSweep(deltas=(4.0, 8.0), **common),
         "carol": WindowSweep(deltas=(2.0, 8.0, math.inf), **common)}
svc = SweepService(mesh=mesh)
for who, spec in specs.items():
    svc.submit(spec, requester=who)
for resp in svc.drain():
    direct = run_window_sweep(resp.spec, mesh=mesh)
    results[resp.requester] = all(
        rec_eq(x, y) for x, y in zip(resp.result.records, direct.records))
results["one_pass"] = svc.stats.n_passes == 1

# ragged union (3 requesters x shared rows) padded to the ens extent, and a
# follow-up with longer n_steps served from the burned-state cache
follow = dataclasses.replace(specs["bob"], n_steps=48)
svc.submit(follow, requester="bob")
(r2,) = svc.drain()
d2 = run_window_sweep(follow, mesh=mesh)
results["cache_follow"] = all(
    rec_eq(x, y) for x, y in zip(r2.result.records, d2.records))
results["cache_hits"] = svc.stats.rows_from_state_cache > 0
print(json.dumps(results))
"""


@pytest.mark.distributed
def test_sharded_coalesced_bit_identity():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT],
                         capture_output=True, text=True, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-4000:]
    results = json.loads(out.stdout.strip().splitlines()[-1])
    assert results == {k: True for k in results}, results


# ---------------------------------------------------------------------------
# wire schema + CLI
# ---------------------------------------------------------------------------


def test_wire_request_round_trip():
    spec = WindowSweep(deltas=(2.0, math.inf), **COMMON)
    obj = json.loads(json.dumps(encode_request(spec, "alice")))
    spec2, who = decode_request(obj)
    assert who == "alice" and spec2 == canonicalize_spec(spec)
    assert spec_to_dict(spec2)["deltas"] == [2.0, "inf"]
    assert spec_from_dict(spec_to_dict(spec2)) == spec2
    with pytest.raises(ValueError, match="schema version"):
        decode_request({**obj, "version": 99})


def test_wire_response_round_trip():
    spec = WindowSweep(deltas=(2.0,), **COMMON)
    svc = SweepService()
    svc.submit(spec, requester="alice")
    (resp,) = svc.drain()
    obj = json.loads(json.dumps(encode_response(resp)))
    back = decode_response(obj)
    assert back.request_id == resp.request_id
    assert back.result.records == resp.result.records
    assert not back.cached


def test_cli_drains_example_queue(tmp_path):
    queue = os.path.join(REPO, "examples", "service_queue.jsonl")
    out_path = tmp_path / "responses.jsonl"
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.service", queue, "--out",
         str(out_path)],
        capture_output=True, text=True, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "1 deduped" in out.stderr and "1 coalesced pass" in out.stderr
    lines = out_path.read_text().strip().splitlines()
    requests = [json.loads(li) for li in
                open(queue).read().strip().splitlines()]
    assert len(lines) == len(requests) == 3
    responses = [decode_response(json.loads(li)) for li in lines]
    # responses come back in queue order with the queue's requester names
    assert [r.requester for r in responses] == [
        r["requester"] for r in requests]
    # alice and carol queued the identical spec: dedup'd, equal records
    assert responses[2].cached and not responses[0].cached
    assert responses[0].result.records == responses[2].result.records


# ---------------------------------------------------------------------------
# adaptive Δ* refinement through the service
# ---------------------------------------------------------------------------


def test_refiner_matches_dense_grid_with_fewer_engine_steps():
    common = dict(Ls=(32,), n_vs=(2,), replicas=6, n_steps=32, burn_in=32,
                  backend="pallas_multistep", k_fuse=8)
    coarse = WindowSweep(deltas=(0.5, 1.0, 2.0, 4.0, 8.0), **common)
    svc = SweepService()
    ref = refine_optimal_window(coarse, rounds=3, service=svc)
    assert ref.interior                      # the paper's claim: Δ* interior
    assert ref.bracket[0] <= ref.delta_star <= ref.bracket[1]
    # the polish round re-measured the winner off cached burned-in rows
    assert svc.stats.rows_from_state_cache > 0

    dense_deltas = tuple(float(x) for x in
                         np.round(np.linspace(0.5, 8.0, 12), 4))
    svc2 = SweepService()
    svc2.submit(WindowSweep(deltas=dense_deltas, **common), "grid")
    opt = optimal_windows(svc2.drain()[0].result)[0]
    spacing = dense_deltas[1] - dense_deltas[0]
    assert abs(ref.delta_star - opt.delta_star) <= 1.5 * spacing
    assert svc.stats.engine_row_steps < svc2.stats.engine_row_steps


def test_refiner_coalesces_probes_and_handles_boundary():
    common = dict(Ls=(16,), n_vs=(2,), replicas=4, n_steps=32, burn_in=16,
                  backend="pallas_multistep", k_fuse=8)
    svc = SweepService()
    ref = refine_optimal_window(WindowSweep(deltas=(1.0, 2.0, 4.0), **common),
                                rounds=2, service=svc)
    # the coarse round coalesced its three single-Δ probes into one pass
    assert svc.stats.n_passes < svc.stats.n_requests
    assert all(math.isfinite(e) for _, e in ref.evaluations)
    if not ref.interior:
        # boundary argmax: no golden-section rounds, coarse winner polished
        assert ref.rounds == 0
        assert ref.delta_star in (1.0, 4.0)
    else:
        assert len(ref.evaluations) >= 3 + 2
