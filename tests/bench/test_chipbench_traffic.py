"""The chip benchmark's traffic generator: seeded, fixed work, bounded shapes."""
import collections
import json
import math

import numpy as np
import pytest

from chipbench_tiny import TENANTS_MIX, _read
from benchmarks.chip import traffic

CONFIG = _read("configs", "dstudy_L10k_nv10")
TENANTS = TENANTS_MIX
STUDY = _read("traffic", "study")
BIG_SEED = 2**31 + 987654321


def _key(r):
    return (round(r.due, 12), r.requester, r.kind,
            tuple(sorted((k, str(v)) for k, v in r.spec.items())))


@pytest.mark.parametrize("seed", [0, 17, BIG_SEED])
def test_open_schedule_same_seed_same_schedule(seed):
    a = traffic.open_schedule(TENANTS, CONFIG, seed, 30.0)
    b = traffic.open_schedule(TENANTS, CONFIG, seed, 30.0)
    assert [_key(r) for r in a] == [_key(r) for r in b]
    assert all(0.0 <= r.due < 30.0 for r in a)
    assert [r.due for r in a] == sorted(r.due for r in a)


def test_open_schedule_seeds_share_their_work():
    """Another seed reorders the same sessions, sizes and gaps."""
    def work(seed):
        reqs = traffic.sessions(TENANTS, CONFIG, seed, 120)
        last = max(r.due for r in reqs if r.kind == "study")
        return collections.Counter((r.kind, len(r.spec["deltas"]),
                                    r.spec["n_steps"]) for r in reqs), last
    (a, a_last), (b, b_last) = work(1), work(BIG_SEED)
    assert a == b and a_last == pytest.approx(b_last, rel=1e-12)
    x = traffic.sessions(TENANTS, CONFIG, 1, 120)
    y = traffic.sessions(TENANTS, CONFIG, 2, 120)
    assert [_key(r) for r in x] != [_key(r) for r in y]


def test_tenants_pass_widths_and_steps_are_bounded():
    reqs = traffic.open_schedule(TENANTS, CONFIG, 5, 60.0)
    rows = {len(r.spec["deltas"]) * r.spec["replicas"] for r in reqs}
    assert rows <= {32, 64, 96, 128, 160}
    assert {r.spec["n_steps"] for r in reqs} <= {512, 1024}
    assert {r.spec["burn_in"] for r in reqs} == {1024}
    kinds = collections.Counter(r.kind for r in reqs)
    assert set(kinds) == {"study", "prefix", "duplicate", "longer"}
    sessions = kinds["study"]
    assert sessions == round(TENANTS["session_rate_per_s"] * 60.0)


def test_followups_keep_their_stream_and_come_from_another_tenant():
    reqs = traffic.sessions(TENANTS, CONFIG, 9, 200)
    first = {r.spec["seed"]: r for r in reqs if r.kind == "study"}
    assert len(first) == sum(r.kind == "study" for r in reqs)
    for r in reqs:
        if r.kind == "study":
            continue
        base = first[r.spec["seed"]]
        assert r.requester != base.requester
        assert base.due <= r.due
        assert r.spec["deltas"] == base.spec["deltas"][:len(r.spec["deltas"])]


def test_closed_loop_same_seed_same_requests():
    def take(seed):
        src = traffic.Source(STUDY, CONFIG, seed, 10.0)
        out = []
        for t in (0.0, 1.0, 2.0):
            (r,) = src.take_due(t)
            out.append(r.spec["seed"])
            src.answered(r, t + 1.0)
        return out
    a, b = take(BIG_SEED), take(BIG_SEED)
    assert a == b and len(set(a)) == 3
    assert all(1 <= s < 2**31 for s in a)


def test_closed_loop_stops_issuing_after_the_window():
    src = traffic.Source(STUDY, CONFIG, 3, 2.0)
    (r,) = src.take_due(0.0)
    src.answered(r, 2.5)
    assert src.take_due(10.0) == [] and src.next_due() is None


#: (config, mix, the request ``spec_for`` builds for stream seed 12345,
#: and its wire line) as the two cells send them
GOLDEN = [
    ("dstudy_L10k_nv10", "study",
     {"Ls": (10000,), "n_vs": (10,),
      "deltas": (1.0, 5.0, 10.0, 100.0, math.inf),
      "replicas": 256, "n_steps": 1024, "burn_in": 1024,
      "backend": "pallas_multistep", "window": "exact", "k_fuse": 16,
      "steady_frac": 0.5, "seed": 12345},
     '{"version": 2, "requester": "client0", "spec": {"Ls": [10000], '
     '"n_vs": [10], "deltas": [1.0, 5.0, 10.0, 100.0, "inf"], '
     '"replicas": 256, "n_steps": 1024, "burn_in": 1024, '
     '"backend": "pallas_multistep", "window": "exact", "k_fuse": 16, '
     '"rd_mode": false, "border_both": false, "steady_frac": 0.5, '
     '"seed": 12345}}'),
    ("ring4_L262k_nv10", "ring_study",
     {"Ls": (262144,), "n_vs": (10,),
      "deltas": (1.0, 5.0, 10.0, 100.0, math.inf),
      "replicas": 32, "n_steps": 512, "burn_in": 512, "backend": "sharded",
      "window": "exact", "k_fuse": 16, "steady_frac": 0.5, "seed": 12345},
     '{"version": 2, "requester": "client0", "spec": {"Ls": [262144], '
     '"n_vs": [10], "deltas": [1.0, 5.0, 10.0, 100.0, "inf"], '
     '"replicas": 32, "n_steps": 512, "burn_in": 512, '
     '"backend": "sharded", "window": "exact", "k_fuse": 16, '
     '"rd_mode": false, "border_both": false, "steady_frac": 0.5, '
     '"seed": 12345}}'),
]


@pytest.mark.parametrize("config, mix, spec, line", GOLDEN,
                         ids=["dstudy", "ring4"])
def test_spec_for_sends_what_the_cells_always_sent(config, mix, spec, line):
    from repro.experiments.sweep import WindowSweep
    from repro.service import wire
    got = traffic.spec_for(_read("configs", config),
                           _read("traffic", mix)["request"], 12345)
    assert got == spec and list(got) == list(spec)
    assert json.dumps(wire.encode_request(WindowSweep(**got),
                                          "client0")) == line


def test_sweep_fields_go_last_into_every_request_warmup_too():
    config = dict(CONFIG, sweep_fields={"rd_mode": True})
    specs = [r.spec for b in traffic.warmup_batches(TENANTS, config)
             for r in b]
    specs += [r.spec for r in traffic.sessions(TENANTS, config, 3, 40)]
    specs += [r.spec for r in traffic.Source(STUDY, config, 3, 1.0)
              .take_due(0.0)]
    assert {r.kind for r in traffic.sessions(TENANTS, config, 3, 40)} == {
        "study", "prefix", "duplicate", "longer"}
    for spec in specs:
        assert list(spec)[-1] == "rd_mode" and spec["rd_mode"] is True
        plain = dict(spec)
        del plain["rd_mode"]
        assert list(plain) == list(traffic.spec_for(CONFIG, STUDY["request"],
                                                    0))


def test_a_sweep_field_that_the_harness_builds_is_refused():
    config = dict(CONFIG, sweep_fields={"k_fuse": 32, "rd_mode": True})
    with pytest.raises(traffic.BenchError, match=r"\['k_fuse'\]"):
        traffic.spec_for(config, STUDY["request"], 0)


def test_a_sweep_field_the_program_lacks_is_left_to_window_sweep():
    from repro.experiments.sweep import WindowSweep
    config = dict(CONFIG, sweep_fields={"coupling_J": 1.0})
    spec = traffic.spec_for(config, STUDY["request"], 0)
    assert spec["coupling_J"] == 1.0
    with pytest.raises(TypeError, match="coupling_J"):
        WindowSweep(**spec)


def test_pe_steps_counts_rows_ring_and_steps():
    spec = traffic.spec_for(CONFIG, STUDY["request"], 1)
    assert traffic.pe_steps(spec) == 5 * 256 * 10_000 * 2048


def test_warmup_covers_every_pass_shape_of_the_mix():
    shapes = set()
    for batch in traffic.warmup_batches(TENANTS, CONFIG):
        for r in batch:
            shapes.add((len(r.spec["deltas"]) * r.spec["replicas"],
                        r.spec["n_steps"]))
            assert r.spec["seed"] < traffic.SEED_RANGE[0]
    want = {(32 * b, 512) for b in range(1, 6)} | {(160, 1024)}
    assert shapes == want


def test_fixed_uniform_is_a_permutation_of_mid_quantiles():
    rng = np.random.default_rng(0)
    x = traffic._fixed_uniform(rng, 4, 0.0, 2.0)
    assert sorted(x) == [0.25, 0.75, 1.25, 1.75]
