"""What decides ``correct``: sound runs pass, the control and faults fail.

At the tiny size of ``chipbench_tiny``: a sound run reads a gap far under
the limit; the bfloat16 control, a burn-in that returns its state
unchanged, half of each Δ's replicas left out of the mean, and an answer
altered where the service produces it each read ``correct: false``.
"""
import numpy as np
import pytest

from chipbench_tiny import run_tiny, tiny_layout
from benchmarks.chip import compare, control, reference

SEEDS = (3, 2**31 + 77, 4_000_000_001)


@pytest.fixture
def layout(tmp_path):
    return tiny_layout(tmp_path, limit=1e-3)


def test_sound_run_is_correct_far_under_the_limit(layout):
    out = run_tiny(layout, "t.study")
    gap = out["checks"]["records_rel_gap"]
    assert out["correct"] is True
    assert gap["value"] < gap["limit"] / 10


@pytest.mark.parametrize("seed", SEEDS)
def test_bfloat16_control_fails_the_limit(layout, seed):
    gap = control.control_gap("t.study", seed, layout)
    assert gap > 3 * layout.read("workloads", "t.study")["check"]["limit"]


def test_reference_is_seeded_and_padding_free():
    spec = dict(L=32, n_v=10, deltas=[1.0, float("inf")], replicas=3,
                burn_in=16, n_steps=32, k_fuse=16, seed=9, steady_frac=0.5)
    a = reference.sweep_records(spec)
    assert reference.sweep_records(spec) == a
    assert reference.sweep_records(spec, pad_rows=10) == a
    assert reference.sweep_records(dict(spec, seed=10)) != a


def test_records_gap_refuses_missing_or_misplaced_records():
    rec = {"delta": 1.0, "u": 0.5}
    assert compare.records_gap([rec], [rec], ["u"]) == 0.0
    assert compare.records_gap([], [rec], ["u"]) == np.inf
    assert compare.records_gap([dict(rec, delta=2.0)], [rec], ["u"]) == np.inf
    assert compare.records_gap([dict(rec, u=float("nan"))], [rec],
                               ["u"]) == np.inf
    assert compare.records_gap([dict(rec, u=0.55)], [rec], ["u"]) == \
        pytest.approx(0.1)


def _frozen_burn(monkeypatch):
    from repro.core import engine
    run_single = engine._run_single

    def frozen(state, seed, cfg, ecfg, n_steps, mode, *a, **kw):
        out, stats = run_single(state, seed, cfg, ecfg, n_steps, mode,
                                *a, **kw)
        return (state, stats) if mode == "burn" else (out, stats)
    monkeypatch.setattr(engine, "_run_single", frozen)


def _half_batch(monkeypatch):
    from repro.core import measurement
    reduce = measurement.sweep_reduce

    def half(stats, n_windows, replicas, **kw):
        h = replicas // 2
        idx = np.concatenate([np.arange(w * replicas, w * replicas + h)
                              for w in range(n_windows)])
        kept = type(stats)(*(np.asarray(a)[:, idx] for a in stats))
        return reduce(kept, n_windows, h, **kw)
    monkeypatch.setattr(measurement, "sweep_reduce", half)


def _altered_answer(monkeypatch):
    from repro.service.api import SweepService
    finish = SweepService._finish_job

    def altered(self, job, red):
        red = dict(red, rate=np.asarray(red["rate"]) * 1.01)
        return finish(self, job, red)
    monkeypatch.setattr(SweepService, "_finish_job", altered)


@pytest.mark.parametrize("fault", [_frozen_burn, _half_batch,
                                   _altered_answer])
def test_a_broken_timed_path_reads_incorrect(layout, monkeypatch, fault):
    fault(monkeypatch)
    out = run_tiny(layout, "t.study")
    gap = out["checks"]["records_rel_gap"]
    assert out["failed"] == 0
    assert gap["value"] > gap["limit"]
    assert out["correct"] is False
