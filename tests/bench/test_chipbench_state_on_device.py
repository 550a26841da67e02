"""``service.state_on_device_pct``: the share of the measured rows whose
burned state reached the measurement without a host round trip."""
import pytest

from chipbench_tiny import run_tiny, tiny_layout
from benchmarks.chip import harness

METRIC = "service.state_on_device_pct"


def _run(stats):
    return harness.Run(cell="c", config={}, mix={}, chips=1, seconds=1.0,
                       setup_s=0.0, served=[], window_end=1.0, stats=stats,
                       compiles=0)


def test_the_reader_divides_the_counter_by_the_rows_measured():
    read = harness.Layout().reader(METRIC)
    assert read(_run({"rows_computed": 2560,
                      "rows_state_on_device": 1280})) == pytest.approx(50.0)
    # a program that does not count such rows, or a window that measured
    # no rows, reads nothing
    assert read(_run({"rows_computed": 2560})) is None
    assert read(_run({"rows_computed": 0, "rows_state_on_device": 0})) is None


def test_fresh_study_requests_keep_every_row_on_the_device(tmp_path):
    out = run_tiny(tiny_layout(tmp_path), "t.study", seconds=1.5,
                   trace=True)
    assert out["correct"] is True
    m = out["metrics"][METRIC]
    assert m["unit"] == "%" and m["value"] == pytest.approx(100.0)
