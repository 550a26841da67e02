"""A configuration's own request fields and its own plain reference.

A tiny config brings ``sweep_fields {"rd_mode": true}`` and names the
reference module ``rd_reference``, written beside the tiny data files: the
field reaches the program through the wire, the run is checked against that
module, and a config whose reference does not declare what it asks for is
refused before anything is served.
"""
import json
import math

import pytest

from chipbench_tiny import add_cell, rd_reference_source, run_tiny, \
    tiny_layout
from benchmarks.chip import compare, control, harness, traffic

#: a reference that computes ``u`` 1 % too high
PLANTED = """
from benchmarks.chip.reference import *  # noqa: F401,F403
from benchmarks.chip import reference as _plain


def sweep_records(spec, **kw):
    return [dict(r, u=r["u"] * 1.01) for r in _plain.sweep_records(spec, **kw)]
"""


def _config(layout, name: str, **keys) -> dict:
    cfg = layout.read("configs", "tiny")
    cfg.update(name=name, **keys)
    return cfg


@pytest.fixture
def layout(tmp_path):
    layout = tiny_layout(tmp_path)
    add_cell(layout, "t.rd", _config(layout, "tiny_rd",
                                     sweep_fields={"rd_mode": True},
                                     reference="rd_reference"),
             modules={"rd_reference": rd_reference_source()})
    return layout


def _spy_submit(monkeypatch) -> list:
    from repro.service.api import SweepService
    seen, submit = [], SweepService.submit

    def spy(self, spec, requester="anon"):
        seen.append(spec)
        return submit(self, spec, requester)
    monkeypatch.setattr(SweepService, "submit", spy)
    return seen


def test_sweep_fields_reach_the_program_and_its_reference_checks_them(
        layout, monkeypatch):
    seen = _spy_submit(monkeypatch)
    out = run_tiny(layout, "t.rd")
    assert out["correct"] is True and out["failed"] == 0
    assert seen and all(s.rd_mode and not s.border_both for s in seen)
    # the field changes the physics: the plain reference reads another u
    config = layout.read("configs", "tiny_rd")
    spec = traffic.spec_for(config, {"replicas": 2, "burn_in": 8,
                                     "n_steps": 8}, 5)
    rd = compare.reference_records(layout.reference(config), config, spec, 6)
    plain = compare.reference_records(
        layout.reference(layout.read("configs", "tiny")), config, spec, 6)
    assert compare.records_gap(rd, plain, ["u"]) > 0.1
    assert [r["u"] for r in rd][-1] == 1.0     # Δ = ∞: every PE moves


@pytest.mark.parametrize("change, match", [
    ({"reference": "reference"}, "does not implement sweep_fields key "
     "'rd_mode' of config 'tiny_rd'"),
    ({"reference": "no_such_reference"}, "no reference module named "
     "'no_such_reference'"),
    ({"reference": "../reference"}, "no reference module named"),
    ({"window": "stale"}, r"implements window \('exact',\), not window "
     "'stale'"),
    ({"sweep_fields": {"rd_mode": True, "seed": 1}},
     r"sweep_fields \['seed'\] of config 'tiny_rd' would set fields"),
], ids=["undeclared_field", "unknown_reference", "path_as_name",
        "undeclared_window", "clashing_field"])
def test_a_config_its_reference_cannot_check_is_refused_before_serving(
        layout, monkeypatch, change, match):
    config = layout.read("configs", "tiny_rd")
    config.update(change)
    (layout.data / "configs" / "tiny_rd.json").write_text(json.dumps(config))
    if "sweep_fields" in change:    # a reference that declares the field
        src = rd_reference_source().replace(
            '("rd_mode",)', '("rd_mode", "seed")')
        (layout.data / "rd_reference.py").write_text(src)

    def no_service(config):
        raise AssertionError("a service was built for a refused config")
    monkeypatch.setattr(harness, "make_service", no_service)
    with pytest.raises(harness.BenchError, match=match):
        run_tiny(layout, "t.rd")


def test_a_planted_error_in_the_named_reference_reads_incorrect(layout):
    add_cell(layout, "t.planted", _config(layout, "tiny_planted",
                                          reference="planted"),
             modules={"planted": PLANTED})
    out = run_tiny(layout, "t.planted")
    gap = out["checks"]["records_rel_gap"]
    assert out["correct"] is False and out["failed"] == 0
    assert gap["value"] == pytest.approx(0.01 / 1.01, rel=1e-3)


def test_bfloat16_control_through_a_named_reference_fails_the_limit(layout):
    gap = control.control_gap("t.rd", 2**31 + 404, layout)
    assert gap > 3 * layout.read("workloads", "t.rd")["check"]["limit"]


@pytest.mark.parametrize("got, want, gap", [
    ([0.5, 2.0, 4.0], [0.5, 2.0, 4.0], 0.0),
    ([0.5, 2.2, 4.0], [0.5, 2.0, 4.0], 0.1),
    ([], [], 0.0),
    ([0.5, 2.0], [0.5, 2.0, 4.0], math.inf),
    ([0.5], 0.5, math.inf),
    (0.5, [0.5], math.inf),
    ([0.5, float("nan")], [0.5, 2.0], math.inf),
], ids=["equal", "one_element_off", "empty", "length_mismatch",
        "list_for_scalar", "scalar_for_list", "nan_element"])
def test_list_fields_compare_element_by_element(got, want, gap):
    rec = {"delta": 1.0}
    assert compare.records_gap([dict(rec, m=got)], [dict(rec, m=want)],
                               ["m"]) == pytest.approx(gap)
