"""The chip benchmark's harness: refusals, data-driven cells, contract shape."""
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

from chipbench_tiny import ROOT, rd_reference_source, run_tiny, tiny_layout
from benchmarks.chip import harness

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "dstudy.study", "--seed", str(2**31 + 5), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=_cpu_env(), capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr and "cpu" in p.stderr


def test_check_platform_names_the_device_it_refuses():
    with pytest.raises(harness.BenchError, match=r"no TPU.*'cpu'.*device"):
        harness.check_platform(1)


def test_a_checkout_with_only_the_benchmark_fails(tmp_path):
    for rel in BENCH["paths"]:
        shutil.copytree(ROOT / rel, tmp_path / rel,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", "dstudy.study",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env=_cpu_env(), capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_peaks_refuse_an_unknown_device_kind():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError, match="TPU v9 imaginary"):
        harness.peaks("TPU v9 imaginary")


def test_unknown_cell_config_mix_or_metric_is_refused(tmp_path):
    layout = tiny_layout(tmp_path)
    with pytest.raises(harness.BenchError, match="no workload"):
        layout.read("workloads", "nope")
    with pytest.raises(harness.BenchError, match="no reader"):
        layout.reader("nope_metric")


def test_cells_match_benchmark_json_and_every_metric_has_a_reader():
    layout = harness.Layout()
    for w in BENCH["workloads"]:
        cell = layout.read("workloads", w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        cfg = layout.read("configs", w["config"])
        assert cfg["name"] == w["config"]
        layout.read("traffic", w["traffic"])
        assert 0 < cell["check"]["limit"] < 1
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert set(c["reduced"]) <= set(
            json.loads((ROOT / c["file"]).read_text())["reduced"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(layout.reader(m["name"]))


def test_benchmark_json_keeps_the_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        reported = [m for m in BENCH["end_to_end"]
                    if w["name"] in m.get("workloads", cells)]
        assert len(reported) >= 2
        assert any(w["name"] in m["workloads"] for m in BENCH["per_layer"])
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)


def test_a_cell_added_as_data_files_alone_runs(tmp_path):
    """A new config, mix and cell, plus their BENCHMARK.json entry: no code.

    A second config also brings request fields (``sweep_fields``) and names
    its own plain reference, one more ``.py`` file beside the data files.
    """
    layout = tiny_layout(tmp_path)
    cfg = json.loads((tmp_path / "configs" / "tiny.json").read_text())
    cfg.update(L=48, n_v=3, deltas=[2, "inf"])
    own = dict(cfg, sweep_fields={"rd_mode": True}, reference="rd_ref")
    (tmp_path / "configs" / "added.json").write_text(json.dumps(cfg))
    (tmp_path / "configs" / "added_own.json").write_text(json.dumps(own))
    (tmp_path / "rd_ref.py").write_text(rd_reference_source())
    (tmp_path / "traffic" / "added_mix.json").write_text(json.dumps(
        {"loop": "closed", "clients": 2,
         "request": {"replicas": 3, "burn_in": 16, "n_steps": 48}}))
    bench = json.loads(layout.benchmark.read_text())
    for cell, config in (("added.cell", "added"), ("added.own", "added_own")):
        (tmp_path / "workloads" / f"{cell}.json").write_text(json.dumps(
            {"config": config, "traffic": "added_mix", "chips": 1,
             "check": {"requests": 2, "limit": 1e-3}}))
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": "added_mix", "chips": 1,
                                   "why": "added from data files"})
        for m in bench["end_to_end"]:
            if m["name"] == "pe_steps_per_s":
                m["workloads"].append(cell)
    layout.benchmark.write_text(json.dumps(bench))
    for cell in ("added.cell", "added.own"):
        out = run_tiny(layout, cell, seconds=1.5)
        assert out["correct"] is True and out["failed"] == 0
        assert out["attempted"] >= 2
        assert set(out["metrics"]) == {"pe_steps_per_s", "setup_s"}
        assert out["metrics"]["pe_steps_per_s"]["unit"] == "pe-steps/s"
        assert list(out)[-1] == "checks"
        assert out["device"]["count"] >= 1


def test_tenants_cell_reports_latency_and_runs_traced(tmp_path):
    layout = tiny_layout(tmp_path)
    out = run_tiny(layout, "t.tenants", seconds=2.0)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"request_p95_s", "request_p50_s",
                                   "setup_s"}
    assert (out["metrics"]["request_p95_s"]["value"]
            >= out["metrics"]["request_p50_s"]["value"] > 0)
    kept = tmp_path / "kept"
    traced = harness.run_cell("t.tenants", 7, 2.0, True, t_start=0.0,
                              layout=layout, require_tpu=False,
                              keep_trace=str(kept))
    assert traced["correct"] is True
    assert len(list(kept.glob("*.xplane.pb"))) == 1
    m = traced["metrics"]
    # the CPU trace has no TPU device plane: device metrics read nothing
    assert "device.idle_pct.latency" not in m
    assert m["sched.coalescing_ratio"]["value"] >= 1.0
    assert 0.0 <= m["cache.state_hit_pct"]["value"] <= 100.0
    assert m["engine.compiles"]["value"] == 0


def test_run_command_is_inside_the_benchmark_paths():
    cmd = BENCH["command"]
    assert cmd[0] == "python3"
    script = pathlib.PurePosixPath(cmd[1])
    assert any(str(script).startswith(p + "/") for p in BENCH["paths"])
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p


def test_knee_sweep_reads_the_backlog_of_one_rate(tmp_path):
    import time
    from benchmarks.chip import knee
    layout = tiny_layout(tmp_path)
    config = layout.read("configs", "tiny")
    mix = layout.read("traffic", "tiny_tenants")
    clock0 = [time.perf_counter()]
    harness.warm_up(config, mix, lambda: time.perf_counter() - clock0[0])
    row = knee.sweep_rate(config, mix, 3.0, 11, 1.5, clock0)
    assert row["rate"] == 3.0 and row["sessions"] >= 3
    assert row["requests"] >= row["sessions"]
    assert 0 < row["p50_s"] <= row["p95_s"]
    assert row["unanswered_at_close"] >= 0 and row["drain_s"] > -1.5
