"""A tiny copy of the chip benchmark's data, for the tests on the CPU.

``tiny_layout(tmp)`` writes configs, mixes and cells that mirror the real
ones at a size the Pallas interpreter runs in seconds (rings of 64 PEs,
4 replicas per Δ, 32 + 32 steps), copies the real metric readers and the
plain reference, and writes a ``BENCHMARK.json`` whose cells are the tiny
ones.  Nothing here touches the real data files.

The open-loop sessions mix (``TENANTS_MIX``) and its metrics have no cell
in ``BENCHMARK.json`` yet: its rate has to come from a knee sweep on the
chip.  The tiny layout runs it, so that the open loop, its latency metrics
and the scheduler and state-cache readers stay covered.
"""
from __future__ import annotations

import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import harness  # noqa: E402

DATA = harness.HERE
TINY_REQUEST = {"replicas": 4, "burn_in": 32, "n_steps": 32}
#: cell name -> (config, mix) of the tiny layout
CELLS = {"t.study": ("tiny", "tiny_study"),
         "t.tenants": ("tiny", "tiny_tenants"),
         "t.ring": ("tiny_ring", "tiny_study")}
#: the multi-tenant sessions mix, at a rate that no knee sweep has set
TENANTS_MIX = {
    "loop": "open", "session_rate_per_s": 2.0, "tenants": 8, "zipf_s": 1.1,
    "request": {"replicas": 32, "burn_in": 1024, "n_steps": 512},
    "followups": [
        {"kind": "prefix", "p": 0.5, "delay_s": [0, 1], "blocks": [1, 4]},
        {"kind": "duplicate", "p": 0.5, "delay_s": [0, 1]},
        {"kind": "longer", "p": 0.5, "delay_s": [0, 2], "n_steps": 1024}]}


def _metric(name, unit, better, moves=None, layer=None, bound=None):
    m = {"name": name, "unit": unit, "better": better,
         "workloads": ["t.tenants"]}
    if moves is None:
        m.update(bound=bound, source="host_clock")
    else:
        m.update(source="device_trace" if layer == "device"
                 else "program_counter", layer=layer, moves=moves)
    return m


#: the sessions mix's end-to-end and per-layer metrics, for ``t.tenants``
TENANTS_E2E = [_metric("request_p95_s", "s", "lower", bound=0.25),
               _metric("request_p50_s", "s", "lower", bound=0.15)]
TENANTS_PER_LAYER = [
    _metric("device.idle_pct.latency", "%", "lower", "request_p95_s",
            "device"),
    _metric("sched.coalescing_ratio", "ratio", "higher", "request_p95_s",
            "scheduler"),
    _metric("cache.state_hit_pct", "%", "higher", "request_p95_s",
            "state cache"),
    _metric("engine.compiles", "count", "lower", "request_p95_s", "engine")]


#: the line of ``reference.py`` that applies the causality rule Eq. (1)
EQ1 = "eq1 = (~picks_left | (tau <= left)) & (~picks_right | (tau <= right))"
#: requests that do not set ``rd_mode`` go to the plain reference as it is
RD_DISPATCH = """

_rd_records = sweep_records


def sweep_records(spec, **kw):
    if spec["rd_mode"]:
        return _rd_records(spec, **kw)
    from benchmarks.chip import reference
    return reference.sweep_records(spec, **kw)
"""


def rd_reference_source() -> str:
    """A plain reference that implements ``sweep_fields {"rd_mode": ...}``.

    Random deposition drops the causality rule: ``reference.py`` with Eq. (1)
    replaced by "always", declaring ``rd_mode``.
    """
    src = (DATA / "reference.py").read_text()
    assert src.count(EQ1) == 1 and src.count("SWEEP_FIELDS = ()") == 1
    return (src.replace(EQ1, "eq1 = jnp.ones(tau.shape, bool)")
            .replace("SWEEP_FIELDS = ()", 'SWEEP_FIELDS = ("rd_mode",)')
            + RD_DISPATCH)


def add_cell(layout, cell: str, config: dict, modules=None) -> None:
    """Add ``cell``, serving ``tiny_study`` on a new ``config``, to a layout.

    ``modules`` maps module names to the source of further ``.py`` files
    (a plain reference) written beside the data files.
    """
    data = layout.data
    (data / "configs" / f"{config['name']}.json").write_text(
        json.dumps(config))
    for name, src in (modules or {}).items():
        (data / f"{name}.py").write_text(src)
    (data / "workloads" / f"{cell}.json").write_text(json.dumps(
        {"config": config["name"], "traffic": "tiny_study", "chips": 1,
         "check": {"requests": 3, "limit": 1e-3}}))
    bench = json.loads(layout.benchmark.read_text())
    bench["workloads"].append({"name": cell, "config": config["name"],
                               "traffic": "tiny_study", "chips": 1,
                               "why": "added by a test"})
    for m in bench["end_to_end"]:
        if m["name"] == "pe_steps_per_s":
            m["workloads"].append(cell)
    layout.benchmark.write_text(json.dumps(bench))


def _read(kind: str, name: str) -> dict:
    return json.loads((DATA / kind / f"{name}.json").read_text())


def tiny_layout(tmp: pathlib.Path, limit: float = 1e-3) -> harness.Layout:
    tmp = pathlib.Path(tmp)
    shutil.copytree(DATA / "metrics", tmp / "metrics")
    shutil.copy(DATA / "reference.py", tmp)
    for kind in ("configs", "traffic", "workloads"):
        (tmp / kind).mkdir()
    cfg = _read("configs", "dstudy_L10k_nv10")
    cfg.update(L=64, deltas=[1, 5, "inf"])
    ring = _read("configs", "ring4_L262k_nv10")
    ring.update(L=64, deltas=[1, 5, "inf"])
    study = _read("traffic", "study")
    study.update(request=TINY_REQUEST)
    tenants = json.loads(json.dumps(TENANTS_MIX))
    tenants.update(request=TINY_REQUEST)
    for f in tenants["followups"]:
        if f["kind"] == "longer":
            f["n_steps"] = 64
    for kind, name, obj in (("configs", "tiny", cfg),
                            ("configs", "tiny_ring", ring),
                            ("traffic", "tiny_study", study),
                            ("traffic", "tiny_tenants", tenants)):
        (tmp / kind / f"{name}.json").write_text(json.dumps(obj))
    for cell, (config, mix) in CELLS.items():
        chips = 4 if config == "tiny_ring" else 1
        (tmp / "workloads" / f"{cell}.json").write_text(json.dumps(
            {"config": config, "traffic": mix, "chips": chips,
             "check": {"requests": 3, "limit": limit}}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    rename = {"dstudy.study": "t.study", "dstudy.tenants": "t.tenants",
              "ring4.study": "t.ring"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [rename[w] for w in m["workloads"]]
    bench["end_to_end"] += TENANTS_E2E
    bench["per_layer"] += TENANTS_PER_LAYER
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return harness.Layout(data=tmp, benchmark=tmp / "BENCHMARK.json")


def run_tiny(layout, cell: str, seed: int = 2**31 + 12345,
             seconds: float = 2.0, trace: bool = False) -> dict:
    """One run of a tiny cell on the CPU, past the harness's TPU check."""
    return harness.run_cell(cell, seed, seconds, trace,
                            t_start=time.perf_counter(), layout=layout,
                            require_tpu=False)
