"""BENCHMARK.json keeps to its contract, and every name in it finds its
file: configurations, traffic mixes, operations and metric readers."""

import json
import re
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}


def test_top_level_keys_and_limits():
    assert set(SPEC) == TOP
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    cells = len(SPEC["workloads"])
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= cells <= 24


def test_entry_keys():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_units_and_text(kind):
    names = [e["name"] for e in SPEC[kind]]
    assert len(names) == len(set(names))
    for e in SPEC[kind]:
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200
                assert "\n" not in e[key] and "\t" not in e[key]
    for c in SPEC["configs"]:
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])


def test_every_cell_resolves_by_name():
    import harness
    for w in SPEC["workloads"]:
        cell = harness.Cell.load(w["name"])
        assert cell.cfg["name"] == w["config"]
        assert cell.cfg["op"] == cell.op.OP
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                         "ops_per_s"}
        assert cell.per_layer
        for m in cell.per_layer:
            reader = harness.load_module(BENCH / "metrics"
                                         / f"{m['name']}.py")
            assert callable(reader.read)


def test_configs_state_their_cuts():
    used = {w["config"] for w in SPEC["workloads"]}
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for c in SPEC["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        for k in c["reduced"]:
            assert k in cfg and k in cfg["source_values"]
            assert not k.endswith(("_dim", "_rank", "_bits", "_limbs"))


def test_per_layer_metrics_move_a_reported_metric():
    import harness
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    for w in SPEC["workloads"]:
        cell = harness.Cell.load(w["name"])
        reported = {m["name"] for m in cell.end_to_end}
        assert all(m["moves"] in reported for m in cell.per_layer)


def test_each_cell_reports_each_quantity_once():
    """A quantity split into metrics bounded for different cells
    (`latency_p95_ms.single` reads `latency_p95_ms`) reaches each cell
    under one name."""
    import harness
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["end_to_end"]:
        assert set(m.get("workloads", cells)) <= cells
    assert harness.quantity("latency_p95_ms.single") == "latency_p95_ms"
    assert harness.quantity("ops_per_s") == "ops_per_s"
    for w in SPEC["workloads"]:
        names = [m["name"] for m in harness.Cell.load(w["name"]).end_to_end]
        quantities = [harness.quantity(n) for n in names]
        assert len(quantities) == len(set(quantities)), names
        assert set(quantities) == {"ops_per_s", "latency_p50_ms",
                                   "latency_p95_ms", "setup_s"}
