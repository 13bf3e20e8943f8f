"""BENCHMARK.json and the files it names: names, units, and that every
cell's configuration, mix and metrics resolve by name."""
import importlib.util
import json
import re

import pytest

import chipbench_testing  # noqa: F401  (sys.path)
from chipbench import spec

ROOT = spec.REPO_ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
TEXT_RE = re.compile(r"^[^\t\n]{1,200}$")


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for word in BENCH["command"]:
        assert TEXT_RE.match(word) and not word.startswith("/")
        assert ".." not in word.split("/")
    assert (ROOT / BENCH["command"][1]).is_file()
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_are_unique_and_allowed(kind):
    names = [e["name"] for e in BENCH[kind]]
    assert len(set(names)) == len(names)
    for n in names:
        spec.check_name(n, kind)


def test_entry_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/chip/")
        for key in c["reduced"]:
            spec.check_name(key, "reduced key")
        assert TEXT_RE.match(c["source"]) and TEXT_RE.match(c["why"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and TEXT_RE.match(w["why"])
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert TEXT_RE.match(m["layer"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        spec.check_unit(m["unit"], m["name"])
        assert m["better"] in ("lower", "higher")


def test_setup_metric_and_every_cell_reports_enough():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        names = {m.name for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m.moves in names, (m.name, m.moves)


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_by_name(workload):
    cell = spec.load_cell(workload)
    assert cell.config["name"] == cell.config_name
    assert cell.traffic["name"] == cell.traffic_name
    cfg = cell.config
    assert cfg["dim"] % cfg["graph"]["M"] == 0
    assert cfg["graph"]["R_slack"] == int(cfg["graph"]["R"]
                                          * cfg["graph"]["slack"])
    entry = next(c for c in BENCH["configs"] if c["name"] == cell.config_name)
    assert set(entry["reduced"]) == set(cfg["reduced"])
    for m in cell.per_layer:
        assert callable(spec.metric_reader(m.name))
    # the check's limits are the cell's own file, found by the cell's name
    assert 0 < cell.limits["recall_miss"] < 1


def test_every_metric_file_is_named_in_the_benchmark():
    names = {m["name"] for m in BENCH["per_layer"]}
    files = {p.name[:-3] for p in (ROOT / "benchmarks/chip/metrics").glob("*.py")}
    assert files == names
    for p in (ROOT / "benchmarks/chip/metrics").glob("*.py"):
        s = importlib.util.spec_from_file_location("m", p)
        mod = importlib.util.module_from_spec(s)
        s.loader.exec_module(mod)
        assert mod.__doc__ and callable(mod.read)


def test_unknown_names_are_errors():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no.such.cell")
    with pytest.raises(spec.SpecError):
        spec.check_name("has space", "x")
    with pytest.raises(spec.SpecError):
        spec.check_unit("tokens per second", "x")
    with pytest.raises(spec.SpecError):
        spec.load_peaks("cpu")
    assert spec.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_run_refuses_without_a_tpu(capsys):
    path = ROOT / "benchmarks/chip/run.py"
    s = importlib.util.spec_from_file_location("chipbench_run", path)
    run = importlib.util.module_from_spec(s)
    s.loader.exec_module(run)
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "wiki768.search", "--seed", "1",
                  "--seconds", "1"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""
