"""Everything the harness finds by name: the cell in ``BENCHMARK.json``,
its configuration file, its traffic mix, the per-layer metric readers and
the table of chip peaks.

A cell names one configuration (``configs/<config>.json``) and one traffic
mix (``traffic/<traffic>.json``), and has the limits of its check in
``limits/<cell>.json``; a per-layer metric ``<name>`` is read by
``metrics/<name>.py``. Adding a cell adds files; no code here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from typing import Any, Callable, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
REPO_ROOT = BENCH_DIR.parents[1]

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class SpecError(ValueError):
    """BENCHMARK.json, a configuration, a mix or a metric file is malformed
    or missing."""


def check_name(name: str, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise SpecError(f"{what} {name!r}: not a valid name")
    return name


def check_unit(unit: str, what: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise SpecError(f"{what}: unit {unit!r} not allowed")
    return unit


def _load_json(path: Path) -> Any:
    if not path.is_file():
        raise SpecError(f"{path.relative_to(REPO_ROOT)} not found")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    workloads: Optional[tuple[str, ...]]  # None → every cell
    moves: str = ""  # the end-to-end metric a per-layer metric should move

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict  # the check's limits set from this cell's readings
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


def _metric(entry: dict, kind: str) -> Metric:
    name = check_name(entry["name"], f"{kind} metric")
    wl = entry.get("workloads")
    return Metric(name=name, unit=check_unit(entry["unit"], name),
                  workloads=tuple(wl) if wl is not None else None,
                  moves=entry.get("moves", ""))


def _named(kind: str, name: str) -> dict:
    check_name(name, kind)
    return _load_json(BENCH_DIR / kind / f"{name}.json")


def load_cell(workload: str) -> Cell:
    bench = _load_json(REPO_ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"{workload}: config {w['config']!r} not listed")
    config = _named("configs", w["config"])
    e2e = tuple(m for m in (_metric(e, "end_to_end")
                            for e in bench["end_to_end"])
                if m.applies_to(workload))
    per_layer = tuple(m for m in (_metric(e, "per_layer")
                                  for e in bench["per_layer"])
                      if m.applies_to(workload))
    return Cell(
        name=workload, config_name=w["config"], traffic_name=w["traffic"],
        chips=int(w["chips"]), config=config,
        traffic=_named("traffic", w["traffic"]),
        limits=_named("limits", workload), end_to_end=e2e,
        per_layer=per_layer,
    )


def metric_reader(name: str) -> Callable:
    """``read(run)`` from ``metrics/<name>.py``: returns the metric's value,
    or None when the run holds nothing for it to read."""
    check_name(name, "per-layer metric")
    path = BENCH_DIR / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path.relative_to(REPO_ROOT)}")
    mod_name = "chipbench_metric_" + re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(device_kind: str) -> dict:
    """The row of ``peaks.json`` for ``device_kind``; an unknown kind is an
    error, never a default."""
    table = _load_json(BENCH_DIR / "peaks.json")
    for row in table["devices"]:
        if row["device_kind"] == device_kind:
            return row
    raise SpecError(f"device_kind {device_kind!r} not in peaks.json "
                    f"(have {[r['device_kind'] for r in table['devices']]})")
