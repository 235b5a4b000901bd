"""`BENCHMARK.json` and the files it names, found by name.

Names and units are checked here, so a file outside the contract fails
before anything touches a device."""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class SpecError(ValueError):
    """`BENCHMARK.json` or a file it names breaks the contract."""


def check_name(name: Any, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise SpecError(f"{what} {name!r}: a name is 1-64 of [A-Za-z0-9_.-], "
                        f"starting with a letter, digit or '_'")
    return name


def check_unit(unit: Any, what: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise SpecError(f"{what} unit {unit!r}: 1-16 of [A-Za-z0-9_/%.-]")
    return unit


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    kind: str                      # "end_to_end" | "per_layer"
    workloads: Optional[List[str]]
    moves: Optional[str] = None

    def applies_to(self, cell: str, reported: List[str]) -> bool:
        """Whether `cell` reports this metric; `reported` is the cell's
        end-to-end metrics (a per-layer metric without a `workloads` list
        goes wherever the metric it moves is reported)."""
        if self.workloads is not None:
            return cell in self.workloads
        if self.kind == "per_layer":
            return self.moves in reported
        return True


class Spec:
    """The benchmark rooted at `root` (the checkout: `BENCHMARK.json` and
    the directory `bench/` beside it)."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.bench = self.root / "bench"
        path = self.root / "BENCHMARK.json"
        try:
            self.raw = json.loads(path.read_text())
        except (OSError, json.JSONDecodeError) as e:
            raise SpecError(f"cannot read {path}: {e}") from e
        self.configs = {check_name(c["name"], "config"): c
                        for c in self.raw["configs"]}
        self.workloads = {}
        for w in self.raw["workloads"]:
            check_name(w["name"], "workload")
            check_name(w["config"], "workload config")
            check_name(w["traffic"], "workload traffic")
            if w["config"] not in self.configs:
                raise SpecError(f"workload {w['name']}: no config "
                                f"{w['config']!r}")
            self.workloads[w["name"]] = w
        self.metrics: Dict[str, Metric] = {}
        for kind in ("end_to_end", "per_layer"):
            for m in self.raw[kind]:
                name = check_name(m["name"], "metric")
                if name in self.metrics:
                    raise SpecError(f"metric {name} named twice")
                if m["better"] not in ("lower", "higher"):
                    raise SpecError(f"metric {name}: better={m['better']!r}")
                self.metrics[name] = Metric(
                    name=name, unit=check_unit(m["unit"], name),
                    better=m["better"], source=m["source"], kind=kind,
                    workloads=m.get("workloads"), moves=m.get("moves"))
        for c in self.configs.values():
            for k in c.get("reduced", []):
                check_name(k, f"config {c['name']} reduced key")

    # -- one cell --------------------------------------------------------------
    def cell(self, name: str) -> Dict[str, Any]:
        if name not in self.workloads:
            raise SpecError(f"no workload {name!r}; known: "
                            f"{sorted(self.workloads)}")
        return self.workloads[name]

    def config_file(self, name: str) -> Dict[str, Any]:
        return json.loads((self.root / self.configs[name]["file"])
                          .read_text())

    def traffic_file(self, name: str) -> Dict[str, Any]:
        return self._data("traffic", name)

    def cell_file(self, name: str) -> Dict[str, Any]:
        return self._data("cells", name)

    def _data(self, sub: str, name: str) -> Dict[str, Any]:
        path = self.bench / sub / f"{check_name(name, sub)}.json"
        try:
            return json.loads(path.read_text())
        except OSError as e:
            raise SpecError(f"no {sub} file for {name!r}: {e}") from e

    def metrics_for(self, cell: str, trace: bool) -> List[Metric]:
        """The metrics a run of `cell` prints: its end-to-end metrics with
        `--trace 0`, its per-layer metrics with `--trace 1`."""
        e2e = [m for m in self.metrics.values()
               if m.kind == "end_to_end" and m.applies_to(cell, [])]
        if not trace:
            return e2e
        names = [m.name for m in e2e]
        return [m for m in self.metrics.values()
                if m.kind == "per_layer" and m.applies_to(cell, names)]

    # -- code found by name ----------------------------------------------------
    def reader(self, metric: str) -> Callable[[Any], Optional[float]]:
        """`read(record)` of bench/metrics/<metric>.py."""
        return self._module("metrics", metric).read

    def reference(self, name: str):
        return self._module("references", name)

    def _module(self, sub: str, name: str):
        path = self.bench / sub / f"{check_name(name, sub)}.py"
        if not path.exists():
            raise SpecError(f"no {sub} module for {name!r} ({path})")
        spec = importlib.util.spec_from_file_location(
            f"bench_{sub}_{name.replace('.', '_').replace('-', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
