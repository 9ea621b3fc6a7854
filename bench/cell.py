"""Resolve a cell of ``BENCHMARK.json`` into the files it names."""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class UnknownWorkload(KeyError):
    """``--workload`` names no cell of ``BENCHMARK.json``."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]


def _json(path: Path) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _for_cell(metrics: List[dict], cell: str) -> List[dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def find_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise UnknownWorkload(
            f"unknown workload {name!r}; BENCHMARK.json has "
            f"{sorted(by_name)}")
    w = by_name[name]
    here = root / "bench"
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_json(here / "configs" / f"{w['config']}.json"),
        traffic=_json(here / "traffic" / f"{w['traffic']}.json"),
        limits=_json(here / "limits" / f"{name}.json")["limits"],
        end_to_end=_for_cell(bench["end_to_end"], name),
        per_layer=_for_cell(bench["per_layer"], name))


def load_peaks(device_kind: str, root: Path = ROOT) -> dict:
    """The chip's peaks; a kind missing from the table is an error."""
    table = _json(root / "bench" / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"bench/peaks.json (has {sorted(table)})")
    return table[device_kind]


def routine(name: str):
    """``bench/routines/<name>.py``: flop count and float64 reference."""
    return importlib.import_module(f"bench.routines.{name}")


def _load(path: Path, what: str):
    """A module loaded by its path (names hold dots, so it is not
    imported by module name)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + what + "_" + path.stem.replace(".", "_"), path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no {what} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str,
                  root: Path = ROOT) -> Callable[..., Optional[float]]:
    """``read`` of ``bench/metrics/<name>.py``."""
    return _load(root / "bench" / "metrics" / f"{name}.py", "metric").read


def loop_class(cell: Cell, root: Path = ROOT):
    """``Loop`` of ``bench/loops/<loop>.py``, the loop the cell's mix
    names."""
    name = cell.traffic["loop"]
    return _load(root / "bench" / "loops" / f"{name}.py", "loop").Loop
