"""BENCHMARK.json and the data files it names, read into one ``Cell``."""

from __future__ import annotations

import dataclasses
import json
import os
import tomllib

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with its configuration and traffic."""

    name: str
    chips: int
    config: dict  # configs/<config>.toml
    traffic: dict  # traffic/<traffic>.toml
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list

    @property
    def peers(self) -> int:
        return int(self.traffic["peers"])


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    """The cell ``name`` of BENCHMARK.json; raises KeyError if absent."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(ROOT, configs[w["config"]]["file"]), "rb") as f:
        config = tomllib.load(f)
    with open(os.path.join(HERE, "traffic", w["traffic"] + ".toml"), "rb") as f:
        traffic = tomllib.load(f)
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
    )


def load_peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind`` from peaks.toml; an unlisted
    kind is an error, never a default."""
    with open(os.path.join(HERE, "peaks.toml"), "rb") as f:
        peaks = tomllib.load(f)
    if device_kind not in peaks:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       "in benchmark/peaks.toml")
    return peaks[device_kind]
