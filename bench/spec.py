"""Resolve a benchmark cell by name: BENCHMARK.json -> configuration file,
traffic file, mix module and metric readers under bench/.

Everything one configuration, traffic mix or per-layer metric needs sits in
files of its own, found by the name BENCHMARK.json gives it:

    bench/configs/<config>.json     deployment sizes and guarantees
    bench/traffic/<traffic>.json    parameters of the open-loop mix; its
                                    "mix" key names the kind (default rank)
    bench/mixes/<kind>.py           the kind's operations, warm-up,
                                    read-back and reference (MIX_API)
    bench/metrics/<metric>.py       a reader: read(obs) -> float | None

Imports nothing of JAX or of the program, so tests and the load generator
can use it.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from types import ModuleType
from typing import Callable, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
MIX_DIR = BENCH_DIR / "mixes"
DEFAULT_MIX = "rank"
# what a mix module defines; all but warm and after_window are numpy-only.
# An item's retrieve, if it has one, is its first op (traffic.check_items).
MIX_API = ("schedule", "warm", "after_window", "compare", "live_rows")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def mix(self) -> str:
        return self.traffic.get("mix", DEFAULT_MIX)


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, bench: Optional[dict] = None) -> Cell:
    """The cell named `workload`, with its configuration, its traffic and
    the metrics it reports.  Raises KeyError for an unknown name."""
    bench = bench or load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    with open(ROOT / cfg_entry["file"]) as f:
        config = json.load(f)
    with open(BENCH_DIR / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    per_layer = [m for m in bench["per_layer"] if _applies(m, workload)]
    return Cell(workload, int(w["chips"]), config, traffic, e2e, per_layer)


def _load(path: pathlib.Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_mix(kind: str, search: pathlib.Path = MIX_DIR) -> ModuleType:
    """<search>/<kind>.py, the mix module that defines MIX_API.  Raises
    KeyError for an unknown kind."""
    path = search / f"{kind}.py"
    if not path.is_file():
        raise KeyError(f"unknown mix {kind!r}; known: "
                       f"{sorted(p.stem for p in search.glob('*.py'))}")
    mod = _load(path, f"bench_mix_{kind.replace('.', '_')}")
    missing = [f for f in MIX_API if not callable(getattr(mod, f, None))]
    if missing:
        raise TypeError(f"mix {kind!r} ({path}) does not define {missing}")
    return mod


def load_reader(metric: str) -> Callable:
    """bench/metrics/<metric>.py's `read(obs)`."""
    return _load(BENCH_DIR / "metrics" / f"{metric}.py",
                 f"bench_metric_{metric.replace('.', '_')}").read


def load_peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of `device_kind`; an unknown device is an error."""
    with open(BENCH_DIR / "peaks.json") as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(peaks)})")
    return peaks[device_kind]
