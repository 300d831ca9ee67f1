"""Find a cell's files by the names `BENCHMARK.json` gives them."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Callable, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class CellError(Exception):
    """The manifest or one of a cell's files is missing or malformed."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise CellError(f"cannot read {path}: {e}") from e


def load_module(path: str):
    """The Python file at `path` as a module (file names may hold
    dots, so they are loaded by path and not by import name)."""
    if not os.path.isfile(path):
        raise CellError(f"no file {path}")
    name = "perf_dyn_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    # a dataclass looks its module up by name while it is defined
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def load_function(path: str, attr: str) -> Callable:
    """The function `attr` of the Python file at `path`."""
    fn = getattr(load_module(path), attr, None)
    if not callable(fn):
        raise CellError(f"{path} defines no function {attr}()")
    return fn


def reader_path(root: str, kind: str, metric: str) -> str:
    """The file that reads `metric`: `perf/<kind>/<metric>.py`. A
    quantity that is split by the end-to-end metric it moves
    (`running_mean.batch`, `running_mean.chat`) has one reader,
    `perf/<kind>/running_mean.py`, unless a split brings its own."""
    own = os.path.join(root, "perf", kind, metric + ".py")
    if os.path.isfile(own) or "." not in metric:
        return own
    return os.path.join(root, "perf", kind,
                        metric.rsplit(".", 1)[0] + ".py")


@dataclasses.dataclass
class Cell:
    """One entry of `workloads` with everything it names, loaded."""
    name: str
    chips: int
    config_name: str
    config: dict            # perf/configs/<config>.json
    traffic_name: str
    traffic: dict           # perf/traffic/<traffic>.json
    generator: Callable     # perf/generators/<generator>.py::block
    end_to_end: list        # manifest metric entries reported here
    per_layer: list
    root: str


def _reported(metrics: list, workload: str) -> list:
    return [m for m in metrics
            if "workloads" not in m or workload in m["workloads"]]


def load_cell(workload: str, root: str = ROOT,
              manifest: Optional[str] = None) -> Cell:
    """`manifest` is a path relative to `root`; the rehearsal keeps a
    manifest of its own so that its tiny cell is in no `BENCHMARK.json`."""
    bench = _load_json(os.path.join(root, manifest or "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise CellError(f"no workload {workload!r} in the manifest; it "
                        f"has {[w['name'] for w in bench['workloads']]}")
    cfg_entry = next((c for c in bench["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise CellError(f"workload {workload!r} names no listed config")
    traffic = _load_json(os.path.join(
        root, "perf", "traffic", entry["traffic"] + ".json"))
    generator = load_function(os.path.join(
        root, "perf", "generators", traffic["generator"] + ".py"), "block")
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config_name=entry["config"],
        config=_load_json(os.path.join(root, cfg_entry["file"])),
        traffic_name=entry["traffic"], traffic=traffic,
        generator=generator,
        end_to_end=_reported(bench["end_to_end"], workload),
        per_layer=_reported(bench["per_layer"], workload), root=root)


def load_peaks(device_kind: str, root: str = ROOT) -> dict:
    """The published peaks of one chip of `device_kind`. A device that
    is not in the table is an error, never a default."""
    table = _load_json(os.path.join(root, "perf", "peaks.json"))
    if device_kind not in table["devices"]:
        raise CellError(f"perf/peaks.json has no device {device_kind!r}; "
                        f"it has {sorted(table['devices'])}")
    return table["devices"][device_kind]
