"""The benchmark's description: BENCHMARK.json and the files it names.

A cell (``workloads`` entry) names a configuration and a traffic mix. The
configuration's file holds the model's published sizes (Hugging Face key
names), the architecture that reads them (``"arch"``, ``archs/<arch>.py``;
``"dense"`` when absent), the serving deployment and the correctness
limit; the traffic mix is ``traffic/<name>.json``; each metric is
``metrics/<name>.py``. Nothing here imports JAX or the program: an
architecture's module, which does, is loaded when a cell first asks for
its model.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
import sys
from dataclasses import dataclass
from types import ModuleType
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
ARCH_DIR = os.path.join(BENCH_DIR, "archs")


def load_json(path: str) -> Any:
    """Read one JSON file."""
    with open(path) as f:
        return json.load(f)


def load_file(path: str, name: str) -> ModuleType:
    """Import the Python file at ``path`` as a module called ``name``
    (entered in ``sys.modules``, as its dataclasses need)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@functools.cache
def load_arch(name: str, arch_dir: str = ARCH_DIR) -> ModuleType:
    """The architecture module ``<arch_dir>/<name>.py``, imported once
    per process (its jitted functions and ``Spec`` class stay the same
    objects for every cell that names it)."""
    return load_file(os.path.join(arch_dir, f"{name}.py"),
                     f"bench_arch_{name}")


@dataclass(frozen=True)
class Cell:
    """One workload of BENCHMARK.json with everything it names, loaded;
    its architecture module and model come from ``arch_dir``."""
    name: str
    chips: int
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    arch_dir: str = ARCH_DIR

    @property
    def arch(self) -> ModuleType:
        """The architecture module the configuration names."""
        return load_arch(self.config.get("arch", "dense"), self.arch_dir)

    @functools.cached_property
    def model(self):
        """The architecture's ``Spec`` of the configuration."""
        return self.arch.spec(self.config)

    @property
    def serving(self) -> Dict[str, Any]:
        """ServingConfig keyword arguments of the deployment."""
        return self.config["deployment"]["serving"]

    def metrics(self, trace: bool) -> List[Dict[str, Any]]:
        """The metrics this cell reports in a run with or without trace."""
        entries = self.per_layer if trace else self.end_to_end
        return [m for m in entries
                if "workloads" not in m or self.name in m["workloads"]]


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    """BENCHMARK.json at the root of the checkout."""
    return load_json(os.path.join(root, "BENCHMARK.json"))


def load_cell(name: str, root: str = ROOT,
              bench: Optional[Dict[str, Any]] = None) -> Cell:
    """Load a workload by name, with its configuration and traffic."""
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, confs[w["config"]]["file"]))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                     f"{w['traffic']}.json"))
    return Cell(name=name, chips=w["chips"], config=config,
                traffic_name=w["traffic"], traffic=traffic,
                end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])
