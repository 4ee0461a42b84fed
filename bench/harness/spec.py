"""The benchmark's description: BENCHMARK.json and the files it names.

A cell (``workloads`` entry) names a configuration and a traffic mix. The
configuration's file holds the model's published sizes (Hugging Face key
names), the serving deployment and the correctness limit; the traffic
mix is ``traffic/<name>.json``; each metric is ``metrics/<name>.py``.
Nothing here imports JAX or the program.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> Any:
    """Read one JSON file."""
    with open(path) as f:
        return json.load(f)


@dataclass(frozen=True)
class ModelSpec:
    """The model dimensions the harness, the counts and the reference use."""
    name: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm: str            # "layernorm" (no scale or bias) | "rmsnorm"
    norm_eps: float
    rope_theta: float
    tied: bool
    dtype: str           # the type the weights are served in

    @classmethod
    def from_config(cls, cfg: Dict[str, Any]) -> "ModelSpec":
        """Read the sizes from a configuration file's Hugging Face keys."""
        heads = cfg["num_attention_heads"]
        return cls(
            name=cfg["name"],
            layers=cfg["num_hidden_layers"],
            d_model=cfg["hidden_size"],
            heads=heads,
            kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
            d_ff=cfg["intermediate_size"],
            vocab=cfg["vocab_size"],
            norm=cfg["norm"],
            norm_eps=cfg.get("rms_norm_eps", cfg.get("norm_eps", 1e-5)),
            rope_theta=float(cfg["rope_theta"]),
            tied=bool(cfg["tie_word_embeddings"]),
            dtype=cfg["serve_dtype"],
        )


@dataclass(frozen=True)
class Cell:
    """One workload of BENCHMARK.json with everything it names, loaded."""
    name: str
    chips: int
    config: Dict[str, Any]
    model: ModelSpec
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

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
                model=ModelSpec.from_config(config),
                traffic_name=w["traffic"], traffic=traffic,
                end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])
