"""The benchmark's description, found by name: ``BENCHMARK.json`` at the
root of the checkout, and under ``bench/`` one file per configuration
(``configs/``), traffic mix (``traffic/``), metric reader (``metrics/``),
model family (``families/``) and cell's correctness limits (``limits/``).
A new cell, mix, configuration or metric is new files and entries; no
code here changes."""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# JAX's persistent compilation cache, at a fixed path inside the checkout
# (the path is part of the cache key), apart from any other cache there
CACHE_DIR = ".bench_jax_cache"


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Metric:
    name: str
    unit: str
    entry: dict
    read: object = field(repr=False)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file as run
    traffic_name: str
    traffic: dict
    family: object = field(repr=False)
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)
    limits: Dict[str, float] = field(default_factory=dict)

    @property
    def published(self) -> dict:
        """The model's keys, as run, that the family reads."""
        return self.config["config"]


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: str = ROOT, bench: str = BENCH) -> Cell:
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    conf_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = _json(os.path.join(root, conf_entry["file"]))
    traffic = _json(os.path.join(bench, "traffic", w["traffic"] + ".json"))
    family = load_module(
        os.path.join(bench, "families", config["family"] + ".py"),
        "bench_family_" + config["family"])

    def metrics(kind: str) -> List[Metric]:
        out = []
        for i, m in enumerate(spec[kind]):
            if _applies(m, name):
                mod = load_module(os.path.join(bench, "metrics",
                                               m["name"] + ".py"),
                                  f"bench_metric_{kind}_{i}")
                out.append(Metric(m["name"], m["unit"], m, mod.read))
        return out

    limits = _json(os.path.join(bench, "limits", name + ".json"))
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                family=family, end_to_end=metrics("end_to_end"),
                per_layer=metrics("per_layer"),
                limits={k: float(v["limit"])
                        for k, v in limits["numbers"].items()})


def derive_seeds(seed: int) -> Dict[str, int]:
    """Independent 31-bit seeds for each use, from any whole ``--seed``
    (JAX keys silently truncate larger Python ints)."""
    words = np.random.SeedSequence(int(seed) % 2**64).generate_state(5, np.uint32)
    names = ("weights", "fl", "data", "partition", "pretrain")
    return {k: int(w >> 1) for k, w in zip(names, words)}


def peaks(device_kind: str, bench: str = BENCH) -> dict:
    table = _json(os.path.join(bench, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"device {device_kind!r} is not in bench/peaks.json")
    return table[device_kind]
